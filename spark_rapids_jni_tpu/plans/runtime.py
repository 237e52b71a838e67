"""Governed plan execution: the memory bracket at PLAN granularity.

The per-op runners bracketed every launch separately — admission, retry,
split and flight-recorder task per op.  A compiled plan is one program,
so the protocol moves up a level: ONE admission covers the whole fused
pipeline's working set, ONE retry/split boundary re-executes the whole
fused program (on RetryOOM the same batch re-runs; on SplitAndRetryOOM
every scan table halves and the fused program runs per half, partials
combining by addition), and ONE flight-recorder task brackets the plan
(docs/OBSERVABILITY.md).  This is exactly the reference protocol
(RmmSpark.java:402-416) applied to a Flare-style fused pipeline instead
of a physical op.

Padding discipline: scan tables are padded to the dp-aligned
pow2-quantized length (``parallel.shuffle.quantized_rows`` — the bucket
lattice the plan cache keys on) with an appended row-valid array, False
on pad rows, that the compiler ANDs into the pipeline mask — more
padding never changes results, and a long-lived executor holds
O(log rows) compiled variants per plan, not one per distinct length.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from spark_rapids_jni_tpu.obs import flight as _flight
from spark_rapids_jni_tpu.obs import trace as _trace
from spark_rapids_jni_tpu.plans import ir
from spark_rapids_jni_tpu.plans.cache import plan_cache
from spark_rapids_jni_tpu.plans.compiler import (
    AGG_KEPT,
    AGG_ROWS,
    VALID_FIELD,
    agg_path,
    cached_compile,
    gather_fields,
)

__all__ = ["pad_tables", "plan_working_set_bytes", "execute_plan",
           "run_governed_plan", "split_scan_tables", "combine_outputs",
           "input_signature_raw", "compiled_plan_for",
           "plan_retry_stats", "suggested_presplit_depth",
           "reset_plan_retry_stats"]

Tables = Dict[str, Dict[str, np.ndarray]]


# --------------------------------------------------------------------------
# per-plan retry statistics (adaptive admission, round 9)
#
# Every governed plan execution records its retry/split history per PLAN
# NAME — the request-class granularity the admission controller steers on.
# ``suggested_presplit_depth`` turns that history into a pre-emptive split
# depth: a plan whose recent runs SplitAndRetried starts its next run
# already split, skipping the doomed full-size attempt (and its blocked
# windows).  The hint DECAYS — one depth level per ``_PRESPLIT_DECAY_S``
# without a new split — so a transient pressure episode doesn't pin small
# pieces forever.  Gated on the serve_adaptive flag (and the controller
# kill switch), so static configurations are bit-identical to round 8.
# --------------------------------------------------------------------------

_PRESPLIT_DECAY_S = 30.0
_STATS_LOCK = threading.Lock()
_PLAN_STATS: Dict[str, dict] = {}


def _stats_entry(name: str) -> dict:
    st = _PLAN_STATS.get(name)
    if st is None:
        st = _PLAN_STATS[name] = {
            "runs": 0, "retries": 0, "split_retries": 0,
            "presplit_depth": 0, "last_split_t": 0.0,
        }
    return st


def _record_plan_retry(name: str) -> None:
    with _STATS_LOCK:
        _stats_entry(name)["retries"] += 1


def _note_plan_run(name: str, presplit: int, reactive_splits: int,
                   max_depth: int) -> None:
    """Record one completed run: the observed total depth (pre-splits plus
    the depth implied by REACTIVE split events — pre-split invocations of
    the split callback are excluded, or the hint could never decay)
    becomes the new hint when it exceeds the decayed current one."""
    observed = presplit
    if reactive_splits > 0:
        observed += max(1, (reactive_splits + 1).bit_length() - 1)
    now = time.monotonic()
    with _STATS_LOCK:
        st = _stats_entry(name)
        st["runs"] += 1
        if reactive_splits > 0:
            st["split_retries"] += reactive_splits
            st["last_split_t"] = now
        # collapse the stored hint to its decayed value first, so a long-
        # faded episode doesn't resurrect at full depth on the next split
        st["presplit_depth"] = min(
            max(observed, _decayed_depth(st, now)), max_depth)


def _decayed_depth(st: dict, now: float) -> int:
    if st["presplit_depth"] <= 0 or st["last_split_t"] <= 0.0:
        return 0
    faded = int((now - st["last_split_t"]) / _PRESPLIT_DECAY_S)
    return max(0, st["presplit_depth"] - faded)


def plan_retry_stats() -> Dict[str, dict]:
    """Per-plan retry/split history (non-destructive copy), with the
    decayed ``suggested_depth`` the next run would start at."""
    now = time.monotonic()
    with _STATS_LOCK:
        return {name: dict(st, suggested_depth=_decayed_depth(st, now))
                for name, st in _PLAN_STATS.items()}


def suggested_presplit_depth(name: str, max_depth: int = 8) -> int:
    """Pre-emptive split depth for the next run of plan ``name`` (0 =
    attempt full size).  Returns 0 unless adaptive admission is enabled
    AND the kill switch is clear — the static path must stay untouched."""
    from spark_rapids_jni_tpu import config

    if not config.get("serve_adaptive") or config.get(
            "serve_controller_freeze"):
        return 0
    now = time.monotonic()
    with _STATS_LOCK:
        st = _PLAN_STATS.get(name)
        if st is None:
            return 0
        return min(_decayed_depth(st, now), max_depth)


def reset_plan_retry_stats() -> None:
    with _STATS_LOCK:
        _PLAN_STATS.clear()


_flight.register_telemetry_source("plan_retry", plan_retry_stats)


def _quantized(n: int, dp: int) -> int:
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    return quantized_rows(n, dp)


def pad_tables(plan: ir.Plan, tables: Tables, dp: int) -> Tables:
    """Pad every scan table onto the pow2 bucket lattice (dp-aligned) and
    append its row-valid array; dims pass through contiguous."""
    import jax

    scans = {s.table for s in ir.scan_tables(plan)}
    out: Tables = {}
    for table, fields in tables.items():
        if table not in scans:
            # already-uploaded device dims (run_governed_plan's one-time
            # hoist) pass through untouched; device_put on them later is
            # a no-op, so split pieces never re-pay the transfer
            out[table] = {k: v if isinstance(v, jax.Array)
                          else np.ascontiguousarray(v)
                          for k, v in fields.items()}
            continue
        n = len(next(iter(fields.values())))
        m = _quantized(n, dp)
        padded = {}
        for k, v in fields.items():
            if len(v) != n:
                raise ValueError(
                    f"ragged scan table {table!r}: field {k!r} has "
                    f"{len(v)} rows, expected {n}")
            if m == n:
                padded[k] = np.ascontiguousarray(v)
            else:
                padded[k] = np.concatenate(
                    [v, np.zeros(m - n, dtype=v.dtype)])
        valid = np.zeros(m, bool)
        valid[:n] = True
        padded[VALID_FIELD] = valid
        out[table] = padded
    return out


def input_signature_raw(plan: ir.Plan, tables: Tables, dp: int):
    """The padded-input signature of RAW (unpadded) ``tables`` — exactly
    what :func:`compiler.input_signature` returns for
    ``pad_tables(plan, tables, dp)``, computed from lengths and dtypes
    alone, with ZERO data movement.  This is how a caller that only
    wants the cached compiled step (make_distributed_q3/q5) looks it up
    without re-padding the whole dataset per call."""
    from spark_rapids_jni_tpu.plans.compiler import _arg_layout

    scans = {s.table for s in ir.scan_tables(plan)}
    sig = []
    for kind, table, field in _arg_layout(plan):
        if field == VALID_FIELD:
            n = len(next(iter(tables[table].values())))
            sig.append((kind, table, field, "bool", _quantized(n, dp)))
            continue
        a = tables[table][field]
        m = _quantized(len(a), dp) if table in scans else len(a)
        sig.append((kind, table, field, str(a.dtype), m))
    return tuple(sig)


def compiled_plan_for(plan: ir.Plan, mesh, tables: Tables):
    """The cached compiled step for (plan, mesh, ``tables``' geometry) —
    compile on miss, O(1) host work on hit (signature from lengths and
    dtypes, no padding copies)."""
    from spark_rapids_jni_tpu.plans.cache import plan_cache
    from spark_rapids_jni_tpu.plans.compiler import compile_plan

    if mesh is None:
        dp = 1
    else:
        from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

        dp = mesh.shape[DATA_AXIS]
    sig = input_signature_raw(plan, tables, dp)
    return plan_cache.get_or_compile(
        (plan, mesh, sig), lambda: compile_plan(plan, mesh, sig))


def plan_working_set_bytes(plan: ir.Plan, tables: Tables, dp: int) -> int:
    """Admission estimate for one fused execution: quantized input bytes
    x3 (inputs + masks/buckets + partials headroom — the same margin the
    per-op runners reserved), plus exchange send/recv buffers for plans
    with a shuffle."""
    scans = {s.table for s in ir.scan_tables(plan)}
    total = 0
    for table, fields in tables.items():
        if table not in scans:
            continue
        for v in fields.values():
            total += _quantized(len(v), dp) * v.itemsize
    total *= 3
    for node in ir.exchange_nodes(plan):
        slots = dp * dp * node.capacity
        total += 2 * slots * (8 * len(node.fields) + 10)
    return total


def execute_plan(mesh, plan: ir.Plan, tables: Tables) -> Dict[str, np.ndarray]:
    """ONE fused launch: pad, compile (cached), upload, run, download —
    each but the compile lookup a child span of the thread's current
    trace context (``plan_pad``, ``plan_upload``, ``plan_run``,
    ``plan_download``; no-ops without one).  A plan with SegmentAgg sinks
    also records one ``segment_agg`` flight event: the path its sums took,
    the rows the aggregation ran over and the rows their masks kept.  A
    plan with GatherJoins records one ``gather_join`` event: the
    fact-length columns its joins gather and how many of them are
    expressions evaluated on the dimension table.

    Raises :class:`mem.governed.ShuffleCapacityExceeded` when an
    Exchange overflowed (``dropped > 0``) — the caller grows the
    capacity and re-runs, like any shuffle-spill retry.  No governance
    here: callers bracket this (run_governed_plan, or the model runners'
    own drivers).
    """
    import jax

    from spark_rapids_jni_tpu.mem.governed import ShuffleCapacityExceeded
    from spark_rapids_jni_tpu.obs.seam import COLLECTIVE, TRANSFER, seam

    if mesh is None:
        dp = 1
        shardings = None
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

        dp = mesh.shape[DATA_AXIS]
        shardings = (NamedSharding(mesh, P(DATA_AXIS)),
                     NamedSharding(mesh, P()))
    with _trace.maybe_span(_trace.SPAN_PLAN_PAD):
        padded = pad_tables(plan, tables, dp)
    compiled = cached_compile(plan, mesh, padded)
    sig = ir.plan_signature(plan)
    scans = {s.table for s in ir.scan_tables(plan)}
    # device_put is asynchronous: the seam range and the span time the
    # enqueue and the host staging copy; a transfer still in flight when
    # the launch waits shows as device idle inside plan_run
    with seam(TRANSFER, f"plan_upload:{plan.name}"), _trace.maybe_span(
            _trace.SPAN_PLAN_UPLOAD):
        flat = []
        for _kind, table, field in _layout_of(compiled):
            arr = padded[table][field]
            if shardings is None:
                flat.append(jax.device_put(arr))
            else:
                flat.append(jax.device_put(
                    arr, shardings[0] if table in scans else shardings[1]))
    with _trace.maybe_span(_trace.SPAN_PLAN_RUN):
        t0 = time.perf_counter()
        with seam(COLLECTIVE, f"launch:plan:{sig}"):
            out = compiled.fn(*flat)
            jax.block_until_ready(out)
        plan_cache.record_execute(time.perf_counter() - t0)
    with _trace.maybe_span(_trace.SPAN_PLAN_DOWNLOAD):
        outputs = {name: np.asarray(v)
                   for name, v in zip(compiled.out_names, out)}
        kept = outputs.pop(AGG_KEPT, None)
        if kept is not None:
            _flight.record(
                _flight.EV_SEGMENT_AGG,
                detail=f"plan:{plan.name}:path:{agg_path(plan)}:scattered:"
                       f"{int(outputs.pop(AGG_ROWS))}:kept:{int(kept)}",
                value=int(kept))
        gathers, dim_side = gather_fields(plan)
        if gathers:
            _flight.record(
                _flight.EV_GATHER_JOIN,
                detail=f"plan:{plan.name}:gathers:{gathers}:"
                       f"dim_side:{dim_side}",
                value=gathers)
        if int(outputs.get("dropped", 0)) > 0:
            raise ShuffleCapacityExceeded(
                f"{int(outputs['dropped'])} rows overflowed the plan's "
                f"exchange capacity")
    return outputs


def _layout_of(compiled):
    for name in compiled.arg_names:
        table, field = name.split(".", 1)
        yield None, table, field


def _upload_dims(plan: ir.Plan, tables: Tables, mesh) -> Tables:
    """Hoist the replicated dim-table uploads to ONCE per governed
    bracket: the device arrays pass through pad_tables untouched and the
    per-piece device_put in execute_plan sees correctly-placed inputs (a
    no-op), so retry/split pieces never re-pay the transfer — the per-op
    q3 runner's deliberate hoist, kept at plan granularity."""
    import jax

    dims = ir.dim_tables(plan)
    if not dims:
        return tables
    rep = None
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        rep = NamedSharding(mesh, P())
    out = dict(tables)
    for d in dims:
        out[d.table] = {
            # analyze: ignore[governed-allocation] - small replicated dim
            # tables uploaded ONCE per governed bracket and shared by
            # every retry/split piece; uploading inside the bracket would
            # re-pay the transfer up to 2^max_split_depth times.  Their
            # bytes ride the working-set margin.
            k: jax.device_put(np.ascontiguousarray(v), rep)
            for k, v in tables[d.table].items()}
    return out


def split_scan_tables(tables: Tables, scans) -> List[Tables]:
    """Halve every scan table's rows (dims replicated into both halves).
    Exact for plans whose sinks are additive aggregates — every fused
    NDS plan here."""
    halves: List[Tables] = [{}, {}]
    scan_names = {s.table for s in scans}
    for table, fields in tables.items():
        if table not in scan_names:
            halves[0][table] = fields
            halves[1][table] = fields
            continue
        n = len(next(iter(fields.values())))
        halves[0][table] = {k: v[: n // 2] for k, v in fields.items()}
        halves[1][table] = {k: v[n // 2:] for k, v in fields.items()}
    return halves


def combine_outputs(results: Sequence[Dict[str, np.ndarray]]) -> Dict:
    """Element-wise sum of output dicts (additive partials)."""
    out = dict(results[0])
    for r in results[1:]:
        for k, v in r.items():
            out[k] = out[k] + v
    return out


def run_governed_plan(
    mesh,
    plan: ir.Plan,
    tables: Tables,
    *,
    budget=None,
    task_id: int = 0,
    manage_task: bool = True,
    nbytes_of: Optional[Callable[[Tables], int]] = None,
    split: Optional[Callable[[Tables], Sequence[Tables]]] = None,
    combine: Optional[Callable[[List[Any]], Any]] = None,
    max_split_depth: int = 8,
) -> Dict[str, np.ndarray]:
    """Execute ``plan`` under ONE governed bracket.

    The whole fused pipeline is admitted as one working set; RetryOOM
    re-runs the fused program on the same batch, SplitAndRetryOOM halves
    every scan table and re-executes the fused program per half (NOT a
    disband into per-op launches), and partial outputs combine by
    addition.  One flight-recorder task spans the plan; with
    ``manage_task`` and no current trace context, so does one ``task``
    root span, the parent of every piece's child spans.
    """
    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.mem.governed import (
        default_device_budget,
        run_with_split_retry,
        task_context,
    )

    if mesh is None:
        dp = 1
    else:
        from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

        dp = mesh.shape[DATA_AXIS]
    if budget is None:
        budget = default_device_budget()
    # the stats-driven rewriter runs FIRST (round 19): stats observed from
    # this upload seed the join-reorder rule, and the CANONICALIZED plan —
    # not the as-written one — keys the result cache below, so two queries
    # that rewrite to the same tree share one cached entry.  Memoized per
    # (plan, stats); off by default, so static configs never re-key.
    if config.get("plan_optimizer"):
        from spark_rapids_jni_tpu.models import tables as _tabreg
        from spark_rapids_jni_tpu.plans.optimizer import optimize_plan

        _tabreg.observe_tables(tables)
        plan = optimize_plan(plan)
    # the result cache consults BEFORE admission (round 15): a hit costs
    # a fingerprint pass over the raw host tables — never a reservation,
    # a retry bracket, or a launch.  Fingerprinted here, before the dim
    # upload below moves anything to the device.
    ckey = cdeps = None
    if config.get("serve_result_cache"):
        from spark_rapids_jni_tpu.plans.rcache import (
            plan_result_key,
            result_cache,
        )

        ckey, cdeps = plan_result_key(plan, dp, tables)
        hit = result_cache.lookup(ckey)
        if hit is not None:
            with _trace.maybe_span(_trace.SPAN_CACHE,
                                   extra=f"plan:{plan.name}"):
                return hit
    scans = ir.scan_tables(plan)
    tables = _upload_dims(plan, tables, mesh)
    if ir.order_sink(plan) is not None and split is None and combine is None:
        # ordered row vectors do not combine by addition, and a row-
        # halved re-execution would need a merge step the default path
        # doesn't have: under pressure an order plan retries at full
        # size (RetryOOM) but never silently splits into wrong answers
        max_split_depth = 0

    # plan-granularity adaptive presplit: this request class's recent
    # retry history decides whether to skip the full-size attempt (0 under
    # static config / kill switch — bit-identical to the round-8 path)
    presplit = suggested_presplit_depth(plan.name, max_split_depth)
    inline_splits = [0]
    attempted = [False]  # flips at the first run attempt: split() calls
    # before it are the pre-split phase (NOT reactive pressure — counting
    # them would pin the hint against decay; exact regardless of how many
    # parts a custom split returns)
    base_split = split or (lambda t: split_scan_tables(t, scans))

    def split_counted(t):
        if attempted[0]:
            inline_splits[0] += 1
        return base_split(t)

    def run(piece: Tables):
        attempted[0] = True
        return execute_plan(mesh, plan, piece)

    def on_retry(_count: int) -> None:
        _record_plan_retry(plan.name)

    ctx = (task_context(budget.gov, task_id) if manage_task
           else contextlib.nullcontext())
    root = (_trace.task_span(task_id, extra=f"plan:{plan.name}")
            if manage_task else contextlib.nullcontext())
    with root, ctx:
        out = run_with_split_retry(
            budget, tables,
            nbytes_of=nbytes_of or (
                lambda t: plan_working_set_bytes(plan, t, dp)),
            run=run,
            split=split_counted,
            combine=combine or combine_outputs,
            max_split_depth=max_split_depth,
            initial_split_depth=presplit,
            on_retry=on_retry,
        )
    _note_plan_run(plan.name, presplit, inline_splits[0], max_split_depth)
    if ckey is not None:
        from spark_rapids_jni_tpu.plans.rcache import result_cache

        # put() revalidates cdeps against the live version registry: a
        # table bumped while this plan computed drops the insert — the
        # result is correct for the OLD content, which no future key
        # can (or should) name
        result_cache.put(ckey, out, cdeps, label=plan.name)
    return out
