"""Plan compiler: trace a whole query plan into ONE jitted program.

Every IR node maps onto the existing device primitives (the same jnp
calls the per-op model bodies used, kept bit-identical so fused results
equal the per-op path exactly); the compiler walks the plan, builds one
python callable over the flat input arrays, wraps it in ``shard_map``
when a mesh is given (facts ride the data axis, dims are replicated,
sink outputs psum), and jits the whole thing — one launch per plan
execution instead of one per op.

Compilation crosses the COMPILE seam (a chaos rule can fail it like the
reference's module-load injector) and is cached in plans/cache.py; the
trace/compile split is measured with the AOT API (``jit(...).lower()``
then ``.compile()``) when the backend supports it, falling back to a
plain jit whose first call pays both.

Emitters are registered with the :func:`emitter` decorator —
``ci/analyze.py``'s governed-allocation pass treats emitter-decorated
functions as traced device code (allocations materialize at the
governed plan launch, not at trace time), the same seeding rule as
``with seam(COMPILE)`` blocks and jit/shard_map arguments.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.plans import ir
from spark_rapids_jni_tpu.plans.cache import CompiledPlan, plan_cache

__all__ = ["compile_plan", "cached_compile", "input_signature",
           "output_names", "emitter", "DTYPES",
           "RaggedProgram", "compile_ragged", "cached_ragged_compile",
           "EXCHANGE_SOURCE", "split_exchange_plan",
           "emit_exchange_partitions", "emit_range_partitions",
           "sample_range_splitters", "eval_post"]

DTYPES = {
    "bool": jnp.bool_,
    "int8": jnp.int8,
    "int32": jnp.int32,
    "int64": jnp.int64,
    "uint64": jnp.uint64,
    "float32": jnp.float32,
    "float64": jnp.float64,
}

#: the implicit per-scan row-validity input the executor appends
VALID_FIELD = "__valid__"

#: the implicit outputs of a plan with SegmentAgg sinks: the rows their
#: masks kept, and the rows the aggregation ran over (int32, each summed
#: over the sinks and the chips)
AGG_KEPT = "__agg_kept__"
AGG_ROWS = "__agg_rows__"
#: the implicit outputs of a plan with KeyJoins: the probe rows their
#: masks kept, those of them that found a build row, and the probe rows
#: the joins ran over (int32, each summed over the joins and the chips)
JOIN_KEPT = "__join_kept__"
JOIN_MATCHED = "__join_matched__"
JOIN_ROWS = "__join_rows__"
#: ... and the kept build rows whose key repeats the row's before it
JOIN_REPEATS = "__join_repeats__"


# ---------------------------------------------------------------- expressions


def _eval(expr, env: Dict[str, object]):
    """Evaluate an IR expression against an environment of traced arrays
    (or, for Plan.post, of aggregate output vectors)."""
    if isinstance(expr, ir.Col):
        return env[expr.name]
    if isinstance(expr, ir.Lit):
        return expr.value
    if isinstance(expr, ir.Cast):
        x = _eval(expr.x, env)
        return jnp.asarray(x).astype(DTYPES[expr.dtype])
    if isinstance(expr, ir.Unary):
        x = _eval(expr.x, env)
        return (~x) if expr.op == "not" else (-x)
    if isinstance(expr, ir.Bin):
        a = _eval(expr.lhs, env)
        b = _eval(expr.rhs, env)
        op = expr.op
        if op == "add":
            return a + b
        if op == "sub":
            return a - b
        if op == "mul":
            return a * b
        if op == "and":
            return a & b
        if op == "or":
            return a | b
        if op == "eq":
            return a == b
        if op == "ne":
            return a != b
        if op == "ge":
            return a >= b
        if op == "gt":
            return a > b
        if op == "le":
            return a <= b
        if op == "lt":
            return a < b
        if op == "min":
            return jnp.minimum(a, b)
        if op == "max":
            return jnp.maximum(a, b)
        if op == "shl":
            return a << b
        if op == "band":
            return a & b
        if op == "bor":
            return a | b
    raise TypeError(f"not an IR expression: {expr!r}")


# ------------------------------------------------------------------- emitters


class _Ctx:
    """One trace: bound input arrays + exchange-drop and aggregate-row
    accumulation."""

    def __init__(self, inputs, rowvalid, mesh):
        self.inputs = inputs      # table -> field -> traced array
        self.rowvalid = rowvalid  # scan table -> traced bool array
        self.mesh = mesh
        self.dropped: List[object] = []
        self.agg_kept: List[object] = []  # rows each SegmentAgg kept
        self.agg_rows = 0  # static rows the aggregation ran over
        self.join_kept: List[object] = []  # probe rows each KeyJoin kept
        self.join_matched: List[object] = []  # ... that found a build row
        self.join_rows = 0  # static probe rows the joins ran over
        self.join_repeats: List[object] = []  # build keys seen twice


class _Rows:
    """A row-level pipeline state: named columns + the AND'd mask."""

    def __init__(self, cols: Dict[str, object], mask):
        self.cols = cols
        self.mask = mask


_EMITTERS: Dict[type, Callable] = {}


def emitter(node_cls):
    """Register the emit function of one IR node type.  Emitter bodies
    are traced device code: ci/analyze.py seeds them as governed roots
    (their allocations happen at the governed plan launch)."""

    def deco(fn):
        _EMITTERS[node_cls] = fn
        return fn

    return deco


def _emit(node, ctx: _Ctx):
    return _EMITTERS[type(node)](node, ctx)


@emitter(ir.Scan)
def _emit_scan(node: ir.Scan, ctx: _Ctx) -> _Rows:
    cols = {f: ctx.inputs[node.table][f] for f in node.fields}
    return _Rows(cols, ctx.rowvalid[node.table])


@emitter(ir.Filter)
def _emit_filter(node: ir.Filter, ctx: _Ctx) -> _Rows:
    rows = _emit(node.child, ctx)
    with jax.named_scope("filter"):
        return _Rows(rows.cols, rows.mask & _eval(node.pred, rows.cols))


@emitter(ir.Project)
def _emit_project(node: ir.Project, ctx: _Ctx) -> _Rows:
    rows = _emit(node.child, ctx)
    cols = dict(rows.cols)
    for name, expr in node.cols:
        cols[name] = _eval(expr, cols)
    return _Rows(cols, rows.mask)


@emitter(ir.GatherJoin)
def _emit_gather_join(node: ir.GatherJoin, ctx: _Ctx) -> _Rows:
    rows = _emit(node.child, ctx)
    dim = ctx.inputs[node.dim.table]
    with jax.named_scope("gather_join"):
        key = _eval(node.key, rows.cols)
        base = _eval(node.base, rows.cols)
        n_dim = dim[node.dim.fields[0]].shape[0]
        idx = jnp.clip(key - base, 0, n_dim - 1)
        cols = dict(rows.cols)
        for source, out in node.fields:
            # an expression source runs over the n_dim dimension rows,
            # then gathers like a column
            vals = dim[source] if isinstance(source, str) \
                else _eval(source, dim)
            cols[out] = vals[idx]
    return _Rows(cols, rows.mask)


def gather_fields(plan: ir.Plan) -> Tuple[int, int]:
    """(fact-length columns the plan's GatherJoins gather, how many of
    them are dimension-side expressions)."""
    fields = [source for n in ir.walk(plan) if isinstance(n, ir.GatherJoin)
              for source, _out in n.fields]
    return len(fields), sum(not isinstance(s, str) for s in fields)


def _rowwise(x, mask):
    """``x`` (a row column or a scalar) as one value per row of ``mask``."""
    return jnp.broadcast_to(jnp.asarray(x), mask.shape)


def _sums_by_sort(dtype: str) -> bool:
    """Integer aggs sum by sort and prefix differences, exact mod 2^k;
    float (and bool) aggs keep the scatter, whose rounding they own."""
    return bool(jnp.issubdtype(DTYPES[dtype], jnp.integer))


def agg_path(plan: ir.Plan) -> str:
    """How the plan's SegmentAgg sinks sum: ``sorted`` (every agg an
    integer), ``scatter`` (none) or ``mixed``."""
    paths = {"sorted" if _sums_by_sort(dtype) else "scatter"
             for sink in plan.sinks if isinstance(sink, ir.SegmentAgg)
             for _name, _expr, dtype in sink.aggs}
    return paths.pop() if len(paths) == 1 else "mixed"


def _lex_below(keys, queries):
    """``keys < queries`` for equally long tuples of columns compared
    lexicographically, the first column most significant."""
    below = keys[-1] < queries[-1]
    for k, q in zip(keys[-2::-1], queries[-2::-1]):
        below = (k < q) | ((k == q) & below)
    return below


def _lower_bounds(sorted_keys, queries, hi):
    """``searchsorted(sorted_keys, queries)`` (side left) for queries whose
    answers are at most ``hi``: a branch-free binary search over
    ``[0, hi]``, one gather of every query a step and ``bit_length(hi)``
    steps, so few where few rows are kept.  Keys and queries are equally
    long tuples of columns, sorted and compared lexicographically.  Its
    loop body is this function's own ops: no jitted helper's trace,
    shared with other programs, carries this caller's frames."""
    size = sorted_keys[0].shape[0]
    steps = 32 - jax.lax.clz(hi)
    top = jax.lax.shift_left(jnp.int32(1), jnp.maximum(steps - 1, 0))

    def step(i, pos):
        # at least cand keys lie below q iff key[cand - 1] < q
        cand = pos + jax.lax.shift_right_logical(top, i)
        at = jnp.minimum(cand, size) - 1
        below = _lex_below(tuple(k[at] for k in sorted_keys), queries)
        return jax.lax.select((cand <= hi) & below, cand, pos)

    return jax.lax.fori_loop(0, steps, step,
                             jnp.zeros(queries[0].shape, jnp.int32))


@emitter(ir.KeyJoin)
def _emit_key_join(node: ir.KeyJoin, ctx: _Ctx) -> _Rows:
    """One payload sort of the build rows by (not kept, key...), so the
    kept rows come first in key order; a binary search of each probe key
    over them; one gather of each field at the found row."""
    probe = _emit(node.probe, ctx)
    build = _emit(node.build, ctx)
    with jax.named_scope("key_join"):
        keys = [_rowwise(_eval(k, build.cols), build.mask)
                for k in node.build_key]
        payload = []
        for source, valid, _out in node.fields:
            payload.append(build.cols[source])
            if valid is not None:
                payload.append(build.cols[valid])
        kept = build.mask
        if ctx.mesh is not None:
            from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

            if ctx.mesh.shape[DATA_AXIS] > 1:
                # a broadcast join: every chip searches all build rows
                def everyone(x):
                    return jax.lax.all_gather(x, DATA_AXIS, tiled=True)

                keys = [everyone(k) for k in keys]
                payload = [everyone(v) for v in payload]
                kept = everyone(kept)
        dropped = jax.lax.convert_element_type(~kept, jnp.int8)
        flag, *ordered = jax.lax.sort((dropped, *keys, *payload),
                                      num_keys=1 + len(keys),
                                      is_stable=False)
        sorted_keys = tuple(ordered[:len(keys)])
        # the build key must be unique: count the kept rows that repeat
        # their predecessor's key, for the runtime to refuse
        repeat = (flag[1:] == 0) & (flag[:-1] == 0)
        for k in sorted_keys:
            repeat = repeat & (k[1:] == k[:-1])
        ctx.join_repeats.append(jnp.sum(repeat, dtype=jnp.int32))
        sorted_vals = iter(ordered[len(keys):])
        queries = tuple(_rowwise(_eval(k, probe.cols), probe.mask)
                        for k in node.probe_key)
        n_kept = jnp.sum(kept, dtype=jnp.int32)
        pos = _lower_bounds(sorted_keys, queries, n_kept)
        at = jnp.minimum(pos, sorted_keys[0].shape[0] - 1)
        found = pos < n_kept
        for k, q in zip(sorted_keys, queries):
            found = found & (k[at] == q)
        cols = dict(probe.cols)
        for _source, valid, out in node.fields:
            value = next(sorted_vals)[at]
            ok = found if valid is None else found & next(sorted_vals)[at]
            cols[out] = jax.lax.select(ok, value, jnp.zeros_like(value))
            cols[f"{out}_valid"] = ok
        ctx.join_kept.append(jnp.sum(probe.mask, dtype=jnp.int32))
        ctx.join_matched.append(jnp.sum(probe.mask & found, dtype=jnp.int32))
        ctx.join_rows += int(probe.mask.shape[0])
    return _Rows(cols, probe.mask)


def _sorted_segment_sums(bucket, n: int, aggs, cols) -> Dict[str, object]:
    """Integer segment sums over ``bucket`` by ONE sort that carries every
    value column as payload: each segment is a run of the sorted stream,
    found by binary search, and its sum is the difference of the wrapping
    prefix sums at the run's ends — bit-identical to ``segment_sum``,
    since integer addition mod 2^k is a group.  A scalar value needs no
    payload: its sum is the run length times the value, in its dtype."""
    payload, scalars = {}, {}
    for name, value_expr, dtype in aggs:
        v = _eval(value_expr, cols)
        if jnp.ndim(v) == 0:
            scalars[name] = jnp.asarray(v).astype(DTYPES[dtype])
        else:
            # a masked row's value rides to bucket n, past every bound
            payload[name] = jnp.asarray(v).astype(DTYPES[dtype])
    sorted_bucket, *sorted_vals = jax.lax.sort(
        (bucket, *payload.values()), num_keys=1, is_stable=False)
    # every bound lies at or below the count of keys below n
    bounds = _lower_bounds(
        (sorted_bucket,), (jnp.arange(n + 1, dtype=sorted_bucket.dtype),),
        jnp.sum(bucket < n, dtype=jnp.int32))
    out = {}
    for name, v in zip(payload, sorted_vals):
        prefix = jnp.concatenate([jnp.zeros((1,), v.dtype),
                                  jax.lax.cumsum(v)])
        out[name] = prefix[bounds[1:]] - prefix[bounds[:-1]]
    counts = bounds[1:] - bounds[:-1]
    for name, c in scalars.items():
        out[name] = counts.astype(c.dtype) * c
    return out


@emitter(ir.SegmentAgg)
def _emit_segment_agg(node: ir.SegmentAgg, ctx: _Ctx) -> Dict[str, object]:
    rows = _emit(node.child, ctx)
    with jax.named_scope("segment_agg"):
        key = _rowwise(_eval(node.key, rows.cols), rows.mask)
        n = node.num_segments
        # masked rows go to the drop bucket n, which no output reads.
        # lax.select, not jnp.where: jnp.where's jitted trace is cached by
        # shape across programs, with the frames of its first caller
        bucket = jax.lax.select(rows.mask, key, jnp.full_like(key, n))
        by_sort = [a for a in node.aggs if _sums_by_sort(a[2])]
        sums = _sorted_segment_sums(bucket, n, by_sort, rows.cols) \
            if by_sort else {}
        out = {}
        for name, value_expr, dtype in node.aggs:
            if name in sums:
                out[name] = sums[name]
                continue
            vals = _rowwise(jnp.asarray(_eval(value_expr, rows.cols))
                            .astype(DTYPES[dtype]), rows.mask)
            vals = jax.lax.select(rows.mask, vals, jnp.zeros_like(vals))
            out[name] = jax.ops.segment_sum(vals, bucket,
                                            num_segments=n + 1)[:-1]
        ctx.agg_kept.append(jnp.sum(rows.mask, dtype=jnp.int32))
        ctx.agg_rows += int(rows.mask.shape[0])
    return out


@emitter(ir.Union)
def _emit_union(node: ir.Union, ctx: _Ctx) -> _Rows:
    parts = [_emit(c, ctx) for c in node.children]
    fields = [f for f in parts[0].cols if all(f in p.cols for p in parts)]
    cols = {f: jnp.concatenate([p.cols[f] for p in parts]) for f in fields}
    cols[node.tag] = jnp.concatenate([
        jnp.full(p.mask.shape, tv, jnp.int8)
        for p, tv in zip(parts, node.tag_values)
    ])
    return _Rows(cols, jnp.concatenate([p.mask for p in parts]))


@emitter(ir.Exchange)
def _emit_exchange(node: ir.Exchange, ctx: _Ctx) -> _Rows:
    from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS
    from spark_rapids_jni_tpu.parallel.shuffle import (
        all_to_all_shuffle,
        partition_of,
    )

    rows = _emit(node.child, ctx)
    dp = jax.lax.axis_size(DATA_AXIS)
    part = partition_of(_eval(node.key, rows.cols), dp)
    ex = all_to_all_shuffle(
        {f: rows.cols[f] for f in node.fields}, part, node.capacity,
        axis=DATA_AXIS, row_valid=rows.mask,
    )
    ctx.dropped.append(ex.dropped)
    return _Rows(dict(ex.columns), ex.valid)


@emitter(ir.RangeExchange)
def _emit_range_exchange(node: ir.RangeExchange, ctx: _Ctx):
    # registration keeps the split/rebuild machinery node-aware; there is
    # deliberately no traced body — psum cannot merge ordered row vectors,
    # so a range shuffle only exists on the cross-process plane
    raise ValueError(
        "RangeExchange has no in-process emitter: split the plan "
        "(split_exchange_plan) and run it on the serve shuffle plane, or "
        "through its single-process oracle (serve.shuffle."
        "run_range_plan_local)")


def _order_env(keys, cols, mask):
    """(permutation, sorted per-key ranks) for ``(expr, ascending)`` sort
    keys over a row environment — the shared front half of every
    order-sensitive emitter."""
    from spark_rapids_jni_tpu.plans import window as win

    ranks = [win.sort_rank(jnp.asarray(_eval(e, cols)), asc)
             for e, asc in keys]
    order = win.order_permutation(ranks, mask)
    return order, [r[order] for r in ranks]


def _gather_cols(cols, order):
    return {k: jnp.asarray(v)[order] if jnp.ndim(v) else v
            for k, v in cols.items()}


@emitter(ir.Window)
def _emit_window(node: ir.Window, ctx: _Ctx) -> _Rows:
    from spark_rapids_jni_tpu.plans import window as win

    rows = _emit(node.child, ctx)
    pkeys = tuple((e, True) for e in node.partition_by)
    order, sranks = _order_env(pkeys + node.order_by, rows.cols, rows.mask)
    cols = _gather_cols(rows.cols, order)
    mask = rows.mask[order]
    np_keys = len(node.partition_by)
    run_start = win.run_boundaries(sranks[:np_keys], mask)
    ochange = win.change_points(sranks[np_keys:]) if node.order_by else (
        jnp.zeros_like(run_start))
    for f in node.funcs:
        if f.kind == "row_number":
            out = win.row_number(run_start)
        elif f.kind == "rank":
            out = win.rank(run_start, ochange)
        elif f.kind == "dense_rank":
            out = win.dense_rank(run_start, ochange)
        else:
            v = jnp.asarray(_eval(f.arg, cols)).astype(DTYPES[f.dtype])
            # invalid rows sort last and open their own run
            # (run_boundaries), so their garbage can never reach a valid
            # segment; zeroing keeps even the masked outputs finite
            v = jnp.where(mask, v, jnp.zeros((), v.dtype))
            if f.kind == "sum":
                out = win.framed_sum(v, run_start, f.preceding)
            else:
                out = win.framed_minmax(v, run_start, f.kind, f.preceding)
        cols[f.name] = out.astype(DTYPES[f.dtype]) if f.kind in (
            "rank", "dense_rank", "row_number") else out
    return _Rows(cols, mask)


def _order_sink_outputs(node, ctx: _Ctx, k=None) -> Dict[str, object]:
    """Shared Sort/TopK sink body: order rows (invalid last), emit the
    named field vectors plus the implicit valid-``rows`` count; TopK
    additionally slices the first ``min(k, n)`` rows (static shapes)."""
    rows = _emit(node.child, ctx)
    order, _ranks = _order_env(node.keys, rows.cols, rows.mask)
    cols = _gather_cols(rows.cols, order)
    nvalid = jnp.sum(rows.mask.astype(jnp.int64))
    out = {}
    for f in node.fields:
        v = cols[f]
        out[f] = v[:min(int(k), v.shape[0])] if k is not None else v
    out["rows"] = jnp.minimum(nvalid, k) if k is not None else nvalid
    return out


@emitter(ir.Sort)
def _emit_sort(node: ir.Sort, ctx: _Ctx) -> Dict[str, object]:
    return _order_sink_outputs(node, ctx)


@emitter(ir.TopK)
def _emit_topk(node: ir.TopK, ctx: _Ctx) -> Dict[str, object]:
    return _order_sink_outputs(node, ctx, k=int(node.k))


@emitter(ir.PresenceCount)
def _emit_presence_count(node: ir.PresenceCount,
                         ctx: _Ctx) -> Dict[str, object]:
    # lazy: models.q97 imports plans at module level; by trace time the
    # module exists, and _count_runs stays single-owner over there
    from spark_rapids_jni_tpu.models.q97 import _count_runs

    rows = _emit(node.child, ctx)
    # a stable name for the sort-merge count's ops in the HLO metadata
    with jax.named_scope("presence_count"):
        so, co, b = _count_runs(rows.cols[node.key],
                                rows.cols[node.tag] == 1, rows.mask)
    return dict(zip(node.names, (so, co, b)))


# ------------------------------------------------------------------ compiling


def output_names(plan: ir.Plan) -> Tuple[str, ...]:
    """Static output order of a compiled plan: sink outputs in sink/agg
    order, then the implicit ``dropped`` (plans with an Exchange), then
    post outputs — filtered/ordered by ``plan.outputs`` when set."""
    names: List[str] = []
    ir.order_sink(plan)  # validates order sinks don't mix with others
    for sink in plan.sinks:
        if isinstance(sink, ir.SegmentAgg):
            names.extend(name for name, _e, _d in sink.aggs)
        elif isinstance(sink, ir.PresenceCount):
            names.extend(sink.names)
        elif isinstance(sink, (ir.Sort, ir.TopK)):
            # ordered field vectors plus the implicit valid-row count
            names.extend(sink.fields)
            names.append("rows")
        else:
            raise TypeError(f"not a sink node: {sink!r}")
    if ir.has_exchange(plan):
        names.append("dropped")
    names.extend(name for name, _e in plan.post)
    if len(set(names)) != len(names):
        raise ValueError(f"duplicate output names in plan {plan.name!r}")
    if plan.outputs:
        missing = set(plan.outputs) - set(names)
        if missing:
            raise ValueError(f"unknown plan outputs {sorted(missing)}")
        if ir.has_exchange(plan) and "dropped" not in plan.outputs:
            # the runtime's overflow guard reads 'dropped' from the
            # compiled outputs; filtering it away would silently disable
            # ShuffleCapacityExceeded and return wrong counts on overflow
            raise ValueError(
                f"plan {plan.name!r} contains an Exchange: its 'outputs' "
                f"must include 'dropped' (the overflow retry signal)")
        return tuple(plan.outputs)
    return tuple(names)


def _arg_layout(plan: ir.Plan):
    """Flat argument order: scans (table-sorted; fields then the implicit
    row-valid), then dims (table-sorted)."""
    layout = []
    for scan in ir.scan_tables(plan):
        for f in scan.fields:
            layout.append(("scan", scan.table, f))
        layout.append(("scan", scan.table, VALID_FIELD))
    for dim in ir.dim_tables(plan):
        for f in dim.fields:
            layout.append(("dim", dim.table, f))
    return layout


def input_signature(plan: ir.Plan, tables) -> Tuple:
    """The dtype+bucket signature of already-padded input ``tables``
    (table -> field -> array, row-valid included) in flat arg order —
    the variable half of the plan-cache key."""
    sig = []
    for kind, table, field in _arg_layout(plan):
        a = tables[table][field]
        sig.append((kind, table, field, str(a.dtype), int(a.shape[0])))
    return tuple(sig)


def compile_plan(plan: ir.Plan, mesh, signature: Tuple) -> CompiledPlan:
    """Trace + compile ``plan`` for one input signature.  Uncached —
    go through :func:`cached_compile`."""
    from spark_rapids_jni_tpu.obs.seam import COMPILE, seam

    layout = _arg_layout(plan)
    if len(signature) != len(layout):
        raise ValueError("signature does not match the plan's arg layout")
    out_names = output_names(plan)
    if any(isinstance(s, ir.SegmentAgg) for s in plan.sinks):
        out_names += (AGG_KEPT, AGG_ROWS)
    if any(isinstance(n, ir.KeyJoin) for n in ir.walk(plan)):
        out_names += (JOIN_KEPT, JOIN_MATCHED, JOIN_ROWS, JOIN_REPEATS)
    local = mesh is None
    if local and ir.has_exchange(plan):
        raise ValueError(
            f"plan {plan.name!r} contains an Exchange: mesh required")
    if ir.range_exchange_nodes(plan):
        raise ValueError(
            f"plan {plan.name!r} contains a RangeExchange: it only runs "
            f"split across the serve shuffle plane (split_exchange_plan)")
    if not local and ir.order_sink(plan) is not None:
        # the mesh path psums every sink output over the data axis —
        # correct for additive partials, destruction for ordered row
        # vectors; distribution happens via the range shuffle instead
        raise ValueError(
            f"plan {plan.name!r} has an order-sensitive sink: compile "
            f"locally (per shuffle partition), not under a mesh")

    def body(*flat):
        inputs: Dict[str, Dict[str, object]] = {}
        rowvalid: Dict[str, object] = {}
        for (kind, table, field), arr in zip(layout, flat):
            if field == VALID_FIELD:
                rowvalid[table] = arr
            else:
                inputs.setdefault(table, {})[field] = arr
        ctx = _Ctx(inputs, rowvalid, mesh)
        outputs: Dict[str, object] = {}
        for sink in plan.sinks:
            outputs.update(_emit(sink, ctx))
        if ctx.dropped:
            outputs["dropped"] = sum(ctx.dropped[1:], ctx.dropped[0])
        if ctx.agg_kept:
            outputs[AGG_KEPT] = sum(ctx.agg_kept[1:], ctx.agg_kept[0])
            outputs[AGG_ROWS] = jnp.int32(ctx.agg_rows)
        if ctx.join_kept:
            outputs[JOIN_KEPT] = sum(ctx.join_kept[1:], ctx.join_kept[0])
            outputs[JOIN_MATCHED] = sum(ctx.join_matched[1:],
                                        ctx.join_matched[0])
            outputs[JOIN_ROWS] = jnp.int32(ctx.join_rows)
            outputs[JOIN_REPEATS] = sum(ctx.join_repeats[1:],
                                        ctx.join_repeats[0])
        if not local:
            from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

            outputs = {k: jax.lax.psum(v, (DATA_AXIS,))
                       for k, v in outputs.items()}
        for name, expr in plan.post:
            outputs[name] = _eval(expr, outputs)
        return tuple(outputs[n] for n in out_names)

    with seam(COMPILE, f"plan:{ir.plan_signature(plan)}"):
        if local:
            step = jax.jit(body)
        else:
            from jax.sharding import PartitionSpec as P

            from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

            in_specs = tuple(
                P(DATA_AXIS) if kind == "scan" else P()
                for kind, _t, _f in layout)
            step = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=in_specs,
                out_specs=tuple(P() for _ in out_names),
                check_vma=False,
            ))
        fn, aot, trace_s, compile_s, aot_err = _try_aot(
            step, mesh, layout, signature)
    return CompiledPlan(fn, plan, mesh, signature, out_names,
                        tuple(f"{t}.{f}" for _k, t, f in layout),
                        aot, trace_s, compile_s, aot_err)


def _try_aot(step, mesh, layout, signature):
    """AOT lower+compile so trace and compile are separately timed (the
    bench's compile-amortization story); fall back to the plain jit —
    whose first call pays both — if the backend refuses the abstract
    shardings."""
    try:
        from jax.sharding import NamedSharding, PartitionSpec as P

        avals = []
        for (kind, _t, _f), (_k2, _t2, _f2, dtype, n) in zip(layout,
                                                             signature):
            sharding = None
            if mesh is not None:
                from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

                sharding = NamedSharding(
                    mesh, P(DATA_AXIS) if kind == "scan" else P())
            avals.append(jax.ShapeDtypeStruct((n,), DTYPES.get(dtype, dtype),
                                              sharding=sharding))
        t0 = time.perf_counter()
        lowered = step.lower(*avals)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        return compiled, True, t1 - t0, t2 - t1, ""
    # analyze: ignore[retry-protocol] - AOT probe at compile time, before
    # any device work launches: no retry bracket is open, and the plain
    # jit fallback is the correct degradation for any lowering failure.
    # NOT silent: the reason rides CompiledPlan.aot_error and the cache
    # counts aot_fallbacks in its stats gauge, so a genuine trace bug
    # deferred to first launch is still visible at the compile layer.
    except Exception as e:  # noqa: BLE001
        return step, False, 0.0, 0.0, f"{type(e).__name__}: {e}"[:200]


def cached_compile(plan: ir.Plan, mesh, tables) -> CompiledPlan:
    """The front door: compiled program for (plan, mesh, padded inputs),
    via the process-global plan cache."""
    sig = input_signature(plan, tables)
    return plan_cache.get_or_compile(
        (plan, mesh, sig), lambda: compile_plan(plan, mesh, sig))


# ------------------------------------------- cross-process exchange split
# A plan whose Exchange runs as a REAL shuffle (serve/shuffle.py: framed
# partition push/pull between executor processes) splits at the Exchange
# node into two halves that reuse this compiler unchanged:
#
# - the **map fragment** — the Exchange's child subtree — runs eagerly
#   per executor over its shard of the scan tables (the SAME registered
#   emitter bodies the jitted path traces, so values are bit-identical),
#   then rows partition by ``partition_of(key) % nparts`` and masked rows
#   drop (exactly what the in-mesh all_to_all's validity mask does);
# - the **reduce plan** — the original plan with the Exchange replaced by
#   a Scan of the synthetic ``EXCHANGE_SOURCE`` table — compiles through
#   :func:`cached_compile` as a LOCAL plan over the concatenated received
#   partitions.  Its sinks are additive partials (psum's host analog is
#   summation at the combiner), so ``post`` expressions move OUT of the
#   reduce plan and evaluate once over the summed sinks (:func:`eval_post`).


#: the synthetic scan table the reduce half reads received rows from
EXCHANGE_SOURCE = "__exchange__"


def split_exchange_plan(plan: ir.Plan):
    """``(exchange_node, reduce_plan)`` for a plan with exactly ONE
    Exchange or RangeExchange.  The reduce plan is local (no Exchange,
    no mesh), reads the shuffled fields from
    ``Scan(EXCHANGE_SOURCE, fields)``, keeps the sinks, and drops
    ``post``/``outputs`` — partials must be combined across executors
    (summed, or order-concatenated for a range shuffle) BEFORE post
    expressions run."""
    exchanges = ir.exchange_nodes(plan) + ir.range_exchange_nodes(plan)
    if len(exchanges) != 1:
        raise ValueError(
            f"plan {plan.name!r} has {len(exchanges)} Exchange nodes; the "
            f"cross-process shuffle supports exactly one")
    exchange = exchanges[0]

    def rebuild(node):
        if node is exchange or node == exchange:
            return ir.Scan(EXCHANGE_SOURCE, node.fields)
        kw = {}
        changed = False
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            if isinstance(v, tuple) and v and all(
                    type(item) in _EMITTERS for item in v):
                nv = tuple(rebuild(item) for item in v)
                changed = changed or nv != v
                kw[f.name] = nv
            elif type(v) in _EMITTERS:
                nv = rebuild(v)
                changed = changed or nv is not v
                kw[f.name] = nv
            else:
                kw[f.name] = v
        return dataclasses.replace(node, **kw) if changed else node

    sinks = tuple(rebuild(s) for s in plan.sinks)
    reduce_plan = ir.Plan(f"{plan.name}:reduce", sinks)
    extra = [s.table for s in ir.scan_tables(reduce_plan)
             if s.table != EXCHANGE_SOURCE]
    if extra:
        raise ValueError(
            f"plan {plan.name!r} scans {extra} ABOVE its Exchange: the "
            f"reduce half would re-read whole fact tables per executor "
            f"and double-count — every Scan must feed the Exchange")
    return exchange, reduce_plan


def emit_exchange_partitions(exchange: ir.Exchange, tables,
                             nparts: int) -> list:
    """The map side of one executor's shard: emit the Exchange's child
    subtree eagerly (same emitter bodies as the traced path), hash the
    key with the SAME placement hash the in-mesh all_to_all uses, and
    return ``nparts`` host partition tables of the exchange fields
    (masked rows dropped — the slot-validity analog).  Partition sizes
    are exact, so the fixed-capacity overflow retry of the in-mesh path
    has no cross-process counterpart."""
    import numpy as np

    from spark_rapids_jni_tpu.parallel.shuffle import partition_of

    rows = _emit_host_rows(exchange, tables)
    key = _eval(exchange.key, rows.cols)
    part = np.asarray(partition_of(key, nparts))
    mask = np.asarray(rows.mask)
    cols = {f: np.asarray(rows.cols[f]) for f in exchange.fields}
    out = []
    for p in range(nparts):
        sel = mask & (part == p)
        out.append({f: np.ascontiguousarray(v[sel])
                    for f, v in cols.items()})
    return out


def _emit_host_rows(exchange, tables) -> _Rows:
    """Eagerly emit an exchange node's child subtree over host shard
    tables (same emitter bodies as the traced path, so values are
    bit-identical) — the shared map-side front half of the hash and
    range partition emitters."""
    inputs: Dict[str, Dict[str, object]] = {}
    rowvalid: Dict[str, object] = {}
    for table, fields in tables.items():
        inputs[table] = {k: jnp.asarray(v) for k, v in fields.items()}
        n = len(next(iter(fields.values())))
        # analyze: ignore[governed-allocation] - the all-valid row mask
        # of an EXACT (unpadded) shard: O(rows) bools inside the serve
        # bracket that admitted the shuffle piece, already covered by
        # the shard's working-set estimate like the shard columns above
        rowvalid[table] = jnp.ones((n,), jnp.bool_)
    return _emit(exchange.child, _Ctx(inputs, rowvalid, None))


def _host_rank_cols(exchange: "ir.RangeExchange", rows: _Rows) -> list:
    """The host uint64 rank columns of a range exchange's sort keys —
    the SAME canonical transform the traced order emitters apply, so
    partition placement and device order can never disagree."""
    import numpy as np

    from spark_rapids_jni_tpu.plans import window as win

    return [win.sort_rank_np(np.asarray(_eval(e, rows.cols)), asc)
            for e, asc in exchange.keys]


def sample_range_splitters(exchange: "ir.RangeExchange", tables,
                           nparts: int, sample_cap: int = 4096) -> list:
    """Driver-side splitter choice for one range shuffle: emit the map
    fragment over the full input ONCE, sample the valid rows' composite
    sort ranks evenly, take quantile boundaries.  Every map shard must
    ride with the SAME splitters (they define the global partition
    order), so this runs once at dispatch, not per shard."""
    import numpy as np

    from spark_rapids_jni_tpu.plans import window as win

    rows = _emit_host_rows(exchange, tables)
    ranks = _host_rank_cols(exchange, rows)
    return win.choose_splitters(ranks, np.asarray(rows.mask), nparts,
                                sample_cap=sample_cap)


def emit_range_partitions(exchange: "ir.RangeExchange", tables,
                          nparts: int, splitters) -> list:
    """The map side of one executor's shard of a RANGE shuffle: emit the
    child subtree eagerly, rank rows by the exchange's sort keys (the
    canonical uint64 transform), and bucket them against the dispatch-
    time ``splitters`` — partition ``p``'s every row orders before
    partition ``p+1``'s, so the reduce side's per-partition sorted
    outputs concatenate into global order with no merge.

    With ``exchange.limit`` set (partial top-k pushdown), only this
    shard's first ``limit`` ordered VALID rows are partitioned at all:
    the global top-k is a subset of the per-shard top-k's, so at most
    ``limit * shards`` rows cross the wire instead of every row."""
    import numpy as np

    from spark_rapids_jni_tpu.plans import window as win

    if len(splitters) != nparts - 1:
        raise ValueError(
            f"range shuffle wants {nparts - 1} splitters, got "
            f"{len(splitters)}")
    rows = _emit_host_rows(exchange, tables)
    ranks = _host_rank_cols(exchange, rows)
    mask = np.asarray(rows.mask)
    sel = np.flatnonzero(mask)
    # valid rows in key order (np.lexsort: last key is primary)
    sel = sel[np.lexsort(tuple(reversed([r[sel] for r in ranks])))]
    if exchange.limit is not None:
        sel = sel[:int(exchange.limit)]
    part = win.range_partition([r[sel] for r in ranks], splitters)
    cols = {f: np.asarray(rows.cols[f])[sel] for f in exchange.fields}
    out = []
    for p in range(nparts):
        take = part == p
        out.append({f: np.ascontiguousarray(v[take])
                    for f, v in cols.items()})
    return out


def eval_post(plan: ir.Plan, sums: Dict[str, object]) -> Dict[str, object]:
    """Post expressions over the cross-executor SUMMED sink outputs —
    the host twin of the traced path's psum-then-post ordering.  Returns
    sinks + posts filtered/ordered like :func:`output_names` (minus the
    in-mesh path's implicit ``dropped``, which exact-size framed
    partitions cannot produce)."""
    import numpy as np

    env = dict(sums)
    for name, expr in plan.post:
        env[name] = np.asarray(_eval(expr, env))
    names = [n for n in output_names(plan) if n != "dropped"]
    return {n: env[n] for n in names}


# ----------------------------------------------- ragged calling convention


@dataclasses.dataclass(frozen=True)
class RaggedProgram:
    """The hashable identity of one page-pool-shaped fused program — the
    plan-cache key the ragged serving path compiles under (the analog of
    an :class:`ir.Plan` value for a handler kernel instead of a query
    IR).  ``geometry`` is a :class:`columnar.pages.PageGeometry`; equal
    (kernel, geometry, out) ticks share one compiled executable, so a
    long-lived executor's cache holds one entry per PAGE GEOMETRY, not
    one per request-shape bucket.

    ``kernel_key`` names the kernel (module-qualified by default):
    handler registration is per engine, but the plan cache is process
    global, so the key must identify the FUNCTION, not the handler name
    a second engine may rebind.
    """

    kernel_key: str
    geometry: object  # columnar.pages.PageGeometry (frozen, hashable)
    out: str          # "rows" (row-aligned) | "riders" (per-rider vector)

    @property
    def name(self) -> str:
        return f"ragged:{self.kernel_key}:{self.geometry.describe()}"


def _ragged_signature(prog: RaggedProgram) -> Tuple:
    """The flat input signature of the page-pool calling convention:
    ``(data[total_rows] dtype, valid[total_rows] bool,
    rid[total_rows] int32)`` — entirely geometry-derived, the property
    the cache-bounding acceptance test pins."""
    g = prog.geometry
    n = g.total_rows
    return (("pages", "pool", "data", g.dtype, n),
            ("pages", "pool", VALID_FIELD, "bool", n),
            ("pages", "pool", "rid", "int32", n))


def compile_ragged(prog: RaggedProgram, kernel: Callable) -> CompiledPlan:
    """Trace + compile ``kernel`` under the page-pool calling convention.

    ``kernel(data, valid, rid, riders_cap)`` is traced device code over
    the flat pool buffers (``riders_cap`` is static, baked into the
    trace); it returns ONE array, either row-aligned (``out="rows"`` —
    the executor scatters slices back per rider) or per-rider
    (``out="riders"``, indexed by the pack's rider ids; padding rows
    carry ``rid == riders_cap`` so a segment scatter's drop bucket is
    index ``riders_cap`` — kernels must size segment outputs
    ``riders_cap + 1`` and drop the tail, like the masked-segment
    aggregate emitter).  Uncached — go through
    :func:`cached_ragged_compile`.
    """
    from spark_rapids_jni_tpu.obs.seam import COMPILE, seam

    g = prog.geometry
    riders_cap = g.riders_cap

    def body(data, valid, rid):
        return (kernel(data, valid, rid, riders_cap),)

    with seam(COMPILE, prog.name):
        step = jax.jit(body)
        fn, aot, trace_s, compile_s, aot_err = _try_aot_flat(
            step, _ragged_signature(prog))
    return CompiledPlan(fn, prog, None, _ragged_signature(prog),
                        ("out",), ("pool.data", "pool.__valid__",
                                   "pool.rid"),
                        aot, trace_s, compile_s, aot_err)


def _try_aot_flat(step, signature):
    """AOT lower+compile over a flat (unsharded) signature — the ragged
    twin of :func:`_try_aot` (which builds per-table shardings a page
    pool does not have)."""
    try:
        avals = [jax.ShapeDtypeStruct((n,), DTYPES.get(dtype, dtype))
                 for _k, _t, _f, dtype, n in signature]
        t0 = time.perf_counter()
        lowered = step.lower(*avals)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        return compiled, True, t1 - t0, t2 - t1, ""
    # analyze: ignore[retry-protocol] - AOT probe at compile time, before
    # any device work launches (same degradation contract as _try_aot):
    # the plain-jit fallback is correct for any lowering refusal, and the
    # reason rides CompiledPlan.aot_error + the cache's aot_fallbacks
    # gauge rather than being swallowed.
    except Exception as e:  # noqa: BLE001
        return step, False, 0.0, 0.0, f"{type(e).__name__}: {e}"[:200]


def cached_ragged_compile(prog: RaggedProgram,
                          kernel: Callable) -> CompiledPlan:
    """The ragged front door: one compiled executable per
    (kernel, page geometry, out kind), via the SAME process-global plan
    cache (ragged programs compete for residency with query plans and
    show up in the same hit/miss gauges — the compile-pressure story is
    one story)."""
    return plan_cache.get_or_compile(
        (prog, None, _ragged_signature(prog)),
        lambda: compile_ragged(prog, kernel))
