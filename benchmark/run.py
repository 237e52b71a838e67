"""Run one benchmark cell on the chips of this machine.

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up generates the cell's tables from ``--seed``, builds the system under
test and runs one warm-up query, so that the cell's one plan shape is
compiled or loaded from the persistent cache.  The window then runs the
cell's traffic for ``--seconds``.  After it, the plain reference checks
every answer of the window.  The last line of standard output is one JSON
object (``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and ``checks`` last); the last lines
of standard error give each compared number beside its limit.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the metrics are the
cell's per-layer metrics.  Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 2.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, Optional, Sequence

from benchmark import cells as cells_mod
from benchmark import trace as trace_mod


class CompileClock:
    """Counts XLA backend compiles (JAX's monitoring events; a persistent
    cache hit records none).  A copy of ``chip_smoke.CompileClock``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, name, secs, **_kw):
        if name == self.EVENT:
            self.count += 1
            self.seconds += secs

    @contextlib.contextmanager
    def listening(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        try:
            yield self
        finally:
            jax.monitoring.unregister_event_duration_listener(self._on_event)


def _probe(clock: CompileClock):
    """The program's counters that per-query records carry."""
    from spark_rapids_jni_tpu.plans import plan_cache

    def probe() -> Dict[str, float]:
        st = plan_cache.stats()
        return {"execute_s": float(st["execute_s"]),
                "plan_misses": int(st["misses"]),
                "backend_compiles": clock.count}

    return probe


@contextlib.contextmanager
def _plans_looked_up():
    """Every compiled plan the program looks up in the block, in order:
    the plans the window ran, a split's pieces and a grow's retries
    included.  Yields the list it fills."""
    from spark_rapids_jni_tpu.plans import plan_cache

    seen = []
    lookup = plan_cache.get_or_compile

    def tapped(key, builder):
        entry = lookup(key, builder)
        seen.append(entry)
        return entry

    plan_cache.get_or_compile = tapped
    try:
        yield seen
    finally:
        del plan_cache.get_or_compile  # the class's method again


def _plan_facts(plans) -> list:
    """What the readers take from each plan the window looked up: its
    chips, the slots its Exchanges' receive buffers hold over all chips
    (dp x dp x capacity each, as ``plans.runtime`` sizes them), and its
    compiled footprint on one chip (``memory_analysis()``: arguments,
    outputs and temporaries, less what outputs alias; None without it)."""
    from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS
    from spark_rapids_jni_tpu.plans import ir

    by_id = {}
    for cp in plans:
        if id(cp) in by_id:
            continue
        dp = 1 if cp.mesh is None else int(cp.mesh.shape[DATA_AXIS])
        chips = 1 if cp.mesh is None else int(cp.mesh.devices.size)
        ma = cp.fn.memory_analysis() if cp.aot else None
        by_id[id(cp)] = {
            "chips": chips,
            "exchange_slots": sum(dp * dp * int(x.capacity)
                                  for x in ir.exchange_nodes(cp.plan)),
            "bytes_per_chip": None if ma is None else (
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes),
        }
    return [by_id[id(cp)] for cp in plans]


@contextlib.contextmanager
def _profiled(enabled: bool):
    """Trace the block with the profiler; yields a holder whose ``path``
    is the ``.xplane.pb`` once the block has ended."""
    holder = {"path": None}
    if not enabled:
        yield holder
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            yield holder
        finally:
            jax.profiler.stop_trace()
        found = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        holder["summary"] = (trace_mod.summarize(trace_mod.load(found[0]))
                             if found else None)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _span(enabled: bool):
    if not enabled:
        return lambda _name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


def run_cell(cell: cells_mod.Cell, seed: int, seconds: float, trace: bool,
             devices: Sequence, peaks: Dict[str, Any],
             t_start: Optional[float] = None) -> Dict[str, Any]:
    """Set-up, window, reference check and metrics of one run of ``cell``
    on ``devices``; returns the result object."""
    from spark_rapids_jni_tpu.mem.governed import default_device_budget

    t_start = time.perf_counter() if t_start is None else t_start
    q = cell.query
    clock = CompileClock()
    with clock.listening():
        tables = q.generate(cell.config, seed)
        run = q.system(cell.config, devices)
        run(tables)  # the warm-up: the window's one plan shape
        setup_s = time.perf_counter() - t_start
        probe = _probe(clock)
        budget = default_device_budget()
        budget.reset_peak()
        with _plans_looked_up() as plans, _profiled(trace) as prof:
            records, window_s = cell.loop.run_window(
                run, tables, seconds, probe, _span(trace))
        governor_peak = budget.reset_peak()
    mem = [d.memory_stats() or {} for d in devices]

    want = q.reference(tables)
    failed, checks = q.checks([r["answer"] for r in records], want)
    correct = bool(records) and all(c["value"] <= c["limit"]
                                    for c in checks.values())
    first, last = records[0]["before"], records[-1]["after"]
    plan_facts = _plan_facts(plans)
    ctx = {
        "chips": len(devices),
        "peaks": peaks,
        "setup_s": setup_s,
        "window_s": window_s,
        "queries": records,
        "rows_per_query": q.rows(tables),
        "facts": q.facts(tables),
        "plans": plan_facts,
        "compiles": {k: last[k] - first[k]
                     for k in ("plan_misses", "backend_compiles")},
        "governor_peak_bytes": governor_peak,
        "memory_peak_bytes": [m.get("peak_bytes_in_use") for m in mem],
        "trace": prof.get("summary"),
    }
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = m.reader.read(ctx)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.spec["unit"]}
    d0 = devices[0]
    # the runtime's peak_bytes_in_use leaves out a program's temporaries
    # on the TPU (PERF.md), so the fullest chip holds at least the larger
    # of it and the largest plan's compiled footprint
    peaks_in_use = [p for p in ctx["memory_peak_bytes"] if p is not None]
    peaks_in_use += [p["bytes_per_chip"] for p in plan_facts
                     if p["bytes_per_chip"] is not None]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(peaks_in_use, default=None)}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    summary = ctx["trace"]
    if trace:
        device["window_s"] = summary.window_s if summary else window_s
        device["busy_s"] = summary.busy_s if summary else None
        if summary is not None:
            result["breakdown"] = trace_mod.breakdown(summary)
    result["checks"] = checks
    return result


def _fail(msg: str) -> int:
    print(f"benchmark: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise: a fixed path
    # outside the checkout that two runs would share
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    try:
        cell = cells_mod.load_cell(args.workload)
        import jax

        import spark_rapids_jni_tpu
    except (ImportError, KeyError, FileNotFoundError) as e:
        return _fail(f"{type(e).__name__}: {e}")
    pkg = os.path.dirname(os.path.abspath(spark_rapids_jni_tpu.__file__))
    if os.path.dirname(pkg) != cells_mod.ROOT:
        return _fail(f"the program at {pkg} is not this checkout's")
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips; JAX found "
                     f"{len(devices)}")
    try:
        peaks = cells_mod.peaks_for(devices[0].device_kind)
    except KeyError as e:
        return _fail(str(e))
    from spark_rapids_jni_tpu import compile_cache

    compile_cache.enable()
    # every program the cell compiles, however quick, is found in the cache
    # by the next run, so set-up does the same work from run to run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell.chips], peaks, t_start)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
