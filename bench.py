"""Staged benchmarks vs a *measured* HBM roofline.

Covers BASELINE.md staged configs 1-4 (the reference's nvbench list,
benchmarks/CMakeLists.txt:72-85 maps to the same ops) plus the config-5
query-step core:

1. murmur3-32 over one INT32 column (headline metric)
2. string<->float casts (string_to_float / float_to_string)
3. JCUDF row conversion to/from rows (fixed-width)
4. bloom filter build+probe and decimal128 multiply
5. q97 two-table join-count core (models/q97.py, single-chip)

The roofline is measured on the same device with a saturating copy kernel
(read+write of a large f32 array); every config reports achieved bytes/s as
a fraction of it, answering "how far from the memory bound are we" without a
flattering nominal (round-1 feedback).  Host-orchestrated ops (string
parsing) additionally report wall-clock rows/s — their cost is real even
where the device is idle.

Prints ONE json line: the headline murmur3 metric, with every config and the
roofline under "detail".
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

NOMINAL_BASELINE_ROWS_PER_S = 1.0e9  # order-of-magnitude GPU figure, config 1


_TIMING_INFO = {}  # stage key -> raw two-point timing detail
_CURRENT_STAGE = [None]

# --profile: re-run each stage under an active SRTP capture and report the
# capture's wall-clock cost as a fraction of the stage (the recorder +
# profiler must stay cheap enough to leave always-on)
_PROFILE = [False]


def _time(fn, iters, *args):
    """Steady-state s/call via two-point marginal timing (obs/timing.py);
    the raw points are kept in ``_TIMING_INFO`` and surfaced under each
    stage's detail."""
    from spark_rapids_jni_tpu.obs.timing import time_marginal_for_iters

    dt, info = time_marginal_for_iters(lambda: fn(*args), iters)
    _TIMING_INFO[_CURRENT_STAGE[0]] = info
    return dt


def _stage(detail, key, fn, nbytes=0):
    """Run one benchmark stage; a failure fails the whole run.

    Every stage's working set is admitted through the memory governor via
    the canonical retry driver (mem/governed.py) — the bench runs governed,
    like any other consumer of the framework.  A bench stage is not
    splittable (it measures one fixed geometry), so a split signal becomes
    the stage's error entry."""
    from spark_rapids_jni_tpu.mem.governed import (
        default_device_budget,
        run_with_split_retry,
    )

    budget = default_device_budget()
    _CURRENT_STAGE[0] = key

    def _run_once():
        return run_with_split_retry(
            budget, None,
            nbytes_of=lambda _b: int(nbytes),
            run=lambda _b: fn(),
            split=lambda _b: [],
            combine=lambda rs: rs[0],
        )

    detail[key] = _run_once()
    info = _TIMING_INFO.pop(key, None)
    if info is not None and isinstance(detail[key], dict):
        detail[key]["timing"] = info
    if _PROFILE[0] and isinstance(detail[key], dict):
        detail[key]["profile"] = _measure_profile_overhead(_run_once, key)


def _measure_profile_overhead(run_once, key):
    """Capture overhead as a fraction of stage wall time.

    The stage already ran once (compiles warm), so two further wall-timed
    runs compare like for like: one plain, one inside Profiler.start()/
    stop() with the flight recorder mirroring STATE events into the
    capture.  Negative deltas (run-to-run noise) clamp to 0."""
    import time as _time

    from spark_rapids_jni_tpu.obs.profiler import Profiler

    t0 = _time.perf_counter()
    run_once()
    t_plain = _time.perf_counter() - t0
    Profiler.start()
    try:
        t0 = _time.perf_counter()
        run_once()
        t_prof = _time.perf_counter() - t0
    finally:
        Profiler.stop()
    _TIMING_INFO.pop(key, None)  # rerun timing detail is not the stage's
    frac = ((t_prof - t_plain) / t_plain) if t_plain > 0 else 0.0
    return {"plain_s": round(t_plain, 4), "profiled_s": round(t_prof, 4),
            "overhead_frac": round(max(0.0, frac), 4)}


def _recommend(detail: dict) -> dict:
    """Measured A/B winners -> config-flag recommendations (>=5% margin
    to flip away from a default; ties keep it).  Read by whoever consumes
    BENCH_r*.json: the r3 verdict's 'flip the default
    to the measured winner' step, made explicit in the output."""
    recs = {}

    def rate(stage):
        v = detail.get(stage)
        return v.get("Grows_per_s") if isinstance(v, dict) else None

    # `is not None`: a measured 0.0 (catastrophically slow backend) is
    # the clearest possible verdict, not a missing stage
    mm_x, mm_p = rate("murmur3_int32"), rate("murmur3_int32_pallas")
    if mm_x is not None and mm_p is not None:
        recs["hash_backend"] = "pallas" if mm_p > 1.05 * mm_x else "xla"
    pm, px = rate("partition_murmur3"), rate("partition_mix32")
    if pm is not None and px is not None:
        recs["partition_hash"] = "mix32" if px > 1.05 * pm else "murmur3"
    return recs


class _CountingSink:
    """Discard capture writer that keeps the byte count (the --profile
    capture's cost is measured in time; its size is reported for scale)."""

    def __init__(self):
        self.nbytes = 0

    def write(self, b):
        self.nbytes += len(b)


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description="staged benchmarks")
    ap.add_argument("--profile", action="store_true",
                    help="re-run each stage inside an SRTP capture and "
                         "report capture overhead per stage (must stay "
                         "under 5%% for the always-on recorder claim)")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py: needs a TPU; JAX found {dev.platform!r}")
    from spark_rapids_jni_tpu import compile_cache

    compile_cache.enable()

    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_jni_tpu.columnar import Column, INT64, INT32, FLOAT64
    from spark_rapids_jni_tpu.columnar.dtypes import DType, Kind
    from spark_rapids_jni_tpu.ops import (
        bloom_filter_create,
        bloom_filter_probe,
        bloom_filter_put,
        convert_from_rows_fixed_width_optimized,
        convert_to_rows_fixed_width_optimized,
        float_to_string,
        multiply128,
        murmur_hash32,
        string_to_float,
    )

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.mem.governor import MemoryGovernor

    detail = {}
    n = config.get("bench_rows")
    iters = config.get("bench_iters")
    rng = np.random.RandomState(42)

    # the bench is a governed tenant like any framework consumer: one
    # dedicated task thread, every stage's working set admitted through the
    # arbiter (_stage reserves nbytes before launching device work)
    gov = MemoryGovernor.initialize()
    gov.current_thread_is_dedicated_to_task(0)

    sink = None
    if args.profile:
        from spark_rapids_jni_tpu.obs.profiler import Profiler

        sink = _CountingSink()
        Profiler.init(sink)
        _PROFILE[0] = True

    # ---- measured HBM roofline (read + write of f32) ----------------------
    roofline_bytes_s = float("nan")

    def _roofline():
        nonlocal roofline_bytes_s
        big = jnp.asarray(rng.rand(max(n, 1 << 24)).astype(np.float32))
        copy = jax.jit(lambda x: x + 1.0)
        dt = _time(copy, iters, big)
        roofline_bytes_s = 2 * big.size * 4 / dt
        return round(roofline_bytes_s / 1e9, 1)

    _stage(detail, "hbm_roofline_GBps", _roofline,
           nbytes=max(n, 1 << 24) * 4 * 2)

    def _frac(bytes_per_s):
        # None (JSON null) when the roofline stage failed, never NaN
        if roofline_bytes_s != roofline_bytes_s:
            return None
        return round(bytes_per_s / roofline_bytes_s, 3)

    # ---- config 1: murmur3-32 on INT32 (XLA and Pallas A/B) ---------------
    mm_rows_s = 0.0

    _mm_cache = {}

    def _murmur(backend):
        nonlocal mm_rows_s
        if "data" not in _mm_cache:  # built under the first stage's budget
            _mm_cache["data"] = jnp.asarray(
                rng.randint(-(2**31), 2**31, size=n).astype(np.int32))
        data = _mm_cache["data"]
        with config.override(hash_backend=backend):
            hash_col = jax.jit(
                lambda d: murmur_hash32([Column(d, None, INT32)],
                                        seed=42).data)
            dt = _time(hash_col, iters, data)
        if backend == "xla":
            mm_rows_s = n / dt  # the headline metric stays the XLA path
        return {
            "Grows_per_s": round(n / dt / 1e9, 3),
            "roofline_frac": _frac((n / dt) * 8),
        }

    _stage(detail, "murmur3_int32", lambda: _murmur("xla"), nbytes=n * 8 * 2)
    _stage(detail, "murmur3_int32_pallas", lambda: _murmur("pallas"),
           nbytes=n * 8 * 2)
    _mm_cache.clear()  # the shared input must not outlive its stages

    ns_h = min(n, 1 << 20)

    _ms_cache = {}

    def _murmur_strings(backend):
        from spark_rapids_jni_tpu.columnar.column import strings_from_bytes

        if "col" not in _ms_cache:  # shared across the two backend stages
            rows = [b"k%08d-%s" % (i, b"x" * (i % 24)) for i in range(ns_h)]
            _ms_cache["col"] = strings_from_bytes(rows)
        scol = _ms_cache["col"]
        total_bytes = int(scol.offsets[-1])
        with config.override(hash_backend=backend):
            dt = _time(lambda: murmur_hash32([scol], seed=42).data,
                       max(iters // 4, 3))
        return {"Mrows_per_s": round(ns_h / dt / 1e6, 2),
                "GBps": round(total_bytes / dt / 1e9, 3),
                "roofline_frac": _frac(total_bytes / dt)}

    _stage(detail, "murmur3_strings", lambda: _murmur_strings("xla"),
           nbytes=ns_h * 40 * 3)
    _stage(detail, "murmur3_strings_pallas",
           lambda: _murmur_strings("pallas"), nbytes=ns_h * 40 * 3)
    _ms_cache.clear()

    # ---- internal shuffle-placement hash A/B (partition_hash flag) --------
    _ph_cache = {}

    def _partition_hash(backend):
        from spark_rapids_jni_tpu.ops.hashing import (
            murmur3_raw_int64,
            partition_mix32,
        )

        if "keys" not in _ph_cache:
            _ph_cache["keys"] = jnp.asarray(
                rng.randint(-(2**62), 2**62, size=n, dtype=np.int64))
        keys = _ph_cache["keys"]
        raw = (murmur3_raw_int64 if backend == "murmur3"
               else partition_mix32)
        fn = jax.jit(lambda d: (raw(d) % jnp.uint32(8)).astype(jnp.int32))
        # pin the murmur leg to XLA so the A/B compares the two MIXES on
        # one backend, not XLA-vs-whatever SRT_HASH_BACKEND selects
        with config.override(hash_backend="xla"):
            dt = _time(fn, iters, keys)
        return {"Grows_per_s": round(n / dt / 1e9, 3),
                "roofline_frac": _frac((n / dt) * 12)}

    _stage(detail, "partition_murmur3", lambda: _partition_hash("murmur3"),
           nbytes=n * 12 * 2)
    _stage(detail, "partition_mix32", lambda: _partition_hash("mix32"),
           nbytes=n * 12 * 2)
    _ph_cache.clear()

    # ---- config 2: string<->float -----------------------------------------
    ns = min(n, 1 << 20)  # host-orchestrated: smaller working set

    def _fcol():
        fvals = rng.rand(ns) * np.exp(rng.uniform(-30, 30, size=ns))
        return Column(jnp.asarray(fvals.view(np.int64)), None, FLOAT64)

    def _f2s():
        from spark_rapids_jni_tpu.ops.float_to_string import (
            PHASES as _f2s_phases,
        )

        fcol = _fcol()
        dt = _time(lambda c: float_to_string(c).chars, max(iters // 4, 3), fcol)
        # one instrumented call: attribute regressions to a pipeline stage
        _f2s_phases.reset()
        float_to_string(fcol).chars
        phases = {k: round(v, 3) for k, v in _f2s_phases.snapshot().items()}
        return {"Mrows_per_s": round(ns / dt / 1e6, 2), "phases_s": phases}

    def _s2f():
        from spark_rapids_jni_tpu.ops.cast_string_to_float import (
            PHASES as _s2f_phases,
        )

        scol = float_to_string(_fcol())
        dt = _time(
            lambda c: string_to_float(c, ansi_mode=False, dtype=FLOAT64).data,
            max(iters // 4, 3), scol)
        _s2f_phases.reset()
        string_to_float(scol, ansi_mode=False, dtype=FLOAT64).data
        phases = {k: round(v, 3) for k, v in _s2f_phases.snapshot().items()}
        return {"Mrows_per_s": round(ns / dt / 1e6, 2), "phases_s": phases}

    _stage(detail, "float_to_string", _f2s, nbytes=ns * 64)
    _stage(detail, "string_to_float", _s2f, nbytes=ns * 64)

    # ---- config 3: row conversion (fixed-width) ---------------------------
    nr = min(n, 1 << 22)

    def _cols():
        return [
            Column(jnp.asarray(
                rng.randint(-(2**31), 2**31, nr, dtype=np.int64)),
                None, INT64),
            Column(jnp.asarray(
                rng.randint(-(2**31), 2**31, nr).astype(np.int32)),
                None, INT32),
            Column(jnp.asarray(rng.rand(nr).view(np.int64)), None, FLOAT64),
        ]

    row_bytes = 8 + 4 + 8 + 4  # 8B-aligned JCUDF row incl. pad + validity

    def _rows_to():
        from spark_rapids_jni_tpu.ops.row_conversion import (
            PHASES as _rows_phases,
        )

        cols = _cols()
        dt = _time(lambda: convert_to_rows_fixed_width_optimized(cols),
                   max(iters // 4, 3))
        _rows_phases.reset()
        convert_to_rows_fixed_width_optimized(cols)
        phases = {k: round(v, 3)
                  for k, v in _rows_phases.snapshot().items()}
        return {
            "Mrows_per_s": round(nr / dt / 1e6, 2),
            "roofline_frac": _frac((nr / dt) * 2 * row_bytes),
            "phases_s": phases,
        }

    def _rows_from():
        from spark_rapids_jni_tpu.ops.row_conversion import (
            PHASES as _rows_phases,
        )

        rows_col = convert_to_rows_fixed_width_optimized(_cols())[0]
        dtypes = [INT64, INT32, FLOAT64]
        dt = _time(
            lambda: convert_from_rows_fixed_width_optimized(rows_col, dtypes),
            max(iters // 4, 3))
        _rows_phases.reset()
        convert_from_rows_fixed_width_optimized(rows_col, dtypes)
        phases = {k: round(v, 3)
                  for k, v in _rows_phases.snapshot().items()}
        return {
            "Mrows_per_s": round(nr / dt / 1e6, 2),
            "roofline_frac": _frac((nr / dt) * 2 * row_bytes),
            "phases_s": phases,
        }

    _stage(detail, "rows_to", _rows_to, nbytes=nr * row_bytes * 3)
    _stage(detail, "rows_from", _rows_from, nbytes=nr * row_bytes * 3)

    # ---- config 4: bloom filter build+probe, decimal128 multiply ----------
    def _bloom():
        keys = Column(jnp.asarray(rng.randint(0, 1 << 62, n, dtype=np.int64)),
                      None, INT64)
        bf0 = bloom_filter_create(3, 1 << 15)

        def build_and_probe(k):
            bf = bloom_filter_put(bf0, k)
            return bloom_filter_probe(k, bf).data

        dt = _time(build_and_probe, max(iters // 4, 3), keys)
        return {
            "Mrows_per_s": round(n / dt / 1e6, 2),
            "roofline_frac": _frac((n / dt) * 16),
        }

    _stage(detail, "bloom_build_probe", _bloom, nbytes=n * 16 * 2)

    from spark_rapids_jni_tpu.columnar.column import Decimal128Column

    nd = min(n, 1 << 22)

    def _dec():
        lo = rng.randint(0, 1 << 62, nd, dtype=np.uint64)
        hi = rng.randint(-(1 << 30), 1 << 30, nd, dtype=np.int64)
        d128 = DType(Kind.DECIMAL128, scale=2)
        a = Decimal128Column(jnp.asarray(hi), jnp.asarray(lo), None, d128)
        mul = jax.jit(lambda x_hi, x_lo: tuple(
            c.hi if hasattr(c, "hi") else c.data
            for c in multiply128(Decimal128Column(x_hi, x_lo, None, d128),
                                 Decimal128Column(x_hi, x_lo, None, d128), 2)))
        dt = _time(mul, max(iters // 8, 2), a.hi, a.lo)
        return {"Mrows_per_s": round(nd / dt / 1e6, 2)}

    _stage(detail, "decimal128_multiply", _dec, nbytes=nd * 16 * 4)

    # ---- config 5 direction: q97 query-step core --------------------------
    def _q97():
        from spark_rapids_jni_tpu.models import q97_local

        nq = min(n, 1 << 22)
        s_cust = jnp.asarray(rng.randint(1, 1 << 20, nq).astype(np.int32))
        s_item = jnp.asarray(rng.randint(1, 1 << 16, nq).astype(np.int32))
        c_cust = jnp.asarray(rng.randint(1, 1 << 20, nq).astype(np.int32))
        c_item = jnp.asarray(rng.randint(1, 1 << 16, nq).astype(np.int32))
        fn = jax.jit(lambda a, b, c, d: tuple(q97_local((a, b), (c, d))))
        dt = _time(fn, max(iters // 4, 3), s_cust, s_item, c_cust, c_item)
        return {"Mrows_per_s": round(2 * nq / dt / 1e6, 2)}

    _stage(detail, "q97_join_count", _q97,
           nbytes=min(n, 1 << 22) * 4 * 4 * 4)

    _json_cache = {}

    def _json_col():
        from spark_rapids_jni_tpu.columnar.column import strings_from_bytes

        if "col" not in _json_cache:
            nj = min(n, 1 << 18)
            rows = [
                b'{"store": {"fruit": [{"weight": %d, "type": "apple"}, '
                b'{"weight": %d}], "book": "b%d"}, "k%d": %d.5}'
                % (i % 9, i % 7, i % 100, i % 3, i)
                for i in range(nj)
            ]
            _json_cache["col"] = strings_from_bytes(rows)
            _json_cache["nj"] = nj
        return _json_cache["col"], _json_cache["nj"]

    def _json():
        from spark_rapids_jni_tpu.ops import get_json_object
        from spark_rapids_jni_tpu.ops.get_json_object import (
            phase_times,
            reset_phase_times,
        )

        jcol, nj = _json_col()
        total_bytes = int(jcol.offsets[-1])

        def run_path():
            return get_json_object(jcol, "$.store.fruit[*].weight").chars

        dt = _time(run_path, max(iters // 8, 2))
        # one extra instrumented call so regressions are attributable to a
        # pipeline stage (tokenize / evaluate / render), not just the total
        reset_phase_times()
        run_path()
        phases = {k: round(v, 3) for k, v in phase_times().items()}
        # rows_per_s too: at krows/s a 2-decimal Mrows/s reads as 0.0
        return {"Mrows_per_s": round(nj / dt / 1e6, 4),
                "rows_per_s": round(nj / dt, 1),
                "GBps": round(total_bytes / dt / 1e9, 3),
                "roofline_frac": _frac(total_bytes / dt),
                "phases_s": phases}

    _stage(detail, "get_json_object", _json,
           nbytes=min(n, 1 << 18) * 110 * 30)

    def _json_multi():
        from spark_rapids_jni_tpu.ops.get_json_object import (
            get_json_object_multiple_paths,
        )

        jcol, nj = _json_col()
        paths = ["$.store.fruit[*].weight", "$.store.book", "$.k0",
                 "$.store.fruit[0].type"]

        def run_multi():
            return tuple(
                c.chars for c in get_json_object_multiple_paths(jcol, paths))

        dt = _time(run_multi, max(iters // 8, 2))
        # rows_per_s counts source rows per call: compare against the
        # single-path stage to read the multi-path amortization (4 paths
        # should cost well under 4x one path)
        return {"Mrows_per_s": round(nj / dt / 1e6, 4),
                "rows_per_s": round(nj / dt, 1),
                "n_paths": len(paths),
                "s_per_call": round(dt, 3)}

    _stage(detail, "get_json_object_multi", _json_multi,
           nbytes=min(n, 1 << 18) * 110 * 30 * 2)
    _json_cache.clear()

    def _plan_cache_span():
        """Delta snapshot of the plan cache around a timed region: the
        per-stage ``phases_s`` (trace/compile/execute split) and
        ``plan_cache`` (hit/miss) sections — the compile-amortization
        story the trajectory point watches."""
        from spark_rapids_jni_tpu.plans import plan_cache

        before = plan_cache.stats()

        def close():
            after = plan_cache.stats()
            phases = {
                "trace": round(after["trace_s"] - before["trace_s"], 3),
                "compile": round(after["compile_s"] - before["compile_s"], 3),
                "execute": round(after["execute_s"] - before["execute_s"], 3),
            }
            cache = {"hits": int(after["hits"] - before["hits"]),
                     "misses": int(after["misses"] - before["misses"])}
            return phases, cache

        return close

    def _q5():
        from spark_rapids_jni_tpu.models import generate_q5_data, q5_local

        sf = min(1.0, max(0.05, n / (1 << 24)))
        data = generate_q5_data(sf=sf, seed=42)
        rows_total = sum(
            len(data.channels[c].sales_sk) + len(data.channels[c].ret_sk)
            for c in data.channels)
        span = _plan_cache_span()
        dt = _time(lambda: tuple(q5_local(data)), max(iters // 8, 2))
        phases, cache = span()
        return {"Mrows_per_s": round(rows_total / dt / 1e6, 2),
                "fact_rows": rows_total,
                "phases_s": phases, "plan_cache": cache}

    _stage(detail, "q5_rollup", _q5, nbytes=int(min(n, 1 << 22) * 8))

    def _q3():
        from spark_rapids_jni_tpu.models import generate_q3_data, q3_local

        sf = min(1.0, max(0.05, n / (1 << 24)))
        data = generate_q3_data(sf=sf, seed=42)
        rows_total = len(data.ss_item_sk)
        span = _plan_cache_span()
        dt = _time(lambda: tuple(q3_local(data)), max(iters // 8, 2))
        phases, cache = span()
        return {"Mrows_per_s": round(rows_total / dt / 1e6, 2),
                "fact_rows": rows_total,
                "phases_s": phases, "plan_cache": cache}

    _stage(detail, "q3_star_join", _q3, nbytes=int(min(n, 1 << 22) * 8))

    # ---- config 6: the order-sensitive tier (round 16) --------------------
    no = min(n, 1 << 20)

    def _sort_1m():
        from spark_rapids_jni_tpu.plans import ir as _ir
        from spark_rapids_jni_tpu.plans.ir import col
        from spark_rapids_jni_tpu.plans.runtime import run_governed_plan

        plan = _ir.Plan("bench_sort", (_ir.Sort(
            _ir.Scan("t", ("k", "sid")),
            keys=((col("k"), True), (col("sid"), True)),
            fields=("k", "sid")),))
        tables = {"t": {
            "k": rng.randint(-(2**62), 2**62, no).astype(np.int64),
            "sid": np.arange(no, dtype=np.int64)}}
        span = _plan_cache_span()
        dt = _time(lambda: int(run_governed_plan(None, plan, tables)
                               ["rows"]), max(iters // 8, 2))
        phases, cache = span()
        return {"Mrows_per_s": round(no / dt / 1e6, 2), "rows": no,
                "phases_s": phases, "plan_cache": cache}

    _stage(detail, "sort_1m", _sort_1m, nbytes=int(no * 16 * 3))

    def _window_rank():
        from spark_rapids_jni_tpu.models.q67 import (
            make_q67_tables,
            q67_plan,
        )
        from spark_rapids_jni_tpu.serve.shuffle import run_range_plan_local

        tables = make_q67_tables(no, 128, 16, seed=42)
        plan = q67_plan(10, 128)
        span = _plan_cache_span()
        dt = _time(lambda: int(run_range_plan_local(plan, tables)
                               ["rows"]), max(iters // 8, 2))
        phases, cache = span()
        return {"Mrows_per_s": round(no / dt / 1e6, 2), "rows": no,
                "phases_s": phases, "plan_cache": cache}

    _stage(detail, "window_rank", _window_rank, nbytes=int(no * 24 * 3))

    def _topk():
        from spark_rapids_jni_tpu.models.q67 import (
            naive_sort_limit_plan,
            topk_sales_plan,
        )
        from spark_rapids_jni_tpu.plans.compiler import (
            emit_range_partitions,
            split_exchange_plan,
        )
        from spark_rapids_jni_tpu.serve.shuffle import (
            range_split_n,
            run_range_plan_local,
        )

        k, nshards = 64, 4
        tables = {"store_sales": {
            "price": rng.randint(0, 1 << 40, no).astype(np.int64),
            "sid": np.arange(no, dtype=np.int64)}}
        plan = topk_sales_plan(k)
        dt = _time(lambda: int(run_range_plan_local(plan, tables)
                               ["rows"]), max(iters // 8, 2))

        def shuffle_bytes(p):
            # what would cross the wire on a 4-shard cluster: every map
            # shard's emitted range partitions, summed
            ex, _reduce = split_exchange_plan(p)
            total = 0
            for s in range_split_n(p, tables, nshards):
                for part in emit_range_partitions(
                        ex, s["tables"], nshards, s["splitters"]):
                    total += sum(v.nbytes for v in part.values())
            return total

        bp = shuffle_bytes(plan)
        bn = shuffle_bytes(naive_sort_limit_plan(k))
        return {"Mrows_per_s": round(no / dt / 1e6, 2), "rows": no,
                "k": k, "map_shards": nshards,
                "shuffle_bytes_pushdown": bp, "shuffle_bytes_naive": bn,
                "byte_reduction_x": round(bn / max(bp, 1), 1)}

    _stage(detail, "topk", _topk, nbytes=int(no * 16 * 3))

    # cumulative plan-cache gauges across every plan-compiled stage: a
    # second same-shape execution must be a hit (hits > 0, misses stable)
    from spark_rapids_jni_tpu.plans import plan_cache as _plan_cache

    detail["plan_cache"] = _plan_cache.stats()

    gov.task_done(0)
    MemoryGovernor.shutdown()

    recs = _recommend(detail)
    if recs:
        detail["recommendations"] = recs

    if args.profile:
        from spark_rapids_jni_tpu.obs.profiler import Profiler

        _PROFILE[0] = False
        Profiler.shutdown()
        fracs = {k: v["profile"]["overhead_frac"]
                 for k, v in detail.items()
                 if isinstance(v, dict) and "profile" in v}
        detail["profile_summary"] = {
            "capture_bytes": sink.nbytes,
            "stages": len(fracs),
            "max_overhead_frac": max(fracs.values()) if fracs else None,
            "max_overhead_stage": (max(fracs, key=fracs.get)
                                   if fracs else None),
        }

    measured = mm_rows_s > 0
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "metric": "murmur3_32_int32_throughput",
        "value": round(mm_rows_s / 1e9, 4) if measured else None,
        "unit": "Grows/s",
        "vs_baseline": (round(mm_rows_s / NOMINAL_BASELINE_ROWS_PER_S, 4)
                        if measured else None),
        "detail": detail,
    }))


if __name__ == "__main__":
    main()
