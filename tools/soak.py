"""Long-lived-process soak: executor-shaped endurance evidence.

The CI suite dodges two environmental failure modes by running one
process per test file (XLA:CPU JIT segfaults in processes that compiled
hundreds of modules; persistent-cache loader crashes — ci/run-tests.sh,
tests/conftest.py).  But a real executor IS one long-lived process, so
the repo needs direct evidence of how THIS framework holds up over many
governed iterations in a single interpreter: memory stability, steady-
state iteration time, no compile-variant leak (round-3 verdict, weak #7).

One iteration = a governed distributed q97 + q5 + q3 on fresh data at
FIXED shapes (so steady state exercises the executor loop, not the
compiler) plus a hash + JSON op batch with fixed bucket geometry.  Emits
one JSON line per iteration (wall seconds, RSS, governed peak) and a
final summary line with linear RSS drift; any crash mid-soak leaves the
per-iteration lines as the evidence trail.

Run (CPU mesh):
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/soak.py --minutes 15 [-o SOAK.jsonl]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--minutes", type=float, default=15.0)
    ap.add_argument("--iters", type=int, default=0,
                    help="stop after N iterations instead of a deadline")
    ap.add_argument("-o", "--output", default="-")
    ap.add_argument("--vary", action="store_true",
                    help="draw batch sizes per iteration (pow2-lattice "
                         "workout: RSS must PLATEAU once the bounded "
                         "shape-variant set saturates, not grow linearly)")
    ap.add_argument("--profile", default="",
                    help="run with the profiler ON, writing the seam-range "
                         "trace to this path (profiler-on endurance)")
    ap.add_argument("--stream-every", type=int, default=0,
                    help="every N iters run a full streamed-q97 lifecycle "
                         "(spill -> governed buckets -> close)")
    args = ap.parse_args(argv)

    import numpy as np

    from spark_rapids_jni_tpu import columnar as c
    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models import (
        generate_q3_data,
        generate_q5_data,
        q3_local,
        q5_local,
        run_distributed_q3,
        run_distributed_q5,
        run_distributed_q97,
    )
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.ops import get_json_object, murmur_hash32
    from spark_rapids_jni_tpu.parallel import make_mesh

    out = sys.stdout if args.output == "-" else open(args.output, "w")

    def emit(rec):
        out.write(json.dumps(rec) + "\n")
        out.flush()

    import jax

    mesh = make_mesh((len(jax.devices()), 1))
    gov = MemoryGovernor.initialize()
    budget = BudgetedResource(gov, 4 << 30)
    if args.profile:
        from spark_rapids_jni_tpu.obs.profiler import Profiler

        Profiler.init(args.profile)
        Profiler.start()
    deadline = time.time() + args.minutes * 60
    n97_fixed = 4096  # fixed shapes: steady state must not recompile
    rss0 = None
    it = 0
    samples = []
    try:
        while True:
            it += 1
            rng = np.random.RandomState(it)
            t0 = time.perf_counter()

            if args.vary:
                # log-uniform batch sizes: the executor's real life — the
                # pow2 quantizers must bound the compile-variant set
                n97 = int(2 ** rng.uniform(10, 15))
                n_str = int(2 ** rng.uniform(7, 10))
            else:
                n97 = n97_fixed
                n_str = 512

            store = (rng.randint(1, 300, n97).astype(np.int32),
                     rng.randint(1, 500, n97).astype(np.int32))
            catalog = (rng.randint(1, 300, n97).astype(np.int32),
                       rng.randint(1, 500, n97).astype(np.int32))
            q97 = run_distributed_q97(mesh, store, catalog, budget=budget,
                                      task_id=it)
            got = (int(q97.store_only), int(q97.catalog_only), int(q97.both))
            if got != q97_host_oracle(store, catalog):
                emit({"iter": it, "error": "q97 mismatch", "got": got})
                return 1

            q5_sf = float(rng.uniform(0.001, 0.02)) if args.vary else 0.002
            q5d = generate_q5_data(sf=q5_sf, seed=it)
            if run_distributed_q5(mesh, q5d, budget=budget,
                                  task_id=it) != q5_local(q5d):
                emit({"iter": it, "error": "q5 mismatch"})
                return 1
            q3_sf = float(rng.uniform(0.005, 0.05)) if args.vary else 0.01
            q3d = generate_q3_data(sf=q3_sf, seed=it)
            if run_distributed_q3(mesh, q3d, budget=budget,
                                  task_id=it) != q3_local(q3d):
                emit({"iter": it, "error": "q3 mismatch"})
                return 1

            if args.stream_every and it % args.stream_every == 0:
                # full out-of-core lifecycle: spill files + governed
                # buckets + close; a leak here compounds per query
                import tempfile

                from spark_rapids_jni_tpu.models.streaming import (
                    generate_q97_chunks,
                    run_streaming_q97,
                )

                host_budget = BudgetedResource(gov, 1 << 28, is_cpu=True)
                with tempfile.TemporaryDirectory(prefix="soak_shuf_") as td:
                    _counts, s_ver, s_stats = run_streaming_q97(
                        mesh,
                        generate_q97_chunks(sf=0.0005, seed=it,
                                            chunk_rows=700),
                        tmpdir=td, n_buckets=4, budget=budget,
                        host_budget=host_budget, task_id=100000 + it,
                        verify=True)
                if s_ver is not True:
                    emit({"iter": it, "error": "streamed q97 mismatch"})
                    return 1
                if host_budget.used != 0:
                    emit({"iter": it, "error": "streamed host leak",
                          "used": host_budget.used})
                    return 1

            # op batch (64-byte bucket geometry; rows vary with --vary)
            scol = c.strings_from_bytes(
                [b"k%08d-%020d" % (rng.randint(1 << 30), i)
                 for i in range(n_str)])
            murmur_hash32([scol], seed=42).data.block_until_ready()
            jrows = [b'{"a": {"b": [%d, %d]}, "c": "x%d"}'
                     % (i, i * 7, rng.randint(99)) for i in range(256)]
            get_json_object(c.strings_from_bytes(jrows), "$.a.b[*]")

            # broader op families every 4th iter (string parse + URI):
            # same endurance contract, different kernels
            if it % 4 == 0:
                from spark_rapids_jni_tpu.ops import (
                    parse_uri_protocol,
                    string_to_float,
                )

                fcol = c.strings_from_bytes(
                    [b"%d.%04de%+03d" % (rng.randint(9999), i, i % 30 - 15)
                     for i in range(256)])
                string_to_float(fcol, ansi_mode=False,
                                dtype=c.FLOAT64).data.block_until_ready()
                ucol = c.strings_from_bytes(
                    [b"https://h%03d.example.com/p/%d?q=%d"
                     % (i, rng.randint(999), i) for i in range(256)])
                parse_uri_protocol(ucol)

            wall = time.perf_counter() - t0
            rss = _rss_mb()
            if rss0 is None:
                rss0 = rss
            peak = budget.reset_peak()
            samples.append((time.time(), rss, wall))
            emit({"iter": it, "wall_s": round(wall, 3),
                  "rss_mb": round(rss, 1),
                  "peak_reserved_mb": round(peak / 1e6, 2)})
            if args.iters and it >= args.iters:
                break
            if not args.iters and time.time() > deadline:
                break
    finally:
        if args.profile:
            from spark_rapids_jni_tpu.obs.profiler import Profiler

            Profiler.stop()
            Profiler.shutdown()
        MemoryGovernor.shutdown()

    def _drift(window):
        if len(window) < 2:
            return 0.0
        ts = np.array([s[0] for s in window])
        rs = np.array([s[1] for s in window])
        return float(np.polyfit(ts - ts[0], rs, 1)[0]) * 3600.0

    # linear RSS drift over the steady-state tail (drop warmup third),
    # plus the LAST-third window alone: with --vary, warmup includes the
    # whole pow2-lattice fill, so only the tail window shows whether RSS
    # plateaus (asymptotic) or keeps climbing (a real leak)
    tail = samples[len(samples) // 3:]
    tail_window = samples[2 * len(samples) // 3:]
    emit({"summary": True, "iters": it,
          "rss_start_mb": round(rss0 or 0, 1),
          "rss_end_mb": round(samples[-1][1], 1),
          "rss_drift_mb_per_h": round(_drift(tail), 2),
          "tail_window_drift_mb_per_h": round(_drift(tail_window), 2),
          "steady_wall_s": round(
              float(np.median([s[2] for s in tail])), 3) if tail else None})
    if out is not sys.stdout:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
