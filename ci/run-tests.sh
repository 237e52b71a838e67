#!/usr/bin/env bash
# CI test entry (premerge-build.sh analog): lint, unit suite on a virtual
# 8-device CPU mesh, arbiter fuzz (fuzz-test.sh analog), multichip dryrun.
# QUICK=1 runs the fast tier only (-m "not slow", no fuzz/dryrun) for
# inner-loop iteration; full CI always runs everything.
set -euo pipefail
cd "$(dirname "$0")/.."

python ci/lint.py
# protocol-aware static analysis: fails on any un-baselined finding
# (lock-order, unguarded-shared-state, retry-protocol, governed-allocation,
# seam-discipline, flight-discipline, guarded-by, wire-protocol incl. the
# frozen flight wire-id registry, state-machine, and — round 16, on the
# CFG layer — resource-lifecycle (every acquire reaches a release on all
# paths incl. exception edges) and blocking-under-lock (no blocking
# primitive while holding a lock) — docs/STATIC_ANALYSIS.md; per-rule
# docs + minimal failing examples via `python ci/analyze --explain <rule>`
if [[ "${QUICK:-0}" == "1" ]]; then
    # inner loop: the content-hash cache + changed-only report keep this
    # sub-second when the tree matches the last full gate run
    python ci/analyze --changed-only HEAD
    exec env JAX_PLATFORMS=cpu \
        python -m pytest tests/ -q -m "not slow"
fi
# full gate, with an asserted runtime budget: the analyze run must stay
# fast as the repo grows (cold, cache-less worst case included)
t0=$(date +%s)
python ci/analyze --no-cache
t1=$(date +%s)
if (( t1 - t0 > 60 )); then
    echo "analyze: full gate took $((t1 - t0))s, budget is 60s" >&2
    exit 1
fi
# ... and the content-hash cache must keep the unchanged-tree rerun
# sub-second (what the QUICK inner loop and pre-commit hooks rely on)
python ci/analyze > /dev/null   # warm the cache the --no-cache run skipped
python - <<'PY'
import subprocess, sys, time
# best-of-3: the budget pins the CACHE, not the box's load average
times = []
for _ in range(3):
    t0 = time.monotonic()
    subprocess.run([sys.executable, "ci/analyze"], check=True,
                   stdout=subprocess.DEVNULL)
    times.append(time.monotonic() - t0)
dt = min(times)
print(f"analyze: cached unchanged-tree rerun {dt:.2f}s (best of 3)")
assert dt < 1.0, f"cached rerun took {dt:.2f}s, budget is 1s"
PY

# One fresh interpreter per test file: XLA:CPU's JIT segfaults sporadically
# in long-lived processes that have compiled hundreds of modules (reproduced
# at test_parse_uri and test_get_json_object ~45 min in); per-file processes
# sidestep it, the same way the round-2 review ran the suite in chunks.
fail=0
for f in tests/test_*.py; do
    env JAX_PLATFORMS=cpu \
        python -m pytest "$f" -q || fail=1
done
[ "$fail" -eq 0 ]

env JAX_PLATFORMS=cpu \
    python -m spark_rapids_jni_tpu.mem.montecarlo \
    --tasks 16 --threads 8 --shuffle-threads 2 \
    --budget-mib 8 --task-max-mib 6 --allocs 40 --skewed --inject-pct 10 \
    --spill-buffers 6 --seed "${FUZZ_SEED:-0}"

# seeded pressure-storm chaos tier (round 9): 3 paired rounds under an
# identical injected-fault schedule and undersized budget — adaptive
# admission (serve/controller.py) must beat static config on median p99
# AND rejected-request count, with zero lost requests in every round
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --chaos-storm --clients 4 --requests 160 \
    --workers 2 --queue-size 8 --seed "${STORM_SEED:-7}"

# crash-only serving chaos tier (round 10): 4 supervised executor
# processes, seeded in-worker proc_kill faults SIGKILL executors
# mid-request — gates on zero lost requests, exactly-once lease
# completion, >= 2 kills with respawns, the degradation ladder stepping
# down AND recovering, bounded p99 inflation, and the per-process flight
# dumps merging into one cross-process timeline (flightdump --cluster).
# Round 14 adds --slo: the LIVE telemetry timeline must reconstruct
# complete multi-process span waterfalls for >= 95% of completed
# requests, and the seeded latency storm must drive an EV_SLO_BURN with
# a ladder reaction and a matching EV_SLO_OK recovery
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --cluster 4 --chaos-kill --slo --clients 8 \
    --requests 120 --workers 2 --queue-size 16 --seed "${KILL_SEED:-3}"

# crash-safe columnar shuffle tier (round 13): every request a q97
# Exchange plan run as a REAL cross-process shuffle over the framed
# peer-to-peer transport; the chaos round corrupts/truncates frames,
# stalls peers, and SIGKILLs executors mid-exchange — gates on zero lost
# + oracle-identical reduce outputs both rounds, >= 2 mid-shuffle kills
# recovered with respawns, checksum-detected corruption re-fetched,
# leases exactly-once, and bounded p99 inflation
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --cluster 3 --chaos-shuffle --clients 4 \
    --requests 24 --seed "${SHUFFLE_SEED:-11}"

# governed result-cache tier (round 15): paired cache-off/cache-on
# supervised rounds over an identical seeded Zipf lookup mix with
# mid-run table-version bumps, plus the governor-pressure phase — gates
# on zero lost + bit-identical both rounds (bit-identical == zero stale
# serves: content differs per version), hit ratio >= 0.6, cache-on
# >= 5x cache-off on throughput, invalidations reclaiming entries, and
# injected pressure demoting cache residency (HBM gauges shrink,
# EV_RCACHE_DEMOTE) without killing the live governed task
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --cache-storm --clients 16 \
    --requests 1280 --workers 2 --queue-size 64 \
    --seed "${CACHE_SEED:-7}"

# continuous ragged batching tier (round 12): paired (micro, ragged)
# rounds under identical seeded heterogeneous-row-count schedules plus a
# chaos pair (pressure storm) — gates on ragged winning median rows/s,
# strictly fewer plan-cache compiles per pair, oracle-identical results,
# and zero lost requests on both paths calm AND chaos
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --ragged-storm --clients 8 --requests 160 \
    --workers 2 --queue-size 32 --ragged-rounds 2 \
    --seed "${RAGGED_SEED:-5}"

# optimizer + adaptive-execution tier (round 19): three phases.
# (1) paired optimizer-off/on rounds over identical seeded query mixes
# (4 spellings of each logical query) — gates on bit-identical results
# vs the unrewritten oracle, zero lost, optimizer winning median p99,
# and canonicalization proving cross-query result-cache sharing
# (optimizer-on misses == one warm compile per logical query).
# (2) skewed Exchange round with adaptive reduce — measured partition
# bytes must change the reduce-side partition count/strategy at runtime
# (EV_ADAPT_EXCHANGE from merged flight dumps), oracle-identical.
# (3) hedge-under-chaos: seeded rare 2s stragglers + SIGKILL faults —
# speculative hedges must recover >= 1 straggler (hedge win) while
# kills re-dispatch, with exactly-once lease completion and zero lost
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --optimizer-storm --clients 4 \
    --requests 24 --workers 2 --queue-size 16 --cluster 3 \
    --seed "${OPT_SEED:-7}"

# tenant attribution tier (round 21): paired calm/chaos supervised
# rounds over a Zipf(1.2) tenant mix from a 10k id universe — gates on
# zero lost, the live endpoint's attribution section populated
# (dominant-share tenant ranking + capacity headroom), attributed
# compute >= 95% of worker-measured busy-ns, byte-seconds reconciling
# with the governor gauges within 5%, and the chaos round's
# SIGKILL+respawn leaving the reconciliation intact
env JAX_PLATFORMS=cpu \
    python tools/serve_bench.py --tenant-storm --clients 8 \
    --requests 96 --workers 2 --queue-size 64 \
    --seed "${TENANT_SEED:-13}"

# the sharded query path on a virtual 8-device CPU mesh (the chip run is
# `python chip_smoke.py --chips 4` through the chip tool)
env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "
from __graft_entry__ import dryrun_multichip
dryrun_multichip(8)
"
