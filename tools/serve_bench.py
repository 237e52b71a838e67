"""Closed-loop load generator for the serving engine (BENCH_serve).

N client threads drive the engine closed-loop (each client waits for its
response — or a backpressure rejection — before submitting the next
request), over a mixed workload: governed distributed q97 queries plus
batchable hash ops, with a spread of session priorities and per-session
byte budgets.  On Backpressure a client honors the ``retry_after_s`` hint
and re-submits (bounded attempts), so the bench exercises the reject/retry
loop a real front end would run.

The zero-lost-requests invariant is the headline assertion: every logical
request ends in exactly one of {succeeded, rejected (backpressure, retries
exhausted), timed_out} — nothing hangs, nothing disappears.

Run (CPU mesh):
    env JAX_PLATFORMS=cpu \
        XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/serve_bench.py --clients 32 --requests 200

Prints ONE json line (name=BENCH_serve): p50/p99 queue-wait and run
latency, admitted/rejected/retried/timed-out counts, client-side outcome
tally, and wall-clock throughput.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from typing import Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def cluster_worker_factory(engine, bytes_per_row: int = 1024,
                           service_ms: float = 2.0) -> None:
    """Executor-side handler registration for ``--cluster`` mode —
    resolved by name inside each spawned worker process (serve/rpc.py)."""
    import numpy as np

    from spark_rapids_jni_tpu.serve import QueryHandler

    def storm_fn(p, ctx):
        time.sleep(service_ms / 1e3)  # a stable service-time floor
        return int(np.sum(p))

    engine.register(QueryHandler(
        name="storm", fn=storm_fn,
        nbytes_of=lambda p: bytes_per_row * len(p),
        split=lambda p: [p[:len(p) // 2], p[len(p) // 2:]],
        combine=lambda rs: int(sum(rs))))


def cache_worker_factory(engine, service_ms: float = 6.0,
                         bytes_per_row: int = 64) -> None:
    """Executor-side registration for ``--cache-storm``: a lookup-style
    query over a NAMED table whose content rides the payload.  The
    handler sleeps a stable service floor (the compute a cache hit
    skips) and returns the content sum — client-checkable, so any stale
    serve is a wrong answer.  Resolved by name in each worker process."""
    import numpy as np

    from spark_rapids_jni_tpu.plans.rcache import array_digest
    from spark_rapids_jni_tpu.serve import QueryHandler

    def fn(p, ctx):
        time.sleep(service_ms / 1e3)
        return int(np.sum(p["rows"]))

    engine.register(QueryHandler(
        name="lookup", fn=fn,
        nbytes_of=lambda p: bytes_per_row * len(p["rows"]),
        cache_key=lambda p: (p["table"],
                             array_digest(np.asarray(p["rows"]))),
        cache_tables=lambda p: (p["table"],)))


def shuffle_worker_factory(engine, capacity: int = 64) -> None:
    """Executor-side registration for ``--cluster --chaos-shuffle``: the
    q97 Exchange plan served as a real peer-to-peer shuffle piece
    (serve/shuffle.py).  Resolved by name inside each worker process."""
    from spark_rapids_jni_tpu.models.q97 import q97_plan
    from spark_rapids_jni_tpu.serve import QueryHandler
    from spark_rapids_jni_tpu.serve.shuffle import make_shuffle_handler

    engine.register(QueryHandler(
        name="q97_shuffle", fn=make_shuffle_handler(q97_plan(capacity)),
        nbytes_of=lambda p: 0))


def _shuffle_round(args, *, chaos: bool, dump_dir: str = "",
                   adaptive: bool = False, skew: bool = False) -> dict:
    """One supervised-cluster shuffle run: every request is a q97
    Exchange plan executed as a REAL cross-process shuffle (map shards on
    distinct executors, framed partition push/pull, reduce-side concat),
    each answer checked against the host oracle.  ``chaos`` arms the
    seeded data-plane storm (frame corruption, truncation, stalled
    peers) plus one-shot mid-exchange SIGKILLs per armed incarnation.
    ``adaptive`` arms the round-19 adaptive Exchange (over-partitioned
    map emit + measured-size reduce grouping) on every worker; ``skew``
    concentrates key mass so one partition runs hot — the shape the
    adaptive grouping exists to absorb."""
    import numpy as np

    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle, q97_plan
    from spark_rapids_jni_tpu.obs import flight as _flight
    from spark_rapids_jni_tpu.obs.faultinj import chaos_shuffle_config
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        Degraded,
        RequestTimeout,
        ShuffleSpec,
        Supervisor,
    )
    from spark_rapids_jni_tpu.serve.shuffle import (
        combine_exchange_outputs,
        scan_table_names,
        split_tables_n,
    )

    from spark_rapids_jni_tpu import config

    if dump_dir:
        config.set("flight_dump_dir", dump_dir)
        _flight.recorder().reset_for_tests()

    def chaos_fn(wid: int, inc: int):
        if not chaos:
            return None
        # incarnation-0 executors each die at most once, mid-exchange
        # (the kill rides the budget-reservation crossing the transport
        # credit and the reduce bracket both take); every incarnation
        # gets the transport weather
        return chaos_shuffle_config(
            seed=args.seed * 1000 + wid * 17 + inc,
            kill=(inc == 0), kill_pct=args.kill_pct,
            stall_ms=args.shuffle_stall_ms)

    worker_flags = {
        # stalls must trip the consumer's per-attempt I/O timeout (the
        # seeded-jitter backoff path), and a stalled fetch must give up
        # (re-dispatch) well before the hung-lease recycler fires
        "serve_shuffle_io_timeout_s": args.shuffle_io_timeout_s,
        "serve_shuffle_fetch_timeout_s": args.shuffle_fetch_timeout_s,
    }
    if dump_dir:
        worker_flags["flight_dump_dir"] = dump_dir
    if adaptive:
        worker_flags.update({
            "serve_adaptive_exchange": True,
            "serve_adaptive_overpartition": args.adaptive_overpartition,
            "serve_adaptive_part_bytes": args.adaptive_part_bytes,
        })
    plan = q97_plan(args.shuffle_capacity)
    scans = scan_table_names(plan)
    sup = Supervisor(
        workers=args.cluster,
        factory="serve_bench:shuffle_worker_factory",
        factory_kwargs={"capacity": args.shuffle_capacity},
        worker_cfg={"workers": max(4, args.workers),
                    "queue_size": max(32, args.queue_size)},
        worker_flags=worker_flags,
        chaos=chaos_fn,
        queue_size=args.queue_size,
        default_deadline_s=args.deadline_s,
        lease_hang_s=args.lease_hang_s,
        lease_max_dispatches=6,
        dump_on_exit=bool(dump_dir))
    sup.register(ShuffleSpec(
        "q97_shuffle",
        split_n=lambda p, n: split_tables_n(p, scans, n),
        combine=combine_exchange_outputs(plan),
        nbytes_of=lambda p: 0, fanout=args.cluster))

    # wait for live capacity so shards actually spread across executors
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        alive = sum(1 for w in sup.snapshot()["workers"].values()
                    if w["state"] == "alive")
        if alive >= args.cluster:
            break
        time.sleep(0.05)

    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "degraded_retries": 0, "wrong_answers": 0}
    latencies = []

    def client(ci: int) -> None:
        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = sup.open_session(
            f"shuffle{ci}", priority=1 if ci % 3 == 0 else 0)
        for ri in range(per_client):
            n = args.shuffle_rows
            if skew:
                # ~70% of key mass on a handful of customers: the hash
                # partitions covering them run hot, the rest are dust
                def keys(size):
                    hot = rng.randint(1, 4, size).astype(np.int32)
                    cold = rng.randint(1, 60, size).astype(np.int32)
                    return np.where(rng.random_sample(size) < 0.7,
                                    hot, cold).astype(np.int32)
            else:
                def keys(size):
                    return rng.randint(1, 60, size).astype(np.int32)
            store = (keys(n), rng.randint(1, 25, n).astype(np.int32))
            catalog = (keys(n), rng.randint(1, 25, n).astype(np.int32))
            payload = {"store": {"cust": store[0], "item": store[1]},
                       "catalog": {"cust": catalog[0],
                                   "item": catalog[1]}}
            want = q97_host_oracle(store, catalog)
            t0 = time.perf_counter()
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = sup.submit(sess, "q97_shuffle", payload)
                except Degraded as bp:
                    with lock:
                        tally["degraded_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.1))
                    continue
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 60)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    got = (int(out["store_only"]),
                           int(out["catalog_only"]), int(out["both"]))
                    if got != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            dt = time.perf_counter() - t0
            with lock:
                tally[outcome] += 1
                if outcome == "succeeded" and ri >= args.storm_warmup:
                    latencies.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sup.wait_drained(timeout=120)
    wall = time.perf_counter() - t0
    snap = sup.snapshot()
    if dump_dir:
        _flight.anomaly("cluster_epilogue", detail="supervisor")
    sup.shutdown()
    accounted = (tally["succeeded"] + tally["rejected"] + tally["timed_out"]
                 + tally["errors"])
    lat_ms = sorted(1e3 * x for x in latencies)
    pct = (lambda p: round(
        lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * p / 100))], 3)
        if lat_ms else 0.0)
    counters = snap["counters"]
    return {
        "chaos": chaos,
        "adaptive": adaptive,
        "requests": total,
        "wall_s": round(wall, 3),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["timed_out"] == 0
                      and tally["wrong_answers"] == 0),
        "oracle_identical": tally["wrong_answers"] == 0,
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "workers_dead": counters.get("workers_dead", 0),
        "respawns": counters.get("workers_spawned", 0) - args.cluster,
        "leases": snap["leases"],
        "shuffle_counters": {
            k: counters.get(k, 0)
            for k in ("shuffles_started", "shuffles_completed",
                      "shuffle_produced", "shuffle_acks",
                      "shuffle_revivals", "shuffle_stale_produces",
                      "leases_redispatched", "duplicate_results")},
        "counters": counters,
    }


def _run_chaos_shuffle(args) -> int:
    """``--cluster N --chaos-shuffle``: the crash-safe data-plane
    acceptance (round 13).  A calm round pins the latency baseline and
    proves cross-process reduce outputs bit-identical to the host
    oracle; the chaos round re-runs the identical workload while the
    seeded storm corrupts/truncates frames, stalls peers, and SIGKILLs
    executors mid-exchange.  Gates: zero lost + oracle-identical both
    rounds, >= 2 mid-shuffle kills recovered with respawns, checksum-
    detected corruption actually re-fetched (retry events with crc/
    truncated reasons AND verified fetches in the merged dumps), leases
    exactly-once, bounded p99 inflation."""
    import tempfile

    calm = _shuffle_round(args, chaos=False)
    dump_dir = args.dump_dir or tempfile.mkdtemp(prefix="srt_shuffle_")
    chaos = _shuffle_round(args, chaos=True, dump_dir=dump_dir)
    merged = _verify_shuffle_dumps(dump_dir)
    p99_bound = max(float(args.chaos_p99_bound_ms),
                    args.p99_inflation_factor * max(calm["p99_ms"], 1.0))
    gates = {
        "zero_lost": calm["zero_lost"] and chaos["zero_lost"],
        "oracle_identical": (calm["oracle_identical"]
                             and chaos["oracle_identical"]),
        "kills_recovered": (chaos["workers_dead"] >= 2
                            and chaos["respawns"] >= 2),
        "corruption_refetched": (merged["retry_integrity"] >= 1
                                 and merged["fetches"] >= 1),
        "leases_exactly_once": (
            chaos["leases"]["outstanding"] == 0
            and chaos["leases"]["completed"] == chaos["leases"]["leases"]),
        "p99_bounded": chaos["p99_ms"] <= p99_bound,
    }
    rec = {
        "name": "BENCH_serve",
        "mode": "chaos_shuffle",
        "seed": args.seed,
        "cluster": args.cluster,
        "clients": args.clients,
        "shuffle_rows": args.shuffle_rows,
        "calm": calm,
        "chaos": chaos,
        "p99_bound_ms": round(p99_bound, 3),
        "dump_dir": dump_dir,
        "shuffle_dumps": merged,
        "gates": gates,
        "zero_lost": gates["zero_lost"],
    }
    print(json.dumps(rec))
    return 0 if all(gates.values()) else 1


def _verify_shuffle_dumps(dump_dir: str) -> dict:
    """What the merged per-process dumps prove about the data plane:
    partition lineage (sid-keyed chains spanning processes), integrity
    retries (crc/truncated), stall retries, verified fetches, acks."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import flightdump

    merged = flightdump.merge_cluster(dump_dir)
    kinds = {}
    retry_integrity = retry_stall = 0
    for e in merged["events"]:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
        if e["kind"] == "shuffle_retry":
            reason = str(e.get("detail", "")).rsplit("reason:", 1)[-1]
            if reason in ("crc", "truncated"):
                retry_integrity += 1
            elif reason in ("stall", "eof"):
                retry_stall += 1
    return {
        "dumps": merged["dumps"],
        "pids": len(merged["pids"]),
        "sids": len(merged.get("sids", {})),
        "cross_process_sids": sum(
            1 for chain in merged.get("sids", {}).values()
            if len({e["pid"] for e in chain}) > 1),
        "produces": kinds.get("shuffle_produce", 0),
        "fetches": kinds.get("shuffle_fetch", 0),
        "acks": kinds.get("shuffle_ack", 0),
        "retries": kinds.get("shuffle_retry", 0),
        "retry_integrity": retry_integrity,
        "retry_stall": retry_stall,
        "worker_dead": kinds.get("worker_dead", 0),
        "redispatches": kinds.get("lease_redispatch", 0),
    }


def _cache_content(table: str, version: int, rows: int):
    """Deterministic content of (table, version): every process — and
    the client's expected-answer check — derives the same bytes, so a
    stale serve (old version's cached result for new content) is a
    WRONG ANSWER the tally catches, not a silent quality loss."""
    import zlib

    import numpy as np

    seed = zlib.crc32(f"{table}:{version}".encode()) % (2 ** 31 - 1)
    return np.random.RandomState(seed).randint(0, 1000, rows) \
        .astype(np.int64)


def _cache_round(args, *, cache_on: bool) -> dict:
    """One supervised-cluster round of the Zipf-skewed lookup mix with
    mid-run table-version bumps; ``cache_on`` toggles the result cache
    on an otherwise identical configuration and schedule."""
    import numpy as np

    from spark_rapids_jni_tpu.models import tables as _tables
    from spark_rapids_jni_tpu.plans.rcache import array_digest, result_cache
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        Degraded,
        HandlerSpec,
        RequestTimeout,
        Supervisor,
    )

    from spark_rapids_jni_tpu import config

    config.set("serve_result_cache", cache_on)
    result_cache.reset_for_tests()
    _tables.reset_for_tests()
    sup = Supervisor(
        workers=args.cache_cluster,
        factory="serve_bench:cache_worker_factory",
        factory_kwargs={"service_ms": args.cache_service_ms,
                        "bytes_per_row": 64},
        worker_cfg={"workers": args.workers,
                    "queue_size": max(32, args.queue_size)},
        worker_flags={"serve_result_cache": cache_on},
        queue_size=args.queue_size,
        default_deadline_s=args.deadline_s)
    sup.register(HandlerSpec(
        "lookup",
        nbytes_of=lambda p: 64 * len(p["rows"]),
        cacheable=True,
        cache_key=lambda p: (p["table"],
                             array_digest(np.asarray(p["rows"]))),
        cache_tables=lambda p: (p["table"],)))

    # both rounds measure serving, not process spawn: wait for the full
    # pool to say hello before the clock starts (shuffle-round twin)
    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        alive = sum(1 for w in sup.snapshot()["workers"].values()
                    if w["state"] == "alive")
        if alive >= args.cache_cluster:
            break
        time.sleep(0.05)

    ntables = args.cache_tables
    # Zipf-ish popularity: p_i ~ 1/(i+1)^s over a bounded table universe
    weights = 1.0 / np.power(np.arange(1, ntables + 1),
                             args.cache_zipf)
    probs = weights / weights.sum()
    versions = {f"t{i}": 0 for i in range(ntables)}
    vlock = threading.Lock()
    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    # client 0 bumps the HOTTEST table at fixed request indices: the
    # deterministic mid-run invalidation the zero-stale gate rides —
    # exactly --cache-bumps indices, evenly spread strictly inside the
    # run (an index at/past per_client would silently never fire)
    bump_every = max(1, per_client // (args.cache_bumps + 1))
    bump_points = {bump_every * (i + 1) for i in range(args.cache_bumps)
                   if bump_every * (i + 1) < per_client}
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "degraded_retries": 0,
             "wrong_answers": 0, "bumps": 0}
    latencies = []

    def client(ci: int) -> None:
        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = sup.open_session(
            f"cache{ci}", priority=1 if ci % 3 == 0 else 0)
        for ri in range(per_client):
            if ci == 0 and ri in bump_points:
                sup.bump_table("t0")  # invalidate FIRST, then publish
                with vlock:           # the new content to the clients
                    versions["t0"] += 1
                with lock:
                    tally["bumps"] += 1
            t = f"t{rng.choice(ntables, p=probs)}"
            with vlock:
                v = versions[t]
            rows = _cache_content(t, v, args.cache_rows)
            want = int(rows.sum())
            payload = {"table": t, "rows": rows}
            t0 = time.perf_counter()
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = sup.submit(sess, "lookup", payload)
                except Degraded as bp:
                    with lock:
                        tally["degraded_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.1))
                    continue
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if int(out) != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            dt = time.perf_counter() - t0
            with lock:
                tally[outcome] += 1
                if outcome == "succeeded" and ri >= args.storm_warmup:
                    latencies.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sup.wait_drained(timeout=60)
    wall = time.perf_counter() - t0
    snap = sup.snapshot()
    sup.shutdown()
    accounted = (tally["succeeded"] + tally["rejected"] + tally["timed_out"]
                 + tally["errors"])
    lat_ms = sorted(1e3 * x for x in latencies)
    pct = (lambda p: round(
        lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * p / 100))], 3)
        if lat_ms else 0.0)
    rc = snap.get("rcache") or {}
    return {
        "cache_on": cache_on,
        "requests": total,
        "wall_s": round(wall, 3),
        "req_per_s": round(total / wall, 2),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["timed_out"] == 0),
        "bit_identical": tally["wrong_answers"] == 0,
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "rcache": {k: rc.get(k, 0) for k in
                   ("lookups", "hits", "misses", "hit_ratio", "stores",
                    "invalidated", "stale_puts", "entries", "hbm_bytes",
                    "host_bytes", "disk_bytes")} if rc else None,
        "counters": {k: v for k, v in snap["counters"].items()
                     if k.startswith("rcache") or k in
                     ("submitted", "completed", "leases_granted")},
    }


def _cache_pressure_phase(args) -> dict:
    """The governance half of the cache-storm acceptance, in-process:
    fill the cache's HBM tier against a small governed budget, then run
    a live governed task whose working set does not fit beside the
    cache.  The budget's spill ladder must demote cached residency
    (EV_RCACHE_DEMOTE, gauges shrink) and the live task must complete —
    the cache yields under RetryOOM pressure, it never causes a kill."""
    import numpy as np

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.mem.governed import (
        attempt_once,
        task_context,
    )
    from spark_rapids_jni_tpu.models import tables as _tables
    from spark_rapids_jni_tpu.plans.rcache import request_key, result_cache

    from spark_rapids_jni_tpu import config

    config.set("serve_result_cache", True)
    result_cache.reset_for_tests()
    _tables.reset_for_tests()
    gov = MemoryGovernor(watchdog_period_s=0.02)
    budget = BudgetedResource(gov, 32 << 20)
    result_cache.bind_budget(budget)
    entry_rows = (1 << 20) // 8
    digests = {}
    for i in range(24):  # ~24 MB of cached results against a 32 MB budget
        key, deps = request_key("fill", f"k{i}", [])
        val = {"v": np.arange(entry_rows, dtype=np.int64) + i}
        result_cache.put(key, val, deps, label="fill")
        digests[i] = int(val["v"].sum())
    before = result_cache.stats()
    live_ok = False
    with task_context(gov, 1):
        out = attempt_once(
            gov, budget, None, lambda p: 24 << 20,
            lambda p: "served")
        live_ok = out == "served"
    after = result_cache.stats()
    # a post-demotion hit must still be bit-identical to what was stored
    intact = True
    for i in (0, 11, 23):
        key, _ = request_key("fill", f"k{i}", [])
        hit = result_cache.lookup(key)
        if hit is not None and int(hit["v"].sum()) != digests[i]:
            intact = False
    result_cache.reset_for_tests()
    gov.close()
    return {
        "budget_bytes": 32 << 20,
        "hbm_bytes_before": before["hbm_bytes"],
        "hbm_bytes_after": after["hbm_bytes"],
        "demotions": after["demotes_hbm_host"],
        "live_task_completed": live_ok,
        "post_demotion_bit_identical": intact,
        "cache_shrunk": after["hbm_bytes"] < before["hbm_bytes"],
    }


def _run_cache_storm(args) -> int:
    """``--cache-storm``: the governed result-cache acceptance (round
    15).  Paired cache-off/cache-on rounds over an identical seeded
    Zipf request mix with mid-run table-version bumps, plus the
    governor-pressure demotion phase.  Gates: zero lost + bit-identical
    (== zero stale serves — content differs across versions) both
    rounds, hit ratio over the floor, cache-on beating cache-off on
    throughput by the configured factor, invalidations actually
    reclaiming entries, and cache residency shrinking under governed
    pressure without killing the live task."""
    off = _cache_round(args, cache_on=False)
    on = _cache_round(args, cache_on=True)
    pressure = _cache_pressure_phase(args)
    speedup = on["req_per_s"] / max(off["req_per_s"], 1e-9)
    p50_x = off["p50_ms"] / max(on["p50_ms"], 1e-3)
    rc = on["rcache"] or {}
    gates = {
        "zero_lost": off["zero_lost"] and on["zero_lost"],
        "bit_identical": off["bit_identical"] and on["bit_identical"],
        "no_stale_serves": (on["bit_identical"]
                            and on["outcomes"]["bumps"] >= 1),
        "hit_ratio": rc.get("hit_ratio", 0.0) >= args.cache_hit_floor,
        "throughput_speedup": speedup >= args.cache_speedup_min,
        "invalidation_reclaims": rc.get("invalidated", 0) >= 1,
        "pressure_demotes_cache": (pressure["cache_shrunk"]
                                   and pressure["demotions"] >= 1
                                   and pressure["live_task_completed"]
                                   and pressure[
                                       "post_demotion_bit_identical"]),
    }
    rec = {
        "name": "BENCH_serve",
        "mode": "cache_storm",
        "seed": args.seed,
        "cluster": args.cache_cluster,
        "clients": args.clients,
        "storm": {"tables": args.cache_tables, "zipf": args.cache_zipf,
                  "rows": args.cache_rows,
                  "service_ms": args.cache_service_ms,
                  "bumps": args.cache_bumps},
        "off": off,
        "on": on,
        "pressure": pressure,
        "comparison": {
            "req_per_s_off": off["req_per_s"],
            "req_per_s_on": on["req_per_s"],
            "speedup": round(speedup, 2),
            "p50_ms_off": off["p50_ms"],
            "p50_ms_on": on["p50_ms"],
            "p50_improvement": round(p50_x, 2),
            "hit_ratio": rc.get("hit_ratio", 0.0),
        },
        "gates": gates,
        "zero_lost": gates["zero_lost"],
    }
    print(json.dumps(rec))
    return 0 if all(gates.values()) else 1


def _cluster_round(args, *, chaos: bool, dump_dir: str = "") -> dict:
    """One supervised-cluster run: N executor processes under the
    router/supervisor, closed-loop clients, optional seeded executor
    chaos (in-worker proc_kill + slow faults).  Returns client outcomes,
    latency percentiles, and the supervisor's lease/ladder evidence."""
    import numpy as np

    from spark_rapids_jni_tpu.obs import flight as _flight
    from spark_rapids_jni_tpu.obs.faultinj import chaos_kill_config
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        HandlerSpec,
        RequestTimeout,
        Supervisor,
    )

    from spark_rapids_jni_tpu import config

    if dump_dir:
        config.set("flight_dump_dir", dump_dir)
        # fresh incident window: this round's dump must not interleave a
        # previous round's rids (task ids restart per supervisor)
        _flight.recorder().reset_for_tests()

    def chaos_fn(wid: int, inc: int):
        if not chaos:
            return None
        # incarnation 0 executors are armed to die (at most once each, at
        # a seeded crossing); respawned incarnations only get the slow
        # weather — the kill count is bounded by the original pool size
        return chaos_kill_config(
            seed=args.seed * 1000 + wid * 17 + inc,
            kill=(inc == 0), kill_pct=args.kill_pct)

    worker_flags = {}
    if dump_dir:
        worker_flags["flight_dump_dir"] = dump_dir
    # the SLO storm half of the round-14 acceptance: a tight latency
    # objective armed for the CHAOS round only — the seeded slow faults
    # and kill-driven redispatch latencies burn it (EV_SLO_BURN -> ladder
    # reaction), and the post-drain quiet recovers it (EV_SLO_OK).  Short
    # windows so a CI-sized round spans them.
    slos = None
    slo_opts = None
    if chaos and args.slo:
        from spark_rapids_jni_tpu.serve.slo import SLO

        slos = [SLO(name="storm", handler="*",
                    p99_ms=args.slo_p99_ms)]
        # windows sized to a CI round: the kill storm spans a few
        # seconds, so the evaluation must see it before the traffic
        # drains (production windows are minutes — serve_slo_config)
        slo_opts = {"fast_window_s": 0.75, "slow_window_s": 2.5,
                    "min_samples": 4}
    sup = Supervisor(
        workers=args.cluster,
        factory="serve_bench:cluster_worker_factory",
        factory_kwargs={"bytes_per_row": args.storm_bytes_per_row,
                        "service_ms": args.cluster_service_ms},
        worker_cfg={"workers": args.workers,
                    "queue_size": max(32, args.queue_size)},
        worker_flags=worker_flags,
        chaos=chaos_fn,
        queue_size=args.queue_size,
        default_deadline_s=args.deadline_s,
        lease_hang_s=args.lease_hang_s,
        slos=slos, slo_opts=slo_opts,
        dump_on_exit=bool(dump_dir))
    sup.register(HandlerSpec(
        "storm",
        nbytes_of=lambda p: args.storm_bytes_per_row * len(p),
        split=lambda p: [p[:len(p) // 2], p[len(p) // 2:]],
        combine=lambda rs: int(sum(rs))))

    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "degraded_retries": 0, "wrong_answers": 0}
    latencies = []

    def client(ci: int) -> None:
        from spark_rapids_jni_tpu.serve import Degraded

        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = sup.open_session(
            f"cluster{ci}", priority=1 if ci % 3 == 0 else 0)
        for ri in range(per_client):
            payload = rng.randint(0, 1000, args.storm_rows).astype(np.int64)
            want = int(payload.sum())
            t0 = time.perf_counter()
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = sup.submit(sess, "storm", payload)
                except Degraded as bp:
                    with lock:
                        tally["degraded_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.1))
                    continue
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if out != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            dt = time.perf_counter() - t0
            with lock:
                tally[outcome] += 1
                if outcome == "succeeded" and ri >= args.storm_warmup:
                    latencies.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sup.wait_drained(timeout=60)
    # give the ladder time to walk back to healthy (the recovery half of
    # the acceptance: transitions down AND back up)
    recover_deadline = time.perf_counter() + 20
    while (sup.level() != 0 and time.perf_counter() < recover_deadline):
        time.sleep(0.1)
    wall = time.perf_counter() - t0
    snap = sup.snapshot()
    # the live-plane half of the round-14 acceptance: BEFORE shutdown,
    # read the cluster timeline off the telemetry endpoint (exactly what
    # `flightdump --live` would) and measure span-waterfall completeness
    # over the requests that completed OK
    live = _verify_live_timeline(sup)
    if dump_dir:
        _flight.anomaly("cluster_epilogue", detail="supervisor")
    sup.shutdown()
    accounted = (tally["succeeded"] + tally["rejected"] + tally["timed_out"]
                 + tally["errors"])
    lat_ms = sorted(1e3 * x for x in latencies)
    pct = (lambda p: round(
        lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * p / 100))], 3)
        if lat_ms else 0.0)
    counters = snap["counters"]
    return {
        "chaos": chaos,
        "requests": total,
        "wall_s": round(wall, 3),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["wrong_answers"] == 0),
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "workers_dead": counters.get("workers_dead", 0),
        "respawns": counters.get("workers_spawned", 0) - args.cluster,
        "leases": snap["leases"],
        "duplicate_results": counters.get("duplicate_results", 0),
        "ladder": snap["ladder"],
        "final_level": snap["ladder"]["level"],
        "counters": counters,
        "live": live,
    }


def _verify_live_timeline(sup) -> dict:
    """Fetch the live timeline from a still-running supervisor and
    summarize span-waterfall completeness + SLO evidence: the
    `flightdump --live`-sourced reconstruction the acceptance gates on."""
    from spark_rapids_jni_tpu.obs import trace as _trace
    from spark_rapids_jni_tpu.serve.telemetry import fetch_view

    ep = sup.telemetry_endpoint()
    if ep is None:
        return {"enabled": False}
    try:
        view = fetch_view(*ep)
    except (OSError, ValueError) as e:
        return {"enabled": True, "error": repr(e)[:200]}
    if "timeline" not in view:
        # the endpoint answers a failing view builder IN-BAND (a
        # mid-respawn gauge race): report it as a failed gate input,
        # never crash the bench round
        return {"enabled": True,
                "error": str(view.get("error", "no timeline in view"))}
    events = view["timeline"]["events"]
    rids = view["timeline"]["rids"]
    falls = _trace.waterfall(events)
    done_ok = {r for r, chain in rids.items()
               if any(e["kind"] == "lease_done"
                      and str(e.get("detail", "")).endswith(":ok")
                      for e in chain)}
    complete_multi = 0
    incomplete = []
    for r in done_ok:
        rec = falls.get(r)
        if (rec is not None and rec["complete"]
                and len(rec["pids"]) >= 2):
            complete_multi += 1
        else:
            incomplete.append({
                "rid": r,
                "spans": [(s["kind"], bool(s["closed"]), s.get("pid"))
                          for s in (rec["spans"] if rec else [])],
            })
    kinds = {}
    for e in events:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    return {
        "enabled": True,
        "endpoint": list(ep),
        "events": len(events),
        "pids": len(view["timeline"]["pids"]),
        "rids_done_ok": len(done_ok),
        "waterfalls_complete_multi_pid": complete_multi,
        "waterfall_frac": round(complete_multi / max(1, len(done_ok)), 4),
        "incomplete_rids": incomplete[:8],
        "span_opens": kinds.get("span_open", 0),
        "span_closes": kinds.get("span_close", 0),
        "slo_burn_events": kinds.get("slo_burn", 0),
        "slo_ok_events": kinds.get("slo_ok", 0),
        "telemetry_stats": view.get("timeline_stats"),
        "slo": view.get("slo"),
    }


def _run_cluster(args) -> int:
    """``--cluster N [--chaos-kill]``: the crash-only serving acceptance.

    A calm round establishes the latency baseline, then (with
    ``--chaos-kill``) an identically-configured round runs while seeded
    in-worker faults SIGKILL executors mid-request.  Gates: zero lost
    requests, every lease completed exactly once, >= 2 executor kills
    with respawns, the degradation ladder stepping down AND back to
    healthy, p99 inflation bounded, and the per-process flight dumps
    merging into one cross-process timeline (flightdump --cluster)."""
    import tempfile

    calm = _cluster_round(args, chaos=False)
    rec = {
        "name": "BENCH_serve",
        "mode": "cluster_chaos" if args.chaos_kill else "cluster",
        "seed": args.seed,
        "cluster": args.cluster,
        "clients": args.clients,
        "workers_per_executor": args.workers,
        "queue_size": args.queue_size,
        "calm": calm,
    }
    if not args.chaos_kill:
        rec["zero_lost"] = calm["zero_lost"]
        print(json.dumps(rec))
        return 0 if calm["zero_lost"] else 1

    dump_dir = args.dump_dir or tempfile.mkdtemp(prefix="srt_cluster_")
    chaos = _cluster_round(args, chaos=True, dump_dir=dump_dir)
    merged = _verify_cluster_dumps(dump_dir)
    p99_bound = max(float(args.chaos_p99_bound_ms),
                    args.p99_inflation_factor * max(calm["p99_ms"], 1.0))
    gates = {
        "zero_lost": calm["zero_lost"] and chaos["zero_lost"],
        "kills_with_respawns": (chaos["workers_dead"] >= 2
                                and chaos["respawns"] >= 2),
        "leases_exactly_once": (
            chaos["leases"]["outstanding"] == 0
            and chaos["leases"]["completed"] == chaos["leases"]["leases"]),
        "ladder_down_and_up": (
            chaos["ladder"]["max_level_seen"] >= 1
            and chaos["final_level"] == 0),
        "p99_bounded": chaos["p99_ms"] <= p99_bound,
        "dumps_reconstruct": (merged["degrade_enter"] >= 1
                              and merged["degrade_exit"] >= 1
                              and merged["rids_done"] >= 1),
    }
    live = chaos.get("live") or {}
    if live.get("enabled"):
        # round 14: the LIVE timeline (telemetry endpoint, no dumps)
        # must reconstruct complete queue -> dispatch -> compute span
        # waterfalls spanning >= 2 pids for >= 95% of the requests that
        # completed OK — under the chaos-kill profile
        gates["live_spans_reconstruct"] = (
            live.get("rids_done_ok", 0) >= 1
            and live.get("waterfall_frac", 0.0) >= 0.95)
    if args.slo:
        # the seeded latency storm must drive a burn the ladder reacts
        # to, and the post-drain quiet must produce the matching
        # recovery — both ledger-visible (EV_SLO_BURN / EV_SLO_OK in the
        # live timeline, the ladder transitions in the supervisor ledger)
        gates["slo_burn_and_recover"] = (
            live.get("slo_burn_events", 0) >= 1
            and live.get("slo_ok_events", 0) >= 1
            and chaos["ladder"]["max_level_seen"] >= 1)
    rec.update({
        "chaos": chaos,
        "p99_bound_ms": round(p99_bound, 3),
        "p99_inflation": round(
            chaos["p99_ms"] / max(calm["p99_ms"], 1e-3), 2),
        "dump_dir": dump_dir,
        "cluster_dumps": merged,
        "gates": gates,
        "zero_lost": gates["zero_lost"],
    })
    print(json.dumps(rec))
    return 0 if all(gates.values()) else 1


def _verify_cluster_dumps(dump_dir: str) -> dict:
    """Merge the per-process flight dumps and summarize what the
    --cluster reconstruction can prove about the run."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import flightdump

    merged = flightdump.merge_cluster(dump_dir)
    kinds = {}
    for e in merged["events"]:
        kinds[e["kind"]] = kinds.get(e["kind"], 0) + 1
    rids_done = sum(1 for r in merged["rids"].values()
                    if any(e["kind"] == "lease_done"
                           and e["detail"].endswith(":ok")
                           for e in r))
    return {
        "dumps": merged["dumps"],
        "pids": len(merged["pids"]),
        "events": len(merged["events"]),
        "rids": len(merged["rids"]),
        "rids_done": rids_done,
        "degrade_enter": kinds.get("degrade_enter", 0),
        "degrade_exit": kinds.get("degrade_exit", 0),
        "worker_dead": kinds.get("worker_dead", 0),
        "redispatches": kinds.get("lease_redispatch", 0),
    }


def _fetch_attribution(sup) -> Optional[dict]:
    """Read the attribution section off the live telemetry endpoint
    (exactly what `servetop --json` / capacity_report would see)."""
    from spark_rapids_jni_tpu.serve.telemetry import fetch_view

    ep = sup.telemetry_endpoint()
    if ep is None:
        return None
    try:
        view = fetch_view(*ep)
    except (OSError, ValueError):
        return None
    return view.get("attribution")


def _tenant_round(args, *, chaos: bool) -> dict:
    """One attribution-plane round: the supervised-cluster storm profile
    with every request labeled by a Zipf-drawn tenant over a >= 10k id
    space.  After drain, the live endpoint's attribution section is
    polled until the telemetry deltas settle, then reconciled against
    the worker-measured gauges: attributed compute vs busy-ns coverage
    and attributed byte-seconds vs the governor's metered byte-ns."""
    import numpy as np

    from spark_rapids_jni_tpu.obs.faultinj import chaos_kill_config
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        HandlerSpec,
        RequestTimeout,
        Supervisor,
    )

    def chaos_fn(wid: int, inc: int):
        if not chaos:
            return None
        # same arming discipline as _cluster_round: incarnation 0 dies
        # at most once at a seeded crossing; respawns run clean, so the
        # reconciliation gate spans a real SIGKILL + gauge re-high-water
        return chaos_kill_config(
            seed=args.seed * 1000 + wid * 17 + inc,
            kill=(inc == 0), kill_pct=args.kill_pct)

    sup = Supervisor(
        workers=args.cluster,
        factory="serve_bench:cluster_worker_factory",
        factory_kwargs={"bytes_per_row": args.storm_bytes_per_row,
                        "service_ms": args.cluster_service_ms},
        worker_cfg={"workers": args.workers,
                    "queue_size": max(32, args.queue_size)},
        chaos=chaos_fn,
        queue_size=args.queue_size,
        default_deadline_s=args.deadline_s,
        lease_hang_s=args.lease_hang_s)
    sup.register(HandlerSpec(
        "storm",
        nbytes_of=lambda p: args.storm_bytes_per_row * len(p),
        split=lambda p: [p[:len(p) // 2], p[len(p) // 2:]],
        combine=lambda rs: int(sum(rs))))

    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "degraded_retries": 0,
             "wrong_answers": 0}
    tenant_counts: dict = {}

    def client(ci: int) -> None:
        from spark_rapids_jni_tpu.serve import Degraded

        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = sup.open_session(
            f"tenantc{ci}", priority=1 if ci % 3 == 0 else 0)
        for _ri in range(per_client):
            # head-heavy Zipf tenant draw folded into the id universe:
            # the modulo keeps the unbounded tail inside --tenant-space
            # without flattening the hot head (rank 1 stays rank 1)
            tid = (int(rng.zipf(args.tenant_zipf)) - 1) % args.tenant_space
            tenant = f"t{tid}"
            with lock:
                tenant_counts[tenant] = tenant_counts.get(tenant, 0) + 1
            payload = rng.randint(0, 1000, args.storm_rows).astype(np.int64)
            want = int(payload.sum())
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = sup.submit(sess, "storm", payload,
                                      tenant=tenant)
                except Degraded as bp:
                    with lock:
                        tally["degraded_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.1))
                    continue
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if out != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            with lock:
                tally[outcome] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sup.wait_drained(timeout=60)
    recover_deadline = time.perf_counter() + 20
    while (sup.level() != 0 and time.perf_counter() < recover_deadline):
        time.sleep(0.1)
    wall = time.perf_counter() - t0

    # settle loop: the last EV_ATTRIB deltas and gauge high-waters ride
    # the workers' periodic MSG_TELEMETRY flush, so poll the endpoint
    # until the reconciliation holds (or a bounded deadline passes) and
    # gate on the final read
    attrib = _fetch_attribution(sup)
    settle_deadline = time.perf_counter() + 12
    while time.perf_counter() < settle_deadline:
        if attrib and _attrib_reconciles(attrib):
            break
        time.sleep(0.4)
        attrib = _fetch_attribution(sup) or attrib
    snap = sup.snapshot()
    sup.shutdown()

    accounted = (tally["succeeded"] + tally["rejected"]
                 + tally["timed_out"] + tally["errors"])
    at = attrib or {}
    measured = at.get("measured") or {}
    cluster_at = at.get("cluster") or {}
    counters = snap["counters"]
    mgbs = measured.get("gov_byte_ns", 0)
    return {
        "chaos": chaos,
        "requests": total,
        "wall_s": round(wall, 3),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["wrong_answers"] == 0),
        "workers_dead": counters.get("workers_dead", 0),
        "respawns": counters.get("workers_spawned", 0) - args.cluster,
        "distinct_tenants_submitted": len(tenant_counts),
        "hottest_tenant_requests": max(tenant_counts.values(), default=0),
        "attribution": {
            "present": bool(at),
            "events": at.get("events", 0),
            "unparsed": at.get("unparsed", 0),
            "requests": at.get("requests", 0),
            "tenants_tracked": at.get("tenants_tracked", 0),
            "top_tenants": [
                {k: t.get(k) for k in ("tenant", "dominant_share",
                                       "dominant_resource", "requests")}
                for t in (at.get("tenants") or [])[:5]],
            "coverage_comp": at.get("coverage_comp"),
            "attributed_gbs": cluster_at.get("gbs", 0),
            "measured_gov_byte_ns": mgbs,
            "gbs_ratio": (round(cluster_at.get("gbs", 0) / mgbs, 4)
                          if mgbs else None),
            "measured_busy_ns": measured.get("busy_ns", 0),
            "ring_dropped": measured.get("ring_dropped", 0),
            "headroom": at.get("headroom"),
            "utilization": at.get("utilization"),
            "capacity": at.get("capacity"),
        },
    }


def _attrib_reconciles(at: dict) -> bool:
    """The round-21 reconciliation predicate: attributed compute covers
    >= 95% of worker-measured busy-ns AND attributed byte-seconds land
    within 5% of the governor's metered byte-ns."""
    cov = at.get("coverage_comp")
    measured = at.get("measured") or {}
    mgbs = measured.get("gov_byte_ns", 0)
    agbs = (at.get("cluster") or {}).get("gbs", 0)
    if cov is None or not mgbs:
        return False
    return cov >= 0.95 and abs(agbs - mgbs) <= 0.05 * mgbs


def _run_tenant_storm(args) -> int:
    """``--tenant-storm``: the round-21 attribution acceptance.

    Paired calm/chaos supervised-cluster rounds (2 executors by
    default) over a Zipf(1.2) tenant mix drawn from a >= 10k id space.
    Gates, per round: zero lost requests, the endpoint's attribution
    section populated (tenants ranked by dominant share, capacity
    headroom computed), per-rid attributed compute >= 95% of the
    worker-measured busy-ns, and attributed byte-seconds reconciling
    with the governor gauges within 5%.  The chaos round additionally
    requires >= 1 SIGKILL with respawn — completed work's attribution
    must survive executor death exactly like spans do."""
    if args.cluster <= 0:
        args.cluster = 2

    calm = _tenant_round(args, chaos=False)
    chaos = _tenant_round(args, chaos=True)

    def round_gates(r: dict) -> dict:
        at = r["attribution"]
        return {
            "zero_lost": r["zero_lost"],
            "attribution_present": at["present"] and at["events"] > 0,
            "tenants_ranked": (
                at["tenants_tracked"] >= 1
                and bool(at["top_tenants"])
                and at["top_tenants"][0]["dominant_share"] > 0),
            "headroom_computed": (
                (at["headroom"] or {}).get("comp_ns") is not None
                and (at["headroom"] or {}).get("gbs") is not None),
            "comp_coverage_95": (at["coverage_comp"] is not None
                                 and at["coverage_comp"] >= 0.95),
            "gbs_within_5pct": (at["gbs_ratio"] is not None
                                and abs(1.0 - at["gbs_ratio"]) <= 0.05),
            "no_unparsed": at["unparsed"] == 0,
        }

    gates = {f"calm_{k}": v for k, v in round_gates(calm).items()}
    gates.update({f"chaos_{k}": v for k, v in round_gates(chaos).items()})
    gates["chaos_kills_with_respawns"] = (chaos["workers_dead"] >= 1
                                          and chaos["respawns"] >= 1)
    gates["zipf_head_hot"] = (
        calm["distinct_tenants_submitted"] >= 2
        and calm["hottest_tenant_requests"]
        > calm["requests"] // max(1, calm["distinct_tenants_submitted"]))
    rec = {
        "name": "BENCH_serve",
        "mode": "tenant_storm",
        "seed": args.seed,
        "cluster": args.cluster,
        "clients": args.clients,
        "workers_per_executor": args.workers,
        "tenant_space": args.tenant_space,
        "tenant_zipf": args.tenant_zipf,
        "calm": calm,
        "chaos": chaos,
        "gates": gates,
        "zero_lost": calm["zero_lost"] and chaos["zero_lost"],
    }
    print(json.dumps(rec))
    return 0 if all(gates.values()) else 1


def _ragged_round(args, *, ragged: bool, chaos: bool) -> dict:
    """One heterogeneous-row-count storm round (fresh governor/engine):
    every client submits requests whose row counts are drawn log-uniform
    (plus a slice of zero-row requests), each wanting its own per-request
    sum.  ``ragged`` toggles the page-pool fused path on an otherwise
    identical configuration; BOTH paths run the SAME kernel through the
    SAME plan cache (the classic fn is serve/ragged.run_rows_compiled,
    the per-request oracle), so the plan-cache miss delta is a
    like-for-like compile count.  ``chaos`` arms the round-9 pressure
    storm (injected RetryOOM on reservations + split_oom at the serve
    seam both paths cross)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.obs.faultinj import (
        FaultInjector,
        pressure_storm_config,
    )
    from spark_rapids_jni_tpu.plans import plan_cache
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        QueryHandler,
        RaggedSpec,
        RequestTimeout,
        ServingEngine,
    )
    from spark_rapids_jni_tpu.serve.ragged import run_rows_compiled

    from spark_rapids_jni_tpu import config

    # paired rounds must not share compiled entries: each round pays (and
    # counts) its own compiles
    plan_cache.clear()
    cache_before = plan_cache.stats()

    gov = MemoryGovernor(watchdog_period_s=0.02)
    budget = BudgetedResource(gov, args.ragged_budget)
    engine = ServingEngine(
        gov=gov, budget=budget, workers=args.workers,
        queue_size=args.queue_size, default_deadline_s=args.deadline_s,
        serve_ragged=ragged)
    page_rows = int(config.get("serve_page_rows"))

    def storm_kernel(data, valid, rid, riders_cap):
        vals = jnp.where(valid, data, jnp.int64(0))
        return jax.ops.segment_sum(vals, rid,
                                   num_segments=riders_cap + 1)[:-1]

    spec = RaggedSpec(
        rows_of=lambda p: np.asarray(p, np.int64),
        kernel=storm_kernel, out="riders",
        result_of=lambda out, p: int(out),
        kernel_key="bench.ragged_storm_sum")

    def storm_fn(p, ctx):
        # the per-request oracle: same kernel, same cache, one rider —
        # compiled per request-shape bucket (exactly the variant
        # explosion the ragged path collapses)
        return int(run_rows_compiled(spec, np.asarray(p, np.int64),
                                     page_rows))

    engine.register(QueryHandler(
        name="rstorm", fn=storm_fn,
        nbytes_of=lambda p: 64 * max(len(p), 1),
        split=lambda p: [p[:len(p) // 2], p[len(p) // 2:]],
        combine=lambda rs: int(sum(rs)),
        ragged=spec))
    if chaos:
        FaultInjector.install(pressure_storm_config(args.seed))

    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "wrong_answers": 0}
    rows_done = [0]

    def client(ci: int) -> None:
        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = engine.open_session(
            f"ragged{ci}", priority=1 if ci % 3 == 0 else 0)
        for _ri in range(per_client):
            if rng.random_sample() < args.ragged_zero_pct / 100.0:
                n = 0
            else:  # log-uniform row counts: the heterogeneity the
                # micro-batcher compiles per shape
                n = int(2 ** rng.uniform(0, np.log2(args.ragged_max_rows)))
            payload = rng.randint(0, 1000, n).astype(np.int64)
            want = int(payload.sum())
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = engine.submit(sess, "rstorm", payload)
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if out != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            with lock:
                tally[outcome] += 1
                if outcome == "succeeded":
                    rows_done[0] += n

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    snap = engine.metrics.snapshot()
    engine.shutdown()
    if chaos:
        FaultInjector.uninstall()
    gov.close()
    cache_after = plan_cache.stats()
    accounted = (tally["succeeded"] + tally["rejected"] + tally["timed_out"]
                 + tally["errors"])
    counters = snap["counters"]
    return {
        "ragged": ragged,
        "chaos": chaos,
        "requests": total,
        "wall_s": round(wall, 3),
        "rows": rows_done[0],
        "rows_per_s": round(rows_done[0] / wall, 1),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["wrong_answers"] == 0),
        "compiles": int(cache_after["misses"] - cache_before["misses"]),
        "launches": (counters.get("ragged_launches", 0) if ragged
                     else tally["succeeded"]),
        "ragged_counters": {k: counters.get(k, 0) for k in
                            ("ragged_batched", "ragged_launches",
                             "ragged_pages", "ragged_rows",
                             "ragged_splits")},
        "batch_miss": snap.get("batch_miss", {}),
        "gauges": {k: v for k, v in snap.get("gauges", {}).items()
                   if k.startswith(("ragged_", "page_pool_"))},
    }


def _run_ragged_storm(args) -> int:
    """``--ragged-storm``: the continuous-ragged-batching acceptance.

    Paired (micro, ragged) rounds per seed under identical request
    schedules — calm pairs judge throughput and compile counts, a final
    chaos pair (seeded pressure storm) judges the protocol: zero lost,
    zero wrong answers on BOTH paths.  Gates: ragged beats micro on
    MEDIAN rows/s, issues STRICTLY fewer plan-cache compiles in every
    calm pair, and both paths return bit-identical (oracle-checked)
    per-session results with nothing lost."""
    import statistics

    base_seed = args.seed
    pairs = []
    for i in range(max(1, args.ragged_rounds)):
        args.seed = base_seed + i
        micro = _ragged_round(args, ragged=False, chaos=False)
        ragged = _ragged_round(args, ragged=True, chaos=False)
        pairs.append({"seed": args.seed, "micro": micro, "ragged": ragged})
    args.seed = base_seed
    chaos_pair = {
        "micro": _ragged_round(args, ragged=False, chaos=True),
        "ragged": _ragged_round(args, ragged=True, chaos=True),
    }
    rows_micro = statistics.median(p["micro"]["rows_per_s"] for p in pairs)
    rows_ragged = statistics.median(p["ragged"]["rows_per_s"] for p in pairs)
    comparison = {
        "pairs": len(pairs),
        "rows_per_s_micro": rows_micro,
        "rows_per_s_ragged": rows_ragged,
        "speedup": round(rows_ragged / max(rows_micro, 1e-9), 2),
        "compiles_micro": sum(p["micro"]["compiles"] for p in pairs),
        "compiles_ragged": sum(p["ragged"]["compiles"] for p in pairs),
        "launches_micro": sum(p["micro"]["launches"] for p in pairs),
        "launches_ragged": sum(p["ragged"]["launches"] for p in pairs),
    }
    gates = {
        "ragged_wins_rows_per_s": rows_ragged > rows_micro,
        "ragged_fewer_compiles": all(
            p["ragged"]["compiles"] < p["micro"]["compiles"]
            for p in pairs),
        "identical_results": all(
            p[k]["zero_lost"] for p in pairs for k in ("micro", "ragged")),
        "chaos_zero_lost": (chaos_pair["micro"]["zero_lost"]
                            and chaos_pair["ragged"]["zero_lost"]),
    }
    rec = {
        "name": "BENCH_serve",
        "mode": "ragged_storm",
        "seed": base_seed,
        "clients": args.clients,
        "workers": args.workers,
        "queue_size": args.queue_size,
        "storm": {"max_rows": args.ragged_max_rows,
                  "zero_pct": args.ragged_zero_pct,
                  "budget": args.ragged_budget},
        "rounds": pairs,
        "chaos_pair": chaos_pair,
        "comparison": comparison,
        "gates": gates,
        "zero_lost": gates["identical_results"] and gates["chaos_zero_lost"],
    }
    print(json.dumps(rec))
    return 0 if all(gates.values()) else 1


def _chaos_tier(args, adaptive: bool) -> dict:
    """One pressure-storm run (fresh governor/engine/injector): a
    deliberately undersized device budget makes EVERY full-size request
    draw the split protocol, and the seeded storm profile layers injected
    RetryOOM/SplitAndRetryOOM weather on top.  Returns client-observed
    outcome + latency stats; ``adaptive`` toggles the admission
    controller on an otherwise identical configuration."""
    import numpy as np

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.obs.faultinj import (
        FaultInjector,
        pressure_storm_config,
    )
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        QueryHandler,
        RequestTimeout,
        ServingEngine,
    )

    from spark_rapids_jni_tpu import config

    gov = MemoryGovernor(watchdog_period_s=0.02)
    budget = BudgetedResource(gov, args.storm_budget)
    # a tight controller tick keeps the learning phase (full-size attempts
    # before the presplit knob lands) short relative to the storm window
    config.set("serve_controller_period_s", 0.02)
    engine = ServingEngine(
        gov=gov, budget=budget, workers=args.workers,
        queue_size=args.queue_size, default_deadline_s=args.deadline_s,
        adaptive=adaptive)

    def storm_fn(p, ctx):
        time.sleep(0.002)  # a stable service-time floor per launch
        return int(np.sum(p))

    engine.register(QueryHandler(
        name="storm", fn=storm_fn,
        nbytes_of=lambda p: args.storm_bytes_per_row * len(p),
        split=lambda p: [p[:len(p) // 2], p[len(p) // 2:]],
        combine=lambda rs: int(sum(rs))))
    FaultInjector.install(pressure_storm_config(args.seed))

    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "wrong_answers": 0}
    latencies = []

    def client(ci: int) -> None:
        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = engine.open_session(
            f"storm{ci}", priority=1 if ci % 3 == 0 else 0)
        for ri in range(per_client):
            payload = rng.randint(0, 1000, args.storm_rows).astype(np.int64)
            want = int(payload.sum())
            t0 = time.perf_counter()
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = engine.submit(sess, "storm", payload)
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if out != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            dt = time.perf_counter() - t0
            with lock:
                tally[outcome] += 1
                # latency percentiles measure STEADY STATE: each client's
                # first few requests (the warm-up in which the adaptive
                # tier is still learning and both tiers pay first-touch
                # costs) are excluded from the sample — outcome accounting
                # above still covers every request (zero-lost is total)
                if outcome == "succeeded" and ri >= args.storm_warmup:
                    latencies.append(dt)

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    ctl_snap = (engine.controller.snapshot()
                if engine.controller is not None else None)
    snap = engine.metrics.snapshot()
    engine.shutdown()
    FaultInjector.uninstall()
    gov.close()
    accounted = (tally["succeeded"] + tally["rejected"] + tally["timed_out"]
                 + tally["errors"])
    lat_ms = sorted(1e3 * x for x in latencies)
    pct = (lambda p: round(
        lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * p / 100))], 3)
        if lat_ms else 0.0)
    return {
        "adaptive": adaptive,
        "requests": total,
        "wall_s": round(wall, 3),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["wrong_answers"] == 0),
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "counters": snap["counters"],
        "controller": ctl_snap,
    }


def _run_chaos_storm(args) -> int:
    """static-vs-adaptive comparison under the identical seeded storm:
    the BENCH_serve block that pins 'the controller beats static config
    on p99 latency and rejected-request count with zero lost requests'.

    Runs ``--storm-rounds`` paired (static, adaptive) rounds — round i
    uses seed+i for BOTH tiers, so each pair sees an identical fault
    schedule — and gates on the MEDIAN p99 across rounds: a single OS
    scheduling hiccup landing in either tier cannot flip the verdict
    (single-pair p99 on a loaded box sits at the noise floor)."""
    import statistics

    rounds = []
    base_seed = args.seed
    for i in range(max(1, args.storm_rounds)):
        args.seed = base_seed + i
        static = _chaos_tier(args, adaptive=False)
        adaptive = _chaos_tier(args, adaptive=True)
        rounds.append({"seed": args.seed, "static": static,
                       "adaptive": adaptive})
    args.seed = base_seed
    p99_static = statistics.median(r["static"]["p99_ms"] for r in rounds)
    p99_adaptive = statistics.median(r["adaptive"]["p99_ms"] for r in rounds)
    rej_static = sum(r["static"]["outcomes"]["rejected"] for r in rounds)
    rej_adaptive = sum(r["adaptive"]["outcomes"]["rejected"] for r in rounds)
    comparison = {
        "rounds": len(rounds),
        "p99_ms_static": p99_static,
        "p99_ms_adaptive": p99_adaptive,
        "rejects_static": rej_static,
        "rejects_adaptive": rej_adaptive,
        "adaptive_wins_p99": p99_adaptive < p99_static,
        # <=: both tiers commonly reach zero final rejects; adaptive must
        # never be WORSE (the acceptance criterion), a tie at zero passes
        "adaptive_wins_rejects": rej_adaptive <= rej_static,
    }
    rec = {
        "name": "BENCH_serve",
        "mode": "chaos_storm",
        "seed": base_seed,
        "clients": args.clients,
        "workers": args.workers,
        "queue_size": args.queue_size,
        "storm": {"rows": args.storm_rows,
                  "bytes_per_row": args.storm_bytes_per_row,
                  "budget": args.storm_budget,
                  "warmup": args.storm_warmup},
        "rounds": rounds,
        "comparison": comparison,
        "zero_lost": all(r["static"]["zero_lost"]
                         and r["adaptive"]["zero_lost"] for r in rounds),
    }
    print(json.dumps(rec))
    ok = (rec["zero_lost"] and comparison["adaptive_wins_p99"]
          and comparison["adaptive_wins_rejects"])
    return 0 if ok else 1


def _optimizer_variants(j: int, epoch: int, nseg: int):
    """Four spellings of ONE logical two-join + two-predicate query —
    join order x filter splitting — all named ``opt_q{j}`` so the
    rewriter's canonical form keys ONE result-cache entry for all four.
    Predicate literals embed the epoch, so epochs never share keys."""
    from spark_rapids_jni_tpu.plans import ir

    lit1 = (epoch * 17 + j) % 40
    lit2 = 60 + (epoch * 7 + j) % 30

    def build(a_first: bool, split_filters: bool):
        node = ir.Scan("facts", ("ka", "kb", "qty"))
        joins = [("dim_a", "w", "ka", "wa"), ("dim_b", "v", "kb", "vb")]
        if not a_first:
            joins.reverse()
        for table, field, key, out in joins:
            node = ir.GatherJoin(node, ir.Dim(table, (field,)),
                                 ir.col(key), ir.lit(0), ((field, out),))
        p1 = ir.Bin("gt", ir.col("qty"), ir.lit(lit1))
        p2 = ir.Bin("ne", ir.col("qty"), ir.lit(lit2))
        if split_filters:
            node = ir.Filter(ir.Filter(node, p1), p2)
        else:
            node = ir.Filter(node, ir.Bin("and", p1, p2))
        sink = ir.SegmentAgg(
            node, ir.col("ka"), nseg,
            (("s", ir.Bin("mul", ir.col("wa"), ir.col("vb")), "int64"),
             ("c", ir.col("qty"), "int64")))
        return ir.Plan(f"opt_q{j}", (sink,))

    return [build(True, False), build(False, False),
            build(True, True), build(False, True)]


def _optimizer_round(args, *, optimizer_on: bool) -> dict:
    """One in-process governed-plan round of the canonicalization
    workload: epochs of K logical queries, each submitted in 4 different
    spellings.  Both tiers run with the result cache ON and an identical
    seeded schedule; the only difference is ``plan_optimizer``.  With the
    rewriter on, every spelling canonicalizes to one tree, so the warm
    pass's K entries serve the whole measure phase (cross-query hits);
    off, each spelling keys separately and recomputes.  Every answer is
    checked bit-identical against the unrewritten compiled oracle."""
    import numpy as np

    from spark_rapids_jni_tpu.models import tables as _tables
    from spark_rapids_jni_tpu.obs import flight as _flight
    from spark_rapids_jni_tpu.plans import execute_plan
    from spark_rapids_jni_tpu.plans import optimizer as _opt
    from spark_rapids_jni_tpu.plans.rcache import result_cache
    from spark_rapids_jni_tpu.plans.runtime import run_governed_plan

    from spark_rapids_jni_tpu import config

    rng = np.random.RandomState(args.seed)
    result_cache.reset_for_tests()
    _tables.reset_for_tests()
    _opt.reset_for_tests()
    nseg, ndim_b = 512, 8
    n = args.opt_rows
    tables = {
        "facts": {"ka": rng.randint(0, nseg, n).astype(np.int32),
                  "kb": rng.randint(0, ndim_b, n).astype(np.int32),
                  "qty": rng.randint(0, 100, n).astype(np.int64)},
        "dim_a": {"w": rng.randint(1, 100, nseg).astype(np.int64)},
        "dim_b": {"v": rng.randint(1, 100, ndim_b).astype(np.int64)},
    }
    K, V, R = args.opt_queries, 4, args.opt_repeats
    tally = {"succeeded": 0, "errors": 0, "wrong_answers": 0}
    latencies = []
    ev0 = sum(1 for e in _flight.snapshot()
              if e["kind"] == "plan_rewrite")

    def run_checked(plan, oracle, measure: bool) -> None:
        t0 = time.perf_counter()
        try:
            out = run_governed_plan(None, plan, tables)
        except Exception:  # noqa: BLE001 - counted, not raised
            tally["errors"] += 1
            return
        dt = time.perf_counter() - t0
        tally["succeeded"] += 1
        for k in oracle:
            if not np.array_equal(np.asarray(out[k]),
                                  np.asarray(oracle[k])):
                tally["wrong_answers"] += 1
                break
        if measure:
            latencies.append(dt)

    t0 = time.perf_counter()
    with config.override(serve_result_cache=True,
                         plan_optimizer=optimizer_on):
        for epoch in range(args.opt_epochs):
            variants = [_optimizer_variants(j, epoch, nseg)
                        for j in range(K)]
            # one config-independent oracle per logical query
            oracles = [execute_plan(None, variants[j][0], tables)
                       for j in range(K)]
            # warm pass (unmeasured): spelling 0 of each query seeds the
            # cache — canonical key when the rewriter is on, verbatim off
            for j in range(K):
                run_checked(variants[j][0], oracles[j], measure=False)
            # measure pass: all four spellings, seeded shuffle
            schedule = [(j, v) for j in range(K) for v in range(V)] * R
            rng.shuffle(schedule)
            for j, v in schedule:
                run_checked(variants[j][v], oracles[j], measure=True)
    wall = time.perf_counter() - t0
    stats = result_cache.stats()
    rewrites = sum(1 for e in _flight.snapshot()
                   if e["kind"] == "plan_rewrite") - ev0
    total = args.opt_epochs * (K + K * V * R)
    lat_ms = sorted(1e3 * x for x in latencies)
    pct = (lambda p: round(
        lat_ms[min(len(lat_ms) - 1, int(len(lat_ms) * p / 100))], 3)
        if lat_ms else 0.0)
    return {
        "optimizer_on": optimizer_on,
        "requests": total,
        "wall_s": round(wall, 3),
        "req_per_s": round(total / wall, 2) if wall else 0.0,
        "outcomes": tally,
        "lost": total - tally["succeeded"] - tally["errors"],
        "zero_lost": (tally["succeeded"] == total
                      and tally["errors"] == 0),
        "bit_identical": tally["wrong_answers"] == 0,
        "p50_ms": pct(50),
        "p99_ms": pct(99),
        "rcache": {k: stats.get(k, 0) for k in
                   ("lookups", "hits", "misses", "stores", "hit_ratio")},
        "rewrite_events": rewrites,
    }


def _hedge_chaos_phase(args) -> dict:
    """Speculative hedging under the round-10 kill storm: seeded rare
    extreme stragglers (faultinj ``slow``) ride alongside one-shot
    mid-request SIGKILLs.  The sweep must hedge a straggling lease onto
    another executor and the hedge must WIN (first-result-wins), while
    kill-driven re-dispatch composes with hedge bookkeeping — zero lost,
    every lease effectively once."""
    import numpy as np

    from spark_rapids_jni_tpu.obs import flight as _flight
    from spark_rapids_jni_tpu.obs.faultinj import chaos_kill_config
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        Degraded,
        HandlerSpec,
        RequestTimeout,
        Supervisor,
    )

    from spark_rapids_jni_tpu import config

    def chaos_fn(wid: int, inc: int):
        # incarnation-0 executors die at most once each (kill + respawn
        # composes with hedging); every incarnation gets the rare
        # extreme-straggler weather the hedge sweep exists to absorb
        return chaos_kill_config(
            seed=args.seed * 1000 + wid * 17 + inc,
            kill=(inc == 0), kill_pct=args.kill_pct,
            slow_pct=args.hedge_slow_pct, slow_ms=args.hedge_slow_ms)

    # hedge knobs are snapshot at construction: the override need only
    # wrap the Supervisor() call
    with config.override(serve_hedge=True,
                         serve_hedge_factor=args.hedge_factor,
                         serve_hedge_budget_frac=args.hedge_budget_frac,
                         serve_hedge_min_samples=8,
                         serve_hedge_window_s=5.0):
        sup = Supervisor(
            workers=args.opt_cluster,
            factory="serve_bench:cluster_worker_factory",
            factory_kwargs={"bytes_per_row": args.storm_bytes_per_row,
                            "service_ms": args.cluster_service_ms},
            worker_cfg={"workers": args.workers,
                        "queue_size": max(32, args.queue_size)},
            chaos=chaos_fn,
            queue_size=args.queue_size,
            default_deadline_s=args.deadline_s,
            lease_hang_s=args.lease_hang_s)
    sup.register(HandlerSpec(
        "storm", nbytes_of=lambda p: args.storm_bytes_per_row * len(p)))

    deadline = time.monotonic() + 180
    while time.monotonic() < deadline:
        alive = sum(1 for w in sup.snapshot()["workers"].values()
                    if w["state"] == "alive")
        if alive >= args.opt_cluster:
            break
        time.sleep(0.05)

    clients = max(2, args.clients)
    per_client = max(1, args.hedge_requests // clients)
    total = per_client * clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "degraded_retries": 0, "wrong_answers": 0}

    def client(ci: int) -> None:
        rng = np.random.RandomState(args.seed * 1000 + ci)
        sess = sup.open_session(
            f"hedge{ci}", priority=1 if ci % 3 == 0 else 0)
        for _ri in range(per_client):
            payload = rng.randint(0, 1000, args.storm_rows).astype(np.int64)
            want = int(payload.sum())
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = sup.submit(sess, "storm", payload)
                except Degraded as bp:
                    with lock:
                        tally["degraded_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.1))
                    continue
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.05))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if out != want:
                        with lock:
                            tally["wrong_answers"] += 1
                break
            with lock:
                tally[outcome] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    sup.wait_drained(timeout=120)
    wall = time.perf_counter() - t0
    snap = sup.snapshot()
    hedge_events = {
        k: sum(1 for e in _flight.snapshot() if e["kind"] == k)
        for k in ("hedge_launch", "hedge_win", "hedge_lose")}
    sup.shutdown()
    counters = snap["counters"]
    leases = snap["leases"]
    accounted = (tally["succeeded"] + tally["rejected"]
                 + tally["timed_out"] + tally["errors"])
    return {
        "requests": total,
        "wall_s": round(wall, 3),
        "outcomes": tally,
        "lost": total - accounted,
        "zero_lost": (accounted == total and tally["errors"] == 0
                      and tally["timed_out"] == 0
                      and tally["wrong_answers"] == 0),
        "hedges_launched": counters.get("hedges_launched", 0),
        "hedge_wins": counters.get("hedge_wins", 0),
        "hedge_losses": counters.get("hedge_losses", 0),
        "hedge_events": hedge_events,
        "workers_dead": counters.get("workers_dead", 0),
        "duplicate_results": counters.get("duplicate_results", 0),
        "leases": leases,
        "exactly_once": (leases["outstanding"] == 0
                         and leases["completed"] == leases["leases"]),
    }


def _run_optimizer_storm(args) -> int:
    """``--optimizer-storm``: the round-19 acceptance tier.

    Phase 1 — paired optimizer-off/on governed-plan rounds over an
    identical seeded multi-spelling workload (>= 3 seeds): the rewriter
    must win median p99 through cross-query result-cache hits, with
    every answer bit-identical to the unrewritten oracle and zero lost.
    Phase 2 — paired static/adaptive Exchange shuffle rounds on a
    skewed q97 workload: the reduce side must demonstrably change
    partition count/strategy (EV_ADAPT_EXCHANGE in the merged dumps)
    with oracle-identical outputs both rounds.  Phase 3 — speculative
    hedging under the seeded kill+straggler storm: hedges launch, a
    hedge wins, SIGKILL re-dispatch composes, zero lost, leases
    effectively once."""
    import re as _re
    import statistics
    import tempfile

    from spark_rapids_jni_tpu.obs import flight as _flight

    from spark_rapids_jni_tpu import config

    rounds = []
    base_seed = args.seed
    for i in range(max(1, args.opt_rounds)):
        args.seed = base_seed + i
        off = _optimizer_round(args, optimizer_on=False)
        on = _optimizer_round(args, optimizer_on=True)
        rounds.append({"seed": args.seed, "off": off, "on": on})
    args.seed = base_seed
    p99_off = statistics.median(r["off"]["p99_ms"] for r in rounds)
    p99_on = statistics.median(r["on"]["p99_ms"] for r in rounds)
    misses_on = sum(r["on"]["rcache"]["misses"] for r in rounds)
    misses_off = sum(r["off"]["rcache"]["misses"] for r in rounds)
    hits_on = sum(r["on"]["rcache"]["hits"] for r in rounds)
    hits_off = sum(r["off"]["rcache"]["hits"] for r in rounds)
    # with the rewriter on, ONLY the warm pass may miss: every measured
    # request — three quarters of which are spelled differently from the
    # entry that seeded the cache — must hit the canonical key
    expected_warm = args.opt_rounds * args.opt_epochs * args.opt_queries
    optimizer = {
        "rounds": len(rounds),
        "p99_ms_off": p99_off,
        "p99_ms_on": p99_on,
        "rcache_misses_off": misses_off,
        "rcache_misses_on": misses_on,
        "rcache_hits_off": hits_off,
        "rcache_hits_on": hits_on,
        "cross_query_hits": (hits_on - hits_off
                             if misses_on == expected_warm else 0),
        "rewrite_events": sum(r["on"]["rewrite_events"] for r in rounds),
    }
    opt_gates = {
        "opt_zero_lost": all(r["off"]["zero_lost"] and r["on"]["zero_lost"]
                             for r in rounds),
        "opt_bit_identical": all(
            r["off"]["bit_identical"] and r["on"]["bit_identical"]
            for r in rounds),
        "opt_p99_win": p99_on < p99_off,
        "opt_cross_query_hits": (misses_on == expected_warm
                                 and hits_on > hits_off),
        "opt_rewrites_narrated": optimizer["rewrite_events"] > 0,
    }

    dump_dir = args.dump_dir or tempfile.mkdtemp(prefix="srt_adapt_")
    static = _shuffle_round(args, chaos=False, skew=True)
    adaptive = _shuffle_round(args, chaos=False, dump_dir=dump_dir,
                              adaptive=True, skew=True)
    config.set("flight_dump_dir", "")
    _flight.recorder().reset_for_tests()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import flightdump

    merged = flightdump.merge_cluster(dump_dir)
    adapt_events = [e for e in merged["events"]
                    if e["kind"] == "adapt_exchange"]
    strategies = {}
    changed = 0
    for e in adapt_events:
        d = str(e.get("detail", ""))
        m = _re.search(r"strategy:(\w+):parts:(\d+)->(\d+)", d)
        if not m:
            continue
        strategies[m.group(1)] = strategies.get(m.group(1), 0) + 1
        if m.group(2) != m.group(3):
            changed += 1
    adaptive_cmp = {
        "p99_ms_static": static["p99_ms"],
        "p99_ms_adaptive": adaptive["p99_ms"],
        "adapt_events": len(adapt_events),
        "strategy_changes": changed,
        "strategies": strategies,
    }
    adapt_gates = {
        "adapt_zero_lost": static["zero_lost"] and adaptive["zero_lost"],
        "adapt_oracle_identical": (static["oracle_identical"]
                                   and adaptive["oracle_identical"]),
        # the acceptance: the reduce side demonstrably REGROUPED — the
        # merged worker dumps carry adapt_exchange decisions whose
        # partition count actually changed (coalesce and/or broadcast)
        "adapt_strategy_changed": changed >= 1,
    }

    hedge = _hedge_chaos_phase(args)
    hedge_gates = {
        "hedge_zero_lost": hedge["zero_lost"],
        "hedge_launched": hedge["hedges_launched"] >= 1,
        "hedge_straggler_recovered": hedge["hedge_wins"] >= 1,
        "hedge_exactly_once": hedge["exactly_once"],
        "hedge_kills_composed": hedge["workers_dead"] >= 1,
    }

    gates = {}
    gates.update(opt_gates)
    gates.update(adapt_gates)
    gates.update(hedge_gates)
    rec = {
        "name": "BENCH_serve",
        "mode": "optimizer_storm",
        "seed": base_seed,
        "clients": args.clients,
        "cluster": args.opt_cluster,
        "optimizer": {"rounds": rounds, "comparison": optimizer},
        "adaptive": {"static": static, "adaptive": adaptive,
                     "comparison": adaptive_cmp, "dump_dir": dump_dir},
        "hedge": hedge,
        "gates": gates,
        "zero_lost": (opt_gates["opt_zero_lost"]
                      and adapt_gates["adapt_zero_lost"]
                      and hedge_gates["hedge_zero_lost"]),
    }
    print(json.dumps(rec))
    return 0 if all(gates.values()) else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="serving-engine load generator")
    ap.add_argument("--clients", type=int, default=32)
    ap.add_argument("--requests", type=int, default=200,
                    help="total logical requests across all clients")
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--queue-size", type=int, default=32)
    ap.add_argument("--deadline-s", type=float, default=60.0)
    ap.add_argument("--q97-rows", type=int, default=512,
                    help="rows per side of each q97 request")
    ap.add_argument("--hash-frac", type=float, default=0.5,
                    help="fraction of requests that are hash32 ops "
                         "(the rest are q97 queries)")
    ap.add_argument("--mixed-plans", action="store_true",
                    help="non-hash requests alternate plan-compiled q3 and "
                         "q5 queries (one shared geometry) instead of q97: "
                         "every session hits the SAME process-global plan "
                         "cache, so compiled-variant reuse across tenants "
                         "is exercised under load; plan-cache gauges are "
                         "recorded in the BENCH_serve line")
    ap.add_argument("--plan-sf", type=float, default=0.02,
                    help="scale factor of the shared q3/q5 datasets in "
                         "--mixed-plans mode")
    ap.add_argument("--max-retries", type=int, default=50,
                    help="backpressure re-submits before a request counts "
                         "as finally rejected")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chaos-storm", action="store_true",
                    help="run the seeded pressure-storm tier TWICE (static "
                         "config, then adaptive admission) under an "
                         "identical fault schedule and undersized budget; "
                         "emits one BENCH_serve comparison block (p99, "
                         "rejects, lost) — the adaptive-admission win "
                         "pinned in the bench trajectory")
    ap.add_argument("--ragged-storm", action="store_true",
                    help="run the heterogeneous-row-count storm in paired "
                         "(micro, ragged) rounds under identical seeded "
                         "schedules, plus one chaos pair (pressure "
                         "storm); gates: ragged wins median rows/s, "
                         "strictly fewer plan-cache compiles per pair, "
                         "oracle-identical results and zero lost on both "
                         "paths")
    ap.add_argument("--cache-storm", action="store_true",
                    help="run the governed result-cache acceptance: "
                         "paired cache-off/cache-on supervised-cluster "
                         "rounds over an identical seeded Zipf lookup "
                         "mix with mid-run table-version bumps, plus an "
                         "in-process governor-pressure phase.  Gates: "
                         "zero lost + bit-identical both rounds (== "
                         "zero stale serves), hit ratio >= the floor, "
                         "cache-on >= the speedup factor on throughput, "
                         "invalidations reclaim entries, and governed "
                         "pressure demotes cache residency without "
                         "killing the live task")
    ap.add_argument("--cache-cluster", type=int, default=2,
                    help="executor processes of the cache-storm rounds")
    ap.add_argument("--cache-tables", type=int, default=32,
                    help="named-table universe of the Zipf mix")
    ap.add_argument("--cache-zipf", type=float, default=1.3,
                    help="Zipf exponent of table popularity (higher = "
                         "hotter head, more hits)")
    ap.add_argument("--cache-rows", type=int, default=2048,
                    help="rows per lookup payload (content is derived "
                         "from (table, version), so the digest in the "
                         "cache key changes on every bump)")
    ap.add_argument("--cache-service-ms", type=float, default=20.0,
                    help="service-time floor of the lookup handler — "
                         "the compute a cache hit skips (the speedup "
                         "gate measures hits against THIS, so it must "
                         "dominate the ~0.5 ms per-request serving "
                         "overhead by a wide margin)")
    ap.add_argument("--cache-bumps", type=int, default=4,
                    help="mid-run bump_table('t0') calls (client 0, "
                         "fixed request indices: deterministic "
                         "concurrent invalidation)")
    ap.add_argument("--cache-speedup-min", type=float, default=5.0,
                    help="cache-on must beat cache-off by this factor "
                         "on closed-loop throughput")
    ap.add_argument("--cache-hit-floor", type=float, default=0.6,
                    help="minimum supervisor-level hit ratio of the "
                         "cache-on round")
    ap.add_argument("--ragged-rounds", type=int, default=2,
                    help="calm (micro, ragged) pairs for the ragged-storm "
                         "verdict (seed+i per pair)")
    ap.add_argument("--ragged-max-rows", type=int, default=8192,
                    help="row counts draw log-uniform from [1, this] "
                         "(plus --ragged-zero-pct zero-row requests)")
    ap.add_argument("--ragged-zero-pct", type=float, default=5.0,
                    help="percent of ragged-storm requests with ZERO rows "
                         "(the adversarial empty rider)")
    ap.add_argument("--ragged-budget", type=int, default=1 << 30,
                    help="device budget for the ragged-storm rounds (the "
                         "chaos pair's splits come from injected weather, "
                         "not sustained starvation)")
    ap.add_argument("--storm-rows", type=int, default=256,
                    help="rows per storm request (chaos-storm mode)")
    ap.add_argument("--storm-bytes-per-row", type=int, default=1024,
                    help="working-set bytes per row the storm handler "
                         "declares: rows x this must EXCEED the storm "
                         "budget so full-size requests always split")
    ap.add_argument("--storm-budget", type=int, default=160_000,
                    help="device budget for the storm tiers (deliberately "
                         "undersized: between one half and one full "
                         "request working set)")
    ap.add_argument("--storm-warmup", type=int, default=4,
                    help="per-client warm-up requests excluded from the "
                         "latency percentile sample (outcome/zero-lost "
                         "accounting still covers them)")
    ap.add_argument("--storm-rounds", type=int, default=3,
                    help="paired (static, adaptive) rounds; the verdict "
                         "compares MEDIAN p99 across rounds (seed+i per "
                         "round, identical schedule within a pair)")
    ap.add_argument("--cluster", type=int, default=0,
                    help="run the supervised multi-process tier: N "
                         "executor worker processes under the "
                         "router/supervisor (serve/supervisor.py), each "
                         "with its own governor")
    ap.add_argument("--chaos-kill", action="store_true",
                    help="with --cluster: arm seeded in-worker faults "
                         "(proc_kill SIGKILLs executors mid-request, slow "
                         "stalls) and gate on zero lost requests, "
                         "exactly-once lease completion, >= 2 kills with "
                         "respawns, the degradation ladder stepping down "
                         "AND recovering, bounded p99 inflation, and "
                         "cross-process dump reconstruction")
    ap.add_argument("--chaos-shuffle", action="store_true",
                    help="with --cluster: every request is a q97 Exchange "
                         "plan run as a REAL cross-process shuffle (framed "
                         "partition push/pull between executors), paired "
                         "calm/chaos rounds; the chaos round corrupts/"
                         "truncates frames, stalls peers, and SIGKILLs "
                         "executors mid-exchange.  Gates: zero lost + "
                         "oracle-identical reduce outputs both rounds, "
                         ">= 2 mid-shuffle kills recovered, checksum-"
                         "detected corruption re-fetched, leases exactly-"
                         "once, bounded p99")
    ap.add_argument("--shuffle-rows", type=int, default=384,
                    help="rows per side of each q97 shuffle request")
    ap.add_argument("--shuffle-capacity", type=int, default=64,
                    help="Exchange capacity of the q97 plan value (plan "
                         "structure only: framed partitions are exact-"
                         "size, so no overflow retry exists off-mesh)")
    ap.add_argument("--shuffle-io-timeout-s", type=float, default=0.75,
                    help="per-attempt socket I/O timeout of one partition "
                         "fetch (must sit BELOW the injected stall so "
                         "peer_stall drives the backoff path)")
    ap.add_argument("--shuffle-fetch-timeout-s", type=float, default=8.0,
                    help="total per-partition fetch budget before the "
                         "piece fails ShuffleFetchStalled and re-"
                         "dispatches (must sit below lease-hang-s)")
    ap.add_argument("--shuffle-stall-ms", type=float, default=1500.0,
                    help="injected peer_stall duration (chaos round)")
    ap.add_argument("--kill-pct", type=float, default=12.0,
                    help="per-crossing probability of the armed "
                         "executors' one-shot proc_kill fault")
    ap.add_argument("--cluster-service-ms", type=float, default=2.0,
                    help="service-time floor of the cluster storm handler")
    ap.add_argument("--lease-hang-s", type=float, default=5.0,
                    help="supervisor hung-lease bound (must exceed the "
                         "worst-case legitimate service time)")
    ap.add_argument("--chaos-p99-bound-ms", type=float, default=8000.0,
                    help="absolute ceiling on chaos-round p99 (the "
                         "'bounded inflation' gate also allows "
                         "--p99-inflation-factor x the calm round's p99)")
    ap.add_argument("--p99-inflation-factor", type=float, default=50.0)
    ap.add_argument("--dump-dir", default="",
                    help="flight-dump directory for the cluster tier "
                         "(default: a fresh temp dir)")
    ap.add_argument("--slo", action="store_true",
                    help="with --cluster --chaos-kill: arm a tight "
                         "service-wide p99 SLO for the chaos round — the "
                         "latency storm must drive EV_SLO_BURN, a ladder "
                         "reaction, and an EV_SLO_OK recovery (gated)")
    ap.add_argument("--slo-p99-ms", type=float, default=30.0,
                    help="the armed SLO's p99 target; must sit well "
                         "under the chaos round's fault-inflated "
                         "latencies so the burn is deterministic")
    ap.add_argument("--tenant-storm", action="store_true",
                    help="round-21 acceptance tier: paired calm/chaos "
                         "supervised-cluster rounds over a Zipf(1.2) "
                         "tenant mix drawn from a >= 10k id space.  "
                         "Gates: zero lost, the live endpoint's "
                         "attribution section populated (dominant-share "
                         "tenant ranking + capacity headroom), "
                         "attributed compute >= 95%% of worker-measured "
                         "busy-ns, byte-seconds reconciling with the "
                         "governor gauges within 5%%, and the chaos "
                         "round's SIGKILL+respawn not breaking "
                         "reconciliation")
    ap.add_argument("--tenant-space", type=int, default=10_000,
                    help="tenant id universe of the Zipf draw (the "
                         "acceptance requires >= 10k)")
    ap.add_argument("--tenant-zipf", type=float, default=1.2,
                    help="Zipf exponent of tenant popularity")
    ap.add_argument("--optimizer-storm", action="store_true",
                    help="round-19 acceptance tier: paired optimizer-"
                         "off/on governed-plan rounds (median-p99 win "
                         "via cross-query rcache hits, bit-identical, "
                         "zero lost), paired static/adaptive Exchange "
                         "rounds on a skewed shuffle (strategy change "
                         "asserted from merged EV_ADAPT_EXCHANGE "
                         "events), and speculative hedging under the "
                         "seeded kill+straggler storm (hedge win, "
                         "exactly-once)")
    ap.add_argument("--opt-rounds", type=int, default=3,
                    help="paired optimizer-off/on rounds (median p99 "
                         "across rounds gates the win)")
    ap.add_argument("--opt-epochs", type=int, default=3,
                    help="cache-cold epochs per optimizer round (each "
                         "epoch uses fresh predicate literals)")
    ap.add_argument("--opt-queries", type=int, default=4,
                    help="logical queries per epoch; each is submitted "
                         "in 4 spellings (join order x filter split)")
    ap.add_argument("--opt-repeats", type=int, default=2,
                    help="measured repeats of each spelling per epoch")
    ap.add_argument("--opt-rows", type=int, default=20000,
                    help="fact-table rows of the optimizer workload "
                         "(compute cost a cache hit skips)")
    ap.add_argument("--opt-cluster", type=int, default=3,
                    help="executor pool size of the hedge chaos phase")
    ap.add_argument("--hedge-requests", type=int, default=400,
                    help="total requests of the hedge chaos phase")
    ap.add_argument("--hedge-factor", type=float, default=2.0,
                    help="hedge trigger multiple of the windowed p99")
    ap.add_argument("--hedge-budget-frac", type=float, default=0.1,
                    help="hedge budget as a fraction of leases granted")
    ap.add_argument("--hedge-slow-pct", type=float, default=0.8,
                    help="per-crossing probability of the injected "
                         "extreme straggler (must stay RARE so the "
                         "windowed p99 keeps reflecting normal service "
                         "and the straggler reads as an outlier)")
    ap.add_argument("--hedge-slow-ms", type=float, default=2000.0,
                    help="injected straggler stall; must dwarf "
                         "hedge-factor x normal p99 so a launched "
                         "hedge beats the stuck primary")
    ap.add_argument("--adaptive-overpartition", type=int, default=4,
                    help="map-side over-partition factor of the "
                         "adaptive Exchange round")
    ap.add_argument("--adaptive-part-bytes", type=int, default=4096,
                    help="target measured bytes per reduce group "
                         "(sized so the CI-scale skewed workload "
                         "actually coalesces)")
    args = ap.parse_args(argv)

    if args.tenant_storm:
        return _run_tenant_storm(args)
    if args.optimizer_storm:
        return _run_optimizer_storm(args)
    if args.cache_storm:
        return _run_cache_storm(args)
    if args.cluster > 0 and args.chaos_shuffle:
        return _run_chaos_shuffle(args)
    if args.cluster > 0:
        return _run_cluster(args)
    if args.chaos_storm:
        return _run_chaos_storm(args)
    if args.ragged_storm:
        return _run_ragged_storm(args)

    import numpy as np

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.parallel import make_mesh
    from spark_rapids_jni_tpu.serve import (
        Backpressure,
        RequestTimeout,
        ServingEngine,
    )

    plan_data = None
    if args.mixed_plans:
        from spark_rapids_jni_tpu.models import (
            generate_q3_data,
            generate_q5_data,
        )
        from spark_rapids_jni_tpu.models.q3 import q3_local_unfused
        from spark_rapids_jni_tpu.models.q5 import q5_local_unfused
        from spark_rapids_jni_tpu.plans import plan_cache

        q3d = generate_q3_data(sf=args.plan_sf, seed=args.seed)
        q5d = generate_q5_data(sf=args.plan_sf, seed=args.seed)
        # verify against the per-op oracle path: under load every fused
        # answer must stay bit-identical
        plan_data = {
            "q3": (q3d, [tuple(r) for r in q3_local_unfused(q3d)]),
            "q5": (q5d, [tuple(r) for r in q5_local_unfused(q5d)]),
        }
        plan_cache.reset_stats()

    mesh = make_mesh()
    gov = MemoryGovernor.initialize()
    budget = BudgetedResource(gov, 1 << 30)
    engine = ServingEngine(
        mesh=mesh, gov=gov, budget=budget, workers=args.workers,
        queue_size=args.queue_size, default_deadline_s=args.deadline_s,
        builtin_handlers=True)

    per_client = max(1, args.requests // args.clients)
    total = per_client * args.clients
    lock = threading.Lock()
    tally = {"succeeded": 0, "rejected": 0, "timed_out": 0, "errors": 0,
             "client_retries": 0, "wrong_answers": 0}

    def client(ci: int) -> None:
        rng = np.random.RandomState(args.seed * 1000 + ci)
        # tenant spread: a third high-priority, a third byte-capped
        sess = engine.open_session(
            f"client{ci}",
            priority=1 if ci % 3 == 0 else 0,
            byte_budget=(64 << 20) if ci % 3 == 1 else None)
        for ri in range(per_client):
            use_hash = rng.random_sample() < args.hash_frac
            if use_hash:
                query = "hash32"
                payload = rng.randint(0, 1 << 40, 256)
                want = None
            elif plan_data is not None:
                # alternate the two plan-compiled queries: every client
                # session submits the SAME geometry, so after the first
                # compile per (plan, bucket) all sessions reuse the
                # process-global compiled variants
                query = "q3" if (ci + ri) % 2 == 0 else "q5"
                payload, want = plan_data[query]
            else:
                query = "q97"
                n = args.q97_rows
                payload = (
                    (rng.randint(1, 200, n).astype(np.int32),
                     rng.randint(1, 50, n).astype(np.int32)),
                    (rng.randint(1, 200, n).astype(np.int32),
                     rng.randint(1, 50, n).astype(np.int32)))
                want = q97_host_oracle(*payload)
            outcome = "rejected"
            for _ in range(args.max_retries):
                try:
                    resp = engine.submit(sess, query, payload)
                except Backpressure as bp:
                    with lock:
                        tally["client_retries"] += 1
                    time.sleep(min(bp.retry_after_s, 0.25))
                    continue
                try:
                    out = resp.result(timeout=args.deadline_s + 30)
                except RequestTimeout:
                    outcome = "timed_out"
                except Exception:  # noqa: BLE001 - counted, not raised
                    outcome = "errors"
                else:
                    outcome = "succeeded"
                    if want is not None:
                        if query in ("q3", "q5"):
                            got = [tuple(r) for r in out]
                        else:
                            got = (int(out.store_only),
                                   int(out.catalog_only), int(out.both))
                        if got != want:
                            with lock:
                                tally["wrong_answers"] += 1
                break
            with lock:
                tally[outcome] += 1

    t0 = time.perf_counter()
    threads = [threading.Thread(target=client, args=(ci,))
               for ci in range(args.clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    engine.shutdown()
    MemoryGovernor.shutdown()

    snap = engine.metrics.snapshot()
    accounted = (tally["succeeded"] + tally["rejected"] + tally["timed_out"]
                 + tally["errors"])
    rec = {
        "name": "BENCH_serve",
        "clients": args.clients,
        "requests": total,
        "workers": args.workers,
        "queue_size": args.queue_size,
        "wall_s": round(wall, 3),
        "req_per_s": round(total / wall, 2),
        "outcomes": tally,
        "zero_lost": accounted == total and tally["errors"] == 0
        and tally["wrong_answers"] == 0,
        "queue_wait_ms": snap["queue_wait"],
        "run_latency_ms": snap["run_latency"],
        "counters": snap["counters"],
    }
    ok = rec["zero_lost"]
    if args.mixed_plans:
        from spark_rapids_jni_tpu.plans import plan_cache

        stats = plan_cache.stats()
        rec["mode"] = "mixed_plans"
        rec["plan_cache"] = stats
        # the reuse invariant under load: compiled variants are shared
        # across sessions — a handful of traces (one per plan x bucket,
        # plus split halves), everything else cache hits.  Gates the exit
        # code alongside zero_lost but never mutates it: the recorded
        # outcome tally must stay literally "were requests lost".
        rec["plan_reuse"] = (stats["hits"] > 0
                             and stats["misses"] <= 8
                             and stats["hits"] >= stats["misses"])
        ok = ok and rec["plan_reuse"]
    print(json.dumps(rec))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
