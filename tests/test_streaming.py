"""Out-of-core NDS streaming (models/streaming.py): chunked generation,
disk-backed grace-hash bucketing, per-bucket governed q97.

The scale contract under test: peak host memory is one chunk (routing) +
one bucket (execution), never the full fact stream — the shape that
extends BASELINE config 5 toward SF100.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu.columnar import INT32, Column
from spark_rapids_jni_tpu.models.streaming import (
    bucket_of_pairs,
    generate_q97_chunks,
    q97_spill_shuffle,
    run_streaming_q97,
)


def _pair_cols(cust, item):
    return [Column(cust, None, INT32), Column(item, None, INT32)]


def _read_pair(shuffle, side, b):
    cols = shuffle.read(side, b)
    return (np.asarray(cols[0].data, np.int32),
            np.asarray(cols[1].data, np.int32))


def test_bucket_hash_stable_and_spread():
    rng = np.random.RandomState(0)
    cust = rng.randint(1, 5000, 20_000).astype(np.int32)
    item = rng.randint(1, 18_000, 20_000).astype(np.int32)
    b1 = bucket_of_pairs(cust, item, 16)
    b2 = bucket_of_pairs(cust.copy(), item.copy(), 16)
    assert np.array_equal(b1, b2), "bucketing must be deterministic"
    assert b1.min() >= 0 and b1.max() < 16
    counts = np.bincount(b1, minlength=16)
    # dense TPC-DS-ish keys must still spread: no bucket > 2x uniform
    assert counts.max() < 2 * (len(cust) / 16)

    # equal pairs agree across "sides" (different array objects)
    same = bucket_of_pairs(np.asarray([7], np.int32),
                           np.asarray([11], np.int32), 64)
    assert int(same[0]) == int(bucket_of_pairs(
        np.asarray([7], np.int32), np.asarray([11], np.int32), 64)[0])


def test_external_shuffle_roundtrip(tmp_path):
    shuffle = q97_spill_shuffle(str(tmp_path), 8)
    rng = np.random.RandomState(1)
    all_rows = {"store": [], "catalog": []}
    for _ in range(5):  # five chunks per side
        for side in ("store", "catalog"):
            cust = rng.randint(1, 400, 1000).astype(np.int32)
            item = rng.randint(1, 300, 1000).astype(np.int32)
            shuffle.append(side, _pair_cols(cust, item))
            all_rows[side].append((cust, item))

    for side in ("store", "catalog"):
        cust_all = np.concatenate([c for c, _ in all_rows[side]])
        item_all = np.concatenate([i for _, i in all_rows[side]])
        want = set(zip(cust_all.tolist(), item_all.tolist()))
        got = set()
        n_read = 0
        for b in range(8):
            cust_b, item_b = _read_pair(shuffle, side, b)
            assert len(cust_b) == len(item_b)
            n_read += len(cust_b)
            # every row must sit in ITS bucket
            assert np.all(bucket_of_pairs(cust_b, item_b, 8) == b)
            got |= set(zip(cust_b.tolist(), item_b.tolist()))
        assert n_read == len(cust_all), "no row lost or duplicated"
        assert got == want
    assert shuffle.max_bucket_rows() > 0
    shuffle.close()
    assert _read_pair(shuffle, "store", 0)[0].size == 0


def test_generate_q97_chunks_bounded_and_complete():
    chunks = list(generate_q97_chunks(sf=0.002, seed=3, chunk_rows=1500))
    per_side = {"store": 0, "catalog": 0}
    for side, cust, item in chunks:
        assert len(cust) <= 1500, "chunk must respect the row bound"
        assert cust.dtype == np.int32 and item.dtype == np.int32
        per_side[side] += len(cust)
    n = max(1000, int(2_800_000 * 0.002))
    assert per_side == {"store": n, "catalog": n}
    # deterministic: same args -> same stream
    again = list(generate_q97_chunks(sf=0.002, seed=3, chunk_rows=1500))
    assert all(np.array_equal(a[1], b[1]) and np.array_equal(a[2], b[2])
               for a, b in zip(chunks, again))


@pytest.mark.slow
def test_streaming_q97_matches_global_oracle(tmp_path):
    """Per-bucket counts must sum to the GLOBAL q97 answer (a pair lands
    in exactly one bucket on both sides), and the per-bucket oracle
    verification must pass."""
    import jax

    from spark_rapids_jni_tpu.mem import MemoryGovernor
    from spark_rapids_jni_tpu.mem.governed import _reset_default_budget_for_tests
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()), 1))
    chunks = list(generate_q97_chunks(sf=0.003, seed=11, chunk_rows=2000))
    store = (np.concatenate([c for s, c, _ in chunks if s == "store"]),
             np.concatenate([i for s, _, i in chunks if s == "store"]))
    catalog = (np.concatenate([c for s, c, _ in chunks if s == "catalog"]),
               np.concatenate([i for s, _, i in chunks if s == "catalog"]))
    want = q97_host_oracle(store, catalog)

    from spark_rapids_jni_tpu.mem import BudgetedResource

    gov = MemoryGovernor.initialize()
    _reset_default_budget_for_tests()
    host_budget = BudgetedResource(gov, 1 << 30, is_cpu=True)
    try:
        counts, verified, stats = run_streaming_q97(
            mesh, iter(chunks), tmpdir=str(tmp_path / "shuf"),
            n_buckets=8, host_budget=host_budget, task_id=5, verify=True)
    finally:
        MemoryGovernor.shutdown()
    assert verified is True
    assert counts == want
    assert stats["rows_in"] == len(store[0]) + len(catalog[0])
    assert stats["max_bucket_rows"] < stats["rows_in"], \
        "bucketing must actually bound the per-piece working set"
    # host staging went through the arbiter's CPU path and closed cleanly
    assert stats["host_peak_reserved"] > 0
    assert host_budget.used == 0


@pytest.mark.slow
def test_nds_harness_sf1_streamed(capsys):
    """VERDICT r3 #5 'done' criterion: nds_harness --sf 1 --verify green
    with per-query peak governed reservation recorded, q97 out-of-core."""
    import json

    from spark_rapids_jni_tpu.models import nds_harness

    rc = nds_harness.main([
        "--sf", "1", "--verify",
        "--stream-chunk-rows", "400000", "--buckets", "16"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    qs = out["queries"]
    assert all(qs[q]["verified"] is True for q in ("q5", "q97", "q3"))
    assert qs["q97"]["fact_rows"] == 2 * 2_800_000
    assert qs["q97"]["streamed"]["max_bucket_rows"] < 2 * 2_800_000
    for q in ("q5", "q97", "q3"):
        assert qs[q]["peak_reserved_bytes"] > 0


def test_two_tenants_contend_on_host_budget(tmp_path):
    """Two streamed q97 tenants share ONE tight host budget (CPU arbiter
    path): pressure must resolve by blocking/waking through the state
    machine — both finish with correct counts, nothing leaks, no hang.
    The budget fits roughly one tenant's bucket at a time."""
    import threading

    import jax

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()), 1))
    gov = MemoryGovernor(watchdog_period_s=0.02)
    dev_budget = BudgetedResource(gov, 1 << 30)
    # ~4 buckets/tenant of ~1400 rows at 16 B/row JCUDF spill -> ~22 KB
    # per bucket; a 32 KB budget fits ONE bucket but not two, so the
    # tenants contend by blocking/waking through the state machine —
    # never by splitting (pinned below) and never deadlocking
    host_budget = BudgetedResource(gov, 32 << 10, is_cpu=True)

    results = {}

    def tenant(tid):
        chunks = list(generate_q97_chunks(sf=0.001, seed=tid, chunk_rows=700))
        store = (np.concatenate([c for s, c, _ in chunks if s == "store"]),
                 np.concatenate([i for s, _, i in chunks if s == "store"]))
        cat = (np.concatenate([c for s, c, _ in chunks if s == "catalog"]),
               np.concatenate([i for s, _, i in chunks if s == "catalog"]))
        counts, _v, stats = run_streaming_q97(
            mesh, iter(chunks), tmpdir=str(tmp_path / f"t{tid}"),
            n_buckets=4, budget=dev_budget, host_budget=host_budget,
            task_id=tid)
        results[tid] = (counts, q97_host_oracle(store, cat), stats)

    try:
        threads = [threading.Thread(target=tenant, args=(t,))
                   for t in (21, 22)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=300)
        assert all(not t.is_alive() for t in threads), "tenant hung"
    finally:
        gov.close()
    assert set(results) == {21, 22}
    for tid, (counts, want, stats) in results.items():
        assert counts == want, f"tenant {tid}"
        assert stats["host_peak_reserved"] > 0
        assert stats["bucket_splits"] == 0, \
            "this test covers the pure block/wake path, not splits"
    assert host_budget.used == 0, "host reservations must all be released"


def test_oversized_bucket_splits_on_disk(tmp_path):
    """A bucket that cannot fit the host budget must SPLIT recursively on
    disk (key-space-consistent grace-hash refinement) and still produce
    the exact global answer — not crash the stream."""
    import jax

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()), 1))
    chunks = list(generate_q97_chunks(sf=0.002, seed=9, chunk_rows=2000))
    store = (np.concatenate([c for s, c, _ in chunks if s == "store"]),
             np.concatenate([i for s, _, i in chunks if s == "store"]))
    cat = (np.concatenate([c for s, c, _ in chunks if s == "catalog"]),
           np.concatenate([i for s, _, i in chunks if s == "catalog"]))
    want = q97_host_oracle(store, cat)

    gov = MemoryGovernor(watchdog_period_s=0.02)
    dev_budget = BudgetedResource(gov, 1 << 30)
    # 2 buckets over 11200 rows -> ~5600 rows * 16 B JCUDF ~= 90 KB per
    # bucket; a 24 KB host budget forces TWO recursive split levels
    # (90 -> 45 -> 22.5 KB) before a piece fits
    host_budget = BudgetedResource(gov, 24 << 10, is_cpu=True)
    try:
        counts, verified, stats = run_streaming_q97(
            mesh, iter(chunks), tmpdir=str(tmp_path / "shuf"),
            n_buckets=2, budget=dev_budget, host_budget=host_budget,
            task_id=31, verify=True)
    finally:
        gov.close()
    assert counts == want
    assert verified is True
    assert stats["bucket_splits"] >= 2, stats
    assert host_budget.used == 0
    assert host_budget.peak <= 24 << 10, "split pieces must fit the budget"


def test_split_bucket_disk_refinement(tmp_path):
    """split_bucket on the q97 pair shuffle: rows re-partition
    consistently, nothing lost, both sides agree on placement."""
    shuffle = q97_spill_shuffle(str(tmp_path), 2)
    rng = np.random.RandomState(4)
    sent = {}
    for side in ("store", "catalog"):
        cust = rng.randint(1, 500, 4000).astype(np.int32)
        item = rng.randint(1, 300, 4000).astype(np.int32)
        shuffle.append(side, _pair_cols(cust, item))
        sent[side] = set(zip(cust.tolist(), item.tolist()))

    b0_rows = shuffle.rows[("store", 0)]
    lo, hi = shuffle.split_bucket(0, chunk_rows=512)
    assert (lo, hi) == (0, 2)
    assert shuffle.rows[("store", 0)] + shuffle.rows[("store", 2)] == b0_rows
    for side in ("store", "catalog"):
        got = set()
        for b in (0, 1, 2):
            cust_b, item_b = _read_pair(shuffle, side, b)
            if b in (0, 2):
                # refined placement: hash % 4 must equal the bucket id
                assert np.all(bucket_of_pairs(cust_b, item_b, 4) == b)
            got |= set(zip(cust_b.tolist(), item_b.tolist()))
        assert got == sent[side], "split must move rows, never lose them"
    shuffle.close()


def _assemble_q5(chunks):
    """Concatenate streamed q5 chunks into a Q5Data for the global oracle."""
    from spark_rapids_jni_tpu.models.tpcds import (
        CHANNELS,
        ChannelTables,
        Q5Data,
        q5_dims,
    )

    dims = q5_dims()
    acc = {}
    for channel, kind, ch in chunks:
        acc.setdefault((channel, kind), []).append(ch)

    def cat(channel, kind, field):
        parts = [c[field] for c in acc.get((channel, kind), [])]
        return np.concatenate(parts) if parts else np.zeros(0, np.int32)

    channels = {}
    for name in CHANNELS:
        web = name == "web"
        channels[name] = ChannelTables(
            sales_sk=cat(name, "sales", "sk"),
            sales_sk_valid=cat(name, "sales", "sk_valid"),
            sales_date=cat(name, "sales", "date"),
            sales_date_valid=cat(name, "sales", "date_valid"),
            sales_price=cat(name, "sales", "m1"),
            sales_profit=cat(name, "sales", "m2"),
            ret_sk=None if web else cat(name, "ret", "sk"),
            ret_sk_valid=None if web else cat(name, "ret", "sk_valid"),
            ret_date=cat(name, "ret", "date"),
            ret_date_valid=cat(name, "ret", "date_valid"),
            ret_amt=cat(name, "ret", "m1"),
            ret_loss=cat(name, "ret", "m2"),
            dim_sk=dims.dim_sk[name],
            dim_id=dims.dim_id[name],
            sales_item=cat(name, "sales", "item") if web else None,
            sales_order=cat(name, "sales", "order") if web else None,
            ret_item=cat(name, "ret", "item") if web else None,
            ret_order=cat(name, "ret", "order") if web else None,
        )
    return Q5Data(channels, dims.date_sk, dims.date_days,
                  dims.sales_date_lo, dims.sales_date_hi)


@pytest.mark.slow
def test_streaming_q5_matches_global_oracle(tmp_path):
    """Streamed q5 over disk buckets must equal q5_local over the SAME
    concatenated chunk stream (additive partials over disjoint buckets),
    and every bucket must pass its local numpy-partials oracle."""
    import jax

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models.q5 import q5_local
    from spark_rapids_jni_tpu.models.streaming import (
        generate_q5_chunks,
        run_streaming_q5,
    )
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()), 1))
    chunks = list(generate_q5_chunks(sf=0.5, seed=6, chunk_rows=3000))
    want = q5_local(_assemble_q5(chunks))

    gov = MemoryGovernor.initialize()
    host_budget = BudgetedResource(gov, 1 << 30, is_cpu=True)
    try:
        rows, verified, stats = run_streaming_q5(
            mesh, iter(chunks), tmpdir=str(tmp_path / "q5shuf"),
            n_buckets=4, host_budget=host_budget, task_id=7, verify=True)
    finally:
        MemoryGovernor.shutdown()
    assert verified is True
    assert rows == want
    assert stats["rows_in"] == sum(len(c[2]["date"]) for c in chunks)
    assert stats["max_bucket_rows"] < stats["rows_in"]
    assert stats["host_peak_reserved"] > 0
    assert host_budget.used == 0


@pytest.mark.slow
def test_streaming_q5_oversized_bucket_splits(tmp_path):
    """An over-budget q5 bucket must recursively split on disk and still
    produce the exact global rollup (partials additive under ANY row
    partition)."""
    import jax

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models.q5 import q5_local
    from spark_rapids_jni_tpu.models.streaming import (
        generate_q5_chunks,
        run_streaming_q5,
    )
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    mesh = make_mesh((len(jax.devices()), 1))
    chunks = list(generate_q5_chunks(sf=0.02, seed=8, chunk_rows=3000))
    want = q5_local(_assemble_q5(chunks))

    gov = MemoryGovernor(watchdog_period_s=0.02)
    dev_budget = BudgetedResource(gov, 1 << 30)
    # sf=0.02 -> ~111k rows over 2 buckets at 32-40 B/row JCUDF -> ~2 MB
    # per bucket; a 192 KB host budget forces recursive disk splits
    host_budget = BudgetedResource(gov, 192 << 10, is_cpu=True)
    try:
        rows, verified, stats = run_streaming_q5(
            mesh, iter(chunks), tmpdir=str(tmp_path / "q5shuf"),
            n_buckets=2, budget=dev_budget, host_budget=host_budget,
            task_id=8, verify=True)
    finally:
        gov.close()
    assert rows == want
    assert verified is True
    assert stats["bucket_splits"] >= 2, stats
    assert host_budget.used == 0
    assert host_budget.peak <= 192 << 10


@pytest.mark.slow
@pytest.mark.parametrize("nprocs,buckets", [(2, 8), (4, 10)])
def test_bucket_ownership_partitions_across_processes(nprocs, buckets):
    """The pod-scale deployment shape: N OS processes ('host groups')
    each execute only the buckets they OWN over the same chunk stream;
    the sum of their partials equals the global q97 answer.  The (4, 10)
    case has an owner count that does NOT divide n_buckets, so owners
    carry unequal bucket shares ({0,4,8}, {1,5,9}, {2,6}, {3,7})."""
    import json
    import os
    import subprocess
    import sys

    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle

    sf, chunk_rows = 0.002, 2000
    chunks = list(generate_q97_chunks(sf, seed=13, chunk_rows=chunk_rows))
    store = (np.concatenate([c for s, c, _ in chunks if s == "store"]),
             np.concatenate([i for s, _, i in chunks if s == "store"]))
    cat = (np.concatenate([c for s, c, _ in chunks if s == "catalog"]),
           np.concatenate([i for s, _, i in chunks if s == "catalog"]))
    want = q97_host_oracle(store, cat)

    from conftest import scrubbed_cpu_env

    env = scrubbed_cpu_env(8)

    worker = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "streaming_worker.py")
    totals = [0, 0, 0]
    rows_seen = set()
    # sequential on the 1-core box: the contract under test is the
    # bucket-space partitioning, not wall-clock parallelism
    for pid in range(nprocs):
        r = subprocess.run(
            [sys.executable, worker, str(pid), str(nprocs), str(sf),
             str(chunk_rows), str(buckets)],
            env=env, capture_output=True, text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-1500:]
        out = json.loads(r.stdout.strip().splitlines()[-1])
        assert out["proc"] == pid
        rows_seen.add(out["rows_in"])
        for i in range(3):
            totals[i] += out["counts"][i]
    assert tuple(totals) == want, (totals, want)
    # each owner saw the full stream but executed only its buckets
    assert rows_seen == {len(store[0]) + len(cat[0])}
