"""Distributed layer tests on the virtual 8-device CPU mesh (see conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_jni_tpu.parallel import (
    all_to_all_shuffle,
    make_mesh,
)
from spark_rapids_jni_tpu.models import (
    QueryStepConfig,
    make_distributed_query_step,
    make_example_batch,
)


@pytest.mark.parametrize("ndev", [2, 4, 8])
@pytest.mark.slow
def test_all_to_all_shuffle_routes_rows(ndev):
    mesh = make_mesh((ndev, 1), devices=jax.devices()[:ndev])
    n_local = 16
    n = ndev * n_local
    rng = np.random.RandomState(0)
    keys = jnp.asarray(rng.randint(0, 1000, size=n).astype(np.int64))
    part = (keys % ndev).astype(jnp.int32)

    def body(keys, part):
        res = all_to_all_shuffle({"k": keys}, part, capacity=n_local, axis="data")
        me = jax.lax.axis_index("data")
        # every valid received row must belong to this device
        ok = jnp.all(
            jnp.where(res.valid, res.columns["k"] % ndev == me.astype(jnp.int64), True)
        )
        n_recv = res.valid.sum()
        return ok[None], n_recv[None], res.dropped[None]

    f = jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(P("data"), P("data")),
            out_specs=(P("data"), P("data"), P("data")),
            check_vma=False,
        )
    )
    ok, n_recv, dropped = f(keys, part)
    assert bool(jnp.all(ok))
    assert int(jnp.sum(n_recv)) + int(jnp.sum(dropped)) == n
    # with capacity == n_local there can still be drops under skew; this data is
    # near-uniform so expect none
    assert int(jnp.sum(dropped)) == 0


@pytest.mark.parametrize("shape", [(8, 1), (4, 2), (2, 4)])
@pytest.mark.slow
def test_distributed_query_step(shape):
    dp, mp = shape
    mesh = make_mesh(shape)
    cfg = QueryStepConfig(n_buckets=128, bloom_bits=1 << 12, bloom_hashes=3)
    rows = 128 * dp
    keys, values = make_example_batch(rows)
    keys = jax.device_put(keys, NamedSharding(mesh, P("data")))
    values = jax.device_put(values, NamedSharding(mesh, P("data")))
    out = make_distributed_query_step(mesh, cfg)(keys, values)

    assert int(out.total_rows) == rows
    assert int(out.dropped) == 0
    # conservation: no row or value lost through the shuffle + aggregation
    assert int(jnp.sum(out.bucket_counts)) == rows
    assert int(jnp.sum(out.bucket_sums)) == int(jnp.sum(values))
    # bloom has no false negatives on inserted keys
    assert int(out.probe_hits) == rows


@pytest.mark.slow
def test_distributed_matches_single_chip_totals():
    mesh = make_mesh((8, 1))
    cfg = QueryStepConfig(n_buckets=64, bloom_bits=1 << 12, bloom_hashes=3)
    keys, values = make_example_batch(512)
    ks = jax.device_put(keys, NamedSharding(mesh, P("data")))
    vs = jax.device_put(values, NamedSharding(mesh, P("data")))
    out = make_distributed_query_step(mesh, cfg)(ks, vs)

    # single-chip oracle: global bucket histogram must match the union of the
    # distributed per-shard partials (each key is shuffled to exactly one shard,
    # so summing shard-local buckets reproduces the global histogram).
    from spark_rapids_jni_tpu.ops.hashing import xxhash64_raw_int64

    bucket = (xxhash64_raw_int64(keys) % jnp.uint64(cfg.n_buckets)).astype(jnp.int32)
    expected = jax.ops.segment_sum(values, bucket, num_segments=cfg.n_buckets)
    got = out.bucket_sums.reshape(8, cfg.n_buckets).sum(axis=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expected))


def test_multihost_single_process_noop_and_pod_mesh():
    """initialize() is a no-op single-process; make_pod_mesh falls back to a
    flat (data, model) mesh when no slice topology exists (CPU mesh)."""
    import jax

    from spark_rapids_jni_tpu.parallel import (
        initialize_multihost,
        is_multihost,
        make_pod_mesh,
    )

    initialize_multihost()  # must not raise or require a coordinator
    assert not is_multihost()
    mesh = make_pod_mesh(mp=2)
    n = len(jax.devices())
    assert mesh.shape["data"] == n // 2 and mesh.shape["model"] == 2
    summary_keys = {"process_index", "process_count",
                    "local_devices", "global_devices"}
    from spark_rapids_jni_tpu.parallel.multihost import process_summary

    assert set(process_summary()) == summary_keys


def test_partition_mix32_placement_backend():
    """The cheap mix32 placement hash (partition_hash config): spreads
    dense keys, is deterministic, and a distributed q97 traced under it
    still matches the host oracle — placement choice can never change
    results, only where rows land."""
    import numpy as np

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.models.q97 import (
        make_distributed_q97,
        q97_host_oracle,
    )
    from spark_rapids_jni_tpu.ops.hashing import partition_mix32
    from spark_rapids_jni_tpu.parallel.shuffle import partition_of

    rng = np.random.RandomState(2)
    # dense TPC-DS-ish packed pairs (the worst case for a weak mix)
    cust = rng.randint(1, 4000, 8192).astype(np.int64)
    item = rng.randint(1, 18000, 8192).astype(np.int64)
    keys = jnp.asarray((cust << 32) | item)
    h1 = np.asarray(partition_mix32(keys))
    h2 = np.asarray(partition_mix32(jnp.asarray(np.asarray(keys))))
    assert np.array_equal(h1, h2)
    counts = np.bincount(h1 % 8, minlength=8)
    assert counts.max() < 2 * len(cust) / 8, counts

    with config.override(partition_hash="mix32"):
        part = np.asarray(jax.jit(
            lambda k: partition_of(k, 8))(keys))
        assert np.array_equal(part, h1 % 8)

        mesh = make_mesh((8, 1))
        n = 8 * 64
        s = (jnp.asarray(cust[:n].astype(np.int32)),
             jnp.asarray(item[:n].astype(np.int32)))
        c = (jnp.asarray(cust[n:2 * n].astype(np.int32)),
             jnp.asarray(item[n:2 * n].astype(np.int32)))
        step = make_distributed_q97(mesh, capacity=2 * n)
        out = step(*s, *c)  # traced INSIDE the override: mix32 placement
    want = q97_host_oracle((np.asarray(s[0]), np.asarray(s[1])),
                           (np.asarray(c[0]), np.asarray(c[1])))
    assert (int(out.store_only), int(out.catalog_only),
            int(out.both)) == want
    assert int(out.dropped) == 0
