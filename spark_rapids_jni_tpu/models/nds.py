"""Flagship pipeline: a distributed columnar hash-aggregate query step.

This is the framework's "model": the representative NDS (TPC-DS-style) inner
loop that the BASELINE configs build toward — hash keys, bloom-filter
build+probe, shuffle rows to their owning partition, partial aggregation —
expressed as one jittable step over a 2D (data, model) mesh.

Parallelism mapping (the columnar-engine analog of NN-training axes):

- **dp**  = ``data`` mesh axis: rows of the batch are partition-parallel, the
  way Spark partitions map onto executors.
- **tp**  = ``model`` mesh axis: the bloom filter's bit array is sharded across
  chips; each chip owns a bit range and the probe combines per-shard verdicts
  with a psum (exactly a tensor-parallel reduce).
- **sp/ep analog** = the `all_to_all` shuffle: rows are exchanged to their hash
  owner, the same collective pattern sequence/expert parallelism uses.
- pp: no pipeline stages exist in a per-batch columnar engine; inter-op
  pipelining happens at the query-plan level (future work, see SURVEY.md §7.8).

Everything is static-shape and compiles once per batch geometry.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from spark_rapids_jni_tpu.ops.hashing import murmur3_raw_int64, xxhash64_raw_int64
from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
from spark_rapids_jni_tpu.parallel.shuffle import all_to_all_shuffle, partition_of


class QueryStepConfig(NamedTuple):
    n_buckets: int = 1024  # aggregation hash-table size per shard
    bloom_bits: int = 1 << 16  # total bloom bit count (sharded over model axis)
    bloom_hashes: int = 3  # k probe hashes
    shuffle_capacity: int = 0  # 0 == safe default (local row count)


class QueryStepOut(NamedTuple):
    bucket_sums: jnp.ndarray  # [n_buckets] per data-shard partial aggregate
    bucket_counts: jnp.ndarray  # [n_buckets]
    bloom_bits: jnp.ndarray  # [bloom_bits/mp] this model-shard's bit range
    probe_hits: jnp.ndarray  # scalar: rows passing the bloom probe (global)
    total_rows: jnp.ndarray  # scalar: global row count (psum'd)
    dropped: jnp.ndarray  # scalar: shuffle capacity overflows (global)


def _bloom_positions(keys: jnp.ndarray, k: int, total_bits: int) -> list:
    """k bit-position arrays, each [n], via double hashing from two murmur
    seeds: position i is (h1 + (i+1) * h2) % total_bits.

    Kept as k separate 1-D arrays, never one [n, k] matrix: on the TPU a
    minor dimension of k pads to 128 lanes, which made the entry step need
    ~1 KiB of HBM per row.

    (Not the Spark sketch's exact bit layout — ops/bloom_filter.py owns
    Spark-serialization-compatible filters; this one is internal to the
    pipeline and only needs self-consistency.)
    """
    h1 = murmur3_raw_int64(keys, 0).astype(jnp.int64)
    h2 = murmur3_raw_int64(keys, 0x9747B28C).astype(jnp.int64)
    return [(h1 + i * h2) % total_bits for i in range(1, k + 1)]


def local_query_step(keys: jnp.ndarray, values: jnp.ndarray, cfg: QueryStepConfig):
    """Single-chip forward step: hash + bloom build/probe + bucket aggregation.

    This is the compile-checked `entry()` function of the framework.
    """
    h = xxhash64_raw_int64(keys)
    bucket = (h % jnp.uint64(cfg.n_buckets)).astype(jnp.int32)
    sums = jax.ops.segment_sum(values, bucket, num_segments=cfg.n_buckets)
    counts = jax.ops.segment_sum(
        # analyze: ignore[governed-allocation] - the compile-checked
        # entry() oracle: the count vector is n_buckets int32 beside the
        # resident fact columns; governed execution goes through
        # run_distributed / the plan tier, never this reference body
        jnp.ones_like(values, dtype=jnp.int32), bucket, num_segments=cfg.n_buckets
    )
    pos = _bloom_positions(keys, cfg.bloom_hashes, cfg.bloom_bits)
    # analyze: ignore[governed-allocation] - bloom_bits u8 bitmap,
    # same oracle path: sized by config, not by data, bounded small
    bits = jnp.zeros((cfg.bloom_bits,), jnp.uint8)
    for p in pos:
        bits = bits.at[p].max(1)
    probed = sum(bits[p].astype(jnp.int32) for p in pos) == cfg.bloom_hashes
    return sums, counts, bits, probed.sum()


def _sharded_step(keys, values, cfg: QueryStepConfig):
    """The body run per device under shard_map over (data, model)."""
    dp = jax.lax.axis_size(DATA_AXIS)
    mp = jax.lax.axis_size(MODEL_AXIS)
    m_idx = jax.lax.axis_index(MODEL_AXIS)
    n_local = keys.shape[0]

    # 1. bloom build, bits sharded over the model axis (tp): each chip sets only
    #    bits in its owned range, then ORs partial bitmaps across the data axis.
    #    Positions mod the *effective* total (bits_per_shard * mp) so no bit
    #    range is orphaned when bloom_bits isn't divisible by the mesh.
    bits_per_shard = cfg.bloom_bits // mp
    pos = _bloom_positions(keys, cfg.bloom_hashes, bits_per_shard * mp)
    lo = m_idx.astype(jnp.int64) * bits_per_shard
    local_pos = [p - lo for p in pos]
    in_range = [(lp >= 0) & (lp < bits_per_shard) for lp in local_pos]
    local_bits = jnp.zeros((bits_per_shard,), jnp.uint8)
    for lp, ok in zip(local_pos, in_range):
        local_bits = local_bits.at[jnp.where(ok, lp, bits_per_shard)].max(
            1, mode="drop")
    local_bits = jax.lax.pmax(local_bits, DATA_AXIS)

    # 2. bloom probe (tp reduce): each model shard counts the probe bits it
    #    owns and has set; a row passes iff the psum over shards reaches k.
    set_here = sum(
        jnp.where(ok, local_bits[jnp.clip(lp, 0, bits_per_shard - 1)]
                  .astype(jnp.int32), 0)
        for lp, ok in zip(local_pos, in_range))
    set_total = jax.lax.psum(set_here, MODEL_AXIS)
    probe_hits = jax.lax.psum((set_total == cfg.bloom_hashes).sum(), DATA_AXIS)

    # 3. shuffle rows to their hash-owner partition (the sp/ep-style all_to_all)
    part = partition_of(keys, dp)
    capacity = cfg.shuffle_capacity or n_local
    shuffled = all_to_all_shuffle(
        {"keys": keys, "values": values}, part, capacity, axis=DATA_AXIS
    )

    # 4. local partial aggregation of owned rows into static buckets
    sk = shuffled.columns["keys"]
    sv = jnp.where(shuffled.valid, shuffled.columns["values"], 0)
    bucket = (xxhash64_raw_int64(sk) % jnp.uint64(cfg.n_buckets)).astype(jnp.int32)
    bucket = jnp.where(shuffled.valid, bucket, cfg.n_buckets)  # pad slot -> dropped
    sums = jax.ops.segment_sum(sv, bucket, num_segments=cfg.n_buckets + 1)[:-1]
    counts = jax.ops.segment_sum(
        shuffled.valid.astype(jnp.int32), bucket, num_segments=cfg.n_buckets + 1
    )[:-1]

    total_rows = jax.lax.psum(
        jnp.asarray(n_local, jnp.int32), (DATA_AXIS, MODEL_AXIS)
    ) // mp
    dropped = jax.lax.psum(shuffled.dropped, (DATA_AXIS, MODEL_AXIS)) // mp
    return QueryStepOut(sums, counts, local_bits, probe_hits, total_rows, dropped)


def make_distributed_query_step(mesh, cfg: QueryStepConfig):
    """jit-compiled full distributed step over ``mesh`` (axes data, model)."""
    step = jax.shard_map(
        functools.partial(_sharded_step, cfg=cfg),
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=QueryStepOut(
            bucket_sums=P(DATA_AXIS),
            bucket_counts=P(DATA_AXIS),
            bloom_bits=P(MODEL_AXIS),
            probe_hits=P(),
            total_rows=P(),
            dropped=P(),
        ),
        check_vma=False,
    )
    return jax.jit(step)


def make_example_batch(n: int, key=None):
    """Tiny synthetic (keys int64, values int64) batch."""
    if key is None:
        key = jax.random.PRNGKey(0)
    k1, k2 = jax.random.split(key)
    keys = jax.random.randint(k1, (n,), 0, 1 << 20, dtype=jnp.int64)
    values = jax.random.randint(k2, (n,), 0, 1000, dtype=jnp.int64)
    return keys, values
