"""Columnar hash-repartition (shuffle) over the device mesh.

The reference's shuffle transport is UCX in the host Spark plugin; this module is
its TPU-native replacement (SURVEY.md §2.3 planning note): rows move between
devices with a single dense `all_to_all` over ICI/DCN instead of point-to-point
RDMA.  XLA requires static shapes, so the exchange uses fixed-capacity buckets:

    local rows --bucket by hash % ndev--> [ndev, capacity] padded send buffer
              --all_to_all--> [ndev, capacity] receive buffer + slot-valid mask

Capacity defaults to the local row count (no row can ever be dropped); callers
with bounded skew can pass a smaller capacity and check `dropped` (a per-shard
count of rows that exceeded a destination bucket, analogous to a shuffle spill
that the caller must retry with a bigger capacity).

All functions here run *inside* `shard_map` (they use axis names), composing
with the query-step pipelines in models/.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS


class ShuffleResult(NamedTuple):
    columns: Dict[str, jnp.ndarray]  # [ndev * capacity] received rows (padded)
    valid: jnp.ndarray  # bool[ndev * capacity] slot occupancy
    dropped: jnp.ndarray  # int32 scalar: rows lost to capacity overflow (local)


def partition_of(keys: jnp.ndarray, n_parts: int) -> jnp.ndarray:
    """Owning partition of each int64 key: the internal placement hash.

    Backend from the ``partition_hash`` config flag, read at TRACE time
    (a cached jitted step keeps the backend it was traced with):
    ``murmur3`` (default; Spark's placement hash) or ``mix32``
    (ops/hashing.partition_mix32 — pure u32 lane math, ~1/3 the multiply
    count; placement only needs every participant to agree, which one
    traced program guarantees).  The A/B lives in bench.py's
    partition-hash stage; flip the default to the measured winner."""
    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.ops.hashing import (
        murmur3_raw_int64,
        partition_mix32,
    )

    if config.get("partition_hash") == "mix32":
        h = partition_mix32(keys)
    else:
        h = murmur3_raw_int64(keys, 42)
    return (h % jnp.uint32(n_parts)).astype(jnp.int32)


def quantized_rows(n: int, mult: int) -> int:
    """Batch length that is a ``mult`` multiple AND pow2-quantized:
    ``mult * next_pow2(ceil(n / mult))`` (min one block).

    Data-dependent exact batch lengths compile one executable per
    distinct value, which a long-lived executor accumulates until the
    compiler OOMs (the streamed-soak LLVM allocation failure after ~500
    out-of-core runs); quantizing bounds the variant set to
    O(log max_rows) per geometry.  Padding rows are validity-masked by
    the callers, so more padding never changes results."""
    from spark_rapids_jni_tpu.columnar.column import next_pow2

    return mult * next_pow2(max(1, -(-int(n) // mult)))


def bucket_by_partition(part: jnp.ndarray, n_parts: int, capacity: int):
    """Assign each local row a slot in a [n_parts, capacity] send layout.

    Returns (slot index [n], in_capacity mask [n], per-bucket counts [n_parts]).
    Rows overflowing a bucket get mask False.
    """
    n = part.shape[0]
    # rank of each row within its partition = number of earlier rows with same part
    # computed stably via sort: order rows by partition, rank = position - start.
    order = jnp.argsort(part, stable=True)
    sorted_part = part[order]
    counts = jnp.bincount(part, length=n_parts).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)[:-1]]
    )
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_part]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    in_cap = rank < capacity
    slot = part.astype(jnp.int32) * capacity + jnp.minimum(rank, capacity - 1)
    return slot, in_cap, counts


def all_to_all_shuffle(
    columns: Dict[str, jnp.ndarray],
    part: jnp.ndarray,
    capacity: int,
    axis: str = DATA_AXIS,
    row_valid: jnp.ndarray | None = None,
) -> ShuffleResult:
    """Exchange rows so each device receives the rows whose ``part`` equals its
    index along ``axis``.  Must be called inside shard_map over ``axis``.

    ``row_valid`` (bool[n], optional) marks padding/invalid local rows: they
    are never sent, never occupy a capacity slot, and don't count in
    ``dropped`` — static-shape callers (governed runners padding a batch to a
    shard multiple) rely on this so pads can't evict real rows or trigger
    spurious capacity retries.

    The ops of its three phases carry stable names in the HLO metadata
    (``jax.named_scope``): ``exchange_bucket``, ``exchange_scatter`` (the
    send buffers) and ``exchange_all_to_all``, so a device trace can
    attribute time to them.
    """
    ndev = jax.lax.axis_size(axis)
    with jax.named_scope("exchange_bucket"):
        if row_valid is not None:
            # invalid rows ride the out-of-range bucket: excluded from
            # ranking, capacity, sending, and the dropped count
            part = jnp.where(row_valid, part, ndev)
        slot, in_cap, _counts = bucket_by_partition(part, ndev, capacity)
        sendable = in_cap if row_valid is None else in_cap & row_valid
        if row_valid is None:
            dropped = jnp.sum(~in_cap).astype(jnp.int32)
        else:
            dropped = jnp.sum(row_valid & ~in_cap).astype(jnp.int32)

    with jax.named_scope("exchange_scatter"):
        dest = jnp.where(sendable, slot, ndev * capacity)
        send_valid = (
            jnp.zeros((ndev * capacity,), jnp.bool_)
            .at[dest].set(True, mode="drop")
            .reshape(ndev, capacity)
        )
        sends = {
            name: jnp.zeros((ndev * capacity,) + data.shape[1:], data.dtype)
            .at[dest].set(data, mode="drop")
            .reshape((ndev, capacity) + data.shape[1:])
            for name, data in columns.items()
        }

    with jax.named_scope("exchange_all_to_all"):
        recv_cols = {
            name: jax.lax.all_to_all(
                send, axis, split_axis=0, concat_axis=0, tiled=False
            ).reshape((ndev * capacity,) + send.shape[2:])
            for name, send in sends.items()
        }
        recv_valid = jax.lax.all_to_all(
            send_valid, axis, split_axis=0, concat_axis=0, tiled=False
        ).reshape(ndev * capacity)
    return ShuffleResult(recv_cols, recv_valid, dropped)
