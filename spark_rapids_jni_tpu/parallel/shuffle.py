"""Columnar hash-repartition (shuffle) over the device mesh.

The reference's shuffle transport is UCX in the host Spark plugin; this module is
its TPU-native replacement (SURVEY.md §2.3 planning note): rows move between
devices with a single dense `all_to_all` over ICI/DCN instead of point-to-point
RDMA.  XLA requires static shapes, so the exchange uses fixed-capacity buckets:

    local rows --stable sort by destination (hash % ndev), columns as
               payload--> ndev contiguous runs
               --one length-capacity slice per run--> [ndev, capacity]
               padded send buffer
               --all_to_all--> [ndev, capacity] receive buffer + slot-valid mask

Slot ``p * capacity + r`` of the send buffer holds the r-th row bound for
device p, in local row order.  The runs' starts come from a binary search over
the sorted destinations, so the buffers are built by copies: no scatter, no
per-row counting.

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
    traced program guarantees).  No benchmark cell measures the two yet;
    flip the default only on a measured win in ``benchmark/``."""
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
    (``jax.named_scope``): ``exchange_bucket`` (the sort by destination and
    the runs' bounds), ``exchange_scatter`` (the send buffers, sliced from
    the sorted runs) and ``exchange_all_to_all``, so a device trace can
    attribute time to them.
    """
    ndev = jax.lax.axis_size(axis)
    n = part.shape[0]
    with jax.named_scope("exchange_bucket"):
        part = part.astype(jnp.int32)
        if row_valid is not None:
            # invalid rows sort after every real destination: never sent,
            # never in a run, never counted in ``dropped``
            part = jnp.where(row_valid, part, ndev)
        # 1-D columns ride the sort as payload; columns with trailing dims
        # are gathered by the sorted permutation, which rides in their place
        flat = [k for k, v in columns.items() if v.ndim == 1]
        wide = len(flat) < len(columns)
        payload = [columns[k] for k in flat]
        if wide:
            payload.append(jnp.arange(n, dtype=jnp.int32))
        sorted_part, *sorted_cols = jax.lax.sort(
            (part, *payload), num_keys=1, is_stable=True)
        starts = jnp.searchsorted(
            sorted_part, jnp.arange(ndev + 1, dtype=jnp.int32)
        ).astype(jnp.int32)
        counts = jnp.diff(starts)
        dropped = jnp.sum(jnp.maximum(counts - capacity, 0)).astype(jnp.int32)

    with jax.named_scope("exchange_scatter"):
        in_run = (jnp.arange(capacity, dtype=jnp.int32)[None, :]
                  < jnp.minimum(counts, capacity)[:, None])

        def zero_past_runs(x):
            m = in_run.reshape(in_run.shape + (1,) * (x.ndim - 2))
            return jnp.where(m, x, jnp.zeros((), x.dtype))

        def runs(stream):
            # [ndev, capacity]: each destination's run of the sorted stream,
            # padded so that no slice is clamped
            stream = jnp.pad(stream, (0, capacity))
            return zero_past_runs(jnp.stack([
                jax.lax.dynamic_slice_in_dim(stream, starts[p], capacity)
                for p in range(ndev)]))

        by_name = dict(zip(flat, sorted_cols))
        perm = runs(sorted_cols[-1]) if wide else None
        sends = {k: runs(by_name[k]) if k in by_name
                 else zero_past_runs(v[perm])
                 for k, v in columns.items()}

    with jax.named_scope("exchange_all_to_all"):
        recv_cols = {
            name: jax.lax.all_to_all(
                send, axis, split_axis=0, concat_axis=0, tiled=False
            ).reshape((ndev * capacity,) + send.shape[2:])
            for name, send in sends.items()
        }
        recv_valid = jax.lax.all_to_all(
            in_run, axis, split_axis=0, concat_axis=0, tiled=False
        ).reshape(ndev * capacity)
    return ShuffleResult(recv_cols, recv_valid, dropped)
