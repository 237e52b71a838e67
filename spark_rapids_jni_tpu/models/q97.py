"""Mini NDS q97: a distributed two-table join-count over the device mesh.

BASELINE.md staged config 5 is "NDS TPC-DS q5+q97 end-to-end"; this module is
the framework-native q97 core.  TPC-DS q97 counts (customer_sk, item_sk)
pairs sold in store only, catalog only, and both, from two fact tables —
i.e. a full outer join on a composite key reduced to presence counts:

    SELECT SUM(store_only), SUM(catalog_only), SUM(both) FROM
      (SELECT customer_sk, item_sk FROM store_sales GROUP BY 1,2) ss
      FULL OUTER JOIN
      (SELECT customer_sk, item_sk FROM catalog_sales GROUP BY 1,2) cs
      USING (customer_sk, item_sk)

The query runs as ONE compiled plan (:func:`q97_plan`, executed by
plans/runtime): both fact scans project the packed int64 composite key,
union with a source tag, exchange by key hash over the data axis (one
tagged all_to_all co-locates every distinct key on its owner shard), and
the ``PresenceCount`` sink sorts each shard's rows and counts equal-key
runs by which sources appear (:func:`_count_runs`, a static-shape
sort-merge "join": no dynamic hash table).  :func:`run_distributed_q97`
drives it under the memory governor; :func:`q97_host_oracle` is the
host-set reference.

The nullable variant (:func:`make_distributed_q97_columns`) takes Column
keys with null validity, which the plan IR has no form for yet: it keys
on a two-limb (k_hi, k_lo) pair through the columnar table shuffle and
counts with the same :func:`_count_runs`.

Shuffled row counts are data-dependent; capacity is a static bound with
overflow reported (parallel/shuffle.py) — the caller retries with a larger
capacity exactly like a Spark shuffle spill retry.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_jni_tpu.parallel.shuffle import partition_of
from spark_rapids_jni_tpu.plans import ir as ir_mod


class Q97Out(NamedTuple):
    store_only: jnp.ndarray  # scalar int32
    catalog_only: jnp.ndarray
    both: jnp.ndarray
    dropped: jnp.ndarray  # shuffle capacity overflows (0 == exact result)


def _count_runs(keys: Union[jnp.ndarray, Tuple[jnp.ndarray, ...]],
                is_store: jnp.ndarray, valid: jnp.ndarray):
    """Sort-merge presence counting over one shard's co-located rows.

    ``keys`` is one int64 key array or a tuple of int64 key limbs (most
    significant first); a key is the tuple of its limbs.  For every
    distinct valid key: did it appear with a store tag, a catalog tag, or
    both?  Returns (store_only, catalog_only, both) scalars.
    """
    limbs = keys if isinstance(keys, tuple) else (keys,)
    # order by key; invalid rows sort last via the max sentinel
    sentinel = jnp.int64(0x7FFFFFFFFFFFFFFF)
    ks = [jnp.where(valid, k, sentinel) for k in limbs]
    order = (jnp.argsort(ks[0]) if len(ks) == 1
             else jnp.lexsort(tuple(reversed(ks))))
    ks = [k[order] for k in ks]
    store_s = jnp.where(valid, is_store, False)[order]
    cat_s = jnp.where(valid, ~is_store, False)[order]

    # run starts: first element or a change in any limb
    n = ks[0].shape[0]
    run_start = functools.reduce(jnp.logical_or, [
        k != jnp.concatenate([k[:1] - 1, k[:-1]]) for k in ks])
    run_id = jnp.cumsum(run_start.astype(jnp.int32)) - 1

    # per-run presence via segment max (bounded by n runs)
    has_store = jax.ops.segment_max(
        store_s.astype(jnp.int32), run_id, num_segments=n
    )
    has_cat = jax.ops.segment_max(
        cat_s.astype(jnp.int32), run_id, num_segments=n
    )
    run_valid = jax.ops.segment_max(
        (ks[0] != sentinel).astype(jnp.int32), run_id, num_segments=n
    )
    has_store = has_store * run_valid
    has_cat = has_cat * run_valid
    both = jnp.sum((has_store & has_cat).astype(jnp.int32))
    store_only = jnp.sum((has_store & (1 - has_cat)).astype(jnp.int32))
    cat_only = jnp.sum((has_cat & (1 - has_store)).astype(jnp.int32))
    return store_only, cat_only, both


def q97_host_oracle(store, catalog):
    """(store_only, catalog_only, both) via host sets — the reference
    semantics both the NDS harness and the monte-carlo workload verify
    against (non-null keys)."""
    s = set(zip(store[0].tolist(), store[1].tolist()))
    c = set(zip(catalog[0].tolist(), catalog[1].tolist()))
    return len(s - c), len(c - s), len(s & c)


@functools.lru_cache(maxsize=64)
def q97_plan(capacity: int) -> ir_mod.Plan:
    """The whole distributed q97 pipeline as ONE plan: two fact scans
    project the packed composite key, union with a source tag, exchange
    by key hash (static ``capacity`` is plan structure — one compiled
    variant per pow2 capacity, as the lru step cache kept before), then
    sort-merge presence counting.  Mesh-only (contains an Exchange)."""
    from spark_rapids_jni_tpu.plans.ir import Bin, Cast, col, lit

    key = Bin("bor",
              Bin("shl", Cast(col("cust"), "int64"), lit(32)),
              Bin("band", Cast(col("item"), "int64"), lit(0xFFFFFFFF)))
    store = ir_mod.Project(ir_mod.Scan("store", ("cust", "item")),
                           (("key", key),))
    catalog = ir_mod.Project(ir_mod.Scan("catalog", ("cust", "item")),
                             (("key", key),))
    node = ir_mod.Union((store, catalog), tag="tag", tag_values=(1, 0))
    node = ir_mod.Exchange(node, key=col("key"), capacity=int(capacity),
                           fields=("key", "tag"))
    return ir_mod.Plan("q97", (ir_mod.PresenceCount(node, key="key",
                                                    tag="tag"),))


# ------------------------------------------------------- nullable columns --
# q97 over real Column inputs with per-column null validity.  SQL semantics:
# NULL keys group *within* a side (DISTINCT treats NULLs as one group) but
# never join *across* sides (NULL = NULL is unknown), so a side's null-key
# groups count as that side's "only" rows.

def _pair_key(cust, cust_valid, item, item_valid, side: int):
    """(k_hi, k_lo) 2-limb group key over nullable (cust, item) int32 pairs.

    Each component widens to 33 bits (value | null flag); rows with any
    null key additionally carry a null marker + the side bit in k_lo so
    null groups stay side-local (never equal across tables).
    """
    # null slots must not leak their underlying data bits into the group key
    # (invalid data is garbage by contract): normalize them to 0|nullflag
    c_ext = jnp.where(cust_valid, cust.astype(jnp.int64) & 0xFFFFFFFF,
                      jnp.int64(1) << 32)
    i_ext = jnp.where(item_valid, item.astype(jnp.int64) & 0xFFFFFFFF,
                      jnp.int64(1) << 32)
    null_any = (~cust_valid) | (~item_valid)
    marker = jnp.int64((2 | (side & 1)) << 33)
    k_lo = i_ext | jnp.where(null_any, marker, jnp.int64(0))
    return c_ext, k_lo


def _sharded_q97_columns(s_cust, s_item, c_cust, c_item, s_rv, c_rv,
                         capacity: int):
    """Per-device body over Column pytrees with nullable keys.

    ``s_rv``/``c_rv`` mark padding rows (row does not exist); a null *key*
    in an existing row is data, handled by the pair-key null semantics.
    The whole table rides one tagged exchange through the columnar
    shuffle (parallel/table_shuffle.py).
    """
    from spark_rapids_jni_tpu.columnar.column import Column
    from spark_rapids_jni_tpu.columnar.dtypes import INT64 as _I64
    from spark_rapids_jni_tpu.parallel.table_shuffle import shuffle_table

    dp = jax.lax.axis_size(DATA_AXIS)
    skh, skl = _pair_key(s_cust.data, s_cust.is_valid(),
                         s_item.data, s_item.is_valid(), side=1)
    ckh, ckl = _pair_key(c_cust.data, c_cust.is_valid(),
                         c_item.data, c_item.is_valid(), side=0)
    k_hi = jnp.concatenate([skh, ckh])
    k_lo = jnp.concatenate([skl, ckl])
    tag = jnp.concatenate(
        [jnp.ones(skh.shape, jnp.int8), jnp.zeros(ckh.shape, jnp.int8)]
    )
    row_valid = jnp.concatenate([s_rv, c_rv])

    mixed = k_hi ^ (k_lo * jnp.int64(-7046029254386353131))  # golden-ratio mix
    part = partition_of(mixed, dp)
    ex = shuffle_table(
        {
            "kh": Column(k_hi, None, _I64),
            "kl": Column(k_lo, None, _I64),
            "tag": Column(tag, None, _I64),
        },
        part, capacity, axis=DATA_AXIS, row_valid=row_valid,
    )
    so, co, b = _count_runs(
        (ex.columns["kh"].data, ex.columns["kl"].data),
        ex.columns["tag"].data == 1, ex.valid,
    )
    axes = (DATA_AXIS,)
    return Q97Out(
        jax.lax.psum(so, axes),
        jax.lax.psum(co, axes),
        jax.lax.psum(b, axes),
        jax.lax.psum(ex.dropped, axes),
    )


def make_distributed_q97_columns(mesh, capacity: int):
    """jit-compiled distributed q97 over nullable Column keys.

    Inputs: four int32 Columns (store customer/item, catalog customer/item,
    each optionally with a validity mask) plus two bool row-valid arrays for
    padding, all sharded over DATA_AXIS.
    """
    def body(s_cust, s_item, c_cust, c_item, s_rv, c_rv):
        return _sharded_q97_columns(s_cust, s_item, c_cust, c_item,
                                    s_rv, c_rv, capacity)

    step = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=tuple(P(DATA_AXIS) for _ in range(6)),
        out_specs=Q97Out(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(step)


# ---------------------------------------------------------------- governed --
# The host-driven control loop around the jitted step: batch admission through
# the memory arbiter, key-space split-and-retry, shuffle-capacity-grow retry.
# This is the protocol of RmmSpark.java:402-416 driving a real query.


@dataclasses.dataclass(frozen=True)
class Q97Batch:
    """One (sub-)batch of host rows: the store and catalog key columns.

    ``split_depth`` tracks which key-space bit splits this piece next;
    ``capacity`` is the per-destination shuffle bucket bound.
    """

    s_cust: np.ndarray
    s_item: np.ndarray
    c_cust: np.ndarray
    c_item: np.ndarray
    capacity: int
    split_depth: int = 0

    @property
    def rows(self) -> int:
        return len(self.s_cust) + len(self.c_cust)


def _split_hash(cust: np.ndarray, item: np.ndarray) -> np.ndarray:
    """Mixing hash of the composite key for key-space splitting (host)."""
    packed = (cust.astype(np.int64) << 32) | (item.astype(np.int64) & 0xFFFFFFFF)
    return packed.astype(np.uint64) * np.uint64(0x9E3779B97F4A7C15)


def split_q97_batch(batch: Q97Batch):
    """Split the *key space* in half (bit ``split_depth`` of a mixing hash).

    Unlike a row split, a key-space split is exact for q97: every distinct
    key lands wholly in one child (both tables filtered by the same
    predicate), so the three presence counters sum across children.

    Each child also halves the shuffle capacity — the exchange buffers
    dominate the working set, and a child carries ~half the rows; if that
    undershoots, the grow retry recovers it.
    """
    bit = np.uint64(63 - batch.split_depth)
    parts = []
    for side in (0, 1):
        sm = ((_split_hash(batch.s_cust, batch.s_item) >> bit) & 1) == side
        cm = ((_split_hash(batch.c_cust, batch.c_item) >> bit) & 1) == side
        parts.append(dataclasses.replace(
            batch,
            s_cust=batch.s_cust[sm], s_item=batch.s_item[sm],
            c_cust=batch.c_cust[cm], c_item=batch.c_item[cm],
            capacity=max(16, batch.capacity // 2),
            split_depth=batch.split_depth + 1,
        ))
    return parts


def q97_working_set_bytes(batch: Q97Batch, dp: int) -> int:
    """Global working-set estimate: inputs + key/tag/valid stream + the
    [dp, capacity] send/recv exchange buffers + sort-merge workspace.
    Row terms use the QUANTIZED (padded) lengths run() actually uploads,
    so admission covers the real device footprint."""
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    n = (quantized_rows(len(batch.s_cust), dp)
         + quantized_rows(len(batch.c_cust), dp))
    per_row = 8 + 1 + 1  # key int64 + tag int8 + row_valid bool
    slots = dp * dp * batch.capacity
    return n * (8 + per_row) + 2 * slots * per_row + 2 * slots * 10


def _pad_to_multiple(arr: np.ndarray, mult: int, fill=0):
    """Pad to the dp-aligned POW2-QUANTIZED batch length (bounded compile
    variants — see parallel.shuffle.quantized_rows); pad rows are
    validity-masked out."""
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    pad = quantized_rows(len(arr), mult) - len(arr)
    if pad == 0:
        return arr, np.ones(len(arr), bool)
    padded = np.concatenate([arr, np.full(pad, fill, dtype=arr.dtype)])
    valid = np.concatenate([np.ones(len(arr), bool), np.zeros(pad, bool)])
    return padded, valid


def default_q97_capacity(total_rows: int, dp: int) -> int:
    """Safe-ish default per-(sender,dest) bucket bound: uniform share with
    a 2x skew margin (overflow is recoverable via the grow retry),
    pow2-rounded so data-dependent totals reuse one compiled step
    (capacity is a static shape parameter — the streamed-soak compiler
    OOM came from one executable per distinct capacity)."""
    from spark_rapids_jni_tpu.columnar.column import next_pow2

    raw = max(16, int(2 * total_rows / (dp * dp)) if dp > 1 else total_rows)
    return next_pow2(raw)


def run_q97_piece(mesh, piece: Q97Batch) -> Q97Out:
    """One FUSED launch of one q97 (sub-)batch through the compiled plan.

    The single-attempt core shared by :func:`run_distributed_q97` (which
    splits inline via run_with_split_retry) and the serving engine's q97
    handler (serve/executor.py, which splits by re-queueing halves) —
    both re-execute the whole fused program per piece, never a per-op
    disband.  Pad/upload/launch live in plans/runtime.execute_plan;
    compiled variants are cached on (plan structure, dtype signature,
    pow2 batch bucket).  Raises :class:`ShuffleCapacityExceeded` when
    rows overflowed the piece's static exchange capacity (the caller
    grows and re-runs).
    """
    from spark_rapids_jni_tpu.plans.runtime import execute_plan

    out = execute_plan(mesh, q97_plan(piece.capacity), {
        "store": {"cust": piece.s_cust, "item": piece.s_item},
        "catalog": {"cust": piece.c_cust, "item": piece.c_item},
    })
    return Q97Out(out["store_only"], out["catalog_only"], out["both"],
                  out["dropped"])


def combine_q97_outs(outs) -> Q97Out:
    """Sum partial presence counts (additive across key-space pieces)."""
    return Q97Out(
        sum(int(o.store_only) for o in outs),
        sum(int(o.catalog_only) for o in outs),
        sum(int(o.both) for o in outs),
        0,
    )


def run_distributed_q97(
    mesh,
    store,
    catalog,
    *,
    budget=None,
    task_id: int = 0,
    capacity: Optional[int] = None,
    max_split_depth: int = 8,
    manage_task: bool = True,
) -> Q97Out:
    """Governed distributed q97 over host (numpy) inputs.

    ``store``/``catalog`` are (customer_sk, item_sk) int32 array pairs.
    Every device launch is admitted through the memory arbiter: the working
    set is reserved before the step runs (mem/governed.py), RetryOOM retries,
    SplitAndRetryOOM splits the key space (exact), and shuffle-capacity
    overflow (dropped > 0) grows the exchange buffers and re-reserves.

    Reference protocol: RmmSpark.java:402-416; admission point analog of
    SparkResourceAdaptorJni.cpp:1731 do_allocate.

    ``manage_task=False`` joins a task context the caller already registered
    (the Spark shape: one dedicated thread registered per task runs many
    ops); the default registers/ends ``task_id`` itself, under a ``task``
    root span when the thread has no trace context (obs/trace.py).
    """
    from spark_rapids_jni_tpu.mem.governed import (
        default_device_budget,
        run_with_split_retry,
        task_context,
    )

    dp = mesh.shape[DATA_AXIS]
    s_cust, s_item = (np.asarray(a, np.int32) for a in store)
    c_cust, c_item = (np.asarray(a, np.int32) for a in catalog)
    if budget is None:
        budget = default_device_budget()
    total = len(s_cust) + len(c_cust)
    cap0 = capacity if capacity is not None else default_q97_capacity(total, dp)
    batch = Q97Batch(s_cust, s_item, c_cust, c_item, capacity=cap0)

    def run(piece: Q97Batch) -> Q97Out:
        return run_q97_piece(mesh, piece)

    import contextlib

    from spark_rapids_jni_tpu.obs import trace as _trace

    ctx = (task_context(budget.gov, task_id) if manage_task
           else contextlib.nullcontext())
    root = (_trace.task_span(task_id, extra="plan:q97") if manage_task
            else contextlib.nullcontext())
    with root, ctx:
        return run_with_split_retry(
            budget, batch,
            nbytes_of=lambda b: q97_working_set_bytes(b, dp),
            run=run,
            split=split_q97_batch,
            combine=combine_q97_outs,
            grow=lambda b: dataclasses.replace(b, capacity=2 * b.capacity),
            max_split_depth=max_split_depth,
        )
