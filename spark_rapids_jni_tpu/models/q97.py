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

Distributed plan (the Spark plan's TPU-native shape):

1. hash the composite key per row (Spark murmur3 row hashing, ops/hashing);
2. all_to_all shuffle BOTH tables by ``hash % ndev`` over the data axis —
   co-locating every distinct key on one owner shard (the exchange Spark
   does with its UCX shuffle, here one ICI collective);
3. per shard: sort the union of (key, source-tag) pairs and count
   equal-key runs by which sources appear — a static-shape sort-merge
   "join" (XLA-friendly: no dynamic hash table);
4. psum the three counters over the mesh.

Shuffled row counts are data-dependent; capacity is a static bound with
overflow reported (parallel/shuffle.py) — the caller retries with a larger
capacity exactly like a Spark shuffle spill retry.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_jni_tpu.parallel.shuffle import all_to_all_shuffle, partition_of
from spark_rapids_jni_tpu.plans import ir as ir_mod


class Q97Out(NamedTuple):
    store_only: jnp.ndarray  # scalar int32
    catalog_only: jnp.ndarray
    both: jnp.ndarray
    dropped: jnp.ndarray  # shuffle capacity overflows (0 == exact result)


def _composite_key(customer_sk: jnp.ndarray, item_sk: jnp.ndarray) -> jnp.ndarray:
    """One int64 key per (customer, item) pair.

    Both sks are positive 32-bit surrogate keys in TPC-DS, so packing is
    exact (no collisions), unlike hashing the pair.
    """
    return (customer_sk.astype(jnp.int64) << 32) | (
        item_sk.astype(jnp.int64) & 0xFFFFFFFF
    )


def _count_runs(keys: jnp.ndarray, is_store: jnp.ndarray, valid: jnp.ndarray):
    """Sort-merge presence counting over one shard's co-located rows.

    For every distinct valid key: did it appear with a store tag, a catalog
    tag, or both?  Returns (store_only, catalog_only, both) scalars.
    """
    # order by key; invalid rows sort last via the max sentinel
    sentinel = jnp.int64(0x7FFFFFFFFFFFFFFF)
    k = jnp.where(valid, keys, sentinel)
    order = jnp.argsort(k)
    ks = k[order]
    store_s = jnp.where(valid, is_store, False)[order]
    cat_s = jnp.where(valid, ~is_store, False)[order]

    # run starts: first element or key change
    n = ks.shape[0]
    prev = jnp.concatenate([ks[:1] - 1, ks[:-1]])
    run_start = ks != prev
    run_id = jnp.cumsum(run_start.astype(jnp.int32)) - 1

    # per-run presence via segment max (bounded by n runs)
    has_store = jax.ops.segment_max(
        store_s.astype(jnp.int32), run_id, num_segments=n
    )
    has_cat = jax.ops.segment_max(
        cat_s.astype(jnp.int32), run_id, num_segments=n
    )
    run_valid = jax.ops.segment_max(
        (ks != sentinel).astype(jnp.int32), run_id, num_segments=n
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


def q97_local(store: tuple, catalog: tuple) -> Q97Out:
    """Single-chip q97 core over (customer_sk, item_sk) int arrays."""
    sk = _composite_key(*store)
    ck = _composite_key(*catalog)
    keys = jnp.concatenate([sk, ck])
    is_store = jnp.concatenate(
        # analyze: ignore[governed-allocation] - the single-chip unfused
        # oracle the parity tests pin the plan path against: tag/validity
        # masks are O(input) bools beside already-resident key arrays, and
        # callers (tests, dryrun) run it whole, never under the retry ladder
        [jnp.ones(sk.shape, bool), jnp.zeros(ck.shape, bool)]
    )
    # analyze: ignore[governed-allocation] - same oracle-path mask
    so, co, b = _count_runs(keys, is_store, jnp.ones(keys.shape, bool))
    return Q97Out(so, co, b, jnp.int32(0))


def _sharded_q97(s_cust, s_item, c_cust, c_item, capacity: int,
                 s_valid=None, c_valid=None):
    dp = jax.lax.axis_size(DATA_AXIS)
    sk = _composite_key(s_cust, s_item)
    ck = _composite_key(c_cust, c_item)

    # co-locate keys from BOTH tables with ONE tagged all_to_all: same bytes
    # moved, half the collective launches on the query hot path
    keys = jnp.concatenate([sk, ck])
    tag = jnp.concatenate(
        [jnp.ones(sk.shape, jnp.int8), jnp.zeros(ck.shape, jnp.int8)]
    )
    row_valid = None
    if s_valid is not None or c_valid is not None:
        sv = jnp.ones(sk.shape, bool) if s_valid is None else s_valid
        cv = jnp.ones(ck.shape, bool) if c_valid is None else c_valid
        row_valid = jnp.concatenate([sv, cv])
    part = partition_of(keys, dp)
    ex = all_to_all_shuffle(
        {"k": keys, "tag": tag}, part, capacity, axis=DATA_AXIS,
        row_valid=row_valid,
    )
    so, co, b = _count_runs(
        ex.columns["k"], ex.columns["tag"] == 1, ex.valid
    )
    axes = (DATA_AXIS,)
    return Q97Out(
        jax.lax.psum(so, axes),
        jax.lax.psum(co, axes),
        jax.lax.psum(b, axes),
        jax.lax.psum(ex.dropped, axes),
    )


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


def make_distributed_q97(mesh, capacity: int, with_validity: bool = False):
    """jit-compiled distributed q97 over ``mesh``'s data axis.

    Inputs: four [rows] int arrays sharded over DATA_AXIS (store customer/
    item, catalog customer/item); with ``with_validity``, two more bool
    arrays (store row-valid, catalog row-valid) marking padding rows that
    must not count.  ``capacity`` bounds per-destination shuffle buckets
    over the COMBINED row stream (both tables ride one tagged all_to_all);
    Q97Out.dropped > 0 means retry with a larger one.
    """
    if with_validity:
        def body(s_cust, s_item, c_cust, c_item, s_valid, c_valid):
            return _sharded_q97(s_cust, s_item, c_cust, c_item, capacity,
                                s_valid=s_valid, c_valid=c_valid)

        in_specs = tuple(P(DATA_AXIS) for _ in range(6))
    else:
        body = functools.partial(_sharded_q97, capacity=capacity)
        in_specs = tuple(P(DATA_AXIS) for _ in range(4))
    step = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=Q97Out(P(), P(), P(), P()),
        check_vma=False,
    )
    return jax.jit(step)


# ------------------------------------------------------- nullable columns --
# q97 over real Column inputs with per-column null validity.  SQL semantics:
# NULL keys group *within* a side (DISTINCT treats NULLs as one group) but
# never join *across* sides (NULL = NULL is unknown), so a side's null-key
# groups count as that side's "only" rows.

_PAIR_SENTINEL = jnp.int64(0x7FFFFFFFFFFFFFFF)


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


def _count_runs_pair(k_hi, k_lo, is_store, valid):
    """_count_runs generalized to a 2-limb key (lexsorted)."""
    kh = jnp.where(valid, k_hi, _PAIR_SENTINEL)
    kl = jnp.where(valid, k_lo, _PAIR_SENTINEL)
    order = jnp.lexsort((kl, kh))
    khs = kh[order]
    kls = kl[order]
    store_s = jnp.where(valid, is_store, False)[order]
    cat_s = jnp.where(valid, ~is_store, False)[order]

    n = khs.shape[0]
    prev_hi = jnp.concatenate([khs[:1] - 1, khs[:-1]])
    prev_lo = jnp.concatenate([kls[:1] - 1, kls[:-1]])
    run_start = (khs != prev_hi) | (kls != prev_lo)
    run_id = jnp.cumsum(run_start.astype(jnp.int32)) - 1

    has_store = jax.ops.segment_max(store_s.astype(jnp.int32), run_id, num_segments=n)
    has_cat = jax.ops.segment_max(cat_s.astype(jnp.int32), run_id, num_segments=n)
    run_valid = jax.ops.segment_max(
        (khs != _PAIR_SENTINEL).astype(jnp.int32), run_id, num_segments=n
    )
    has_store = has_store * run_valid
    has_cat = has_cat * run_valid
    both = jnp.sum((has_store & has_cat).astype(jnp.int32))
    store_only = jnp.sum((has_store & (1 - has_cat)).astype(jnp.int32))
    cat_only = jnp.sum((has_cat & (1 - has_store)).astype(jnp.int32))
    return store_only, cat_only, both


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
    so, co, b = _count_runs_pair(
        ex.columns["kh"].data, ex.columns["kl"].data,
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


def run_q97_piece(mesh, piece: Q97Batch, *, sharding=None) -> Q97Out:
    """One FUSED launch of one q97 (sub-)batch through the compiled plan.

    The single-attempt core shared by :func:`run_distributed_q97` (which
    splits inline via run_with_split_retry) and the serving engine's q97
    handler (serve/executor.py, which splits by re-queueing halves) —
    both re-execute the whole fused program per piece, never a per-op
    disband.  Pad/upload/launch live in plans/runtime.execute_plan;
    compiled variants are cached on (plan structure, dtype signature,
    pow2 batch bucket).  Raises :class:`ShuffleCapacityExceeded` when
    rows overflowed the piece's static exchange capacity (the caller
    grows and re-runs).  ``sharding`` is accepted for API compatibility;
    the plan runtime derives placements from the plan itself.
    """
    from spark_rapids_jni_tpu.plans.runtime import execute_plan

    del sharding
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
    ops); the default registers/ends ``task_id`` itself.
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

    ctx = (task_context(budget.gov, task_id) if manage_task
           else contextlib.nullcontext())
    with ctx:
        return run_with_split_retry(
            budget, batch,
            nbytes_of=lambda b: q97_working_set_bytes(b, dp),
            run=run,
            split=split_q97_batch,
            combine=combine_q97_outs,
            grow=lambda b: dataclasses.replace(b, capacity=2 * b.capacity),
            max_split_depth=max_split_depth,
        )
