"""Out-of-core NDS execution: streamed generation + grace-hash bucketing.

BASELINE config 5 names TPC-DS SF100; no single host (and certainly not
this 1-core box) holds the fact stream in memory.  The scalable shape is
the classic external hash shuffle the reference relies on Spark for:

- facts are *generated/ingested in chunks* (bounded host memory),
- each chunk's rows are routed to a key-space bucket by a stable hash of
  the join key and appended to that bucket's spill file — JCUDF row
  batches carrying the FULL table (validity, strings, decimal128) through
  io/spill.py's ExternalTableShuffle, the host analog of
  parallel/table_shuffle.py's device exchange,
- each bucket then fits in memory by construction (total/n_buckets) and
  is executed as one governed distributed query piece; per-bucket results
  are additive because a (customer, item) pair lands in exactly one
  bucket on both sides.

On a pod the same plan maps bucket -> host group and spill file ->
ICI/DCN all_to_all (parallel/table_shuffle.py); here the seam between
"route rows" and "execute bucket" is identical, just disk-backed.
Parity: the reference delegates exactly this to Spark's external shuffle
(RapidsShuffleManager) carrying its JCUDF row batches
(row_conversion.cu:574); q97 itself is src/main/java: same join-count
semantics as models/q97.py.
"""

from __future__ import annotations

from typing import Dict, Iterable, Iterator, Optional, Tuple

import numpy as np

from spark_rapids_jni_tpu.io.spill import ExternalTableShuffle, pair_mix64

__all__ = [
    "ExternalTableShuffle",
    "generate_q97_chunks",
    "run_streaming_q97",
    "bucket_of_pairs",
    "q97_spill_shuffle",
    "generate_q5_chunks",
    "run_streaming_q5",
]


def bucket_of_pairs(cust: np.ndarray, item: np.ndarray,
                    n_buckets: int) -> np.ndarray:
    """Stable key-space bucket of (customer, item) int32 pairs: splitmix64
    finalizer over the packed pair (io/spill.py pair_mix64).  Any fixed mix
    works — both sides must agree, nothing else — but it must be *well
    mixed*: TPC-DS surrogate keys are dense integers, and ``pair % n``
    would put all of one customer in one bucket."""
    return (pair_mix64(cust, item) % np.uint64(n_buckets)).astype(np.int64)


def _pair_key_hash(cols) -> np.ndarray:
    """ExternalTableShuffle key hash for the q97 (cust, item) int32 pair —
    identical mix to :func:`bucket_of_pairs`, so bucket placement agrees
    with the ownership filter and the legacy tests' expectations."""
    return pair_mix64(np.asarray(cols[0].data), np.asarray(cols[1].data))


def q97_spill_shuffle(tmpdir: str, n_buckets: int) -> ExternalTableShuffle:
    """The q97 fact-pair spill shuffle: two non-null int32 key columns in
    JCUDF rows, routed by the pair hash."""
    from spark_rapids_jni_tpu.columnar.dtypes import INT32

    return ExternalTableShuffle(
        tmpdir, n_buckets, [INT32, INT32], key_indices=(0, 1),
        key_hash=_pair_key_hash)


def generate_q97_chunks(sf: float, seed: int, chunk_rows: int
                        ) -> Iterator[Tuple[str, np.ndarray, np.ndarray]]:
    """Stream the q97 fact pair as ``(side, cust, item)`` chunks of at most
    ``chunk_rows`` rows.

    The chunks concatenate to tpcds.generate_q97_tables(sf, seed) for any
    ``chunk_rows``: they are slices of its seeded generation blocks
    (tpcds.q97_blocks), the streaming analog of dsdgen's parallel
    generation, which also seeds per partition."""
    from spark_rapids_jni_tpu.models.tpcds import q97_blocks

    for side, cust, item in q97_blocks(sf, seed):
        for start in range(0, len(cust), chunk_rows):
            yield (side, cust[start:start + chunk_rows],
                   item[start:start + chunk_rows])


# ------------------------------------------------------------ streamed q5 --
# q5's aggregates are per-(channel, id code) sums — additive over any
# disjoint partition of the probe rows — with one join that needs
# co-location: a web return meets its sale by (item, order).  So store and
# catalog facts route by (dimension key, date), which has values enough to
# split a bucket again and again, and web sales and returns by order
# number, each through its own ExternalTableShuffle with the same
# bucket count: bucket b of the stream is bucket b of both, and a split
# refines both alike (the modulus doubles per bucket), so every return
# stays in its sale's bucket.  Facts spill as full JCUDF tables (nullable
# keys + int64 money); sides are "{channel}.{sales|ret}".


def generate_q5_chunks(sf: float, seed: int, chunk_rows: int,
                       null_pct: float = 0.04):
    """Stream the q5 fact tables as ``(channel, kind, arrays)`` chunks.

    The row counts of tpcds.generate_q5_data (section 3's SF 10 counts
    scaled to ``sf``) from per-chunk seeded rngs, so any prefix is
    reproducible without materializing a table.  ``kind`` is "sales"
    (m1=price, m2=profit) or "ret" (m1=amt, m2=loss); every chunk has
    ``date`` and ``date_valid``; store and catalog chunks ``sk`` and
    ``sk_valid``; web sales ``sk``, ``sk_valid``, ``item`` and ``order``,
    web returns ``item`` and ``order`` only.  Each web sales chunk is
    followed by the returns of its lines, its share of web_returns.
    """
    from spark_rapids_jni_tpu.models.tpcds import (
        CHANNELS,
        ITEM_ROWS,
        ORPHAN_SHARE,
        q5_dims,
        q5_returns,
        q5_rows,
        q5_sales,
        web_orders,
        web_returns_of,
    )

    dims = q5_dims()

    def chunk(sales, kind):
        money = ("price", "profit") if kind == "sales" else ("amt", "loss")
        out = {k: v for k, v in sales.items() if k not in money}
        out["m1"], out["m2"] = sales[money[0]], sales[money[1]]
        return out

    for ci, name in enumerate(CHANNELS):
        n_dim = dims.channel_size(name)
        n_s = q5_rows(f"{name}_sales", sf)
        n_r = q5_rows(f"{name}_returns", sf)

        def rng_of(ki, c):
            return np.random.RandomState(
                (seed + 7_000_003 * ci + 500_009 * ki + c) % (2**31 - 1))

        if name != "web":
            for ki, (kind, total) in enumerate((("sales", n_s),
                                               ("ret", n_r))):
                for c, at in enumerate(range(0, total, chunk_rows)):
                    m = min(chunk_rows, total - at)
                    rng = rng_of(ki, c)
                    rows = (q5_sales(rng, m, n_dim, null_pct)
                            if kind == "sales"
                            else q5_returns(rng, m, n_dim, null_pct))
                    yield name, kind, chunk(rows, kind)
            continue
        first_order = 1
        orphans = 0
        for c, at in enumerate(range(0, n_s, chunk_rows)):
            m = min(chunk_rows, n_s - at)
            rng = rng_of(0, c)
            sales = q5_sales(rng, m, n_dim, null_pct)
            sales["item"], sales["order"] = web_orders(
                rng, m, ITEM_ROWS, first_order)
            first_order = int(sales["order"][-1]) + 1
            yield name, "sales", chunk(sales, "sales")
            r = n_r * (at + m) // n_s - n_r * at // n_s
            if r:
                # orphans take order numbers above any line's
                ret = web_returns_of(rng, sales, r, null_pct,
                                     n_s + 1 + orphans)
                orphans += int(round(r * ORPHAN_SHARE))
                yield name, "ret", chunk(ret, "ret")


def _columns(ch, names, dtypes):
    """JCUDF columns of a chunk: ``names`` in order, a key's validity from
    ``<name>_valid`` where the chunk has one."""
    from spark_rapids_jni_tpu.columnar.column import Column

    return [Column(ch[n], ch.get(f"{n}_valid"), d)
            for n, d in zip(names, dtypes)]


#: the spill columns of each shuffle: store/catalog route by their
#: dimension key and date (columns 0 and 1), web by order number (column
#: 0); web returns spill an all-null site column
_BY_SK = ("sk", "date", "m1", "m2")
_BY_ORDER = ("order", "item", "sk", "date", "m1", "m2")


def _q5_side_facts(shuffles, channel: str, bucket: int):
    """Decode one channel's (sales, ret) spill sides of one bucket into the
    q5 fact-array dict the partials step consumes."""
    web = channel == "web"
    names = _BY_ORDER if web else _BY_SK
    out = {}
    for kind, money in (("sales", ("price", "profit")),
                        ("ret", ("amt", "loss"))):
        cols = dict(zip(names, shuffles[web].read(f"{channel}.{kind}",
                                                  bucket)))
        for name, col in cols.items():
            field = {"m1": money[0], "m2": money[1]}.get(name, name)
            if web and kind == "ret" and name == "sk":
                continue
            out[f"{kind}_{field}"] = np.asarray(col.data)
            if name in ("sk", "date"):
                n = len(out[f"{kind}_{field}"])
                out[f"{kind}_{field}_valid"] = (
                    np.ones(n, bool) if col.validity is None
                    else np.asarray(col.validity))
    return out


def run_streaming_q5(
    mesh,
    chunks,
    *,
    tmpdir: str,
    n_buckets: int = 16,
    budget=None,
    host_budget=None,
    task_id: int = 0,
    verify: bool = False,
    bucket_owner: Optional[Tuple[int, int]] = None,
):
    """Out-of-core governed distributed q5 over streamed fact chunks.

    Returns ``(rows, verified, stats)`` where ``rows`` is the query's
    result (the rollup, its order and its limit).  Each bucket runs
    through ONE cached compiled partials step (geometry is the dimension
    side, bucket-independent); per-bucket partial vectors sum into the
    global answer because every aggregate is additive over the disjoint
    bucket rows, and each web return is in its sale's bucket.  ``verify``
    checks each bucket against the numpy reference
    (models.q5.q5_host_channel_partials) — bucket-local, bounded memory.

    Host staging is governed like streamed q97: the bucket's ACTUAL
    spill-file bytes are reserved on the arbiter's CPU path; an
    over-budget bucket recursively splits on disk, in both shuffles alike.
    """
    import os

    from spark_rapids_jni_tpu.columnar.dtypes import INT32, INT64
    from spark_rapids_jni_tpu.mem.governed import (
        default_device_budget,
        run_with_split_retry,
        task_context,
    )
    from spark_rapids_jni_tpu.models.q5 import (
        ChannelPartials,
        add_partials,
        id_codes,
        q5_host_channel_partials,
        q5_rollup,
        run_q5_partials,
    )
    from spark_rapids_jni_tpu.models.tpcds import CHANNELS, q5_dims

    if bucket_owner is not None:
        proc_id, nprocs = bucket_owner
        if not (0 <= proc_id < nprocs):
            raise ValueError(f"bucket_owner {bucket_owner}: need "
                             "0 <= proc_id < nprocs")
    if budget is None:
        budget = default_device_budget()
    dims = q5_dims()
    codes = {n: id_codes(dims.dim_id[n]) for n in CHANNELS}
    dtypes = {False: [INT32, INT32, INT64, INT64],
              True: [INT32, INT32, INT32, INT32, INT64, INT64]}
    shuffles = {web: ExternalTableShuffle(
        os.path.join(tmpdir, "by_order" if web else "by_sk"), n_buckets,
        dtypes[web], key_indices=(0,) if web else (0, 1))
        for web in (False, True)}
    rows_in = 0
    try:
        for channel, kind, ch in chunks:
            rows_in += len(ch["date"])
            web = channel == "web"
            if web and "sk" not in ch:
                ch = dict(ch, sk=np.zeros(len(ch["date"]), np.int32),
                          sk_valid=np.zeros(len(ch["date"]), bool))
            shuffle = shuffles[web]
            cols = _columns(ch, _BY_ORDER if web else _BY_SK, dtypes[web])
            hashes = shuffle.row_hashes(cols)
            if bucket_owner is not None:
                ids = (hashes % np.uint64(n_buckets)).astype(np.int64)
                mine = (ids % bucket_owner[1]) == bucket_owner[0]
                if not mine.any():
                    continue
                cols = [type(col)(np.asarray(col.data)[mine],
                                  None if col.validity is None
                                  else np.asarray(col.validity)[mine],
                                  col.dtype) for col in cols]
                hashes = hashes[mine]
            shuffle.append(f"{channel}.{kind}", cols, hashes=hashes)

        verified: Optional[bool] = True if verify else None

        def run_bucket(b: int):
            batch = {name: _q5_side_facts(shuffles, name, b)
                     for name in CHANNELS}
            per = run_q5_partials(mesh, batch, dims, budget=budget,
                                  task_id=task_id, manage_task=False)
            oracle_ok = True
            if verify:
                for name in CHANNELS:
                    want = q5_host_channel_partials(
                        batch[name], codes[name][0], dims.date_sk,
                        dims.date_days, dims.sales_date_lo,
                        dims.sales_date_hi)
                    oracle_ok = oracle_ok and all(
                        np.array_equal(np.asarray(g, np.int64),
                                       np.asarray(w, np.int64))
                        for g, w in zip(per[name], want))
            return per, oracle_ok

        n_splits = [0]

        def split_piece(b: int):
            n_splits[0] += 1
            pieces = [sh.split_bucket(b) for sh in shuffles.values()]
            return pieces[0]

        def combine_pieces(rs):
            acc = rs[0][0]
            for per, _ok in rs[1:]:
                acc = add_partials(acc, per)
            return acc, all(ok for _p, ok in rs)

        def bucket_nbytes(b: int) -> int:
            return sum(sh.bucket_nbytes(b) for sh in shuffles.values())

        totals = None
        with task_context(budget.gov, task_id):
            for b in range(n_buckets):
                if bucket_owner is not None and \
                        b % bucket_owner[1] != bucket_owner[0]:
                    continue
                if not any(sh.bucket_rows(b) for sh in shuffles.values()):
                    continue
                if host_budget is not None:
                    per, oracle_ok = run_with_split_retry(
                        host_budget, b,
                        nbytes_of=bucket_nbytes,
                        run=run_bucket,
                        split=split_piece,
                        combine=combine_pieces,
                    )
                else:
                    per, oracle_ok = run_bucket(b)
                if verify and not oracle_ok:
                    verified = False
                totals = per if totals is None else add_partials(totals, per)
        if totals is None:  # no owned rows at all
            totals = {name: ChannelPartials(
                np.zeros(n, np.int64), np.zeros(n, np.int64),
                np.zeros(n, np.int64), np.zeros(n, np.int32))
                for name, n in ((c, len(codes[c][1])) for c in CHANNELS)}
        rows = q5_rollup(totals, {n: codes[n][1] for n in CHANNELS})
        per_bucket: Dict[int, int] = {}
        for sh in shuffles.values():
            for (_side, b), n in sh.rows.items():
                per_bucket[b] = per_bucket.get(b, 0) + n
        stats = {
            "rows_in": rows_in,
            "n_buckets": n_buckets,
            "max_bucket_rows": max(per_bucket.values(), default=0),
        }
        if host_budget is not None:
            stats["host_peak_reserved"] = host_budget.peak
            stats["bucket_splits"] = n_splits[0]
        return rows, verified, stats
    finally:
        for sh in shuffles.values():
            sh.close()


def run_streaming_q97(
    mesh,
    chunks: Iterable[Tuple[str, np.ndarray, np.ndarray]],
    *,
    tmpdir: str,
    n_buckets: int = 16,
    budget=None,
    host_budget=None,
    task_id: int = 0,
    verify: bool = False,
    bucket_owner: Optional[Tuple[int, int]] = None,
) -> Tuple[Tuple[int, int, int], Optional[bool], Dict[str, int]]:
    """Out-of-core governed distributed q97 over streamed fact chunks.

    Returns ``((store_only, catalog_only, both), verified, stats)``.
    ``verified`` is per-bucket host-set oracle agreement (None when
    ``verify`` is off) — bucket-local sets are the whole point: the
    oracle's working set is also bounded by the bucket size.

    ``host_budget`` (a ``BudgetedResource(..., is_cpu=True)``) governs the
    HOST-side bucket materialization: each bucket's ACTUAL spill-file bytes
    are reserved through the arbiter's CPU path before the bucket is read
    back, so a multi-tenant host blocks/wakes on pinned-host pressure
    exactly like device pressure (the reference governs CPU allocations
    through the same state machine — SparkResourceAdaptorJni.cpp is_for_cpu
    paths).

    ``bucket_owner=(proc_id, nprocs)`` restricts execution to the buckets
    this participant OWNS (``b % nprocs == proc_id``) — the pod-scale
    deployment shape: host groups partition the bucket space, per-owner
    counts stay additive, and the global answer is the sum of the owners'
    results (tests/streaming_worker.py drives this across real OS
    processes).
    """
    from spark_rapids_jni_tpu.columnar.column import Column
    from spark_rapids_jni_tpu.columnar.dtypes import INT32
    from spark_rapids_jni_tpu.mem.governed import (
        default_device_budget,
        run_with_split_retry,
        task_context,
    )
    from spark_rapids_jni_tpu.models.q97 import (
        default_q97_capacity,
        run_distributed_q97,
    )
    from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS

    if bucket_owner is not None:
        proc_id, nprocs = bucket_owner
        if not (0 <= proc_id < nprocs):
            raise ValueError(f"bucket_owner {bucket_owner}: need "
                             "0 <= proc_id < nprocs")
    if budget is None:
        budget = default_device_budget()
    shuffle = q97_spill_shuffle(tmpdir, n_buckets)
    rows_in = 0
    try:
        for side, cust, item in chunks:
            rows_in += len(cust)
            hashes = pair_mix64(cust, item)
            if bucket_owner is not None:
                # spool ONLY owned buckets: (nprocs-1)/nprocs of the
                # shuffle disk IO is someone else's and never read here
                ids = (hashes % np.uint64(n_buckets)).astype(np.int64)
                mine = (ids % bucket_owner[1]) == bucket_owner[0]
                if not mine.any():
                    continue
                cust, item, hashes = cust[mine], item[mine], hashes[mine]
            shuffle.append(
                side,
                [Column(cust, None, INT32), Column(item, None, INT32)],
                hashes=hashes)

        dp = mesh.shape[DATA_AXIS]
        # ONE capacity for every bucket piece -> one compiled step reused
        cap = default_q97_capacity(shuffle.max_bucket_rows(), dp)
        totals = [0, 0, 0]
        verified: Optional[bool] = True if verify else None

        def read_pair(side: str, b: int):
            cols = shuffle.read(side, b)
            return (np.asarray(cols[0].data, np.int32),
                    np.asarray(cols[1].data, np.int32))

        def run_bucket(b: int):
            store_b = read_pair("store", b)
            cat_b = read_pair("catalog", b)
            out = run_distributed_q97(
                mesh, store_b, cat_b, budget=budget, task_id=task_id,
                capacity=cap, manage_task=False)
            got = (int(out.store_only), int(out.catalog_only), int(out.both))
            oracle_ok = True
            if verify:
                s = set(zip(store_b[0].tolist(), store_b[1].tolist()))
                c = set(zip(cat_b[0].tolist(), cat_b[1].tolist()))
                oracle_ok = got == (len(s - c), len(c - s), len(s & c))
            return got, oracle_ok

        n_splits = [0]

        def split_piece(b: int):
            # recursive grace hash: re-partition the oversized bucket on
            # disk into two key-space-consistent halves (counts stay
            # additive); run_with_split_retry then reserves each half
            n_splits[0] += 1
            return shuffle.split_bucket(b)

        def combine_pieces(rs):
            return (tuple(sum(r[0][i] for r in rs) for i in range(3)),
                    all(r[1] for r in rs))

        with task_context(budget.gov, task_id):
            for b in range(n_buckets):
                if bucket_owner is not None and \
                        b % bucket_owner[1] != bucket_owner[0]:
                    continue
                if shuffle.bucket_rows(b) == 0:
                    continue
                if host_budget is not None:
                    # the canonical retry driver brackets the host
                    # reservation — sized by the bucket's ACTUAL spill-file
                    # bytes: RetryOOM from multi-tenant pressure re-runs
                    # the bucket; an over-budget bucket splits on disk
                    # instead of crashing the stream
                    got, oracle_ok = run_with_split_retry(
                        host_budget, b,
                        nbytes_of=shuffle.bucket_nbytes,
                        run=run_bucket,
                        split=split_piece,
                        combine=combine_pieces,
                    )
                else:
                    got, oracle_ok = run_bucket(b)
                if verify and not oracle_ok:
                    verified = False
                for i in range(3):
                    totals[i] += got[i]
        stats = {
            "rows_in": rows_in,
            "n_buckets": n_buckets,
            "max_bucket_rows": shuffle.max_bucket_rows(),
            "capacity": cap,
        }
        if host_budget is not None:
            # snapshot, NOT reset_peak(): the budget may be shared by
            # concurrent tenants, and mutating a caller-owned high-water
            # mark would race; this is the global peak so far by contract
            stats["host_peak_reserved"] = host_budget.peak
            stats["bucket_splits"] = n_splits[0]
        return tuple(totals), verified, stats
    finally:
        shuffle.close()
