"""NDS q5: the three-channel sales/returns rollup (TPC-DS query5.tpl).

Per channel the spec unions the sales and the returns of the channel,
joins them to a 15-day window of ``date_dim`` and to the channel's
business dimension, and sums ``sales``, ``returns``, ``profit`` and
``profit_loss`` per business id; then

    select channel, id, sum(sales), sum(returns), sum(profit)
    from (store ∪ catalog ∪ web) group by rollup (channel, id)
    order by channel, id limit 100

A web return has no site of its own: it takes ``ws_web_site_sk`` from the
sale it names, through ``web_returns left outer join web_sales on
(wr_item_sk = ws_item_sk and wr_order_number = ws_order_number)``; a
return that names no sale, or a sale with a null site, drops out at the
inner join with ``web_site``.

The TPU plan (:func:`q5_plan`, plans/ir.py) is ONE compiled program over
the six fact scans.  Each scan gathers two columns computed on replicated
dimension tables: its day's offset in the window (-1 outside it, an
expression evaluated on ``date_dim``), and its dimension row's dictionary
code of the business id (codes over the distinct ids, in id order,
computed on the host; store and web_site keep history, so several
surrogate keys share one id and one code).  The web returns first take
their site through a :class:`plans.ir.KeyJoin` against web_sales.  Each
scan then sums into its channel's code space by ``SegmentAgg``'s sorted
sums, as exact int64 cents (decimal(7,2) sums widen to decimal(17,2),
which int64 holds); the count of rows tells an empty group from a group
that sums to zero.  The rollup, order and limit run on the host over the
few thousand groups, with Spark's ordering: nulls first, so the grand
total leads, then each channel's subtotal before its ids.

The partials are additive over any partition of the probe rows: the
governed runner halves every scan but the KeyJoin's build under
SplitAndRetryOOM, and the streamed form buckets web sales and returns by
order number, so that each return meets its sale in one bucket.

:func:`q5_local_unfused` (eager, one dispatch per op, scatter sums and a
packed-key ``searchsorted`` join) and :func:`q5_host_channel_partials`
(numpy) are the references the plan is checked against.
"""

from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.models.tpcds import (
    CHANNELS,
    DATE_DIM_FIRST_SK,
    Q5Data,
    Q5Dims,
)
from spark_rapids_jni_tpu.plans import ir
from spark_rapids_jni_tpu.plans.ir import Bin, Cast, band_all, col, lit

__all__ = [
    "Q5Row",
    "Q5Grid",
    "q5_local",
    "q5_local_unfused",
    "q5_plan",
    "make_distributed_q5",
    "run_distributed_q5",
    "run_distributed_q5_grid",
    "run_q5_partials",
    "q5_rollup",
    "q5_host_channel_partials",
    "q5_host_oracle",
    "ChannelPartials",
    "add_partials",
    "id_codes",
]

#: the query's LIMIT
ROWS_LIMIT = 100
#: the spec's channel column, and the prefix of its id column
LABELS = {"store": "store channel", "catalog": "catalog channel",
          "web": "web channel"}
ID_PREFIX = {"store": "store", "catalog": "catalog_page", "web": "web_site"}


class Q5Row(NamedTuple):
    """One result row: ROLLUP levels use None for grouped-out columns."""

    channel: object  # str | None
    id: object  # str | None
    sales: int  # cents
    returns_: int
    profit: int


class ChannelPartials(NamedTuple):
    """Per-code partial aggregates of one channel — ADDITIVE over any
    disjoint partition of the probe rows (the invariant row splits and
    the streamed bucket pipeline rely on)."""

    sales: np.ndarray  # int64[n_codes]
    returns_: np.ndarray
    profit: np.ndarray
    count: np.ndarray  # int32[n_codes] contributing rows (sales+returns)


def add_partials(
    a: Dict[str, ChannelPartials], b: Dict[str, ChannelPartials]
) -> Dict[str, ChannelPartials]:
    """Element-wise sum of per-channel partial dicts (the additivity every
    split/bucket combine relies on)."""
    return {name: ChannelPartials(*(x + y for x, y in zip(a[name], b[name])))
            for name in a}


def id_codes(dim_id) -> Tuple[np.ndarray, np.ndarray]:
    """(each dimension row's 0-based code, the distinct ids in code
    order): a dense dictionary code of the business id."""
    ids, code = np.unique(np.asarray(dim_id), return_inverse=True)
    return code.reshape(-1).astype(np.int32), ids


def _dims_of(data: Q5Data) -> Q5Dims:
    return Q5Dims(data.date_sk, data.date_days, data.sales_date_lo,
                  data.sales_date_hi,
                  {n: data.channels[n].dim_sk for n in CHANNELS},
                  {n: data.channels[n].dim_id for n in CHANNELS})


# ------------------------------------------------------------------ the plan


def _or_miss(pred, value):
    """``value`` where ``pred`` holds, else -1 (``value`` is never
    negative)."""
    return Bin("sub", Bin("mul", Cast(pred, "int32"),
                          Bin("add", value, lit(1))), lit(1))


@functools.lru_cache(maxsize=64)
def q5_plan(n_codes: Tuple[int, ...], lo: int, hi: int,
            date_sk0: int = DATE_DIM_FIRST_SK) -> ir.Plan:
    """The whole q5 device pipeline as ONE plan over the six fact scans.

    ``n_codes`` counts each channel's distinct business ids; ``lo`` and
    ``hi`` are the window's first and last day (days since 1970-01-01,
    both included); ``date_sk0`` is date_dim's first key.  Each scan
    gathers ``w``, its day's offset in the window or -1 (evaluated on
    date_dim), filters on it and on its keys' validity, gathers ``g``,
    its dimension row's id code, and sums into the channel's codes.  The
    web returns first take ``site`` from web_sales by (order, item).
    Geometry scalars normalize through ``plans.ir.lit``, so equal
    geometry builds an EQUAL plan: one entry of the process-global plan
    cache.  Memoized: the per-request hot path does not rebuild (and
    re-hash) the plan tree."""
    n_codes = tuple(int(n) for n in n_codes)
    date = ir.Dim("date_dim", ("days",))
    window_day = _or_miss(
        band_all(Bin("ge", col("days"), lit(lo)),
                 Bin("le", col("days"), lit(hi))),
        Bin("sub", col("days"), lit(lo)))

    def in_window(node, *valid):
        node = ir.GatherJoin(node, date, key=col("date"),
                             base=lit(date_sk0), fields=((window_day, "w"),))
        return ir.Filter(node, band_all(
            *(col(v) for v in valid), col("date_valid"),
            Bin("ge", col("w"), lit(0))))

    sinks: list = []
    post: list = []
    outputs: list = []
    for name, n in zip(CHANNELS, n_codes):
        dim = ir.Dim(f"{name}_dim", ("code",))
        sales = in_window(ir.Scan(f"{name}_sales", (
            "sk", "sk_valid", "date", "date_valid", "price", "profit")),
            "sk_valid")
        if name == "web":
            returns = in_window(ir.Scan("web_returns", (
                "item", "order", "date", "date_valid", "amt", "loss")))
            returns = ir.KeyJoin(
                returns, ir.Scan("web_sales_keys",
                                 ("item", "order", "sk", "sk_valid")),
                probe_key=(col("order"), col("item")),
                build_key=(col("order"), col("item")),
                fields=(("sk", "sk_valid", "site"),))
            returns = ir.Filter(returns, col("site_valid"))
            site = "site"
        else:
            returns = in_window(ir.Scan(f"{name}_returns", (
                "sk", "sk_valid", "date", "date_valid", "amt", "loss")),
                "sk_valid")
            site = "sk"
        for node, key, aggs in (
            (sales, "sk",
             ((f"{name}_sales", col("price"), "int64"),
              (f"{name}_profit_s", col("profit"), "int64"),
              (f"{name}_count_s", lit(1), "int32"))),
            (returns, site,
             ((f"{name}_returns", col("amt"), "int64"),
              (f"{name}_loss", col("loss"), "int64"),
              (f"{name}_count_r", lit(1), "int32"))),
        ):
            node = ir.GatherJoin(node, dim, key=col(key), base=lit(1),
                                 fields=(("code", "g"),))
            sinks.append(ir.SegmentAgg(node, key=col("g"), num_segments=n,
                                       aggs=aggs))
        post.append((f"{name}_profit",
                     Bin("sub", col(f"{name}_profit_s"),
                         col(f"{name}_loss"))))
        post.append((f"{name}_count",
                     Bin("add", col(f"{name}_count_s"),
                         col(f"{name}_count_r"))))
        outputs.extend([f"{name}_sales", f"{name}_returns",
                        f"{name}_profit", f"{name}_count"])
    return ir.Plan("q5", tuple(sinks), tuple(post), tuple(outputs))


def _facts_of(ch) -> Dict[str, np.ndarray]:
    """One channel's fact columns under the plan's field names: web's
    sales add their (item, order) key, and its returns carry that key
    in place of a dimension key."""
    facts = {
        "sales_sk": ch.sales_sk, "sales_sk_valid": ch.sales_sk_valid,
        "sales_date": ch.sales_date,
        "sales_date_valid": ch.sales_date_valid,
        "sales_price": ch.sales_price, "sales_profit": ch.sales_profit,
        "ret_date": ch.ret_date, "ret_date_valid": ch.ret_date_valid,
        "ret_amt": ch.ret_amt, "ret_loss": ch.ret_loss,
    }
    if ch.ret_item is None:
        facts["ret_sk"] = ch.ret_sk
        facts["ret_sk_valid"] = ch.ret_sk_valid
    else:
        facts.update(sales_item=ch.sales_item, sales_order=ch.sales_order,
                     ret_item=ch.ret_item, ret_order=ch.ret_order)
    return facts


def _q5_tables(batch: Dict[str, Dict[str, np.ndarray]], dims: Q5Dims):
    """The plan's input tables from a per-channel fact-array batch (the
    ``_facts_of`` field names) and the dimensions."""
    tables = {"date_dim": {"days": np.asarray(dims.date_days)}}
    for name, f in batch.items():
        tables[f"{name}_dim"] = {"code": id_codes(dims.dim_id[name])[0]}
        tables[f"{name}_sales"] = {
            "sk": f["sales_sk"], "sk_valid": f["sales_sk_valid"],
            "date": f["sales_date"], "date_valid": f["sales_date_valid"],
            "price": f["sales_price"], "profit": f["sales_profit"],
        }
        ret = {"date": f["ret_date"], "date_valid": f["ret_date_valid"],
               "amt": f["ret_amt"], "loss": f["ret_loss"]}
        if name == "web":
            tables["web_returns"] = dict(ret, item=f["ret_item"],
                                         order=f["ret_order"])
            tables["web_sales_keys"] = {
                "item": f["sales_item"], "order": f["sales_order"],
                "sk": f["sales_sk"], "sk_valid": f["sales_sk_valid"]}
        else:
            tables[f"{name}_returns"] = dict(ret, sk=f["ret_sk"],
                                             sk_valid=f["ret_sk_valid"])
    return tables


def _plan_of(dims: Q5Dims) -> ir.Plan:
    return q5_plan(tuple(len(id_codes(dims.dim_id[n])[1]) for n in CHANNELS),
                   dims.sales_date_lo, dims.sales_date_hi,
                   int(dims.date_sk[0]))


def _partials_of(outputs: Dict[str, np.ndarray]) -> Dict[str, ChannelPartials]:
    return {name: ChannelPartials(
        outputs[f"{name}_sales"], outputs[f"{name}_returns"],
        outputs[f"{name}_profit"], outputs[f"{name}_count"])
        for name in CHANNELS}


# ------------------------------------------------------------ the rollup --


def q5_rollup(per_channel: Dict[str, ChannelPartials],
              ids: Dict[str, np.ndarray],
              limit: Optional[int] = ROWS_LIMIT) -> List[Q5Row]:
    """ROLLUP(channel, id), ordered by (channel, id) with nulls first as
    Spark orders them, and the first ``limit`` rows (all with None).
    ``ids`` maps a channel to its distinct business ids in code order.
    A group exists where rows joined, even if its sums are zero; without
    any, the rollup has no rows at all."""
    rows: List[Q5Row] = []
    total = np.zeros(3, object)
    for name in CHANNELS:
        p = per_channel[name]
        present = np.flatnonzero(np.asarray(p.count))
        if not len(present):
            continue
        label = LABELS[name]
        sums = np.zeros(3, object)
        for c in present.tolist():
            row = (int(p.sales[c]), int(p.returns_[c]), int(p.profit[c]))
            rows.append(Q5Row(label, ID_PREFIX[name] + str(ids[name][c]),
                              *row))
            sums += row
        rows.append(Q5Row(label, None, *map(int, sums)))
        total += sums
    if rows:
        rows.append(Q5Row(None, None, *map(int, total)))
    rows.sort(key=lambda r: (r.channel is not None, r.channel or "",
                             r.id is not None, r.id or ""))
    return rows[:limit]


class Q5Grid(NamedTuple):
    """The per-channel partials as the plan downloaded them, with each
    channel's distinct business ids in code order."""

    partials: Dict[str, ChannelPartials]
    ids: Dict[str, np.ndarray]

    def rows(self, limit: Optional[int] = ROWS_LIMIT) -> List[Q5Row]:
        """The query's result, or every rollup row with ``limit`` None."""
        return q5_rollup(self.partials, self.ids, limit)


def _grid(partials, dims: Q5Dims) -> Q5Grid:
    return Q5Grid(partials, {n: id_codes(dims.dim_id[n])[1]
                             for n in CHANNELS})


# ---------------------------------------------------------- the references


def _window_member(date, date_valid, dim_sk, dim_days, lo, hi):
    """Inner-join membership of fact date_sk in the window's date rows."""
    idx = jnp.clip(jnp.searchsorted(dim_sk, date), 0, dim_sk.shape[0] - 1)
    return (date_valid & (dim_sk[idx] == date) & (dim_days[idx] >= lo)
            & (dim_days[idx] <= hi))


def _masked_segment(values, code, ok, n, dtype=jnp.int64):
    """segment_sum of values into codes, masked rows dropped."""
    bucket = jnp.where(ok, code, n)
    return jax.ops.segment_sum(
        jnp.where(ok, values, 0).astype(dtype), bucket, num_segments=n + 1
    )[:-1]


def _web_sites(ret_item, ret_order, sales_item, sales_order, sales_sk,
               sales_sk_valid):
    """(site, site valid) of each web return: the sale with its (order,
    item), packed into one int64 key and found by searchsorted."""
    def packed(order, item):
        return (order.astype(jnp.int64) << 32) | item.astype(jnp.int64)

    keys = packed(sales_order, sales_item)
    order = jnp.argsort(keys)
    sorted_keys = keys[order]
    want = packed(ret_order, ret_item)
    idx = jnp.clip(jnp.searchsorted(sorted_keys, want), 0, keys.shape[0] - 1)
    hit = sorted_keys[idx] == want
    return sales_sk[order][idx], hit & sales_sk_valid[order][idx]


def _channel_partials(ch, code, dim_sk, dim_days, lo, hi) -> ChannelPartials:
    """One channel's partial aggregates over its codes, per-op eager form
    (``code`` maps a 1-based dimension key to its 0-based id code)."""
    n = int(code.max()) + 1
    n_dim = code.shape[0]

    def codes_of(sk):
        return code[jnp.clip(sk - 1, 0, n_dim - 1)]

    s_ok = ch["sales_sk_valid"] & _window_member(
        ch["sales_date"], ch["sales_date_valid"], dim_sk, dim_days, lo, hi)
    if "ret_item" in ch:
        ret_sk, ret_valid = _web_sites(
            ch["ret_item"], ch["ret_order"], ch["sales_item"],
            ch["sales_order"], ch["sales_sk"], ch["sales_sk_valid"])
    else:
        ret_sk, ret_valid = ch["ret_sk"], ch["ret_sk_valid"]
    r_ok = ret_valid & _window_member(
        ch["ret_date"], ch["ret_date_valid"], dim_sk, dim_days, lo, hi)
    s_code, r_code = codes_of(ch["sales_sk"]), codes_of(ret_sk)

    sales = _masked_segment(ch["sales_price"], s_code, s_ok, n)
    profit_s = _masked_segment(ch["sales_profit"], s_code, s_ok, n)
    returns_ = _masked_segment(ch["ret_amt"], r_code, r_ok, n)
    loss = _masked_segment(ch["ret_loss"], r_code, r_ok, n)
    count = (
        # analyze: ignore[governed-allocation] - per-op ORACLE path: it
        # runs only eagerly under q5_local_unfused, the reference the
        # fused (governed) program is checked against in tests; the ones
        # masks are fact-row-sized, test-scoped
        _masked_segment(jnp.ones_like(s_code), s_code, s_ok, n, jnp.int32)
        # analyze: ignore[governed-allocation] - same oracle-path rationale
        + _masked_segment(jnp.ones_like(r_code), r_code, r_ok, n, jnp.int32)
    )
    return ChannelPartials(sales, returns_, profit_s - loss, count)


def q5_local_unfused(data: Q5Data) -> List[Q5Row]:
    """Per-op eager q5: one device dispatch per op, partials per channel,
    host rollup.  The plan path's reference."""
    dims = _dims_of(data)
    dim_sk = jnp.asarray(data.date_sk)
    dim_days = jnp.asarray(data.date_days)
    per_channel = {}
    for name in CHANNELS:
        parts = _channel_partials(
            {k: jnp.asarray(v) for k, v in
             _facts_of(data.channels[name]).items()},
            jnp.asarray(id_codes(dims.dim_id[name])[0]),
            dim_sk, dim_days, data.sales_date_lo, data.sales_date_hi)
        per_channel[name] = ChannelPartials(*map(np.asarray, parts))
    return _grid(per_channel, dims).rows()


def q5_host_channel_partials(facts: Dict[str, np.ndarray], code: np.ndarray,
                             date_sk: np.ndarray, date_days: np.ndarray,
                             lo: int, hi: int) -> ChannelPartials:
    """Host (numpy) reference for one channel's partial vectors over its
    codes (``code`` maps a 1-based dimension key to its 0-based id code):
    the same joins, window and sums as the device, int64-exact.  Bucket-
    local by construction: its working set is the rows it is given (how
    streamed q5 checks each bucket without a global materialize)."""
    n = int(code.max()) + 1

    def member(date, dvalid):
        idx = np.clip(np.searchsorted(date_sk, date), 0, len(date_sk) - 1)
        return (dvalid & (date_sk[idx] == date) & (date_days[idx] >= lo)
                & (date_days[idx] <= hi))

    def seg(values, sk, ok, dtype=np.int64):
        acc = np.zeros(n, dtype)
        np.add.at(acc, code[sk[ok].astype(np.int64) - 1],
                  values[ok].astype(dtype))
        return acc

    if "ret_item" in facts:
        site = {(int(o), int(i)): (int(s), bool(v)) for o, i, s, v in zip(
            facts["sales_order"], facts["sales_item"], facts["sales_sk"],
            facts["sales_sk_valid"])}
        found = [site.get((int(o), int(i)), (1, False)) for o, i in
                 zip(facts["ret_order"], facts["ret_item"])]
        ret_sk = np.asarray([s for s, _v in found], np.int64).reshape(-1)
        ret_valid = np.asarray([v for _s, v in found], bool).reshape(-1)
    else:
        ret_sk, ret_valid = facts["ret_sk"], facts["ret_sk_valid"]
    s_ok = facts["sales_sk_valid"] & member(facts["sales_date"],
                                            facts["sales_date_valid"])
    r_ok = ret_valid & member(facts["ret_date"], facts["ret_date_valid"])
    sales = seg(facts["sales_price"], facts["sales_sk"], s_ok)
    profit_s = seg(facts["sales_profit"], facts["sales_sk"], s_ok)
    returns_ = seg(facts["ret_amt"], ret_sk, r_ok)
    loss = seg(facts["ret_loss"], ret_sk, r_ok)
    count = (seg(np.ones(len(s_ok), np.int32), facts["sales_sk"], s_ok,
                 np.int32)
             + seg(np.ones(len(r_ok), np.int32), ret_sk, r_ok, np.int32))
    return ChannelPartials(sales, returns_, profit_s - loss, count)


def q5_host_oracle(data: Q5Data,
                   limit: Optional[int] = ROWS_LIMIT) -> List[Q5Row]:
    """The query on the host: :func:`q5_host_channel_partials` for every
    channel, then the rollup."""
    dims = _dims_of(data)
    return _grid({n: q5_host_channel_partials(
        _facts_of(data.channels[n]), id_codes(dims.dim_id[n])[0],
        data.date_sk, data.date_days, data.sales_date_lo,
        data.sales_date_hi) for n in CHANNELS}, dims).rows(limit)


# -------------------------------------------------------------- the plan path


def q5_local(data: Q5Data) -> List[Q5Row]:
    """Single-chip q5 through the compiled plan: the six-scan pipeline is
    ONE jitted program (cached across calls on the pow2 bucket lattice),
    then the host rollup."""
    from spark_rapids_jni_tpu.plans.runtime import execute_plan

    dims = _dims_of(data)
    tables = _q5_tables({n: _facts_of(data.channels[n]) for n in CHANNELS},
                        dims)
    outputs = execute_plan(None, _plan_of(dims), tables)
    return _grid(_partials_of(outputs), dims).rows()


def make_distributed_q5(mesh, data: Q5Data):
    """Compiled distributed q5 plan over ``mesh``'s data axis.

    Returns the :class:`plans.cache.CompiledPlan` for ``data``'s geometry
    and batch bucket — facts sharded over DATA_AXIS, dimensions
    replicated, partial vectors psum'd.  Same-geometry data returns the
    IDENTICAL cached object with O(1) host work on a hit: the cache key
    derives from lengths and dtypes alone, never a padded copy."""
    from spark_rapids_jni_tpu.plans.runtime import compiled_plan_for

    dims = _dims_of(data)
    tables = _q5_tables({n: _facts_of(data.channels[n]) for n in CHANNELS},
                        dims)
    return compiled_plan_for(_plan_of(dims), mesh, tables)


def run_q5_partials(
    mesh,
    batch: Dict[str, Dict[str, np.ndarray]],
    dims: Q5Dims,
    *,
    budget=None,
    task_id: int = 0,
    manage_task: bool = True,
) -> Dict[str, ChannelPartials]:
    """Governed distributed q5 PARTIALS over a host fact batch.

    ``batch`` maps channel -> fact-array dict (the ``_facts_of`` field
    names).  The whole pipeline is ONE compiled plan under ONE governed
    bracket: one admission for the fused working set, RetryOOM re-runs
    the fused program, SplitAndRetryOOM halves every probe-side scan and
    re-executes per half (exact — all aggregates are additive, and every
    half probes the whole web_sales build), and one flight-recorder task
    spans the plan.  Every caller with one dimension geometry and batch
    bucket — in-memory q5, every bucket of streamed q5 — reuses ONE
    cached program."""
    from spark_rapids_jni_tpu.plans.runtime import run_governed_plan

    outputs = run_governed_plan(
        mesh, _plan_of(dims), _q5_tables(batch, dims),
        budget=budget, task_id=task_id, manage_task=manage_task,
    )
    return _partials_of(outputs)


def run_distributed_q5_grid(mesh, data: Q5Data, *, budget=None,
                            task_id: int = 0,
                            manage_task: bool = True) -> Q5Grid:
    """Governed distributed q5 through the compiled plan, up to the
    downloaded partials and the ids that decode them."""
    dims = _dims_of(data)
    per_channel = run_q5_partials(
        mesh, {n: _facts_of(data.channels[n]) for n in CHANNELS}, dims,
        budget=budget, task_id=task_id, manage_task=manage_task)
    return _grid(per_channel, dims)


def run_distributed_q5(mesh, data: Q5Data, *, budget=None, task_id: int = 0,
                       manage_task: bool = True) -> List[Q5Row]:
    """Governed distributed q5: :func:`run_distributed_q5_grid`, then the
    rollup, its order and its limit."""
    return run_distributed_q5_grid(mesh, data, budget=budget,
                                   task_id=task_id,
                                   manage_task=manage_task).rows()
