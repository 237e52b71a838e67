"""TPC-DS-shaped synthetic data for the NDS model pipelines (q5, q97).

Seeded generators of the tables the NDS queries touch, with the shapes
that make TPC-DS data hard: nullable foreign keys, string business ids
that repeat where a dimension keeps history, composite keys, and
decimal(7,2) money columns (stored as unscaled int64 cents, the Arrow/
Spark DECIMAL representation).  q5's fact tables follow section 3's row
counts: SF 10's, scaled linearly by ``sf`` (sf=0.01 ~ 55k fact rows, SF 10
55.4M); q3's and q97's generators size by their own rules.

This stands in for the reference benchmarks' generate_input.cu data layer
(/root/reference/src/main/cpp/benchmarks/common/generate_input.cu) on the
NDS side: not a full dsdgen port, but faithful to the column shapes the
query plans exercise.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import numpy as np

__all__ = ["Q3Data", "Q5Data", "Q5Dims", "q5_dims", "generate_q3_data",
           "generate_q5_data", "generate_q97_tables", "q97_blocks",
           "write_q97_parquet",
           "CHANNELS"]

#: q5's three channels, in the order its per-channel outputs come
CHANNELS = ("store", "catalog", "web")

_D0 = 2450815  # d_date_sk epoch base the q3 generator uses

#: TPC-DS v3.2.0 section 3 row counts at SF 10 of the tables q5 scans
Q5_ROWS_SF10 = {
    "store_sales": 28_800_991, "store_returns": 2_875_432,
    "catalog_sales": 14_401_261, "catalog_returns": 1_439_749,
    "web_sales": 7_197_566, "web_returns": 719_217,
}
#: the business dimensions at SF 10: store, catalog_page, web_site rows
Q5_DIM_ROWS = {"store": 102, "catalog": 12_000, "web": 42}
#: date_dim: one row a day from 1900-01-02, whose d_date_sk is 2415022
DATE_DIM_ROWS = 73_049
DATE_DIM_FIRST_SK = 2_415_022
DATE_DIM_FIRST_DAY = "1900-01-02"
#: sales are sold from 1998-01-02 to 2003-01-02
SOLD_DATE_SK_FIRST, SOLD_DATE_SK_LAST = 2_450_816, 2_452_642
#: query5.tpl's qualification SALES_DATE; the window is 15 days from it
Q5_SALES_DATE = "2000-08-23"
Q5_WINDOW_DAYS = 15


@dataclasses.dataclass
class ChannelTables:
    """One channel's sales and returns facts and its business dimension,
    column-oriented numpy arrays with TPC-DS's columns.

    Sales: the channel's dimension key (``ss_store_sk``,
    ``cs_catalog_page_sk``, ``ws_web_site_sk``), the sold-date key,
    ``ext_sales_price`` and ``net_profit``.  Returns: the dimension key
    (``sr_store_sk``, ``cr_catalog_page_sk``; web_returns has none: a web
    return finds its site through the sale it names by (``wr_item_sk``,
    ``wr_order_number``)), the returned-date key, ``return_amt`` and
    ``net_loss``.  Money is decimal(7,2) as unscaled int64 cents.  A
    nullable key carries a mask (True == valid).  ``dim_sk`` holds the
    dimension's dense surrogate keys 1..n and ``dim_id`` each row's
    business id (``s_store_id``,
    ``cp_catalog_page_id``, ``web_site_id``): store and web_site keep
    history, so one id names several surrogate keys.
    """

    sales_sk: np.ndarray
    sales_sk_valid: np.ndarray
    sales_date: np.ndarray
    sales_date_valid: np.ndarray
    sales_price: np.ndarray  # int64 cents
    sales_profit: np.ndarray  # int64 cents

    ret_sk: Optional[np.ndarray]  # None for web
    ret_sk_valid: Optional[np.ndarray]
    ret_date: np.ndarray
    ret_date_valid: np.ndarray
    ret_amt: np.ndarray
    ret_loss: np.ndarray

    dim_sk: np.ndarray  # [n_dim] surrogate keys (dense, 1..n)
    dim_id: np.ndarray  # [n_dim] business id strings, with repeats

    #: web only: ws_item_sk and ws_order_number, the primary key of
    #: web_sales, and wr_item_sk and wr_order_number of each return
    sales_item: Optional[np.ndarray] = None
    sales_order: Optional[np.ndarray] = None
    ret_item: Optional[np.ndarray] = None
    ret_order: Optional[np.ndarray] = None


@dataclasses.dataclass
class Q5Data:
    channels: Dict[str, ChannelTables]
    date_sk: np.ndarray  # date_dim's d_date_sk, dense from its first
    date_days: np.ndarray  # d_date as days since 1970-01-01
    sales_date_lo: int  # the window's first and last day, both included
    sales_date_hi: int


def business_ids(n: int, scd: bool) -> np.ndarray:
    """``n`` business ids as dsdgen writes them: 16 letters A-P, the key's
    hex digits from the ninth letter on, least significant first.  A
    dimension that keeps history (``scd``) repeats its ids in runs of 1, 2
    and 3 rows per six, as dsdgen's revisions do."""
    rows = np.arange(n)
    key = 3 * (rows // 6) + np.array([0, 1, 1, 2, 2, 2])[rows % 6] + 1 \
        if scd else rows + 1
    digits = (key[:, None] >> (4 * np.arange(8))) & 0xF
    letters = np.full((n, 16), ord("A"), np.uint8)
    letters[:, 8:] += digits.astype(np.uint8)
    return letters.view("S16").reshape(-1).astype(str)


def _money(rng, n: int, lo=0, hi=500_00) -> np.ndarray:
    return rng.randint(lo, hi, n).astype(np.int64)


def _nullable(rng, vals: np.ndarray, null_pct: float):
    valid = rng.rand(len(vals)) >= null_pct
    return np.where(valid, vals, 0).astype(vals.dtype), valid


def _days(day: str) -> int:
    return int(np.datetime64(day, "D").astype(np.int64))


@dataclasses.dataclass
class Q5Dims:
    """The q5 dimension side: date_dim + per-channel business dims.

    Deterministic and sf-independent (SF 10's dimensions at every scale:
    the spec grows them slowly, the facts linearly), so a streamed
    producer and a bucket executor can each rebuild them without
    exchanging anything — the replicated-broadcast-dim shape of the plan.
    """

    date_sk: np.ndarray
    date_days: np.ndarray
    sales_date_lo: int
    sales_date_hi: int
    dim_sk: Dict[str, np.ndarray]
    dim_id: Dict[str, np.ndarray]

    @property
    def n_dims(self):
        return tuple(len(self.dim_sk[n]) for n in CHANNELS)

    def channel_size(self, name: str) -> int:
        return len(self.dim_sk[name])


def q5_dims() -> Q5Dims:
    """Build the (deterministic) q5 dimension tables."""
    lo = _days(Q5_SALES_DATE)
    return Q5Dims(
        date_sk=np.arange(DATE_DIM_FIRST_SK, DATE_DIM_FIRST_SK
                          + DATE_DIM_ROWS, dtype=np.int32),
        date_days=(_days(DATE_DIM_FIRST_DAY)
                   + np.arange(DATE_DIM_ROWS)).astype(np.int32),
        sales_date_lo=lo,
        sales_date_hi=lo + Q5_WINDOW_DAYS - 1,
        dim_sk={n: np.arange(1, Q5_DIM_ROWS[n] + 1, dtype=np.int32)
                for n in CHANNELS},
        dim_id={n: business_ids(Q5_DIM_ROWS[n], scd=n != "catalog")
                for n in CHANNELS},
    )


def q5_rows(table: str, sf: float) -> int:
    """Rows of a q5 fact table at ``sf``: section 3's SF 10 count scaled
    linearly (at least 8)."""
    return max(8, int(round(Q5_ROWS_SF10[table] * sf / 10)))


def q5_sales(rng, n: int, n_dim: int, null_pct: float) -> dict:
    """One channel's sales rows: dimension and sold-date keys uniform,
    each null at ``null_pct``; ext_sales_price = quantity (1..100) x sales
    price (0.00..300.00) and net_profit = quantity x (sales price -
    wholesale cost (1.00..100.00)), in cents."""
    sk, skv = _nullable(rng, rng.randint(1, n_dim + 1, n).astype(np.int32),
                        null_pct)
    date, datev = _nullable(rng, rng.randint(
        SOLD_DATE_SK_FIRST, SOLD_DATE_SK_LAST + 1, n).astype(np.int32),
        null_pct)
    qty = rng.randint(1, 101, n).astype(np.int64)
    price = _money(rng, n, 0, 300_01)
    return {"sk": sk, "sk_valid": skv, "date": date, "date_valid": datev,
            "price": qty * price,
            "profit": qty * (price - _money(rng, n, 1_00, 100_01))}


def q5_returns(rng, n: int, n_dim: int, null_pct: float,
               sold=None) -> dict:
    """One channel's returns rows: the returned date 1..90 days after a
    sold date (``sold``, else one drawn uniform), null at ``null_pct``;
    return_amt = quantity (1..100) x price (0.00..300.00) and net_loss =
    up to half of it plus a fee of 0.00..100.00, in cents.  ``n_dim``
    None draws no dimension key (web_returns has none)."""
    if sold is None:
        sold = rng.randint(SOLD_DATE_SK_FIRST, SOLD_DATE_SK_LAST + 1, n)
    date, datev = _nullable(
        rng, (sold + rng.randint(1, 91, n)).astype(np.int32), null_pct)
    amt = rng.randint(1, 101, n).astype(np.int64) * _money(rng, n, 0, 300_01)
    out = {"date": date, "date_valid": datev, "amt": amt,
           "loss": (rng.randint(0, 2**31 - 1, n) % (amt // 2 + 1)
                    + _money(rng, n, 0, 100_01))}
    if n_dim is not None:
        out["sk"], out["sk_valid"] = _nullable(
            rng, rng.randint(1, n_dim + 1, n).astype(np.int32), null_pct)
    return out


#: web_sales lines per order, uniform over 1..LINES_MAX
LINES_MAX = 16
#: the share of web returns that name no web_sales row: a real sale's
#: item under an order number past the last order
ORPHAN_SHARE = 0.01


def web_orders(rng, n: int, n_items: int, first_order: int = 1):
    """(ws_item_sk, ws_order_number) of ``n`` web_sales lines: orders of
    1..LINES_MAX lines numbered from ``first_order``, each line a distinct
    item of 1..``n_items`` (the order's first item plus a per-order
    stride)."""
    if n_items < LINES_MAX:
        raise ValueError(f"{n_items} items cannot fill an order of "
                         f"{LINES_MAX} distinct lines")
    lines = rng.randint(1, LINES_MAX + 1, n + 1)
    ends = np.cumsum(lines)
    n_orders = int(np.searchsorted(ends, n)) + 1
    lines = lines[:n_orders]
    order = np.repeat(np.arange(first_order, first_order + n_orders,
                                dtype=np.int64), lines)[:n]
    line = np.arange(n) - np.repeat(ends[:n_orders] - lines, lines)[:n]
    start = rng.randint(0, n_items, n_orders)[order - first_order]
    stride = rng.randint(1, max(2, n_items // LINES_MAX),
                         n_orders)[order - first_order]
    item = (start + line * stride) % n_items + 1
    return item.astype(np.int32), order.astype(np.int32)


def web_returns_of(rng, sales: dict, n: int, null_pct: float,
                   next_order: int) -> dict:
    """``n`` web returns of the lines in ``sales``: distinct lines, each
    returned after its sale, plus an ``ORPHAN_SHARE`` that names an order
    number from ``next_order`` on, which no sale has."""
    n_orphan = int(round(n * ORPHAN_SHARE))
    lines = np.concatenate([
        rng.choice(len(sales["item"]), n - n_orphan, replace=False),
        rng.randint(0, len(sales["item"]), n_orphan)])
    sold = np.where(sales["date_valid"][lines], sales["date"][lines],
                    rng.randint(SOLD_DATE_SK_FIRST, SOLD_DATE_SK_LAST + 1, n))
    ret = q5_returns(rng, n, None, null_pct, sold=sold)
    ret["item"] = sales["item"][lines]
    ret["order"] = sales["order"][lines].copy()
    ret["order"][n - n_orphan:] = next_order + np.arange(n_orphan)
    return ret


def channel_tables(name: str, sales: dict, ret: dict,
                   dims: Q5Dims) -> ChannelTables:
    """A ChannelTables from generated sales and returns column dicts."""
    return ChannelTables(
        sales_sk=sales["sk"], sales_sk_valid=sales["sk_valid"],
        sales_date=sales["date"], sales_date_valid=sales["date_valid"],
        sales_price=sales["price"], sales_profit=sales["profit"],
        ret_sk=ret.get("sk"), ret_sk_valid=ret.get("sk_valid"),
        ret_date=ret["date"], ret_date_valid=ret["date_valid"],
        ret_amt=ret["amt"], ret_loss=ret["loss"],
        dim_sk=dims.dim_sk[name], dim_id=dims.dim_id[name],
        sales_item=sales.get("item"), sales_order=sales.get("order"),
        ret_item=ret.get("item"), ret_order=ret.get("order"))


#: item rows at SF 10, the domain of ws_item_sk
ITEM_ROWS = 102_000


def generate_q5_data(sf: float = 0.01, seed: int = 0,
                     null_pct: float = 0.04) -> Q5Data:
    """The q5 table set at scale factor ``sf``: section 3's row counts
    scaled from SF 10, SF 10's dimensions, the spec's key and date
    domains."""
    rng = np.random.RandomState(seed)
    dims = q5_dims()
    channels: Dict[str, ChannelTables] = {}
    for name in CHANNELS:
        n_dim = dims.channel_size(name)
        n_s = q5_rows(f"{name}_sales", sf)
        n_r = q5_rows(f"{name}_returns", sf)
        sales = q5_sales(rng, n_s, n_dim, null_pct)
        if name == "web":
            sales["item"], sales["order"] = web_orders(rng, n_s, ITEM_ROWS)
            ret = web_returns_of(rng, sales, n_r, null_pct,
                                 int(sales["order"].max()) + 1)
        else:
            ret = q5_returns(rng, n_r, n_dim, null_pct)
        channels[name] = channel_tables(name, sales, ret, dims)
    return Q5Data(channels, dims.date_sk, dims.date_days,
                  dims.sales_date_lo, dims.sales_date_hi)


@dataclasses.dataclass
class Q3Data:
    """q3 table set: store_sales fact + item and date_dim dimensions.

    item: dense surrogate keys 1..n_items, each row's ``i_brand_id`` and
    ``i_brand`` (many items share a brand; the ids may be sparse, as the
    spec's composite category/class/brand ids are), and a manufacturer id
    (the query's filter).  date_dim: dense keys with (d_year, d_moy)
    attributes.
    """

    ss_item_sk: np.ndarray
    ss_item_sk_valid: np.ndarray
    ss_sold_date_sk: np.ndarray
    ss_sold_date_sk_valid: np.ndarray
    ss_ext_sales_price: np.ndarray  # int64 cents (decimal scale 2)

    item_sk: np.ndarray  # [n_items] dense 1..n
    item_brand_id: np.ndarray  # [n_items] int32
    item_brand: np.ndarray  # [n_items] str: each row's i_brand
    item_manufact_id: np.ndarray  # [n_items] int32

    date_sk: np.ndarray  # [n_dates] dense keys (from date_sk[0])
    date_year: np.ndarray
    date_moy: np.ndarray

    manufact_id: int  # the query's i_manufact_id literal
    moy: int  # the query's d_moy literal
    #: the generator's dense brand table (brand_id b -> brand_names[b-1]);
    #: the query paths read ``item_brand`` and never this
    brand_names: Optional[list] = None


def generate_q3_data(sf: float = 0.01, seed: int = 0,
                     null_pct: float = 0.04) -> Q3Data:
    """Generate the q3 table set at scale factor ``sf``."""
    rng = np.random.RandomState(seed + 3)
    n_items = max(12, int(200 * sf))
    n_brands = max(5, n_items // 4)
    n_manufact = 8
    n_dates = 3 * 365
    n_sales = max(16, int(120_000 * sf))

    item_sk = np.arange(1, n_items + 1, dtype=np.int32)
    item_brand_id = rng.randint(1, n_brands + 1, n_items).astype(np.int32)
    item_manufact_id = rng.randint(1, n_manufact + 1, n_items).astype(np.int32)
    brand_names = [f"corpbrand #{b}" for b in range(1, n_brands + 1)]

    date_sk = np.arange(_D0, _D0 + n_dates, dtype=np.int32)
    date_year = (1998 + np.arange(n_dates) // 365).astype(np.int32)
    date_moy = (1 + (np.arange(n_dates) % 365) // 31).astype(np.int32)

    i_sk, i_v = _nullable(
        rng, rng.randint(1, n_items + 1, n_sales).astype(np.int32), null_pct)
    d_sk, d_v = _nullable(
        rng, rng.randint(_D0, _D0 + n_dates, n_sales).astype(np.int32),
        null_pct)

    return Q3Data(
        ss_item_sk=i_sk, ss_item_sk_valid=i_v,
        ss_sold_date_sk=d_sk, ss_sold_date_sk_valid=d_v,
        ss_ext_sales_price=_money(rng, n_sales),
        item_sk=item_sk, item_brand_id=item_brand_id,
        item_brand=np.asarray(brand_names)[item_brand_id - 1],
        item_manufact_id=item_manufact_id,
        date_sk=date_sk, date_year=date_year, date_moy=date_moy,
        manufact_id=int(rng.randint(1, n_manufact + 1)), moy=11,
        brand_names=brand_names,
    )


#: rows per seeded generation block of the q97 fact pair: the table is the
#: same whatever chunk size a streamed reader asks for
Q97_BLOCK_ROWS = 1 << 20


def q97_rows(sf: float) -> int:
    """Rows per q97 fact table at ``sf`` (SF1 store_sales is ~2.9M rows)."""
    return max(1000, int(2_800_000 * sf))


def q97_blocks(sf: float, seed: int):
    """The q97 fact pair as ``(side, cust, item)`` int32 blocks of at most
    :data:`Q97_BLOCK_ROWS` rows, store side first.  Block b of a side draws
    from its own seeded rng, so a streamed reader holds one block at a
    time and any prefix is reproducible."""
    n = q97_rows(sf)
    n_cust = max(2, n // 14)
    for side_idx, side in enumerate(("store", "catalog")):
        for block, start in enumerate(range(0, n, Q97_BLOCK_ROWS)):
            m = min(Q97_BLOCK_ROWS, n - start)
            rng = np.random.RandomState(
                (seed + 1_000_003 * side_idx + block) % (2**31 - 1))
            yield (side,
                   rng.randint(1, n_cust, m).astype(np.int32),
                   rng.randint(1, 18_000, m).astype(np.int32))


def generate_q97_tables(sf: float, seed: int):
    """The q97 fact pair: ``(customer_sk, item_sk)`` int32 arrays per
    channel, the concatenation of :func:`q97_blocks` (so the in-memory and
    the streamed harness modes see the same table)."""
    parts = {"store": ([], []), "catalog": ([], [])}
    for side, cust, item in q97_blocks(sf, seed):
        parts[side][0].append(cust)
        parts[side][1].append(item)
    return tuple((np.concatenate(parts[side][0]),
                  np.concatenate(parts[side][1]))
                 for side in ("store", "catalog"))


def write_q97_parquet(outdir: str, sf: float = 0.05, seed: int = 42,
                      rows_per_group: int = 65536):
    """Write the q97 fact pair as multi-row-group parquet files.

    Each file carries the two join keys plus money columns the query does
    NOT touch — so split planning via the footer (row-group midpoint
    filter) and column pruning are both load-bearing when the NDS harness
    reads these back (``nds_harness --input``).  Returns the two paths.
    """
    import os

    import pyarrow as pa
    import pyarrow.parquet as pq

    os.makedirs(outdir, exist_ok=True)
    store, catalog = generate_q97_tables(sf, seed)
    rng = np.random.RandomState(seed + 97)
    paths = {}
    for name, prefix, (cust, item) in (
            ("store_sales", "ss", store), ("catalog_sales", "cs", catalog)):
        n = len(cust)
        table = pa.table({
            f"{prefix}_customer_sk": pa.array(cust, pa.int32()),
            f"{prefix}_item_sk": pa.array(item, pa.int32()),
            # pruned by the q97 read schema: never materialized
            f"{prefix}_ext_sales_price": pa.array(
                _money(rng, n), pa.int64()),
            f"{prefix}_net_profit": pa.array(
                rng.rand(n) * 100.0, pa.float64()),
        })
        path = os.path.join(outdir, f"{name}.parquet")
        pq.write_table(table, path, row_group_size=rows_per_group)
        paths[name] = path
    return paths["store_sales"], paths["catalog_sales"]
