"""TPC-DS query 5 as the benchmark drives it (query5.tpl, SALES_DATE
2000-08-23).

Per channel the query unions the sales and the returns, keeps the rows
whose date lies in the 15 days from SALES_DATE (both ends in), joins them
to the channel's business dimension and sums per business id:

    ssr: store_sales ∪ store_returns ⋈ date_dim ⋈ store, by s_store_id
    csr: catalog_sales ∪ catalog_returns ⋈ date_dim ⋈ catalog_page,
         by cp_catalog_page_id
    wsr: web_sales ∪ (web_returns ⟕ web_sales on (wr_item_sk = ws_item_sk
         and wr_order_number = ws_order_number)) ⋈ date_dim ⋈ web_site,
         by web_site_id

    SELECT channel, id, SUM(sales), SUM(returns), SUM(profit - profit_loss)
    FROM ('store channel', 'store' || s_store_id, ...) ∪ (...) ∪ (...)
    GROUP BY ROLLUP (channel, id) ORDER BY channel, id LIMIT 100

A web return takes its site from the sale it names; a return that names
no sale, or whose sale has a null site, drops out at the join with
web_site.  Spark sorts nulls first: the grand total leads, then each
channel's subtotal before its ids.

The configuration's file gives the row counts, the domains and the
qualification date.  The tables are generated on the host: each fact table
in fixed blocks of 2**20 rows, each from its own generator seeded by (seed,
table, block); web_sales' orders and the lines that web_returns name from
one generator each, seeded by (seed, table); the dimensions are fixed.  The
same seed gives the same tables, and every seed the same sizes.  The
program under test is ``models.q5.run_distributed_q5_grid`` over a (chips,
1) mesh, handed the spec's raw columns; the reference is plain numpy and
imports nothing of the program.

An answer holds the query's 100 rows, every rollup row (each channel's ids,
its subtotal and the grand total, in the query's order) and the grand
total's three sums.  The 100 rows are the grand total, the catalog
subtotal and 98 catalog pages: the store and web groups, and so the key
join and the grouping by business id of store and web_site, show only in
the groups and the total.
"""

from __future__ import annotations

import collections
import itertools
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

#: the query's LIMIT
LIMIT = 100
BLOCK_ROWS = 1 << 20
#: table numbers in the generators' seeds
(STORE_SALES, STORE_RETURNS, CATALOG_SALES, CATALOG_RETURNS, WEB_SALES,
 WEB_RETURNS, WEB_ORDERS, WEB_RETURN_LINES) = range(8)
#: per channel: its label, its dimension's table (also the prefix of its
#: ids in the result) and the configuration's key of its row count
CHANNELS = (("store", "store channel", "store", "store_rows"),
            ("catalog", "catalog channel", "catalog_page",
             "catalog_page_rows"),
            ("web", "web channel", "web_site", "web_site_rows"))

Tables = Dict[str, Dict[str, np.ndarray]]
Row = Tuple[object, object, int, int, int]  # channel, id, sales, returns, profit
Answer = Dict[str, object]  # {"rows": [Row], "groups": [Row], "total": (3)}


def _days(day: str) -> int:
    return int(np.datetime64(day, "D").astype(np.int64))


def _business_ids(n: int, scd: bool) -> np.ndarray:
    """dsdgen's 16-letter ids (A-P hex digits from the ninth letter on,
    least significant first); a dimension that keeps history repeats its
    ids in runs of 1, 2 and 3 rows per six."""
    ids = []
    for row in range(n):
        key = 3 * (row // 6) + (0, 1, 1, 2, 2, 2)[row % 6] + 1 if scd \
            else row + 1
        ids.append("AAAAAAAA" + "".join(
            chr(ord("A") + ((key >> (4 * i)) & 0xF)) for i in range(8)))
    return np.asarray(ids)


def _blocks(seed: int, table: int, n: int):
    """(first row, last row + 1, rng) of each generation block."""
    for block, a in enumerate(range(0, n, BLOCK_ROWS)):
        yield a, min(n, a + BLOCK_ROWS), np.random.default_rng(
            [seed % 2**64, table, block])


def _money(config: dict, rng, m: int) -> Tuple[np.ndarray, np.ndarray]:
    """(quantity x price, quantity x (price - wholesale cost)) cents."""
    qty = rng.integers(1, int(config["quantity_max"]) + 1, m)
    price = rng.integers(0, int(config["sales_price_max_cents"]) + 1, m)
    cost = rng.integers(int(config["wholesale_cost_min_cents"]),
                        int(config["wholesale_cost_max_cents"]) + 1, m)
    return qty * price, qty * (price - cost)


def _sales(config: dict, seed: int, table: int, n: int, n_dim: int) -> dict:
    null = float(config["null_share"])
    out = {"sk": np.empty(n, np.int32), "sk_valid": np.empty(n, bool),
           "date": np.empty(n, np.int32), "date_valid": np.empty(n, bool),
           "price": np.empty(n, np.int64), "profit": np.empty(n, np.int64)}
    for a, b, rng in _blocks(seed, table, n):
        out["sk"][a:b] = rng.integers(1, n_dim + 1, b - a, dtype=np.int32)
        out["sk_valid"][a:b] = rng.random(b - a) >= null
        out["date"][a:b] = rng.integers(
            int(config["sold_date_sk_first"]),
            int(config["sold_date_sk_last"]) + 1, b - a, dtype=np.int32)
        out["date_valid"][a:b] = rng.random(b - a) >= null
        out["price"][a:b], out["profit"][a:b] = _money(config, rng, b - a)
    return out


def _returns(config: dict, seed: int, table: int, n: int, n_dim=None,
             sold=None) -> dict:
    """Returns rows: the returned date 1..return_lag_days_max days after a
    sold date (``sold``'s row, else one drawn uniform); a dimension key
    unless ``n_dim`` is None."""
    null = float(config["null_share"])
    out = {"date": np.empty(n, np.int32), "date_valid": np.empty(n, bool),
           "amt": np.empty(n, np.int64), "loss": np.empty(n, np.int64)}
    if n_dim is not None:
        out["sk"] = np.empty(n, np.int32)
        out["sk_valid"] = np.empty(n, bool)
    for a, b, rng in _blocks(seed, table, n):
        m = b - a
        if n_dim is not None:
            out["sk"][a:b] = rng.integers(1, n_dim + 1, m, dtype=np.int32)
            out["sk_valid"][a:b] = rng.random(m) >= null
        base = sold[a:b] if sold is not None else rng.integers(
            int(config["sold_date_sk_first"]),
            int(config["sold_date_sk_last"]) + 1, m)
        out["date"][a:b] = base + rng.integers(
            1, int(config["return_lag_days_max"]) + 1, m)
        out["date_valid"][a:b] = rng.random(m) >= null
        amt, _profit = _money(config, rng, m)
        out["amt"][a:b] = amt
        out["loss"][a:b] = rng.integers(0, amt // 2 + 1) + rng.integers(
            0, int(config["return_fee_max_cents"]) + 1, m)
    return out


def _web_orders(config: dict, seed: int, n: int):
    """(ws_item_sk, ws_order_number) of ``n`` lines: orders of 1..L lines,
    each line a distinct item (first item plus a per-order stride)."""
    lines_max = int(config["lines_per_order_max"])
    items = int(config["item_rows"])
    rng = np.random.default_rng([seed % 2**64, WEB_ORDERS])
    lines = rng.integers(1, lines_max + 1, n + 1)
    ends = np.cumsum(lines)
    n_orders = int(np.searchsorted(ends, n)) + 1
    lines, ends = lines[:n_orders], ends[:n_orders]
    order = np.repeat(np.arange(n_orders), lines)[:n]
    line = np.arange(n) - (ends - lines)[order]
    start = rng.integers(0, items, n_orders)
    stride = rng.integers(1, max(2, items // lines_max), n_orders)
    item = (start[order] + line * stride[order]) % items + 1
    return item.astype(np.int32), (order + 1).astype(np.int32)


def _web_returns(config: dict, seed: int, ws: dict, n: int) -> dict:
    """Returns of distinct web_sales lines, and the orphan share: a sold
    item under an order number past the last order."""
    rng = np.random.default_rng([seed % 2**64, WEB_RETURN_LINES])
    n_orphan = int(round(n * float(config["orphan_return_share"])))
    lines = np.concatenate([
        rng.choice(len(ws["item"]), n - n_orphan, replace=False),
        rng.integers(0, len(ws["item"]), n_orphan)])
    ret = _returns(config, seed, WEB_RETURNS, n, sold=ws["date"][lines])
    ret["item"] = ws["item"][lines]
    ret["order"] = ws["order"][lines]
    ret["order"][n - n_orphan:] = int(ws["order"][-1]) + 1 + np.arange(
        n_orphan)
    return ret


def _entry():
    """The program's entry; a program without it cannot run this cell."""
    from spark_rapids_jni_tpu.models import q5

    if not hasattr(q5, "run_distributed_q5_grid"):
        raise SystemExit(
            "benchmark: this program's models.q5 has no "
            "run_distributed_q5_grid: it does not run q5 on the spec's "
            "columns, so the q5 cell cannot run")
    return q5.run_distributed_q5_grid


def generate(config: dict, seed: int) -> Tables:
    """The six fact tables, the three business dimensions (each row's
    business id, by surrogate key 1..n), date_dim (d_date_sk, d_date as
    days since 1970-01-01) and the window's first and last day as
    ``params``.  It first checks that the program can run the cell."""
    _entry()
    c = config
    t: Tables = {
        "store_sales": _sales(c, seed, STORE_SALES,
                              int(c["store_sales_rows"]),
                              int(c["store_rows"])),
        "store_returns": _returns(c, seed, STORE_RETURNS,
                                  int(c["store_returns_rows"]),
                                  int(c["store_rows"])),
        "catalog_sales": _sales(c, seed, CATALOG_SALES,
                                int(c["catalog_sales_rows"]),
                                int(c["catalog_page_rows"])),
        "catalog_returns": _returns(c, seed, CATALOG_RETURNS,
                                    int(c["catalog_returns_rows"]),
                                    int(c["catalog_page_rows"])),
        "web_sales": _sales(c, seed, WEB_SALES, int(c["web_sales_rows"]),
                            int(c["web_site_rows"])),
    }
    ws = t["web_sales"]
    ws["item"], ws["order"] = _web_orders(c, seed, len(ws["sk"]))
    t["web_returns"] = _web_returns(c, seed, ws, int(c["web_returns_rows"]))
    for _name, _label, dim, rows_key in CHANNELS:
        t[dim] = {"id": _business_ids(int(c[rows_key]),
                                      scd=dim != "catalog_page")}
    n = int(c["date_dim_rows"])
    t["date_dim"] = {
        "sk": (int(c["date_dim_first_sk"]) + np.arange(n)).astype(np.int32),
        "days": (_days(c["date_dim_first_date"]) + np.arange(n)).astype(
            np.int32)}
    lo = _days(c["sales_date"])
    t["params"] = {"lo": np.int64(lo),
                   "hi": np.int64(lo + int(c["window_days"]) - 1)}
    return t


FACTS = ("store_sales", "store_returns", "catalog_sales", "catalog_returns",
         "web_sales", "web_returns")


def rows(tables: Tables) -> int:
    """Real fact rows one query reads, over the six tables (no padding)."""
    return sum(len(tables[f]["date"]) for f in FACTS)


def _answer(rows: Sequence, groups: Sequence) -> Answer:
    """The result's rows, every rollup row and the grand total's sums, as
    plain Python values."""
    def plain(rs):
        return [(r[0], r[1], int(r[2]), int(r[3]), int(r[4])) for r in rs]

    groups = plain(groups)
    total = next((g[2:] for g in groups if g[0] is None), (0, 0, 0))
    return {"rows": plain(rows), "groups": groups, "total": tuple(total)}


def system(config: dict, devices: Sequence) -> Callable[[Tables], Answer]:
    """The program's q5 entry over a (len(devices), 1) mesh: the id codes,
    plan compile or cache, pad and upload, governor admission, the
    compiled plan with its window and id gathers, the web returns' key
    join and the grouped sums, the download of the partials, and the
    rollup with its order and limit."""
    from spark_rapids_jni_tpu.models.tpcds import ChannelTables, Q5Data
    from spark_rapids_jni_tpu.parallel import make_mesh

    run_grid = _entry()
    mesh = make_mesh((len(devices), 1), devices=list(devices))

    def run(tables: Tables) -> Answer:
        channels = {}
        for name, _label, dim, _rows in CHANNELS:
            s = tables[f"{name}_sales"]
            r = tables[f"{name}_returns"]
            web = name == "web"
            channels[name] = ChannelTables(
                sales_sk=s["sk"], sales_sk_valid=s["sk_valid"],
                sales_date=s["date"], sales_date_valid=s["date_valid"],
                sales_price=s["price"], sales_profit=s["profit"],
                ret_sk=r.get("sk"), ret_sk_valid=r.get("sk_valid"),
                ret_date=r["date"], ret_date_valid=r["date_valid"],
                ret_amt=r["amt"], ret_loss=r["loss"],
                dim_sk=np.arange(1, len(tables[dim]["id"]) + 1,
                                 dtype=np.int32),
                dim_id=tables[dim]["id"],
                sales_item=s["item"] if web else None,
                sales_order=s["order"] if web else None,
                ret_item=r["item"] if web else None,
                ret_order=r["order"] if web else None)
        dd, p = tables["date_dim"], tables["params"]
        grid = run_grid(mesh, Q5Data(channels, dd["sk"], dd["days"],
                                     int(p["lo"]), int(p["hi"])))
        return _answer(grid.rows(), grid.rows(None))

    return run


def facts(tables: Tables) -> Dict[str, float]:
    """Quantities of the query that the per-layer readers need.

    ``min_bytes``: what a query must move at the least: each scanned fact
    column read once (26 B a real row of store and catalog sales and
    returns: a 4 B key, a 4 B date, their 1 B validities and two 8 B
    decimals; web_sales 34 B with its item and order, web_returns 29 B),
    the dimension columns the joins read (a 4 B day of each date_dim row,
    a 4 B id code of each store, catalog_page and web_site row), and the
    partials written (three 8 B sums and a 4 B count a business id)."""
    scanned = sum(v.nbytes for f in FACTS for v in tables[f].values())
    ids = sum(len(np.unique(tables[dim]["id"]))
              for _n, _l, dim, _r in CHANNELS)
    dims = sum(len(tables[dim]["id"]) for _n, _l, dim, _r in CHANNELS)
    return {"min_bytes": scanned + 4 * len(tables["date_dim"]["sk"])
            + 4 * dims + 28 * ids}


def _q5(tables: Tables, dtype=np.int64) -> Answer:
    """q5 with every sum in ``dtype``."""
    dd, p = tables["date_dim"], tables["params"]
    lo, hi, sk0 = int(p["lo"]), int(p["hi"]), int(dd["sk"][0])

    def in_window(t):
        day = dd["days"][np.clip(t["date"].astype(np.int64) - sk0, 0,
                                 len(dd["days"]) - 1)]
        return t["date_valid"] & (day >= lo) & (day <= hi)

    ws = tables["web_sales"]
    key = (ws["order"].astype(np.int64) << 32) | ws["item"].astype(np.int64)
    by_key = np.argsort(key)
    groups: List[Row] = []
    for name, label, dim, _rows in CHANNELS:
        sales = tables[f"{name}_sales"]
        ret = tables[f"{name}_returns"]
        s_keep = sales["sk_valid"] & in_window(sales)
        s_sk = sales["sk"][s_keep]
        r_keep = in_window(ret)
        if name == "web":
            want = (ret["order"].astype(np.int64) << 32) \
                | ret["item"].astype(np.int64)
            at = np.clip(np.searchsorted(key[by_key], want), 0,
                         len(key) - 1)
            line = by_key[at]
            r_keep &= (key[line] == want) & ws["sk_valid"][line]
            r_sk = ws["sk"][line][r_keep]
        else:
            r_keep &= ret["sk_valid"]
            r_sk = ret["sk"][r_keep]
        ids = tables[dim]["id"]
        names, g = np.unique(np.concatenate([ids[s_sk - 1], ids[r_sk - 1]]),
                             return_inverse=True)
        g = g.reshape(-1)
        n_s = len(s_sk)
        sums = np.zeros((4, len(names)), dtype)
        for i, (idx, v) in enumerate((
                (g[:n_s], sales["price"][s_keep]),
                (g[n_s:], ret["amt"][r_keep]),
                (g[:n_s], sales["profit"][s_keep]),
                (g[n_s:], ret["loss"][r_keep]))):
            np.add.at(sums[i], idx, v.astype(dtype))
        for j, ident in enumerate(names.tolist()):
            # the id column is the dimension's name, then its id
            groups.append((label, dim + ident, sums[0, j], sums[1, j],
                           sums[2, j] - sums[3, j]))
    rollup = list(groups)
    for label in sorted({r[0] for r in groups}):
        mine = [r for r in groups if r[0] == label]
        rollup.append((label, None) + tuple(
            np.sum([r[i] for r in mine], dtype=dtype) for i in (2, 3, 4)))
    if groups:
        rollup.append((None, None) + tuple(
            np.sum([r[i] for r in groups], dtype=dtype) for i in (2, 3, 4)))
    rollup.sort(key=lambda r: (r[0] is not None, r[0] or "",
                               r[1] is not None, r[1] or ""))
    return _answer(rollup[:LIMIT], rollup)


def reference(tables: Tables) -> Answer:
    """Plain numpy q5: int64 sums, the rollup, its order and its limit."""
    return _q5(tables)


def control(tables: Tables) -> Answer:
    """The reference one precision down: every sum in float32.  It breaks
    the configuration's exact int64 cents; the comparison must call it not
    correct."""
    return _q5(tables, dtype=np.float32)


#: the checks, in the order ``_gaps`` gives them
CHECKS = ("row_gap", "group_gap", "total_gap")


def _gaps(ans: Answer, want: Answer) -> Tuple[int, int, int]:
    rows = sum(1 for a, w in itertools.zip_longest(ans["rows"], want["rows"])
               if a != w)
    got = collections.Counter(ans["groups"])
    ref = collections.Counter(want["groups"])
    groups = sum(((got - ref) + (ref - got)).values())
    total = sum(abs(a - w) for a, w in zip(ans["total"], want["total"]))
    return rows, groups, total


def checks(answers: List[Answer], want: Answer):
    """(failed answers, {check: {"value", "limit"}}): every answer of the
    window against the reference, each check its widest gap.  The answer
    is exact, so every limit is 0.

    ``row_gap``: the result's rows that differ from the reference's in any
    field or position (a missing or extra row counts).  ``group_gap``: the
    rollup rows, each (channel, id) with its sums and each subtotal and
    the grand total with theirs, that one side has and the other lacks.
    ``total_gap``: cents between the grand totals, over the three sums."""
    gaps = [_gaps(ans, want) for ans in answers]
    failed = sum(1 for g in gaps if any(g))
    return failed, {name: {"value": max((g[i] for g in gaps), default=0),
                           "limit": 0}
                    for i, name in enumerate(CHECKS)}
