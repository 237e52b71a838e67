"""TPC-DS query 3 as the benchmark drives it.

    SELECT dt.d_year, item.i_brand_id brand_id, item.i_brand brand,
           SUM(ss_ext_sales_price) sum_agg
    FROM date_dim dt, store_sales, item
    WHERE dt.d_date_sk = store_sales.ss_sold_date_sk
      AND store_sales.ss_item_sk = item.i_item_sk
      AND item.i_manufact_id = 128 AND dt.d_moy = 11
    GROUP BY dt.d_year, item.i_brand, item.i_brand_id
    ORDER BY dt.d_year, sum_agg DESC, brand_id
    LIMIT 100

The configuration's file gives the row counts, the key domains and the
qualification literals.  The tables are generated on the host: store_sales
in fixed blocks of 2**20 rows, each from its own generator seeded by
(seed, table, block), and item from one generator seeded by (seed, table);
date_dim is the calendar.  The same seed gives the same tables, and every
seed the same sizes.  The program under test is
``models.q3.run_distributed_q3_grid`` over a (chips, 1) mesh, handed the
spec's raw columns, and its grid's order and limit; the reference is plain
numpy and imports nothing of the program.  Where two groups tie on (d_year,
sum_agg, brand_id), i_brand orders them.

An answer holds the query's 100 rows, every non-empty group of the grid
(each year's, in the query's order) and the grid's grand total, its sums
added in the grid's own dtype.  At SF 10 no group's sum passes 2**24 cents,
so sums in float32 would give every row and group exactly; the grand total,
about 1.8e9 cents, is what a float32 grid cannot hold.
"""

from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

#: the query's LIMIT
LIMIT = 100
BLOCK_ROWS = 1 << 20
#: table numbers in the generators' seeds
STORE_SALES, ITEM = 0, 1
#: syllables of the generated i_brand names
SYLLABLES = ("amalg", "edu", "export", "import", "brand", "corp", "scholar",
             "univ", "maxi", "nameless", "packs", "ation", "ought", "able",
             "pri", "anti")

Tables = Dict[str, Dict[str, np.ndarray]]
Row = Tuple[int, int, str, int]  # (d_year, brand_id, brand, sum_agg cents)
Answer = Dict[str, object]  # {"rows": [Row], "groups": [Row], "total": int}


def _item(config: dict, seed: int) -> Dict[str, np.ndarray]:
    """i_brand_id a composite of category, class and brand drawn per item,
    i_brand a name made from it, i_manufact_id uniform."""
    n = int(config["item_rows"])
    rng = np.random.default_rng([seed % 2**64, ITEM])
    category = rng.integers(1, int(config["categories"]) + 1, n)
    klass = rng.integers(1, int(config["classes"]) + 1, n)
    brand = rng.integers(1, int(config["brands"]) + 1, n)
    brand_id = (category * 10**6 + klass * 10**3 + brand).astype(np.int32)
    ids, inverse = np.unique(brand_id, return_inverse=True)
    names = np.asarray([
        f"{SYLLABLES[b // 10**6 - 1]}{SYLLABLES[(b // 10**3) % 10**3 - 1]}"
        f" #{b % 10**3}" for b in ids.tolist()])
    return {
        "sk": np.arange(1, n + 1, dtype=np.int32),
        "brand_id": brand_id,
        "brand": names[inverse.reshape(-1)],
        "manufact_id": rng.integers(1, int(config["manufact_ids"]) + 1, n,
                                    dtype=np.int32),
    }


def _date_dim(config: dict) -> Dict[str, np.ndarray]:
    """One row a day from date_dim_first_date, keys from date_dim_first_sk."""
    n = int(config["date_dim_rows"])
    days = np.datetime64(config["date_dim_first_date"]) + np.arange(n)
    months = days.astype("datetime64[M]").astype(np.int64)
    return {
        "sk": (int(config["date_dim_first_sk"]) + np.arange(n)).astype(
            np.int32),
        "year": (months // 12 + 1970).astype(np.int32),
        "moy": (months % 12 + 1).astype(np.int32),
    }


def generate(config: dict, seed: int) -> Tables:
    """store_sales (item key, sold-date key, each with its validity, and
    the extended price in cents), item, date_dim, and the query's
    qualification literals as ``params``."""
    n = int(config["store_sales_rows"])
    null = float(config["null_share"])
    lo = int(config["sold_date_sk_first"])
    hi = int(config["sold_date_sk_last"])
    ss = {"item": np.empty(n, np.int32), "item_valid": np.empty(n, bool),
          "date": np.empty(n, np.int32), "date_valid": np.empty(n, bool),
          "price": np.empty(n, np.int64)}
    for block, a in enumerate(range(0, n, BLOCK_ROWS)):
        b = min(n, a + BLOCK_ROWS)
        rng = np.random.default_rng([seed % 2**64, STORE_SALES, block])
        ss["item"][a:b] = rng.integers(1, int(config["item_rows"]) + 1, b - a,
                                       dtype=np.int32)
        ss["item_valid"][a:b] = rng.random(b - a) >= null
        ss["date"][a:b] = rng.integers(lo, hi + 1, b - a, dtype=np.int32)
        ss["date_valid"][a:b] = rng.random(b - a) >= null
        ss["price"][a:b] = rng.integers(
            1, int(config["quantity_max"]) + 1, b - a) * rng.integers(
            0, int(config["sales_price_max_cents"]) + 1, b - a)
    params = {k: np.int64(config[k]) for k in ("manufact_id", "moy")}
    return {"store_sales": ss, "item": _item(config, seed),
            "date_dim": _date_dim(config), "params": params}


def rows(tables: Tables) -> int:
    """Real store_sales rows one query reads (no padding)."""
    return len(tables["store_sales"]["item"])


def _answer(rows: Sequence, groups: Sequence, sums: np.ndarray) -> Answer:
    """The result's rows, every group and the grand total of ``sums``,
    added in their own dtype, as plain Python values."""
    def plain(rs):
        return [(int(y), int(b), str(n), int(s)) for y, b, n, s in rs]

    return {"rows": plain(rows), "groups": plain(groups),
            "total": int(np.sum(sums, dtype=sums.dtype))}


def system(config: dict, devices: Sequence) -> Callable[[Tables], Answer]:
    """The program's q3 entry over a (len(devices), 1) mesh: the brand
    coding, plan compile or cache, pad and upload, governor admission, the
    compiled plan with its gathers, filter and grouped sum, the download
    of the grid and the result's order and limit."""
    from spark_rapids_jni_tpu.models.q3 import run_distributed_q3_grid
    from spark_rapids_jni_tpu.models.tpcds import Q3Data
    from spark_rapids_jni_tpu.parallel import make_mesh

    mesh = make_mesh((len(devices), 1), devices=list(devices))

    def run(tables: Tables) -> Answer:
        ss, item, dd = (tables[t] for t in ("store_sales", "item",
                                            "date_dim"))
        data = Q3Data(
            ss_item_sk=ss["item"], ss_item_sk_valid=ss["item_valid"],
            ss_sold_date_sk=ss["date"], ss_sold_date_sk_valid=ss["date_valid"],
            ss_ext_sales_price=ss["price"],
            item_sk=item["sk"], item_brand_id=item["brand_id"],
            item_brand=item["brand"], item_manufact_id=item["manufact_id"],
            date_sk=dd["sk"], date_year=dd["year"], date_moy=dd["moy"],
            manufact_id=int(tables["params"]["manufact_id"]),
            moy=int(tables["params"]["moy"]))
        grid = run_distributed_q3_grid(mesh, data)
        return _answer(grid.rows(), grid.rows(None), grid.sums)

    return run


def facts(tables: Tables) -> Dict[str, float]:
    """Quantities of the query that the per-layer readers need.

    ``min_bytes``: what a query must move at the least: each scanned
    store_sales column read once (4 + 1 + 4 + 1 + 8 B a real row), the
    dimension fields the joins and the filter read (a 4 B group code and
    the 4 B manufacturer of each item, the 4 B year and month of each
    day), and the grid written (8 B sum and 4 B count for each year and
    distinct (i_brand_id, i_brand))."""
    ss, item, dd = (tables[t] for t in ("store_sales", "item", "date_dim"))
    scanned = sum(v.nbytes for v in ss.values())
    groups = np.unique(np.stack([
        item["brand_id"].astype(np.int64),
        np.unique(item["brand"], return_inverse=True)[1].reshape(-1)]),
        axis=1).shape[1]
    years = int(dd["year"].max()) - int(dd["year"].min()) + 1
    return {"min_bytes": scanned + 8 * len(item["sk"]) + 8 * len(dd["sk"])
            + 12 * years * groups}


def _q3(tables: Tables, validity: bool = True, dtype=np.int64) -> Answer:
    """q3 with every sum in ``dtype``; ``validity`` False joins a null
    foreign key by the value stored under it."""
    ss, item, dd, q = (tables[t] for t in ("store_sales", "item", "date_dim",
                                           "params"))
    i = ss["item"].astype(np.int64) - 1  # i_item_sk is 1..item_rows
    d = ss["date"].astype(np.int64) - int(dd["sk"][0])
    keep = ((item["manufact_id"][i] == int(q["manufact_id"]))
            & (dd["moy"][d] == int(q["moy"])))
    if validity:
        keep &= ss["item_valid"] & ss["date_valid"]
    i, d, price = i[keep], d[keep], ss["price"][keep]
    names, name_idx = np.unique(item["brand"][i], return_inverse=True)
    keys, group = np.unique(
        np.stack([dd["year"][d].astype(np.int64),
                  item["brand_id"][i].astype(np.int64),
                  name_idx.reshape(-1).astype(np.int64)]),
        axis=1, return_inverse=True)
    sums = np.zeros(keys.shape[1], dtype)
    np.add.at(sums, group.reshape(-1), price.astype(dtype))
    year, brand_id, name = keys
    order = np.lexsort((name, brand_id, -sums, year))
    groups = [(year[g], brand_id[g], names[name[g]], sums[g]) for g in order]
    return _answer(groups[:LIMIT], groups, sums)


def reference(tables: Tables) -> Answer:
    """Plain numpy q3: int64 sums, lexsort, then the limit."""
    return _q3(tables)


def control(tables: Tables) -> Answer:
    """The reference one precision down: every sum in float32.  It breaks
    the configuration's exact int64 cents; the comparison must call it not
    correct."""
    return _q3(tables, dtype=np.float32)


def control_nulls_joined(tables: Tables) -> Answer:
    """The reference with validity ignored: a null foreign key joins by the
    value stored under it.  It breaks the configuration's null semantics;
    the comparison must call it not correct."""
    return _q3(tables, validity=False)


#: the checks, in the order ``_gaps`` gives them
CHECKS = ("row_gap", "group_gap", "total_gap")


def _gaps(ans: Answer, want: Answer) -> Tuple[int, int, int]:
    rows = sum(1 for a, w in itertools.zip_longest(ans["rows"], want["rows"])
               if a != w)
    got = {r[:3]: r[3] for r in ans["groups"]}
    ref = {r[:3]: r[3] for r in want["groups"]}
    groups = sum(1 for k in got.keys() | ref.keys()
                 if got.get(k) != ref.get(k))
    return rows, groups, abs(ans["total"] - want["total"])


def checks(answers: List[Answer], want: Answer):
    """(failed answers, {check: {"value", "limit"}}): every answer of the
    window against the reference, each check its widest gap.  The answer
    is exact, so every limit is 0.

    ``row_gap``: the result's rows that differ from the reference's in any
    field or position (a missing or extra row counts).  ``group_gap``: the
    groups of the whole grid, every year's, that one side lacks or whose
    sums differ.  ``total_gap``: cents between the grand totals."""
    gaps = [_gaps(ans, want) for ans in answers]
    failed = sum(1 for g in gaps if any(g))
    return failed, {name: {"value": max((g[i] for g in gaps), default=0),
                           "limit": 0}
                    for i, name in enumerate(CHECKS)}
