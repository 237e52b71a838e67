"""TPC-DS query 97 as the benchmark drives it.

q97 counts the (customer_sk, item_sk) pairs sold through the store only,
through the catalog only, and through both:

    SELECT SUM(store_only), SUM(catalog_only), SUM(store_and_catalog) FROM
      (SELECT ss_customer_sk, ss_item_sk FROM store_sales GROUP BY 1, 2) ssci
      FULL OUTER JOIN
      (SELECT cs_bill_customer_sk, cs_item_sk FROM catalog_sales
       GROUP BY 1, 2) csci ON (customer_sk, item_sk)

The configuration's file gives the row counts and key domains.  The tables
are generated on the host in fixed blocks of 2**20 rows, each from its own
generator seeded by (seed, table, block): the same seed gives the same
tables, and every seed the same sizes.  The program under test is
``models.q97.run_distributed_q97`` over a (chips, 1) mesh; the reference is
plain numpy and imports nothing of the program.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Sequence, Tuple

import numpy as np

BLOCK_ROWS = 1 << 20
#: (table, the configuration's row-count key), in generation order
FACTS = (("store", "store_sales_rows"), ("catalog", "catalog_sales_rows"))

Tables = Dict[str, Tuple[np.ndarray, np.ndarray]]
Answer = Tuple[int, int, int]  # (store_only, catalog_only, both)


def generate(config: dict, seed: int) -> Tables:
    """(customer_sk, item_sk) int32 columns of both fact tables, keys
    drawn uniformly over 1..customer_rows and 1..item_rows."""
    n_cust, n_item = int(config["customer_rows"]), int(config["item_rows"])
    tables = {}
    for t, (table, rows_key) in enumerate(FACTS):
        n = int(config[rows_key])
        cust = np.empty(n, np.int32)
        item = np.empty(n, np.int32)
        for block, lo in enumerate(range(0, n, BLOCK_ROWS)):
            hi = min(n, lo + BLOCK_ROWS)
            rng = np.random.default_rng([seed % 2**64, t, block])
            cust[lo:hi] = rng.integers(1, n_cust + 1, hi - lo, dtype=np.int32)
            item[lo:hi] = rng.integers(1, n_item + 1, hi - lo, dtype=np.int32)
        tables[table] = (cust, item)
    return tables


def rows(tables: Tables) -> int:
    """Real input fact rows one query reads (no padding)."""
    return sum(len(tables[t][0]) for t, _ in FACTS)


def system(config: dict, devices: Sequence) -> Callable[[Tables], Answer]:
    """The program's q97 entry over a (len(devices), 1) mesh: plan compile
    or cache, pad and upload, governor admission, the compiled plan with
    its Exchange and PresenceCount, and the download of the counts."""
    from spark_rapids_jni_tpu.models.q97 import run_distributed_q97
    from spark_rapids_jni_tpu.parallel import make_mesh

    mesh = make_mesh((len(devices), 1), devices=list(devices))

    def run(tables: Tables) -> Answer:
        out = run_distributed_q97(mesh, tables["store"], tables["catalog"])
        return int(out.store_only), int(out.catalog_only), int(out.both)

    return run


def facts(tables: Tables) -> Dict[str, float]:
    """Quantities of the query that the per-layer readers need.

    ``exchange_rows``: real rows one query sends through its Exchange
    (every row of both sides; the readers take the slots from the plans
    the window ran).  ``min_bytes``: what a query must move at the least:
    each scanned column read once and the three counts written."""
    return {
        "exchange_rows": rows(tables),
        "min_bytes": sum(c.nbytes + i.nbytes for c, i in tables.values())
        + 3 * 8,
    }


def _counts(s: np.ndarray, c: np.ndarray) -> Answer:
    """q97's counts from the distinct keys of each side."""
    s, c = np.unique(s), np.unique(c)
    both_sides = np.concatenate([s, c])
    both_sides.sort(kind="stable")  # two sorted runs: a linear merge
    both = int(np.count_nonzero(both_sides[1:] == both_sides[:-1]))
    return len(s) - both, len(c) - both, both


def reference(tables: Tables) -> Answer:
    """Plain numpy q97: exact 64-bit (customer_sk, item_sk) keys."""
    def key(side):
        cust, item = tables[side]
        return (cust.astype(np.int64) << 32) | item.astype(np.int64)

    return _counts(key("store"), key("catalog"))


def control(tables: Tables) -> Answer:
    """The reference one precision down: each pair hashed to a 32-bit key
    (the narrower sort key a later PR might be tempted by).  Collisions
    merge distinct pairs, so it breaks the configuration's guarantee of
    exact counts; the comparison must call it not correct."""
    def key(side):
        cust, item = tables[side]
        packed = (cust.astype(np.uint64) << np.uint64(32)) | item.astype(
            np.uint64)
        return ((packed * np.uint64(0x9E3779B97F4A7C15))
                >> np.uint64(32)).astype(np.uint32)

    return _counts(key("store"), key("catalog"))


def checks(answers: List[Answer], want: Answer):
    """(failed answers, {check: {"value", "limit"}}): every answer of the
    window against the reference.  The counts are exact, so the widest gap
    of any count has the limit 0."""
    gaps = [max(abs(a - w) for a, w in zip(ans, want)) for ans in answers]
    failed = sum(1 for g in gaps if g)
    return failed, {"count_gap": {"value": max(gaps, default=0), "limit": 0}}
