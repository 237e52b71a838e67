"""q5 (three-channel sales/returns rollup) vs an independent pandas oracle.

BASELINE config 5's second half (q97 lives in test_q97*.py).  The oracle
recomputes the whole query with pandas joins/groupbys from the same
generated tables — null FK drops, date-window dim join, the web returns'
key join to web_sales, per-id sums, ROLLUP(channel, id).
"""

import numpy as np
import pandas as pd

import jax

from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor, task_context
from spark_rapids_jni_tpu.models.q5 import (
    q5_local,
    run_distributed_q5,
)
from spark_rapids_jni_tpu.models.tpcds import CHANNELS, generate_q5_data
from spark_rapids_jni_tpu.parallel import make_mesh
import pytest

NDEV = 8


LABELS = {"store": ("store channel", "store"),
          "catalog": ("catalog channel", "catalog_page"),
          "web": ("web channel", "web_site")}


def _oracle(data):
    """pandas re-implementation of the q5 query: the window (both ends
    in), the web returns' left outer join to web_sales, the inner joins
    to the business dimension, sums per business id, then ROLLUP(channel,
    id) ordered with nulls first and LIMIT 100."""
    dates = pd.DataFrame({"sk": data.date_sk, "days": data.date_days})
    window = dates[(dates.days >= data.sales_date_lo)
                   & (dates.days <= data.sales_date_hi)]["sk"]
    groups = []
    for name in CHANNELS:
        ch = data.channels[name]
        dim = pd.DataFrame({"sk": ch.dim_sk, "id": ch.dim_id})
        sales = pd.DataFrame({
            "sk": np.where(ch.sales_sk_valid, ch.sales_sk, -1),
            "dt": np.where(ch.sales_date_valid, ch.sales_date, -1),
            "sales": ch.sales_price, "profit": ch.sales_profit,
            "returns": 0, "loss": 0,
        })
        rets = pd.DataFrame({
            "dt": np.where(ch.ret_date_valid, ch.ret_date, -1),
            "sales": 0, "profit": 0, "returns": ch.ret_amt,
            "loss": ch.ret_loss,
        })
        if ch.ret_item is None:
            rets["sk"] = np.where(ch.ret_sk_valid, ch.ret_sk, -1)
        else:
            keyed = pd.DataFrame({
                "item": ch.ret_item, "order": ch.ret_order}).merge(
                pd.DataFrame({
                    "item": ch.sales_item, "order": ch.sales_order,
                    "sk": np.where(ch.sales_sk_valid, ch.sales_sk, -1)}),
                on=["item", "order"], how="left")
            rets["sk"] = keyed["sk"].fillna(-1).to_numpy()
        both = pd.concat([sales, rets])
        both = both[both.dt.isin(window)].merge(dim, on="sk")
        agg = both.groupby("id")[["sales", "returns", "profit",
                                  "loss"]].sum()
        label, prefix = LABELS[name]
        for ident, row in agg.iterrows():
            groups.append((label, prefix + ident, int(row.sales),
                           int(row.returns), int(row.profit - row.loss)))
    rows = list(groups)
    for label in {g[0] for g in groups}:
        mine = [g for g in groups if g[0] == label]
        rows.append((label, None) + tuple(
            sum(g[i] for g in mine) for i in (2, 3, 4)))
    if groups:
        rows.append((None, None) + tuple(
            sum(g[i] for g in groups) for i in (2, 3, 4)))
    rows.sort(key=lambda r: (r[0] is not None, r[0] or "",
                             r[1] is not None, r[1] or ""))
    return rows[:100]


@pytest.mark.slow
def test_q5_local_matches_oracle():
    data = generate_q5_data(sf=0.02, seed=5)
    got = [tuple(r) for r in q5_local(data)]
    assert got == _oracle(data)


@pytest.mark.slow
def test_q5_local_zero_price_group_kept():
    data = generate_q5_data(sf=0.01, seed=6)
    ch = data.channels["store"]
    # force one row to contribute zero cents: group must still appear
    sel = np.where(ch.sales_sk_valid & ch.sales_date_valid)[0]
    if len(sel):
        ch.sales_price[sel[0]] = 0
    got = [tuple(r) for r in q5_local(data)]
    assert got == _oracle(data)


@pytest.mark.slow
def test_q5_distributed_matches_local_and_oracle():
    data = generate_q5_data(sf=0.05, seed=7)
    mesh = make_mesh((NDEV, 1), devices=jax.devices()[:NDEV])
    gov = MemoryGovernor(watchdog_period_s=0.02)
    try:
        budget = BudgetedResource(gov, 1 << 30)
        got = [tuple(r) for r in
               run_distributed_q5(mesh, data, budget=budget, task_id=1)]
        assert got == _oracle(data)
        assert got == [tuple(r) for r in q5_local(data)]
    finally:
        gov.close()


def test_q5_distributed_split_retry_exact():
    """Tight budget: fact rows split (additive aggregates) and the result
    still matches the oracle, with split metrics recorded."""
    data = generate_q5_data(sf=0.05, seed=8)
    mesh = make_mesh((NDEV, 1), devices=jax.devices()[:NDEV])
    gov = MemoryGovernor(watchdog_period_s=0.02)
    try:
        total = sum(v.nbytes for n in CHANNELS
                    for v in vars(data.channels[n]).values()
                    if isinstance(v, np.ndarray))
        budget = BudgetedResource(gov, int(total * 1.2))  # < nbytes_of(batch)
        with task_context(gov, 2):
            got = [tuple(r) for r in
                   run_distributed_q5(mesh, data, budget=budget, task_id=2,
                                      manage_task=False)]
            splits = gov.get_and_reset_num_split_retry(2)
        assert got == _oracle(data)
        assert splits >= 1
    finally:
        gov.close()
