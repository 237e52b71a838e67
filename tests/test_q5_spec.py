"""q5 as TPC-DS writes it, and the KeyJoin it forced, on the CPU mesh.

Hand-made tables pin each rule of the query: null store, page and site
keys; null dates; the window's first and 15th day in and its 16th out; web
returns with no matching sale, with a null ``ws_web_site_sk`` and with keys
that match on item but not order; business ids shared by several
surrogate keys; Spark's nulls-first rollup order and LIMIT 100; sums past
2^31 cents.  The plan, the eager per-op path and the numpy reference must
all give the expected rows.  Then the KeyJoin alone: misses, null build
values, a masked build row, a repeated build key refused, one and several
data shards, and a split that keeps the build whole.
"""

from __future__ import annotations

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor, \
    task_context
from spark_rapids_jni_tpu.models.q5 import (
    Q5Row,
    q5_host_oracle,
    q5_local,
    q5_local_unfused,
    run_distributed_q5,
)
from spark_rapids_jni_tpu.models.tpcds import (
    ChannelTables,
    Q5Data,
    generate_q5_data,
    q5_dims,
)
from spark_rapids_jni_tpu.obs import flight
from spark_rapids_jni_tpu.parallel import make_mesh
from spark_rapids_jni_tpu.plans import ir
from spark_rapids_jni_tpu.plans.ir import Bin, Cast, col, lit
from spark_rapids_jni_tpu.plans.runtime import execute_plan, run_governed_plan

DIMS = q5_dims()
#: the date key of the window's first day, and a price past 2^31 / 1000
SK_LO = int(DIMS.date_sk[DIMS.date_days == DIMS.sales_date_lo][0])
BIG = 2_999_900


def _i32(xs):
    return np.asarray(xs, np.int32)


def _channel(dim_id, sales, returns, web=None):
    """ChannelTables from row tuples.  ``sales``: (sk or None, day offset
    from the window's first day or None, price, profit); ``returns``:
    (sk or None, day offset or None, amt, loss).  For web, ``web`` gives
    each sale's (item, order) and each return's (item, order) in place of
    its sk."""
    def cols(rows):
        sk, day, a, b = zip(*rows)
        return (_i32([1 if k is None else k for k in sk]),
                np.asarray([k is not None for k in sk]),
                _i32([SK_LO + (0 if d is None else d) for d in day]),
                np.asarray([d is not None for d in day]),
                np.asarray(a, np.int64), np.asarray(b, np.int64))

    s = cols(sales)
    r = cols(returns)
    keys = {}
    if web is not None:
        (s_item, s_order), (r_item, r_order) = (zip(*web[0]), zip(*web[1]))
        keys = dict(sales_item=_i32(s_item), sales_order=_i32(s_order),
                    ret_item=_i32(r_item), ret_order=_i32(r_order))
    return ChannelTables(
        sales_sk=s[0], sales_sk_valid=s[1], sales_date=s[2],
        sales_date_valid=s[3], sales_price=s[4], sales_profit=s[5],
        ret_sk=None if web else r[0], ret_sk_valid=None if web else r[1],
        ret_date=r[2], ret_date_valid=r[3], ret_amt=r[4], ret_loss=r[5],
        dim_sk=np.arange(1, len(dim_id) + 1, dtype=np.int32),
        dim_id=np.asarray(dim_id), **keys)


def _data():
    """Store: ids A, B, B, C on sks 1-4.  Catalog: 120 pages with a sale
    each.  Web: sites 1-3 with ids W1, W1, W2."""
    store = _channel(
        ["A", "B", "B", "C"],
        sales=[(1, 0, 100, 10),        # first day: in
               (2, 14, 200, 20),       # 15th day: in
               (3, 15, 400, 40),       # 16th day: out
               (3, 3, 800, 80),        # sk 3 shares id B with sk 2
               (None, 3, 1600, 160),   # null store key: out
               (1, None, 3200, 320),   # null date: out
               (4, -1, 6400, 640)]     # the day before: out
        + [(4, 7, BIG, 1)] * 1000,     # id C sums past 2^31 cents
        returns=[(2, 1, 7, 3), (None, 1, 11, 5), (1, 20, 13, 1),
                 (4, 2, 17, 2)])
    catalog = _channel(
        [f"P{i:03d}" for i in range(120)],
        sales=[(i + 1, i % 15, 1000 + i, i) for i in range(120)]
        + [(None, 3, 99_999, 9)],    # null page key: out
        returns=[(5, 0, 50, 5), (None, 0, 77, 7)])
    web = _channel(
        ["W1", "W1", "W2"],
        sales=[(1, 0, 1000, 100), (3, 1, 2000, 200),
               (None, 2, 4000, 400), (2, 3, 8000, 800)],  # a null site
        returns=[(None, 4, 1, 1),    # (item 5, order 1): site 1, W1
                 (None, 4, 2, 2),    # (item 6, order 1): site 3, W2
                 (None, 4, 4, 4),    # (item 5, order 2): null site
                 (None, 4, 8, 8),    # (item 5, order 9): item only
                 (None, 4, 16, 16),  # (item 8, order 3): no such sale
                 (None, 16, 32, 32)],  # (item 7, order 3): 17th day
        web=([(5, 1), (6, 1), (5, 2), (7, 3)],
             [(5, 1), (6, 1), (5, 2), (5, 9), (8, 3), (7, 3)]))
    return Q5Data({"store": store, "catalog": catalog, "web": web},
                  DIMS.date_sk, DIMS.date_days, DIMS.sales_date_lo,
                  DIMS.sales_date_hi)


def _expected():
    c_rows = [("catalog channel", f"catalog_pageP{i:03d}", 1000 + i, 0, i)
              for i in range(120)]
    c_rows[4] = c_rows[4][:3] + (50, 4 - 5)
    store = [("store channel", "storeA", 100, 0, 10),
             ("store channel", "storeB", 1000, 7, 100 - 3),
             ("store channel", "storeC", 1000 * BIG, 17, 1000 - 2)]
    web = [("web channel", "web_siteW1", 1000 + 8000, 1, 900 - 1),
           ("web channel", "web_siteW2", 2000, 2, 200 - 2)]

    def total(rows):
        return tuple(sum(r[i] for r in rows) for i in (2, 3, 4))

    rows = [(None, None) + total(c_rows + store + web),
            ("catalog channel", None) + total(c_rows)] + c_rows \
        + [("store channel", None) + total(store)] + store \
        + [("web channel", None) + total(web)] + web
    return [Q5Row(*r) for r in rows]


def test_each_rule_of_the_query_on_hand_made_tables():
    data = _data()
    want = _expected()
    assert want[0].sales > 2**31
    assert q5_host_oracle(data, limit=None) == want
    got = q5_local(data)
    assert got == want[:100] == q5_local_unfused(data) == q5_host_oracle(data)
    # the grand total, the catalog subtotal, then catalog pages
    assert (got[0].channel, got[1].id) == (None, None)
    assert {r.channel for r in got[1:]} == {"catalog channel"}


@pytest.mark.parametrize("ndev", [1, 4])
def test_the_mesh_path_gives_the_same_rows(ndev):
    data = _data()
    mesh = make_mesh((ndev, 1), devices=jax.devices()[:ndev])
    assert run_distributed_q5(mesh, data) == _expected()[:100]


@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_generated_tables_keep_the_spec_domains(seed):
    data = generate_q5_data(sf=0.02, seed=seed)
    web = data.channels["web"]
    # web_sales' (item, order) is a primary key; 1% of returns name none
    pairs = set(zip(web.sales_order.tolist(), web.sales_item.tolist()))
    assert len(pairs) == len(web.sales_order)
    named = [(o, i) in pairs for o, i in zip(web.ret_order.tolist(),
                                             web.ret_item.tolist())]
    assert 0 < named.count(False) <= 0.02 * len(named)
    assert len(set(zip(web.ret_order.tolist(), web.ret_item.tolist()))) \
        == len(named)
    assert len(data.channels["store"].sales_sk) == 57_602
    for name in ("store", "web"):  # ids repeat where history is kept
        ids = data.channels[name].dim_id
        assert len(np.unique(ids)) == len(ids) // 2
    assert len(np.unique(data.channels["catalog"].dim_id)) == 12_000
    assert data.sales_date_hi - data.sales_date_lo == 14
    assert q5_local(data) == q5_local_unfused(data) == q5_host_oracle(data)


# ----------------------------------------------------------- the KeyJoin --

def _join_plan(build_filter=False):
    """Each probe row's build value ``y`` by (a, b), summed into the probe
    row's own slot ``x`` (so a mesh's psum keeps it), with its validity."""
    build = ir.Scan("bld", ("a", "b", "v", "v_valid", "keep"))
    if build_filter:
        build = ir.Filter(build, col("keep"))
    node = ir.KeyJoin(ir.Scan("probe", ("a", "b", "x")), build,
                      probe_key=(col("a"), col("b")),
                      build_key=(col("a"), col("b")),
                      fields=(("v", "v_valid", "y"),))
    return ir.Plan("join", (ir.SegmentAgg(
        node, key=col("x"), num_segments=8,
        aggs=(("y", col("y"), "int64"),
              ("ok", Cast(col("y_valid"), "int32"), "int32"))),))


def _join_tables(build_a=(1, 1, 2, 3), build_b=(7, 8, 7, 7)):
    probe = {"a": _i32([1, 1, 2, 2, 3, 4, 1, 3]),
             "b": _i32([7, 8, 7, 8, 7, 7, 9, 8]),
             "x": _i32(range(8))}
    n = len(build_a)
    bld = {"a": _i32(build_a), "b": _i32(build_b),
           "v": np.asarray([10, 20, 30, 40][:n], np.int64),
           "v_valid": np.asarray([True, True, True, False][:n]),
           "keep": np.asarray([True, True, False, True][:n])}
    return {"probe": probe, "bld": bld}


def _want(build_filter=False):
    # (1,7)->10, (1,8)->20, (2,7)->30 unless filtered, (2,8) misses, (3,7)
    # has a null value, (4,7) misses, (1,9) matches a only, (3,8) b only
    y = [10, 20, 0 if build_filter else 30, 0, 0, 0, 0, 0]
    return y, [int(v > 0) for v in y]


@pytest.mark.parametrize("build_filter", [False, True])
def test_key_join_misses_nulls_and_masked_build_rows(build_filter):
    out = execute_plan(None, _join_plan(build_filter), _join_tables())
    y, ok = _want(build_filter)
    assert out["y"].tolist() == y and out["ok"].tolist() == ok


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_key_join_on_one_and_more_data_shards(ndev):
    mesh = make_mesh((ndev, 1), devices=jax.devices()[:ndev])
    out = execute_plan(mesh, _join_plan(), _join_tables())
    y, ok = _want()
    assert out["y"].tolist() == y and out["ok"].tolist() == ok


def test_a_repeated_build_key_is_refused():
    tables = _join_tables(build_a=(1, 1, 1, 3), build_b=(7, 8, 7, 7))
    with pytest.raises(ValueError, match="repeat a key"):
        execute_plan(None, _join_plan(), tables)
    # a repeat among masked build rows is no repeat
    tables["bld"]["keep"] = np.asarray([True, True, False, True])
    out = execute_plan(None, _join_plan(build_filter=True), tables)
    assert out["y"].tolist()[:2] == [10, 20]


def test_a_split_probes_the_whole_build():
    plan = _join_plan()
    tables = _join_tables()
    assert ir.build_tables(plan) == frozenset({"bld"})
    gov = MemoryGovernor(watchdog_period_s=0.02)
    try:
        # 3 x (96 B of probe + 72 B of build) does not fit; with half
        # the probe and the whole build, 3 x (48 + 72) B does
        budget = BudgetedResource(gov, 400)
        with task_context(gov, 4):
            out = run_governed_plan(None, plan, tables, budget=budget,
                                    task_id=4, manage_task=False)
            assert gov.get_and_reset_num_split_retry(4) >= 1
    finally:
        gov.close()
    y, ok = _want()
    assert out["y"].tolist() == y and out["ok"].tolist() == ok


def test_a_build_table_scanned_elsewhere_is_refused():
    plan = _join_plan()
    other = ir.SegmentAgg(ir.Scan("bld", ("a", "b", "v", "v_valid", "keep")),
                          key=lit(0), num_segments=1,
                          aggs=(("n", lit(1), "int32"),))
    with pytest.raises(ValueError, match="both as a KeyJoin build"):
        ir.build_tables(ir.Plan("twice", plan.sinks + (other,)))


def test_each_run_records_one_key_join_event():
    seq = max((e["seq"] for e in flight.snapshot()), default=-1)
    execute_plan(None, _join_plan(), _join_tables())
    events = [e for e in flight.snapshot()
              if e["seq"] > seq and e["kind"] == flight.EV_KEY_JOIN]
    assert [e["detail"] for e in events] == [
        "plan:join:probe:8:kept:8:matched:4"]
    assert flight.EVENT_KINDS[50:51] == ("key_join",)


def test_q5_records_its_join_and_gathers():
    seq = max((e["seq"] for e in flight.snapshot()), default=-1)
    q5_local(_data())
    kinds = {e["kind"]: e["detail"] for e in flight.snapshot()
             if e["seq"] > seq}
    # 6 returns, 6 slots once padded, 5 in the window, 3 found a sale
    assert kinds[flight.EV_KEY_JOIN] == "plan:q5:probe:8:kept:5:matched:3"
    # a window and an id code per scan: the windows are dim-side
    assert kinds[flight.EV_GATHER_JOIN] == "plan:q5:gathers:12:dim_side:6"


def test_the_key_join_has_its_scope_in_the_program():
    from spark_rapids_jni_tpu.plans.compiler import compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    plan = _join_plan()
    cp = compile_plan(plan, None,
                      input_signature_raw(plan, _join_tables(), 1))
    text = cp.fn.as_text()
    assert "key_join" in text and text.count(" sort(") >= 2


def test_an_expression_key_compares_like_a_column():
    plan = _join_plan()
    (sink,) = plan.sinks
    join = sink.child
    shifted = ir.KeyJoin(join.probe, join.build,
                         probe_key=(Bin("add", col("a"), lit(0)), col("b")),
                         build_key=join.build_key, fields=join.fields)
    import dataclasses

    plan2 = ir.Plan("join2", (dataclasses.replace(sink, child=shifted),))
    out = execute_plan(None, plan2, _join_tables())
    assert out["y"].tolist() == _want()[0]
