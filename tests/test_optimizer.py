"""Stats-driven plan rewriter (round 19): every rewrite bit-identical.

What the optimizer acceptance pins (ISSUE 18):

- each rule is an exact algebraic identity of the compiler's masked-row
  semantics — unit-pinned per rule, then FUZZED: random small plans over
  the existing IR nodes must produce bit-identical outputs through the
  unrewritten compiled oracle, and the rewriter must reach a fixed point
  (idempotent, bounded passes);
- join reordering follows the table-stats registry (smaller dim gathers
  first) and doubles as canonicalization: two queries written with
  different join orders rewrite to the SAME tree, so their result-cache
  keys collide on purpose (cross-query hits);
- common-subplan extraction reports subtrees another plan already
  registered;
- the run_governed_plan hook is gated on the ``plan_optimizer`` config
  flag and changes results by exactly nothing.
"""

import numpy as np
import pytest

from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.models import tables as tabreg
from spark_rapids_jni_tpu.obs import flight
from spark_rapids_jni_tpu.plans import execute_plan, ir
from spark_rapids_jni_tpu.plans.optimizer import (
    MAX_PASSES,
    common_subplan_tokens,
    expr_columns,
    optimize_plan,
    reset_for_tests,
    rewrite_plan,
)


@pytest.fixture(autouse=True)
def _fresh():
    reset_for_tests()
    tabreg.reset_for_tests()
    yield
    reset_for_tests()
    tabreg.reset_for_tests()


def _facts(n=64, seed=0):
    rng = np.random.default_rng(seed)
    return {
        "facts": {"ka": rng.integers(0, 4, n).astype(np.int32),
                  "kb": rng.integers(0, 3, n).astype(np.int32),
                  "qty": rng.integers(0, 9, n).astype(np.int64)},
        "dim_a": {"w": rng.integers(1, 9, 4).astype(np.int64)},
        "dim_b": {"v": rng.integers(1, 9, 3).astype(np.int64)},
    }


def _two_join_plan(a_first=True, name="q"):
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    ja = (ir.Dim("dim_a", ("w",)), ir.col("ka"), (("w", "wa"),))
    jb = (ir.Dim("dim_b", ("v",)), ir.col("kb"), (("v", "vb"),))
    for dim, key, fields in ([ja, jb] if a_first else [jb, ja]):
        node = ir.GatherJoin(node, dim, key, ir.lit(0), fields)
    node = ir.Filter(node, ir.Bin("gt", ir.col("qty"), ir.lit(2)))
    sink = ir.SegmentAgg(
        node, ir.col("ka"), 4,
        (("s", ir.Bin("mul", ir.col("wa"), ir.col("vb")), "int64"),))
    return ir.Plan(name, (sink,))


def _assert_same_outputs(p1, p2, tables):
    o1 = execute_plan(None, p1, tables)
    o2 = execute_plan(None, p2, tables)
    assert sorted(o1) == sorted(o2)
    for k in o1:
        np.testing.assert_array_equal(np.asarray(o1[k]),
                                      np.asarray(o2[k]))


# ------------------------------------------------------------ rule units


def test_expr_columns_walks_every_expression_shape():
    e = ir.Bin("add", ir.Cast(ir.col("a"), "int64"),
               ir.Unary("neg", ir.Bin("mul", ir.col("b"), ir.lit(2))))
    assert expr_columns(e) == frozenset({"a", "b"})


def test_filter_pushes_below_independent_gather():
    plan = _two_join_plan()
    out, applied = rewrite_plan(plan, {})
    rules = [r for r, _ in applied]
    assert rules.count("filter_below_gather") == 2
    # the filter now sits directly on the scan, gathers above it
    node = out.sinks[0].child
    assert isinstance(node, ir.GatherJoin)
    assert isinstance(node.child, ir.GatherJoin)
    assert isinstance(node.child.child, ir.Filter)
    assert isinstance(node.child.child.child, ir.Scan)
    _assert_same_outputs(plan, out, _facts())


def test_filter_reading_gathered_column_stays_put():
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    node = ir.GatherJoin(node, ir.Dim("dim_a", ("w",)), ir.col("ka"),
                         ir.lit(0), (("w", "wa"),))
    node = ir.Filter(node, ir.Bin("gt", ir.col("wa"), ir.lit(3)))
    sink = ir.SegmentAgg(node, ir.col("ka"), 4,
                         (("s", ir.col("qty"), "int64"),))
    plan = ir.Plan("dep", (sink,))
    out, applied = rewrite_plan(plan, {})
    assert applied == ()
    assert out == plan


def test_adjacent_filters_fuse_to_one_and():
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    node = ir.Filter(node, ir.Bin("gt", ir.col("qty"), ir.lit(1)))
    node = ir.Filter(node, ir.Bin("lt", ir.col("qty"), ir.lit(7)))
    sink = ir.SegmentAgg(node, ir.col("ka"), 4,
                         (("s", ir.col("qty"), "int64"),))
    plan = ir.Plan("ff", (sink,))
    out, applied = rewrite_plan(plan, {})
    assert [r for r, _ in applied] == ["filter_fuse"]
    fused = out.sinks[0].child
    assert isinstance(fused, ir.Filter)
    assert isinstance(fused.child, ir.Scan)
    assert fused.pred.op == "and"
    _assert_same_outputs(plan, out, _facts())


def test_projects_fuse_with_inner_substitution():
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    node = ir.Project(node, (("d", ir.Bin("add", ir.col("qty"),
                                          ir.lit(1))),))
    node = ir.Project(node, (("e", ir.Bin("mul", ir.col("d"),
                                          ir.lit(3))),))
    sink = ir.SegmentAgg(node, ir.col("ka"), 4,
                         (("s", ir.col("e"), "int64"),))
    plan = ir.Plan("pp", (sink,))
    out, applied = rewrite_plan(plan, {})
    assert [r for r, _ in applied] == ["project_fuse"]
    proj = out.sinks[0].child
    assert isinstance(proj, ir.Project)
    assert isinstance(proj.child, ir.Scan)
    # 'e' now computes from qty directly (inner 'd' inlined)
    assert dict(proj.cols)["e"] == ir.Bin(
        "mul", ir.Bin("add", ir.col("qty"), ir.lit(1)), ir.lit(3))
    _assert_same_outputs(plan, out, _facts())


def test_join_reorder_puts_smaller_dim_first_by_stats():
    plan = _two_join_plan(a_first=True)
    # dim_a is the big one: the canonical order applies dim_b first
    out, applied = rewrite_plan(plan, {"dim_a": 1000, "dim_b": 3})
    assert "join_reorder" in [r for r, _ in applied]
    upper = out.sinks[0].child
    assert upper.dim.table == "dim_a"          # big dim gathers last
    assert upper.child.dim.table == "dim_b"    # small dim first
    _assert_same_outputs(plan, out, _facts())


def test_join_reorder_canonicalizes_equivalent_queries():
    """Two spellings of the same query rewrite to ONE tree — the plan
    signatures (and so the result-cache keys) collide on purpose."""
    stats = {"dim_a": 1000, "dim_b": 3}
    out1, _ = rewrite_plan(_two_join_plan(a_first=True), stats)
    out2, _ = rewrite_plan(_two_join_plan(a_first=False), stats)
    assert out1 == out2
    assert ir.plan_signature(out1) == ir.plan_signature(out2)


def test_join_reorder_without_stats_ties_break_by_table_name():
    out1, _ = rewrite_plan(_two_join_plan(a_first=True), {})
    out2, _ = rewrite_plan(_two_join_plan(a_first=False), {})
    assert out1 == out2  # deterministic canonical order even stat-less


def test_filter_pushes_below_exchange_for_integer_sinks():
    from spark_rapids_jni_tpu.serve.shuffle import run_exchange_plan_local

    node = ir.Scan("facts", ("ka", "kb", "qty"))
    node = ir.Exchange(node, key=ir.col("ka"), capacity=64,
                       fields=("ka", "qty"))
    node = ir.Filter(node, ir.Bin("gt", ir.col("qty"), ir.lit(2)))
    sink = ir.SegmentAgg(node, ir.col("ka"), 4,
                         (("s", ir.col("qty"), "int64"),))
    plan = ir.Plan("ex", (sink,))
    out, applied = rewrite_plan(plan, {})
    assert "filter_below_exchange" in [r for r, _ in applied]
    ex = out.sinks[0].child
    assert isinstance(ex, ir.Exchange)
    assert isinstance(ex.child, ir.Filter)  # masked rows drop pre-wire
    tables = _facts()
    o1 = run_exchange_plan_local(plan, tables)
    o2 = run_exchange_plan_local(out, tables)
    for k in o1:
        np.testing.assert_array_equal(np.asarray(o1[k]),
                                      np.asarray(o2[k]))


def test_filter_reading_non_wire_column_stays_above_exchange():
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    node = ir.Exchange(node, key=ir.col("ka"), capacity=64,
                       fields=("ka", "qty"))
    # 'kb' does not cross the wire: the predicate cannot move below
    sink = ir.SegmentAgg(
        ir.Filter(node, ir.Bin("gt", ir.col("qty"), ir.lit(2))),
        ir.col("ka"), 4, (("s", ir.col("qty"), "float64"),))
    plan = ir.Plan("exf", (sink,))
    out, applied = rewrite_plan(plan, {})
    # float sink: the exchange-pushdown precondition fails, filter stays
    assert "filter_below_exchange" not in [r for r, _ in applied]


def _expr_join_plan(pred, a_first=True, key_b="kb"):
    """Two joins whose fields are expressions over their dims' columns:
    ``wa`` = 2 w, and ``vb`` = v where v > 3, else -1."""
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    gated = ir.Bin("sub", ir.Bin("mul", ir.Cast(ir.Bin(
        "gt", ir.col("v"), ir.lit(3)), "int64"), ir.Bin(
        "add", ir.col("v"), ir.lit(1))), ir.lit(1))
    ja = (ir.Dim("dim_a", ("w",)), ir.col("ka"),
          ((ir.Bin("mul", ir.col("w"), ir.lit(2)), "wa"),))
    jb = (ir.Dim("dim_b", ("v",)), ir.col(key_b), ((gated, "vb"),))
    for dim, key, fields in ([ja, jb] if a_first else [jb, ja]):
        node = ir.GatherJoin(node, dim, key, ir.lit(0), fields)
    sink = ir.SegmentAgg(
        ir.Filter(node, pred), ir.col("ka"), 4,
        (("s", ir.Bin("mul", ir.col("wa"), ir.col("vb")), "int64"),
         ("c", ir.lit(1), "int64")))
    return ir.Plan("expr", (sink,))


def test_filter_below_expression_gather_reads_its_out_names():
    """A filter slides below a join whose fields are expressions exactly
    when it reads none of the join's out_names: the dim columns the
    expressions read are not row columns."""
    below_both = _expr_join_plan(ir.Bin("gt", ir.col("qty"), ir.lit(2)))
    out, applied = rewrite_plan(below_both, {})
    assert [r for r, _ in applied] == ["filter_below_gather"] * 2
    assert isinstance(out.sinks[0].child.child.child, ir.Filter)
    _assert_same_outputs(below_both, out, _facts())

    on_wa = _expr_join_plan(ir.Bin("gt", ir.col("wa"), ir.lit(6)))
    out, applied = rewrite_plan(on_wa, {})
    assert [r for r, _ in applied] == ["filter_below_gather"]
    upper = out.sinks[0].child
    assert upper.dim.table == "dim_b"
    assert isinstance(upper.child, ir.Filter)
    assert upper.child.child.dim.table == "dim_a"
    _assert_same_outputs(on_wa, out, _facts())

    on_vb = _expr_join_plan(ir.Bin("ge", ir.col("vb"), ir.lit(0)))
    assert rewrite_plan(on_vb, {}) == (on_vb, ())


def _joins_top_down(plan):
    return [n.dim.table for n in ir.walk(plan)
            if isinstance(n, ir.GatherJoin)]


def test_join_reorder_moves_expression_gathers_by_their_out_names():
    stats = {"dim_a": 1000, "dim_b": 3}
    pred = ir.Bin("ge", ir.col("vb"), ir.lit(0))
    plan = _expr_join_plan(pred)
    out, applied = rewrite_plan(plan, stats)
    assert "join_reorder" in [r for r, _ in applied]
    assert _joins_top_down(out) == ["dim_a", "dim_b"]
    assert out == rewrite_plan(_expr_join_plan(pred, a_first=False),
                               stats)[0]
    _assert_same_outputs(plan, out, _facts())
    # dim_b's key reads dim_a's expression field: the order is forced
    keyed = _expr_join_plan(pred, key_b="wa")
    out, applied = rewrite_plan(keyed, stats)
    assert "join_reorder" not in [r for r, _ in applied]
    assert _joins_top_down(out) == ["dim_b", "dim_a"]
    _assert_same_outputs(keyed, out, _facts())


# ------------------------------------------------------- fixed point + fuzz


def _random_plan(rng) -> ir.Plan:
    """A random small plan over Scan/Filter/Project/GatherJoin stacks
    with an integer SegmentAgg sink — the node set the rewriter moves."""
    cols = ["ka", "kb", "qty"]
    node = ir.Scan("facts", ("ka", "kb", "qty"))
    gathers = [("dim_a", "w", "ka"), ("dim_b", "v", "kb")]
    n_new = 0
    for _ in range(int(rng.integers(1, 6))):
        choice = rng.integers(0, 3)
        if choice == 0:
            c = cols[int(rng.integers(0, len(cols)))]
            op = ("gt", "le", "ne")[int(rng.integers(0, 3))]
            node = ir.Filter(node, ir.Bin(op, ir.col(c),
                                          ir.lit(int(rng.integers(0, 6)))))
        elif choice == 1:
            c = cols[int(rng.integers(0, len(cols)))]
            n_new += 1
            name = f"p{n_new}"
            node = ir.Project(node, ((name, ir.Bin(
                "add", ir.col(c), ir.lit(int(rng.integers(1, 4))))),))
            cols.append(name)
        elif gathers:
            table, field, key = gathers.pop(int(rng.integers(0, len(gathers))))
            out_name = f"g_{field}"
            node = ir.GatherJoin(node, ir.Dim(table, (field,)),
                                 ir.col(key), ir.lit(0),
                                 ((field, out_name),))
            cols.append(out_name)
    vcol = cols[int(rng.integers(0, len(cols)))]
    sink = ir.SegmentAgg(node, ir.col("ka"), 4,
                         (("s", ir.col(vcol), "int64"),
                          ("c", ir.lit(1), "int64")))
    return ir.Plan("fuzz", (sink,))


def test_rewrite_equivalence_fuzz():
    """Random plans: optimizer output bit-identical to the unrewritten
    compiled oracle; the rewriter reaches a fixed point (re-running it
    applies nothing) within the bounded pass budget."""
    rng = np.random.default_rng(1234)
    stats_cases = ({}, {"dim_a": 1000, "dim_b": 3},
                   {"dim_a": 2, "dim_b": 900})
    for i in range(30):
        plan = _random_plan(rng)
        stats = stats_cases[i % len(stats_cases)]
        out, applied = rewrite_plan(plan, stats)
        assert len(applied) < 64, "rewriter did not converge"
        again, reapplied = rewrite_plan(out, stats)
        assert reapplied == (), f"not a fixed point: {reapplied}"
        assert again == out
        tables = _facts(n=96, seed=i)
        _assert_same_outputs(plan, out, tables)
    assert MAX_PASSES >= 2  # the bound the engine enforces


# -------------------------------------- memoization, events, common subplans


def test_optimize_plan_memoizes_and_narrates_once():
    flight.recorder().reset_for_tests()
    tabreg.record_stats("dim_a", rows=1000)
    tabreg.record_stats("dim_b", rows=3)
    plan = _two_join_plan()
    out1 = optimize_plan(plan)
    out2 = optimize_plan(plan)
    assert out1 is out2  # lru-cached value
    evs = [e for e in flight.snapshot() if e["kind"] == "plan_rewrite"]
    assert evs, "applied rules must narrate EV_PLAN_REWRITE"
    details = [e["detail"] for e in evs]
    assert any(":rule:done" in d for d in details)
    # memo hit emitted nothing new
    assert len([e for e in flight.snapshot()
                if e["kind"] == "plan_rewrite"]) == len(evs)


def test_stats_change_reoptimizes():
    plan = _two_join_plan()
    tabreg.record_stats("dim_a", rows=1000)
    tabreg.record_stats("dim_b", rows=3)
    small_b = optimize_plan(plan)
    tabreg.record_stats("dim_a", rows=3)
    tabreg.record_stats("dim_b", rows=1000)
    small_a = optimize_plan(plan)
    assert small_b != small_a  # join order follows the live registry
    assert small_b.sinks[0].child.dim.table == "dim_a"
    assert small_a.sinks[0].child.dim.table == "dim_b"


def test_common_subplan_tokens_report_shared_prefix():
    p1, _ = rewrite_plan(_two_join_plan(a_first=True, name="q_one"), {})
    p2, _ = rewrite_plan(_two_join_plan(a_first=False, name="q_two"), {})
    assert common_subplan_tokens(p1) == []  # first registrant
    shared = common_subplan_tokens(p2)
    assert shared, "canonicalized twin must report shared subtrees"
    assert all(first == "q_one" for _sig, _ntype, first in shared)


def test_observe_tables_records_rows_and_versioned_stats():
    t = _facts()
    tabreg.observe_tables(t)
    st = tabreg.stats_of("dim_a")
    assert st is not None and st["rows"] == 4
    assert tabreg.stats_of("facts")["rows"] == 64
    tabreg.bump("dim_a")
    assert tabreg.stats_of("dim_a") is None  # stale after a bump
    tabreg.observe_tables(t)
    assert tabreg.stats_of("dim_a")["rows"] == 4


def test_run_governed_plan_gate_is_bit_identical():
    from spark_rapids_jni_tpu.plans.runtime import run_governed_plan

    plan = _two_join_plan()
    tables = _facts()
    off = run_governed_plan(None, plan, tables)
    with config.override(plan_optimizer=True):
        on = run_governed_plan(None, plan, tables)
    for k in off:
        np.testing.assert_array_equal(np.asarray(off[k]),
                                      np.asarray(on[k]))


def test_canonicalized_queries_share_one_result_cache_key():
    """The tentpole's cross-query story end to end: two differently
    written queries, optimizer on, produce EQUAL plan_result_keys — the
    second literally hits the first's cached work."""
    from spark_rapids_jni_tpu.plans.rcache import plan_result_key

    tables = _facts()
    tabreg.observe_tables(tables)
    tabreg.record_stats("dim_a", rows=1000)
    tabreg.record_stats("dim_b", rows=3)
    k1, _ = plan_result_key(
        optimize_plan(_two_join_plan(a_first=True, name="q")), 1, tables)
    k2, _ = plan_result_key(
        optimize_plan(_two_join_plan(a_first=False, name="q")), 1, tables)
    assert k1 == k2
