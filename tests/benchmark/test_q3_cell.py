"""The q3 cell (``nds_sf10_q3_1chip.q3_power``) on the CPU at tiny sizes:
its tables, its query driver against its reference, what decides
``correct`` shown to fail, and its per-layer readers.  The chip readings of
the control are in PERF.md."""

from __future__ import annotations

import dataclasses
import hashlib
import re

import jax
import numpy as np
import pytest

from bench_helpers import CPU_PEAKS
from benchmark import cells, run

CELL = "nds_sf10_q3_1chip.q3_power"
#: a test size: a manufacturer that a third of the items have, so that the
#: filter keeps enough rows for more than the limit's 100 groups
TINY = {"store_sales_rows": 20_000, "item_rows": 2_000, "manufact_ids": 3,
        "manufact_id": 2}


def tiny_cell(**sizes) -> cells.Cell:
    cell = cells.load_cell(CELL)
    return dataclasses.replace(cell,
                               config={**cell.config, **TINY, **sizes})


@pytest.fixture
def fresh_plans():
    """Faults are planted at trace time: compile every plan anew, and drop
    the broken programs afterwards."""
    from spark_rapids_jni_tpu.plans import plan_cache

    plan_cache.clear()
    yield
    plan_cache.clear()


# ------------------------------------------------------------- the tables --

def test_the_config_holds_tpcds_sf10_q3():
    c = cells.load_cell(CELL).config
    assert (c["store_sales_rows"], c["item_rows"], c["date_dim_rows"]) == (
        28_800_991, 102_000, 73_049)
    assert (c["manufact_id"], c["moy"]) == (128, 11)
    assert c["reduced"] == [] and c["query"] == "q3"


def test_generated_tables_repeat_exactly_for_a_seed_at_spec_domains():
    cell = tiny_cell(store_sales_rows=(1 << 20) + 7)
    q = cell.query
    seed = 2**31 + 12345
    a, b, c = (q.generate(cell.config, s) for s in (seed, seed, seed + 1))
    for table in ("store_sales", "item"):
        for k, x in a[table].items():
            assert np.array_equal(x, b[table][k])
    assert not np.array_equal(a["store_sales"]["item"],
                              c["store_sales"]["item"])
    ss, item, dd = a["store_sales"], a["item"], a["date_dim"]
    assert q.rows(a) == (1 << 20) + 7
    assert ss["item"].min() >= 1 and ss["item"].max() <= 2_000
    assert ss["date"].min() >= 2450816 and ss["date"].max() <= 2452642
    for key in ("item_valid", "date_valid"):
        assert 0.035 < 1 - ss[key].mean() < 0.045  # 4% null
    assert ss["price"].min() >= 0 and ss["price"].max() <= 100 * 30_000
    # sparse composite ids, one name each
    ids = item["brand_id"]
    assert ids.min() >= 1_001_001 and ids.max() <= 10_016_010
    assert len(np.unique(ids)) == len(np.unique(item["brand"]))
    # the calendar: 2415022 is 1900-01-02, 201 years to 2100-01-01
    assert (dd["sk"][0], dd["year"][0], dd["moy"][0]) == (2415022, 1900, 1)
    assert (dd["year"][-1], dd["moy"][-1]) == (2100, 1)
    nov_1998 = 2450816 - 2415022 + 303  # 1998-11-01
    assert (dd["year"][nov_1998], dd["moy"][nov_1998]) == (1998, 11)
    assert dd["moy"][nov_1998 - 1] == 10


# --------------------------------------------------- driver vs reference --

@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_query_driver_equals_its_reference(seed):
    cell = tiny_cell()
    q = cell.query
    tables = q.generate(cell.config, seed)
    want = q.reference(tables)
    assert len(want["rows"]) == 100 and want["rows"] == want["groups"][:100]
    assert len({g[0] for g in want["groups"]}) == 5  # 1998-2002
    assert want["total"] == sum(g[3] for g in want["groups"])
    assert q.system(cell.config, jax.devices()[:1])(tables) == want
    assert q.checks([want], want) == (0, {
        name: {"value": 0, "limit": 0} for name in q.CHECKS})


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
@pytest.mark.parametrize("control", ["control", "control_nulls_joined"])
def test_the_control_fails_the_comparison(control, seed):
    cell = tiny_cell()
    q = cell.query
    tables = q.generate(cell.config, seed)
    failed, checks = q.checks([getattr(q, control)(tables)],
                              q.reference(tables))
    assert failed == 1
    if control == "control":
        # float32 holds every group's sum exactly; the grand total, past
        # 2**24 cents, it does not
        assert checks["row_gap"]["value"] == checks["group_gap"]["value"] == 0
        assert checks["total_gap"]["value"] > 0
    else:
        assert checks["row_gap"]["value"] > 0
        assert checks["group_gap"]["value"] > 0


# ------------------------------------------------- a broken timed path --

def _half_rows(monkeypatch):
    import spark_rapids_jni_tpu.models.q3 as q3

    real = q3._facts

    def half(data):
        return {k: v[:len(v) // 2] for k, v in real(data).items()}

    monkeypatch.setattr(q3, "_facts", half)


def _validity_ignored(monkeypatch):
    import spark_rapids_jni_tpu.models.q3 as q3

    real = q3._facts

    def all_valid(data):
        f = dict(real(data))
        for k in ("ss_item_v", "ss_date_v"):
            f[k] = np.ones_like(f[k])
        return f

    monkeypatch.setattr(q3, "_facts", all_valid)


def _one_sum_altered(monkeypatch):
    import spark_rapids_jni_tpu.plans.runtime as runtime

    real = runtime.run_governed_plan

    def altered(*args, **kw):
        out = dict(real(*args, **kw))
        sums = out["sums"].copy()
        sums[np.flatnonzero(out["counts"])[0]] += 1
        out["sums"] = sums
        return out

    monkeypatch.setattr(runtime, "run_governed_plan", altered)


def _float32_sums(monkeypatch):
    import spark_rapids_jni_tpu.models.q3 as q3

    real = q3.q3_plan

    def float32(**geo):
        plan = real(**geo)
        (sink,) = plan.sinks
        aggs = tuple((n, e, "float32" if d == "int64" else d)
                     for n, e, d in sink.aggs)
        return dataclasses.replace(
            plan, sinks=(dataclasses.replace(sink, aggs=aggs),))

    monkeypatch.setattr(q3, "q3_plan", float32)


@pytest.mark.parametrize("fault, check", [
    (_half_rows, "row_gap"), (_validity_ignored, "row_gap"),
    (_one_sum_altered, "group_gap"), (_float32_sums, "total_gap")],
    ids=["half_rows", "validity_ignored", "one_sum_altered", "float32_sums"])
def test_a_broken_timed_path_is_not_correct(fault, check, monkeypatch,
                                            fresh_plans):
    cell = tiny_cell()
    devices = jax.devices()[:1]
    assert run.run_cell(cell, 3, 0.0, False, devices, CPU_PEAKS)["correct"]
    fault(monkeypatch)
    out = run.run_cell(cell, 3, 0.0, False, devices, CPU_PEAKS)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"][check]["value"] > 0


# ------------------------------------------------------------ the readers --

def test_a_traced_cpu_run_reports_the_cells_readers():
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    cell = tiny_cell()
    q = cell.query
    out = run.run_cell(cell, 8, 0.0, True, jax.devices()[:1], CPU_PEAKS)
    assert out["correct"] and list(out)[-1] == "checks"
    got = out["metrics"]
    assert {"query_host_s", "task_self_s", "plan_execute_s", "pad_s",
            "upload_s", "admit_s", "window_compiles", "governor_peak_gb",
            "peak_hbm_gb", "agg_row_fill"} <= set(got)
    # the CPU has no device plane: the trace's readers find nothing
    assert not set(got) & {"plan_roofline", "device_idle_share"}
    assert got["window_compiles"]["value"] == 0
    assert got["pad_s"]["value"] > 0 and got["plan_execute_s"]["value"] > 0
    # the counter: the rows the filter kept over the padded rows
    t = q.generate(cell.config, 8)
    ss, item, dd = t["store_sales"], t["item"], t["date_dim"]
    kept = np.count_nonzero(
        ss["item_valid"] & ss["date_valid"]
        & (item["manufact_id"][ss["item"] - 1] == 2)
        & (dd["moy"][ss["date"] - dd["sk"][0]] == 11))
    fill = 100.0 * kept / quantized_rows(q.rows(t), 1)
    assert got["agg_row_fill"] == {"value": pytest.approx(fill), "unit": "%"}


class _Ring:
    """Flight-event dicts: ``task`` roots and ``segment_agg`` counters."""

    def __init__(self):
        self.events = []
        self.sid = 0

    def task(self, start, counts, dur=1000):
        self.sid += 1
        detail = f"rid:{self.sid}:span:{self.sid}:parent:0:kind:task"
        self.events += [
            {"kind": "span_open", "t_ns": start, "detail": detail,
             "value": 0},
            {"kind": "span_close", "t_ns": start + dur, "detail": detail,
             "value": dur}]
        for i, (n, k) in enumerate(counts):
            self.events.append({
                "kind": "segment_agg", "t_ns": start + 10 * (i + 1),
                "detail": f"plan:q3:scattered:{n}:kept:{k}", "value": k})

    def snapshot(self):
        return sorted(self.events, key=lambda e: e["t_ns"])


def _fill(monkeypatch, ring, n):
    from spark_rapids_jni_tpu.obs import flight

    reader = {m.name: m.reader for m in cells.load_cell(CELL).per_layer}
    monkeypatch.setattr(flight, "snapshot", ring.snapshot)
    return reader["agg_row_fill"].read({"queries": [{}] * n})


def test_row_fill_sums_the_window_and_skips_the_warm_up(monkeypatch):
    ring = _Ring()
    ring.task(0, [(1000, 900)])  # the warm-up
    ring.task(10_000, [(1000, 10)])
    ring.task(20_000, [(500, 4), (500, 6)])  # a split query: two runs
    assert _fill(monkeypatch, ring, 2) == pytest.approx(100.0 * 20 / 2000)
    assert _fill(monkeypatch, ring, 3) == pytest.approx(100.0 * 920 / 3000)
    assert _fill(monkeypatch, ring, 4) is None  # too few roots
    no_counter = _Ring()
    no_counter.task(0, [])
    assert _fill(monkeypatch, no_counter, 1) is None


def test_the_roofline_reads_the_q3_facts():
    from benchmark import trace

    cell = tiny_cell()
    tables = cell.query.generate(cell.config, 4)
    facts = cell.query.facts(tables)
    ss = tables["store_sales"]
    # 18 B a real row, 8 B an item or a day, 12 B a grid slot
    groups = len(np.unique(tables["item"]["brand_id"]))
    assert facts["min_bytes"] == 18 * len(ss["item"]) + 8 * 2_000 \
        + 8 * 73_049 + 12 * 201 * groups
    s = trace.Summary(chips=1, window_s=1.0, busy_s=0.5, op_s={}, kind_s={},
                      module_s={"jit_body": 0.25}, idle_gaps=[])
    reader = {m.name: m.reader for m in cell.per_layer}
    ctx = {"trace": s, "queries": [{}], "chips": 1,
           "peaks": {"hbm_bytes_per_s": 1e9}, "facts": facts}
    assert reader["plan_roofline"].read(ctx) == pytest.approx(
        100.0 * facts["min_bytes"] / 1e9 / 0.25)
    assert reader["device_idle_share"].read(ctx) == pytest.approx(50.0)


# ------------------------------------------------- q97's plan is unchanged --

#: sha256 of q97's plan compiled on a one-device CPU mesh at 3,000 + 1,500
#: rows, with the source locations left out (the persistent compile cache
#: keys on the program without them): no change to the SegmentAgg,
#: GatherJoin or Filter emitters may reach q97, which has none of them
Q97_PROGRAM = ("443392c89f4e32948f4ea939f80affd5"
               "fa4d05c53a677f10f571eb4f0ac878b7")


def _program_text(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(
        line for line in text.split("\n\n", 1)[-1].splitlines()
        if not re.match(r"^(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames|\d+ )", line))


def test_q97s_compiled_plan_is_unchanged():
    from spark_rapids_jni_tpu.models.q97 import default_q97_capacity, q97_plan
    from spark_rapids_jni_tpu.parallel import make_mesh
    from spark_rapids_jni_tpu.plans.compiler import (
        AGG_KEPT,
        AGG_ROWS,
        compile_plan,
    )
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    tables = {"store": {"cust": np.empty(3000, np.int32),
                        "item": np.empty(3000, np.int32)},
              "catalog": {"cust": np.empty(1500, np.int32),
                          "item": np.empty(1500, np.int32)}}
    plan = q97_plan(default_q97_capacity(4500, 1))
    cp = compile_plan(plan, make_mesh((1, 1), devices=jax.devices()[:1]),
                      input_signature_raw(plan, tables, 1))
    assert not {AGG_KEPT, AGG_ROWS} & set(cp.out_names)
    text = _program_text(cp.fn.as_text())
    assert not re.search(r"segment_agg|gather_join|/filter", cp.fn.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == Q97_PROGRAM
