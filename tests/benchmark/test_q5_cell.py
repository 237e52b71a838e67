"""The q5 cell (``nds_sf10_q5_1chip.q5_power``) on the CPU at tiny sizes:
its tables, its query driver against its reference, what decides
``correct`` shown to fail, and its per-layer readers.  The chip readings
of the control are in PERF.md."""

from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from bench_helpers import CPU_PEAKS
from benchmark import cells, run

CELL = "nds_sf10_q5_1chip.q5_power"
#: a test size: small tables whose sales fall in a two-month span around
#: the window, so that about a quarter of them are in it, and more catalog
#: pages than the limit's 100 rows have sales there
TINY = {"store_sales_rows": 6000, "store_returns_rows": 600,
        "catalog_sales_rows": 4000, "catalog_returns_rows": 400,
        "web_sales_rows": 3000, "web_returns_rows": 300,
        "store_rows": 12, "catalog_page_rows": 300, "web_site_rows": 12,
        "item_rows": 200,
        "sold_date_sk_first": 2451758, "sold_date_sk_last": 2451818}


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

def test_the_config_holds_tpcds_sf10_q5():
    c = cells.load_cell(CELL).config
    assert [c[f"{t}_rows"] for t in (
        "store_sales", "store_returns", "catalog_sales", "catalog_returns",
        "web_sales", "web_returns")] == [
        28_800_991, 2_875_432, 14_401_261, 1_439_749, 7_197_566, 719_217]
    assert [c[f"{t}_rows"] for t in ("store", "catalog_page", "web_site",
                                     "date_dim")] == [102, 12_000, 42,
                                                      73_049]
    assert (c["sales_date"], c["window_days"]) == ("2000-08-23", 15)
    assert c["reduced"] == [] and c["query"] == "q5"


def test_generated_tables_repeat_exactly_for_a_seed_at_spec_domains():
    cell = tiny_cell(store_sales_rows=(1 << 20) + 7,
                     sold_date_sk_first=2450816, sold_date_sk_last=2452642)
    q = cell.query
    seed = 2**31 + 12345
    a, b, c = (q.generate(cell.config, s) for s in (seed, seed, seed + 1))
    for table in q.FACTS:
        for k, x in a[table].items():
            assert np.array_equal(x, b[table][k])
    assert not np.array_equal(a["store_sales"]["sk"], c["store_sales"]["sk"])
    ss, ws, wr = a["store_sales"], a["web_sales"], a["web_returns"]
    assert q.rows(a) == (1 << 20) + 7 + 600 + 4000 + 400 + 3000 + 300
    assert ss["sk"].min() >= 1 and ss["sk"].max() <= 12
    assert ss["date"].min() >= 2450816 and ss["date"].max() <= 2452642
    for key in ("sk_valid", "date_valid"):
        assert 0.035 < 1 - ss[key].mean() < 0.045  # 4% null
    assert ss["price"].min() >= 0 and ss["price"].max() <= 100 * 30_000
    assert ss["profit"].min() < 0 < ss["profit"].max()
    # web_sales' (order, item) is unique; 1% of the returns name no sale
    pairs = set(zip(ws["order"].tolist(), ws["item"].tolist()))
    assert len(pairs) == len(ws["order"])
    assert ws["item"].min() >= 1 and ws["item"].max() <= 200
    named = [p in pairs for p in zip(wr["order"].tolist(),
                                     wr["item"].tolist())]
    assert named.count(False) == 3
    # a return comes 1..90 days after its sale
    line = {p: i for i, p in enumerate(zip(ws["order"].tolist(),
                                           ws["item"].tolist()))}
    lag = [d - ws["date"][line[p]] for d, p, ok in zip(
        wr["date"].tolist(), zip(wr["order"].tolist(), wr["item"].tolist()),
        named) if ok]
    assert 1 <= min(lag) and max(lag) <= 90
    # history: 12 stores carry 6 ids, catalog pages one each
    assert len(np.unique(a["store"]["id"])) == 6
    assert a["store"]["id"][0] == "AAAAAAAABAAAAAAA"
    assert len(np.unique(a["catalog_page"]["id"])) == 300
    # the calendar: 2415022 is 1900-01-02; the window is 2000-08-23..09-06
    dd = a["date_dim"]
    assert dd["sk"][0] == 2415022
    assert str(np.datetime64(int(dd["days"][0]), "D")) == "1900-01-02"
    lo, hi = (int(a["params"][k]) for k in ("lo", "hi"))
    assert str(np.datetime64(lo, "D")) == "2000-08-23"
    assert str(np.datetime64(hi, "D")) == "2000-09-06"


# --------------------------------------------------- driver vs reference --

@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_query_driver_equals_its_reference(seed):
    cell = tiny_cell()
    q = cell.query
    tables = q.generate(cell.config, seed)
    want = q.reference(tables)
    assert len(want["rows"]) == 100 and want["rows"] == want["groups"][:100]
    # nulls first: the grand total, the catalog subtotal, then its pages
    assert want["rows"][0][:2] == (None, None)
    assert want["rows"][1][:2] == ("catalog channel", None)
    assert {r[0] for r in want["rows"][1:]} == {"catalog channel"}
    channels = [g for g in want["groups"] if g[1] is None and g[0]]
    assert [g[0] for g in channels] == ["catalog channel", "store channel",
                                        "web channel"]
    assert want["total"] == tuple(sum(g[i] for g in channels)
                                  for i in (2, 3, 4))
    assert len([g for g in want["groups"]
                if g[0] == "store channel"]) == 6 + 1
    assert q.system(cell.config, jax.devices()[:1])(tables) == want
    assert q.checks([want], want) == (0, {
        name: {"value": 0, "limit": 0} for name in q.CHECKS})


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_fails_the_comparison(seed):
    cell = tiny_cell()
    q = cell.query
    tables = q.generate(cell.config, seed)
    failed, checks = q.checks([q.control(tables)], q.reference(tables))
    assert failed == 1
    # float32 cannot hold the store and web groups' sums, nor the total
    assert checks["group_gap"]["value"] > 0
    assert checks["total_gap"]["value"] > 0


def test_a_program_without_the_entry_fails_at_once(monkeypatch):
    from spark_rapids_jni_tpu.models import q5

    cell = tiny_cell()
    monkeypatch.delattr(q5, "run_distributed_q5_grid")
    with pytest.raises(SystemExit, match="run_distributed_q5_grid"):
        cell.query.generate(cell.config, 1)


# ------------------------------------------------- a broken timed path --

def _facts_fault(monkeypatch, change):
    import spark_rapids_jni_tpu.models.q5 as q5

    real = q5._facts_of

    def broken(ch):
        return change(dict(real(ch)))

    monkeypatch.setattr(q5, "_facts_of", broken)


def _half_rows(monkeypatch):
    def half(f):
        n_s = len(f["sales_date"]) // 2
        n_r = len(f["ret_date"]) // 2
        return {k: v[:n_s] if k.startswith("sales") else v[:n_r]
                for k, v in f.items()}

    _facts_fault(monkeypatch, half)


def _validity_ignored(monkeypatch):
    def all_valid(f):
        return {k: np.ones_like(v) if k.endswith("_valid") else v
                for k, v in f.items()}

    _facts_fault(monkeypatch, all_valid)


def _returns_joined_by_position(monkeypatch):
    def by_position(f):
        if "ret_item" in f:
            n = len(f["ret_item"])
            f["ret_item"] = f["sales_item"][:n]
            f["ret_order"] = f["sales_order"][:n]
        return f

    _facts_fault(monkeypatch, by_position)


def _sixteen_day_window(monkeypatch):
    import spark_rapids_jni_tpu.models.q5 as q5

    real = q5.q5_plan
    monkeypatch.setattr(q5, "q5_plan", lambda n, lo, hi, sk0: real(
        n, lo, hi + 1, sk0))


def _grouped_by_surrogate_key(monkeypatch):
    import spark_rapids_jni_tpu.models.q5 as q5

    monkeypatch.setattr(q5, "id_codes", lambda dim_id: (
        np.arange(len(dim_id), dtype=np.int32), np.asarray(dim_id)))


def _one_sum_altered(monkeypatch):
    import spark_rapids_jni_tpu.plans.runtime as runtime

    real = runtime.run_governed_plan

    def altered(*args, **kw):
        out = dict(real(*args, **kw))
        sums = out["store_sales"].copy()
        sums[np.flatnonzero(out["store_count"])[0]] += 1
        out["store_sales"] = sums
        return out

    monkeypatch.setattr(runtime, "run_governed_plan", altered)


@pytest.mark.parametrize("fault, check", [
    (_half_rows, "row_gap"), (_validity_ignored, "row_gap"),
    (_sixteen_day_window, "row_gap"), (_grouped_by_surrogate_key, "group_gap"),
    (_returns_joined_by_position, "group_gap"),
    (_one_sum_altered, "group_gap")],
    ids=["half_rows", "validity_ignored", "sixteen_day_window",
         "grouped_by_surrogate_key", "returns_joined_by_position",
         "one_sum_altered"])
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
            "peak_hbm_gb", "agg_row_fill", "key_join_row_fill"} <= set(got)
    # the CPU has no device plane: the trace's readers find nothing
    assert not set(got) & {"plan_roofline", "device_idle_share", "sort_ms"}
    assert got["window_compiles"]["value"] == 0
    # the key join's counter: the web returns in the window over the
    # padded web returns
    t = q.generate(cell.config, 8)
    wr, dd = t["web_returns"], t["date_dim"]
    day = dd["days"][wr["date"] - dd["sk"][0]]
    kept = np.count_nonzero(wr["date_valid"] & (day >= t["params"]["lo"])
                            & (day <= t["params"]["hi"]))
    fill = 100.0 * kept / quantized_rows(len(wr["date"]), 1)
    assert got["key_join_row_fill"] == {"value": pytest.approx(fill),
                                        "unit": "%"}
    assert 0 < got["agg_row_fill"]["value"] < 100


class _Ring:
    """Flight-event dicts: ``task`` roots and ``key_join`` counters."""

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
                "kind": "key_join", "t_ns": start + 10 * (i + 1),
                "detail": f"plan:q5:probe:{n}:kept:{k}:matched:{k // 2}",
                "value": k})

    def snapshot(self):
        return sorted(self.events, key=lambda e: e["t_ns"])


def test_key_join_row_fill_sums_the_window_and_skips_the_warm_up(
        monkeypatch):
    from spark_rapids_jni_tpu.obs import flight

    reader = {m.name: m.reader
              for m in cells.load_cell(CELL).per_layer}["key_join_row_fill"]
    ring = _Ring()
    ring.task(0, [(1000, 900)])  # the warm-up
    ring.task(10_000, [(1000, 10)])
    ring.task(20_000, [(500, 4), (500, 6)])  # a split query: two runs
    monkeypatch.setattr(flight, "snapshot", ring.snapshot)

    def fill(n):
        return reader.read({"queries": [{}] * n})

    assert fill(2) == pytest.approx(100.0 * 20 / 2000)
    assert fill(3) == pytest.approx(100.0 * 920 / 3000)
    assert fill(4) is None  # too few roots
    ring.events = []
    ring.task(0, [])
    assert fill(1) is None  # no join in the plan


def test_the_roofline_reads_the_q5_facts():
    from benchmark import trace

    cell = tiny_cell()
    tables = cell.query.generate(cell.config, 4)
    facts = cell.query.facts(tables)
    # 26 B a store or catalog row, 34 B a web sale, 29 B a web return; a
    # 4 B day or id code a dimension row; 28 B of partials a business id
    assert facts["min_bytes"] == 26 * (6000 + 600 + 4000 + 400) \
        + 34 * 3000 + 29 * 300 + 4 * 73_049 + 4 * (12 + 300 + 12) \
        + 28 * (6 + 300 + 6)
    s = trace.Summary(chips=1, window_s=1.0, busy_s=0.5, op_s={}, kind_s={},
                      module_s={"jit_body": 0.25}, idle_gaps=[])
    reader = {m.name: m.reader for m in cell.per_layer}
    ctx = {"trace": s, "queries": [{}], "chips": 1,
           "peaks": {"hbm_bytes_per_s": 1e9}, "facts": facts}
    assert reader["plan_roofline"].read(ctx) == pytest.approx(
        100.0 * facts["min_bytes"] / 1e9 / 0.25)
