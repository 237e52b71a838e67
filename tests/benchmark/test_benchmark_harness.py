"""The benchmark harness on the CPU at tiny sizes: generation, the query
driver against its reference, finding cells by name, the refusal without a
TPU, BENCHMARK.json's form, and the trace reduction."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench_helpers import CELLS, CPU_PEAKS, ROOT, copy_benchmark, load_json, \
    tiny_cell
from benchmark import cells, run, trace

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


# ----------------------------------------------------------- generation --

def test_generated_tables_repeat_exactly_for_a_seed():
    cell = tiny_cell(CELLS[0], store_sales_rows=(1 << 20) + 7)
    seed = 2**31 + 12345  # past 32 signed bits, as the driver's seeds are
    a = cell.query.generate(cell.config, seed)
    b = cell.query.generate(cell.config, seed)
    c = cell.query.generate(cell.config, seed + 1)
    for side in ("store", "catalog"):
        for x, y, z in zip(a[side], b[side], c[side]):
            assert x.dtype == np.int32 and np.array_equal(x, y)
            assert len(x) == len(z) and not np.array_equal(x, z)
    assert len(a["store"][0]) == (1 << 20) + 7
    cust, item = a["store"]
    assert cust.min() >= 1 and cust.max() <= cell.config["customer_rows"]
    assert item.min() >= 1 and item.max() <= cell.config["item_rows"]


def test_configs_hold_the_tpcds_sf10_sizes():
    for name in CELLS:
        c = cells.load_cell(name).config
        assert (c["store_sales_rows"], c["catalog_sales_rows"],
                c["customer_rows"], c["item_rows"]) == (
                    28_800_991, 14_401_261, 500_000, 102_000)


# ------------------------------------------------ driver vs reference --

def _set_oracle(tables):
    s = set(zip(*(a.tolist() for a in tables["store"])))
    c = set(zip(*(a.tolist() for a in tables["catalog"])))
    return len(s - c), len(c - s), len(s & c)


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [0, 2**31 + 3])
def test_query_driver_equals_its_reference(name, seed):
    cell = tiny_cell(name)
    tables = cell.query.generate(cell.config, seed)
    run_q = cell.query.system(cell.config, jax.devices()[:cell.chips])
    want = cell.query.reference(tables)
    assert want == _set_oracle(tables)
    assert min(want) > 0  # tiny domains: all three counts are exercised
    assert run_q(tables) == want
    assert cell.query.rows(tables) == 4500


# -------------------------------------------------------- cells by name --

NEW_QUERY = '''
"""A query driver that only a later PR's files define."""
import importlib.util, os
_spec = importlib.util.spec_from_file_location(
    "q97_base", os.path.join(os.path.dirname(__file__), "q97.py"))
_base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_base)
generate, rows, system, facts, reference, checks = (
    _base.generate, _base.rows, _base.system, _base.facts, _base.reference,
    _base.checks)
'''

NEW_METRIC = '''
def read(ctx):
    return float(len(ctx["queries"]))
'''


def test_a_cell_defined_only_by_new_files_is_found_and_runs(tmp_path):
    root = copy_benchmark(str(tmp_path))
    b = os.path.join(root, "benchmark")
    cfg = load_json(os.path.join(b, "configs", "nds_sf10_1chip.json"))
    cfg.update(name="nds_tiny_2chip", chips=2, store_sales_rows=900,
               catalog_sales_rows=700, customer_rows=30, item_rows=20)
    with open(os.path.join(b, "configs", "nds_tiny_2chip.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(b, "traffic", "q97x_power.json"), "w") as f:
        json.dump({"query": "q97x", "loop": "closed"}, f)
    with open(os.path.join(b, "queries", "q97x.py"), "w") as f:
        f.write(NEW_QUERY)
    with open(os.path.join(b, "metrics", "queries_in_window.py"), "w") as f:
        f.write(NEW_METRIC)
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    bench["configs"].append({
        "name": "nds_tiny_2chip", "source": "test",
        "file": "benchmark/configs/nds_tiny_2chip.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "nds_tiny_2chip.q97x_power", "config": "nds_tiny_2chip",
        "traffic": "q97x_power", "chips": 2, "why": "test"})
    bench["per_layer"].append({
        "name": "queries_in_window", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "query runner (models/q97)",
        "moves": "rows_per_s", "workloads": ["nds_tiny_2chip.q97x_power"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    cell = cells.load_cell("nds_tiny_2chip.q97x_power", root)
    assert cell.chips == 2 and cell.config["store_sales_rows"] == 900
    assert [m.name for m in cell.per_layer][-1] == "queries_in_window"
    out = run.run_cell(cell, 11, 0.0, True, jax.devices()[:2], CPU_PEAKS)
    assert out["correct"] is True and out["attempted"] == 1
    assert out["metrics"]["queries_in_window"]["value"] == 1.0
    with pytest.raises(KeyError):
        cells.load_cell("no_such.cell", root)


@pytest.mark.parametrize("name", CELLS)
def test_a_cpu_run_reports_its_metrics_and_checks(name):
    cell = tiny_cell(name)
    out = run.run_cell(cell, 5, 0.0, False, jax.devices()[:cell.chips],
                       CPU_PEAKS)
    assert list(out)[-1] == "checks"
    assert out["correct"] and out["failed"] == 0 and out["attempted"] >= 1
    assert set(out["metrics"]) == {"rows_per_s", "setup_s"}
    dev = dict(out["device"])
    # the CPU has no memory_stats(): the peak is the plan's footprint
    assert dev.pop("memory_peak_bytes") > 0
    assert dev == {"platform": "cpu", "kind": "cpu", "count": cell.chips}
    traced = run.run_cell(cell, 5, 0.0, True, jax.devices()[:cell.chips],
                          CPU_PEAKS)
    # no device plane on the CPU: the trace's readers find nothing, and
    # the counters' readers still read
    assert traced["correct"] and traced["device"]["busy_s"] is None
    got = set(traced["metrics"])
    assert {"query_host_s", "plan_execute_s", "window_compiles",
            "governor_peak_gb", "exchange_slot_fill", "peak_hbm_gb"} <= got
    assert not got & {"sort_ms", "plan_roofline", "device_idle_share",
                      "collective_ms"}
    assert traced["metrics"]["window_compiles"]["value"] == 0


def _plan_readings(cell, seed, monkeypatch):
    """A traced CPU run of ``cell``, and the plans its window looked up."""
    from spark_rapids_jni_tpu.plans import plan_cache

    looked_up = []
    real = run._plan_facts

    def keep(plans):
        looked_up.extend(plans)
        return real(plans)

    monkeypatch.setattr(run, "_plan_facts", keep)
    out = run.run_cell(cell, seed, 0.0, True, jax.devices()[:cell.chips],
                       CPU_PEAKS)
    assert "get_or_compile" not in vars(plan_cache)  # the tap is gone
    return out, looked_up


@pytest.mark.parametrize("name", CELLS)
def test_slot_fill_and_memory_read_the_plans_the_window_ran(name,
                                                           monkeypatch):
    from spark_rapids_jni_tpu.plans import ir

    cell = tiny_cell(name)
    out, plans = _plan_readings(cell, 6, monkeypatch)
    assert out["correct"] and len(plans) == out["attempted"]
    (cap,) = {x.capacity for cp in plans for x in ir.exchange_nodes(cp.plan)}
    slots = cell.chips ** 2 * cap
    fill = out["metrics"]["exchange_slot_fill"]["value"]
    assert fill == pytest.approx(100.0 * 4500 / slots)
    ma = plans[0].fn.memory_analysis()
    per_chip = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert out["metrics"]["peak_hbm_gb"]["value"] == pytest.approx(
        per_chip * cell.chips / 1e9)
    assert out["device"]["memory_peak_bytes"] == per_chip


def test_slot_fill_counts_the_capacity_a_grow_retry_ran(monkeypatch):
    """A capacity the program picks apart from ``default_q97_capacity``
    (here too small, so every query overflows and grows) shows in the
    fill: every attempt's slots count, as the plans that ran have them."""
    import spark_rapids_jni_tpu.models.q97 as q97
    from spark_rapids_jni_tpu.plans import ir

    cell = tiny_cell(CELLS[1])
    monkeypatch.setattr(q97, "default_q97_capacity", lambda *_a: 16)
    out, plans = _plan_readings(cell, 7, monkeypatch)
    assert out["correct"]
    caps = [x.capacity for cp in plans for x in ir.exchange_nodes(cp.plan)]
    assert caps[0] == 16 and len(caps) > out["attempted"]  # grow retries
    fill = out["metrics"]["exchange_slot_fill"]["value"]
    assert fill == pytest.approx(
        100.0 * 4500 * out["attempted"] / (cell.chips ** 2 * sum(caps)))
    shapes_only = 100.0 * 4500 / (cell.chips ** 2 * max(caps))
    assert fill < shapes_only


# ------------------------------------------------------------ refusals --

def test_the_command_refuses_a_non_tpu_backend(capsys):
    assert run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                     "1", "--trace", "0"]) != 0
    assert capsys.readouterr().out == ""


def test_the_command_refuses_a_checkout_without_the_program(tmp_path):
    root = copy_benchmark(str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_unknown_device_kind_has_no_peaks():
    assert cells.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        cells.peaks_for("cpu")


# ------------------------------------------------- BENCHMARK.json form --

def test_benchmark_json_keeps_the_contract():
    b = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert b["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= b["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("benchmark/") and os.path.isfile(
            os.path.join(ROOT, c["file"]))
        assert all(NAME.match(k) for k in c["reduced"])
    assert len({c["source"] for c in b["configs"]}) == len(b["configs"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    four = [w for w in b["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(b["workloads"]) // 2)
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200 and NAME.match(w["name"])
        cells.load_cell(w["name"])  # every part is found
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert os.path.isfile(os.path.join(
            ROOT, "benchmark", "metrics", cells.module_file(m["name"])))
        for w in m.get("workloads", ()):
            assert w in {x["name"] for x in b["workloads"]}
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in b["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    for w in b["workloads"]:
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in b["per_layer"])


# ------------------------------------------------------ trace reduction --

def _ev(name, start, dur, category=""):
    return trace.Event(name, float(start), float(dur), category)


def _synthetic():
    """Two chips, a 1000 ns window: chip 0 busy 100-400 (overlapping ops)
    and 600-700, chip 1 busy 100-500; the host waits in two spans."""
    ops = {
        0: [_ev("sort.1", 100, 200), _ev("fusion.2", 250, 150),
            _ev("all-to-all.3", 600, 100), _ev("sort.1", 990, 50)],
        1: [_ev("sort.1", 100, 300), _ev("all-to-all.3", 400, 100)],
    }
    modules = {0: [_ev("jit_step", 100, 600)], 1: [_ev("jit_step", 100, 400)]}
    host = {"python": [_ev(trace.WINDOW_SPAN, 0, 1000),
                       _ev("bench.query", 0, 1000),
                       _ev("pad_and_upload", 0, 90),
                       _ev("download", 700, 300)]}
    return trace.Trace(ops, modules, host)


def test_trace_summary_unions_busy_time_and_names_idle_gaps():
    s = trace.summarize(_synthetic())
    assert s.chips == 2 and s.window_s == pytest.approx(1000e-9)
    # chip 0: [100,400] + [600,700] + [990,1000] = 410; chip 1: 400
    assert s.busy_s == pytest.approx(405e-9)
    assert s.op_s["sort.1"] == pytest.approx((200 + 50 + 300) / 2 * 1e-9)
    assert trace.seconds_matching(s.op_s, "all-to-all") == pytest.approx(
        100e-9)
    assert s.kind_s["sort"] == pytest.approx(275e-9)
    assert s.module_s["jit_step"] == pytest.approx(500e-9)
    gaps = dict(s.idle_gaps)
    assert [g for _n, g in s.idle_gaps] == sorted(gaps.values(),
                                                  reverse=True)
    assert gaps["download"] == pytest.approx(290e-9)  # 700..990
    assert s.idle_gaps[0] == ("download", pytest.approx(290e-9))
    assert ("pad_and_upload", pytest.approx(100e-9)) in s.idle_gaps
    bd = trace.breakdown(s, top=2)
    assert [k for k, _v in bd["device_ops"]] == ["sort.1", "all-to-all.3"]
    assert len(bd["idle_gaps"]) == 2


def test_trace_without_device_ops_summarizes_to_none():
    t = _synthetic()
    assert trace.summarize(trace.Trace({}, {}, t.host)) is None


def test_trace_readers_report_from_the_summary_and_skip_without_it():
    s = trace.summarize(_synthetic())
    ctx = {"trace": s, "queries": [{}], "chips": 2,
           "peaks": {"hbm_bytes_per_s": 1e9}, "facts": {"min_bytes": 100}}
    cell = cells.load_cell(CELLS[1])
    readers = {m.name: m.reader for m in cell.per_layer}
    assert readers["device_idle_share"].read(ctx) == pytest.approx(59.5)
    assert readers["collective_ms"].read(ctx) == pytest.approx(1e-4)
    assert readers["sort_ms"].read(ctx) == pytest.approx(2.75e-4)
    # least time 100 B / (2 chips x 1 GB/s) = 50 ns over 500 ns of program
    assert readers["plan_roofline"].read(ctx) == pytest.approx(10.0)
    for name in ("device_idle_share", "collective_ms", "sort_ms",
                 "plan_roofline"):
        assert readers[name].read(dict(ctx, trace=None)) is None


def test_trace_load_reads_a_real_profile(tmp_path):
    f = jax.jit(lambda x: (x * 2).sum())
    x = np.arange(1 << 12, dtype=np.float32)
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(p for p in tmp_path.rglob("*.xplane.pb"))
    t = trace.load(str(path))
    assert any(e.name == trace.WINDOW_SPAN
               for evs in t.host.values() for e in evs)
    assert t.ops == {} and trace.summarize(t) is None  # the CPU has no TPU


def test_op_events_are_named_by_their_hlo_instruction():
    e = trace.op_event(
        "%fusion.6 = (u32[67108864]{0:T(1024)}, u32[67108864]{0:T(1024)}) "
        "fusion(u32[67108864]{0:T(1024)} %broadcast.52.clone), "
        "kind=kCustom, calls=%fused_computation.6", 5.0, 7.0)
    assert e.category == "fusion"
    assert e.name == "fusion.6 fusion kCustom (u32[67108864], u32[67108864])"
    s = trace.op_event("%sort.41 = (u32[8]{0}, s32[8]{0}) sort(u32[8]{0} "
                       "%a, s32[8]{0} %b), dimensions={0}", 0.0, 1.0)
    assert s.category == "sort" and trace.kind(s) == "sort"
    assert trace.op_event("sort.0", 0.0, 1.0).category == ""


def test_a_recorded_chip_trace_reduces_to_its_device_time():
    """One q97 query of nds_sf10_1chip, traced on a v5e (my chip run 1,
    PR 22): 89 ops, one program, 14.17 s busy in a 14.71 s window."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "q97_1chip_window.xplane.pb")
    s = trace.summarize(trace.load(path))
    assert s.chips == 1
    assert s.window_s == pytest.approx(14.706786803)
    assert s.busy_s == pytest.approx(14.174617237)
    assert sum(s.module_s.values()) == pytest.approx(14.174623983)
    assert s.kind_s["sort"] == pytest.approx(1.498148436)
    assert s.kind_s["fusion"] == pytest.approx(12.657793975, rel=1e-6)
    assert "all-to-all" not in s.kind_s
    top = trace.breakdown(s)["device_ops"][0]
    assert top[0].startswith("fusion.6 fusion kCustom")
    assert top[1] == pytest.approx(6.472044636)
    assert s.idle_gaps[0][0] == "bench.query"
    assert s.idle_gaps[0][1] == pytest.approx(0.5275537, rel=1e-6)
