"""Profiler capture/convert, fault injection, and dispatch-seam tests."""

import io
import json
import os
import subprocess
import sys
import time

import pytest

from spark_rapids_jni_tpu.columnar.column import column, strings_column
from spark_rapids_jni_tpu.columnar.dtypes import INT32
from spark_rapids_jni_tpu.mem.exceptions import (
    GpuRetryOOM,
    GpuSplitAndRetryOOM,
    InjectedException,
)
from spark_rapids_jni_tpu.obs import FaultInjector, Profiler
from spark_rapids_jni_tpu.obs.convert import parse_capture, to_chrome
from spark_rapids_jni_tpu.obs.profiler import CLOCK_ANCHOR
from spark_rapids_jni_tpu import ops


@pytest.fixture(autouse=True)
def _clean():
    yield
    FaultInjector.uninstall()
    Profiler.shutdown()


def _run_some_ops():
    col = column([1, 2, 3, None], INT32)
    ops.murmur_hash32([col], seed=42)
    ops.xxhash64([col])


def test_profiler_capture_and_convert(tmp_path):
    path = tmp_path / "capture.srtp"
    Profiler.init(str(path))
    Profiler.start()
    _run_some_ops()
    Profiler.marker("checkpoint-a")
    Profiler.counter("batch_rows", 4)
    Profiler.stop()
    Profiler.shutdown()

    events = list(parse_capture(path.read_bytes()))
    ranges = [e for e in events if e["type"] == "range"]
    names = {e["name"] for e in ranges}
    assert "murmur_hash32" in names and "xxhash64" in names
    cats = {e["name"]: e["category"] for e in ranges}
    assert cats["murmur_hash32"] == "op"
    assert cats["xxhash64"] == "op"
    assert cats["column"] == "transfer"  # h2d construction seam
    assert all(e["end_ns"] >= e["start_ns"] for e in ranges)
    markers = [e for e in events if e["type"] == "instant"]
    assert markers and markers[0]["name"] == "checkpoint-a"
    counters = [e for e in events if e["type"] == "counter"
                and e["name"] != CLOCK_ANCHOR]
    assert counters and counters[0]["value"] == 4
    # the start() clock anchor must be present for device-trace alignment
    assert any(e["type"] == "counter" and e["name"] == CLOCK_ANCHOR
               for e in events)

    chrome = to_chrome(events)
    assert any(t["ph"] == "X" and t["name"] == "murmur_hash32"
               for t in chrome["traceEvents"])


def test_profiler_writer_object_and_block_framing():
    sink = io.BytesIO()
    Profiler.init(sink, buffer_bytes=64)  # tiny buffer: force many blocks
    Profiler.start()
    for i in range(50):
        Profiler.marker(f"m{i}")
    Profiler.stop()
    Profiler.shutdown()
    data = sink.getvalue()
    events = list(parse_capture(data))
    assert sum(e["type"] == "instant" for e in events) == 50
    # every block is self-contained (string table restarts per block)
    assert {e["name"] for e in events if e["name"] != CLOCK_ANCHOR} \
        == {f"m{i}" for i in range(50)}


def test_profiler_inactive_records_nothing():
    sink = io.BytesIO()
    Profiler.init(sink)
    _run_some_ops()  # before start(): nothing captured
    Profiler.start()
    Profiler.stop()
    Profiler.shutdown()
    # only the start() clock anchor may appear; no op/seam traffic leaked
    evs = list(parse_capture(sink.getvalue()))
    assert [e["name"] for e in evs] == [CLOCK_ANCHOR]


@pytest.mark.slow
def test_convert_cli(tmp_path):
    path = tmp_path / "c.srtp"
    Profiler.init(str(path))
    Profiler.start()
    Profiler.marker("cli-marker")
    Profiler.stop()
    Profiler.shutdown()
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_jni_tpu.obs.convert",
         str(path), "--format", "json"],
        capture_output=True, text=True, check=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    lines = [json.loads(l) for l in out.stdout.splitlines()]
    assert any(e["name"] == "cli-marker" for e in lines)


def test_fault_injection_by_name_and_count():
    FaultInjector.install({
        "op": {"murmur_hash32": {"injectionType": "exception",
                                 "interceptionCount": 2}},
    })
    col = column([1, 2], INT32)
    for _ in range(2):
        with pytest.raises(InjectedException, match="murmur_hash32"):
            ops.murmur_hash32([col], seed=42)
    # count exhausted: op works again
    assert ops.murmur_hash32([col], seed=42).to_list() is not None
    # other ops unaffected throughout
    assert ops.xxhash64([col]).to_list() is not None


def test_fault_injection_wildcard_and_types():
    FaultInjector.install({
        "op": {"*": {"injectionType": "retry_oom", "interceptionCount": 1}},
    })
    col = strings_column(["1.5"])
    with pytest.raises(GpuRetryOOM):
        ops.string_to_float(col, ansi_mode=False)
    # exhausted
    ops.string_to_float(col, ansi_mode=False)
    FaultInjector.uninstall()

    FaultInjector.install({
        "op": {"xxhash64": {"injectionType": "split_oom"}},
    })
    icol = column([1], INT32)
    with pytest.raises(GpuSplitAndRetryOOM):
        ops.xxhash64([icol])
    FaultInjector.uninstall()

    from spark_rapids_jni_tpu.mem.exceptions import OffHeapOOM

    FaultInjector.install({
        "op": {"murmur_hash32": {"injectionType": "host_oom"}},
    })
    with pytest.raises(OffHeapOOM):
        ops.murmur_hash32([icol], seed=0)
    FaultInjector.uninstall()

    # transfer seam: host->device column construction is interceptable too
    FaultInjector.install({
        "transfer": {"strings_column": {"injectionType": "exception"}},
    })
    with pytest.raises(InjectedException):
        strings_column(["x"])


def test_fault_injection_percent_seeded():
    FaultInjector.install({
        "seed": 7,
        "op": {"murmur_hash32": {"injectionType": "exception",
                                 "percent": 50}},
    })
    col = column([1], INT32)
    hits = 0
    for _ in range(100):
        try:
            ops.murmur_hash32([col], seed=0)
        except InjectedException:
            hits += 1
    assert 20 <= hits <= 80  # seeded coin; bounds loose but meaningful


def test_fault_injection_seeded_schedule_is_deterministic():
    """Same seed => same injected-fault schedule, different seed => a
    different one (chaos runs must be replayable; docs/OBSERVABILITY.md
    documents the config schema incl. ``seed``)."""
    col = column([1], INT32)

    def schedule(seed):
        FaultInjector.install({
            "seed": seed,
            "op": {"murmur_hash32": {"injectionType": "exception",
                                     "percent": 50}},
        })
        try:
            outcomes = []
            for _ in range(64):
                try:
                    ops.murmur_hash32([col], seed=0)
                    outcomes.append(0)
                except InjectedException:
                    outcomes.append(1)
            return outcomes
        finally:
            FaultInjector.uninstall()

    a, b, c = schedule(1234), schedule(1234), schedule(4321)
    assert a == b, "same seed must replay the exact fault schedule"
    assert 0 < sum(a) < 64  # the coin actually flips both ways
    assert a != c  # 2^-64 false-failure odds: different seed, new schedule


def test_fault_injection_slow_behavior_stalls_the_crossing():
    """The round-10 ``slow`` kind: the crossing stalls durationMs then
    proceeds normally — a degraded-but-correct executor, not a failure."""
    col = column([1], INT32)
    FaultInjector.install({
        "op": {"murmur_hash32": {"injectionType": "slow",
                                 "durationMs": 80.0}},
    })
    try:
        t0 = time.monotonic()
        ops.murmur_hash32([col], seed=0)  # completes, just late
        assert time.monotonic() - t0 >= 0.07
    finally:
        FaultInjector.uninstall()


def test_fault_injection_slow_seeded_schedule_is_deterministic():
    """Behavioral kinds roll the same config-level RNG as fault kinds:
    a seeded slow schedule replays exactly (chaos-kill runs depend on
    this — the proc_kill crossing is picked the same way)."""
    col = column([1], INT32)

    def schedule(seed):
        FaultInjector.install({
            "seed": seed,
            "op": {"murmur_hash32": {"injectionType": "slow",
                                     "percent": 50,
                                     "durationMs": 15.0}},
        })
        try:
            outcomes = []
            for _ in range(32):
                t0 = time.monotonic()
                ops.murmur_hash32([col], seed=0)
                outcomes.append(1 if time.monotonic() - t0 >= 0.012 else 0)
            return outcomes
        finally:
            FaultInjector.uninstall()

    def replayed(seed):
        # a call is "slow" in both of two replays: a load spike (or the
        # first call's compile) can slow one un-injected call past the
        # threshold, never two replays at the same index by chance
        return [x & y for x, y in zip(schedule(seed), schedule(seed))]

    a, b = replayed(77), replayed(77)
    assert a == b, "same seed must replay the exact slow schedule"
    assert 0 < sum(a) < 32


def test_fault_injection_proc_kill_sigkills_the_process():
    """``proc_kill`` is the crash-only drill: the armed process vanishes
    mid-crossing with SIGKILL — no cleanup, no exception (run in a child
    so the suite survives its own chaos)."""
    import subprocess
    import sys

    code = (
        "from spark_rapids_jni_tpu.obs.faultinj import FaultInjector\n"
        "from spark_rapids_jni_tpu.obs.seam import seam, OP\n"
        "FaultInjector.install({'op': {'die': "
        "{'injectionType': 'proc_kill'}}})\n"
        "with seam(OP, 'die'):\n"
        "    pass\n"
        "print('survived')\n"
    )
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True, timeout=60,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == -9, (proc.returncode, proc.stdout,
                                   proc.stderr)
    assert "survived" not in proc.stdout


def test_fault_injection_hot_reload(tmp_path):
    cfg = tmp_path / "faults.json"
    cfg.write_text(json.dumps({"dynamic": True, "op": {}}))
    FaultInjector.install(str(cfg))
    col = column([1], INT32)
    ops.murmur_hash32([col], seed=0)  # no faults configured
    cfg.write_text(json.dumps({
        "dynamic": True,
        "op": {"murmur_hash32": {"injectionType": "exception"}},
    }))
    os.utime(cfg, (time.time() + 2, time.time() + 2))
    deadline = time.time() + 5
    fired = False
    while time.time() < deadline and not fired:
        try:
            ops.murmur_hash32([col], seed=0)
            time.sleep(0.05)
        except InjectedException:
            fired = True
    assert fired, "hot reload never armed the new rule"


def test_env_var_activation(tmp_path, monkeypatch):
    from spark_rapids_jni_tpu.obs import faultinj as fi

    cfg = tmp_path / "env_faults.json"
    cfg.write_text(json.dumps(
        {"op": {"xxhash64": {"injectionType": "exception"}}}))
    monkeypatch.setenv(fi.ENV_CONFIG_PATH, str(cfg))
    assert fi.install_from_env() is not None
    with pytest.raises(InjectedException):
        ops.xxhash64([column([1], INT32)])


@pytest.mark.slow
def test_profiler_real_pipeline_capture(tmp_path):
    """Golden-shape test over a REAL profiled run: a governed distributed
    q97 under the profiler must capture op, transfer, and collective ranges
    with sane nesting (start <= end, categories present), and the converter
    must round-trip the capture (VERDICT r2 next-step #7)."""
    import numpy as np

    import jax

    from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
    from spark_rapids_jni_tpu.models import run_distributed_q97
    from spark_rapids_jni_tpu.parallel import make_mesh

    path = tmp_path / "cap.bin"
    Profiler.init(str(path))
    Profiler.start()
    gov = MemoryGovernor(watchdog_period_s=0.05)
    try:
        rng = np.random.RandomState(3)
        store = (rng.randint(1, 40, 160).astype(np.int32),
                 rng.randint(1, 12, 160).astype(np.int32))
        catalog = (rng.randint(1, 40, 120).astype(np.int32),
                   rng.randint(1, 12, 120).astype(np.int32))
        mesh = make_mesh((8, 1), devices=jax.devices()[:8])
        budget = BudgetedResource(gov, 1 << 30)
        run_distributed_q97(mesh, store, catalog, budget=budget, task_id=1)
    finally:
        gov.close()
        Profiler.stop()
        Profiler.shutdown()

    events = list(parse_capture(path.read_bytes()))
    ranges = [e for e in events if e["type"] == "range"]
    assert ranges, "no ranges captured"
    cats = {e["category"] for e in ranges}
    # the q97 pipeline crosses the collective seam (all_to_all), the
    # transfer seam (device_put/materialization), and the ALLOC seam
    # (budget admission — the reference's allocator-interception point)
    assert "collective" in cats, cats
    assert "transfer" in cats, cats
    assert "alloc" in cats, cats
    counters = [e for e in events if e["type"] == "counter"]
    assert any(e["name"] == "device_budget_used" for e in counters)
    for e in ranges:
        assert e["start_ns"] <= e["end_ns"], e
    # nesting sanity per thread: a range overlapping its parent must nest
    by_thread = {}
    for e in sorted(ranges, key=lambda e: (e["tid"], e["start_ns"])):
        by_thread.setdefault(e["tid"], []).append(e)
    for tid, evs in by_thread.items():
        stack = []
        for e in evs:
            while stack and stack[-1]["end_ns"] <= e["start_ns"]:
                stack.pop()
            if stack:
                assert (e["end_ns"] <= stack[-1]["end_ns"]
                        or e["start_ns"] >= stack[-1]["end_ns"])
            stack.append(e)

    # converter round-trip on the real capture
    chrome = to_chrome(events)
    assert chrome["traceEvents"], "chrome conversion empty"


def test_convert_merges_synthetic_device_trace(tmp_path):
    """Converter merge (VERDICT r3 #6): a perfetto-format device trace is
    interleaved with SRTP host ranges in one chrome trace, device events
    placed on the host monotonic timeline via the clock anchor."""
    import gzip
    import json
    import os
    import time

    from spark_rapids_jni_tpu.obs.convert import main as convert_main

    path = tmp_path / "cap.srtp"
    Profiler.init(str(path))
    Profiler.start()
    _run_some_ops()
    Profiler.stop()
    Profiler.shutdown()

    # fabricate a jax.profiler perfetto export: one device kernel event
    # stamped in WALL microseconds (the XPlane timebase)
    run_dir = tmp_path / "xplane" / "plugins" / "profile" / "run1"
    os.makedirs(run_dir)
    wall_us = time.time_ns() / 1e3
    dev = {"traceEvents": [
        {"ph": "M", "pid": 2, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "X", "pid": 2, "tid": 1, "name": "fusion.1",
         "ts": wall_us, "dur": 42.0},
    ]}
    with gzip.open(run_dir / "perfetto_trace.json.gz", "wt") as f:
        json.dump(dev, f)

    out = tmp_path / "merged.json"
    rc = convert_main([str(path), "--format", "chrome",
                       "--device-trace", str(tmp_path / "xplane"),
                       "-o", str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())["traceEvents"]
    host = [e for e in merged if e.get("pid", 0) < 1000 and e["ph"] == "X"]
    devs = [e for e in merged if e.get("pid", 0) >= 1000 and e["ph"] == "X"]
    assert host and devs, "must contain both host ranges and device events"
    k = devs[0]
    assert k["name"] == "fusion.1" and k["dur"] == 42.0
    # exact anchor alignment: the wall-stamped kernel lands inside (or
    # within seconds of) the monotonic host window, not hours away
    host_ts = [e["ts"] for e in host]
    assert min(host_ts) - 5e6 <= k["ts"] <= max(host_ts) + 5e6
    # device track metadata survives the merge under the shifted pid
    assert any(e["ph"] == "M" and e["pid"] >= 1000 for e in merged)


@pytest.mark.slow
def test_profiler_xplane_real_device_capture(tmp_path):
    """End to end on the real backend: Profiler with xplane_dir captures a
    jitted op; the converter's merged chrome trace contains BOTH host seam
    ranges and at least one on-device trace event (VERDICT r3 #6 done
    criterion)."""
    import json

    from spark_rapids_jni_tpu.obs.convert import main as convert_main

    path = tmp_path / "cap.srtp"
    xdir = tmp_path / "xplane"
    Profiler.init(str(path), xplane_dir=str(xdir))
    Profiler.start()
    _run_some_ops()
    Profiler.stop()
    Profiler.shutdown()

    out = tmp_path / "merged.json"
    rc = convert_main([str(path), "--format", "chrome",
                       "--device-trace", str(xdir), "-o", str(out)])
    assert rc == 0
    merged = json.loads(out.read_text())["traceEvents"]
    host = [e for e in merged if e.get("pid", 0) < 1000 and e["ph"] == "X"]
    devs = [e for e in merged if e.get("pid", 0) >= 1000 and e["ph"] == "X"]
    assert any(e["name"] == "murmur_hash32" for e in host)
    assert devs, "jax.profiler exported no device events to merge"
