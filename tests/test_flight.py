"""Governance flight recorder: ring, feeds, STATE capture, converter v2.

The tentpole's unit tier — chaos-driven anomaly dumps live in
test_flight_chaos.py.  Covers: ring bounding and per-task accumulators,
the arbiter blocked/woken feed with real contention, telemetry sources,
anomaly-dump artifacts and rate limiting, SRTP v2 STATE streaming +
per-task chrome governance tracks, v1/v2 converter round-trip, converter
robustness (truncated final block, consume-from-mid-stream), the serve
metrics memory-pressure gauges, and the flightdump reconstruction tool.
"""

import io
import json
import os
import struct
import subprocess
import sys
import threading
import time

import pytest

from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.mem import (
    BudgetedResource,
    GpuRetryOOM,
    GpuSplitAndRetryOOM,
    MemoryGovernor,
    task_context,
)
from spark_rapids_jni_tpu.obs import flight
from spark_rapids_jni_tpu.obs.convert import parse_capture, to_chrome
from spark_rapids_jni_tpu.obs.profiler import MAGIC, Profiler

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools"))

import flightdump  # noqa: E402  (needs the tools/ dir on sys.path)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_recorder():
    flight.recorder().reset_for_tests()
    yield
    flight.recorder().reset_for_tests()
    Profiler.shutdown()


@pytest.fixture
def gov():
    g = MemoryGovernor(watchdog_period_s=0.02)
    yield g
    g.close()


# ------------------------------------------------------------- ring basics


def test_ring_is_bounded_and_ordered():
    rec = flight.FlightRecorder(ring_size=8)
    for i in range(20):
        rec.record(flight.EV_RETRY, task_id=i)
    evs = rec.snapshot()
    assert len(evs) == 8  # bounded: only the newest survive
    assert [e["task_id"] for e in evs] == list(range(12, 20))
    assert all(e["kind"] == "retry" for e in evs)
    ts = [e["t_ns"] for e in evs]
    assert ts == sorted(ts)


def test_per_task_stats_accumulate():
    rec = flight.FlightRecorder(ring_size=64)
    rec.record(flight.EV_RETRY, 5)
    rec.record(flight.EV_RETRY, 5)
    rec.record(flight.EV_SPLIT_RETRY, 5)
    rec.record(flight.EV_TASK_WOKEN, 5, value=1000)
    rec.record(flight.EV_TASK_WOKEN, 5, value=500)
    rec.record(flight.EV_TASK_KILLED, 5)
    rec.record(flight.EV_RETRY, 6)
    st = rec.task_stats()
    assert st[5] == {"retries": 2, "split_retries": 1, "blocked_ns": 1500,
                     "wakes": 2, "killed": 1}
    assert st[6]["retries"] == 1
    # untasked events never create stats entries
    rec.record(flight.EV_RETRY, -1)
    assert -1 not in rec.task_stats()


def test_telemetry_sources_and_failure_isolation():
    rec = flight.FlightRecorder(ring_size=8)
    rec.register_telemetry_source("good", lambda: {"x": 1})
    rec.register_telemetry_source("bad", lambda: 1 / 0)
    snap = rec.unified_snapshot()
    assert snap["good"] == {"x": 1}
    assert "error" in snap["bad"]  # a failing source reports in-band
    rec.unregister_telemetry_source("bad")
    assert "bad" not in rec.unified_snapshot()


def test_anomaly_dump_schema_artifact_and_rate_limit(tmp_path):
    rec = flight.FlightRecorder(ring_size=16)
    rec.record(flight.EV_TASK_ADMITTED, 3)
    rec.record(flight.EV_TASK_BLOCKED, 3, detail="alloc:dev")
    rec.record(flight.EV_TASK_WOKEN, 3, detail="alloc:ready", value=42)
    with config.override(flight_dump_dir=str(tmp_path)):
        d = rec.anomaly("test_reason", detail="why")
        assert d is not None
        # same reason inside the rate window: suppressed, counted
        assert rec.anomaly("test_reason") is None
        # a different reason dumps immediately
        assert rec.anomaly("other_reason") is not None
    assert rec.dump_count == 2 and rec.dumps_suppressed == 1
    assert d["schema"] == flight.DUMP_SCHEMA
    assert d["reason"] == "test_reason" and d["detail"] == "why"
    kinds = [e["kind"] for e in d["events"]]
    assert kinds[:3] == ["admitted", "blocked", "woken"]
    assert kinds[-1] == "anomaly"
    assert d["tasks"]["3"]["blocked_ns"] == 42
    # sources are per-recorder: the fresh unit recorder has none, the
    # module singleton carries the governor/spill gauge sources
    assert d["telemetry"] == {}
    assert {"governor", "spill"} <= set(flight.unified_snapshot())
    # the artifact round-trips through json on disk
    path = d["artifact"]
    assert os.path.exists(path) and str(tmp_path) in path
    with open(path) as f:
        assert json.load(f)["reason"] == "test_reason"


def test_event_kind_vocabulary_is_stable():
    # wire ids are tuple positions: appending is safe, reordering is not —
    # the round-7 vocabulary keeps its ids (v2 captures stay readable),
    # the round-9 controller kinds sit right after it, and the round-10
    # supervision kinds are strictly appended after those
    assert flight.EVENT_KINDS.index("admitted") == 0
    assert flight.KIND_IDS[flight.EV_ANOMALY] == 12
    assert flight.EVENT_KINDS[13:16] == ("control_adjust", "control_freeze",
                                         "control_presplit")
    assert (flight.KIND_IDS[flight.EV_TASK_HUNG]
            > flight.KIND_IDS[flight.EV_CONTROL_PRESPLIT])
    assert flight.EVENT_KINDS[16:24] == (
        "task_hung", "degrade_enter", "degrade_exit",
        "lease_grant", "lease_redispatch", "lease_done",
        "worker_spawn", "worker_dead")
    # round 12: the ragged batching kinds are strictly appended after
    assert flight.EVENT_KINDS[24:27] == (
        "ragged_pack", "ragged_launch", "ragged_split")
    # round 13: the shuffle data-plane kinds are strictly appended after
    assert flight.EVENT_KINDS[27:31] == (
        "shuffle_produce", "shuffle_fetch", "shuffle_retry",
        "shuffle_ack")
    # round 14: the telemetry-plane kinds (spans, SLO, export) appended
    assert flight.EVENT_KINDS[31:37] == (
        "span_open", "span_close", "slo_burn", "slo_ok",
        "telemetry_export", "telemetry_drop")
    # round 15: the result-cache kinds are strictly appended after
    assert flight.EVENT_KINDS[37:42] == (
        "rcache_hit", "rcache_store", "rcache_demote",
        "rcache_evict", "rcache_invalidate")
    # round 19: optimizer / adaptive-exchange / hedging kinds appended
    assert flight.EVENT_KINDS[42:47] == (
        "plan_rewrite", "adapt_exchange",
        "hedge_launch", "hedge_win", "hedge_lose")
    # round 21: the per-tenant attribution kind is strictly appended after
    assert flight.EVENT_KINDS[47:48] == ("attrib",)
    # the plan runtime's aggregate counter is strictly appended after
    assert flight.EVENT_KINDS[48:49] == ("segment_agg",)
    assert len(set(flight.EVENT_KINDS)) == len(flight.EVENT_KINDS)


# ------------------------------------------------------- the arbiter feed


def test_contended_acquire_emits_blocked_then_woken(gov):
    """Two tasks over one small budget: the loser's park must appear as a
    blocked event closed by a woken event carrying the wait in ns."""
    budget = BudgetedResource(gov, limit_bytes=100)
    barrier = threading.Barrier(2)
    hold = threading.Event()

    def holder():
        with task_context(gov, 1):
            budget.acquire(80)
            barrier.wait()
            hold.wait(5)
            budget.release(80)

    def waiter():
        with task_context(gov, 2):
            barrier.wait()
            budget.acquire(60)  # must block until the holder releases
            budget.release(60)

    th = threading.Thread(target=holder)
    tw = threading.Thread(target=waiter)
    th.start(), tw.start()
    import time

    time.sleep(0.1)  # let the waiter park
    hold.set()
    th.join(timeout=10), tw.join(timeout=10)
    assert not th.is_alive() and not tw.is_alive()

    evs = [e for e in flight.snapshot() if e["task_id"] == 2]
    kinds = [e["kind"] for e in evs]
    assert "blocked" in kinds and "woken" in kinds
    woken = next(e for e in evs if e["kind"] == "woken")
    assert woken["value"] > 0  # a real wait was measured
    assert flight.task_stats()[2]["blocked_ns"] == woken["value"]
    assert flightdump.timeline_complete(evs)


def test_task_context_brackets_admitted_done(gov):
    with task_context(gov, 11):
        pass
    kinds = [(e["kind"], e["task_id"]) for e in flight.snapshot()]
    assert ("admitted", 11) in kinds and ("task_done", 11) in kinds


def test_retry_signal_recorded_with_task(gov):
    budget = BudgetedResource(gov, limit_bytes=10)
    with task_context(gov, 9):
        gov.force_retry_oom(num_ooms=1)
        with pytest.raises(GpuRetryOOM):
            budget.acquire(5)
    retries = [e for e in flight.snapshot() if e["kind"] == "retry"]
    assert retries and retries[0]["task_id"] == 9
    assert retries[0]["detail"] == "GpuRetryOOM"
    assert flight.task_stats()[9]["retries"] == 1


def test_spill_events_bracket_the_copy(gov):
    import numpy as np

    from spark_rapids_jni_tpu.mem import SpillPool
    from spark_rapids_jni_tpu.mem.spill import pool_gauges

    budget = BudgetedResource(gov, limit_bytes=1 << 20)
    pool = SpillPool(budget)
    with task_context(gov, 4):
        buf = pool.add(np.zeros(64, np.int64))
        with buf.use():
            pass
        assert pool.spill_until(buf.nbytes) == buf.nbytes
    evs = flight.snapshot()
    begin = next(e for e in evs if e["kind"] == "spill_begin")
    end = next(e for e in evs if e["kind"] == "spill_end")
    assert begin["value"] == buf.nbytes  # begin carries bytes
    assert end["value"] >= 0 and end["detail"] == f"{buf.nbytes}B"
    assert begin["task_id"] == end["task_id"] == 4
    assert pool_gauges()["spilled_bytes"] >= buf.nbytes
    pool.close()


# ------------------------------------- STATE capture + converter v2 tracks


def _capture_deadlock_break(gov, sink):
    budget = BudgetedResource(gov, limit_bytes=10)
    Profiler.init(sink)
    Profiler.start()

    def task():
        with task_context(gov, 7):
            with pytest.raises((GpuRetryOOM, GpuSplitAndRetryOOM)):
                budget.acquire(50)  # never fits: the watchdog breaks it

    t = threading.Thread(target=task)
    t.start()
    t.join(timeout=15)
    assert not t.is_alive()
    Profiler.stop()
    Profiler.shutdown()


def test_state_records_stream_into_capture_and_chrome(gov):
    sink = io.BytesIO()
    _capture_deadlock_break(gov, sink)

    evs = list(parse_capture(sink.getvalue()))
    states = [e for e in evs if e["type"] == "state"]
    kinds = {e["kind"] for e in states}
    assert {"admitted", "blocked", "woken", "deadlock_verdict",
            "retry", "task_done"} <= kinds
    s7 = [e for e in states if e["task_id"] == 7]
    assert s7 and all(e["tid"] > 0 for e in s7)
    # the capture mirrors the ring bit-for-bit (same kinds in order)
    ring7 = [e for e in flight.snapshot() if e["task_id"] == 7]
    assert [e["kind"] for e in s7] == [e["kind"] for e in ring7]

    chrome = to_chrome(evs)
    gov_evs = [e for e in chrome["traceEvents"] if e.get("pid") == 2000]
    # per-task governance track, named, holding spans AND instants
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               and e["args"]["name"] == "governance" for e in gov_evs)
    assert any(e["ph"] == "M" and e.get("tid") == 7
               and "task 7" in e["args"]["name"] for e in gov_evs)
    spans = [e for e in gov_evs if e["ph"] == "X" and e.get("tid") == 7]
    assert spans and spans[0]["name"] == "blocked"
    assert spans[0]["dur"] > 0
    assert any(e["ph"] == "i" and e["name"] == "deadlock_verdict"
               for e in gov_evs)
    # aligned with host seam events: same monotonic-us timeline, pid 0
    host_ts = [e["ts"] for e in chrome["traceEvents"]
               if e.get("pid") == 0 and "ts" in e]
    if host_ts:
        assert min(host_ts) - 1e6 <= spans[0]["ts"] <= max(host_ts) + 1e6


def test_counter_records_carry_tid_in_v2():
    sink = io.BytesIO()
    Profiler.init(sink)
    Profiler.start()
    Profiler.counter("c", 5)
    Profiler.stop()
    Profiler.shutdown()
    counters = [e for e in parse_capture(sink.getvalue())
                if e["type"] == "counter"]
    me = threading.get_ident() & 0xFFFFFFFF
    assert counters and all(e["tid"] == me for e in counters)


def _v1_capture() -> bytes:
    """A hand-packed format-v1 stream: one block with a STRING_DEF, a
    RANGE, and a tid-less COUNTER (the pre-flight-recorder layout)."""
    name = b"old_op"
    payload = struct.pack("<BIH", 0, 0, len(name)) + name
    payload += struct.pack("<BIBQQI", 1, 0, 0, 100, 200, 77)
    payload += struct.pack("<BIQq", 3, 0, 150, -9)
    return (MAGIC + struct.pack("<I", 1)
            + struct.pack("<I", len(payload)) + payload)


def test_converter_reads_v1_and_v2():
    evs = list(parse_capture(_v1_capture()))
    assert [e["type"] for e in evs] == ["range", "counter"]
    assert evs[0]["name"] == "old_op" and evs[0]["tid"] == 77
    assert evs[1]["value"] == -9 and evs[1]["tid"] is None  # v1: no tid
    # v1 streams cannot contain STATE records; chrome conversion still works
    assert to_chrome(evs)["traceEvents"]

    # v2 round-trip of the same shapes plus a STATE record
    sink = io.BytesIO()
    Profiler.init(sink)
    Profiler.start()
    flight.record(flight.EV_QUEUE_REJECT, 3, detail="handler:q")
    Profiler.counter("c2", 8)
    Profiler.stop()
    Profiler.shutdown()
    evs2 = list(parse_capture(sink.getvalue()))
    st = [e for e in evs2 if e["type"] == "state"]
    assert st and st[0]["kind"] == "queue_reject"
    assert st[0]["task_id"] == 3 and st[0]["detail"] == "handler:q"

    with pytest.raises(ValueError, match="unsupported SRTP version"):
        list(parse_capture(MAGIC + struct.pack("<I", 99)))


def test_converter_tolerates_truncated_final_block():
    sink = io.BytesIO()
    Profiler.init(sink, buffer_bytes=64)  # many small blocks
    Profiler.start()
    for i in range(40):
        Profiler.marker(f"m{i}")
    Profiler.stop()
    Profiler.shutdown()
    data = sink.getvalue()
    full = list(parse_capture(data))
    for cut in (1, 7, 15):
        part = list(parse_capture(data[:-cut]))
        assert 0 < len(part) < len(full)  # clean stop, no raise
        assert all(e in full for e in part)
    with pytest.raises(ValueError, match="truncated"):
        list(parse_capture(data[:-3], strict=True))
    # corruption INSIDE a complete block still raises
    bad = bytearray(data)
    bad[12] = 250  # first record kind of the first block
    with pytest.raises(ValueError, match="corrupt"):
        list(parse_capture(bytes(bad)))


def test_converter_consumes_from_mid_stream():
    sink = io.BytesIO()
    Profiler.init(sink, buffer_bytes=64)
    Profiler.start()
    for i in range(40):
        Profiler.marker(f"m{i}")
    Profiler.stop()
    Profiler.shutdown()
    data = sink.getvalue()
    # skip the header and the first block: blocks are self-contained
    (blen,) = struct.unpack_from("<I", data, 8)
    rest = data[8 + 4 + blen:]
    assert rest, "need at least two blocks for a mid-stream consumer"
    mid = list(parse_capture(rest, midstream=True))
    full = list(parse_capture(data))
    assert 0 < len(mid) < len(full)
    # names resolve (per-block string tables), never dangling #ids
    assert all(not e["name"].startswith("#") for e in mid
               if e["type"] == "instant")


# -------------------------------------------- serve metrics gauges (sat.)


def test_serve_metrics_snapshot_and_publish_carry_pressure_gauges(gov):
    from spark_rapids_jni_tpu.serve import QueryHandler, ServingEngine

    budget = BudgetedResource(gov, limit_bytes=1 << 20)
    eng = ServingEngine(gov=gov, budget=budget, workers=1, queue_size=4,
                        default_deadline_s=30.0)
    try:
        eng.register(QueryHandler(name="w", fn=lambda p, ctx: p + 1,
                                  nbytes_of=lambda p: 64))
        s = eng.open_session()
        sink = io.BytesIO()
        Profiler.init(sink)
        Profiler.start()
        assert eng.submit(s, "w", 1).result(timeout=60) == 2
        # publish() runs on the worker thread AFTER the result is
        # delivered: wait for it to land before stopping the capture
        deadline = time.monotonic() + 5.0
        while (eng.metrics.get("completed") < 1
               or eng.queue.outstanding() > 0) \
                and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.1)
        Profiler.stop()
        Profiler.shutdown()

        snap = eng.metrics.snapshot()
        g = snap["gauges"]
        # governor device/host bytes-in-use + spill-pool bytes are present
        for key in ("gov_device_bytes_in_use", "gov_device_bytes_limit",
                    "gov_host_bytes_in_use", "gov_blocked_or_bufn",
                    "spill_pool_bytes", "spill_spilled_bytes",
                    "plan_cache_hits", "plan_cache_misses",
                    "plan_cache_entries"):
            assert key in g, key
        assert g["gov_device_bytes_limit"] >= 1 << 20
        # per-task arbiter accumulators ride the snapshot
        assert isinstance(snap["tasks"], dict)
        # publish() emitted the gauges as capture counters
        counters = {e["name"] for e in parse_capture(sink.getvalue())
                    if e["type"] == "counter"}
        assert "serve_gov_device_bytes_in_use" in counters
        assert "serve_spill_pool_bytes" in counters
    finally:
        eng.shutdown()


# ------------------------------------------------------ flightdump (tool)


def _sample_dump() -> dict:
    rec = flight.FlightRecorder(ring_size=32)
    rec.record(flight.EV_TASK_ADMITTED, 1, detail="dedicated")
    rec.record(flight.EV_TASK_BLOCKED, 1, detail="alloc:dev")
    rec.record(flight.EV_TASK_WOKEN, 1, detail="alloc:ready", value=5000)
    rec.record(flight.EV_TASK_ADMITTED, 2)
    rec.record(flight.EV_TASK_BLOCKED, 2, detail="alloc:dev")
    rec.record(flight.EV_TASK_KILLED, 2, detail="OutOfBudget")
    rec.record(flight.EV_QUEUE_REJECT, 3, detail="handler:q")
    return rec.anomaly("unit_test")


def test_flightdump_reconstruction_and_completeness():
    dump = _sample_dump()
    tasks = flightdump.reconstruct(dump)
    assert set(tasks) >= {1, 2, 3, -1}
    assert [e["kind"] for e in tasks[1]] == ["admitted", "blocked", "woken"]
    assert flightdump.timeline_complete(tasks[1])
    assert flightdump.timeline_complete(tasks[2])  # killed closes blocked
    # an open blocked window is detected
    assert not flightdump.timeline_complete(
        [{"kind": "blocked"}, {"kind": "retry"}])
    text = flightdump.format_dump(dump)
    assert "task 1" in text and "blocked" in text and "unit_test" in text
    assert "OPEN BLOCKED WINDOW" not in text


def test_flightdump_cli(tmp_path):
    dump = _sample_dump()
    p = tmp_path / "d.json"
    p.write_text(json.dumps(dump))
    out = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "flightdump.py"),
         str(p), "--json"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    doc = json.loads(out.stdout)
    assert doc["1"]["complete"] is True
    assert [e["kind"] for e in doc["2"]["events"]] == \
        ["admitted", "blocked", "task_killed"]
    # human output too
    out2 = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "tools", "flightdump.py"),
         str(p), "--task", "1"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out2.returncode == 0 and "task 1" in out2.stdout
    assert "task 2" not in out2.stdout
