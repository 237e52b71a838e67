"""One span vocabulary on the governed plan path (obs/trace.py).

A governed q97 task run outside the serving path is a ``task`` root span
whose direct children name its phases (``admit``, ``plan_pad``,
``plan_upload``, ``plan_run``, ``plan_download``); a served request's
phases nest under its compute span instead, and with spans off it records
none.  Every scoped span also lands on the profiler's host plane, and the
plan program's exchange and presence count carry stable scope names in the
HLO metadata.
"""

from __future__ import annotations

import re
import time

import jax
import numpy as np
import pytest

from spark_rapids_jni_tpu import config
from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor, \
    task_context
from spark_rapids_jni_tpu.models import run_distributed_q97
from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
from spark_rapids_jni_tpu.obs import flight, trace
from spark_rapids_jni_tpu.obs.faultinj import FaultInjector
from spark_rapids_jni_tpu.parallel import make_mesh

PHASES = ("admit", "plan_pad", "plan_upload", "plan_run", "plan_download")
SPAN_EVENTS = ("span_open", "span_close")


@pytest.fixture
def gov():
    g = MemoryGovernor(watchdog_period_s=0.05)
    yield g
    g.close()


def _mesh(ndev=4):
    return make_mesh((ndev, 1), devices=jax.devices()[:ndev])


def _tables(seed, n=200):
    rng = np.random.RandomState(seed)
    return ((rng.randint(1, 40, n).astype(np.int32),
             rng.randint(1, 12, n).astype(np.int32)),
            (rng.randint(1, 40, n - 50).astype(np.int32),
             rng.randint(1, 12, n - 50).astype(np.int32)))


def _events_of(fn):
    """(fn's result, the flight events recorded while it ran)."""
    snap = flight.snapshot()
    cursor = snap[-1]["seq"] if snap else 0
    out = fn()
    return out, flight.snapshot_since(cursor)[0]


def _roots(events):
    """rid -> (the task root span, its direct children), by waterfall."""
    out = {}
    for rid, rec in trace.waterfall(events).items():
        roots = [s for s in rec["spans"] if s["kind"] == "task"]
        if roots:
            (root,) = roots
            out[rid] = (root, [s for s in rec["spans"]
                               if s["parent"] == root["span"]])
    return out


def _answer(out):
    return int(out.store_only), int(out.catalog_only), int(out.both)


def test_each_governed_q97_call_is_one_task_root_with_one_child_per_phase(
        gov):
    store, catalog = _tables(1)
    budget = BudgetedResource(gov, 1 << 30)

    def two_calls():
        return [run_distributed_q97(_mesh(), store, catalog, budget=budget,
                                    task_id=0) for _ in range(2)]

    outs, events = _events_of(two_calls)
    assert all(_answer(o) == q97_host_oracle(store, catalog) for o in outs)
    roots = _roots(events)
    assert len(roots) == 2  # the same task id, two distinct rids
    for root, children in roots.values():
        assert root["parent"] == 0 and root["closed"]
        assert sorted(c["kind"] for c in children) == sorted(PHASES)
        assert all(c["closed"] and c["parent"] == root["span"]
                   for c in children)
        assert sum(c["dur_ms"] for c in children) <= root["dur_ms"]


def test_a_split_on_the_alloc_seam_keeps_every_piece_under_one_root(gov):
    store, catalog = _tables(2, n=400)
    budget = BudgetedResource(gov, 1 << 30)
    FaultInjector.install({
        "alloc": {"reserve:dev:*": {"injectionType": "split_oom",
                                    "interceptionCount": 1}},
    })
    try:
        out, events = _events_of(lambda: run_distributed_q97(
            _mesh(), store, catalog, budget=budget, task_id=3))
    finally:
        FaultInjector.uninstall()
    assert _answer(out) == q97_host_oracle(store, catalog)
    ((root, children),) = _roots(events).values()
    kinds = [c["kind"] for c in children]
    # the refused attempt failed at the seam, before its acquire; each of
    # the two halves then ran every phase under the same root
    assert all(kinds.count(k) == 2 for k in PHASES), kinds
    assert len(kinds) == 2 * len(PHASES)
    assert all(c["closed"] for c in children)
    assert budget.used == 0


def test_no_root_without_manage_task_and_no_context(gov):
    store, catalog = _tables(3)
    budget = BudgetedResource(gov, 1 << 30)

    def joined():
        with task_context(gov, 9):
            return run_distributed_q97(_mesh(), store, catalog,
                                       budget=budget, task_id=9,
                                       manage_task=False)

    out, events = _events_of(joined)
    assert _answer(out) == q97_host_oracle(store, catalog)
    assert [e for e in events if e["kind"] in SPAN_EVENTS] == []
    assert {"admitted", "task_done"} <= {e["kind"] for e in events}


def _served_q97(telemetry: bool, store, catalog):
    from spark_rapids_jni_tpu.serve import ServingEngine

    g = MemoryGovernor(watchdog_period_s=0.05)
    with config.override(serve_telemetry=telemetry):
        eng = ServingEngine(mesh=_mesh(), gov=g,
                            budget=BudgetedResource(g, 1 << 30), workers=1,
                            queue_size=4, builtin_handlers=True)
    try:
        def serve():
            resp = eng.submit(eng.open_session(), "q97", (store, catalog))
            out = resp.result(timeout=120)
            # span closes land just after the result is published
            time.sleep(0.2)
            return resp, out

        (resp, out), events = _events_of(serve)
    finally:
        eng.shutdown(drain=False, timeout=5)
        g.close()
    assert _answer(out) == q97_host_oracle(store, catalog)
    return resp, events


def test_a_served_request_with_spans_off_records_no_span_events():
    store, catalog = _tables(4)
    _resp, events = _served_q97(False, store, catalog)
    assert [e for e in events if e["kind"] in SPAN_EVENTS] == []


def test_a_served_request_nests_the_phases_under_its_compute_span():
    store, catalog = _tables(5)
    resp, events = _served_q97(True, store, catalog)
    rec = trace.waterfall(events)[str(resp.trace.rid)]
    (compute,) = [s for s in rec["spans"] if s["kind"] == "compute"]
    under = sorted(s["kind"] for s in rec["spans"]
                   if s["parent"] == compute["span"])
    assert under == sorted(PHASES)
    assert not any(s["kind"] == "task" for s in rec["spans"])


def test_spans_land_on_the_profilers_host_plane(gov, tmp_path):
    store, catalog = _tables(6)
    budget = BudgetedResource(gov, 1 << 30)
    run_distributed_q97(_mesh(), store, catalog, budget=budget)  # compile
    with jax.profiler.trace(str(tmp_path)):
        run_distributed_q97(_mesh(), store, catalog, budget=budget)
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = [e for plane in jax.profiler.ProfileData.from_file(
        str(path)).planes if plane.name == "/host:CPU"
        for line in plane.lines for e in line.events]
    by_name = {}
    for e in host:
        by_name.setdefault(e.name.split("#")[0], []).append(e)
    (task,) = by_name["task"]
    for kind in ("plan_pad", "plan_upload", "plan_run"):
        (e,) = by_name[kind]
        assert task.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= task.start_ns + task.duration_ns


def test_plan_scopes_name_the_exchange_and_presence_count_ops():
    """The q97 plan lowered on a (4,1) CPU mesh: each scope names ops in
    the compiled HLO's ``op_name`` metadata."""
    from spark_rapids_jni_tpu.models.q97 import default_q97_capacity, q97_plan
    from spark_rapids_jni_tpu.plans.compiler import compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    store, catalog = _tables(7)
    tables = {"store": {"cust": store[0], "item": store[1]},
              "catalog": {"cust": catalog[0], "item": catalog[1]}}
    plan = q97_plan(default_q97_capacity(350, 4))
    cp = compile_plan(plan, _mesh(), input_signature_raw(plan, tables, 4))
    assert cp.aot
    names = re.findall(r'op_name="([^"]*)"', cp.fn.as_text())
    scoped = {scope: [n for n in names if scope in n.split("/")]
              for scope in ("exchange_bucket", "exchange_scatter",
                            "exchange_all_to_all", "presence_count")}
    assert all(scoped.values()), {k: len(v) for k, v in scoped.items()}
    assert any(n.endswith("all_to_all") for n in
               scoped["exchange_all_to_all"])
    assert any("sort" in n for n in scoped["presence_count"])
