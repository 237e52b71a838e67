"""What decides ``correct``, shown to fail.

The control (the reference one precision down: 32-bit keys) must fail the
comparison, and a run with the timed path broken underneath must come out
not correct for each fault a q97 cell can have: half of the input left
out, the exchange between chips left out, and an answer altered where it
is produced.  The chip readings of the control are in PERF.md.
"""

from __future__ import annotations

import jax
import pytest

from bench_helpers import CELLS, CPU_PEAKS, tiny_cell
from benchmark import run

#: big enough key domains and tables that 32-bit keys collide (about
#: n^2 / 2^33 merged pairs: ~5 here, ~2e5 at SF 10)
COLLIDING = {"store_sales_rows": 400_000, "catalog_sales_rows": 200_000,
             "customer_rows": 500_000, "item_rows": 102_000}


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_the_control_fails_the_comparison(seed):
    cell = tiny_cell(CELLS[0], **COLLIDING)
    q = cell.query
    tables = q.generate(cell.config, seed)
    want = q.reference(tables)
    failed, checks = q.checks([q.control(tables)], want)
    assert failed == 1
    assert checks["count_gap"]["value"] > checks["count_gap"]["limit"] == 0
    assert q.checks([want], want) == (
        0, {"count_gap": {"value": 0, "limit": 0}})


@pytest.fixture
def fresh_plans():
    """Faults are planted at trace time: compile every plan anew, and drop
    the broken programs afterwards."""
    from spark_rapids_jni_tpu.plans import plan_cache

    plan_cache.clear()
    yield
    plan_cache.clear()


def _half_batch(monkeypatch):
    import spark_rapids_jni_tpu.models.q97 as q97

    real = q97.run_distributed_q97

    def half(mesh, store, catalog, **kw):
        n = len(store[0]) // 2
        return real(mesh, (store[0][:n], store[1][:n]), catalog, **kw)

    monkeypatch.setattr(q97, "run_distributed_q97", half)


def _no_exchange(monkeypatch):
    # every chip keeps its own rows: the all_to_all becomes the identity
    monkeypatch.setattr(jax.lax, "all_to_all",
                        lambda x, *_a, **_k: x)


def _altered_answer(monkeypatch):
    import spark_rapids_jni_tpu.models.q97 as q97

    real = q97._count_runs

    def altered(*args):
        so, co, b = real(*args)
        return so, co, b + 1

    monkeypatch.setattr(q97, "_count_runs", altered)


@pytest.mark.parametrize("fault", [_half_batch, _no_exchange,
                                   _altered_answer],
                         ids=["half_batch", "no_exchange", "altered_answer"])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch, fresh_plans):
    cell = tiny_cell(CELLS[1])
    devices = jax.devices()[:cell.chips]
    assert run.run_cell(cell, 3, 0.0, False, devices, CPU_PEAKS)["correct"]
    fault(monkeypatch)
    from spark_rapids_jni_tpu.plans import plan_cache

    plan_cache.clear()
    out = run.run_cell(cell, 3, 0.0, False, devices, CPU_PEAKS)
    assert out["correct"] is False
    assert out["failed"] == out["attempted"] >= 1
    assert out["checks"]["count_gap"]["value"] > 0
