"""The per-layer readers of the program's spans (``benchmark/spans.py``):
on synthetic flight events, and in a traced CPU run of each cell."""

from __future__ import annotations

import jax
import pytest

from bench_helpers import CELLS, CPU_PEAKS, tiny_cell
from benchmark import cells, run, spans

READERS = ("admit_s", "pad_s", "upload_s", "task_self_s")


def _readers():
    cell = cells.load_cell(CELLS[0])
    return {m.name: m.reader for m in cell.per_layer if m.name in READERS}


class Ring:
    """Flight-event dicts as the program's ring holds them, oldest first."""

    def __init__(self):
        self.events = []
        self._ids = iter(range(1, 1 << 30))

    def span(self, kind, start, dur, parent=0, rid=7):
        sid = next(self._ids)
        detail = f"rid:{rid}:span:{sid}:parent:{parent}:kind:{kind}"
        self.events += [
            {"kind": "span_open", "t_ns": start, "detail": detail,
             "value": 0},
            {"kind": "span_close", "t_ns": start + dur, "detail": detail,
             "value": dur}]
        return sid

    def task(self, start, children, dur=1000):
        """A ``task`` root at ``start`` with (kind, offset, length)
        children."""
        root = self.span("task", start, dur)
        for kind, off, length in children:
            self.span(kind, start + off, length, parent=root)
        return root

    def snapshot(self):
        return sorted(self.events, key=lambda e: e["t_ns"])


def _read(monkeypatch, ring, n):
    from spark_rapids_jni_tpu.obs import flight

    monkeypatch.setattr(flight, "snapshot", ring.snapshot)
    ctx = {"queries": [{}] * n}
    return {name: r.read(ctx) for name, r in _readers().items()}


PHASES = [("admit", 0, 10), ("plan_pad", 10, 200), ("plan_upload", 210, 300),
          ("plan_run", 510, 400), ("plan_download", 910, 50)]


def test_the_warm_up_task_before_the_window_is_skipped(monkeypatch):
    ring = Ring()
    ring.task(0, [("admit", 0, 500), ("plan_pad", 500, 400)])  # warm-up
    ring.task(10_000, PHASES)
    ring.task(20_000, [(k, off, 2 * n) for k, off, n in PHASES], dur=2000)
    got = _read(monkeypatch, ring, 2)
    assert got["admit_s"] == pytest.approx(15e-9)
    assert got["pad_s"] == pytest.approx(300e-9)
    assert got["upload_s"] == pytest.approx(450e-9)
    # task 1: 1000 - 960 covered; task 2 (overlapping doubled children):
    # covered [0, 1310), so 2000 - 1310
    assert got["task_self_s"] == pytest.approx((40 + 690) / 2 * 1e-9)


def test_a_split_tasks_children_sum_by_kind(monkeypatch):
    ring = Ring()
    halves = [(k, off // 2, n // 2) for k, off, n in PHASES]
    ring.task(0, halves + [(k, 500 + off, n) for k, off, n in halves])
    got = _read(monkeypatch, ring, 1)
    assert got["admit_s"] == pytest.approx(10e-9)
    assert got["pad_s"] == pytest.approx(200e-9)
    assert got["upload_s"] == pytest.approx(300e-9)
    # halves cover [0, 480) and [500, 980)
    assert got["task_self_s"] == pytest.approx(40e-9)


def test_self_time_takes_the_union_of_overlapping_children(monkeypatch):
    ring = Ring()
    ring.task(0, [("plan_pad", 100, 300), ("plan_upload", 200, 400),
                  ("admit", 250, 50), ("plan_run", 1500, 100)])
    ((t,),) = [spans.tasks(ring.snapshot(), 1)]
    assert t["self_s"] == pytest.approx((1000 - 500) * 1e-9)
    assert t["by_kind"]["plan_pad"] == pytest.approx(300e-9)
    got = _read(monkeypatch, ring, 1)
    assert got["task_self_s"] == pytest.approx(500e-9)


def test_every_reader_is_absent_when_the_ring_holds_too_few_roots(
        monkeypatch):
    ring = Ring()
    ring.task(0, PHASES)
    ring.task(10_000, PHASES)
    assert all(v is not None for v in _read(monkeypatch, ring, 2).values())
    assert _read(monkeypatch, ring, 3) == dict.fromkeys(READERS)
    # a ring that wrapped past a root's open event: that root is not whole
    ring.events = ring.snapshot()[1:]
    assert _read(monkeypatch, ring, 2) == dict.fromkeys(READERS)
    # a program that records no spans at all
    assert _read(monkeypatch, Ring(), 1) == dict.fromkeys(READERS)


def test_a_phase_no_root_has_reads_as_absent(monkeypatch):
    ring = Ring()
    ring.task(0, [("plan_pad", 0, 100)])
    got = _read(monkeypatch, ring, 1)
    assert got["admit_s"] is None and got["upload_s"] is None
    assert got["pad_s"] == pytest.approx(100e-9)
    assert got["task_self_s"] == pytest.approx(900e-9)


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_cpu_run_reports_every_span_reader(name):
    cell = tiny_cell(name)
    out = run.run_cell(cell, 8, 0.0, True, jax.devices()[:cell.chips],
                       CPU_PEAKS)
    assert out["correct"]
    got = {k: out["metrics"][k]["value"] for k in READERS}
    assert all(v >= 0 for v in got.values()), got
    assert got["pad_s"] > 0 and got["upload_s"] > 0
    assert all(out["metrics"][k]["unit"] == "s" for k in READERS)
    # the phases and the unnamed rest lie inside the query's wall
    wall = out["metrics"]["query_host_s"]["value"] + \
        out["metrics"]["plan_execute_s"]["value"]
    assert sum(got.values()) < wall
