"""Per-query readings of the program's own spans.

The program records each governed task as a ``task`` root span, with its
phases as direct children (``admit``, ``plan_pad``, ``plan_upload``,
``plan_run``, ``plan_download``), as open and close events in its
always-on flight ring (``spark_rapids_jni_tpu.obs.flight``).  A span event's
detail starts ``rid:<r>:span:<s>:parent:<p>:kind:<k>``; a close carries
the span's duration in ns.

After the window, the last ``n`` closed roots are the window's ``n``
queries: the warm-up's root comes before them, and the reference check
after the window runs no task.  A root counts only when the ring still
holds its open event, and with it every child's.  The parsing and the
arithmetic live here, apart from the program's own reconstruction, so that
what the readers compute does not move with the program.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional

ROOT = "task"
_TOKENS = re.compile(
    r"(?:^|:)rid:(\d+):span:(\d+):parent:(\d+):kind:([a-z_]+)")


def _spans(events) -> Dict[int, dict]:
    """Span id -> {parent, kind, start_ns, dur_ns}; a span missing its
    open or its close keeps None there."""
    spans: Dict[int, dict] = {}
    for e in events:
        ev = e.get("kind")
        if ev not in ("span_open", "span_close"):
            continue
        m = _TOKENS.search(str(e.get("detail", "")))
        if not m:
            continue
        s = spans.setdefault(int(m.group(2)), {
            "parent": int(m.group(3)), "kind": m.group(4),
            "start_ns": None, "dur_ns": None})
        if ev == "span_open":
            s["start_ns"] = int(e["t_ns"])
        else:
            s["dur_ns"] = int(e["value"])
    return spans


def _union_ns(intervals, lo: int, hi: int) -> int:
    """Length of the union of ``intervals``, clipped to [lo, hi]."""
    total, end = 0, lo
    for a, b in sorted((max(a, lo), min(b, hi)) for a, b in intervals):
        a = max(a, end)
        if b <= a:
            continue
        total += b - a
        end = b
    return total


def tasks(events, n: int) -> Optional[List[dict]]:
    """The last ``n`` whole ``task`` roots among ``events`` (flight-event
    dicts, oldest first), oldest first: for each, ``dur_s``, ``by_kind``
    (seconds of its direct children, summed by kind) and ``self_s`` (its
    duration less the union of its children's intervals).  None when
    fewer than ``n`` are found."""
    spans = _spans(events)
    whole = {sid: s for sid, s in spans.items()
             if s["start_ns"] is not None and s["dur_ns"] is not None}
    roots = sorted((sid for sid, s in whole.items()
                    if s["kind"] == ROOT and s["parent"] == 0),
                   key=lambda sid: whole[sid]["start_ns"])
    if n <= 0 or len(roots) < n:
        return None
    children: Dict[int, List[dict]] = {sid: [] for sid in roots[-n:]}
    for s in whole.values():
        if s["parent"] in children:
            children[s["parent"]].append(s)
    out = []
    for sid in roots[-n:]:
        root = whole[sid]
        lo, hi = root["start_ns"], root["start_ns"] + root["dur_ns"]
        by_kind: Dict[str, float] = {}
        for c in children[sid]:
            by_kind[c["kind"]] = by_kind.get(c["kind"], 0.0) + \
                c["dur_ns"] / 1e9
        covered = _union_ns(((c["start_ns"], c["start_ns"] + c["dur_ns"])
                             for c in children[sid]), lo, hi)
        out.append({"dur_s": root["dur_ns"] / 1e9, "by_kind": by_kind,
                    "self_s": (root["dur_ns"] - covered) / 1e9})
    return out


def window_tasks(ctx) -> Optional[List[dict]]:
    """:func:`tasks` of the program's flight ring, one per query of the
    window that ``ctx`` describes."""
    from spark_rapids_jni_tpu.obs import flight

    return tasks(flight.snapshot(), len(ctx["queries"]))


def seconds_per_query(ctx, kind: str) -> Optional[float]:
    """Mean seconds per window query in ``kind`` children of its root;
    None without the roots, or where no root has such a child."""
    ts = window_tasks(ctx)
    if ts is None or not any(kind in t["by_kind"] for t in ts):
        return None
    return sum(t["by_kind"].get(kind, 0.0) for t in ts) / len(ts)
