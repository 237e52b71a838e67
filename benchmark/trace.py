"""Reduce a profiler trace (XPlane) to device time, idle share and gaps.

:func:`load` reads ``*.xplane.pb`` with ``jax.profiler.ProfileData`` into
plain :class:`Trace` data: per device, its op and module events; per host
thread, its spans.  :func:`summarize` does the arithmetic, and the tests
check it on a synthetic :class:`Trace`.

- busy: the union of the intervals in which an op ran on a device, inside
  the traced window, averaged over the chips;
- idle share: 1 - busy / window;
- op and module time: the sum of event durations by name, averaged over
  the chips;
- idle gaps: the longest stretches of device 0 with no op, each named by
  the innermost host span that covers its middle.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)")
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the benchmark's own host span around the measured window
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    category: str = ""  # the op's HLO opcode, where the name gives one

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    ops: Dict[int, List[Event]]  # device ordinal -> op events
    modules: Dict[int, List[Event]]  # device ordinal -> program events
    host: Dict[str, List[Event]]  # host thread -> spans


@dataclasses.dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float  # mean over chips
    op_s: Dict[str, float]  # op name -> seconds, mean over chips
    kind_s: Dict[str, float]  # opcode (or name sans .N) -> seconds
    module_s: Dict[str, float]  # program name -> seconds, mean over chips
    idle_gaps: List[Tuple[str, float]]  # longest first, device 0


#: a TPU op event's name is its HLO instruction: ``%name = type opcode(...``
_HLO = re.compile(r"^%(\S+) = (.*?) ([a-z][a-z0-9_-]*)\(")


def op_event(name: str, start_ns: float, dur_ns: float) -> Event:
    """An op event named ``<name> <opcode> [fusion kind] <type>`` with its
    opcode as the category; a name that is no HLO text is kept as it is."""
    m = _HLO.match(name)
    if not m:
        return Event(name, start_ns, dur_ns)
    fusion = re.search(r"kind=(k\w+)", name)
    shape = re.sub(r"\{[^}]*\}", "", m.group(2))
    label = " ".join(x for x in (m.group(1), m.group(3),
                                 fusion.group(1) if fusion else "", shape)
                     if x)
    return Event(label[:160], start_ns, dur_ns, m.group(3))


def load(path: str) -> Trace:
    """Read one ``.xplane.pb`` written by ``jax.profiler``."""
    from jax.profiler import ProfileData

    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: Dict[str, List[Event]] = {}
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = [op_event(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
                elif line.name == MODULES_LINE:
                    modules[dev] = [Event(e.name, e.start_ns, e.duration_ns)
                                    for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host[line.name] = [Event(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events]
    return Trace(ops, modules, host)


def merged(events: Sequence[Event], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi], in order."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                   if e.end_ns > lo and e.start_ns < hi)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def window_of(trace: Trace) -> Optional[Tuple[float, float]]:
    """[start, end] ns of the benchmark's window span on the host."""
    for events in trace.host.values():
        for e in events:
            if e.name == WINDOW_SPAN:
                return e.start_ns, e.end_ns
    return None


def kind(e: Event) -> str:
    """An op's opcode, or its name without the ``.N`` suffix."""
    return e.category or re.sub(r"\.\d+$", "", e.name)


def _host_label(trace: Trace, t: float) -> str:
    """The innermost (shortest) host span that covers time ``t``."""
    best = None
    for events in trace.host.values():
        for e in events:
            if e.start_ns <= t <= e.end_ns and e.name != WINDOW_SPAN and (
                    best is None or e.dur_ns < best.dur_ns):
                best = e
    return best.name if best is not None else "(no host span)"


def summarize(trace: Trace, top_gaps: int = 10) -> Optional[Summary]:
    """Device time inside the window; None when no device ran an op."""
    devs = sorted(d for d, evs in trace.ops.items() if evs)
    win = window_of(trace)
    if not devs or win is None or win[1] <= win[0]:
        return None
    lo, hi = win
    n = len(devs)
    busy = 0.0
    op_s: Dict[str, float] = {}
    kind_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    for d in devs:
        busy += sum(b - a for a, b in merged(trace.ops[d], lo, hi))
        for e in trace.ops[d]:
            if lo <= e.start_ns < hi:
                op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns / n / 1e9
                k = kind(e)
                kind_s[k] = kind_s.get(k, 0.0) + e.dur_ns / n / 1e9
        for e in trace.modules.get(d, ()):
            if lo <= e.start_ns < hi:
                module_s[e.name] = module_s.get(e.name, 0.0) + \
                    e.dur_ns / n / 1e9
    spans = merged(trace.ops[devs[0]], lo, hi)
    edges = [lo] + [x for s in spans for x in s] + [hi]
    gaps = sorted(((edges[i + 1] - edges[i], edges[i])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:top_gaps]
    idle = [(_host_label(trace, a + g / 2), g / 1e9) for g, a in gaps]
    return Summary(n, (hi - lo) / 1e9, busy / n / 1e9, op_s, kind_s,
                   module_s, idle)


def seconds_matching(table: Dict[str, float], pattern: str) -> float:
    """Sum of ``table``'s seconds whose key matches ``pattern``."""
    rx = re.compile(pattern)
    return sum(v for k, v in table.items() if rx.search(k))


def breakdown(s: Summary, top: int = 10) -> dict:
    """The driver's ``breakdown``: the device ops that took most time and
    the longest idle gaps by what the host was doing."""
    ops = sorted(s.op_s.items(), key=lambda kv: kv[1], reverse=True)[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.idle_gaps[:top]]}
