"""The grouped aggregation's useful share, in %: the rows the plan's
``SegmentAgg`` masks kept over the rows its scatters ran over, summed over
the window's queries.

The program records one ``segment_agg`` event in its flight ring per plan
run with such a sink, its detail ending ``scattered:<n>:kept:<k>``.  The
window's events are those at or after the open of the first of the last n
``task`` roots (the rule of ``benchmark/spans.py``); the reference check
after the window runs no plan.  None where the program records no such
event, or the ring no longer holds the window's roots."""

import re

from benchmark import spans

_COUNTS = re.compile(r"(?:^|:)scattered:(\d+):kept:(\d+)$")


def read(ctx):
    from spark_rapids_jni_tpu.obs import flight

    events = flight.snapshot()
    n = len(ctx["queries"])
    starts = sorted(s["start_ns"] for s in spans._spans(events).values()
                    if s["kind"] == spans.ROOT and s["parent"] == 0
                    and s["start_ns"] is not None
                    and s["dur_ns"] is not None)
    if n <= 0 or len(starts) < n:
        return None
    scattered = kept = 0
    for e in events:
        if e.get("kind") != "segment_agg" or e["t_ns"] < starts[-n]:
            continue
        m = _COUNTS.search(str(e.get("detail", "")))
        if m:
            scattered += int(m.group(1))
            kept += int(m.group(2))
    return 100.0 * kept / scattered if scattered else None
