"""The key join's useful share, in %: the probe rows the plan's
``KeyJoin`` masks kept over the probe rows its binary searches ran over,
summed over the window's queries.

The program records one ``key_join`` event in its flight ring per plan run
with such a join, its detail ending ``probe:<n>:kept:<k>:matched:<m>``.
The window's events are those at or after the open of the first of the
last n ``task`` roots (the rule of ``benchmark/spans.py``); the reference
check after the window runs no plan.  None where the program records no
such event, or the ring no longer holds the window's roots."""

import re

from benchmark import spans

_COUNTS = re.compile(r"(?:^|:)probe:(\d+):kept:(\d+):matched:(\d+)$")


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
    probe = kept = 0
    for e in events:
        if e.get("kind") != "key_join" or e["t_ns"] < starts[-n]:
            continue
        m = _COUNTS.search(str(e.get("detail", "")))
        if m:
            probe += int(m.group(1))
            kept += int(m.group(2))
    return 100.0 * kept / probe if probe else None
