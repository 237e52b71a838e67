"""A closed-loop power run, as in the TPC-DS power test.

One stream sends its next query only when the previous one has returned.
Queries start while the window is open; the window ends with the last
completion, so every query started is counted whole, with all its time.
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Callable, ContextManager, Dict, List, Tuple


def run_window(run: Callable[[Any], Any], tables: Any, seconds: float,
               probe: Callable[[], Dict[str, float]],
               span: Callable[[str], ContextManager] = (
                   lambda _name: contextlib.nullcontext()),
               ) -> Tuple[List[Dict[str, Any]], float]:
    """Run queries back to back for ``seconds`` (at least one).

    Returns one record per query (its start and wall on the host clock,
    its answer, the program's counters before and after it) and the
    window's length, from its start to the last completion."""
    records: List[Dict[str, Any]] = []
    t0 = time.perf_counter()
    with span("bench.window"):
        while True:
            before = probe()
            q0 = time.perf_counter()
            with span("bench.query"):
                answer = run(tables)
            q1 = time.perf_counter()
            records.append({"start_s": q0 - t0, "wall_s": q1 - q0,
                            "answer": answer, "before": before,
                            "after": probe()})
            if q1 - t0 >= seconds:
                break
    return records, q1 - t0
