"""Steady-state device timing by two-point subtraction.

``time_marginal(fn, iters_lo, iters_hi)`` times a loop of ``fn`` at two
iteration counts, each ending in ``jax.block_until_ready`` on the last
output, and reports ``(t_hi - t_lo) / (iters_hi - iters_lo)``.  The
subtraction cancels the fixed costs of a window (compile-cache lookup,
the final sync, dispatch-queue ramp); what remains is the steady-state
per-call time.

The reference's nvbench benchmarks (e.g.
``src/main/cpp/benchmarks/row_conversion.cpp:27``) get the same effect
from CUDA events.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Tuple

import jax

__all__ = ["time_marginal", "time_marginal_for_iters"]


def time_marginal(
    fn: Callable[[], Any],
    iters_lo: int = 5,
    iters_hi: int = 25,
    sync: Callable[[Any], Any] = jax.block_until_ready,
) -> Tuple[float, dict]:
    """Steady-state seconds per call of ``fn`` via two-point subtraction.

    Returns ``(seconds_per_call, info)`` where info carries the raw points
    for the bench detail blob.  ``fn`` is invoked ``iters_lo + iters_hi + 1``
    times total (1 warmup).  If noise makes the subtraction non-positive,
    falls back to the amortized hi-point rate (which still contains the
    fixed sync overhead and therefore *understates* throughput — safe
    direction for a reported number).
    """
    out = fn()
    sync(out)  # compile + warm

    times = []
    for iters in (iters_lo, iters_hi):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn()
        sync(out)
        times.append(time.perf_counter() - t0)

    marginal = (times[1] - times[0]) / (iters_hi - iters_lo)
    amortized = times[1] / iters_hi
    info = {
        "t_lo_s": round(times[0], 6),
        "t_hi_s": round(times[1], 6),
        "iters": [iters_lo, iters_hi],
        "amortized_s_per_call": round(amortized, 9),
        "method": "marginal",
    }
    if marginal <= 0:
        info["method"] = "amortized-fallback"
        return amortized, info
    return marginal, info


def time_marginal_for_iters(fn: Callable[[], Any], iters: int):
    """`time_marginal` with the two points derived from a caller's legacy
    iteration budget.  Cheap stages (small ``iters``) stay cheap: total
    calls ~= 2*iters + 1, never more than ~1.3x the pre-marginal loop for
    large ``iters``.  Single place for the derivation so bench.py and
    tools/ cannot drift apart.
    """
    if iters <= 4:
        lo, hi = 1, max(3, iters)
    else:
        lo, hi = max(2, iters // 4), iters
    return time_marginal(fn, lo, hi)
