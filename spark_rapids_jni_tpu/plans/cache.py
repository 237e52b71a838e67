"""Compiled-plan cache: one traced program per (plan, dtypes, pow2 bucket).

The model runners used to keep one ``functools.lru_cache`` of jitted
steps per query module, each with its own geometry-keying rules — and the
soak tool caught what happens when a key drifts (a fresh jit wrapper plus
a compiled-executable cache entry leaked per call, ~3 MB RSS each).  This
module centralizes that caching for every plan-compiled query:

- the key is ``(plan value, mesh, input signature)`` where the input
  signature is the tuple of (table, field, dtype, padded-length) the
  executor actually uploads — lengths come pre-quantized onto the pow2
  bucket lattice (``parallel.shuffle.quantized_rows`` / ``next_pow2``,
  the same lattice columnar/buckets.py bounds string shapes with), so
  data-dependent row counts collapse onto O(log rows) variants;
- plans are frozen dataclasses built through :func:`plans.ir.lit`, which
  normalizes numpy scalars, so equal geometry can never build two
  unequal keys (the q5 ``_q5_step_cached`` geometry-keying fix, now a
  structural property);
- hit/miss/trace/eviction counters and cumulative trace/compile/execute
  seconds are exported as gauges through ``serve/metrics`` (the engine's
  gauge source) and as an ``obs/flight`` telemetry source, so anomaly
  dumps and BENCH json both show compile amortization.

Entries are LRU-bounded by the ``plan_cache_size`` flag — the Sparkle
large-memory-tier model: compiled variants stay resident while hot.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

from spark_rapids_jni_tpu.obs import flight as _flight

__all__ = ["CompiledPlan", "PlanCache", "plan_cache"]


class CompiledPlan:
    """One cached executable: the fused program plus its call metadata."""

    __slots__ = ("fn", "plan", "mesh", "signature", "out_names", "arg_names",
                 "aot", "trace_s", "compile_s", "aot_error")

    def __init__(self, fn, plan, mesh, signature, out_names, arg_names,
                 aot: bool, trace_s: float, compile_s: float,
                 aot_error: str = ""):
        self.fn = fn
        self.plan = plan
        self.mesh = mesh
        self.signature = signature
        self.out_names = out_names
        self.arg_names = arg_names
        self.aot = aot
        self.trace_s = trace_s
        self.compile_s = compile_s
        # why AOT lower+compile fell back to plain jit ("" = it didn't):
        # a real trace bug surfacing here would otherwise defer to first
        # launch and misattribute to the COLLECTIVE seam
        self.aot_error = aot_error


class PlanCache:
    """Process-global LRU of :class:`CompiledPlan` + gauge counters."""

    def __init__(self, maxsize: Optional[int] = None):
        self._maxsize = maxsize
        self._lock = threading.RLock()
        # the LRU table + its gauges: every access below goes through
        # _lock (the guarded-by pass enforces it), so stats() readers on
        # dump/telemetry threads can never see a half-updated eviction
        self._entries: "collections.OrderedDict" = \
            collections.OrderedDict()  # guarded-by: _lock
        self._building: Dict[Tuple, threading.Event] = {}  # guarded-by: _lock
        self._stats: Dict[str, float] = {  # guarded-by: _lock
            "hits": 0, "misses": 0, "evictions": 0, "aot_fallbacks": 0,
            "trace_s": 0.0, "compile_s": 0.0,
            "execute_calls": 0, "execute_s": 0.0,
        }
        self._last_aot_error = ""  # guarded-by: _lock

    def _cap(self) -> int:
        if self._maxsize is not None:
            return self._maxsize
        from spark_rapids_jni_tpu import config

        return int(config.get("plan_cache_size"))

    def get_or_compile(self, key: Tuple,
                       builder: Callable[[], CompiledPlan]) -> CompiledPlan:
        """Return the cached program for ``key``, building (tracing +
        compiling) on miss.  Builds are deduplicated PER KEY, not by
        holding the cache lock across the multi-second compile: a
        concurrent same-key request waits for the one in-flight build,
        while different keys compile in parallel and cache hits — and
        the stats() readers behind serve gauges and flight anomaly
        dumps — never stall behind someone else's cold shape."""
        while True:
            with self._lock:
                hit = self._entries.get(key)
                if hit is not None:
                    self._entries.move_to_end(key)
                    self._stats["hits"] += 1
                    return hit
                ev = self._building.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._building[key] = ev
                    break  # we own this build
            # same-key build in flight: wait, then re-check (the owner
            # may have failed — an injected compile fault — in which
            # case the next loop iteration claims the build itself)
            ev.wait()
        try:
            t0 = time.perf_counter()
            entry = builder()
            dt = time.perf_counter() - t0
        except BaseException:
            with self._lock:
                del self._building[key]
            ev.set()
            raise
        with self._lock:
            del self._building[key]
            self._stats["misses"] += 1
            if entry.aot_error:
                # the build fell back from AOT lower+compile to plain jit
                # (entry.aot_error says why): surfaced as a gauge so a
                # swallowed trace failure is visible in telemetry, not
                # silently deferred to the first launch.  Entries that
                # never compile (row_conversion's host-built permutation
                # plans) carry no error and are not fallbacks.
                self._stats["aot_fallbacks"] += 1
                self._last_aot_error = entry.aot_error
            # builder-reported phase split when available (AOT lower/
            # compile); else the whole build counts as trace time
            if entry.trace_s or entry.compile_s:
                self._stats["trace_s"] += entry.trace_s
                self._stats["compile_s"] += entry.compile_s
            else:
                self._stats["trace_s"] += dt
            self._entries[key] = entry
            cap = self._cap()
            while len(self._entries) > max(cap, 1):
                self._entries.popitem(last=False)
                self._stats["evictions"] += 1
        ev.set()
        return entry

    def record_execute(self, seconds: float) -> None:
        with self._lock:
            self._stats["execute_calls"] += 1
            self._stats["execute_s"] += seconds

    def stats(self) -> Dict[str, Any]:
        """Gauge snapshot (JSON-able).  ``traces`` mirrors ``misses``:
        every miss is exactly one trace of the fused program — the
        number a retrace-stability test watches."""
        with self._lock:
            out = dict(self._stats)
            out["entries"] = len(self._entries)
            out["traces"] = out["misses"]
            if self._last_aot_error:
                out["last_aot_error"] = self._last_aot_error
            for k in ("trace_s", "compile_s", "execute_s"):
                out[k] = round(out[k], 6)
            return out

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def reset_stats(self) -> None:
        with self._lock:
            for k in self._stats:
                self._stats[k] = 0 if isinstance(self._stats[k], int) else 0.0
            self._last_aot_error = ""


#: the process-global cache every plan-compiled query shares (like the
#: governor's default budget: one resident set, one gauge surface)
plan_cache = PlanCache()

# anomaly dumps carry the compile-cache state next to serve/governor
# gauges: a retry storm caused by compile-variant churn is visible as a
# miss/eviction ramp in the same artifact
_flight.register_telemetry_source("plan_cache", plan_cache.stats)
