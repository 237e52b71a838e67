"""Governed execution: the retry/split-and-retry driver over the arbiter.

This is the glue the reference expresses in its *protocol documentation*
(RmmSpark.java:402-416): task code brackets device work in a retry block,
reserves its working set before launching, and reacts to the two arbiter
signals —

- ``RetryOOM``: roll back and retry the same batch (the arbiter has already
  blocked the thread until memory was freed);
- ``SplitAndRetryOOM``: the thread holds the highest priority and still can't
  make progress — *split the input batch* into smaller disjoint pieces and
  process them sequentially, combining partial results.

On the reference GPU stack the reservation point is RMM ``do_allocate``
(SparkResourceAdaptorJni.cpp:1731); on TPU, XLA owns allocation, so the
admission point is :meth:`BudgetedResource.acquire` *before* the jitted
launch.  Everything else — blocking, BUFN escalation, watchdog, metrics —
is byte-identical state-machine behavior (native/task_arbiter.cpp).

Usage shape (what models/ and the plan runtime go through)::

    gov = MemoryGovernor.instance()
    budget = default_device_budget(gov)
    with task_context(gov, task_id=7):
        out = run_with_split_retry(
            budget, batch,
            nbytes_of=lambda b: b.nbytes * 3,   # working-set estimate
            run=step,                            # launches device work
            split=split_in_half,                 # -> [b0, b1] disjoint
            combine=sum_outputs,
        )
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Any, Callable, List, Optional, Sequence

from spark_rapids_jni_tpu.mem.exceptions import RetryOOM, SplitAndRetryOOM
from spark_rapids_jni_tpu.mem.governor import (
    BudgetedResource,
    MemoryGovernor,
    OutOfBudget,
)
from spark_rapids_jni_tpu.obs import flight as _flight
from spark_rapids_jni_tpu.obs import trace as _trace

__all__ = [
    "task_context",
    "reservation",
    "run_with_split_retry",
    "attempt_once",
    "default_device_budget",
    "MaxSplitDepthExceeded",
    "ShuffleCapacityExceeded",
]


class _AttribHook:
    """Deferred binding of serve/attribution's ``note_reservation``:
    mem/ loads during package bootstrap, long before the serve package
    can (serve -> ragged -> columnar, which is mid-import above us), so
    the hook resolves on the FIRST governed release instead of at import
    and caches the bound function.  reservation() is THE single choke
    point every governed byte passes through — metering byte·seconds
    here covers runtime, executor, and shuffle-credit reservations
    alike."""

    __slots__ = ("_fn",)

    def __init__(self):
        self._fn = None

    def note_reservation(self, nbytes: int, held_ns: int) -> None:
        fn = self._fn
        if fn is None:
            from spark_rapids_jni_tpu.serve.attribution import (
                note_reservation,
            )

            fn = self._fn = note_reservation
        fn(nbytes, held_ns)


_attrib = _AttribHook()


class MaxSplitDepthExceeded(MemoryError):
    """A batch could not be made small enough within the split-depth cap."""


class ShuffleCapacityExceeded(Exception):
    """Raised by a ``run`` callback when a fixed-capacity exchange overflowed
    (``ShuffleResult.dropped > 0``).  The driver responds by re-running the
    same piece after ``grow(piece)`` — the shuffle-spill retry the reference
    protocol describes for exchanges that outgrow their buffers."""


@contextlib.contextmanager
def task_context(gov: MemoryGovernor, task_id: int):
    """Register the current thread as the dedicated thread of ``task_id``
    for the duration (startDedicatedTaskThread / taskDone pairing).
    Admission and completion land in the governance flight recorder, so a
    task's lifetime brackets its blocked/retry history in the ring."""
    gov.current_thread_is_dedicated_to_task(task_id)
    _flight.record(_flight.EV_TASK_ADMITTED, task_id, detail="dedicated")
    try:
        yield gov
    finally:
        gov.task_done(task_id)
        _flight.record(_flight.EV_TASK_DONE, task_id)


@contextlib.contextmanager
def reservation(budget: BudgetedResource, nbytes: int):
    """Reserve ``nbytes`` of budget around a block of device work.

    ``acquire`` drives the arbiter's pre_alloc/post_alloc protocol: it may
    block (another task holds the budget), raise RetryOOM/SplitAndRetryOOM
    (escalation decided this thread must retry or split), or raise
    OutOfBudget (non-retryable; request exceeds the whole budget).  The
    acquire, blocked wait included, is the ``admit`` span of the thread's
    current trace context (none without one).

    The acquire crosses the ALLOC seam — the allocation-interception
    point of the reference's chaos/profiling stack (faultinj.cu hooks the
    allocator; CUPTI sees malloc activity): the profiler records the
    admission (including any blocked wait) as a range plus a budget-used
    counter, and a chaos rule on ``alloc``/``reserve:*`` injects an
    allocation failure INSIDE the retry protocol.
    """
    from spark_rapids_jni_tpu.obs import seam as _seam

    # lock-free hot-path gate, same flags seam() itself checks: with the
    # profiler and injector both inactive this adds zero locks/formatting
    # to the admission path (incl. the up-to-500 RetryOOM retry loop)
    if _seam._profiler_range is None and _seam._injector is None:
        t0 = 0
        with _trace.maybe_span(_trace.SPAN_ADMIT):
            budget.acquire(nbytes)
        try:
            t0 = time.monotonic_ns()
            yield
        finally:
            budget.release(nbytes)
            # byte·second attribution: reservation size x hold time,
            # stamped at the choke point so every governed byte is
            # metered exactly once (no lock on this path; the counter
            # lock lives inside note_reservation and is uncontended)
            if t0:
                _attrib.note_reservation(
                    nbytes, time.monotonic_ns() - t0)
        return

    from spark_rapids_jni_tpu.obs.profiler import Profiler

    ctr = "cpu_budget_used" if budget.is_cpu else "device_budget_used"

    def _emit():
        # sample + timestamp under the budget lock so concurrent tenants'
        # counter points can never reorder against the values they carry
        with budget._lock:
            Profiler.counter(ctr, budget.used)

    acquired = False
    try:
        with _seam.seam(
                _seam.ALLOC,
                f"reserve:{'cpu' if budget.is_cpu else 'dev'}:{nbytes}"):
            with _trace.maybe_span(_trace.SPAN_ADMIT):
                budget.acquire(nbytes)
            acquired = True
    except BaseException:
        # the seam __exit__ (profiler range close) runs AFTER a
        # successful acquire: a fault there must hand the reservation
        # back before propagating, or the budget shrinks forever
        if acquired:
            budget.release(nbytes)
        raise
    t0 = 0
    try:
        # the admission counter point emits INSIDE the release bracket:
        # a profiler fault mid-emit used to leak the fresh reservation
        # (nothing released it) — the resource-lifecycle gate pins this.
        # _emit samples under the budget lock, so its ordering against
        # concurrent tenants is unchanged by sitting after the seam.
        t0 = time.monotonic_ns()
        _emit()
        yield
    finally:
        budget.release(nbytes)
        if t0:
            _attrib.note_reservation(nbytes, time.monotonic_ns() - t0)
        _emit()


_NO_BUDGET_LOCK = threading.Lock()
_DEFAULT_BUDGET: Optional[BudgetedResource] = None


def _probed_hbm_bytes() -> Optional[int]:
    """Total accelerator memory of device 0 if the backend reports it."""
    try:
        import jax

        stats = jax.local_devices()[0].memory_stats()
        limit = (stats or {}).get("bytes_limit")
        return int(limit) if limit else None
    # analyze: ignore[retry-protocol] - backend capability probe at budget
    # construction, before any task registers: no retry bracket exists yet
    except Exception:  # backend without memory_stats (CPU), or no device
        return None


def default_device_budget(gov: Optional[MemoryGovernor] = None) -> BudgetedResource:
    """Process-wide device (HBM) budget.

    Sized like the reference sizes its RMM pool — from the real device when
    the backend reports capacity (``memory_stats()['bytes_limit']``), else
    the ``device_budget_bytes`` config flag.  The cached facade is rebuilt
    if the governor it was bound to has been shut down (a stale budget
    would otherwise drive a closed native arbiter).
    """
    global _DEFAULT_BUDGET
    with _NO_BUDGET_LOCK:
        stale = (
            _DEFAULT_BUDGET is not None
            and _DEFAULT_BUDGET.gov.arbiter._h is None
        )
        if _DEFAULT_BUDGET is None or stale:
            from spark_rapids_jni_tpu import config

            limit = _probed_hbm_bytes() or int(config.get("device_budget_bytes"))
            _DEFAULT_BUDGET = BudgetedResource(
                gov or MemoryGovernor.instance(), limit
            )
        return _DEFAULT_BUDGET


def _reset_default_budget_for_tests():
    global _DEFAULT_BUDGET
    with _NO_BUDGET_LOCK:
        _DEFAULT_BUDGET = None


def run_with_split_retry(
    budget: BudgetedResource,
    batch: Any,
    *,
    nbytes_of: Callable[[Any], int],
    run: Callable[[Any], Any],
    split: Callable[[Any], Sequence[Any]],
    combine: Callable[[List[Any]], Any],
    grow: Optional[Callable[[Any], Any]] = None,
    max_split_depth: int = 8,
    max_grows: int = 8,
    initial_split_depth: int = 0,
    on_retry: Optional[Callable[[int], None]] = None,
) -> Any:
    """Process ``batch`` under the arbiter's retry protocol.

    Each (sub-)batch attempt is bracketed in a retry block; the working set
    ``nbytes_of(b)`` is reserved before ``run(b)`` launches device work and
    released after.  ``RetryOOM`` retries the same piece (the arbiter already
    blocked us until memory freed); ``SplitAndRetryOOM`` — and a first-level
    non-retryable ``OutOfBudget`` whose request exceeds the total budget —
    replaces the piece with ``split(b)`` (disjoint sub-batches), processed
    depth-first so partial results stay in input order for ``combine``.

    ``run`` may additionally raise :class:`ShuffleCapacityExceeded` to signal
    a fixed-capacity exchange overflow; the piece is re-attempted as
    ``grow(piece)`` (typically doubling the shuffle capacity), with the
    reservation recomputed for the bigger buffers.

    ``initial_split_depth`` pre-splits the batch BEFORE the first attempt
    (the adaptive controller's pre-emptive split sizing: a class whose
    history shows SplitAndRetryOOM skips the doomed full-size attempt and
    its blocked/retry churn).  Pieces start at that depth, so the
    ``max_split_depth`` cap covers pre-splits + reactive splits together.
    ``on_retry(count)`` is forwarded to every piece's retry bracket.
    """
    gov = budget.gov
    results: List[Any] = []
    # depth-first work list of (piece, depth, grows) keeps combine() order ==
    # input order
    work: List[tuple] = [(batch, 0, 0)]
    for _ in range(max(0, min(initial_split_depth, max_split_depth))):
        nxt: List[tuple] = []
        for piece, depth, grows in work:
            parts = list(split(piece))
            if len(parts) <= 1:  # not splittable further: keep as-is
                nxt.append((piece, depth, grows))
            else:
                nxt.extend((p, depth + 1, grows) for p in parts)
        if len(nxt) == len(work):
            break  # nothing split this round; deeper rounds won't either
        work = nxt
    while work:
        piece, depth, grows = work.pop(0)
        try:
            results.append(_attempt(gov, budget, piece, nbytes_of, run,
                                    on_retry=on_retry))
            continue
        except ShuffleCapacityExceeded:
            if grow is None or grows >= max_grows:
                raise
            work.insert(0, (grow(piece), depth, grows + 1))
            continue
        except SplitAndRetryOOM as e:
            err = e
        except OutOfBudget as e:
            if int(nbytes_of(piece)) <= budget.limit:
                # the arbiter declared this non-retryable (livelock cap /
                # unregistered thread): a real OOM, as in the reference
                raise
            err = e
        if depth >= max_split_depth:
            raise MaxSplitDepthExceeded(
                f"split depth {depth} reached and batch still does not fit"
            ) from err
        parts = list(split(piece))
        if len(parts) <= 1:
            raise MaxSplitDepthExceeded(
                "batch is not splittable further"
            ) from err
        work = [(p, depth + 1, grows) for p in parts] + work
    return combine(results)


def attempt_once(gov, budget, piece, nbytes_of, run, *,
                 on_retry: Optional[Callable[[int], None]] = None,
                 max_retries: int = 500):
    """One retry-block around one piece.

    Returns run's result; raises SplitAndRetryOOM / terminal OutOfBudget
    (request larger than the whole budget) for the caller to split, and
    passes ShuffleCapacityExceeded through for the caller to grow.

    Public because it is the protocol bracket EVERY single-piece admission
    goes through — :func:`run_with_split_retry` for inline splitting, and
    the serving engine (serve/executor.py), which splits by re-queueing
    halves instead.  ``on_retry(count)`` is called after each RetryOOM
    (serve metrics / deadline checks); an exception it raises aborts the
    attempt with the retry block closed cleanly.
    """
    nbytes = int(nbytes_of(piece))
    gov.start_retry_block()
    retries = 0
    try:
        while True:
            try:
                with reservation(budget, nbytes):
                    return run(piece)
            except RetryOOM:
                # arbiter blocked us until ready; same piece, try again.
                # The native 500-cap counts BUFN-path throws only, so
                # injected/self-escalated RetryOOMs (the wasted-wake
                # livelock breaker) are bounded here, mirroring the
                # reference's retry limit -> real OOM.
                retries += 1
                if on_retry is not None:
                    on_retry(retries)
                if retries >= max_retries:
                    raise OutOfBudget(
                        f"retry limit exceeded ({max_retries}) for one piece")
                continue
    finally:
        gov.end_retry_block()


_attempt = attempt_once
