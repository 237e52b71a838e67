"""Chaos beneath the op layer (VERDICT r3 #7): fault injection at the
device-transfer, collective-launch, and compile seams of a governed
distributed query — the failure classes the reference's CUDA-API
injector reaches (faultinj.cu:32 CUPTI interception).

Each test asserts the system RESPONDS (retry or clean abort with intact
arbiter state) rather than hanging.
"""

import numpy as np
import pytest

import jax

from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor
from spark_rapids_jni_tpu.mem.arbiter import STATE_RUNNING
from spark_rapids_jni_tpu.mem import current_thread_id
from spark_rapids_jni_tpu.models import run_distributed_q97
from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
from spark_rapids_jni_tpu.obs.faultinj import FaultInjector, InjectedException
from spark_rapids_jni_tpu.parallel import make_mesh


@pytest.fixture
def gov():
    g = MemoryGovernor(watchdog_period_s=0.05)
    yield g
    g.close()


def _tables(seed=5, n=160):
    rng = np.random.RandomState(seed)
    return ((rng.randint(1, 40, n).astype(np.int32),
             rng.randint(1, 12, n).astype(np.int32)),
            (rng.randint(1, 40, n - 40).astype(np.int32),
             rng.randint(1, 12, n - 40).astype(np.int32)))


def _mesh():
    return make_mesh((len(jax.devices()), 1))


def test_transfer_fault_mid_query_retries_to_completion(gov):
    """An injected RetryOOM at the batch-upload TRANSFER seam mid-governed
    query must drive the normal retry protocol: the query completes with
    the correct answer, no hang, no stuck arbiter state."""
    store, catalog = _tables()
    budget = BudgetedResource(gov, 1 << 30)
    FaultInjector.install({
        "transfer": {"plan_upload:q97": {"injectionType": "retry_oom",
                                          "interceptionCount": 1}},
    })
    try:
        out = run_distributed_q97(_mesh(), store, catalog,
                                  budget=budget, task_id=1)
    finally:
        FaultInjector.uninstall()
    want = q97_host_oracle(store, catalog)
    assert (int(out.store_only), int(out.catalog_only),
            int(out.both)) == want
    assert budget.used == 0, "retry path must not leak reservations"


def test_transfer_hard_fault_aborts_cleanly(gov):
    """A non-retryable injected exception at the TRANSFER seam must abort
    the query (propagate) with the thread back in RUNNING and the budget
    fully released — not hang, not wedge the arbiter."""
    store, catalog = _tables(seed=6)
    budget = BudgetedResource(gov, 1 << 30)
    FaultInjector.install({
        "transfer": {"plan_upload:q97": {"injectionType": "exception",
                                          "interceptionCount": 1}},
    })
    try:
        with pytest.raises(InjectedException):
            run_distributed_q97(_mesh(), store, catalog,
                                budget=budget, task_id=2)
    finally:
        FaultInjector.uninstall()
    assert budget.used == 0, "abort path must release the reservation"
    # protocol intact: the same query immediately succeeds
    out = run_distributed_q97(_mesh(), store, catalog,
                              budget=budget, task_id=2)
    assert (int(out.store_only), int(out.catalog_only),
            int(out.both)) == q97_host_oracle(store, catalog)


def test_collective_launch_fault_aborts_cleanly(gov):
    """A fault at the collective-launch seam (the wedged-collective
    simulation) aborts cleanly and leaves the task thread RUNNING."""
    store, catalog = _tables(seed=7)
    budget = BudgetedResource(gov, 1 << 30)
    gov.current_thread_is_dedicated_to_task(3)
    FaultInjector.install({
        "collective": {"launch:plan:q97:*": {"injectionType": "exception",
                                           "interceptionCount": 1}},
    })
    try:
        with pytest.raises(InjectedException):
            run_distributed_q97(_mesh(), store, catalog, budget=budget,
                                task_id=3, manage_task=False)
        assert gov.arbiter.state_of(current_thread_id()) == STATE_RUNNING
        assert budget.used == 0
        out = run_distributed_q97(_mesh(), store, catalog, budget=budget,
                                  task_id=3, manage_task=False)
        assert (int(out.store_only), int(out.catalog_only),
                int(out.both)) == q97_host_oracle(store, catalog)
    finally:
        FaultInjector.uninstall()
        gov.task_done(3)


def test_compile_fault_aborts_cleanly(gov):
    """A fault at the COMPILE seam (step build on cache miss) simulates a
    failed XLA compile; a fresh capacity forces the miss."""
    store, catalog = _tables(seed=8, n=170)
    budget = BudgetedResource(gov, 1 << 30)
    FaultInjector.install({
        "compile": {"plan:q97:*": {"injectionType": "exception",
                                   "interceptionCount": 1}},
    })
    try:
        with pytest.raises(InjectedException):
            run_distributed_q97(_mesh(), store, catalog, budget=budget,
                                task_id=4, capacity=171)  # unique -> miss
    finally:
        FaultInjector.uninstall()
    assert budget.used == 0
    out = run_distributed_q97(_mesh(), store, catalog, budget=budget,
                              task_id=4, capacity=171)
    assert (int(out.store_only), int(out.catalog_only),
            int(out.both)) == q97_host_oracle(store, catalog)


def test_alloc_seam_fault_retries_to_completion(gov):
    """An injected RetryOOM at the ALLOC seam (budget admission — the
    reference's allocator-interception point, faultinj.cu hooking the
    CUDA allocator) drives the normal retry protocol to the correct
    answer."""
    store, catalog = _tables(seed=9)
    budget = BudgetedResource(gov, 1 << 30)
    FaultInjector.install({
        "alloc": {"reserve:dev:*": {"injectionType": "retry_oom",
                                    "interceptionCount": 2}},
    })
    try:
        out = run_distributed_q97(_mesh(), store, catalog,
                                  budget=budget, task_id=5)
    finally:
        FaultInjector.uninstall()
    assert (int(out.store_only), int(out.catalog_only),
            int(out.both)) == q97_host_oracle(store, catalog)
    assert budget.used == 0
