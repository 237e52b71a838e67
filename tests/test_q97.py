"""Mini NDS q97 (distributed two-table join-count) through its compiled
plan vs the host set oracle."""

import numpy as np
import jax
import pytest

from spark_rapids_jni_tpu.mem.governed import ShuffleCapacityExceeded
from spark_rapids_jni_tpu.models import run_distributed_q97
from spark_rapids_jni_tpu.models.q97 import q97_host_oracle, q97_plan
from spark_rapids_jni_tpu.parallel.mesh import make_mesh
from spark_rapids_jni_tpu.plans.runtime import execute_plan


def _gen(rng, n, n_cust, n_item):
    return (rng.randint(1, n_cust + 1, n).astype(np.int32),
            rng.randint(1, n_item + 1, n).astype(np.int32))


def _one_device_mesh():
    return make_mesh((1, 1), devices=jax.devices()[:1])


def _counts(out):
    assert int(out.dropped) == 0
    return int(out.store_only), int(out.catalog_only), int(out.both)


@pytest.mark.slow
def test_q97_local_matches_oracle():
    rng = np.random.RandomState(7)
    store = _gen(rng, 500, 40, 25)
    catalog = _gen(rng, 700, 40, 25)
    out = run_distributed_q97(_one_device_mesh(), store, catalog)
    assert _counts(out) == q97_host_oracle(store, catalog)


def test_q97_empty_and_disjoint():
    mesh = _one_device_mesh()
    empty = (np.zeros((0,), np.int32), np.zeros((0,), np.int32))
    one = (np.asarray([1], np.int32), np.asarray([2], np.int32))
    assert _counts(run_distributed_q97(mesh, one, empty)) == (1, 0, 0)
    out = run_distributed_q97(
        mesh,
        (np.asarray([1, 1], np.int32), np.asarray([2, 2], np.int32)),
        (np.asarray([1], np.int32), np.asarray([3], np.int32)),
    )
    # duplicates collapse; (1,2) store-only, (1,3) catalog-only
    assert _counts(out) == (1, 1, 0)


@pytest.mark.parametrize("shape", [(8, 1), (4, 2)])
@pytest.mark.slow
def test_q97_distributed_matches_oracle(shape):
    if len(jax.devices()) < shape[0] * shape[1]:
        pytest.skip("needs 8 devices")
    mesh = make_mesh(shape)
    rng = np.random.RandomState(3)
    n = 1024  # divisible by dp
    store = _gen(rng, n, 60, 40)
    catalog = _gen(rng, n, 60, 40)
    out = run_distributed_q97(mesh, store, catalog,
                              capacity=2 * n)  # both tables: no drops
    assert _counts(out) == q97_host_oracle(store, catalog)


@pytest.mark.slow
def test_q97_capacity_overflow_reported():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 devices")
    mesh = make_mesh((8, 1))
    # all rows share one key -> all land on one shard; tiny capacity drops
    n = 256
    ones = np.ones((n,), np.int32)
    tables = {"store": {"cust": ones, "item": ones},
              "catalog": {"cust": ones, "item": ones}}
    # the retry-with-bigger-capacity signal fires
    with pytest.raises(ShuffleCapacityExceeded):
        execute_plan(mesh, q97_plan(4), tables)
