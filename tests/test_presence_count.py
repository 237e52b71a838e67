"""The q97 presence-count kernel (``models.q97._count_runs``) vs host sets,
over a one-limb packed key and a two-limb nullable pair key."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.models.q97 import _count_runs, _pair_key


def _rows(case, rng):
    """(cust, item, cust_valid, item_valid, is_store, valid) host arrays."""
    n = {"duplicates": 96, "garbage_invalid": 96, "all_invalid": 32,
         "single_row": 1}[case]
    cust = rng.randint(1, 6, n).astype(np.int32)
    item = rng.randint(1, 5, n).astype(np.int32)
    # a few null key components: grouped within a side, never joined
    cust_valid = rng.rand(n) >= 0.1
    item_valid = rng.rand(n) >= 0.1
    is_store = rng.rand(n) < 0.5
    valid = np.ones(n, bool)
    if case == "garbage_invalid":
        valid = rng.rand(n) >= 0.3
    elif case == "all_invalid":
        valid[:] = False
    elif case == "single_row":
        is_store[:] = True
    return cust, item, cust_valid, item_valid, is_store, valid


def _limbs(nlimbs, cust, item, cust_valid, item_valid, is_store):
    if nlimbs == 1:
        return ((cust.astype(np.int64) << 32)
                | (item.astype(np.int64) & 0xFFFFFFFF),)
    # each side keys its rows with its own side bit, as the nullable q97 does
    store_key, catalog_key = (
        [np.asarray(a) for a in _pair_key(
            jnp.asarray(cust), jnp.asarray(cust_valid),
            jnp.asarray(item), jnp.asarray(item_valid), side=side)]
        for side in (1, 0))
    return tuple(np.where(is_store, s, c)
                 for s, c in zip(store_key, catalog_key))


def _oracle(limbs, is_store, valid):
    keys = list(zip(*(k.tolist() for k in limbs)))
    s = {k for k, st, v in zip(keys, is_store, valid) if v and st}
    c = {k for k, st, v in zip(keys, is_store, valid) if v and not st}
    return len(s - c), len(c - s), len(s & c)


@pytest.mark.parametrize("case", ["duplicates", "garbage_invalid",
                                  "all_invalid", "single_row"])
@pytest.mark.parametrize("nlimbs", [1, 2])
def test_presence_count_matches_host_sets(nlimbs, case):
    rng = np.random.RandomState(97 + nlimbs)
    cust, item, cv, iv, is_store, valid = _rows(case, rng)
    if nlimbs == 1:
        cv[:] = True
        iv[:] = True
    limbs = _limbs(nlimbs, cust, item, cv, iv, is_store)
    want = _oracle(limbs, is_store, valid)
    # invalid rows carry garbage: values that collide with real keys and
    # the sort sentinel itself must not change a count
    garbage = [np.where(valid, k, rng.choice(
        np.concatenate([k, [np.iinfo(np.int64).max]]), len(k)))
        for k in limbs]
    keys = tuple(jnp.asarray(k) for k in garbage)
    got = jax.jit(_count_runs)(keys if nlimbs > 1 else keys[0],
                               jnp.asarray(is_store), jnp.asarray(valid))
    assert tuple(int(x) for x in got) == want
    if case == "duplicates":
        assert min(want) > 0, want  # the fixture must exercise all three
    elif case == "all_invalid":
        assert want == (0, 0, 0)
    elif case == "single_row":
        assert want == (1, 0, 0)
