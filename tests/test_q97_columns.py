"""Distributed q97 over nullable Column keys vs a SQL-semantics host oracle.

NULL key semantics (Spark/SQL): DISTINCT groups NULL keys within a table,
but NULL never equals NULL across the join — so a side's null-key groups
count as that side's "only" rows.
"""

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_jni_tpu.columnar.column import Column, column
from spark_rapids_jni_tpu.columnar.dtypes import INT32
from spark_rapids_jni_tpu.models.q97 import make_distributed_q97_columns
from spark_rapids_jni_tpu.parallel import DATA_AXIS, make_mesh
import pytest

NDEV = 8


def _mesh():
    return make_mesh((NDEV, 1), devices=jax.devices()[:NDEV])


def _oracle(store, catalog):
    """Pairs with None keys: distinct per side, never matching across."""
    s = set(zip(store[0], store[1]))
    c = set(zip(catalog[0], catalog[1]))

    def has_null(p):
        return p[0] is None or p[1] is None

    s_null = {p for p in s if has_null(p)}
    c_null = {p for p in c if has_null(p)}
    s_nn, c_nn = s - s_null, c - c_null
    return (
        len(s_nn - c_nn) + len(s_null),
        len(c_nn - s_nn) + len(c_null),
        len(s_nn & c_nn),
    )


def _run(store, catalog, capacity=None):
    mesh = _mesh()
    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def col_of(vals):
        c = column([v for v in vals], INT32)
        return Column(
            jax.device_put(c.data, sharding),
            None if c.validity is None
            else jax.device_put(c.validity, sharding),
            c.dtype,
        )

    n_s, n_c = len(store[0]), len(catalog[0])
    assert n_s % NDEV == 0 and n_c % NDEV == 0
    cap = capacity or (2 * (n_s + n_c) // NDEV)
    step = make_distributed_q97_columns(mesh, cap)
    rv = lambda n: jax.device_put(np.ones(n, bool), sharding)  # noqa: E731
    out = step(col_of(store[0]), col_of(store[1]),
               col_of(catalog[0]), col_of(catalog[1]),
               rv(n_s), rv(n_c))
    jax.block_until_ready(out)
    assert int(out.dropped) == 0
    return int(out.store_only), int(out.catalog_only), int(out.both)


def _gen(rng, n, null_pct=0.15, hi=40):
    cust = [None if rng.rand() < null_pct else int(v)
            for v in rng.randint(1, hi, n)]
    item = [None if rng.rand() < null_pct else int(v)
            for v in rng.randint(1, 12, n)]
    return cust, item


@pytest.mark.slow
def test_nullable_q97_matches_sql_oracle():
    rng = np.random.RandomState(21)
    store = _gen(rng, 40 * NDEV)
    catalog = _gen(rng, 30 * NDEV)
    assert _run(store, catalog) == _oracle(store, catalog)


@pytest.mark.slow
def test_nullable_q97_no_nulls_agrees_with_plain_path():
    rng = np.random.RandomState(22)
    store = _gen(rng, 16 * NDEV, null_pct=0.0)
    catalog = _gen(rng, 16 * NDEV, null_pct=0.0)
    got = _run(store, catalog)
    assert got == _oracle(store, catalog)

    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle

    assert got == q97_host_oracle(
        (np.asarray(store[0], np.int32), np.asarray(store[1], np.int32)),
        (np.asarray(catalog[0], np.int32), np.asarray(catalog[1], np.int32)),
    )


@pytest.mark.slow
def test_all_null_sides():
    """Every store row has a null key: nothing can join."""
    rng = np.random.RandomState(23)
    n = 8 * NDEV
    store = ([None] * n, [1] * n)
    catalog = _gen(rng, n, null_pct=0.0)
    so, co, both = _run(store, catalog)
    assert both == 0
    assert so == 1  # one distinct (NULL, 1) group
    assert co == len(set(zip(catalog[0], catalog[1])))


def test_same_null_pair_both_sides_does_not_join():
    """(NULL, 7) in both tables: two separate groups, zero matches."""
    base = ([10, None] * (4 * NDEV), [7, 7] * (4 * NDEV))
    so, co, both = _run(base, base)
    # (10,7) joins with itself; (NULL,7) appears on both sides but never joins
    assert both == 1
    assert so == 1 and co == 1


@pytest.mark.slow
def test_null_slots_with_garbage_data_group_correctly():
    """Invalid slots may hold arbitrary data bits (review r3 finding): two
    logically-(NULL, i) rows with different garbage must form ONE group."""
    n = 4 * NDEV
    mesh = _mesh()
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    # cust data all distinct, but masked invalid on every row
    cust = Column(
        jax.device_put(np.arange(1, n + 1, dtype=np.int32), sharding),
        jax.device_put(np.zeros(n, bool), sharding), INT32)
    item = Column(
        jax.device_put(np.full(n, 7, np.int32), sharding), None, INT32)
    rv = jax.device_put(np.ones(n, bool), sharding)
    step = make_distributed_q97_columns(mesh, capacity=2 * n)
    out = step(cust, item, cust, item, rv, rv)
    jax.block_until_ready(out)
    # one distinct (NULL, 7) group per side; they never join across sides
    assert int(out.store_only) == 1
    assert int(out.catalog_only) == 1
    assert int(out.both) == 0
