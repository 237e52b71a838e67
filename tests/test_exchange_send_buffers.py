"""The exchange's send buffers, built by sorting rows by destination and
slicing, against the earlier argsort + rank-scatter + bincount formulation
kept here as the plain reference.  Virtual CPU mesh (see conftest)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from spark_rapids_jni_tpu.parallel import all_to_all_shuffle, make_mesh

N_LOCAL = 32
WIDTH = 5


def _reference_shuffle(columns, part, capacity, axis, row_valid=None):
    """Slot of each row from its rank within its partition, then one
    scatter per column into the [ndev, capacity] send buffer."""
    ndev = jax.lax.axis_size(axis)
    if row_valid is not None:
        part = jnp.where(row_valid, part, ndev)
    n = part.shape[0]
    order = jnp.argsort(part, stable=True)
    sorted_part = part[order]
    counts = jnp.bincount(part, length=ndev).astype(jnp.int32)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)[:-1]])
    rank_sorted = jnp.arange(n, dtype=jnp.int32) - starts[sorted_part]
    rank = jnp.zeros((n,), jnp.int32).at[order].set(rank_sorted)
    in_cap = rank < capacity
    slot = part.astype(jnp.int32) * capacity + jnp.minimum(rank, capacity - 1)
    if row_valid is None:
        sendable = in_cap
        dropped = jnp.sum(~in_cap).astype(jnp.int32)
    else:
        sendable = in_cap & row_valid
        dropped = jnp.sum(row_valid & ~in_cap).astype(jnp.int32)
    dest = jnp.where(sendable, slot, ndev * capacity)
    send_valid = (jnp.zeros((ndev * capacity,), jnp.bool_)
                  .at[dest].set(True, mode="drop").reshape(ndev, capacity))
    sends = {
        name: jnp.zeros((ndev * capacity,) + data.shape[1:], data.dtype)
        .at[dest].set(data, mode="drop")
        .reshape((ndev, capacity) + data.shape[1:])
        for name, data in columns.items()
    }

    def a2a(x):
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=False)

    recv = {k: a2a(v).reshape((ndev * capacity,) + v.shape[2:])
            for k, v in sends.items()}
    return recv, a2a(send_valid).reshape(ndev * capacity), dropped


def _inputs(ndev, case, masked, seed=0):
    rng = np.random.RandomState(seed)
    n = ndev * N_LOCAL
    cols = {
        "k64": rng.randint(-2**62, 2**62, size=n, dtype=np.int64),
        "t8": rng.randint(-128, 128, size=n).astype(np.int8),
        "b": rng.rand(n) < 0.5,
        "bytes": rng.randint(0, 256, size=(n, WIDTH)).astype(np.uint8),
    }
    if case == "uniform":
        part = rng.randint(0, ndev, size=n).astype(np.int32)
        capacity = N_LOCAL
    else:  # every row to one device, more rows than a bucket holds
        part = np.full(n, ndev - 1, np.int32)
        capacity = N_LOCAL // 2
    valid = rng.rand(n) < 0.7 if masked else None
    return cols, part, capacity, valid


def _sharded(fn, ndev, masked):
    mesh = make_mesh((ndev, 1), devices=jax.devices()[:ndev])
    nin = 3 if masked else 2
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(P("data"),) * nin,
        out_specs=(P("data"), P("data"), P("data")), check_vma=False))


def _run(shuffle, ndev, cols, part, capacity, valid):
    def body(c, p, *v):
        if shuffle is all_to_all_shuffle:
            res = shuffle(c, p, capacity, axis="data",
                          row_valid=v[0] if v else None)
            return res.columns, res.valid, res.dropped[None]
        recv, ok, dropped = shuffle(c, p, capacity, "data",
                                    v[0] if v else None)
        return recv, ok, dropped[None]

    args = (cols, part) + ((valid,) if valid is not None else ())
    return _sharded(body, ndev, valid is not None)(*args)


@pytest.mark.parametrize("ndev", [1, 4])
@pytest.mark.parametrize("case", ["uniform", "overflow"])
@pytest.mark.parametrize("masked", [False, True], ids=["all_rows", "row_valid"])
def test_send_buffers_match_scatter_reference(ndev, case, masked):
    cols, part, capacity, valid = _inputs(ndev, case, masked)
    got = _run(all_to_all_shuffle, ndev, cols, part, capacity, valid)
    want = _run(_reference_shuffle, ndev, cols, part, capacity, valid)
    for name in cols:
        assert got[0][name].dtype == want[0][name].dtype, name
        np.testing.assert_array_equal(np.asarray(got[0][name]),
                                      np.asarray(want[0][name]), err_msg=name)
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))
    np.testing.assert_array_equal(np.asarray(got[2]), np.asarray(want[2]))
    if case == "overflow":
        assert int(np.asarray(got[2]).sum()) > 0


@pytest.mark.parametrize("ndev", [1, 4])
def test_exchange_lowers_without_scatter(ndev):
    cols, part, capacity, valid = _inputs(ndev, "uniform", True)

    def body(c, p, v):
        res = all_to_all_shuffle(c, p, capacity, axis="data", row_valid=v)
        return res.columns, res.valid, res.dropped[None]

    text = _sharded(body, ndev, True).lower(cols, part, valid).as_text()
    assert "stablehlo.sort" in text
    assert ("all_to_all" in text) == (ndev > 1)  # one device: no collective
    assert "scatter" not in text
