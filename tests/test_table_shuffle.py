"""Columnar table shuffle: real batches (validity, strings, decimal128)
across the device mesh, matching a host oracle.

Closes VERDICT r2 missing #3: the exchange now moves nullable fixed-width
columns, DECIMAL128 limb pairs, and string columns (as padded byte
rectangles), not just bare arrays.  Reference intent: row_conversion.cu:574
exists to serialize rows for exchange; the TPU-native form is dense
per-column collective payloads.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from spark_rapids_jni_tpu.columnar.column import (
    Column,
    Decimal128Column,
    column,
    decimal128_column,
    strings_column,
)
from spark_rapids_jni_tpu.columnar.dtypes import INT32
from spark_rapids_jni_tpu.parallel import (
    DATA_AXIS,
    make_mesh,
    materialize_strings,
    shuffle_table,
)

NDEV = 8


def _mesh():
    return make_mesh((NDEV, 1), devices=jax.devices()[:NDEV])


def _shuffle_fn(mesh, capacity, width):
    """jit(shard_map) wrapper: partition by an int column mod ndev."""

    def body(keys, fixed, dec, sbytes, slens, svalid):
        from spark_rapids_jni_tpu.parallel.table_shuffle import PaddedStrings

        part = (keys.data % NDEV).astype(jnp.int32)
        ex = shuffle_table(
            {
                "k": keys,
                "x": fixed,
                "d": dec,
                "s": PaddedStrings(sbytes, slens, svalid),
            },
            part, capacity, axis=DATA_AXIS,
        )
        return ex.columns, ex.valid, jax.lax.psum(ex.dropped, DATA_AXIS)

    return jax.jit(
        jax.shard_map(
            body,
            mesh=mesh,
            in_specs=tuple(P(DATA_AXIS) for _ in range(6)),
            out_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
            check_vma=False,
        )
    )


@pytest.fixture(scope="module")
def shuffled():
    """One shuffled table (all column kinds), shared across assertions."""
    rng = np.random.RandomState(3)
    n = 32 * NDEV
    keys_np = rng.randint(0, 1000, n).astype(np.int32)
    xs = [None if rng.rand() < 0.2 else int(v)
          for v in rng.randint(-50, 50, n)]
    decs = [None if rng.rand() < 0.2 else
            (int(v) << 64) + int(rng.randint(0, 1 << 30))
            for v in rng.randint(-5, 5, n)]
    strs = [None if rng.rand() < 0.2 else
            ("s%d" % v) * (1 + v % 4) for v in rng.randint(0, 99, n)]

    keys = column([int(k) for k in keys_np], INT32)
    fixed = column(xs, INT32)
    dec = decimal128_column(decs, precision=38, scale=2)
    scol = strings_column(strs)
    width = max(scol.max_len(), 1)

    mesh = _mesh()
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    put = functools.partial(jax.device_put, device=sharding)
    sbytes, slens = scol.padded(width)

    capacity = n  # safe: no drops
    fn = _shuffle_fn(mesh, capacity, width)
    cols, valid, dropped = fn(
        jax.tree.map(put, keys),
        jax.tree.map(put, fixed),
        jax.tree.map(put, dec),
        put(sbytes), put(slens), put(scol.is_valid()),
    )
    from spark_rapids_jni_tpu.parallel import ShuffledTable

    ex = ShuffledTable(cols, valid, dropped)
    jax.block_until_ready((cols, valid, dropped))
    rows = list(zip(keys_np.tolist(), xs, decs, strs))
    return ex, rows, capacity


def _received(ex):
    """(slot -> device) mapping plus host views of the received table."""
    valid = np.asarray(ex.valid)
    k = np.asarray(ex.columns["k"].data)
    return valid, k


@pytest.mark.slow
def test_no_rows_dropped(shuffled):
    ex, rows, capacity = shuffled
    assert int(np.asarray(ex.dropped).sum()) == 0
    valid, _ = _received(ex)
    assert valid.sum() == len(rows)


def test_rows_land_on_owner_device(shuffled):
    ex, rows, capacity = shuffled
    valid, k = _received(ex)
    # global receive layout: [ndev_recv, ndev_src, capacity] flattened per
    # device; slot i on device d must hold keys with k % NDEV == d
    per_dev = NDEV * capacity
    for d in range(NDEV):
        sl = slice(d * per_dev, (d + 1) * per_dev)
        got = k[sl][valid[sl]]
        assert np.all(got % NDEV == d)


def test_fixed_and_decimal_and_strings_match_oracle(shuffled):
    ex, rows, capacity = shuffled
    valid, k = _received(ex)
    x = ex.columns["x"]
    d = ex.columns["d"]
    s = materialize_strings(ex.columns["s"])

    x_list = Column(x.data, x.validity, x.dtype).to_list()
    d_list = Decimal128Column(d.hi, d.lo, d.validity, d.dtype).unscaled_to_list()
    s_list = s.to_list()

    got = sorted(
        [(int(k[i]), x_list[i], d_list[i], s_list[i])
         for i in range(len(valid)) if valid[i]],
        key=repr,
    )
    want = sorted(rows, key=repr)
    assert got == want


def test_null_validity_survives_exchange(shuffled):
    ex, rows, capacity = shuffled
    valid, k = _received(ex)
    x = ex.columns["x"]
    xv = np.asarray(x.validity)
    # every pad slot must read as null, not garbage
    assert not xv[~valid].any()
    # null fraction of real rows matches the input
    n_null_in = sum(1 for _, xx, _, _ in rows if xx is None)
    assert (~xv[valid]).sum() == n_null_in


def test_string_column_rejected_without_padding():
    rng = np.random.RandomState(0)
    scol = strings_column(["a", "bb"])
    with pytest.raises(TypeError, match="PaddedStrings"):
        shuffle_table({"s": scol}, jnp.zeros(2, jnp.int32), 2)


@pytest.mark.slow
def test_capacity_overflow_reports_dropped_and_recovers():
    """Skewed keys overflow a small capacity (dropped > 0, the shuffle-spill
    signal the governed runners grow on); a doubled capacity recovers all
    rows — the grow-retry contract for real tables."""
    rng = np.random.RandomState(9)
    n = 16 * NDEV
    # heavy skew: most rows hash to one destination
    keys_np = np.where(rng.rand(n) < 0.8, 3, rng.randint(0, 1000, n))
    keys_np = keys_np.astype(np.int32)
    strs = ["s%d" % v for v in range(n)]

    keys = column([int(k) for k in keys_np], INT32)
    scol = strings_column(strs)
    width = max(scol.max_len(), 1)
    mesh = _mesh()
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    put = functools.partial(jax.device_put, device=sharding)
    sbytes, slens = scol.padded(width)

    def counts(capacity):
        fn = _shuffle_fn(mesh, capacity, width)
        cols, valid, dropped = fn(
            jax.tree.map(put, keys), jax.tree.map(put, keys),
            jax.tree.map(put, decimal128_column([0] * n, 38, 2)),
            put(sbytes), put(slens), put(scol.is_valid()),
        )
        return int(np.asarray(dropped)), int(np.asarray(valid).sum())

    small_dropped, small_received = counts(2)
    assert small_dropped > 0
    assert small_received == n - small_dropped
    big_dropped, big_received = counts(n)
    assert big_dropped == 0 and big_received == n


@pytest.mark.slow
def test_jcudf_row_bytes_ride_the_exchange():
    """SURVEY §7.8's original plan — 'all_to_all of serialized row batches,
    reuses the row conversion' (row_conversion.cu:574 exists to serialize
    rows for exchange): JCUDF fixed-width rows are a [n, row_bytes] byte
    rectangle, which the shuffle moves like any fixed-width column; the
    receiver deserializes back to columns, nulls intact."""
    from spark_rapids_jni_tpu.columnar.column import ListColumn
    from spark_rapids_jni_tpu.columnar.dtypes import INT64, FLOAT64
    from spark_rapids_jni_tpu.ops.row_conversion import (
        convert_from_rows,
        convert_to_rows,
    )

    rng = np.random.RandomState(4)
    n = 16 * NDEV
    keys_np = rng.randint(0, 100, n).astype(np.int64)
    vals = [None if rng.rand() < 0.25 else float(v)
            for v in rng.rand(n).round(6)]

    key_col = column([int(k) for k in keys_np], INT64)
    val_col = column(vals, FLOAT64)
    [rows_col] = convert_to_rows([key_col, val_col])
    offs = np.asarray(rows_col.offsets)
    row_bytes = int(offs[1] - offs[0])
    rect = jnp.reshape(rows_col.child.data, (n, row_bytes))

    mesh = _mesh()
    sharding = NamedSharding(mesh, P(DATA_AXIS))

    def body(rows_rect, part):
        from spark_rapids_jni_tpu.parallel import all_to_all_shuffle

        ex = all_to_all_shuffle({"r": rows_rect}, part, n, axis=DATA_AXIS)
        return ex.columns["r"], ex.valid

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=(P(DATA_AXIS), P(DATA_AXIS)), check_vma=False))
    part = (keys_np % NDEV).astype(np.int32)
    recv, valid = fn(jax.device_put(rect, sharding),
                     jax.device_put(part, sharding))
    valid_np = np.asarray(valid)

    # compact received rows and deserialize through the same JCUDF layout
    got_rows = np.asarray(recv)[valid_np]
    m = got_rows.shape[0]
    assert m == n
    flat = jnp.asarray(got_rows.reshape(-1))
    offsets = jnp.arange(0, (m + 1) * row_bytes, row_bytes, dtype=jnp.int32)
    back = convert_from_rows(
        ListColumn(offsets, Column(flat, None, rows_col.child.dtype), None),
        [INT64, FLOAT64])
    got = sorted(zip(back[0].to_list(), back[1].to_list()), key=repr)
    want = sorted(zip(keys_np.tolist(), vals), key=repr)
    assert got == want
