"""The compiled ``SegmentAgg`` against ``jax.ops.segment_sum`` and numpy.

Integer aggs sum by one payload sort and prefix-sum differences, float aggs
by a scatter; either way every sum must equal a masked ``segment_sum`` into
a drop bucket, wraparound included, bit for bit, on a local plan and on a
two-device mesh (per-shard partials, then psum)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu.plans import ir
from spark_rapids_jni_tpu.plans.compiler import agg_path
from spark_rapids_jni_tpu.plans.ir import col, lit
from spark_rapids_jni_tpu.plans.runtime import execute_plan

ROWS = 3001  # odd, so the mesh pads a row


def _case(name, rng):
    """(key, mask, {agg: (values or scalar, dtype)}, n_segments)."""
    n = 37
    key = rng.integers(0, n, ROWS).astype(np.int32)
    mask = rng.random(ROWS) < 0.6
    small = rng.integers(-1000, 1000, ROWS)
    if name in ("int32", "int64", "uint64"):
        vals = small.astype(name) if name != "uint64" else rng.integers(
            0, 2**64 - 1, ROWS, dtype=np.uint64, endpoint=True)
        return key, mask, {"s": (vals, name)}, n
    if name == "all_masked":
        return key, np.zeros(ROWS, bool), {"s": (small, "int64"),
                                           "c": (1, "int32")}, n
    if name == "one_group":
        return np.full(ROWS, 5, np.int32), np.ones(ROWS, bool), {
            "s": (small, "int64"), "c": (1, "int32")}, n
    if name == "int64_wrap":
        # every sum passes 2**63 many times over
        vals = rng.integers(2**62, 2**63 - 1, ROWS, dtype=np.int64)
        return key, mask, {"s": (vals, "int64")}, n
    if name == "int32_wrap":
        vals = rng.integers(2**30, 2**31 - 1, ROWS).astype(np.int32)
        return key, mask, {"s": (vals, "int32")}, n
    if name == "empty_groups_and_ends":
        # only keys 0, 9 and n - 1 hold rows; every other group is empty
        key = rng.choice(np.asarray([0, 9, n - 1], np.int32), ROWS)
        return key, mask, {"s": (small, "int64")}, n
    if name == "literal_count":
        return key, mask, {"c": (1, "int32"), "c3": (3, "int64")}, n
    if name == "literal_key":
        return 3, mask, {"s": (small, "int64"), "c": (1, "int32"),
                         "f": (small.astype(np.float32), "float32")}, n
    if name == "int64_and_float32":
        # small whole numbers: float32 sums them exactly in any order
        return key, mask, {"s": (small, "int64"),
                           "f": (small.astype(np.float32), "float32")}, n
    raise KeyError(name)


CASES = ["int32", "int64", "uint64", "all_masked", "one_group", "int64_wrap",
         "int32_wrap", "empty_groups_and_ends", "literal_count",
         "literal_key", "int64_and_float32"]


def _plan(key, aggs, n):
    fields = ("k",) * bool(np.ndim(key)) + ("m",) + tuple(
        a for a, (v, _d) in aggs.items() if np.ndim(v))
    node = ir.Filter(ir.Scan("t", fields), col("m"))
    sink = ir.SegmentAgg(node, key=col("k") if np.ndim(key) else lit(key),
                         num_segments=n, aggs=tuple(
        (a, col(a) if np.ndim(v) else lit(v), d)
        for a, (v, d) in aggs.items()))
    return ir.Plan("grouped_sum_parity", (sink,))


def _numpy_sums(key, mask, v, dtype, n):
    out = np.zeros(n, dtype)
    vals = np.broadcast_to(np.asarray(v).astype(dtype), key.shape)
    np.add.at(out, key[mask], vals[mask])  # wraps like the device
    return out


def _segment_sum(key, mask, v, dtype, n):
    vals = jnp.where(mask, jnp.asarray(v), 0).astype(dtype)
    return np.asarray(jax.ops.segment_sum(vals, jnp.where(mask, key, n),
                                          num_segments=n + 1)[:-1])


@pytest.mark.parametrize("chips", [None, 2], ids=["local", "mesh2"])
@pytest.mark.parametrize("case", CASES)
def test_grouped_sums_equal_segment_sum_and_numpy(case, chips):
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    key, mask, aggs, n = _case(case, np.random.default_rng(CASES.index(case)))
    table = {"m": mask, **({"k": key} if np.ndim(key) else {})}
    table.update({a: v for a, (v, _d) in aggs.items() if np.ndim(v)})
    mesh = None if chips is None else make_mesh(
        (chips, 1), devices=jax.devices()[:chips])
    plan = _plan(key, aggs, n)
    got = execute_plan(mesh, plan, {"t": table})
    key = np.broadcast_to(np.int32(key), mask.shape)
    for a, (v, d) in aggs.items():
        want = _numpy_sums(key, mask, v, d, n)
        assert got[a].dtype == want.dtype, a
        assert got[a].tobytes() == want.tobytes(), a
        assert got[a].tobytes() == _segment_sum(key, mask, v, d, n).tobytes()
    floats = any(d.startswith("float") for _v, d in aggs.values())
    assert agg_path(plan) == ("mixed" if floats else "sorted")
    if case == "int64_wrap":
        # the fixture wraps: the exact sums lie far past int64
        exact = [sum(int(x) for x in aggs["s"][0][mask & (key == g)])
                 for g in range(n)]
        assert max(map(abs, exact)) > 2**64
    if case == "int32_wrap":
        assert int(np.abs(_numpy_sums(key, mask, aggs["s"][0], "int64",
                                      n)).max()) > 2**32


def test_a_grouped_sum_leaves_no_frame_in_q97s_program():
    """Jitted helpers cache their traces across programs, by shape: a
    grouped sum over the rows of q97's exchange (a union of 4,096 and
    2,048 padded rows), compiled first, leaves no frame of its emitter in
    q97's program."""
    import re

    from spark_rapids_jni_tpu.models.q97 import default_q97_capacity, q97_plan
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh
    from spark_rapids_jni_tpu.plans.compiler import compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    mesh = make_mesh((1, 1), devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    fields = ("k", "m", "s", "f")
    node = ir.Filter(ir.Union(tuple(ir.Scan(t, fields) for t in "ab"),
                              "tag", (1, 2)), col("m"))
    sink = ir.SegmentAgg(node, key=col("k"), num_segments=1, aggs=(
        ("s", col("s"), "int64"), ("f", col("f"), "float32"),
        ("c", lit(1), "int32")))
    tables = {t: {"k": np.zeros(rows, np.int32),
                  "m": rng.random(rows) < 0.5,
                  "s": np.arange(rows), "f": np.ones(rows, np.float32)}
              for t, rows in (("a", 3000), ("b", 1500))}
    got = execute_plan(mesh, ir.Plan("grouped_union", (sink,)), tables)
    kept = sum(int(np.count_nonzero(t["m"])) for t in tables.values())
    assert int(got["c"][0]) == int(got["f"][0]) == kept
    tables = {t: {"cust": np.empty(rows, np.int32),
                  "item": np.empty(rows, np.int32)}
              for t, rows in (("store", 3000), ("catalog", 1500))}
    plan = q97_plan(default_q97_capacity(4500, 1))
    cp = compile_plan(plan, mesh, input_signature_raw(plan, tables, 1))
    assert not re.search(r"segment_agg|_sorted_segment_sums|_lower_bounds",
                         cp.fn.as_text())
