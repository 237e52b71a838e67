"""q3 at the TPC-DS specification's domains, small size, on the CPU.

The item table carries the spec's sparse composite ``i_brand_id``
(category * 10^6 + class * 10^3 + brand) with an ``i_brand`` name per row,
``date_dim`` starts at d_date_sk 2415022 (1900-01-02) and spans 201 years,
the foreign keys are null over in-domain values, and the query keeps the
spec's ``ORDER BY d_year, sum_agg DESC, brand_id LIMIT 100``.  Every q3 path
of the program must equal a plain numpy q3 written here.
"""

from __future__ import annotations

import re

import numpy as np
import pytest

from spark_rapids_jni_tpu.models.q3 import (
    q3_columns_host_oracle,
    q3_local,
    q3_local_unfused,
    run_distributed_q3,
)
from spark_rapids_jni_tpu.models.tpcds import Q3Data

DATE_SK0 = 2415022  # 1900-01-02
DATE_ROWS = 73049  # to 2100-01-01
SOLD = (2450816, 2452642)  # 1998-01-02 .. 2003-01-02


def _calendar():
    days = np.datetime64("1900-01-02") + np.arange(DATE_ROWS)
    months = days.astype("datetime64[M]").astype(np.int64)
    return ((DATE_SK0 + np.arange(DATE_ROWS)).astype(np.int32),
            (months // 12 + 1970).astype(np.int32),
            (months % 12 + 1).astype(np.int32))


def spec_data(seed, *, n_sales=6000, n_items=600, n_manufact=3,
              price="uniform", null_share=0.04) -> Q3Data:
    rng = np.random.default_rng(seed)
    brand_id = (rng.integers(1, 11, n_items) * 10**6
                + rng.integers(1, 17, n_items) * 10**3
                + rng.integers(1, 11, n_items)).astype(np.int32)
    names = np.asarray([f"brand{b // 10**6}-{b // 10**3 % 10**3}"
                        f" #{b % 10**3}" for b in brand_id.tolist()])
    date_sk, year, moy = _calendar()
    if price == "uniform":
        cents = rng.integers(1, 101, n_sales) * rng.integers(0, 30001,
                                                             n_sales)
    else:  # every row the same price: group sums tie on equal counts
        cents = np.full(n_sales, 1999)
    return Q3Data(
        ss_item_sk=rng.integers(1, n_items + 1, n_sales, dtype=np.int32),
        ss_item_sk_valid=rng.random(n_sales) >= null_share,
        ss_sold_date_sk=rng.integers(SOLD[0], SOLD[1] + 1, n_sales,
                                     dtype=np.int32),
        ss_sold_date_sk_valid=rng.random(n_sales) >= null_share,
        ss_ext_sales_price=cents.astype(np.int64),
        item_sk=np.arange(1, n_items + 1, dtype=np.int32),
        item_brand_id=brand_id, item_brand=names,
        item_manufact_id=rng.integers(1, n_manufact + 1, n_items,
                                      dtype=np.int32),
        date_sk=date_sk, date_year=year, date_moy=moy,
        manufact_id=2, moy=11)


def numpy_q3(data: Q3Data, limit=100):
    """The spec's q3 in plain numpy: int64 sums, lexsort, then LIMIT; a
    null foreign key joins nothing."""
    i = data.ss_item_sk.astype(np.int64) - 1
    d = data.ss_sold_date_sk.astype(np.int64) - int(data.date_sk[0])
    keep = (data.ss_item_sk_valid & data.ss_sold_date_sk_valid
            & (data.item_manufact_id[i] == data.manufact_id)
            & (data.date_moy[d] == data.moy))
    i, d, price = i[keep], d[keep], data.ss_ext_sales_price[keep]
    names, name_idx = np.unique(data.item_brand[i], return_inverse=True)
    keys, group = np.unique(np.stack([
        data.date_year[d].astype(np.int64),
        data.item_brand_id[i].astype(np.int64),
        name_idx.reshape(-1).astype(np.int64)]), axis=1, return_inverse=True)
    sums = np.zeros(keys.shape[1], np.int64)
    np.add.at(sums, group.reshape(-1), price)
    year, bid, name = keys
    order = np.lexsort((name, bid, -sums, year))[:limit]
    return [(int(year[g]), int(bid[g]), str(names[name[g]]), int(sums[g]))
            for g in order]


def _one_device_mesh():
    import jax

    from spark_rapids_jni_tpu.parallel.mesh import make_mesh

    return make_mesh((1, 1), devices=jax.devices()[:1])


PATHS = {
    "run_distributed_q3": lambda d: run_distributed_q3(_one_device_mesh(), d),
    "q3_local": q3_local,
    "q3_local_unfused": q3_local_unfused,
    "q3_columns_host_oracle": q3_columns_host_oracle,
}


@pytest.mark.parametrize("path", sorted(PATHS))
@pytest.mark.parametrize("price", ["uniform", "tied"])
def test_every_q3_path_equals_numpy_at_spec_domains(path, price):
    data = spec_data(31, price=price)
    want = numpy_q3(data)
    got = [tuple(r) for r in PATHS[path](data)]
    assert got == want
    # the fixture exercises what it claims: a full LIMIT cut from more
    # groups, sparse ids past any dense grid, and (tied) equal sums that
    # only brand_id orders
    assert len(want) == 100 and len(numpy_q3(data, limit=10**6)) > 100
    assert max(r[1] for r in want) > 10**6
    if price == "tied":
        ties = [(a, b) for a, b in zip(want, want[1:])
                if (a[0], a[3]) == (b[0], b[3])]
        assert ties and all(a[1] < b[1] for a, b in ties)


def test_nulls_over_in_domain_values_join_nothing():
    data = spec_data(5, null_share=0.3)
    everything = Q3Data(**{
        **vars(data),
        "ss_item_sk_valid": np.ones_like(data.ss_item_sk_valid),
        "ss_sold_date_sk_valid": np.ones_like(data.ss_sold_date_sk_valid)})
    got = [tuple(r) for r in q3_local(data)]
    assert got == numpy_q3(data)
    assert got != numpy_q3(everything)


def test_sparse_brand_ids_are_coded_not_gridded():
    """Two brands at the ends of the spec's id range, one of them far past
    any dense grid over the item count: the grid holds one slot per year
    and distinct (i_brand_id, i_brand), and each sum lands on its own id
    and name, read from the item rows."""
    from spark_rapids_jni_tpu.models.q3 import _geometry

    data = spec_data(7, n_items=4, n_sales=400, n_manufact=1)
    data.item_brand_id[:] = [10_016_010, 1_001_001, 10_016_010, 1_001_001]
    data.item_brand = np.asarray(["exportiunivamalg #10", "amalgamalg #1",
                                  "exportiunivamalg #10", "amalgamalg #1"])
    data.manufact_id = 1
    geo = _geometry(data)
    assert geo["n_brands"] == 2 and geo["n_years"] == 201
    got = [tuple(r) for r in run_distributed_q3(_one_device_mesh(), data)]
    assert got == numpy_q3(data)
    assert {(r[1], r[2]) for r in got} == {
        (10_016_010, "exportiunivamalg #10"), (1_001_001, "amalgamalg #1")}


def test_one_brand_id_with_two_names_is_two_groups():
    data = spec_data(8, n_items=6, n_sales=600, n_manufact=1)
    data.item_brand_id[:] = 3_002_001
    data.item_brand = np.asarray(["b", "a", "b", "a", "b", "a"])
    data.manufact_id = 1
    got = [tuple(r) for r in q3_local(data)]
    assert got == numpy_q3(data)
    assert {r[2] for r in got} == {"a", "b"}


def test_sums_past_32_bits_stay_exact():
    """Groups of about 920 rows at 29,999.99 a row: sums past 2**31 cents,
    and odd, so no 32-bit integer or float32 accumulator holds them."""
    data = spec_data(11, n_sales=2000, n_items=2, n_manufact=1)
    data.manufact_id = 1
    data.ss_sold_date_sk[:] = SOLD[0] + 303  # 1998-11-01
    data.ss_ext_sales_price[:] = 2_999_999
    want = numpy_q3(data)
    assert max(r[3] for r in want) > 2**31
    for path in sorted(PATHS):
        assert [tuple(r) for r in PATHS[path](data)] == want, path


@pytest.mark.parametrize("chips", [1, 2])
def test_segment_agg_counter_counts_the_kept_rows(chips):
    """One ``segment_agg`` flight event per q3 plan run: the padded rows
    the scatter ran over, on all chips, and the rows the filter kept."""
    import jax

    from spark_rapids_jni_tpu.obs import flight
    from spark_rapids_jni_tpu.parallel.mesh import make_mesh
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    data = spec_data(9)
    seq = max((e["seq"] for e in flight.snapshot()), default=0)
    run_distributed_q3(make_mesh((chips, 1), devices=jax.devices()[:chips]),
                       data)
    events = [e for e in flight.snapshot()
              if e["seq"] > seq and e["kind"] == flight.EV_SEGMENT_AGG]
    i = data.ss_item_sk.astype(np.int64) - 1
    d = data.ss_sold_date_sk.astype(np.int64) - DATE_SK0
    kept = int(np.count_nonzero(
        data.ss_item_sk_valid & data.ss_sold_date_sk_valid
        & (data.item_manufact_id[i] == 2) & (data.date_moy[d] == 11)))
    n = quantized_rows(len(data.ss_item_sk), chips)
    assert [e["detail"] for e in events] == [
        f"plan:q3:path:sorted:scattered:{n}:kept:{kept}"]
    assert events[0]["value"] == kept > 0


def test_q3_plan_scopes_name_the_join_filter_and_aggregate_ops():
    from spark_rapids_jni_tpu.models.q3 import (
        _dims,
        _facts,
        _geometry,
        _q3_tables,
        q3_plan,
    )
    from spark_rapids_jni_tpu.plans.compiler import (
        AGG_KEPT,
        AGG_ROWS,
        compile_plan,
    )
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    data = spec_data(3)
    plan = q3_plan(**_geometry(data))
    tables = _q3_tables(_facts(data), _dims(data))
    cp = compile_plan(plan, _one_device_mesh(),
                      input_signature_raw(plan, tables, 1))
    assert cp.aot and cp.out_names[-2:] == (AGG_KEPT, AGG_ROWS)
    names = re.findall(r'op_name="([^"]*)"', cp.fn.as_text())
    for scope in ("gather_join", "filter", "segment_agg"):
        assert any(scope in n.split("/") for n in names), scope


def _scope_opcodes(hlo: str, scope: str) -> list:
    """The HLO opcode of each instruction whose ``op_name`` holds
    ``scope`` as a path component."""
    ops = []
    for line in hlo.splitlines():
        name = re.search(r'op_name="([^"]*)"', line)
        op = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z][a-z0-9-]*)\(", line)
        if name and op and scope in name.group(1).split("/"):
            ops.append(op.group(1))
    return ops


@pytest.mark.parametrize("sums", ["int64", "float32"])
def test_q3_plan_sums_integers_by_sort_and_floats_by_scatter(sums):
    """The q3 plan's integer aggs take one sort and no scatter; a float32
    sum keeps its scatter beside the sorted count."""
    import dataclasses

    from spark_rapids_jni_tpu.models.q3 import (
        _dims,
        _facts,
        _geometry,
        _q3_tables,
        q3_plan,
    )
    from spark_rapids_jni_tpu.plans.compiler import agg_path, compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    data = spec_data(3)
    plan = q3_plan(**_geometry(data))
    (sink,) = plan.sinks
    plan = dataclasses.replace(plan, sinks=(dataclasses.replace(
        sink, aggs=tuple((n, e, sums if d == "int64" else d)
                         for n, e, d in sink.aggs)),))
    tables = _q3_tables(_facts(data), _dims(data))
    cp = compile_plan(plan, _one_device_mesh(),
                      input_signature_raw(plan, tables, 1))
    ops = _scope_opcodes(cp.fn.as_text(), "segment_agg")
    assert "sort" in ops
    if sums == "int64":
        assert agg_path(plan) == "sorted" and "scatter" not in ops
    else:
        assert agg_path(plan) == "mixed" and "scatter" in ops


# ------------------------------------------- one gather per dimension join --

def test_q3_plan_gathers_one_column_per_dimension_join():
    """The manufacturer and month tests, the brand code and the year
    offset are evaluated on the dimension tables: the program gathers one
    fact-length column per join, two in all (four when each dimension
    field was gathered)."""
    from spark_rapids_jni_tpu.models.q3 import (
        _dims,
        _facts,
        _geometry,
        _q3_tables,
        q3_plan,
    )
    from spark_rapids_jni_tpu.plans.compiler import compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    data = spec_data(3)
    plan = q3_plan(**_geometry(data))
    tables = _q3_tables(_facts(data), _dims(data))
    cp = compile_plan(plan, _one_device_mesh(),
                      input_signature_raw(plan, tables, 1))
    ops = _scope_opcodes(cp.fn.as_text(), "gather_join")
    assert ops.count("gather") == 2


def _no_manufacturer(data):
    data.manufact_id = int(data.item_manufact_id.max()) + 1


def _no_day_of_the_month(data):
    data.date_moy[data.date_moy == data.moy] = 12


def _all_keys_null(data):
    data.ss_item_sk_valid[:] = False
    data.ss_sold_date_sk_valid[:] = False


def _keys_at_the_dims_edges(data):
    """Every key the first or the last row of its dimension, every one of
    those rows qualifying: the kept rows fill the grid's first and last
    year and the first and last item's codes."""
    rng = np.random.default_rng(17)
    n = len(data.ss_item_sk)
    data.ss_item_sk[:] = rng.choice([1, len(data.item_sk)], n)
    data.ss_sold_date_sk[:] = rng.choice([DATE_SK0, DATE_SK0 + DATE_ROWS - 1],
                                         n)
    data.item_manufact_id[[0, -1]] = data.manufact_id
    data.date_moy[[0, -1]] = data.moy


@pytest.mark.parametrize("case", [
    _no_manufacturer, _no_day_of_the_month, _all_keys_null,
    _keys_at_the_dims_edges], ids=lambda f: f.__name__.lstrip("_"))
def test_dimension_side_fields_equal_the_per_op_grid(case):
    """The plan's grid, kept where the gathered brand code and year offset
    are not -1, equals the per-op body's, which gathers every dimension
    field and filters per fact row: sums and counts, slot for slot."""
    import jax.numpy as jnp

    from spark_rapids_jni_tpu.models.q3 import (
        _brand_codes,
        _dims,
        _facts,
        _geometry,
        _partials,
        _q3_tables,
        q3_plan,
    )
    from spark_rapids_jni_tpu.plans import execute_plan

    data = spec_data(21)
    case(data)
    codes = _brand_codes(data)
    geo = _geometry(data, codes)
    dims = _dims(data, codes)
    got = execute_plan(None, q3_plan(**geo),
                       _q3_tables(_facts(data), dims))
    want = _partials(*(jnp.asarray(v) for v in _facts(data).values()),
                     **{k: jnp.asarray(v) for k, v in dims.items()}, **geo)
    np.testing.assert_array_equal(got["sums"], np.asarray(want.sums))
    np.testing.assert_array_equal(got["counts"], np.asarray(want.counts))
    assert [tuple(r) for r in q3_local(data)] == numpy_q3(data) == [
        tuple(r) for r in q3_local_unfused(data)]
    counts = got["counts"].reshape(geo["n_years"], geo["n_brands"])
    if case is _keys_at_the_dims_edges:
        first, last = codes.item[[0, -1]] - 1
        assert counts[0, first] > 0 and counts[-1, last] > 0
        assert counts.sum() == len(data.ss_item_sk) - np.count_nonzero(
            ~data.ss_item_sk_valid | ~data.ss_sold_date_sk_valid)
    else:
        assert counts.sum() == 0
