"""NDS q3: star join (store_sales x item x date_dim) + grouped aggregation.

    select d_year, i_brand_id, i_brand, sum(ss_ext_sales_price)
    from date_dim, store_sales, item
    where d_date_sk = ss_sold_date_sk and ss_item_sk = i_item_sk
      and i_manufact_id = M and d_moy = 11
    group by d_year, i_brand_id, i_brand
    order by d_year, sum_agg desc, i_brand_id

Third query pattern in the models family (q97 = shuffle join-count, q5 =
broadcast rollup): a selective dimension FILTER pushed through two dense
dimension joins into one grouped money aggregation.  TPU shape: both
dimensions are dense surrogate-keyed, so each join is a replicated-table
gather; the group key (d_year, i_brand_id) lives in a small dense product
space, so the aggregation is one masked segment-sum into a
[n_years * n_brands] grid and the distributed form psums that grid over
the data axis — no row exchange, same as q5's partials.

Money stays unscaled int64 cents (decimal scale 2) end to end; brand
STRINGS materialize only in the host-formatted result rows.

Since round 6 the int64 path is ONE compiled plan (:func:`q3_plan`,
plans/ir.py): both gathers, the filter and the grouped segment-sum trace
into a single jitted program cached on (plan structure, dtype signature,
pow2 batch bucket), and the governed runner admits the whole plan as one
working set (SplitAndRetryOOM re-executes the fused program on fact
halves — exact, sums/counts are additive).  The pre-plan eager per-op
path survives as :func:`q3_local_unfused`, the bit-parity oracle
tests/test_plans.py pins the fused program against.  The decimal-columns
variant keeps its own fused step (Column pytrees are outside the scalar
plan IR).
"""

from __future__ import annotations

from typing import List, NamedTuple

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from spark_rapids_jni_tpu.models.tpcds import Q3Data
from spark_rapids_jni_tpu.parallel.mesh import DATA_AXIS
from spark_rapids_jni_tpu.plans import ir
from spark_rapids_jni_tpu.plans.ir import Bin, Cast, band_all, col, lit

__all__ = ["Q3Row", "q3_local", "q3_local_unfused", "q3_plan",
           "make_distributed_q3", "run_distributed_q3",
           "run_distributed_q3_columns", "q3_columns_host_oracle",
           "q3_working_set_bytes"]


class Q3Row(NamedTuple):
    d_year: int
    brand_id: int
    brand: str
    sum_agg: int  # cents


class _Partials(NamedTuple):
    sums: jnp.ndarray  # [n_years * n_brands] int64 cents
    counts: jnp.ndarray  # [n_years * n_brands] int32


def _partials(ss_item, ss_item_v, ss_date, ss_date_v, price,
              item_brand, item_manufact, date_year, date_moy,
              *, n_brands: int, year0: int, n_years: int,
              date_sk0: int, manufact_id: int, moy: int) -> _Partials:
    """Device body over [rows] facts; dims are replicated dense tables."""
    i_idx = jnp.clip(ss_item - 1, 0, item_brand.shape[0] - 1)
    d_idx = jnp.clip(ss_date - date_sk0, 0, date_year.shape[0] - 1)
    ok = (
        ss_item_v & ss_date_v
        & (item_manufact[i_idx] == manufact_id)
        & (date_moy[d_idx] == moy)
    )
    brand = item_brand[i_idx].astype(jnp.int32)  # 1-based
    year_off = (date_year[d_idx] - year0).astype(jnp.int32)
    group = jnp.clip(year_off, 0, n_years - 1) * n_brands + (brand - 1)
    ngroups = n_years * n_brands
    # analyze: ignore[governed-allocation] - per-op ORACLE path: since the
    # plan port this body runs only eagerly under q3_local_unfused, the
    # bit-parity reference the fused (governed) program is checked against
    # in tests; group-grid partials are tiny and test-scoped by design
    sums = jnp.zeros((ngroups,), jnp.int64).at[group].add(
        jnp.where(ok, price, 0), mode="drop")
    # analyze: ignore[governed-allocation] - same oracle-path rationale
    counts = jnp.zeros((ngroups,), jnp.int32).at[group].add(
        jnp.where(ok, 1, 0), mode="drop")
    return _Partials(sums, counts)


def _assemble_rows(counts: np.ndarray, sum_of, year0: int, n_brands: int,
                   render_brands) -> List[Q3Row]:
    """Shared result assembly: drop empty groups, decode the group grid
    (year = year0 + g//n_brands, brand = g%n_brands + 1), attach brand
    names via ``render_brands(zero_based_idx_array)``, order by
    (d_year, sum desc, brand_id) — ONE owner of the grid layout and sort
    rule for both the int64 and the decimal-columns variants."""
    groups = np.nonzero(counts)[0]
    names = render_brands((groups % n_brands).astype(np.int32))
    rows = [
        Q3Row(year0 + int(g) // n_brands, int(g) % n_brands + 1,
              name, sum_of(int(g)))
        for g, name in zip(groups, names)
    ]
    rows.sort(key=lambda r: (r.d_year, -r.sum_agg, r.brand_id))
    return rows


def _format(parts: _Partials, data: Q3Data, year0: int) -> List[Q3Row]:
    """Host: int64-partials formatting (host-list brand lookup)."""
    sums = np.asarray(parts.sums)
    return _assemble_rows(
        np.asarray(parts.counts), lambda g: int(sums[g]), year0,
        len(data.brand_names),
        lambda idx: [data.brand_names[i] for i in idx])


def _geometry(data: Q3Data):
    year0 = int(data.date_year.min())
    n_years = int(data.date_year.max()) - year0 + 1
    return dict(
        n_brands=len(data.brand_names), year0=year0, n_years=n_years,
        date_sk0=int(data.date_sk[0]), manufact_id=data.manufact_id,
        moy=data.moy,
    )


def _facts(data: Q3Data) -> dict:
    return dict(
        ss_item=data.ss_item_sk, ss_item_v=data.ss_item_sk_valid,
        ss_date=data.ss_sold_date_sk, ss_date_v=data.ss_sold_date_sk_valid,
        price=data.ss_ext_sales_price,
    )


# ------------------------------------------------------------------ the plan


@functools.lru_cache(maxsize=64)
def q3_plan(*, n_brands: int, year0: int, n_years: int, date_sk0: int,
            manufact_id: int, moy: int) -> ir.Plan:
    """The whole q3 device pipeline as ONE plan: scan -> item gather ->
    date gather -> manufact/moy filter -> grouped segment-sum into the
    dense [n_years * n_brands] grid.  Geometry scalars normalize through
    ``plans.ir.lit`` so equal geometry always builds an EQUAL plan (one
    cache entry on the process-global plan cache).  Memoized per
    geometry: the per-request hot path must not rebuild (and re-hash)
    the plan tree every call."""
    item = ir.Dim("item", ("brand", "manufact"))
    date = ir.Dim("date_dim", ("year", "moy"))
    node: ir.Node = ir.Scan(
        "store_sales", ("ss_item", "ss_item_v", "ss_date", "ss_date_v",
                        "price"))
    node = ir.GatherJoin(node, item, key=col("ss_item"), base=lit(1),
                         fields=(("brand", "brand"),
                                 ("manufact", "manufact")))
    node = ir.GatherJoin(node, date, key=col("ss_date"), base=lit(date_sk0),
                         fields=(("year", "year"), ("moy", "moy")))
    node = ir.Filter(node, band_all(
        col("ss_item_v"), col("ss_date_v"),
        Bin("eq", col("manufact"), lit(manufact_id)),
        Bin("eq", col("moy"), lit(moy)),
    ))
    # group = clip(year - year0, 0, n_years-1) * n_brands + (brand - 1),
    # exactly the per-op body's grid arithmetic (brand is 1-based)
    year_off = Cast(Bin("sub", col("year"), lit(year0)), "int32")
    clipped = Bin("min", Bin("max", year_off, lit(0)), lit(n_years - 1))
    group = Bin("add", Bin("mul", clipped, lit(n_brands)),
                Bin("sub", Cast(col("brand"), "int32"), lit(1)))
    node = ir.Project(node, (("group", group),))
    sink = ir.SegmentAgg(
        node, key=col("group"), num_segments=n_years * n_brands,
        aggs=(("sums", col("price"), "int64"),
              ("counts", lit(1), "int32")))
    return ir.Plan("q3", (sink,))


def _q3_tables(facts: dict, dims: dict) -> dict:
    """The plan's input tables from the fact/dim array dicts."""
    return {
        "store_sales": dict(facts),
        "item": {"brand": dims["item_brand"],
                 "manufact": dims["item_manufact"]},
        "date_dim": {"year": dims["date_year"], "moy": dims["date_moy"]},
    }


def _dims(data: Q3Data) -> dict:
    # raw numpy: q3_local's jnp ops take them directly, and
    # run_distributed_q3 device_puts them with a replicated sharding
    # (no device->host->device round-trip)
    return dict(
        item_brand=data.item_brand_id,
        item_manufact=data.item_manufact_id,
        date_year=data.date_year,
        date_moy=data.date_moy,
    )


def q3_local_unfused(data: Q3Data) -> List[Q3Row]:
    """Per-op eager q3 (the pre-plan shape): one device dispatch per op.
    The plan path's bit-parity oracle."""
    geo = _geometry(data)
    parts = _partials(
        *(jnp.asarray(v) for v in _facts(data).values()),
        **{k: jnp.asarray(v) for k, v in _dims(data).items()}, **geo)
    return _format(parts, data, geo["year0"])


def q3_local(data: Q3Data) -> List[Q3Row]:
    """Single-chip q3 through the compiled plan: gathers, filter and
    grouped sum are ONE jitted program (cached across calls on the pow2
    bucket lattice), then host formatting."""
    from spark_rapids_jni_tpu.plans.runtime import execute_plan

    geo = _geometry(data)
    plan = q3_plan(**geo)
    outputs = execute_plan(None, plan, _q3_tables(_facts(data), _dims(data)))
    return _format(_Partials(outputs["sums"], outputs["counts"]),
                   data, geo["year0"])


def make_distributed_q3(mesh, data: Q3Data):
    """Compiled distributed q3 plan over ``mesh``'s data axis.

    Returns the :class:`plans.cache.CompiledPlan` for ``data``'s geometry
    and batch bucket — facts sharded over DATA_AXIS, dims replicated,
    the group grid psum'd.  Same-geometry data returns the IDENTICAL
    cached object (plan-cache identity, replacing the per-module lru
    step cache) with O(1) host work on a hit — the key derives from
    lengths and dtypes, never a padded dataset copy."""
    from spark_rapids_jni_tpu.plans.runtime import compiled_plan_for

    plan = q3_plan(**_geometry(data))
    return compiled_plan_for(plan, mesh, _q3_tables(_facts(data),
                                                    _dims(data)))


def _pad_facts(facts: dict, dp: int) -> dict:
    """dp-aligned pow2-quantized padding (bounded compile variants);
    pad rows carry False validity."""
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    n = len(facts["ss_item"])
    pad = quantized_rows(n, dp) - n
    if pad == 0:
        return facts
    out = {k: np.concatenate([v, np.zeros(pad, v.dtype)])
           for k, v in facts.items()}
    out["ss_item_v"][-pad:] = False
    out["ss_date_v"][-pad:] = False
    return out


def q3_working_set_bytes(facts_or_data, dp: int = 1) -> int:
    """Reserved bytes for one governed q3 attempt over the given facts
    (inputs + masks/buckets + partials headroom): the admission size for
    the decimal-columns runner, and what tests size budgets from.  The
    plan-compiled runner admits via ``plans.runtime
    .plan_working_set_bytes``, which applies the SAME quantized-bytes x3
    margin to the plan's scan tables — numerically equal here, pinned by
    test_plans.test_q3_admission_formulas_agree so budget-sizing tests
    can't silently desynchronize from the runner's real admission.  With
    ``dp``, row counts are the quantized (padded) lengths run() actually
    uploads."""
    from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows

    facts = (facts_or_data if isinstance(facts_or_data, dict)
             else _facts(facts_or_data))
    return sum(quantized_rows(len(v), dp) * v.itemsize
               for v in facts.values()) * 3


def _split_facts(facts: dict):
    n = len(facts["ss_item"])
    return [{k: v[:n // 2] for k, v in facts.items()},
            {k: v[n // 2:] for k, v in facts.items()}]


def run_distributed_q3(mesh, data: Q3Data, *, budget=None, task_id: int = 0,
                       manage_task: bool = True) -> List[Q3Row]:
    """Governed distributed q3 through the compiled plan: ONE admission
    for the fused working set, RetryOOM re-runs the fused program,
    SplitAndRetryOOM halves fact rows and re-executes the fused program
    per half (exact: sums/counts are additive), one flight-recorder task
    spans the plan."""
    from spark_rapids_jni_tpu.plans.runtime import run_governed_plan

    geo = _geometry(data)
    plan = q3_plan(**geo)
    outputs = run_governed_plan(
        mesh, plan, _q3_tables(_facts(data), _dims(data)),
        budget=budget, task_id=task_id, manage_task=manage_task,
    )
    return _format(_Partials(outputs["sums"], outputs["counts"]),
                   data, geo["year0"])


# ----------------------------------------------------------- columns variant
# The real TPC-DS q3 selects i_brand (a STRING) and sums a DECIMAL money
# column.  This variant puts both through the flagship governed distributed
# path: ss_ext_sales_price flows as a Decimal128Column whose per-group SUM
# is accumulated in 128-bit limb arithmetic on device — exact mod 2^128,
# i.e. for every total that fits int128 (reference decimal_utils.cu:32
# chunked math; here the unsigned low limb is decomposed into 32-bit-safe
# segment sums recombined after the psum, while the top limb accumulates
# with ordinary wrapping int64 adds, which ARE mod-2^64 adds and therefore
# modularly correct for the high limb at any magnitude).  The brand
# dimension is a device StringColumn whose result rows are RENDERED through
# the string machinery (padded gather + strings_from_padded), not a host
# list lookup.


class _DecPartials(NamedTuple):
    hi: jnp.ndarray  # int64[n_groups] high limb of the decimal sum
    lo: jnp.ndarray  # uint64[n_groups] low limb
    counts: jnp.ndarray  # int32[n_groups]


def _dec_partials(ss_item, ss_date, price, item_brand, item_manufact,
                  date_year, date_moy, *, n_brands: int, year0: int,
                  n_years: int, date_sk0: int, manufact_id: int,
                  moy: int) -> _DecPartials:
    """Device body: 128-bit grouped money sum over nullable Columns.

    The low limb is decomposed into 32-bit halves so its carries are
    recoverable (segment sums stay int64-exact for any batch under 2^31
    rows); halves recombine into (hi, lo) AFTER the cross-device psum
    (the psum is linear in the decomposed sums).  The HIGH limb needs no
    decomposition: it is the top limb, so a wrapping int64 accumulation
    is exactly the required mod-2^64 arithmetic — intermediate wraps
    cannot corrupt a total that fits int128.
    """
    i_idx = jnp.clip(ss_item.data - 1, 0, item_brand.shape[0] - 1)
    d_idx = jnp.clip(ss_date.data - date_sk0, 0, date_year.shape[0] - 1)
    ok = (
        ss_item.is_valid() & ss_date.is_valid() & price.is_valid()
        & (item_manufact[i_idx] == manufact_id)
        & (date_moy[d_idx] == moy)
    )
    brand = item_brand[i_idx].astype(jnp.int32)
    year_off = (date_year[d_idx] - year0).astype(jnp.int32)
    group = jnp.clip(year_off, 0, n_years - 1) * n_brands + (brand - 1)
    ngroups = n_years * n_brands

    lo0 = (price.lo & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64)
    lo1 = (price.lo >> jnp.uint64(32)).astype(jnp.int64)

    def seg(values, dtype=jnp.int64):
        return jnp.zeros((ngroups,), dtype).at[group].add(
            jnp.where(ok, values, 0), mode="drop")

    s0 = jax.lax.psum(seg(lo0), (DATA_AXIS,))
    s1 = jax.lax.psum(seg(lo1), (DATA_AXIS,))
    sh = jax.lax.psum(seg(price.hi), (DATA_AXIS,))
    counts = jax.lax.psum(seg(1, jnp.int32), (DATA_AXIS,))

    # recombine: total = sh*2^64 + s1*2^32 + s0 (mod 2^128), s0/s1 >= 0
    u = s1 + (s0 >> 32)
    lo = ((u.astype(jnp.uint64) & jnp.uint64(0xFFFFFFFF))
          << jnp.uint64(32)) | (s0.astype(jnp.uint64)
                                & jnp.uint64(0xFFFFFFFF))
    hi = sh + (u >> 32)
    return _DecPartials(hi, lo, counts)


@functools.lru_cache(maxsize=32)
def _q3_columns_step_cached(mesh, geo_items: tuple):
    from spark_rapids_jni_tpu.obs.seam import COMPILE, seam

    geo = dict(geo_items)
    with seam(COMPILE, "q3_columns_step"):
        def body(ss_item, ss_date, price, item_brand, item_manufact,
                 date_year, date_moy):
            return _dec_partials(ss_item, ss_date, price, item_brand,
                                 item_manufact, date_year, date_moy, **geo)

        step = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(DATA_AXIS),) * 3 + (P(),) * 4,
            out_specs=_DecPartials(P(), P(), P()),
            check_vma=False,
        )
        return jax.jit(step)


def _price_limbs(price: np.ndarray):
    """int64 cents -> two's-complement (hi, lo) 64-bit limb arrays."""
    lo = price.astype(np.int64).view(np.uint64)
    hi = np.where(price < 0, np.int64(-1), np.int64(0))
    return hi, lo


def q3_columns_host_oracle(data: Q3Data) -> List[Q3Row]:
    """Arbitrary-precision host oracle (python ints — exact at magnitudes
    where the int64 oracle in q3_local would overflow)."""
    geo = _geometry(data)
    sums: dict = {}
    counts: dict = {}
    for i in range(len(data.ss_item_sk)):
        if not (data.ss_item_sk_valid[i] and data.ss_sold_date_sk_valid[i]):
            continue
        isk = int(data.ss_item_sk[i])
        dsk = int(data.ss_sold_date_sk[i]) - geo["date_sk0"]
        if not (1 <= isk <= len(data.item_sk)) or \
                not (0 <= dsk < len(data.date_year)):
            continue
        if int(data.item_manufact_id[isk - 1]) != geo["manufact_id"]:
            continue
        if int(data.date_moy[dsk]) != geo["moy"]:
            continue
        key = (int(data.date_year[dsk]), int(data.item_brand_id[isk - 1]))
        sums[key] = sums.get(key, 0) + int(data.ss_ext_sales_price[i])
        counts[key] = counts.get(key, 0) + 1
    rows = [Q3Row(y, b, data.brand_names[b - 1], s)
            for (y, b), s in sums.items()]
    rows.sort(key=lambda r: (r.d_year, -r.sum_agg, r.brand_id))
    return rows


def run_distributed_q3_columns(mesh, data: Q3Data, *, budget=None,
                               task_id: int = 0,
                               manage_task: bool = True) -> List[Q3Row]:
    """Governed distributed q3 with Decimal128Column money and a
    StringColumn brand dimension.

    Same protocol as :func:`run_distributed_q3` (admission, RetryOOM,
    row-split SplitAndRetryOOM) but per-group sums are exact for every
    total that fits int128 — far beyond the int64 path's range (128-bit
    limbs on device; combine in python ints) — and the result brand
    strings are gathered from the device StringColumn via the padded-view
    machinery.
    """
    import contextlib

    from spark_rapids_jni_tpu.columnar.column import (
        Column,
        Decimal128Column,
        strings_column,
        strings_from_padded,
    )
    from spark_rapids_jni_tpu.columnar.dtypes import INT32, decimal
    from spark_rapids_jni_tpu.mem.governed import (
        default_device_budget,
        run_with_split_retry,
        task_context,
    )

    from jax.sharding import NamedSharding

    geo = _geometry(data)
    dp = mesh.shape[DATA_AXIS]
    step = _q3_columns_step_cached(mesh, tuple(sorted(geo.items())))
    sharding = NamedSharding(mesh, P(DATA_AXIS))
    rep = NamedSharding(mesh, P())
    # analyze: ignore[governed-allocation] - shared replicated dim tables,
    # as in run_distributed_q3 above
    dims = {k: jax.device_put(v, rep) for k, v in _dims(data).items()}
    brands = strings_column(data.brand_names)  # the STRING dimension

    hi0, lo0 = _price_limbs(data.ss_ext_sales_price)
    facts = dict(
        ss_item=data.ss_item_sk, ss_item_v=data.ss_item_sk_valid,
        ss_date=data.ss_sold_date_sk, ss_date_v=data.ss_sold_date_sk_valid,
        price_hi=hi0, price_lo=lo0,
    )

    def nbytes_of(f):
        return q3_working_set_bytes(f, dp)

    def run(f):
        from spark_rapids_jni_tpu.obs.seam import COLLECTIVE, TRANSFER, seam

        padded = _pad_facts(f, dp)
        with seam(TRANSFER, "q3_columns_batch_upload"):
            put = lambda v: jax.device_put(  # noqa: E731
                np.ascontiguousarray(v), sharding)
            ss_item = Column(put(padded["ss_item"]),
                             put(padded["ss_item_v"]), INT32)
            ss_date = Column(put(padded["ss_date"]),
                             put(padded["ss_date_v"]), INT32)
            price = Decimal128Column(
                put(padded["price_hi"]), put(padded["price_lo"]),
                None, decimal(38, 2))
        with seam(COLLECTIVE, "launch:q3_columns_step"):
            out = step(ss_item, ss_date, price, *dims.values())
            jax.block_until_ready(out)
        hi = np.asarray(out.hi)
        lo = np.asarray(out.lo)
        sums = [int(h) * (1 << 64) + int(x)
                for h, x in zip(hi.astype(np.int64), lo.astype(np.uint64))]
        return sums, np.asarray(out.counts)

    def combine(results):
        sums = [sum(r[0][g] for r in results)
                for g in range(len(results[0][0]))]
        counts = sum(r[1] for r in results)
        return sums, counts

    budget = budget if budget is not None else default_device_budget()
    ctx = (task_context(budget.gov, task_id) if manage_task
           else contextlib.nullcontext())
    with ctx:
        sums, counts = run_with_split_retry(
            budget, facts, nbytes_of=nbytes_of, run=run,
            split=_split_facts, combine=combine)

    # result assembly shares _assemble_rows; brand strings are RENDERED
    # from the device StringColumn.  The gather length is pow2-quantized
    # (pad rows gather row 0, sliced off after) so a long-lived executor
    # sees a bounded shape-variant set, not one cached executable per
    # distinct non-empty-group count.
    from spark_rapids_jni_tpu.columnar.column import next_pow2

    def render_brands(idx: np.ndarray):
        n_sel = len(idx)
        sel_np = np.zeros(next_pow2(max(n_sel, 1)), np.int32)
        sel_np[:n_sel] = idx
        padded, lens = brands.padded()
        sel = jnp.asarray(sel_np)
        return strings_from_padded(
            padded[sel], lens[sel]).to_list()[:n_sel]

    return _assemble_rows(counts, lambda g: sums[g], geo["year0"],
                          len(data.brand_names), render_brands)
