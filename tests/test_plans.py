"""Plan compiler (plans/): fused pipelines vs the per-op oracles.

Round-6 acceptance coverage:

- fused-vs-unfused bit-parity for q3/q5/q97 across 3+ pow2 batch
  buckets (the plan cache's variant lattice);
- plan-cache hit/miss behavior across the lattice: same bucket = hit
  (zero retrace), new bucket = exactly one new trace;
- cache identity for the compiled distributed steps — same geometry can
  NEVER leak a fresh jit wrapper per call (the `_q5_step_cached`
  geometry-keying regression, now a structural property of plans.ir.lit
  normalization + the process-global plan cache);
- chaos: an injected RetryOOM mid-plan re-runs the WHOLE fused program
  (cache hit, no retrace), and SplitAndRetry halves re-execute the fused
  program and join to the unfused oracle result.
"""

import numpy as np
import pytest

import jax

from spark_rapids_jni_tpu.mem import BudgetedResource, MemoryGovernor, task_context
from spark_rapids_jni_tpu.models.q3 import q3_local, q3_local_unfused
from spark_rapids_jni_tpu.models.q5 import (
    make_distributed_q5,
    q5_local,
    q5_local_unfused,
    q5_plan,
    run_distributed_q5,
)
from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
from spark_rapids_jni_tpu.models import (
    generate_q3_data,
    generate_q5_data,
    run_distributed_q97,
)
from spark_rapids_jni_tpu.obs.faultinj import FaultInjector
from spark_rapids_jni_tpu.parallel import make_mesh
from spark_rapids_jni_tpu.parallel.shuffle import quantized_rows
from spark_rapids_jni_tpu.plans import execute_plan, ir, plan_cache

NDEV = 8


@pytest.fixture(autouse=True)
def _fresh_plan_cache():
    """Deterministic hit/miss counting per test (the cache is
    process-global by design)."""
    plan_cache.clear()
    plan_cache.reset_stats()
    yield


@pytest.fixture
def gov():
    g = MemoryGovernor(watchdog_period_s=0.02)
    yield g
    g.close()


def _mesh():
    return make_mesh((NDEV, 1), devices=jax.devices()[:NDEV])


# ------------------------------------------------------------ IR mechanics


def _toy_plan(num_segments=4):
    node = ir.Scan("t", ("k", "v"))
    node = ir.Filter(node, ir.Bin("ge", ir.col("v"), ir.lit(0)))
    sink = ir.SegmentAgg(node, key=ir.col("k"), num_segments=num_segments,
                         aggs=(("s", ir.col("v"), "int64"),
                               ("c", ir.lit(1), "int32")))
    return ir.Plan("toy", (sink,))


def _toy_tables(n, seed=0):
    rng = np.random.RandomState(seed)
    return {"t": {"k": rng.randint(0, 4, n).astype(np.int32),
                  "v": rng.randint(-5, 100, n).astype(np.int64)}}


def _toy_oracle(tables):
    k, v = tables["t"]["k"], tables["t"]["v"]
    ok = v >= 0
    s = np.bincount(k[ok], weights=v[ok], minlength=4).astype(np.int64)
    c = np.bincount(k[ok], minlength=4).astype(np.int32)
    return s, c


def test_plan_values_are_hashable_and_equal_by_structure():
    assert _toy_plan() == _toy_plan()
    assert hash(_toy_plan()) == hash(_toy_plan())
    assert _toy_plan(4) != _toy_plan(8)


def test_lit_normalizes_numpy_scalars():
    # the q5 geometry-keying fix as a structural property: numpy-int and
    # python-int geometry build EQUAL plans (one cache entry, never two)
    assert ir.lit(np.int64(7)) == ir.lit(7)
    assert q5_plan((np.int64(3), np.int32(4), 5), np.int64(10), 20) == \
        q5_plan((3, 4, 5), 10, 20)


def test_toy_plan_matches_numpy_oracle():
    tables = _toy_tables(100)
    out = execute_plan(None, _toy_plan(), tables)
    s, c = _toy_oracle(tables)
    np.testing.assert_array_equal(out["s"], s)
    np.testing.assert_array_equal(out["c"], c)


def test_plan_signature_deterministic_across_processes():
    # seam/flight labels must be pinnable across runs: the signature is a
    # content digest, never the salted python hash()
    import subprocess
    import sys

    from spark_rapids_jni_tpu.models.q97 import q97_plan

    sig = ir.plan_signature(q97_plan(64))
    code = ("from spark_rapids_jni_tpu.models.q97 import q97_plan; "
            "from spark_rapids_jni_tpu.plans import ir; "
            "print(ir.plan_signature(q97_plan(64)))")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True, timeout=120)
    assert out.stdout.strip() == sig


def test_exchange_plan_outputs_must_keep_dropped():
    # filtering 'dropped' out of an Exchange plan would silently disable
    # the ShuffleCapacityExceeded overflow guard
    from spark_rapids_jni_tpu.plans import output_names

    node = ir.Project(ir.Scan("t", ("k",)), (("key", ir.col("k")),))
    node = ir.Exchange(node, key=ir.col("key"), capacity=8,
                       fields=("key",))
    sink = ir.SegmentAgg(node, key=ir.lit(0), num_segments=1,
                         aggs=(("s", ir.lit(1), "int64"),))
    ok = ir.Plan("ex", (sink,), outputs=("s", "dropped"))
    assert output_names(ok) == ("s", "dropped")
    bad = ir.Plan("ex", (sink,), outputs=("s",))
    with pytest.raises(ValueError, match="dropped"):
        output_names(bad)


# --------------------------------------------------- cache across the lattice


def test_plan_cache_hit_miss_across_pow2_lattice():
    """Same pow2 bucket = cache hit (zero retrace); a new bucket = exactly
    one new trace.  Results stay exact at every length (pad rows are
    masked out by the implicit row-valid input)."""
    plan = _toy_plan()
    lengths = [100, 120, 128, 200, 512, 700]
    buckets = [quantized_rows(n, 1) for n in lengths]
    assert len(set(buckets)) == 4  # 128, 256, 512, 1024 -> 3+ buckets
    seen = set()
    for n, bucket in zip(lengths, buckets):
        before = plan_cache.stats()
        tables = _toy_tables(n, seed=n)
        out = execute_plan(None, plan, tables)
        s, c = _toy_oracle(tables)
        np.testing.assert_array_equal(out["s"], s)
        np.testing.assert_array_equal(out["c"], c)
        after = plan_cache.stats()
        if bucket in seen:
            assert after["traces"] == before["traces"], \
                f"length {n} (bucket {bucket}) retraced a cached variant"
            assert after["hits"] == before["hits"] + 1
        else:
            assert after["traces"] == before["traces"] + 1
            seen.add(bucket)
    assert plan_cache.stats()["entries"] == 4


def test_second_execution_zero_retrace():
    """Acceptance: a second same-shape execution is a cache hit with ZERO
    retrace (trace-count stability)."""
    data = generate_q3_data(sf=0.05, seed=42)
    first = q3_local(data)
    t0 = plan_cache.stats()["traces"]
    second = q3_local(data)
    stats = plan_cache.stats()
    assert stats["traces"] == t0, "same-shape re-execution must not retrace"
    assert stats["hits"] >= 1
    assert first == second


def test_raw_signature_matches_padded_signature():
    """The O(1) raw-tables signature (make_distributed_* cache lookups)
    must equal the padded-tables signature execute_plan keys on — both
    entry points MUST share one cache entry per geometry."""
    from spark_rapids_jni_tpu.plans import input_signature
    from spark_rapids_jni_tpu.plans.runtime import (
        input_signature_raw,
        pad_tables,
    )

    plan = _toy_plan()
    for n, dp in ((100, 1), (100, 8), (129, 8)):
        tables = _toy_tables(n, seed=n)
        raw = input_signature_raw(plan, tables, dp)
        padded = input_signature(plan, pad_tables(plan, tables, dp))
        assert raw == padded


def test_q3_admission_formulas_agree():
    """models.q3.q3_working_set_bytes (what budget-sizing tests use) and
    plans.runtime.plan_working_set_bytes (what the plan runner actually
    admits) must stay numerically equal for q3 — a drift would make the
    arbiter-contention preconditions in test_governed vacuous."""
    from spark_rapids_jni_tpu.models import generate_q3_data
    from spark_rapids_jni_tpu.models.q3 import (
        _dims,
        _facts,
        _geometry,
        _q3_tables,
        q3_plan,
        q3_working_set_bytes,
    )
    from spark_rapids_jni_tpu.plans.runtime import plan_working_set_bytes

    data = generate_q3_data(sf=0.05, seed=17)
    plan = q3_plan(**_geometry(data))
    tables = _q3_tables(_facts(data), _dims(data))
    for dp in (1, 8):
        assert plan_working_set_bytes(plan, tables, dp) == \
            q3_working_set_bytes(_facts(data), dp)


def test_compiled_step_identity_same_geometry():
    """make_distributed_q5 on same-geometry data returns the IDENTICAL
    compiled object — a fresh jit wrapper can never leak per call (the
    `_q5_step_cached` soak regression, ~3 MB RSS per leaked wrapper)."""
    data = generate_q5_data(sf=0.02, seed=5)
    mesh = _mesh()
    step1 = make_distributed_q5(mesh, data)
    entries = plan_cache.stats()["entries"]
    for _ in range(5):
        assert make_distributed_q5(mesh, data) is step1
    assert plan_cache.stats()["entries"] == entries


def test_cache_builds_dedup_per_key_without_global_stall():
    """A slow build of one key must neither start twice for concurrent
    same-key callers NOR block a different key's build or stats()."""
    import threading

    from spark_rapids_jni_tpu.plans.cache import CompiledPlan, PlanCache

    cache = PlanCache(maxsize=8)
    a_started = threading.Event()
    a_release = threading.Event()
    a_builds = []

    def build_a():
        a_builds.append(1)
        a_started.set()
        assert a_release.wait(timeout=30)
        return CompiledPlan(lambda: None, None, None, (), (), (),
                            False, 0.0, 0.0)

    def build_b():
        return CompiledPlan(lambda: None, None, None, (), (), (),
                            False, 0.0, 0.0)

    results = {}
    t1 = threading.Thread(
        target=lambda: results.update(a1=cache.get_or_compile("A", build_a)))
    t2 = threading.Thread(
        target=lambda: results.update(a2=cache.get_or_compile("A", build_a)))
    t1.start()
    assert a_started.wait(timeout=30)
    t2.start()  # same key: must wait for t1's build, not start a second
    # different key + stats() proceed while A's build is in flight
    results["b"] = cache.get_or_compile("B", build_b)
    assert cache.stats()["misses"] == 1  # B done; A still building
    a_release.set()
    t1.join(timeout=30)
    t2.join(timeout=30)
    assert len(a_builds) == 1, "same-key concurrent build must dedup"
    assert results["a1"] is results["a2"]
    s = cache.stats()
    assert s["misses"] == 2 and s["hits"] == 1  # t2's wait resolved as hit


def test_cache_failed_build_releases_waiters():
    import threading

    from spark_rapids_jni_tpu.plans.cache import CompiledPlan, PlanCache

    cache = PlanCache(maxsize=8)
    calls = []

    def failing_then_ok():
        calls.append(1)
        if len(calls) == 1:
            raise RuntimeError("injected compile fault")
        return CompiledPlan(lambda: None, None, None, (), (), (),
                            False, 0.0, 0.0)

    with pytest.raises(RuntimeError):
        cache.get_or_compile("K", failing_then_ok)
    # a failed build leaves no wedged in-flight marker: the next caller
    # claims the build and succeeds
    assert cache.get_or_compile("K", failing_then_ok) is not None
    assert len(calls) == 2


def test_governed_plan_dims_uploaded_once(gov):
    """run_governed_plan hoists dim uploads out of the retry bracket:
    pad_tables passes already-device dim arrays through untouched."""
    import jax

    from spark_rapids_jni_tpu.plans.runtime import _upload_dims, pad_tables

    from spark_rapids_jni_tpu.models.q3 import (
        _dims,
        _facts,
        _geometry,
        q3_plan,
        _q3_tables,
    )
    from spark_rapids_jni_tpu.models import generate_q3_data

    data = generate_q3_data(sf=0.02, seed=13)
    plan = q3_plan(**_geometry(data))
    tables = _q3_tables(_facts(data), _dims(data))
    up = _upload_dims(plan, tables, None)
    assert isinstance(up["item"]["brand"], jax.Array)
    padded = pad_tables(plan, up, 1)
    assert padded["item"]["brand"] is up["item"]["brand"]


# ------------------------------------------------- fused vs unfused parity


@pytest.mark.parametrize("sf", [0.01, 0.05, 0.2])
def test_q3_fused_matches_unfused(sf):
    data = generate_q3_data(sf=sf, seed=11)
    assert q3_local(data) == q3_local_unfused(data)


@pytest.mark.parametrize("sf", [0.01, 0.05, 0.2])
def test_q5_fused_matches_unfused(sf):
    data = generate_q5_data(sf=sf, seed=12)
    assert [tuple(r) for r in q5_local(data)] == \
        [tuple(r) for r in q5_local_unfused(data)]


def test_parity_buckets_actually_distinct():
    # the sf ladder above must span 3+ pow2 batch buckets, or the
    # "parity at 3+ buckets" claim is vacuous
    q3_buckets = set()
    q5_buckets = set()
    for sf in (0.01, 0.05, 0.2):
        d3 = generate_q3_data(sf=sf, seed=11)
        q3_buckets.add(quantized_rows(len(d3.ss_item_sk), 1))
        d5 = generate_q5_data(sf=sf, seed=12)
        q5_buckets.add(quantized_rows(
            len(d5.channels["store"].sales_sk), 1))
    assert len(q3_buckets) >= 3
    assert len(q5_buckets) >= 3


def _q97_tables(seed, n):
    rng = np.random.RandomState(seed)
    return ((rng.randint(1, 40, n).astype(np.int32),
             rng.randint(1, 12, n).astype(np.int32)),
            (rng.randint(1, 40, max(1, n - n // 4)).astype(np.int32),
             rng.randint(1, 12, max(1, n - n // 4)).astype(np.int32)))


@pytest.mark.parametrize("n", [120, 600, 2500])
def test_q97_fused_matches_unfused(gov, n):
    # three sizes -> three pow2 buckets of the fused (Exchange-bearing)
    # q97 plan; fused counts must equal the host oracle bit for bit
    store, catalog = _q97_tables(seed=n, n=n)
    budget = BudgetedResource(gov, 1 << 30)
    out = run_distributed_q97(_mesh(), store, catalog, budget=budget,
                              task_id=1)
    got = (int(out.store_only), int(out.catalog_only), int(out.both))
    assert got == q97_host_oracle(store, catalog)


# ------------------------------------------------------------------- chaos


def test_retry_oom_mid_plan_reruns_whole_fused_program(gov):
    """An injected RetryOOM mid-plan (at the fused upload seam) drives
    the plan-granularity retry: the WHOLE fused program re-runs — as a
    cache hit, zero retrace — and the answer matches the unfused
    oracle."""
    data = generate_q5_data(sf=0.05, seed=8)
    budget = BudgetedResource(gov, 1 << 30)
    FaultInjector.install({
        "transfer": {"plan_upload:q5": {"injectionType": "retry_oom",
                                        "interceptionCount": 1}},
    })
    try:
        got = [tuple(r) for r in
               run_distributed_q5(_mesh(), data, budget=budget, task_id=2)]
    finally:
        FaultInjector.uninstall()
    assert got == [tuple(r) for r in q5_local_unfused(data)]
    stats = plan_cache.stats()
    assert stats["traces"] == 1, \
        "the retry must re-execute the cached fused program, not retrace"
    assert stats["hits"] >= 1  # the re-run hit the cache
    assert budget.used == 0, "retry path must not leak reservations"


def test_split_and_retry_halves_join_to_unfused_oracle(gov):
    """Tight budget: SplitAndRetry halves every scan table and re-executes
    the FUSED program per half (never a per-op disband); the joined
    partials match the unfused oracle exactly."""
    data = generate_q5_data(sf=0.05, seed=9)
    from spark_rapids_jni_tpu.models.tpcds import CHANNELS

    total = sum(v.nbytes for n in CHANNELS
                for v in vars(data.channels[n]).values()
                if isinstance(v, np.ndarray))
    budget = BudgetedResource(gov, int(total * 1.2))
    with task_context(gov, 3):
        got = [tuple(r) for r in
               run_distributed_q5(_mesh(), data, budget=budget, task_id=3,
                                  manage_task=False)]
        splits = gov.get_and_reset_num_split_retry(3)
    assert splits >= 1
    assert got == [tuple(r) for r in q5_local_unfused(data)]
    # every (re-)execution went through the fused plan: each distinct
    # half-geometry is one trace, and execution count covers the halves
    stats = plan_cache.stats()
    assert stats["execute_calls"] >= 2
    assert stats["traces"] <= stats["execute_calls"]


# ---------------------------------------------------- GatherJoin fields

_DIM_BASE = 5


def _gather_tables(n=300, n_dim=40, seed=0):
    """Fact keys over the dimension and two rows past each end (the join
    clips them), an int32, an int64 past 32 bits and a flag per dim row."""
    rng = np.random.default_rng(seed)
    return {
        "f": {"rid": np.arange(n, dtype=np.int32),
              "k": rng.integers(_DIM_BASE - 2, _DIM_BASE + n_dim + 2, n,
                                dtype=np.int32)},
        "d": {"a": rng.integers(-1000, 1000, n_dim, dtype=np.int32),
              "w": rng.integers(-2**40, 2**40, n_dim, dtype=np.int64),
              "flag": rng.integers(0, 3, n_dim, dtype=np.int32)},
    }


#: (out_name, expression over the dim's columns): int32, int64, and an
#: int32 gated by a predicate, -1 where it fails
_DIM_EXPRS = (
    ("x", ir.Bin("add", ir.Bin("mul", ir.col("a"), ir.lit(3)), ir.lit(-7))),
    ("y", ir.Bin("sub", ir.col("w"), ir.Cast(ir.col("a"), "int64"))),
    ("z", ir.Bin("sub", ir.Bin("mul", ir.Cast(ir.Bin(
        "eq", ir.col("flag"), ir.lit(1)), "int32"), ir.Bin(
        "add", ir.col("a"), ir.lit(1))), ir.lit(1))),
)


def _rows_plan(node, name):
    return ir.Plan(name, (ir.Sort(node, keys=((ir.col("rid"), True),),
                                  fields=("rid", "x", "y", "z")),))


def test_expression_field_equals_the_expression_after_a_plain_gather():
    """A GatherJoin field that is an expression over the dim's columns
    gathers, row for row and bit for bit, what a plain gather of those
    columns followed by the same expression on the fact side gives."""
    scan = ir.Scan("f", ("rid", "k"))
    dim = ir.Dim("d", ("a", "w", "flag"))
    dim_side = ir.GatherJoin(scan, dim, ir.col("k"), ir.lit(_DIM_BASE),
                             tuple((e, out) for out, e in _DIM_EXPRS))
    fact_side = ir.Project(
        ir.GatherJoin(scan, dim, ir.col("k"), ir.lit(_DIM_BASE),
                      tuple((f, f) for f in dim.fields)), _DIM_EXPRS)
    tables = _gather_tables()
    got = execute_plan(None, _rows_plan(dim_side, "dim_side"), tables)
    want = execute_plan(None, _rows_plan(fact_side, "fact_side"), tables)
    assert [got[f].dtype for f in ("x", "y", "z")] == [
        np.int32, np.int64, np.int32]
    for f in ("rid", "x", "y", "z", "rows"):
        np.testing.assert_array_equal(got[f], want[f])
    assert np.any(got["z"] == -1) and np.any(got["z"] != -1)
    assert np.any(np.abs(got["y"]) > 2**32)


def _program_text(text: str) -> str:
    """A compiled program's text without its source locations."""
    import re

    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    return "\n".join(
        line for line in text.split("\n\n", 1)[-1].splitlines()
        if not re.match(r"^(FileNames|FunctionNames|FileLocations|"
                        r"StackFrames|\d+ )", line))


def _q64_map_plan():
    """q64's map side, its two name-field GatherJoins, projection and
    filter, under a local Sort sink."""
    from spark_rapids_jni_tpu.models.q64 import q64_plan

    (ex,) = ir.range_exchange_nodes(q64_plan(3, 30, 25, 2))
    return ir.Plan("q64_map", (ir.Sort(ex.child, keys=ex.keys,
                                       fields=ex.fields),))


#: sha256 of the q64 map plan compiled locally at 1,000 rows, source
#: locations left out: name fields gather as they did before a GatherJoin
#: field could be an expression
Q64_MAP_PROGRAM = ("a81f2cc448a2815d628a2c6ff46a3435"
                   "50c2b11e362e3d3f972b5781372a84fd")


def test_name_field_joins_compile_to_the_same_program():
    import hashlib

    from spark_rapids_jni_tpu.models.q64 import make_q64_tables
    from spark_rapids_jni_tpu.plans.compiler import compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    plan = _q64_map_plan()
    tables = make_q64_tables(1000, 30, 25, seed=1)
    cp = compile_plan(plan, None, input_signature_raw(plan, tables, 1))
    text = _program_text(cp.fn.as_text())
    assert hashlib.sha256(text.encode()).hexdigest() == Q64_MAP_PROGRAM


def _run_q3():
    q3_local(generate_q3_data(sf=0.01, seed=3))


def _run_q64_map():
    from spark_rapids_jni_tpu.models.q64 import make_q64_tables

    execute_plan(None, _q64_map_plan(), make_q64_tables(500, 30, 25, seed=2))


def _run_toy():
    execute_plan(None, _toy_plan(), _toy_tables(100))


@pytest.mark.parametrize("run, details", [
    (_run_q3, ["plan:q3:gathers:2:dim_side:2"]),
    (_run_q64_map, ["plan:q64_map:gathers:3:dim_side:0"]),
    (_run_toy, [])], ids=["q3", "q64_map", "no_joins"])
def test_gather_join_counter_counts_the_gathered_columns(run, details):
    """One ``gather_join`` flight event per run of a plan with
    GatherJoins: its fact-length gathered columns, and how many of them
    are expressions evaluated on the dimension table."""
    from spark_rapids_jni_tpu.obs import flight

    seq = max((e["seq"] for e in flight.snapshot()), default=0)
    run()
    events = [e for e in flight.snapshot()
              if e["seq"] > seq and e["kind"] == flight.EV_GATHER_JOIN]
    assert [e["detail"] for e in events] == details
    assert [e["value"] for e in events] == [
        int(d.split(":")[3]) for d in details]
