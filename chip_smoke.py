"""Bring-up smoke of the main path on the TPU, in one process, no fallback.

    python chip_smoke.py             # one chip: kernels, NDS queries, serving
    python chip_smoke.py --chips 4   # the sharded query path on (4,1) and (2,2)

Every phase prints one JSON line: its name, wall and compile seconds, and
its check (``ok`` plus what was compared).  The last line of a run whose
phases all passed is ``{"ok": true, "device": {...}}``.  Without a TPU, or
when any phase fails, the script prints no such line and exits non-zero.

Each phase is a function of its sizes, so the CPU tests
(``tests/test_chip_smoke.py``) drive the same code at tiny sizes.
Everything runs in this process: a child would find the chip taken.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 42


def _emit(**fields) -> None:
    print(json.dumps(fields), flush=True)


class CompileClock:
    """Sums XLA backend compile time between :meth:`mark` calls (JAX's
    monitoring events; a persistent-cache hit records none)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, secs, **_kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.count += 1

    def mark(self):
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def _rng(salt: int = 0):
    import numpy as np

    return np.random.RandomState(SEED + salt)


def _sample(n: int, k: int, salt: int = 0):
    import numpy as np

    return np.sort(_rng(salt).choice(n, size=min(k, n), replace=False))


def _oracles():
    """The repo's host oracles (tests/ holds them beside their tests)."""
    tests = os.path.join(REPO, "tests")
    if tests not in sys.path:
        sys.path.insert(0, tests)
    import json_oracle
    import spark_oracles

    return spark_oracles, json_oracle


@contextlib.contextmanager
def host_arms_forbidden():
    """Make every numpy host arm of the string/row ops raise while active:
    an op that completes inside ran its device arm."""
    import importlib

    def mod(name):  # ops/__init__ re-exports functions under module names
        return importlib.import_module(f"spark_rapids_jni_tpu.ops.{name}")

    patched = [(mod("cast_string_to_float"), "_string_to_float_host"),
               (mod("float_to_string"), "_float_to_string_host"),
               (mod("get_json_object"), "_get_json_object_host"),
               (mod("row_conversion"), "_convert_to_rows_host"),
               (mod("row_conversion"), "_convert_from_rows_host")]
    saved = [getattr(m, name) for m, name in patched]

    def forbid(name):
        def _raise(*_a, **_k):
            raise AssertionError(f"host arm {name} ran on the device path")
        return _raise

    for m, name in patched:
        setattr(m, name, forbid(name))
    try:
        yield
    finally:
        for (m, name), fn in zip(patched, saved):
            setattr(m, name, fn)


# ------------------------------------------------------------- one chip --

def phase_device():
    import jax

    devs = jax.devices()
    d = devs[0]
    stats = d.memory_stats() or {}
    assert d.platform == "tpu", f"platform is {d.platform!r}, not tpu"
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs), "bytes_limit": stats.get("bytes_limit")}


def phase_murmur3(n: int = 1 << 24, sample: int = 2048):
    """murmur3-32 over INT32 on the XLA arm and the compiled Pallas arm:
    bit-equal to each other and to the host oracle on a seeded sample."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.columnar import INT32, Column
    from spark_rapids_jni_tpu.ops import hash_pallas, murmur_hash32

    so, _ = _oracles()
    data = _rng(1).randint(-(2**31), 2**31, size=n).astype(np.int32)
    d = jax.device_put(data)
    outs = {}
    for backend in ("xla", "pallas"):
        with config.override(hash_backend=backend):
            fn = jax.jit(lambda x: murmur_hash32([Column(x, None, INT32)],
                                                 seed=42).data)
            outs[backend] = np.asarray(jax.block_until_ready(fn(d)))
    assert np.array_equal(outs["xla"], outs["pallas"]), "xla != pallas"
    idx = _sample(n, sample)
    want = np.array([so.to_signed32(so.murmur32_int(int(v), 42))
                     for v in data[idx]], np.int32)
    assert np.array_equal(outs["xla"][idx], want), "murmur3 != oracle"
    return {"rows": n, "xla_eq_pallas": True, "oracle_sample": len(idx),
            "pallas_interpret": hash_pallas._use_interpret()}


def phase_xxhash64(n: int = 1 << 24, sample: int = 2048):
    import jax
    import numpy as np

    from spark_rapids_jni_tpu.columnar import INT64, Column
    from spark_rapids_jni_tpu.ops import xxhash64

    so, _ = _oracles()
    data = _rng(2).randint(-(2**62), 2**62, size=n, dtype=np.int64)
    fn = jax.jit(lambda x: xxhash64([Column(x, None, INT64)]).data)
    got = np.asarray(jax.block_until_ready(fn(jax.device_put(data))))
    idx = _sample(n, sample)
    want = np.array([so.to_signed64(so.xxh64_long(int(v), 42))
                     for v in data[idx]], np.int64)
    assert np.array_equal(got[idx], want), "xxhash64 != oracle"
    return {"rows": n, "oracle_sample": len(idx)}


def _query_step_reference(keys, values, cfg):
    """Host reference of models.nds.local_query_step from the Spark hash
    oracles: bucket sums/counts, the bloom bitmap and the probe count."""
    import numpy as np

    so, _ = _oracles()
    nb, k, m = cfg.n_buckets, cfg.bloom_hashes, cfg.bloom_bits
    sums = np.zeros(nb, np.int64)
    counts = np.zeros(nb, np.int32)
    bits = np.zeros(m, np.uint8)
    pos = []
    for key, v in zip(keys.tolist(), values.tolist()):
        b = so.xxh64_long(key, 42) % nb
        sums[b] += v
        counts[b] += 1
        h1 = so.murmur32_long(key, 0)
        h2 = so.murmur32_long(key, 0x9747B28C)
        p = [(h1 + i * h2) % m for i in range(1, k + 1)]
        bits[p] = 1
        pos.append(p)
    hits = sum(all(bits[q] for q in p) for p in pos)
    return sums, counts, bits, hits


def phase_entry_step(n: int = 1 << 24, ref_n: int = 4096):
    """models.nds.local_query_step (what __graft_entry__.entry() compiles):
    invariants at ``n`` rows, and bit-equality with the host reference at
    ``ref_n`` rows."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu.models import QueryStepConfig, local_query_step

    cfg = QueryStepConfig(n_buckets=256, bloom_bits=1 << 12, bloom_hashes=3)
    step = jax.jit(lambda k, v: local_query_step(k, v, cfg=cfg))
    rng = _rng(3)
    keys = rng.randint(0, 1 << 40, n, dtype=np.int64)
    values = rng.randint(0, 1000, n, dtype=np.int64)
    sums, counts, bits, hits = (np.asarray(x) for x in jax.block_until_ready(
        step(jax.device_put(keys), jax.device_put(values))))
    assert int(counts.sum()) == n and int(sums.sum()) == int(values.sum())
    assert int(hits) == n, "an inserted key failed its own bloom probe"
    rk, rv = keys[:ref_n], values[:ref_n]
    got = [np.asarray(x) for x in step(jax.device_put(rk),
                                       jax.device_put(rv))]
    want = _query_step_reference(rk, rv, cfg)
    for g, w, what in zip(got, want, ("sums", "counts", "bits", "hits")):
        assert np.array_equal(g, w), f"entry step {what} != reference"
    return {"rows": n, "reference_rows": ref_n, "bits_set": int(bits.sum())}


def phase_bloom(n: int = 1 << 24, ref_n: int = 1 << 12):
    """Spark bloom filter build + probe: byte-identical to the Spark
    oracle at ``ref_n`` keys; at ``n`` keys no false negatives and a
    false-positive rate near the design point (10 bits per key, k=3)."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu.columnar import INT64, Column
    from spark_rapids_jni_tpu.ops import (
        bloom_filter_create,
        bloom_filter_probe,
        bloom_filter_put,
    )
    from spark_rapids_jni_tpu.ops.bloom_filter import bloom_filter_serialize

    _oracles()  # puts tests/ on the path
    from test_bloom_filter import SparkBloomOracle

    def build_probe(keys, probes, longs):
        bf = bloom_filter_put(bloom_filter_create(3, longs),
                              Column(jax.device_put(keys), None, INT64))
        hit = bloom_filter_probe(Column(jax.device_put(probes), None, INT64),
                                 bf).data
        return bf, np.asarray(jax.block_until_ready(hit))

    rng = _rng(4)
    keys = rng.randint(-(2**62), 2**62, n, dtype=np.int64)
    fresh = rng.randint(-(2**62), 2**62, n, dtype=np.int64)
    longs = max(1, n * 10 // 64)
    _, hit_in = build_probe(keys, keys, longs)
    _, hit_out = build_probe(keys, fresh, longs)
    assert hit_in.all(), "false negative"
    fpr = float(hit_out.mean())
    assert fpr < 0.05, f"false-positive rate {fpr}"

    rk, rp = keys[:ref_n], fresh[:ref_n]
    r_longs = max(1, ref_n * 10 // 64)
    bf, got = build_probe(rk, rp, r_longs)
    oracle = SparkBloomOracle(3, r_longs)
    for v in rk.tolist():
        oracle.put(v)
    assert bloom_filter_serialize(bf) == oracle.serialize(), "bits differ"
    want = np.array([oracle.might_contain(v) for v in rp.tolist()])
    assert np.array_equal(got, want), "probe != oracle"
    return {"keys": n, "fpr": fpr, "oracle_keys": ref_n}


def phase_decimal128(n: int = 1 << 22, sample: int = 2048):
    """DECIMAL128 multiply (Spark multiply128, interim cast) against the
    host oracle on a seeded sample."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu.columnar.column import Decimal128Column
    from spark_rapids_jni_tpu.columnar.dtypes import DType, Kind
    from spark_rapids_jni_tpu.ops import multiply128

    so, _ = _oracles()
    rng = _rng(5)
    lo = rng.randint(0, 1 << 62, n, dtype=np.uint64)
    hi = rng.randint(-(1 << 30), 1 << 30, n, dtype=np.int64)
    lo2 = rng.randint(0, 1 << 40, n, dtype=np.uint64)
    hi2 = np.zeros(n, np.int64)
    d = DType(Kind.DECIMAL128, scale=2)

    def mul(ah, al, bh, bl):
        ov, r = multiply128(Decimal128Column(ah, al, None, d),
                            Decimal128Column(bh, bl, None, d), 2)
        return ov.data, r.hi, r.lo

    ov, rh, rl = (np.asarray(x) for x in jax.block_until_ready(
        jax.jit(mul)(*(jax.device_put(x) for x in (hi, lo, hi2, lo2)))))
    idx = _sample(n, sample, 5)
    for i in idx.tolist():
        a = (int(hi[i]) << 64) | int(lo[i])
        b = (int(hi2[i]) << 64) | int(lo2[i])
        w_ov, w_val = so.dec_multiply(a, b, 2, 2, 2, True)
        assert bool(ov[i]) == w_ov, i
        if not w_ov:
            got = (int(rh[i]) << 64) | int(rl[i])
            assert got == w_val, i
    return {"rows": n, "oracle_sample": len(idx),
            "overflows": int(ov.astype(bool).sum())}


def phase_rows(n: int = 1 << 22):
    """Fixed-width JCUDF row <-> column on the device arm: round trip
    exact, row bytes equal to the host arm's."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.columnar import FLOAT64, INT32, INT64, Column
    from spark_rapids_jni_tpu.ops import (
        convert_from_rows_fixed_width_optimized,
        convert_to_rows_fixed_width_optimized,
    )

    rng = _rng(6)
    arrays = [rng.randint(-(2**62), 2**62, n, dtype=np.int64),
              rng.randint(-(2**31), 2**31, n).astype(np.int32),
              rng.rand(n).view(np.int64)]
    valid = rng.rand(n) >= 0.1
    dtypes = [INT64, INT32, FLOAT64]
    cols = [Column(jax.device_put(a), jax.device_put(valid) if i == 1
                   else None, dt)
            for i, (a, dt) in enumerate(zip(arrays, dtypes))]
    with host_arms_forbidden():
        rows = convert_to_rows_fixed_width_optimized(cols)
        back = [convert_from_rows_fixed_width_optimized(r, dtypes)
                for r in rows]
    for j, (a, dt) in enumerate(zip(arrays, dtypes)):
        got = np.concatenate([np.asarray(b[j].data) for b in back])
        if j == 1:
            gv = np.concatenate([np.asarray(b[j].is_valid()) for b in back])
            assert np.array_equal(gv, valid), "validity lost"
            assert np.array_equal(got[valid], a[valid]), f"column {j}"
        else:
            assert np.array_equal(got, a), f"column {j} round trip"
    with config.override(rows_device_path=False):
        ref = convert_to_rows_fixed_width_optimized(cols)
    for r, w in zip(rows, ref):
        assert np.array_equal(np.asarray(r.child.data),
                              np.asarray(w.child.data)), \
            "device rows != host rows"
    return {"rows": n, "batches": len(rows)}


def phase_floats(n: int = 1 << 20):
    """float -> string and string -> float on the device arms, each equal
    to its host arm (which the tests hold to the Spark oracles).  Parsing
    the text back is reported, not required, to be exact: Spark's parser,
    like the reference's, is not correctly rounded."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu import config
    from spark_rapids_jni_tpu.columnar import FLOAT64, Column
    from spark_rapids_jni_tpu.ops import float_to_string, string_to_float

    rng = _rng(7)
    vals = rng.rand(n) * np.exp(rng.uniform(-30, 30, size=n))
    vals[rng.rand(n) < 0.5] *= -1
    vals[:4] = [0.0, np.inf, -np.inf, np.nan]
    bits = vals.view(np.int64)
    col = Column(jax.device_put(bits), None, FLOAT64)
    with host_arms_forbidden():
        s = float_to_string(col)
        got = np.asarray(jax.block_until_ready(
            string_to_float(s, ansi_mode=False, dtype=FLOAT64).data))
    with config.override(float_device_render=False, cast_device_parse=False):
        want_s = float_to_string(col)
        want = np.asarray(string_to_float(want_s, ansi_mode=False,
                                          dtype=FLOAT64).data)
    assert np.array_equal(np.asarray(s.chars), np.asarray(want_s.chars)) \
        and np.array_equal(np.asarray(s.offsets),
                           np.asarray(want_s.offsets)), "device != host text"
    assert np.array_equal(got, want), "device parse != host parse"
    finite = np.isfinite(vals)
    ulps = np.abs(got[finite] - bits[finite])
    return {"rows": n, "chars": int(np.asarray(s.offsets)[-1]),
            "inexact_round_trips": int((ulps > 0).sum()),
            "max_ulp": int(ulps.max())}


_JSON_PATHS = ["$.store.fruit[*].weight", "$.store.book", "$.k0",
               "$.store.fruit[0].type"]


def phase_json(n: int = 1 << 18, sample: int = 256):
    """get_json_object over several paths on the device arm, against the
    sequential JSON oracle on a seeded sample."""
    import numpy as np

    from spark_rapids_jni_tpu.columnar.column import strings_from_bytes
    from spark_rapids_jni_tpu.ops import json_tokenizer
    from spark_rapids_jni_tpu.ops.get_json_object import (
        get_json_object_multiple_paths,
        parse_path,
    )

    _, jo = _oracles()
    rows = [b'{"store": {"fruit": [{"weight": %d, "type": "apple"}, '
            b'{"weight": %d}], "book": "b%d"}, "k%d": %d.5}'
            % (i % 9, i % 7, i % 100, i % 3, i) for i in range(n)]
    for i in range(0, n, 97):
        rows[i] = rows[i][:-1]  # invalid JSON rows -> null
    with host_arms_forbidden():
        outs = get_json_object_multiple_paths(strings_from_bytes(rows),
                                              _JSON_PATHS)
        got = [o.to_list() for o in outs]
    idx = _sample(n, sample, 8)
    for p, g in zip(_JSON_PATHS, got):
        parsed = parse_path(p)
        for i in idx.tolist():
            want = jo.get_json_object(rows[i].decode(), parsed)
            assert g[i] == want, (p, i, g[i], want)
    onehot = json_tokenizer._use_onehot_gathers()
    import jax

    assert onehot == (jax.default_backend() != "cpu")
    return {"rows": n, "paths": len(_JSON_PATHS), "oracle_sample": len(idx),
            "onehot_gathers": onehot,
            "nonnull": [int(np.sum([x is not None for x in g])) for g in got]}


def _q97_numpy_oracle(store, catalog):
    """(store_only, catalog_only, both) over distinct packed keys — the
    q97_host_oracle semantics with numpy sets, fast at SF 10."""
    import numpy as np

    def keys(side):
        c, i = side
        return np.unique((c.astype(np.int64) << 32) | i.astype(np.int64))

    s, c = keys(store), keys(catalog)
    both = len(np.intersect1d(s, c, assume_unique=True))
    return len(s) - both, len(c) - both, both


def phase_nds(sf_big: float = 10.0, sf_small: float = 1.0,
              stream_chunk_rows: int = 1 << 20, buckets: int = 8):
    """models.nds_harness in-process: q97 in memory and streamed at
    ``sf_big`` (equal counts, equal to the numpy oracle), and q5/q97/q3 at
    ``sf_small`` with --verify plus the host oracles.  No plan may fall
    back from AOT compilation."""
    from spark_rapids_jni_tpu.models import (
        generate_q3_data,
        generate_q5_data,
        nds_harness,
        q3_local,
        q5_local,
    )
    from spark_rapids_jni_tpu.models.q3 import q3_columns_host_oracle
    from spark_rapids_jni_tpu.models.q5 import q5_host_oracle
    from spark_rapids_jni_tpu.models.tpcds import generate_q97_tables
    from spark_rapids_jni_tpu.plans import plan_cache

    def split(report):
        return {q: dict(v["plan"], wall_s=v["wall_s"],
                        fact_rows=v["fact_rows"])
                for q, v in report["queries"].items()}

    fallbacks0 = plan_cache.stats()["aot_fallbacks"]
    seed = ["--seed", str(SEED)]
    mem = nds_harness.run(["--sf", str(sf_big)] + seed)
    streamed = nds_harness.run(
        ["--sf", str(sf_big), "--stream-chunk-rows", str(stream_chunk_rows),
         "--buckets", str(buckets)] + seed)
    small = nds_harness.run(["--sf", str(sf_small), "--verify"] + seed)

    want_big = list(_q97_numpy_oracle(*generate_q97_tables(sf_big, SEED)))
    q97_mem = mem["queries"]["q97"]["counts"]
    q97_str = streamed["queries"]["q97"]["counts"]
    assert q97_mem == q97_str == want_big, (q97_mem, q97_str, want_big)
    assert all(q["verified"] is True for q in small["queries"].values()), \
        small["queries"]

    d5 = generate_q5_data(sf=sf_small, seed=SEED)
    want5 = q5_host_oracle(d5)
    assert small["queries"]["q5"]["result_rows"] == len(want5)
    assert q5_local(d5) == want5, "q5 != host oracle"
    d3 = generate_q3_data(sf=sf_small, seed=SEED)
    assert [tuple(r) for r in q3_local(d3)] == \
        [tuple(r) for r in q3_columns_host_oracle(d3)], "q3 != host oracle"
    fallbacks = plan_cache.stats()["aot_fallbacks"] - fallbacks0
    assert fallbacks == 0, f"{fallbacks} plan(s) fell back from AOT"
    return {"q97_sf%g" % sf_big: q97_mem,
            "in_memory": split(mem), "streamed": split(streamed),
            "sf%g" % sf_small: split(small), "aot_fallbacks": fallbacks}


def phase_serving(sf: float = 1.0, requests: int = 8, sessions: int = 2):
    """An in-process ServingEngine answers ``requests`` q97 requests from
    ``sessions`` sessions; every answer equals q97_host_oracle and the
    budget drains to zero."""
    from spark_rapids_jni_tpu.mem import MemoryGovernor
    from spark_rapids_jni_tpu.models.q97 import q97_host_oracle
    from spark_rapids_jni_tpu.models.tpcds import generate_q97_tables
    from spark_rapids_jni_tpu.parallel import make_mesh
    from spark_rapids_jni_tpu.serve.executor import ServingEngine

    tables = [generate_q97_tables(sf, SEED + s) for s in range(sessions)]
    want = [q97_host_oracle(*t) for t in tables]
    MemoryGovernor.initialize()
    engine = ServingEngine(mesh=make_mesh(), builtin_handlers=True)
    try:
        sess = [engine.open_session(f"s{s}") for s in range(sessions)]
        futs = [(i % sessions,
                 engine.submit(sess[i % sessions], "q97", tables[i % sessions]))
                for i in range(requests)]
        for s, f in futs:
            out = f.result(timeout=600)
            got = (int(out.store_only), int(out.catalog_only), int(out.both))
            assert got == want[s], (s, got, want[s])
        used = engine.budget.used
    finally:
        engine.shutdown()
        MemoryGovernor.shutdown()
    assert used == 0, f"budget still holds {used} bytes"
    return {"requests": requests, "sessions": sessions, "sf": sf,
            "answers": [list(w) for w in want]}


ONE_CHIP = (
    ("device", phase_device),
    ("murmur3_int32", phase_murmur3),
    ("xxhash64", phase_xxhash64),
    ("entry_step", phase_entry_step),
    ("bloom_filter", phase_bloom),
    ("decimal128_multiply", phase_decimal128),
    ("row_conversion", phase_rows),
    ("float_string", phase_floats),
    ("get_json_object", phase_json),
    ("nds", phase_nds),
    ("serving", phase_serving),
)


# ----------------------------------------------------------- four chips --

def _spread(arrays, mesh):
    """Place row arrays over the mesh's data axis and check that each one
    really has a shard on every device of the mesh."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    out = [jax.device_put(a, NamedSharding(mesh, P("data"))) for a in arrays]
    for a in out:
        devs = {s.device for s in a.addressable_shards}
        assert devs == set(mesh.devices.flat), \
            f"array on {len(devs)} of {mesh.size} devices"
    return out


def mesh_query_step(mesh, rows_per_chip: int = 1 << 22):
    """The distributed query step vs local_query_step on one chip."""
    import jax
    import numpy as np

    from spark_rapids_jni_tpu.models import (
        QueryStepConfig,
        local_query_step,
        make_distributed_query_step,
    )

    dp, mp = mesh.shape["data"], mesh.shape["model"]
    cfg = QueryStepConfig(n_buckets=256, bloom_bits=1 << 12, bloom_hashes=3)
    n = rows_per_chip * mesh.size  # one size for every mesh: one reference
    rng = _rng(11)
    keys = rng.randint(0, 1 << 40, n, dtype=np.int64)
    values = rng.randint(0, 1000, n, dtype=np.int64)
    out = make_distributed_query_step(mesh, cfg)(*_spread([keys, values],
                                                          mesh))
    one = jax.devices()[0]
    sums, counts, bits, hits = (np.asarray(x) for x in jax.jit(
        lambda k, v: local_query_step(k, v, cfg=cfg))(
            jax.device_put(keys, one), jax.device_put(values, one)))
    nb = cfg.n_buckets
    assert np.array_equal(np.asarray(out.bucket_sums).reshape(dp, nb).sum(0),
                          sums)
    assert np.array_equal(
        np.asarray(out.bucket_counts).reshape(dp, nb).sum(0), counts)
    assert np.array_equal(np.asarray(out.bloom_bits), bits)
    assert int(out.probe_hits) == int(hits) == n
    assert int(out.total_rows) == n and int(out.dropped) == 0
    assert int(counts.sum()) == n and int(sums.sum()) == int(values.sum())
    return {"rows": n, "mesh": [dp, mp]}


def mesh_q97(mesh, sf: float = 10.0):
    """run_distributed_q97 (the compiled q97 plan) at ``sf`` vs the numpy
    q97 oracle (the oracle the one-chip smoke holds its SF 10 counts to)."""
    from spark_rapids_jni_tpu.models import run_distributed_q97
    from spark_rapids_jni_tpu.models.q97 import default_q97_capacity
    from spark_rapids_jni_tpu.models.tpcds import generate_q97_tables

    dp = mesh.shape["data"]
    store, catalog = generate_q97_tables(sf, SEED)
    n = len(store[0])
    cap = default_q97_capacity(n + len(catalog[0]), dp)
    q = run_distributed_q97(mesh, store, catalog, capacity=cap)
    got = [int(q.store_only), int(q.catalog_only), int(q.both)]
    want = list(_q97_numpy_oracle(store, catalog))
    assert int(q.dropped) == 0 and got == want, (got, want, int(q.dropped))
    return {"rows_per_side": n, "counts": got, "capacity": cap}


def mesh_q5_q3(mesh, sf: float = 1.0):
    """run_distributed_q5 / run_distributed_q3 vs the one-chip plans and
    the host oracles."""
    from spark_rapids_jni_tpu.models import (
        generate_q3_data,
        generate_q5_data,
        q3_local,
        q5_local,
        run_distributed_q3,
        run_distributed_q5,
    )
    from spark_rapids_jni_tpu.models.q3 import q3_columns_host_oracle
    from spark_rapids_jni_tpu.models.q5 import q5_host_oracle
    d5 = generate_q5_data(sf=sf, seed=SEED)
    d3 = generate_q3_data(sf=sf, seed=SEED)
    want5 = q5_host_oracle(d5)
    want3 = [tuple(r) for r in q3_columns_host_oracle(d3)]
    q5 = run_distributed_q5(mesh, d5)
    q3 = run_distributed_q3(mesh, d3)
    assert q5 == q5_local(d5) == want5, "q5 differs"
    assert [tuple(r) for r in q3] == [tuple(r) for r in q3_local(d3)] \
        == want3, "q3 differs"
    return {"q5_rows": len(q5), "q3_rows": len(q3), "sf": sf}


def mesh_table_shuffle(mesh, rows_per_chip: int = 4096):
    """Strings + nullable decimal128 through the all_to_all table shuffle
    (__graft_entry__.table_shuffle_check), every row intact on its owner."""
    from __graft_entry__ import table_shuffle_check

    n = rows_per_chip * mesh.shape["data"]
    assert table_shuffle_check(mesh, n) == n
    return {"rows": n}


# (mesh shape, phases), most important first.  The (2,2) mesh runs the
# phase that uses its model axis (the query step's sharded bloom bits); the
# q97/q5/q3 programs only shard rows, so on (2,2) they would be the (4,1)
# programs over two devices.  The nullable-key q97 stays on the virtual mesh
# (__graft_entry__.dryrun_multichip): its 2-limb lexsort takes ~212 s to
# compile for four chips.
FOUR_CHIP = (
    ((4, 1), (("query_step", mesh_query_step),
              ("q97_sf10", mesh_q97),
              ("q5_q3", mesh_q5_q3),
              ("table_shuffle", mesh_table_shuffle))),
    ((2, 2), (("query_step", mesh_query_step),)),
)


# ----------------------------------------------------------------- main --

def _run_phase(name, fn, clock, *args):
    t0 = time.perf_counter()
    try:
        check = fn(*args)
        ok, err = True, None
    except Exception as e:  # noqa: BLE001 - every phase reports, then fail
        import traceback

        traceback.print_exc()
        check, ok, err = None, False, f"{type(e).__name__}: {e}"[:500]
    compile_s, compiles = clock.mark()
    line = {"phase": name, "ok": ok, "wall_s": time.perf_counter() - t0,
            "compile_s": compile_s, "compiles": compiles}
    if check is not None:
        line["check"] = check
    if err is not None:
        line["error"] = err
    _emit(**line)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded query path on (4,1) and "
                         "(2,2) meshes")
    args = ap.parse_args(argv)

    try:
        import jax
    except ImportError as e:
        print(f"chip_smoke: JAX is not importable: {e}", file=sys.stderr)
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX found "
              f"{len(devs)} device(s)", file=sys.stderr)
        return 2
    if not os.path.isdir(os.path.join(REPO, "spark_rapids_jni_tpu")):
        print("chip_smoke: run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    sys.path.insert(0, REPO)
    from spark_rapids_jni_tpu import compile_cache

    _emit(phase="setup", compile_cache=compile_cache.enable(),
          jax=jax.__version__)
    clock = CompileClock()
    ok = True
    if args.chips == 1:
        for name, fn in ONE_CHIP:
            ok &= _run_phase(name, fn, clock)
    else:
        from spark_rapids_jni_tpu.parallel import make_mesh

        for shape, phases in FOUR_CHIP:
            mesh = make_mesh(shape, devices=devs[:4])
            for name, fn in phases:
                ok &= _run_phase(f"{name}@{shape[0]}x{shape[1]}", fn, clock,
                                 mesh)
    if not ok:
        return 1
    d = jax.devices()[0]
    _emit(ok=True, device={"platform": d.platform, "kind": d.device_kind,
                           "count": len(jax.devices())})
    return 0


if __name__ == "__main__":
    sys.exit(main())
