"""Main-path kernels compiled for a described TPU v5e at the sizes
chip_smoke.py runs them (on-chip-measurement §2): what the chip's compiler
refuses, and what does not fit one chip's HBM, fails here without a chip.

The topology is described inside a module fixture, never at import: only
one process may load libtpu, and every xdist worker imports this file.
Code that asks jax.default_backend() still sees the CPU here, so each case
compiles the jitted kernel itself and forces its accelerator choices in
the test (interpret mode off, device arms on).
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from spark_rapids_jni_tpu import config

os.environ.setdefault("TPU_LOG_DIR", "disabled")

HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    m = compiled.memory_analysis()
    used = (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled, m


@pytest.fixture
def mosaic(monkeypatch):
    """Pallas kernels lowered through Mosaic, as on the chip."""
    from spark_rapids_jni_tpu.ops import hash_pallas

    monkeypatch.setattr(hash_pallas, "_use_interpret", lambda: False)
    hash_pallas._launch.clear_cache()
    yield hash_pallas
    hash_pallas._launch.clear_cache()


def test_pallas_murmur3_int_2p24(mosaic, one_chip):
    n = 1 << 24
    c, _ = _compile(mosaic.mm_hash_int_pallas,
                    _spec((n,), jnp.int32, one_chip),
                    _spec((n,), jnp.uint32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_pallas_xxhash64_fixed8_2p22(mosaic, one_chip):
    n = 1 << 22
    c, _ = _compile(lambda v: mosaic.xx_hash_fixed8_pallas(v, 42),
                    _spec((n,), jnp.uint64, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_pallas_murmur3_bytes_words_2p20(mosaic, one_chip):
    n, lw = 1 << 20, 8
    c, _ = _compile(lambda w, nw: mosaic.mm_bytes_words_pallas(w, nw, 42),
                    _spec((n, lw), jnp.uint32, one_chip),
                    _spec((n,), jnp.int32, one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_murmur3_xla_2p24(one_chip):
    from spark_rapids_jni_tpu.columnar import INT32, Column
    from spark_rapids_jni_tpu.ops import murmur_hash32

    with config.override(hash_backend="xla"):
        c, _ = _compile(
            lambda x: murmur_hash32([Column(x, None, INT32)], seed=42).data,
            _spec((1 << 24,), jnp.int32, one_chip))
    assert "tpu_custom_call" not in c.as_text()


def test_entry_step_2p24_fits_one_chip(one_chip):
    """The __graft_entry__ step at 2^24 int64 rows: 16.19 GiB of HBM before
    the bloom positions went [n, k] -> k x [n]; now well under 1 GiB."""
    from spark_rapids_jni_tpu.models import QueryStepConfig, local_query_step

    cfg = QueryStepConfig(n_buckets=256, bloom_bits=1 << 12, bloom_hashes=3)
    x = _spec((1 << 24,), jnp.int64, one_chip)
    _, m = _compile(lambda k, v: local_query_step(k, v, cfg=cfg), x, x)
    assert m.temp_size_in_bytes < (1 << 30), m.temp_size_in_bytes


def test_decimal128_multiply_2p22(one_chip):
    """DECIMAL128 multiply at 2^22 rows: 18.4 GB of HBM while the 256-bit
    limbs were row-major ([n, 8] pads 8 -> 128 lanes); limb-major now."""
    from spark_rapids_jni_tpu.columnar.column import Decimal128Column
    from spark_rapids_jni_tpu.columnar.dtypes import DType, Kind
    from spark_rapids_jni_tpu.ops import multiply128

    d = DType(Kind.DECIMAL128, scale=2)

    def mul(ah, al, bh, bl):
        ov, r = multiply128(Decimal128Column(ah, al, None, d),
                            Decimal128Column(bh, bl, None, d), 2)
        return ov.data, r.hi, r.lo

    n = 1 << 22
    hi, lo = (_spec((n,), jnp.int64, one_chip),
              _spec((n,), jnp.uint64, one_chip))
    _, m = _compile(mul, hi, lo, hi, lo)
    assert m.temp_size_in_bytes < (4 << 30), m.temp_size_in_bytes


def test_float_to_string_ryu_core_2p20(one_chip):
    """float_device_render's Ryu d2d core at 2^20.  With its digit loops
    unrolled and a variable-divisor u64 modulo it took 357 s to compile for
    the chip; rolled, ~23 s."""
    import importlib

    f2s = importlib.import_module("spark_rapids_jni_tpu.ops.float_to_string")
    with config.override(float_device_render=True):
        assert f2s._device_render_enabled()
        _compile(f2s._d2d, _spec((1 << 20,), jnp.uint64, one_chip))


def test_string_to_float_scan_and_assemble_2p20(one_chip):
    """cast_device_parse's two kernels: the padded byte scan of one length
    bucket (2^20 rows x 32 bytes) and the softfloat binary64 assembly."""
    import importlib

    s2f = importlib.import_module(
        "spark_rapids_jni_tpu.ops.cast_string_to_float")
    n, w = 1 << 20, 32
    with config.override(cast_device_parse=True):
        assert s2f._device_parse_enabled()
        pad = _spec((n, w), jnp.uint8, one_chip)
        lens = _spec((n,), jnp.int32, one_chip)
        _compile(lambda p, l: s2f._scan_padded(p, l, 4), pad, lens)
        fields = jax.eval_shape(lambda p, l: s2f._scan_padded(p, l, 4),
                                pad, lens)
        f = {k: _spec(v.shape, v.dtype, one_chip)
             for (k, _), v in zip(s2f._SCAN_FIELDS, fields)}
        _compile(s2f._assemble_device, f)


def test_row_conversion_fixed_width_2p22(one_chip):
    """rows_device_path: the fixed-width table -> JCUDF rows program
    (column lanes + the cached permutation gather) at 2^22 rows."""
    import importlib

    from spark_rapids_jni_tpu.columnar import FLOAT64, INT32, INT64, Column

    rc = importlib.import_module("spark_rapids_jni_tpu.ops.row_conversion")
    n = 1 << 22
    dtypes = [INT64, INT32, FLOAT64]
    with config.override(rows_device_path=True):
        assert rc._rows_device_enabled()
        plan = rc._build_row_plan(rc._row_plan_sig(dtypes))
        _, _, _, spr = rc.compute_layout(dtypes)

        def to_rows(a, b, c, bv):
            cols = [Column(a, None, INT64), Column(b, bv, INT32),
                    Column(c, None, FLOAT64)]
            lanes = jnp.concatenate([rc._col_le_bytes(x) for x in cols]
                                    + [rc._validity_bytes(cols)], axis=1)
            return rc._gather_fixed(lanes, plan["perm"],
                                    plan["keep"])[:, :spr]

        _compile(to_rows, _spec((n,), jnp.int64, one_chip),
                 _spec((n,), jnp.int32, one_chip),
                 _spec((n,), jnp.int64, one_chip),
                 _spec((n,), jnp.bool_, one_chip))


def test_json_tokenizer_onehot_2p18(one_chip, monkeypatch):
    """json_device_render's one-hot token compaction + grammar scan, the
    path the accelerator takes (the CPU takes the numpy twins), at 2^18
    rows of the smoke's 128-byte length bucket.  The byte scan before it
    compiles in ~40-55 s and is left to chip_smoke.py."""
    import importlib

    tok = importlib.import_module("spark_rapids_jni_tpu.ops.json_tokenizer")
    monkeypatch.setattr(tok, "_ONEHOT_GATHERS", True)
    assert tok._use_onehot_gathers()
    n, w = 1 << 18, 128
    ts, kind, end, counts, _ = jax.eval_shape(
        tok._scan_bytes, jax.ShapeDtypeStruct((n, w), jnp.uint8),
        jax.ShapeDtypeStruct((n,), jnp.int32))
    specs = [_spec(s.shape, s.dtype, one_chip)
             for s in (ts, kind, end, counts)]
    T = 64  # pow2 token budget of the smoke's rows (~30 tokens each)
    _compile(lambda a, b, c, d: tok._compact_and_grammar(a, b, c, d, T),
             *specs)
