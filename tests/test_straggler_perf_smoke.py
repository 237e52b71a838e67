"""Throughput floor smoke tests for the round-20 straggler fast paths.

These are NOT benchmarks — ``benchmark/`` owns the real numbers.  They are
regression tripwires: the pre-round-20 pipelines ran at O(100) rows/s per
stage on the CPU mesh (BENCH_r09), the fast paths run 3-4 orders of
magnitude above these floors, so a trip means a dispatch regression (the
slow arm became the default again), not noise.  Floors are set ~100x below
measured fast-path throughput to stay robust on loaded CI hosts.
"""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_jni_tpu.columnar import Column, FLOAT64, INT32, INT64
from spark_rapids_jni_tpu.ops import (
    convert_from_rows_fixed_width_optimized,
    convert_to_rows_fixed_width_optimized,
    float_to_string,
    string_to_float,
)

N = 1 << 16


def _rate(fn, n):
    fn()  # warm: plan-cache misses and jit tracing don't count
    best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return n / best


@pytest.fixture(scope="module")
def fcol():
    rng = np.random.RandomState(17)
    vals = rng.rand(N) * np.exp(rng.uniform(-30, 30, size=N))
    return Column(jnp.asarray(vals.view(np.int64)), None, FLOAT64)


def test_float_to_string_floor(fcol):
    rate = _rate(lambda: np.asarray(float_to_string(fcol).chars), N)
    assert rate >= 5000, f"float_to_string {rate:.0f} rows/s < 5000"


def test_string_to_float_floor(fcol):
    scol = float_to_string(fcol)
    rate = _rate(
        lambda: np.asarray(
            string_to_float(scol, ansi_mode=False, dtype=FLOAT64).data), N)
    assert rate >= 5000, f"string_to_float {rate:.0f} rows/s < 5000"


def test_rows_roundtrip_floor():
    rng = np.random.RandomState(23)
    cols = [
        Column(jnp.asarray(rng.randint(-(2 ** 31), 2 ** 31, N,
                                       dtype=np.int64)), None, INT64),
        Column(jnp.asarray(rng.randint(-(2 ** 31), 2 ** 31, N)
                           .astype(np.int32)), None, INT32),
        Column(jnp.asarray(rng.rand(N).view(np.int64)), None, FLOAT64),
    ]
    dtypes = [c.dtype for c in cols]

    def roundtrip():
        for b in convert_to_rows_fixed_width_optimized(cols):
            convert_from_rows_fixed_width_optimized(b, dtypes)

    rate = _rate(roundtrip, N)
    assert rate >= 20000, f"rows round-trip {rate:.0f} rows/s < 20000"
