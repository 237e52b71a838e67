"""obs/timing: marginal timing over jax.block_until_ready.

On the CPU backend these tests pin the API contract and the fallback
behavior; the times themselves mean nothing here.
"""

import jax.numpy as jnp

from spark_rapids_jni_tpu.obs.timing import time_marginal


def test_time_marginal_syncs_pytrees_and_dtypes():
    tree = {
        "f": jnp.arange(8, dtype=jnp.float32),
        "i": jnp.arange(8, dtype=jnp.int64),
        "b": jnp.arange(8) % 2 == 0,
        "empty": jnp.zeros((0,), jnp.float32),
        "host": 3.5,  # non-array leaf must be ignored
    }
    dt, info = time_marginal(lambda: tree, 1, 2)  # default sync: must not raise
    assert dt > 0 and info["iters"] == [1, 2]


def test_time_marginal_positive_and_info():
    x = jnp.arange(1024, dtype=jnp.float32)
    dt, info = time_marginal(lambda: x + 1.0, 2, 6)
    assert dt > 0
    assert info["iters"] == [2, 6]
    assert info["method"] in ("marginal", "amortized-fallback")
    assert info["t_hi_s"] >= 0


def test_time_marginal_fallback_is_amortized(monkeypatch):
    # Force a negative two-point subtraction (t_lo=10s, t_hi=0s) via a
    # controlled clock; the clock cycles rather than exhausts so other
    # in-process perf_counter callers can't break it mid-window.
    import itertools

    import spark_rapids_jni_tpu.obs.timing as timing

    seq = itertools.cycle([0.0, 10.0, 10.0, 10.0])
    monkeypatch.setattr(timing.time, "perf_counter", lambda: next(seq))
    dt, info = time_marginal(lambda: 1, 2, 4, sync=lambda _out: None)
    assert info["method"] == "amortized-fallback"
    assert dt == info["amortized_s_per_call"]


def test_time_marginal_for_iters_small_budget_stays_cheap():
    from spark_rapids_jni_tpu.obs.timing import time_marginal_for_iters

    calls = []
    dt, info = time_marginal_for_iters(lambda: calls.append(1), 2)
    assert dt > 0
    # warmup + lo(1) + hi(3): small legacy budgets must not balloon
    assert len(calls) <= 5
