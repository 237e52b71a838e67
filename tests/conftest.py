"""Test configuration: force an 8-device virtual CPU mesh.

Multi-chip sharding logic is tested on a virtual CPU mesh; the chip itself
is exercised by ``chip_smoke.py`` through the chip tool, and the TPU
compiler by ``tests/test_tpu_compile.py`` against a described topology.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent XLA compilation cache: big win for repeat runs, but DISABLED by
# default — on this box, loading entries whose recorded CPU "machine
# features" (incl. XLA pseudo-features like +prefer-no-scatter) don't match
# the loader's detection SEGFAULTs inside cpu_aot_loader (three reproduced
# crashes in compilation_cache.get_executable_and_time).  Opt back in with
# SRT_JAX_CACHE=1 on a machine where the feature set is stable.
if os.environ.get("SRT_JAX_CACHE") == "1":
    _cache = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", _cache)
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def scrubbed_cpu_env(device_count: int = 8) -> dict:
    """Env for subprocess workers pinned to a virtual CPU mesh of
    ``device_count`` devices.  Single source of truth for every
    multi-process test."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={device_count}"
    return env
