"""chip_smoke.py on the CPU: its refusal without a TPU, and every phase
function at tiny sizes (the chip runs the same functions at full size)."""

import os
import shutil
import subprocess
import sys

import pytest

from spark_rapids_jni_tpu import config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

# the CPU picks the numpy host arms under "auto"; the phases forbid them
DEVICE_ARMS = dict(json_device_render=True, float_device_render=True,
                   cast_device_parse=True, rows_device_path=True)


def _run_script(script, cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def test_exits_nonzero_without_tpu():
    r = _run_script(os.path.join(REPO, "chip_smoke.py"), REPO)
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert '"ok": true' not in r.stdout


def test_exits_nonzero_alone_in_a_directory(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run_script(str(tmp_path / "chip_smoke.py"), str(tmp_path),
                    "--chips", "4")
    assert r.returncode != 0 and '"ok": true' not in r.stdout


def test_device_phase_refuses_cpu():
    with pytest.raises(AssertionError, match="not tpu"):
        chip_smoke.phase_device()


@pytest.mark.parametrize("phase,kwargs", [
    ("phase_murmur3", dict(n=4096, sample=64)),
    ("phase_xxhash64", dict(n=4096, sample=64)),
    ("phase_entry_step", dict(n=8192, ref_n=256)),
    ("phase_bloom", dict(n=4096, ref_n=256)),
    ("phase_decimal128", dict(n=4096, sample=64)),
    ("phase_rows", dict(n=2048)),
    ("phase_floats", dict(n=512)),
    ("phase_json", dict(n=256, sample=32)),
])
def test_kernel_phase_tiny(phase, kwargs):
    with config.override(**DEVICE_ARMS):
        check = getattr(chip_smoke, phase)(**kwargs)
    assert isinstance(check, dict)


def test_host_arm_forbidden_under_auto_on_cpu():
    """On the CPU, auto picks the host arm, which the phase refuses: the
    guard really detects a host arm."""
    with pytest.raises(AssertionError, match="host arm"):
        chip_smoke.phase_floats(n=64)


def test_nds_phase_tiny():
    check = chip_smoke.phase_nds(sf_big=0.01, sf_small=0.005,
                                 stream_chunk_rows=4096, buckets=2)
    assert check["aot_fallbacks"] == 0
    assert check["streamed"]["q97"]["fact_rows"] == \
        check["in_memory"]["q97"]["fact_rows"]


def test_serving_phase_tiny():
    check = chip_smoke.phase_serving(sf=0.001, requests=4, sessions=2)
    assert check["requests"] == 4


def test_four_chip_phases_tiny():
    """The --chips 4 path on (4,1) and (2,2) meshes of virtual devices."""
    import jax

    from spark_rapids_jni_tpu.parallel import make_mesh

    for shape in ((4, 1), (2, 2)):
        mesh = make_mesh(shape, devices=jax.devices()[:4])
        chip_smoke.mesh_query_step(mesh, rows_per_chip=1024)
        chip_smoke.mesh_q97(mesh, sf=0.002)
        chip_smoke.mesh_q5_q3(mesh, sf=0.01)
        chip_smoke.mesh_table_shuffle(mesh, rows_per_chip=16)
