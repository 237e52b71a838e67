"""Two real OS processes form a JAX process group and run distributed q97.

This is the closest a single box gets to the multi-host claim: each
process owns 2 virtual CPU devices, ``multihost.initialize`` joins them
through a real coordinator, ``make_pod_mesh`` spans all 4 global devices,
and the SAME compiled q97 plan that runs single-process executes with
cross-process collectives.  (On a pod, the identical code path rides
ICI/DCN — SURVEY.md §2.3's planning note.)
"""

import json
import os
import socket
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow

_HERE = os.path.dirname(os.path.abspath(__file__))


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_group_with_port_retry(nproc: int):
    # one retry with a fresh port, ONLY for the _free_port close-then-bind
    # race; real failures (wrong results, hangs) must surface first-run
    try:
        _run_group_once(nproc)
    except AssertionError as e:
        markers = ("Address already in use", "Failed to bind", "UNAVAILABLE")
        if any(m in str(e) for m in markers):
            _run_group_once(nproc)
        else:
            raise


def test_two_process_group_runs_distributed_q97():
    _run_group_with_port_retry(2)


def test_four_process_group_runs_distributed_q97():
    """Pod-shape evidence past 2 processes: a 4-process group (8 global
    devices) runs the same compiled plan with cross-process
    collectives (SURVEY §2.3 planning note; VERDICT r4 #9)."""
    _run_group_with_port_retry(4)


def _run_group_once(nproc: int):
    from conftest import scrubbed_cpu_env

    env = scrubbed_cpu_env(2)

    coord = f"127.0.0.1:{_free_port()}"
    worker = os.path.join(_HERE, "multihost_worker.py")
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), str(nproc), coord],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        for pid in range(nproc)
    ]
    outs = []
    try:
        for p in procs:
            try:
                out, err = p.communicate(timeout=600)
            except subprocess.TimeoutExpired:
                pytest.fail("multihost worker hung")
            assert p.returncode == 0, err.strip().splitlines()[-5:]
            outs.append(json.loads(out.strip().splitlines()[-1]))
    finally:
        # a failure on worker 0 must not leak the others blocked on the
        # dead coordinator for the rest of the session
        for q in procs:
            if q.poll() is None:
                q.kill()

    for rec in outs:
        assert rec["got"] == rec["want"], rec
        assert rec["summary"]["process_count"] == nproc
        assert rec["summary"]["local_devices"] == 2
        assert rec["summary"]["global_devices"] == 2 * nproc
    # every process saw the same global result
    assert all(rec["got"] == outs[0]["got"] for rec in outs)
