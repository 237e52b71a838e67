"""Streamed-NDS scaling runs (BASELINE config-5 SF100 trajectory).

Runs `nds_harness --verify --stream-chunk-rows` at each requested scale
factor in a child process and appends one JSON line per run to
SCALING_r05.jsonl: the harness output plus the child's REAL exit code,
wall seconds, and max RSS from getrusage(RUSAGE_CHILDREN).

    python tools/scale_run.py 3:16 10:32 30:64 100:128
"""

from __future__ import annotations

import json
import os
import resource
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "SCALING_r05.jsonl")


def run_one(sf: float, buckets: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    t0 = time.time()
    rss0 = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
    proc = subprocess.run(
        [sys.executable, "-m", "spark_rapids_jni_tpu.models.nds_harness",
         "--sf", str(sf), "--verify", "--stream-chunk-rows", "1000000",
         "--buckets", str(buckets)],
        cwd=REPO, env=env, capture_output=True, text=True)
    wall = int(time.time() - t0)
    rss1 = resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss
    lines = proc.stdout.strip().splitlines()
    try:
        harness = json.loads(lines[-1]) if lines else {}
    except Exception:
        harness = {"parse_error": lines[-1][-400:]}
    if proc.returncode != 0:
        harness.setdefault("stderr_tail",
                           proc.stderr.strip().splitlines()[-3:])
    return {"sf": sf, "buckets": buckets, "rc": proc.returncode,
            "wall_total_s": wall,
            "maxrss_mb": round(max(rss0, rss1) / 1024, 1),
            "harness": harness}


def main(argv) -> int:
    rc = 0
    for spec in argv:
        sf_s, _, b_s = spec.partition(":")
        sf, buckets = float(sf_s), int(b_s or "16")
        print(f"=== sf={sf} buckets={buckets} ===", file=sys.stderr)
        rec = run_one(sf, buckets)
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        rc = rc or rec["rc"]
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
