"""Worker process for the 2-process multihost test (not a test module).

Each process joins the JAX process group through the framework's own
``multihost.initialize`` (explicit coordinator args — the CPU-cluster /
test path), builds the pod mesh, and runs the compiled q97 plan
(``run_distributed_q97``) over inputs sharded across every process's
devices.  Prints one JSON line with the process summary and the q97
totals; the parent test asserts both processes agree with the host-set
oracle.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    coord = sys.argv[3]

    from spark_rapids_jni_tpu.parallel import multihost

    multihost.initialize(coordinator_address=coord,
                         num_processes=nproc, process_id=pid)

    import jax
    import numpy as np

    from spark_rapids_jni_tpu.models.q97 import (
        q97_host_oracle,
        run_distributed_q97,
    )

    assert multihost.is_multihost()
    mesh = multihost.make_pod_mesh(mp=1, axis_names=("data", "model"))
    ndev = len(jax.devices())

    # identical global inputs in every process (deterministic seed); the
    # plan runtime places each process's shards of them on its devices
    rng = np.random.RandomState(11)
    rows = 512
    glb = [rng.randint(1, 50, rows).astype(np.int32) for _ in range(4)]

    out = run_distributed_q97(mesh, (glb[0], glb[1]), (glb[2], glb[3]),
                              capacity=rows)
    got = {
        "store_only": int(out.store_only),
        "catalog_only": int(out.catalog_only),
        "both": int(out.both),
        "dropped": int(out.dropped),
    }
    want = dict(zip(("store_only", "catalog_only", "both"),
                    q97_host_oracle((glb[0], glb[1]), (glb[2], glb[3]))),
                dropped=0)
    print(json.dumps({"proc": pid, "summary": multihost.process_summary(),
                      "got": got, "want": want, "ndev": ndev}), flush=True)
    return 0 if got == want else 1


if __name__ == "__main__":
    sys.exit(main())
