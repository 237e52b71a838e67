"""Compile each cell's plan for a described v5e, with no chip attached.

    JAX_PLATFORMS=cpu python3 -m benchmark.rehearse_compile [cell ...]

For each cell (default: every cell of BENCHMARK.json) this builds the plan
that the cell's window runs, at the cell's sizes and on a (chips, 1) mesh of
described v5e devices (topology ``v5e:2x2``), compiles it with the TPU
compiler, and prints one JSON line: compile seconds, ``memory_analysis()``
and the count of sorts and all-to-alls in the compiled HLO.  A compile that
passes here is not a chip run and gives no time of the chip.

Only the q97 driver's plan is known here; a cell of another query is
reported as skipped.
"""

from __future__ import annotations

import json
import os
import re
import sys


def _q97_plan_inputs(cell):
    import numpy as np

    from spark_rapids_jni_tpu.models.q97 import default_q97_capacity, q97_plan

    c = cell.config
    n_store, n_cat = int(c["store_sales_rows"]), int(c["catalog_sales_rows"])
    cap = default_q97_capacity(n_store + n_cat, cell.chips)
    # np.empty maps pages lazily: only lengths and dtypes are read
    tables = {"store": {"cust": np.empty(n_store, np.int32),
                        "item": np.empty(n_store, np.int32)},
              "catalog": {"cust": np.empty(n_cat, np.int32),
                          "item": np.empty(n_cat, np.int32)}}
    return q97_plan(cap), tables, cap


def rehearse(cell, topo) -> dict:
    from spark_rapids_jni_tpu.parallel import make_mesh
    from spark_rapids_jni_tpu.plans.compiler import compile_plan
    from spark_rapids_jni_tpu.plans.runtime import input_signature_raw

    if cell.traffic["query"] != "q97":
        return {"cell": cell.name, "skipped": "no rehearsal for this query"}
    plan, tables, cap = _q97_plan_inputs(cell)
    mesh = make_mesh((cell.chips, 1), devices=topo.devices[:cell.chips])
    sig = input_signature_raw(plan, tables, cell.chips)
    cp = compile_plan(plan, mesh, sig)
    out = {"cell": cell.name, "chips": cell.chips, "capacity": cap,
           "signature": [list(s) for s in sig], "aot": cp.aot,
           "aot_error": cp.aot_error, "trace_s": cp.trace_s,
           "compile_s": cp.compile_s}
    if cp.aot:
        ma = cp.fn.memory_analysis()
        out["memory_analysis"] = {
            k: getattr(ma, k) for k in dir(ma)
            if k.endswith("_in_bytes") and isinstance(getattr(ma, k), int)}
        hlo = cp.fn.as_text()
        out["hlo_sorts"] = len(re.findall(r"\bsort\(", hlo))
        out["hlo_all_to_alls"] = len(re.findall(r"all-to-all\(", hlo))
    return out


def main(argv=None) -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import json as _json

    from jax.experimental import topologies

    from benchmark import cells

    names = list(argv if argv is not None else sys.argv[1:])
    if not names:
        with open(os.path.join(cells.ROOT, "BENCHMARK.json")) as f:
            names = [w["name"] for w in _json.load(f)["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    for name in names:
        print(json.dumps(rehearse(cells.load_cell(name), topo)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
