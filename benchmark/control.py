"""Read the control of a cell's comparison at the cell's own size.

    python3 -m benchmark.control --workload <cell> --seeds 1 2 3

For each seed, generate the cell's tables, compute the plain reference and
its control (the reference one precision down, the query driver's
``control``), and print one JSON line with the control's checks beside
their limits.  The control has to come out not correct on every seed; the
readings set the upper end of each limit (PERF.md).  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from benchmark import cells


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = cells.load_cell(args.workload)
    q = cell.query
    for seed in args.seeds:
        t0 = time.perf_counter()
        tables = q.generate(cell.config, seed)
        t1 = time.perf_counter()
        want = q.reference(tables)
        t2 = time.perf_counter()
        got = q.control(tables)
        failed, checks = q.checks([got], want)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "reference": want,
            "control": got, "control_correct": failed == 0 and all(
                c["value"] <= c["limit"] for c in checks.values()),
            "checks": checks, "generate_s": t1 - t0, "reference_s": t2 - t1,
            "control_s": time.perf_counter() - t2}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
