"""Readings that a cell's correctness limit is set from: for each seed, one
whole run of the cell (set-up, window, check) in one process.  On the first
``--control-seeds`` seeds the float8 control stands in the program's place
in the check, and its verdict is the run's ``correct``, which has to come
out false; the program's own reading of the same sample is printed beside
it.  Prints one JSON line per seed.  Run on the chip, by hand, when a cell
or a limit is set:

    python chipbench/calibrate.py --workload <cell> --seconds 20 \\
        --seeds 101 102 103 ... --control-seeds 4

The limit goes between the widest program gap over the seeds (the lower
reading) and the narrowest control gap (the upper reading); ``PERF.md``
records both and the limit in ``chipbench/limits/<cell>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="put the float8 control in the program's place on "
                         "the first this many seeds")
    args = ap.parse_args()
    from chipbench import harness, spec
    cell = spec.load_cell(ROOT, args.workload)
    for j, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        control = j < args.control_seeds
        result, run, notes = harness.run_cell(cell, seed, args.seconds,
                                              False, t0, control=control)
        print(json.dumps({
            "seed": seed, "control": control,
            "program_gap": run.readings[0] if run.readings else None,
            "control_gap": run.readings[1] if control else None,
            "correct": result["correct"], "checks": result["checks"],
            "requests_checked": run.checked[0],
            "tokens_checked": run.checked[1],
            "metrics": {k: v["value"] for k, v in result["metrics"].items()},
            "memory_peak_bytes": result["device"]["memory_peak_bytes"],
            "notes": notes, "seconds": time.perf_counter() - t0}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
