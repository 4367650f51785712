"""The chip benchmark's entry point: one run of one cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout, on a machine whose JAX finds the TPU
chips the cell asks for; there is no CPU fallback.  Each run is a new
process: it loads the cell named in ``BENCHMARK.json``, sets up, measures
for ``--seconds``, checks what the window served against the plain
reference, and prints one JSON object as its last line.  With ``--trace 0``
its metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  Lines before
it, and the checks on standard error, are diagnostics.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="also keep the traced window's .xplane.pb here")
    args = ap.parse_args(argv)

    from chipbench import harness, spec
    cell = spec.load_cell(ROOT, args.workload)
    try:
        result, _, notes = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), T_START,
            trace_dir=args.trace_dir)
    except harness.NoChip as e:
        print(f"chipbench: {e}; no result", file=sys.stderr)
        return 2
    for line in notes:
        print(line, flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
