"""Find the highest arrival rate an open-loop cell's program sustains: one
process sets the cell up once, then measures one window at each rate and
prints a line per rate.  A rate is sustained while the backlog at the
window's close stays small and the first-token tail does not grow with the
window.  Run on the chip, by hand, when a cell's rate is chosen:

    python chipbench/sweep.py --workload <cell> --seed 1 --seconds 20 \\
        --rates 1 2 3 4
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                             # noqa: E402
import copy                                                 # noqa: E402
import json                                                 # noqa: E402
import os                                                   # noqa: E402
import sys                                                  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True,
                    help="rates to measure, lowest first")
    ap.add_argument("--stop-unsustained", action="store_true",
                    help="stop at the first rate whose backlog grows")
    args = ap.parse_args()
    import numpy as np
    from chipbench import harness, spec
    from chipbench.loadgen import generator_module
    cell = spec.load_cell(ROOT, args.workload)
    sess = harness.setup(cell, args.seed, args.seconds)
    print("\n".join(sess.notes), flush=True)
    make = generator_module(cell.traffic["kind"]).make
    sent = 0
    for rate in args.rates:
        traffic = copy.deepcopy(cell.traffic)
        traffic["rate_per_s"] = rate
        sess.gen = make(traffic, args.seed, sess.deploy["max_slots"],
                        args.seconds, cell.conf["vocab_size"])
        for ask in sess.gen.asks:        # request ids unique over the sweep:
            ask.rid += sent              # the recorder keys first tokens by id
        sent += len(sess.gen.asks)
        sess.feeder.tracks.clear()
        sess.feeder.steps.clear()
        open_t, close_t = harness.measure(sess, args.seconds)
        tracks = list(sess.feeder.tracks.values())
        arrived = [t for t in tracks if t.ask.arrival is not None]
        backlog = sum(1 for t in arrived if t.times
                      and t.times[0] > close_t)
        ttft = [t.times[0] - (open_t + t.ask.arrival) for t in arrived
                if t.times]
        half = [t.times[0] - (open_t + t.ask.arrival) for t in arrived
                if t.times and t.ask.arrival > args.seconds / 2]
        done = sum(1 for t in arrived if t.req.done and t.times
                   and t.times[-1] <= close_t)
        print(json.dumps({
            "rate_per_s": rate, "arrived": len(arrived),
            "finished_in_window": done,
            "first_token_after_close": backlog,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else None,
            "ttft_p95_s": float(np.percentile(ttft, 95)) if ttft else None,
            "ttft_p95_second_half_s": (float(np.percentile(half, 95))
                                       if half else None),
            "window_s": close_t - open_t, "notes": sess.notes[-2:]}),
            flush=True)
        if args.stop_unsustained and backlog > 1:
            break
        while sess.feeder.busy():
            sess.feeder.step()
    return 0


if __name__ == "__main__":
    sys.exit(main())
