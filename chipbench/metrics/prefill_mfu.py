"""Share of the chip's peak that admission and prefill reach: for each
request first served in the window, the least time its prompt's prefill
could take (``prefill_costs.least_time``: its operations at the bf16 peak
or its bytes at the HBM peak, whichever is longer), summed, over the
device time of every program outside the decode step in the traced window
(the prefills, the admissions' install bursts and the engine's small eager
operations)."""

from chipbench import prefill_costs


def value(run):
    t = run.trace
    served = [tr for tr in run.tracks.values()
              if tr.times and run.in_window(tr.times[0])]
    if not t or not t["other_s"] or not served:
        return None
    least = sum(prefill_costs.least_time(run.cell.conf, len(tr.req.prompt),
                                         run.peaks) for tr in served)
    return 100.0 * least / t["other_s"]
