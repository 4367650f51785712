"""The whole decode step's share of the chip's peak: the least time the
chip could take for a step's required work (``costs.least_time`` of its
operations at the bf16 peak and its bytes at the HBM peak, whichever is
longer), averaged over the window's decode steps, over the decode
program's device time per call in the trace."""

from chipbench import costs


def value(run):
    t = run.trace
    steps = [s for s in run.window_steps() if s.batch]
    if not t or not t["decode_s"] or not t["decode_calls"] or not steps:
        return None
    conf = run.cell.conf
    least = sum(costs.least_time(costs.decode_step_flops(conf, s.batch,
                                                         s.context),
                                 costs.decode_step_bytes(conf, s.batch,
                                                         s.context),
                                 run.peaks)[0] for s in steps) / len(steps)
    return 100.0 * least / (t["decode_s"] / t["decode_calls"])
