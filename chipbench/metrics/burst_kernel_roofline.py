"""Share of their roofline that the decode program's burst kernels reach:
the bytes they must move per step (``costs.burst_kernel_bytes`` from each
step's live frames and batch, averaged over the window's decode steps) at
the chip's HBM bandwidth, over their device time per decode call in the
trace.  The kernels do no arithmetic, so bytes bound them."""

from chipbench import costs


def value(run):
    t = run.trace
    steps = [s for s in run.window_steps() if s.batch]
    if not t or not t["kernel_s"] or not t["decode_calls"] or not steps:
        return None
    need = sum(costs.burst_kernel_bytes(run.cell.conf, s.live_frames,
                                        s.batch) for s in steps) / len(steps)
    per_call = t["kernel_s"] / t["decode_calls"]
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / per_call
