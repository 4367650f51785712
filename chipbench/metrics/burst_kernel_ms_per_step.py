"""Device time of the Medusa burst kernels (the fused gather and scatter)
inside the decode program, per decode call, from the trace."""


def value(run):
    t = run.trace
    if not t or not t["decode_calls"] or not t["kernel_calls"]:
        return None
    return 1e3 * t["kernel_s"] / t["decode_calls"]
