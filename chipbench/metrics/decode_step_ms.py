"""Device time of the jitted decode program per call, from the trace."""


def value(run):
    t = run.trace
    if not t or not t["decode_calls"]:
        return None
    return 1e3 * t["decode_s"] / t["decode_calls"]
