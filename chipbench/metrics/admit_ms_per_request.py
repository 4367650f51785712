"""Device time of every program outside the decode step (prefill, the
admission's install burst, the engine's small eager operations) in the
traced window, over the requests admitted in it."""


def value(run):
    t = run.trace
    admitted = sum(s.admitted for s in run.window_steps())
    if not t or not admitted:
        return None
    return 1e3 * t["other_s"] / admitted
