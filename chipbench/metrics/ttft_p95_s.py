"""95th percentile, over every request that arrived in the window, of the
time from its scheduled arrival to the host holding its first token (host
clock; late first tokens are waited for after the window closes)."""

import numpy as np


def value(run):
    t = run.ttfts()
    return float(np.percentile(t, 95)) if t else None
