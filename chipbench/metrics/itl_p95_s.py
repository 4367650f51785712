"""95th percentile of every gap between consecutive output tokens of a
request, both tokens within the window (host clock)."""

import numpy as np


def value(run):
    gaps = run.itl_gaps()
    return float(np.percentile(gaps, 95)) if gaps else None
