"""95th percentile, over every request that arrived in the window, of the
time from its scheduled arrival to the start of its prefill, which the
engine stamps on the host clock (``Request.admitted_s``); a request still
not admitted when the wait after the window gave up counts that wait, as
``ttft_p95_s`` does.  A program without the stamp reports nothing."""

import numpy as np


def value(run):
    arrived = run.arrived_in_window()
    if not arrived or not hasattr(arrived[0].req, "admitted_s"):
        return None
    return float(np.percentile(
        [(tr.req.admitted_s if tr.req.admitted_s is not None
          else run.drained_at) - (run.open + tr.ask.arrival)
         for tr in arrived], 95))
