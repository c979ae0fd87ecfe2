"""95th percentile over the window's requests of the time from the round's
submission to the request first leaving the loop's admission queue (the
loop's ``Request.admitted`` stamp), in milliseconds: the queueing part of
TTFT.  Nothing to read from a program without the stamp."""

import numpy as np


def read(run):
    waits = [r.admitted - rd.submit for rd in run.rounds for r in rd.requests
             if getattr(r, "admitted", None) is not None]
    if not waits:
        return None
    return float(np.percentile(waits, 95)) * 1e3
