"""95th percentile over the window's requests of the time from the round's
submission to the host resolving the request's first token, in
milliseconds (queueing inside the round plus prefill)."""

import numpy as np


def read(run):
    ttft = [r.generated.times[0] - rd.submit
            for rd in run.rounds for r in rd.requests if r.generated.times]
    if not ttft:
        return None
    return float(np.percentile(ttft, 95)) * 1e3
