"""95th percentile of every gap between successive resolved tokens of one
request, pooled over all requests of the window, in milliseconds."""

import numpy as np


def read(run):
    gaps = [np.diff(r.generated.times) for r in run.requests()]
    gaps = np.concatenate([g for g in gaps if len(g)] or [np.zeros(0)])
    if not len(gaps):
        return None
    return float(np.percentile(gaps, 95)) * 1e3
