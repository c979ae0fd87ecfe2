"""Share of the traced window in which no operation ran on the device:
1 - (union of device-op intervals) / window, averaged over the chips."""

import tracing


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    lo, hi = run.trace.window()
    busy = [tracing.busy_ns(ev, lo, hi) for ev in run.trace.devices.values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / (hi - lo))
