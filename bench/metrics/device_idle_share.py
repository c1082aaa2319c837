"""Share of the traced slice in which no operation ran on the device:
1 - (union of the op intervals) / slice length."""

import xplane


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = xplane.window(run.trace)
    return 100.0 * (1.0 - xplane.busy_ns(run.trace) / (hi - lo))
