"""Device milliseconds per decode step in the coded head's erasure solve:
the ops the serve program names ``coded_head/solve`` (survivor gather,
LU factorisation, two triangular solves and the refinement), over the
traced slice's decode-only dispatches (``bench/scoped.py``)."""

import scoped


def read(run):
    t = scoped.scope_times(run)
    return None if t is None else t.ms_per_step("coded_head/solve")
