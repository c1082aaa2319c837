"""Device milliseconds per decode step in the routed expert layer: the
ops the serve program names ``model/moe`` (each layer's norm, router,
sort, grouped matmuls and combine), over the traced slice's decode-only
dispatches (``bench/scoped.py``). A program that names no such scope
gives nothing."""

import scoped


def read(run):
    t = scoped.scope_times(run)
    if t is None or "model/moe" not in t.by_scope:
        return None
    return t.ms_per_step("model/moe")
