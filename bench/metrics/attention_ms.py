"""Device milliseconds per decode step in paged attention: the ops the
serve program names ``model/attention`` (each layer's KV write into the
pool, the gather of the pool and the attend), over the traced slice's
decode-only dispatches (``bench/scoped.py``)."""

import scoped


def read(run):
    t = scoped.scope_times(run)
    return None if t is None else t.ms_per_step("model/attention")
