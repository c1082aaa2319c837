"""Device milliseconds per decode step in the attention of the layers
that attend through a window: the ops the serve program names
``model/window_attention`` (KV write, gather, windowed attend), over the
traced slice's decode-only dispatches (``bench/scoped.py``). A program
that names no such scope gives nothing."""

import scoped


def read(run):
    t = scoped.scope_times(run)
    if t is None or "model/window_attention" not in t.by_scope:
        return None
    return t.ms_per_step("model/window_attention")
