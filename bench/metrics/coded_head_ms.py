"""Device milliseconds per decode step in the coded LM head: the ops the
serve program names ``coded_head/*`` (the block mix, the finish mask and
the erasure solve), over the traced slice's decode-only dispatches
(``bench/scoped.py``)."""

import scoped


def read(run):
    t = scoped.scope_times(run)
    return None if t is None else t.ms_per_step(prefix="coded_head/")
