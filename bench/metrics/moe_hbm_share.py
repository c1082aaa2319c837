"""Share of peak HBM bandwidth that the routed expert layer reaches in a
decode step: its least bytes per step, the routers of every layer and
the held experts that at least one token reached (the program's
``ServeReport.held_experts_hit`` over the traced replay's decode steps,
at the served dtype: the reference's ``Dims.router_bytes`` and
``Dims.expert_bytes``), over ``model/moe`` device ms per decode step of
the slice's decode-only dispatches, over peak bytes/s. A program that
counts no experts, or names no such scope, gives nothing."""

import scoped


def read(run):
    t = scoped.scope_times(run)
    if t is None or "model/moe" not in t.by_scope:
        return None
    hit = [getattr(r, "held_experts_hit", None) for r in run.reports]
    steps = sum(r.decode_rounds for r in run.reports)
    ms = t.ms_per_step("model/moe")
    if None in hit or not steps or not ms:
        return None
    moved = run.dims.router_bytes() + sum(hit) / steps * run.dims.expert_bytes()
    return 100.0 * moved / (ms * 1e-3) / run.peaks["hbm_bytes_per_s"]
