"""Device time of the serve program by the scopes it names on the device.

A TPU profile names each op by its HLO instruction alone. A traced
``Server.serve`` reports, in ``ServeReport.scopes``, an instruction ->
scope map for each compiled program it ran, keyed by chunk size: the
innermost of ``repro.obs.trace.SCOPES`` in each instruction's metadata
(a fusion takes the scope of the instruction it is named for). The runs
of the serve program in the traced slice are matched to its dispatches
in order, as ``step_hbm_share`` does, and each dispatch's chunk size
picks the map of the program that ran. Leaf ops only: loops and
conditionals count through the ops inside them.

The profiler can lose a program run's event and keep the run's ops.
Ops that lie in no program's run, between two runs, are then taken as
one lost run of the serve program, if they span at least half its
shortest run.

A program that reports no maps (one without named scopes) gives None,
as does a trace with no chip plane or runs that still do not match the
dispatches.
"""
from __future__ import annotations

import dataclasses

import xplane


@dataclasses.dataclass
class ScopeTimes:
    by_scope: dict  # scope -> device ns
    leaf_ns: float  # every leaf op's device ns in the runs counted
    decode_steps: int  # decode steps of the dispatches counted

    def ms_per_step(self, *scopes: str, prefix: str | None = None):
        """Device ms per decode step in ``scopes`` (or every scope that
        starts with ``prefix``); None without decode steps."""
        if not self.decode_steps:
            return None
        ns = sum(v for k, v in self.by_scope.items()
                 if k in scopes or (prefix is not None and k.startswith(prefix)))
        return ns * 1e-6 / self.decode_steps


def scope_times(run, decode_only: bool = True) -> ScopeTimes | None:
    """Device time by scope over the slice's runs of the serve program;
    with ``decode_only``, only the dispatches that splice no prompt chunk
    (so attention is decode attention alone)."""
    if run.trace is None or not run.trace.ops or not run.reports:
        return None
    maps = getattr(run.reports[0], "scopes", None)
    if not maps:
        return None
    runs = serve_runs(run.trace, run.program, len(run.dispatches))
    if runs is None:
        return None
    if any(d.steps not in maps for d in run.dispatches):
        return None
    out = ScopeTimes({}, 0.0, 0)
    ops, i = run.trace.ops[0], 0
    for r, d in zip(runs, run.dispatches):
        while i < len(ops) and ops[i].start < r.start:
            i += 1
        if decode_only and (d.prefill or not d.steps):
            continue
        out.decode_steps += d.steps
        scope = maps[d.steps]
        j = i
        while j < len(ops) and ops[j].start < r.end:
            e = ops[j]
            j += 1
            if any(c in e.name for c in xplane.CONTAINERS):
                continue
            dur = e.end - e.start
            out.leaf_ns += dur
            s = scope.get(xplane.op_name(e.name))
            if s is not None:
                out.by_scope[s] = out.by_scope.get(s, 0.0) + dur
    return out


def serve_runs(trace, program: str, n: int) -> list | None:
    """The ``n`` runs of the serve program in the slice, in order, with
    any run whose event the profile lost rebuilt from its ops; None
    where that does not give ``n``."""
    runs = xplane.program_runs(trace, program)
    if len(runs) >= n:
        return runs if len(runs) == n else None
    if not runs:
        return None
    lo, hi = xplane.window(trace)
    mods = [m for m in trace.modules[0] if m.end > lo and m.start < hi]
    # ops in no module's run, grouped by the module that follows them
    # (a chip runs one program at a time, so runs do not overlap)
    lost: dict[int, list] = {}
    k = 0
    for e in trace.ops[0]:
        if e.end <= lo or e.start >= hi:
            continue
        while k < len(mods) and mods[k].end <= e.start:
            k += 1
        if k == len(mods) or e.start < mods[k].start:
            span = lost.setdefault(k, [e.start, e.end])
            span[1] = max(span[1], e.end)
    shortest = min(r.end - r.start for r in runs)
    runs = sorted(runs + [xplane.Event(program, s, t) for s, t in lost.values()
                          if t - s >= shortest / 2], key=lambda r: r.start)
    return runs if len(runs) == n else None
