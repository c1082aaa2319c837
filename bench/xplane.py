"""Reduction of the profiler's ``.xplane.pb`` to the numbers the
per-layer metrics read.

A TPU profile holds one plane per chip (``/device:TPU:<n>``), whose
``XLA Ops`` line has one event per operation that ran on the chip and
whose ``XLA Modules`` line has one event per program run, and a host
plane (``/host:CPU``) whose thread lines hold the
``jax.profiler.TraceAnnotation`` spans. All are on one clock, in
nanoseconds.
"""
from __future__ import annotations

import dataclasses
import glob
import os

#: host spans kept: the harness's replay span and the serve loop's own
#: spans, which name the idle gaps under them
HOST_SPANS = ("replay", "serve_setup", "admit", "prepare", "prefill_chunk",
              "decode_chunk", "dispatch", "retire", "finish")
#: ops that contain other ops on the same line (their time is counted
#: again in the ops inside them)
CONTAINERS = (" while(", " conditional(", " call(")


@dataclasses.dataclass
class Event:
    name: str  # an op's event name is its whole HLO instruction
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Trace:
    ops: list  # per chip: list of Event, by start
    modules: list  # per chip: list of Event, by start
    host: list  # Event of HOST_SPANS, by start


def find(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            chip_ops, chip_mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    chip_ops = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
                elif line.name == "XLA Modules":
                    chip_mods = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                 for e in line.events]
            ops.append(sorted(chip_ops, key=lambda e: e.start))
            modules.append(sorted(chip_mods, key=lambda e: e.start))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name in HOST_SPANS)
    return Trace(ops, modules, sorted(host, key=lambda e: e.start))


def union(events, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged busy intervals of ``events`` clipped to [lo, hi]."""
    out: list[list[float]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, lo), min(e.end, hi)
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def window(trace: Trace) -> tuple[float, float]:
    """The traced slice: the harness's ``replay`` span(s)."""
    reps = [e for e in trace.host if e.name == "replay"]
    if not reps:
        raise ValueError("trace holds no replay span")
    return min(e.start for e in reps), max(e.end for e in reps)


def busy_ns(trace: Trace) -> float:
    """Device-busy ns in the slice, averaged over the chips."""
    lo, hi = window(trace)
    per_chip = [sum(t - s for s, t in union(ops, lo, hi)) for ops in trace.ops]
    return sum(per_chip) / len(per_chip) if per_chip else 0.0


def program_runs(trace: Trace, program: str, chip: int = 0) -> list:
    """Runs of the program whose module name contains ``program`` that
    overlap the slice, in order (the host and device planes are aligned
    to within microseconds, so a run may stick out of its replay span)."""
    if chip >= len(trace.modules):
        return []
    lo, hi = window(trace)
    return [m for m in trace.modules[chip]
            if program in m.name and m.end > lo and m.start < hi]


def ops_within(trace: Trace, runs, chip: int = 0) -> list:
    """Ops of chip ``chip`` that lie inside any of ``runs``."""
    out, ops, i = [], trace.ops[chip], 0
    for r in sorted(runs, key=lambda r: r.start):
        while i < len(ops) and ops[i].start < r.start:
            i += 1
        j = i
        while j < len(ops) and ops[j].start < r.end:
            out.append(ops[j])
            j += 1
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``%fusion.12``."""
    return event_name.split(" = ", 1)[0]


def top_ops(trace: Trace, n: int = 10) -> list:
    """[instruction, seconds] of the ops that took most device time in the
    slice (chip 0); loops and conditionals count through the ops inside."""
    lo, hi = window(trace)
    tot: dict[str, float] = {}
    for e in trace.ops[0] if trace.ops else []:
        if e.start >= lo and e.end <= hi and not any(c in e.name for c in CONTAINERS):
            key = op_name(e.name)
            tot[key] = tot.get(key, 0.0) + (e.end - e.start)
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in best]


def idle_gaps(trace: Trace, n: int = 10) -> list:
    """[host span open over the gap, seconds] of the longest idle gaps on
    chip 0 in the slice; the innermost span open at the gap's middle."""
    lo, hi = window(trace)
    busy = union(trace.ops[0], lo, hi) if trace.ops else []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, t in gaps[:n]:
        mid = (s + t) / 2
        open_ = [e for e in trace.host if e.start <= mid <= e.end]
        inner = min(open_, key=lambda e: e.end - e.start).name if open_ else "none"
        out.append([inner, (t - s) * 1e-9])
    return out


def describe(path: str, per_line: int = 5) -> str:
    """Planes, lines, event counts and a few events with their stats: for
    reading a trace by hand."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    out = []
    for plane in pd.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for e in evs[:per_line]:
                out.append(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                           f"stats={list(e.stats)}")
    return "\n".join(out)
