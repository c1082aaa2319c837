"""Share of peak HBM bandwidth that decode steps reach: the least bytes
each step must move (the reference's ``Dims.decode_step_bytes``: every
weight once, each active slot's KV context) over the device time of the serve program's runs
dispatched under ``decode_chunk`` spans, over peak bytes/s."""

import xplane


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    runs = xplane.program_runs(run.trace, run.program)
    if len(runs) != len(run.dispatches):
        return None
    moved = seconds = 0.0
    for r, d in zip(runs, run.dispatches):
        if d.prefill or not d.steps:
            continue
        moved += sum(run.dims.decode_step_bytes(c) for c in d.decode_contexts)
        seconds += (r.end - r.start) * 1e-9
    if not seconds:
        return None
    return 100.0 * moved / seconds / run.peaks["hbm_bytes_per_s"]
