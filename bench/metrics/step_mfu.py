"""Model FLOP/s utilization of the whole serve step over the traced
slice: operations of every prompt and output token processed (the
reference's ``Dims.token_flops``), over the slice's seconds, over peak
bf16 FLOP/s."""

import xplane


def read(run):
    if run.trace is None or not run.dispatches:
        return None
    flops = 0
    for d in run.dispatches:
        flops += sum(run.dims.token_flops(c) for c in d.prefill_contexts)
        for step in d.decode_contexts:
            flops += sum(run.dims.token_flops(c) for c in step)
    lo, hi = xplane.window(run.trace)
    return 100.0 * flops / ((hi - lo) * 1e-9) / run.peaks["bf16_flops_per_s"]
