"""Host milliseconds per dispatch in the serve loop's ``prepare`` spans
over the traced replay: building each round's prefill chunk and active
mask and handing the round's host arrays to the device. None where the
program opens no such span."""

CHUNKS = ("prefill_chunk", "decode_chunk")


def read(run):
    prepare = sum(t1 - t0 for name, t0, t1, _ in run.spans if name == "prepare")
    dispatches = sum(1 for s in run.spans if s[0] in CHUNKS)
    if not prepare or not dispatches:
        return None
    return 1e3 * prepare / dispatches
