"""Host milliseconds per dispatch of the serve loop's own work over the
traced replay: the loop's wall time, from its first ``admit`` span to the
end of its last chunk span, less the ``dispatch`` spans (the calls into
the compiled program, which wait while the device's queue is full) and
the harness's ``profile_stop``, over the number of dispatches. It holds
the scheduler, the per-round host arrays and their transfer, and the
spans themselves."""

LOOP = ("admit", "prefill_chunk", "decode_chunk")
NOT_HOST = ("dispatch", "profile_stop")


def read(run):
    loop = [s for s in run.spans if s[0] in LOOP]
    dispatches = sum(1 for s in loop if s[0] != "admit")
    if not dispatches:
        return None
    lo, hi = min(s[1] for s in loop), max(s[2] for s in loop)
    other = sum(max(0.0, min(t1, hi) - max(t0, lo))
                for name, t0, t1, _ in run.spans if name in NOT_HOST)
    return 1e3 * (hi - lo - other) / dispatches
