"""Share of decode rounds whose coded head erased no systematic block,
so the erasure decode passed them through with no solve, over the traced
replay: ``passthrough_rounds`` / ``decode_rounds`` of the program's
``ServeReport``. A program that does not count them gives nothing."""


def read(run):
    rounds = sum(r.decode_rounds for r in run.reports)
    passed = [getattr(r, "passthrough_rounds", None) for r in run.reports]
    if not rounds or None in passed:
        return None
    return 100.0 * sum(passed) / rounds
