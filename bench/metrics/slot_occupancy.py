"""Share of slot-rounds that emitted a token over the traced replays:
tokens / (slots x decode rounds), from the program's ``ServeReport``."""


def read(run):
    rounds = sum(r.decode_rounds for r in run.reports)
    if not rounds:
        return None
    return 100.0 * sum(r.tokens for r in run.reports) / (run.slots * rounds)
