"""Device-busy milliseconds of the solver's programs over the search
rounds of the traced proposals (the bodies' own counts)."""
from benchlib.metrics import program_seconds, rounds


def read(ctx):
    n = sum(rounds(s) for s in ctx.traced_solves)
    if ctx.trace is None or not n:
        return None
    return 1000.0 * program_seconds(ctx) / n or None
