"""Device-busy milliseconds of the solver's programs over the search
rounds of the traced proposals (the bodies' own counts)."""
from benchlib.metrics import program_seconds, rounds


def read(ctx):
    n = sum(rounds(s) for s in ctx.traced_solves)
    seconds = program_seconds(ctx) if ctx.trace is not None and n else None
    return 1000.0 * seconds / n if seconds else None
