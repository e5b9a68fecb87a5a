"""Device-busy milliseconds of the bounded megastep programs (the move and
swap drivers, donated or not) over the bounded rounds of the traced
requests, each request's rounds read from the program's counters before
and after it (``solver_dispatch_rounds{grid=}``)."""
from benchlib.bounded import ROUNDS, labelled, moved
from benchlib.metrics import program_seconds


def read(ctx):
    if ctx.trace is None or not ctx.traced_solves \
            or not labelled(ctx.at_close):
        return None
    n = sum(moved(s.before, s.after, ROUNDS) for s in ctx.traced_solves)
    seconds = program_seconds(ctx) if n else None
    return 1000.0 * seconds / n if seconds else None
