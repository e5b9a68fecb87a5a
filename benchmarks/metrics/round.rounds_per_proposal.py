"""Search rounds a proposal, from the served bodies' counts."""
from benchlib.metrics import rounds


def read(ctx):
    if not ctx.solves:
        return None
    return sum(rounds(s) for s in ctx.solves) / len(ctx.solves)
