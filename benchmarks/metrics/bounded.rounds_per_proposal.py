"""Search rounds a proposal on the bounded per-goal route, narrow and wide
grids together, from the program's ``solver_dispatch_rounds{grid=}``
histogram (speculative dispatches run none). Nothing to read where the
window ran no bounded round, or where the series carry no ``grid``."""
from benchlib.bounded import ROUNDS, labelled, moved


def read(ctx):
    if not ctx.solves or not labelled(ctx.at_close):
        return None
    rounds = moved(ctx.at_setup, ctx.at_close, ROUNDS)
    return rounds / len(ctx.solves) if rounds else None
