"""Solver dispatches a proposal, from the program's counter."""


def read(ctx):
    n = ctx.delta("solver_dispatches_total")
    if not ctx.solves or not n:
        return None
    return n / len(ctx.solves)
