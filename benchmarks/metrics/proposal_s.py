"""Seconds a proposal, over the whole loop: from the window's first
instant (the first sampling round) to the last completed body, over the
proposals completed."""


def read(ctx):
    if not ctx.solves:
        return None
    return (ctx.solves[-1].ended - ctx.t0) / len(ctx.solves)
