"""Rounds a physical dispatch on the bounded route: the bounded rounds of
the window over its bounded megastep dispatches, speculative ones
included (each is an XLA execution the pump pays for). What the adaptive
controller achieves; the pump pays per dispatch."""
from benchlib.bounded import DISPATCHES, ROUNDS, labelled, moved


def read(ctx):
    if not ctx.solves or not labelled(ctx.at_close):
        return None
    dispatches = moved(ctx.at_setup, ctx.at_close, DISPATCHES)
    if not dispatches:
        return None
    return moved(ctx.at_setup, ctx.at_close, ROUNDS) / dispatches
