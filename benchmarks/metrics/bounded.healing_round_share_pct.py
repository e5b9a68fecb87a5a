"""The bounded route's move rounds that built the per-slot offline mask, as
a percentage of all its rounds in the window: the program's counter
``solver_healing_rounds_total{grid="narrow"|"wide"}`` over
``solver_dispatch_rounds{grid="narrow"|"wide"}`` (move and swap rounds;
the fused chain's ``grid="fused"`` left out).

A round heals while a replica is still on a DEAD broker (a removed one in
a drain) and its goal moves replicas: ``round.score_offline`` then builds
the ``[P, S]`` mask and the offline priority (``analyzer/chain.py:
_self_healing``). So the share says how much of a drain's search is
evacuation; a change that evacuates in fewer rounds lowers it. A program
whose bounded route counts no healing rounds gives nothing to read."""
from benchlib.bounded import GRIDS, ROUNDS, moved

HEALING = "solver_healing_rounds_total"


def read(ctx):
    if not ctx.solves or not any(
            n == HEALING and any(f'grid="{g}"' in labels for g in GRIDS)
            for n, labels in ctx.at_close):
        return None
    rounds = moved(ctx.at_setup, ctx.at_close, ROUNDS)
    if not rounds:
        return None
    return 100.0 * sum(ctx.delta(HEALING, grid=g) for g in GRIDS) / rounds
