"""The wide grids' rounds as a percentage of all the bounded route's
rounds in the window (``solver_dispatch_rounds{grid="wide"}`` over
``narrow`` and ``wide``).

A GUARD, as ``scaleout.fill_pct`` is one: above 0 says the wide grids ran
(the optimizer widens the goals that prefer wide batches from
``solver.wide.batch.min.brokers`` on); a change that moves rounds between
the grids, which cost about four times apart a round, shows here. It moves
``proposal_s`` in no fixed direction; ``better`` and ``moves`` are what
the contract has to be given."""
from benchlib.bounded import ROUNDS, labelled, moved


def read(ctx):
    if not ctx.solves or not labelled(ctx.at_close):
        return None
    rounds = moved(ctx.at_setup, ctx.at_close, ROUNDS)
    if not rounds:
        return None
    return 100.0 * moved(ctx.at_setup, ctx.at_close, ROUNDS,
                         grids=("wide",)) / rounds
