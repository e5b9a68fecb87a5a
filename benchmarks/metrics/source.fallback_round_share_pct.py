"""The search rounds whose source selection reduced every broker's row, as
a percentage of the window's search rounds: the program's counter
``solver_source_fallback_rounds_total`` over its ``solver_dispatch_rounds``
histogram's sum, both under every label (the fused chain's rounds, the
bounded route's move and swap rounds; a swap round selects no sources).

Where the selection reduces the rows of its candidate brokers alone
(``analyzer/candidates.py:broker_blocks``), a round falls back to every
row only when fewer of those rows hold a finite best than the grid keeps
while a source broker may lie outside them: the share says how often the
shortcut missed. A program without the counter gives nothing to read."""

FALLBACKS = "solver_source_fallback_rounds_total"
ROUNDS = "solver_dispatch_rounds_sum"


def read(ctx):
    if not ctx.solves \
            or not any(n == FALLBACKS for n, _labels in ctx.at_close):
        return None
    rounds = ctx.delta(ROUNDS)
    if not rounds:
        return None
    return 100.0 * ctx.delta(FALLBACKS) / rounds
