"""Goals a proposal leaves violated after their turn in the chain (what
``goalSummary`` renders as ``VIOLATED``), from the program's counter
``solver_goals_violated_after_total`` over the passes the window ran
(``pass_seq``: a request still in flight when the window closes has
counted its pass and is no solve of the window's).

A GUARD on quality, as ``scaleout.fill_pct`` is: ``balancedness_after``
lists its cells and the list is the benchmark's to extend, so this cell
reads the same thing as a count. It moves ``proposal_s`` in no direction
of its own; ``better: lower`` and ``moves`` are what the contract has to
be given and what ISSUE 34 named. A program without the counter (before
PR 34) gives nothing to read."""


def read(ctx):
    name = "solver_goals_violated_after_total"
    passes = ctx.delta("pass_seq")
    if not ctx.solves or not passes \
            or not any(n == name for n, _labels in ctx.at_close):
        return None
    return ctx.delta(name) / passes
