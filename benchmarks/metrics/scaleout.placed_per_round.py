"""Replicas placed on the new brokers a search round of the passes that ran
with a new broker present, from the program's counters: how much a few
destinations take in a round, which is what rounds x ms a round turns on in
a scale-out. A program without the counters (before PR 32) gives nothing to
read."""


def read(ctx):
    placed, rounds = ("solver_scale_out_replicas_total",
                      "solver_scale_out_rounds_total")
    names = {n for n, _labels in ctx.at_close}
    if not ctx.solves or placed not in names or rounds not in names \
            or not ctx.delta(rounds):
        return None
    return ctx.delta(placed, onto="new") / ctx.delta(rounds)
