"""Replicas a proposal moved off dead or removed brokers: what the passes
of the window found offline on entry less what they left, from the
program's counter, over the proposals. A program without the counter
(before PR 27) gives nothing to read."""


def read(ctx):
    name = "solver_offline_replicas_total"
    if not ctx.solves or not any(n == name for n, _labels in ctx.at_close):
        return None
    return (ctx.delta(name, when="before")
            - ctx.delta(name, when="remaining")) / len(ctx.solves)
