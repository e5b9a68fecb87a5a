"""Search rounds a proposal spent in goals that were entered with replicas
still offline, from the program's counter. A program without the counter
(before PR 27) gives nothing to read."""


def read(ctx):
    name = "solver_evacuation_rounds_total"
    if not ctx.solves or not any(n == name for n, _labels in ctx.at_close):
        return None
    return ctx.delta(name) / len(ctx.solves)
