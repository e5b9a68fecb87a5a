"""Request milliseconds outside the optimizer a proposal: monitor and
model refresh, facade, diff hand-over, render and HTTP."""


def read(ctx):
    if not ctx.solves:
        return None
    request_s = sum(s.ended - s.started for s in ctx.solves)
    solver_s = ctx.delta("analyzer_proposal_computation_seconds_sum")
    return 1000.0 * (request_s - solver_s) / len(ctx.solves)
