"""Milliseconds a proposal spent building the response body: journey
segments ``render`` (summary, goal summary, load) and ``proposal_diff``
(the list of moves) of the requests of the cell's operation."""
from benchlib.spans import SEGMENTS, endpoint, ms_per_solve, segment_seconds


def read(ctx):
    p, at = ctx.param, endpoint(ctx)
    if not ctx.solves or not ctx.delta(SEGMENTS + "_count",
                                       segment=p["segments"][0],
                                       endpoint=at):
        return None
    return ms_per_solve(ctx, segment_seconds(ctx, p["segments"], at))
