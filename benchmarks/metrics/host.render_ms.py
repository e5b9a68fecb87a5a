"""Milliseconds a proposal spent building the response body: journey
segments ``render`` (summary, goal summary, load) and ``proposal_diff``
(the list of moves) of the endpoint's requests."""
from benchlib.spans import SEGMENTS, ms_per_solve, segment_seconds


def read(ctx):
    p = ctx.param
    if not ctx.solves or not ctx.delta(SEGMENTS + "_count",
                                       segment=p["segments"][0],
                                       endpoint=p["endpoint"]):
        return None
    return ms_per_solve(ctx, segment_seconds(ctx, p["segments"],
                                             p["endpoint"]))
