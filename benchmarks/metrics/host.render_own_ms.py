"""``host.render_ms`` with the collector's pauses taken out: journey
segments ``render`` and ``proposal_diff`` of the requests of the cell's
operation, less ``journey_segment_gc_seconds_total`` of the same, a
proposal. The render allocates a dict and two lists a move, so the pauses
land in it; what is left is the render's own work."""
from benchlib.collector import segment_pauses, watched
from benchlib.spans import SEGMENTS, endpoint, ms_per_solve, segment_seconds


def read(ctx):
    p, at = ctx.param, endpoint(ctx)
    if not watched(ctx) or not ctx.solves \
            or not ctx.delta(SEGMENTS + "_count", segment=p["segments"][0],
                             endpoint=at):
        return None
    return ms_per_solve(ctx, segment_seconds(ctx, p["segments"], at)
                        - segment_pauses(ctx, p["segments"], at))
