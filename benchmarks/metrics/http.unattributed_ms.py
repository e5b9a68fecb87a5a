"""Milliseconds of a proposal's root span ``http.request`` that none of
the named pieces covers (serialize, write, model refresh, optimizer, render):
front door, task engine, facade, and whatever no span covers."""
from benchlib.spans import (
    endpoint, ms_per_solve, segment_seconds, span_count, span_seconds,
)


def read(ctx):
    p, at = ctx.param, endpoint(ctx)
    if not ctx.solves or not span_count(ctx, p["root"], endpoint=at):
        return None
    rest = span_seconds(ctx, [p["root"]], endpoint=at) \
        - span_seconds(ctx, p["less_http_spans"], endpoint=at) \
        - span_seconds(ctx, p["less_spans"]) \
        - segment_seconds(ctx, p["less_segments"], at)
    return ms_per_solve(ctx, rest)
