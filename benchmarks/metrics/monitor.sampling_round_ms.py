"""Mean milliseconds of a sampling round in the window: span
``monitor.sample_fetch`` over its own count (describe, the sampler plug-in,
ingest; the plug-in is the harness's stand-in)."""
from benchlib.spans import span_count, span_seconds


def read(ctx):
    rounds = span_count(ctx, ctx.param["spans"][0])
    if not rounds:
        return None
    return 1000.0 * span_seconds(ctx, ctx.param["spans"]) / rounds
