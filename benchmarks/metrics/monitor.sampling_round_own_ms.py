"""``monitor.sampling_round_ms`` with the collector's pauses taken out:
span ``monitor.sample_fetch`` less its ``trace_span_gc_seconds_total``,
over its own count."""
from benchlib.collector import span_pauses, watched
from benchlib.spans import span_count, span_seconds


def read(ctx):
    spans = ctx.param["spans"]
    rounds = span_count(ctx, spans[0])
    if not watched(ctx) or not rounds:
        return None
    return 1000.0 * (span_seconds(ctx, spans) - span_pauses(ctx, spans)) \
        / rounds
