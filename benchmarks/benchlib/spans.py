"""What the readers of the program's own spans share: seconds that the
span and journey-segment histograms moved inside the window.

Every live span of ``cruise_control_tpu/utils/tracing.py`` feeds
``trace_span_seconds{span=...}`` when it closes, and every journey segment
``journey_segment_seconds{endpoint=...,segment=...}`` when its request's
journey closes. A program without a span has no such series: the count
reads 0 and the reader returns None.
"""

from __future__ import annotations

from .metrics import Context

SPANS = "trace_span_seconds"
SEGMENTS = "journey_segment_seconds"


def endpoint(ctx: Context) -> str:
    """The ``endpoint`` label of the cell's own requests: its operation as
    the program's router names it (``proposals`` -> ``PROPOSALS``,
    ``remove_broker`` -> ``REMOVE_BROKER``)."""
    return str(ctx.cfg.get("operation", "proposals")).upper()


def span_count(ctx: Context, span: str, **labels) -> float:
    return ctx.delta(SPANS + "_count", span=span, **labels)


def span_seconds(ctx: Context, spans, **labels) -> float:
    return sum(ctx.delta(SPANS + "_sum", span=s, **labels) for s in spans)


def segment_seconds(ctx: Context, segments, endpoint: str) -> float:
    return sum(ctx.delta(SEGMENTS + "_sum", segment=s, endpoint=endpoint)
               for s in segments)


def ms_per_solve(ctx: Context, seconds: float) -> float:
    return 1000.0 * seconds / len(ctx.solves)


def read_spans(ctx: Context, **labels) -> float | None:
    """Milliseconds a proposal spent in the metric's ``spans``; None where
    the first of them never closed in the window."""
    if not ctx.solves or not span_count(ctx, ctx.param["spans"][0], **labels):
        return None
    return ms_per_solve(ctx, span_seconds(ctx, ctx.param["spans"], **labels))


def read_endpoint_spans(ctx: Context) -> float | None:
    """``read_spans`` over the requests of the cell's own endpoint (the
    ``http.*`` spans carry the label)."""
    return read_spans(ctx, endpoint=endpoint(ctx))
