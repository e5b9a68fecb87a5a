"""What the readers of Python's collector share: the pauses the program
counted inside the window, and a host span priced without them.

The program times every collection of the collector itself
(``cruise_control_tpu/utils/tracing.py:watch_collector``, one entry in
``gc.callbacks``) and publishes, when its registry renders:
``python_gc_collections_total{generation=}``,
``python_gc_pause_seconds_sum{generation=}`` / ``_count``, the gauge
``python_allocated_blocks`` (as the last full collection left it), and for
the pauses that overlapped a span or
a journey segment ``trace_span_gc_seconds_total{span=}`` /
``journey_segment_gc_seconds_total{endpoint=,segment=}`` under the labels
of the span's own histogram series.

The harness's client is a thread of the same process and allocates too (it
parses every body): a collection it sets off stops the served threads
alike and is inside ``proposal_s``, so it counts here as any other.

A program without the hook has no ``python_gc_*`` series: ``watched`` is
False and every reader returns None. With the hook a reader returns a
number, 0 included: the series exist at 0 from the install on.
"""

from __future__ import annotations

from .metrics import Context

COLLECTIONS = "python_gc_collections_total"
PAUSE_SECONDS = "python_gc_pause_seconds_sum"
PAUSE_COUNT = "python_gc_pause_seconds_count"
BLOCKS = "python_allocated_blocks"
SPAN_PAUSES = "trace_span_gc_seconds_total"
SEGMENT_PAUSES = "journey_segment_gc_seconds_total"
FULL = "2"      # the generation of a full collection


def watched(ctx: Context) -> bool:
    """The program counted its collections in this run."""
    return any(name == COLLECTIONS for name, _labels in ctx.at_close)


def pause_seconds(ctx: Context, **labels) -> float:
    return ctx.delta(PAUSE_SECONDS, **labels)


def pauses(ctx: Context, **labels) -> float:
    return ctx.delta(PAUSE_COUNT, **labels)


def span_pauses(ctx: Context, spans, **labels) -> float:
    """Seconds of the collector's pauses that overlapped the spans, under
    the labels ``spans.span_seconds`` takes."""
    return sum(ctx.delta(SPAN_PAUSES, span=s, **labels) for s in spans)


def segment_pauses(ctx: Context, segments, endpoint: str) -> float:
    """``span_pauses`` for journey segments (``spans.segment_seconds``)."""
    return sum(ctx.delta(SEGMENT_PAUSES, segment=s, endpoint=endpoint)
               for s in segments)
