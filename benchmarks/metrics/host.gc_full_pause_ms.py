"""Milliseconds ONE full collection took in the window: generation 2's
added pause seconds over its added count. The number that grows with the
heap (a full collection walks every container the process keeps); 0 where
the window held none."""
from benchlib.collector import FULL, pause_seconds, pauses, watched


def read(ctx):
    if not watched(ctx):
        return None
    count = pauses(ctx, generation=FULL)
    return 1000.0 * pause_seconds(ctx, generation=FULL) / count \
        if count else 0.0
