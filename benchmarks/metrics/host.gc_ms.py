"""Milliseconds a proposal that Python's collector paused the process for:
the pause seconds of all three generations that the window added
(``python_gc_pause_seconds_sum``, timed by the program's own entry in
``gc.callbacks``), over the window's proposals. The harness's client
allocates in this process too; its collections stop the served threads
alike and are in ``proposal_s``, so they count (``benchlib/collector.py``)."""
from benchlib.collector import pause_seconds, watched


def read(ctx):
    if not watched(ctx) or not ctx.solves:
        return None
    return 1000.0 * pause_seconds(ctx) / len(ctx.solves)
