"""Seconds jax spent tracing Python to jaxprs and lowering them to MLIR
from process start to the end of set-up: what a persistent-cache hit does
not skip. The program's histograms hold each event's own seconds (a nested
jit's trace and a constant's compile taken out of the trace that holds
them), so the sum counts no second twice."""
from benchlib.sut import series_total


def read(ctx):
    histograms = ctx.param["histograms"]
    if not series_total(ctx.at_setup, histograms[0] + "_count"):
        return None
    return sum(series_total(ctx.at_setup, h + "_sum") for h in histograms)
