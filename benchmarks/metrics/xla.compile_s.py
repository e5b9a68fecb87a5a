"""Seconds in backend compiles from process start to the end of set-up."""
from benchlib.sut import series_total


def read(ctx):
    return series_total(ctx.at_setup, "xla_compile_seconds_sum")
