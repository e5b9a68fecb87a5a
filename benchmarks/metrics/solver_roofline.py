"""The least time the chip could take for one proposal (the bytes the
problem needs over the peak bandwidth: the bound is memory, goal evaluation
does a few operations a byte) over the device-busy seconds the solver's
programs took for it in the trace."""
from benchlib.bytes_model import peak, proposal_bytes
from benchlib.metrics import program_seconds


def read(ctx):
    if ctx.trace is None or not ctx.trace["requests"]:
        return None
    busy_s = program_seconds(ctx)
    if not busy_s:
        return None
    least_s = proposal_bytes(ctx.cfg) / peak(
        ctx.device["kind"])["hbm_bytes_per_s"]
    return 100.0 * least_s / (busy_s / ctx.trace["requests"])
