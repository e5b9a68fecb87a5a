"""95th percentile of every read that was due in the window, from its due
time to its last byte. A failed or refused read counts as over any value:
it reads as its timeout."""
import math


def read(ctx):
    if not ctx.reads:
        return None
    timeout_ms = 1000.0 * float(ctx.mix["reads"].get("timeout_s", 30))
    ms = sorted(1000.0 * (r.ended - r.due) if r.status == 200 else timeout_ms
                for r in ctx.reads)
    return ms[math.ceil(0.95 * len(ms)) - 1]
