"""Memory blocks the interpreter's allocator holds
(``sys.getallocatedblocks()``, gauge ``python_allocated_blocks``, which the
program reads at the end of every full collection: the heap without its
garbage) at the last full collection of the window less at the last one
of set-up, over the window's proposals: what a proposal leaves behind for
the next full collection to walk. 0 where the window held no full
collection; may read below 0 (what set-up left is freed)."""
from benchlib.collector import BLOCKS, watched


def read(ctx):
    if not watched(ctx) or not ctx.solves:
        return None
    return ctx.delta(BLOCKS) / len(ctx.solves)
