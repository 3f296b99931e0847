"""Device milliseconds per fused arena step: the device time of the
fused-step programs in the traced window over the fused steps the
engine ran in it (``serve_batches_total``).  A fused-step program is one
whose ops read or write a whole arena slab (see ``bench/arena.py``).
Layer: fused steps (``launch/serve.py`` ``make_arena_step``)."""
from bench import arena


def read(ctx):
    t = arena.fused_step_seconds(ctx)
    steps = sum(ctx.counters["serve_batches_total"].values())
    if t is None or steps <= 0:
        return None
    return 1e3 * t / steps
