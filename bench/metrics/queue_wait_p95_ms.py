"""95th percentile of the queue wait of every request submitted in the
window: the engine's trace events, ``popped`` minus ``submit`` (host
clock).  Layer: engine and scheduler (``serve/engine.py``,
``serve/scheduler.py``)."""
import numpy as np


def read(ctx):
    if not ctx.queue_waits:
        return None
    return float(np.percentile(ctx.queue_waits, 95)) * 1e3
