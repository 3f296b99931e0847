"""Share of its roofline that the arena gather and scatter reach.

Work: the bytes the algorithm needs (``bench/counts.py``): per fused
step of B lanes, B rows of every slab leaf read and written by the
gather and B rows of every leaf the op writes read and written by the
scatter.  The bound is bytes, over the chip's HBM bandwidth
(``bench/peaks.json``); data movement has no FLOPs.  Time: the device
time of every op in the fused steps that reads or writes a whole slab
leaf (Pallas kernels, XLA gathers, scatters and dynamic-update fusions,
and the layout copies around them), found by operand shape in the ops'
HLO text (``bench/arena.py``), whatever implements them.  Layer:
kernels (``kernels/session_gather.py``) and the arena."""
from bench import arena, counts


def read(ctx):
    t = arena.slab_op_seconds(ctx)
    if not t or not ctx.peaks:
        return None
    lanes = ctx.counters["serve_lanes_total"]
    cache_len = ctx.config["engine"]["cache_len"]
    need = sum(counts.gather_scatter_bytes(ctx.dims, cache_len, op,
                                           int(lanes.get(op, 0)))
               for op in ("ingest", "query"))
    return 100.0 * need / ctx.peaks["hbm_bytes_per_s"] / t
