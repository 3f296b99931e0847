"""Model FLOPs of the window's real tokens (``bench/counts.py``: 2 x the
matmul weights each token passes, the LM head for query tokens, the
<COMP> tokens and their LoRA, attention over memory, cache and causal
prefix; no pad token) over the fused-step programs' device time times
the chip's bf16 peak (``bench/peaks.json``).  Layer: the model inside
the fused steps (``core/``, ``models/``)."""
from bench import arena


def read(ctx):
    t = arena.fused_step_seconds(ctx)
    if not t or not ctx.peaks:
        return None
    return 100.0 * sum(ctx.turn_flops) / (t * ctx.peaks["bf16_flops_per_s"])
