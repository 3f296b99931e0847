"""Find the arena's whole-slab ops in a reduced trace by shape.

An op touches a whole slab leaf when its HLO text (the trace's
``long_name``) names an array with as many elements as one of the
arena's key/value slabs, whatever its dims (a reshape or a relayout
keeps the count): ``(n_slots + 1) x layers x tokens x kv_heads x
head_dim`` for the memory (``max_steps * comp_len`` tokens) and for the
cache (``cache_len`` tokens).  The fused steps are the programs that
hold such an op.
"""
from __future__ import annotations

import re
from typing import Optional, Set

_ARRAY = re.compile(r"\b(?:bf16|f32|f16|s32|u32|s8|u8|pred)\[([\d,]*)\]")


def slab_sizes(ctx) -> Set[int]:
    m, e = ctx.dims, ctx.config["engine"]
    rows = e["n_slots"] + 1
    per_tok = m.n_layers * m.n_kv * m.hd
    return {rows * per_tok * m.max_steps * m.comp_len,
            rows * per_tok * e["cache_len"]}


def touches_slab(long_name: str, sizes: Set[int]) -> bool:
    for dims in _ARRAY.findall(long_name):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        if n in sizes:
            return True
    return False


def _slab_ops(ctx):
    sizes = slab_sizes(ctx)
    for d in ctx.trace.devices:
        for o in d.ops:
            if o.long_name and touches_slab(o.long_name, sizes):
                yield d, o


def slab_op_seconds(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    t = sum(o.dur_ns for _, o in _slab_ops(ctx)) * 1e-9
    return t or None


def fused_step_seconds(ctx) -> Optional[float]:
    """Device seconds of the program runs that hold a whole-slab op."""
    if ctx.trace is None:
        return None
    names = {o.module for _, o in _slab_ops(ctx) if o.module}
    t = sum(o.dur_ns for d in ctx.trace.devices for o in d.modules
            if o.name in names) * 1e-9
    return t or None
