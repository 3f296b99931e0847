"""Session-arena pack/unpack kernels — Pallas TPU.

The serving arena stores per-session state as slabs with a leading slot
axis.  Building a scheduler batch is a row gather (pack) and the
post-step writeback is a row scatter (unpack).  Both are pure DMA: the
scalar-prefetched slot ids drive the BlockSpec index maps, so each grid
step copies one row tile HBM->VMEM->HBM with no compute.

Rows are viewed as ``(S, 1, R)``: the slot axis is squeezed out of the
block, and the block's last two dims ``(1, block_cols)`` equal the
array's unit middle dim and either equal ``R`` or are a multiple of 128,
which is what the TPU lowering requires of every block (the (8, 128)
rule) — for bf16 KV rows, memory rows and ``R == 1`` counters alike.

  session_gather  — rows = slab[ids]          (B, 1, R) out of (S, 1, R)
  session_scatter — slab[ids] = rows, in place via input/output aliasing
                    (the kernel writes only the B touched rows)

Only the kernel moves O(B) rows.  A Mosaic operand is row-major, and
XLA's native TPU layout for a serve-arena leaf such as
``(S, L, 1, C, Hkv, D)`` bf16 is not (C is minor-most), so a caller
holding the slab in that layout pays a whole-slab relayout into the
view and back (`kernels.ops.session_gather`).

Duplicate ids in ``session_scatter`` (the scheduler's padding rows all
point at the arena's scratch slot) write the same row more than once;
any serialization order is acceptable since pad rows carry scratch data.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _block_cols(R: int, block_cols: int) -> int:
    """The whole row when it fits one block, else a 128-multiple tile."""
    return R if R <= block_cols else block_cols // 128 * 128


def _copy_kernel(ids_ref, src_ref, dst_ref):
    del ids_ref
    dst_ref[...] = src_ref[...]


def session_gather(slab, ids, block_cols: int = 16384,
                   interpret: bool = True):
    """slab (S, 1, R), ids (B,) int32 -> (B, 1, R) packed rows."""
    S, _, R = slab.shape
    B = ids.shape[0]
    bc = _block_cols(R, block_cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, pl.cdiv(R, bc)),
        in_specs=[pl.BlockSpec((None, 1, bc), lambda b, c, ids_ref:
                               (ids_ref[b], 0, c))],
        out_specs=pl.BlockSpec((None, 1, bc),
                               lambda b, c, ids_ref: (b, 0, c)),
    )
    return pl.pallas_call(
        _copy_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, 1, R), slab.dtype),
        interpret=interpret,
    )(ids.astype(jnp.int32), slab)


def _scatter_kernel(ids_ref, rows_ref, slab_ref, out_ref):
    del ids_ref, slab_ref
    out_ref[...] = rows_ref[...]


def session_scatter(slab, ids, rows, block_cols: int = 16384,
                    interpret: bool = True):
    """slab (S, 1, R), ids (B,), rows (B, 1, R) -> slab with
    slab[ids] = rows.

    The slab operand is aliased to the output: the kernel writes the B
    touched rows and leaves the rest of the donated buffer alone.
    """
    S, _, R = slab.shape
    B = ids.shape[0]
    bc = _block_cols(R, block_cols)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, pl.cdiv(R, bc)),
        in_specs=[
            pl.BlockSpec((None, 1, bc), lambda b, c, ids_ref: (b, 0, c)),
            # the aliased slab is only written: left in HBM, never read
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, 1, bc), lambda b, c, ids_ref:
                               (ids_ref[b], 0, c)),
    )
    return pl.pallas_call(
        _scatter_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, 1, R), slab.dtype),
        input_output_aliases={2: 0},   # slab (after the prefetched ids) -> out
        interpret=interpret,
    )(ids.astype(jnp.int32), rows, slab)
