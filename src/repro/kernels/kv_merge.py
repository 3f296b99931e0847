"""CCM-merge memory-update kernels — Pallas TPU.

  kv_merge_update  — online update Mem(t) = (1-a_t) Mem(t-1) + a_t h(t),
                     a_t a runtime scalar (1/t arithmetic mean or EMA).
                     Elementwise, bandwidth-bound; blocked rows in VMEM.
  kv_cummean       — parallel-training form: running means over the time
                     axis, one sequential grid dim carrying the fp32
                     accumulator (associative-scan analogue).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _merge_kernel(a_ref, mem_ref, h_ref, o_ref):
    a = a_ref[0, 0]
    mem = mem_ref[...].astype(jnp.float32)
    h = h_ref[...].astype(jnp.float32)
    o_ref[...] = ((1.0 - a) * mem + a * h).astype(o_ref.dtype)


def kv_merge_update(mem, h, a, block_rows: int = 256,
                    interpret: bool = True):
    """mem/h: any shape (flattened to (R, C)); a: scalar fp32 weight."""
    shape = mem.shape
    C = shape[-1]
    R = mem.size // C
    memf = mem.reshape(R, C)
    hf = h.reshape(R, C)
    br = min(block_rows, R)
    nr = pl.cdiv(R, br)
    out = pl.pallas_call(
        _merge_kernel,
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda ir: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((br, C), lambda ir: (ir, 0)),
            pl.BlockSpec((br, C), lambda ir: (ir, 0)),
        ],
        out_specs=pl.BlockSpec((br, C), lambda ir: (ir, 0)),
        out_shape=jax.ShapeDtypeStruct((R, C), mem.dtype),
        interpret=interpret,
    )(jnp.asarray(a, jnp.float32).reshape(1, 1), memf, hf)
    return out.reshape(shape)


def _cummean_kernel(h_ref, o_ref, acc_ref, *, T: int):
    it = pl.program_id(1)

    @pl.when(it == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += h_ref[...].astype(jnp.float32)
    denom = (it + 1).astype(jnp.float32)
    o_ref[...] = (acc_ref[...] / denom).astype(o_ref.dtype)


def kv_cummean(h, block_cols: int = 512, interpret: bool = True):
    """h (T, R) -> running means along axis 0.

    Rows ride as (T, 1, R) so each (1, block_cols) block's trailing dims
    are legal on TPU: 1 is the whole unit dim, and the columns are the
    whole row or a multiple of 128."""
    T, R = h.shape
    bc = R if R <= block_cols else block_cols // 128 * 128
    ncol = pl.cdiv(R, bc)
    kernel = functools.partial(_cummean_kernel, T=T)
    out = pl.pallas_call(
        kernel,
        grid=(ncol, T),
        in_specs=[pl.BlockSpec((None, 1, bc), lambda ic, it: (it, 0, ic))],
        out_specs=pl.BlockSpec((None, 1, bc), lambda ic, it: (it, 0, ic)),
        out_shape=jax.ShapeDtypeStruct((T, 1, R), h.dtype),
        scratch_shapes=[pltpu.VMEM((1, bc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(h[:, None, :])
    return out[:, 0, :]
