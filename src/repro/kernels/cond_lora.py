"""Fused conditional-LoRA matmul — Pallas TPU kernel.

y = x @ W + gate * ((x @ A^T) @ B) * scale, gate in {0,1} per row
(1 at <COMP> tokens). Both matmuls and the gate are fused in one VMEM pass:
the rank-r intermediate (block_m x r) lives entirely in scratch, the base
GEMM accumulates in fp32, and the delta is applied at the final k-step —
no separate LoRA kernel launch, no gather of <COMP> rows (DESIGN §3).

Grid (nm, nn, nk): k sequential ('arbitrary') with fp32 accumulators.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, a_ref, b_ref, g_ref, o_ref,
            acc_ref, xa_ref, *, scale: float, nk: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        xa_ref[...] = jnp.zeros_like(xa_ref)

    x = x_ref[...].astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        x, w_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    xa_ref[...] += jax.lax.dot_general(
        x, a_ref[...].astype(jnp.float32), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _final():
        delta = jax.lax.dot_general(
            xa_ref[...], b_ref[...].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        gate = g_ref[...].astype(jnp.float32)      # (bm, 1)
        o_ref[...] = (acc_ref[...] + delta * gate).astype(o_ref.dtype)


def cond_lora_matmul(x, w, a, b, gate, scale: float,
                     block_m: int = 128, block_n: int = 128,
                     block_k: int = 512, interpret: bool = True):
    """x (M, K); w (K, N); a (r, K); b (r, N); gate (M,). Returns (M, N).

    M/N/K must be multiples of the block sizes (ops.py pads).
    """
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[0]
    nm, nn, nk = M // block_m, N // block_n, K // block_k
    kernel = functools.partial(_kernel, scale=scale, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda im, i_n, ik: (im, ik)),
            pl.BlockSpec((block_k, block_n), lambda im, i_n, ik: (ik, i_n)),
            pl.BlockSpec((r, block_k), lambda im, i_n, ik: (0, ik)),
            pl.BlockSpec((r, block_n), lambda im, i_n, ik: (0, i_n)),
            pl.BlockSpec((block_m, 1), lambda im, i_n, ik: (im, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n),
                               lambda im, i_n, ik: (im, i_n)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_m, block_n), jnp.float32),
            pltpu.VMEM((block_m, r), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w, a, b, gate[:, None])
