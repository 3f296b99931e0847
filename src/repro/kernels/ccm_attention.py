"""Flash attention with the CCM block-sparse mask — Pallas TPU kernel.

Mask predicate per (q, k): causal AND (same-segment OR key-is-<COMP>) AND
key-valid — evaluated per (block_q x block_k) tile from i32 metadata vectors
(VMEM-resident, SMEM-sized). Tiles that cannot contain any visible key
(k-segment strictly ahead of every q-segment and no <COMP>/memory key in the
tile, or entirely a-causal) are *skipped*: since <COMP> keys are a few
percent of the sequence, the off-diagonal cost collapses to the comp columns
and the effective FLOPs approach block-diagonal + t*m gather columns
(DESIGN §3/§4).

Layouts: q (B, Hq, Sq, D), k/v (B, Hkv, Sk, D) — GQA is handled by the
k/v index_map (h // group), no repetition is materialized.

Grid: (B, Hq, nq, nk); the k dimension is 'arbitrary' (sequential) with
running-softmax state in VMEM scratch — the canonical TPU flash pattern.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qidx_ref, qseg_ref, kidx_ref, kseg_ref, kcomp_ref, kval_ref,
            q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, scale: float, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qidx = qidx_ref[0, :]                       # (bq,) i32
    qseg = qseg_ref[0, :]
    kidx = kidx_ref[0, :]                       # (bk,) i32
    kseg = kseg_ref[0, :]
    kcomp = kcomp_ref[0, :]                     # i32 {0,1}
    kval = kval_ref[0, :]

    # ---- tile-level visibility precheck (block sparsity) ----
    causal_possible = jnp.min(kidx) <= jnp.max(qidx)
    has_comp = jnp.max(kcomp * kval) > 0
    seg_overlap = (jnp.min(kseg) <= jnp.max(qseg)) & \
                  (jnp.max(kseg) >= jnp.min(qseg))
    visible = causal_possible & (has_comp | seg_overlap)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)      # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)      # (bk, D)
        v = v_ref[0, 0]                          # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        mask = (kidx[None, :] <= qidx[:, None]) \
            & ((kseg[None, :] == qseg[:, None]) | (kcomp[None, :] > 0)) \
            & (kval[None, :] > 0)
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_ref[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] \
            + jax.lax.dot_general(p, v.astype(jnp.float32),
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        m_ref[:, 0] = m_new
        l_ref[:, 0] = l_new

    @pl.when(ik == nk - 1)
    def _final():
        l = jnp.maximum(l_ref[:, 0], 1e-37)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def ccm_flash_attention(q, k, v, q_idx, q_seg, k_idx, k_seg, k_comp, k_valid,
                        scale: float, block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """q (B,Hq,Sq,D); k/v (B,Hkv,Sk,D); metadata i32 (Sq,)/(Sk,).

    Sq/Sk must be multiples of block_q/block_k (ops.py pads).
    ``interpret=None`` backend-selects like ops.py: compiled on TPU,
    Pallas interpreter elsewhere — direct callers no longer silently run
    the interpreter on TPU.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Hq, Sq, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    G = Hq // Hkv
    nq, nk = Sq // block_q, Sk // block_k

    def im_q(b, h, iq, ik):
        return (b, h, iq, 0)

    def im_kv(b, h, iq, ik):
        return (b, h // G, ik, 0)

    def im_qmeta(b, h, iq, ik):
        return (0, iq)

    def im_kmeta(b, h, iq, ik):
        return (0, ik)

    grid = (B, Hq, nq, nk)
    kernel = functools.partial(_kernel, scale=scale, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q), im_qmeta),
            pl.BlockSpec((1, block_q), im_qmeta),
            pl.BlockSpec((1, block_k), im_kmeta),
            pl.BlockSpec((1, block_k), im_kmeta),
            pl.BlockSpec((1, block_k), im_kmeta),
            pl.BlockSpec((1, block_k), im_kmeta),
            pl.BlockSpec((1, 1, block_q, D), im_q),
            pl.BlockSpec((1, 1, block_k, D), im_kv),
            pl.BlockSpec((1, 1, block_k, D), im_kv),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), im_q),
        out_shape=jax.ShapeDtypeStruct((B, Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_idx[None, :], q_seg[None, :], k_idx[None, :], k_seg[None, :],
      k_comp[None, :], k_valid[None, :], q, k, v)
