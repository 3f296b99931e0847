"""jit'd public wrappers around the Pallas kernels: layout adaptation,
padding to block multiples, backend selection (TPU compiled / CPU interpret).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ccm_attention as _attn
from repro.kernels import cond_lora as _lora
from repro.kernels import decode_attention as _dattn
from repro.kernels import kv_merge as _merge
from repro.kernels import ref as _ref
from repro.kernels import session_gather as _sess


def _use_interpret() -> bool:
    return jax.default_backend() != "tpu"


_pad_axis = _ref.pad_axis


@functools.partial(jax.jit, static_argnames=("scale", "block_q", "block_k",
                                             "interpret"))
def ccm_attention(q, k, v, q_info, k_info, scale: float,
                  block_q: int = 128, block_k: int = 128,
                  interpret: Optional[bool] = None):
    """Drop-in for repro.models.attention.attend: q (B,Sq,Hq,D), k/v
    (B,Sk,Hkv,D), KeyInfo metadata. Returns (B,Sq,Hq,D)."""
    interpret = _use_interpret() if interpret is None else interpret
    B, Sq, Hq, D = q.shape
    Sk = k.shape[1]
    qt = _pad_axis(q.transpose(0, 2, 1, 3), block_q, 2)
    kt = _pad_axis(k.transpose(0, 2, 1, 3), block_k, 2)
    vt = _pad_axis(v.transpose(0, 2, 1, 3), block_k, 2)
    big = 2 ** 30
    q_idx = _pad_axis(q_info.idx.astype(jnp.int32), block_q, 0, fill=-big)
    q_seg = _pad_axis(q_info.seg.astype(jnp.int32), block_q, 0, fill=-3)
    k_idx = _pad_axis(k_info.idx.astype(jnp.int32), block_k, 0, fill=big)
    k_seg = _pad_axis(k_info.seg.astype(jnp.int32), block_k, 0, fill=-2)
    k_comp = _pad_axis(k_info.comp.astype(jnp.int32), block_k, 0, fill=0)
    valid = k_info.valid if k_info.valid is not None else \
        jnp.ones((Sk,), bool)
    k_val = _pad_axis(valid.astype(jnp.int32), block_k, 0, fill=0)
    out = _attn.ccm_flash_attention(
        qt, kt, vt, q_idx, q_seg, k_idx, k_seg, k_comp, k_val, scale,
        block_q=block_q, block_k=block_k, interpret=interpret)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)


def segmented_attention(q, segs, q_idx, q_seg, scale: float,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None):
    """Drop-in for repro.models.attention.attend_segments (impl='pallas'):
    q (B, Sq, Hq, D) over in-place KV segments — see
    decode_attention.segmented_flash_attention for the seg-dict schema.

    B is the LANE axis: segment ``length``/``layer`` may be per-lane
    ``(B,)`` vectors (metadata ``(B, S)``, q_idx/q_seg ``(B, Sq)``), and
    a per-lane stacked cache uses the lane-major ``(B, L, S, Hkv, D)``
    layout with ``lane_major=True`` — each lane then tile-skips past its
    own valid prefix (the serve engine's vmapped-session route).  Scalars
    / 1-D metadata broadcast to all lanes (the single-session layout).

    Not jitted here: hot paths call it from inside already-jitted steps
    and the segment list's None-structure is part of the trace."""
    return _dattn.segmented_flash_attention(
        q, segs, q_idx, q_seg, scale, block_q=block_q, block_k=block_k,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("scale", "block_m", "block_n",
                                             "block_k", "interpret"))
def cond_lora(x, w, a, b, gate, scale: float, block_m: int = 128,
              block_n: int = 128, block_k: int = 512,
              interpret: Optional[bool] = None):
    """x (M,K) @ w (K,N) + gate*(x@a.T@b)*scale — fused."""
    interpret = _use_interpret() if interpret is None else interpret
    M, K = x.shape
    N = w.shape[1]
    bm = min(block_m, M) if M % block_m else block_m
    xp = _pad_axis(_pad_axis(x, block_m, 0), block_k, 1)
    wp = _pad_axis(_pad_axis(w, block_k, 0), block_n, 1)
    ap = _pad_axis(a, block_k, 1)
    bp = _pad_axis(b, block_n, 1)
    gp = _pad_axis(gate.astype(x.dtype), block_m, 0)
    out = _lora.cond_lora_matmul(xp, wp, ap, bp, gp, scale,
                                 block_m=block_m, block_n=block_n,
                                 block_k=block_k, interpret=interpret)
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("interpret",))
def session_gather(slab, ids, interpret: Optional[bool] = None):
    """Arena pack in the fused serve steps: slab (S, ...), ids (B,) int32
    -> (B, ...) rows.

    TPU -> compiled Pallas DMA gather; elsewhere the pure-jnp ref (unless
    ``interpret=True`` forces the Pallas interpreter for validation).
    The kernel reads a row-major ``(S, 1, R)`` view.  On TPU the slab's
    native tiled layout is not row-major, so XLA relayouts the whole
    slab into that view: a call moves O(S) rows, not O(B).
    """
    if interpret is None and _use_interpret():
        return _ref.session_gather_ref(slab, ids)
    S = slab.shape[0]
    out = _sess.session_gather(slab.reshape(S, 1, -1), ids,
                               interpret=bool(interpret))
    return out.reshape((ids.shape[0],) + slab.shape[1:])


@functools.partial(jax.jit, static_argnames=("interpret",),
                   donate_argnums=(0,))
def session_scatter(slab, ids, rows, interpret: Optional[bool] = None):
    """Arena unpack: slab (S, ...) with slab[ids] = rows (B, ...).  The
    slab argument is donated on both backends.  The kernel writes only
    the B rows of its aliased view, but on TPU the view costs a
    whole-slab relayout in and out, as in `session_gather`."""
    if interpret is None and _use_interpret():
        return _ref.session_scatter_ref(slab, ids, rows)
    S = slab.shape[0]
    out = _sess.session_scatter(slab.reshape(S, 1, -1), ids,
                                rows.reshape(rows.shape[0], 1, -1),
                                interpret=bool(interpret))
    return out.reshape(slab.shape)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_merge_update(mem, h, a, interpret: Optional[bool] = None):
    interpret = _use_interpret() if interpret is None else interpret
    return _merge.kv_merge_update(mem, h, a, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kv_cummean(h, interpret: Optional[bool] = None):
    interpret = _use_interpret() if interpret is None else interpret
    T = h.shape[0]
    flat = h.reshape(T, -1)
    out = _merge.kv_cummean(flat, interpret=interpret)
    return out.reshape(h.shape)
