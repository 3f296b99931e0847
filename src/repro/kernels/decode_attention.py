"""Fused segmented decode attention — Pallas TPU kernel.

A small q block attends an ordered list of KV *segments* —
[mem | cache(:length) | self] — each read IN PLACE from its own refs.
Nothing is ever concatenated: the grid's sequential k dimension walks the
segments' k-blocks back to back and a running softmax (m, l, acc) in VMEM
scratch combines them, exactly like flash-decoding's split-softmax merge
(Infini-attention fuses compressive memory + local attention in the same
two-segment form; this kernel generalizes to any static segment list).

Per-segment valid-prefix lengths arrive via scalar prefetch and gate a
tile-level skip: a k-block whose start lies past ``length`` costs nothing,
so decode work scales with ``cache.length`` rounded up to ``block_k`` —
not with the cache's allocated capacity.  int8 segments are dequantized
tile-wise in-kernel from their ``k_scale``/``v_scale`` refs (the fp
full-cache dequant copy of the concat path disappears).

The leading grid axis is the *lane* axis (a serve batch of independent
sessions, or a plain batch): the scalar-prefetch table is 2-D,
``(lanes, 2 * n_segments)`` holding ``[lens | layer ids]`` PER LANE, and
both the in-kernel skip predicate and the layered index maps read row
``program_id(0)``.  Each lane therefore skips past its *own* valid
prefix — under the serve engine's vmapped session steps this is what
keeps decode cost proportional to per-lane cache occupancy instead of
lowering to a batch-wide ``select`` (see ``models.attention``'s
``custom_vmap`` route).  Per-lane layered segments (each lane brings its
own stacked cache) use the lane-major layout ``(lanes, L, S, H, D)``
(``lane_major=True``); a layered segment shared across an inner batch
keeps the model-native layer-major ``(L, B, S, H, D)``.

KV layouts are the model's native (B, S, Hkv, D) — segments are consumed
where they live; no per-step transpose of a large cache.  A KV block is
(1, bk, Hkv, D): every KV head at once, so its trailing dims are whole
and the block is legal on TPU for any head count, and each KV byte is
read once per q block.  The grid is (lanes, q blocks, k blocks); one step
folds all Hq query heads, each against its group's KV head.  Only q is
transposed, to head-major (B, Hq, Sq, D).  Per-token metadata rides as
(B, 1, S) so its (1, bk) blocks are legal too.

Mask predicate per (q, k), identical to models.attention.mask_from_info:
  causal AND (same-segment OR key-is-<COMP>) AND key-valid AND pos<length
with memory-like segments (no metadata refs) reducing to pos < length.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import pad_axis as _pad_axis

NEG_INF = -1e30


class SegDesc(NamedTuple):
    """Static per-segment layout inside the fused grid."""
    off: int          # first grid index along the k dimension
    nk: int           # number of k-blocks
    bk: int           # k-block width
    quantized: bool   # int8 k/v with fp32 scale refs
    has_info: bool    # per-token idx/seg/comp/valid metadata refs follow
    layered: bool     # k/v carry a layer axis, indexed by the
                      # scalar-prefetched per-lane layer id (stacked-state)
    lane_major: bool  # layered layout is (lanes, L, S, ...) — each lane
                      # owns its stack — vs layer-major (L, B, S, ...)
    n_refs: int       # tensor+meta refs this segment contributes


def _desc(off: int, S: int, bk: int, quantized: bool, has_info: bool,
          layered: bool, lane_major: bool) -> SegDesc:
    nk = pl.cdiv(S, bk)
    n = 2 + (2 if quantized else 0) + (4 if has_info else 0)
    return SegDesc(off, nk, bk, quantized, has_info, layered, lane_major, n)


def _kernel(descs, scale, nk_total, G,
            lens_ref, qidx_ref, qseg_ref, q_ref, *rest):
    n_in = sum(d.n_refs for d in descs)
    o_ref = rest[n_in]
    m_ref, l_ref, acc_ref = rest[n_in + 1:]
    Hq, bq = q_ref.shape[1], q_ref.shape[2]
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ptr = 0
    for si, d in enumerate(descs):
        refs = rest[ptr:ptr + d.n_refs]
        ptr += d.n_refs
        k_ref, v_ref = refs[0], refs[1]
        ks_ref, vs_ref = (refs[2], refs[3]) if d.quantized else (None, None)
        meta = refs[2 + (2 if d.quantized else 0):]
        start = (ik - d.off) * d.bk
        in_seg = (ik >= d.off) & (ik < d.off + d.nk)
        seg_len = lens_ref[b, si]                   # THIS lane's [lens | ids]
        visible = in_seg & (start < seg_len)
        if d.has_info:
            # tile-level CCM visibility precheck (block sparsity): skip
            # tiles that cannot contain a visible key for any q row
            kidx, kseg, kcomp, kval = (r[0, :] for r in meta)
            qidx = qidx_ref[0, :]
            qseg = qseg_ref[0, :]
            causal_possible = jnp.min(kidx) <= jnp.max(qidx)
            has_comp = jnp.max(kcomp * kval) > 0
            seg_overlap = (jnp.min(kseg) <= jnp.max(qseg)) & \
                          (jnp.max(kseg) >= jnp.min(qseg))
            visible = visible & causal_possible & (has_comp | seg_overlap)

        @pl.when(visible)
        def _fold(d=d, k_ref=k_ref, v_ref=v_ref, ks_ref=ks_ref,
                  vs_ref=vs_ref, meta=meta, start=start, seg_len=seg_len):
            pos = start + jax.lax.broadcasted_iota(jnp.int32, (bq, d.bk), 1)
            mask = pos < seg_len
            if d.has_info:
                kidx, kseg, kcomp, kval = (r[0, :] for r in meta)
                qidx = qidx_ref[0, :]
                qseg = qseg_ref[0, :]
                mask = mask & (kidx[None, :] <= qidx[:, None]) \
                    & ((kseg[None, :] == qseg[:, None])
                       | (kcomp[None, :] > 0)) \
                    & (kval[None, :] > 0)
            # the block holds every KV head: load each once and fold the
            # G query heads of its group against it
            for j in range(Hq // G):
                lead = (0, 0) if d.layered else (0,)
                k = k_ref[lead + (slice(None), j, slice(None))]   # (bk, D)
                v = v_ref[lead + (slice(None), j, slice(None))]
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
                if d.quantized:   # tile-wise in-kernel dequant
                    sc = lead + (slice(None), slice(j, j + 1))
                    k = k * ks_ref[sc]
                    v = v * vs_ref[sc]
                for h in range(j * G, (j + 1) * G):
                    q = q_ref[0, h].astype(jnp.float32)       # (bq, D)
                    s = jax.lax.dot_general(
                        q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
                    s = jnp.where(mask, s, NEG_INF)            # (bq, bk)
                    m_prev = m_ref[h]                          # (bq, 1)
                    m_new = jnp.maximum(m_prev,
                                        jnp.max(s, axis=1, keepdims=True))
                    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
                    alpha = jnp.exp(m_prev - m_new)
                    l_ref[h] = l_ref[h] * alpha \
                        + jnp.sum(p, axis=1, keepdims=True)
                    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                        p, v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                    m_ref[h] = m_new

    @pl.when(ik == nk_total - 1)
    def _final():
        for h in range(Hq):
            l = jnp.maximum(l_ref[h], 1e-37)
            o_ref[0, h] = (acc_ref[h] / l).astype(o_ref.dtype)


def segmented_flash_attention(q, segs: Sequence[Dict[str, Any]],
                              q_idx, q_seg, scale: float,
                              block_q: int = 128, block_k: int = 128,
                              interpret: Optional[bool] = None):
    """q (B, Sq, Hq, D) — B is the lane axis (independent serve lanes, or
    a plain batch).  Each seg a dict of arrays:

      k/v (B, S, Hkv, D) [int8 allowed with k_scale/v_scale (B, S, Hkv)],
      length () or (B,) int32, or None (fully valid) — PER-LANE valid
      prefix when (B,): each lane's k-block loop skips past its own,
      idx/seg/comp/valid (S,) or (B, S) metadata, or None (memory-like
      segment: always-visible keys),
      layer () or (B,) int32, or None — when set, k/v (and scales) carry
      a layer axis and blocks are DMA'd straight out of that layer of
      the stacked state (no layer-slice copy).  Layout is layer-major
      (L, B, S, ...) by default; ``lane_major=True`` marks the per-lane
      stacked form (B, L, S, ...) produced by the serve engine's arena
      gather (lane axis outermost).

    Returns (B, Sq, Hq, D).  Sq and every S are padded to block multiples
    here; hot-path callers keep capacities block-aligned so this is free.
    The scalar-prefetch table is (B, 2 * n_segments) int32 —
    ``[valid lengths | layer ids]`` per lane — read by both the in-kernel
    tile-skip predicate and the layered index maps at row
    ``program_id(0)``, which is what makes the skip truly per-lane.
    ``q_idx``/``q_seg`` are (Sq,) shared or (B, Sq) per-lane.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, Sq, Hq, D = q.shape
    Hkv = segs[0]["k"].shape[-2]
    G = Hq // Hkv
    big = 2 ** 30

    def lanes(x):
        """Broadcast shared 1-D metadata to the (B, S) per-lane form."""
        x = jnp.asarray(x)
        if x.ndim == 1:
            x = jnp.broadcast_to(x, (B,) + x.shape)
        return x

    def rows(x):
        """(B, S) metadata -> (B, 1, S): blocks (1, width) are legal."""
        return x[:, None, :]

    bq = min(block_q, max(Sq, 8))
    # head-major q (small: the query block), so a block holds all heads
    qp = _pad_axis(q.transpose(0, 2, 1, 3), bq, 2)
    nq = qp.shape[2] // bq
    qi = rows(_pad_axis(lanes(jnp.asarray(q_idx, jnp.int32)), bq, 1,
                        fill=-big))
    qs = rows(_pad_axis(lanes(jnp.asarray(q_seg, jnp.int32)), bq, 1,
                        fill=-3))

    descs: List[SegDesc] = []
    ns = len(segs)
    lens, layers, inputs, in_specs = [], [], [], []
    off = 0
    for si, s in enumerate(segs):
        layered = s.get("layer") is not None
        lane_major = layered and bool(s.get("lane_major"))
        tok_ax = 2 if layered else 1
        S = s["k"].shape[tok_ax]
        quant = s.get("k_scale") is not None
        has_info = s.get("idx") is not None
        bk = min(block_k, max(S, 8))
        d = _desc(off, S, bk, quant, has_info, layered, lane_major)
        descs.append(d)
        off += d.nk
        lens.append(jnp.broadcast_to(
            jnp.asarray(S if s.get("length") is None else s["length"],
                        jnp.int32), (B,)))
        layers.append(jnp.broadcast_to(
            jnp.zeros((), jnp.int32) if not layered
            else jnp.asarray(s["layer"], jnp.int32), (B,)))

        def im_kv(b, iq, ik, lens_ref, d=d, si=si):
            blk = jnp.clip(ik - d.off, 0, d.nk - 1)
            if d.lane_major:
                return (b, lens_ref[b, ns + si], blk, 0, 0)
            if d.layered:
                return (lens_ref[b, ns + si], b, blk, 0, 0)
            return (b, blk, 0, 0)

        def im_sc(b, iq, ik, lens_ref, d=d, si=si):
            blk = jnp.clip(ik - d.off, 0, d.nk - 1)
            if d.lane_major:
                return (b, lens_ref[b, ns + si], blk, 0)
            if d.layered:
                return (lens_ref[b, ns + si], b, blk, 0)
            return (b, blk, 0)

        def im_meta(b, iq, ik, lens_ref, d=d):
            return (b, 0, jnp.clip(ik - d.off, 0, d.nk - 1))

        # every KV head per block: the trailing (Hkv, D) dims are whole,
        # so the block is legal on TPU for any head count
        kv_block = (1, 1, bk, Hkv, D) if layered else (1, bk, Hkv, D)
        sc_block = (1, 1, bk, Hkv) if layered else (1, bk, Hkv)
        inputs += [_pad_axis(s["k"], bk, tok_ax),
                   _pad_axis(s["v"], bk, tok_ax)]
        in_specs += [pl.BlockSpec(kv_block, im_kv)] * 2
        if quant:
            inputs += [_pad_axis(s["k_scale"], bk, tok_ax),
                       _pad_axis(s["v_scale"], bk, tok_ax)]
            in_specs += [pl.BlockSpec(sc_block, im_sc)] * 2
        if has_info:
            valid = s.get("valid")
            if valid is None:
                valid = jnp.ones((S,), bool)
            inputs += [
                rows(_pad_axis(lanes(jnp.asarray(s["idx"], jnp.int32)), bk,
                               1, fill=big)),
                rows(_pad_axis(lanes(jnp.asarray(s["seg"], jnp.int32)), bk,
                               1, fill=-2)),
                rows(_pad_axis(lanes(s["comp"]).astype(jnp.int32), bk, 1)),
                rows(_pad_axis(lanes(valid).astype(jnp.int32), bk, 1))]
            in_specs += [pl.BlockSpec((None, 1, bk), im_meta)] * 4

    nk_total = off

    def im_q(b, iq, ik, lens_ref):
        return (b, 0, iq, 0)

    def im_qmeta(b, iq, ik, lens_ref):
        return (b, 0, iq)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nq, nk_total),
        in_specs=[pl.BlockSpec((None, 1, bq), im_qmeta),
                  pl.BlockSpec((None, 1, bq), im_qmeta),
                  pl.BlockSpec((1, Hq, bq, D), im_q)] + in_specs,
        out_specs=pl.BlockSpec((1, Hq, bq, D), im_q),
        scratch_shapes=[pltpu.VMEM((Hq, bq, 1), jnp.float32),
                        pltpu.VMEM((Hq, bq, 1), jnp.float32),
                        pltpu.VMEM((Hq, bq, D), jnp.float32)])
    kernel = functools.partial(_kernel, tuple(descs), scale, nk_total, G)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.stack(lens + layers, axis=1), qi, qs, qp, *inputs)
    return out[:, :, :Sq].transpose(0, 2, 1, 3)
