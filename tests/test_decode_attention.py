"""Segmented attention subsystem: segmented-vs-dense equivalence across
layouts (mem only / mem+cache / mem+cache+self, ragged lanes, GQA), the
Pallas kernel vs the concat oracle, in-kernel int8 dequant vs the
full-dequant path, the O(block) ragged window write, and the
LANE-BATCHED route (per-lane tile skip under vmap: kernel vs the batched
oracle, custom_vmap vs per-lane loops, select-path equivalence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import inference as I
from repro.core import masks as M
from repro.kernels import ops, ref
from repro.models import attention as A
from repro.models import transformer as T
from repro.models.config import CCMConfig, ModelConfig


def _cfg(Hq=4, Hkv=2, D=16, **kw):
    return ModelConfig(name="t", d_model=Hq * D, n_heads=Hq, n_kv_heads=Hkv,
                       head_dim=D, compute_dtype="float32", **kw)


def _kv(key, B, S, Hkv, D):
    return (jax.random.normal(key, (B, S, Hkv, D)),
            jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, D)))


def _quantize(k, v):
    """Production int8 layout (the helper under test, not a re-impl)."""
    k8, ks = I.quantize_kv(k)
    v8, vs = I.quantize_kv(v)
    return k8, v8, ks, vs


def _self_info(Sq, valid=None):
    return A.KeyInfo(idx=jnp.arange(Sq, dtype=jnp.int32),
                     seg=jnp.ones((Sq,), jnp.int32),
                     comp=jnp.zeros((Sq,), bool), valid=valid)


# ---------------------------------------------------------------------------
# attend_segments (jnp online-softmax) == materialized-concat baseline
# ---------------------------------------------------------------------------

LAYOUTS = [
    # (Hq, Hkv, mem_S, mem_len, cache_S, cache_len, Sq)
    (4, 2, 0, 0, 0, 0, 9),          # self only
    (4, 2, 16, 10, 0, 0, 9),        # mem + self, partial mem
    (4, 2, 16, 16, 96, 40, 9),      # mem + cache + self (GQA)
    (8, 1, 16, 2, 100, 77, 5),      # MQA, unaligned cache length
    (4, 4, 16, 0, 64, 0, 7),        # MHA, everything empty but self
    (4, 2, 16, 16, 64, 64, 1),      # decode shape: 1-token q, full cache
]


@pytest.mark.parametrize("case", LAYOUTS)
def test_segmented_equals_concat(case):
    Hq, Hkv, mS, mL, cS, cL, Sq = case
    D = 16
    cfg = _cfg(Hq, Hkv, D).replace(attn_seg_block=32)
    key = jax.random.PRNGKey(sum(case))
    q = jax.random.normal(key, (2, Sq, Hq, D))
    segs = []
    if mS:
        mk, mv = _kv(jax.random.fold_in(key, 2), 2, mS, Hkv, D)
        segs.append(A.KVSegment(k=mk, v=mv, length=jnp.asarray(mL)))
    if cS:
        ck, cv = _kv(jax.random.fold_in(key, 3), 2, cS, Hkv, D)
        segs.append(A.KVSegment(k=ck, v=cv, length=jnp.asarray(cL)))
    sk, sv = _kv(jax.random.fold_in(key, 4), 2, Sq, Hkv, D)
    info = _self_info(Sq)
    segs.append(A.KVSegment(k=sk, v=sv, info=info))
    out = A.attend_segments(cfg, q, segs, info)
    want = A.attend_segments(cfg, q, segs, info, impl="concat")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_segmented_ragged_lane_and_layered():
    """Ragged self validity (mid-sequence hole, as ragged ingest produces)
    plus a stacked-layer cache segment read via KVSegment.layer."""
    Hq, Hkv, D, Sq, Lyr, cS = 4, 2, 16, 12, 3, 64
    cfg = _cfg(Hq, Hkv, D).replace(attn_seg_block=32)
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (2, Sq, Hq, D))
    CK = jax.random.normal(jax.random.fold_in(key, 1), (Lyr, 2, cS, Hkv, D))
    CV = jax.random.normal(jax.random.fold_in(key, 2), (Lyr, 2, cS, Hkv, D))
    sk, sv = _kv(jax.random.fold_in(key, 3), 2, Sq, Hkv, D)
    valid = M.lane_valid(Sq, jnp.asarray(7), tail_start=10)  # hole [7, 10)
    info = _self_info(Sq, valid=valid)
    for li in (0, Lyr - 1):
        segs = [A.KVSegment(k=CK, v=CV, length=jnp.asarray(33),
                            layer=jnp.asarray(li)),
                A.KVSegment(k=sk, v=sv, info=info)]
        out = A.attend_segments(cfg, q, segs, info)
        want = A.attend_segments(cfg, q, segs, info, impl="concat")
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)


def test_segmented_large_q_chunked_path():
    """Sq beyond the q-chunk exercises the per-q-block scan (prefill)."""
    cfg = _cfg(4, 2, 16).replace(attn_chunk=16, attn_seg_block=32)
    key = jax.random.PRNGKey(5)
    Sq = 50
    q = jax.random.normal(key, (1, Sq, 4, 16))
    mk, mv = _kv(jax.random.fold_in(key, 1), 1, 24, 2, 16)
    sk, sv = _kv(jax.random.fold_in(key, 2), 1, Sq, 2, 16)
    info = _self_info(Sq)
    segs = [A.KVSegment(k=mk, v=mv, length=jnp.asarray(13)),
            A.KVSegment(k=sk, v=sv, info=info)]
    out = A.attend_segments(cfg, q, segs, info)
    want = A.attend_segments(cfg, q, segs, info, impl="concat")
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# Pallas kernel (interpret) vs the concat oracle
# ---------------------------------------------------------------------------

def test_pallas_segmented_vs_ref():
    B, Hq, Hkv, D = 2, 4, 2, 32
    key = jax.random.PRNGKey(0)
    Sq, mS, cS = 40, 24, 100
    q = jax.random.normal(key, (B, Sq, Hq, D))
    mk, mv = _kv(jax.random.fold_in(key, 1), B, mS, Hkv, D)
    ck, cv = _kv(jax.random.fold_in(key, 2), B, cS, Hkv, D)
    ck8, cv8, ks, vs = _quantize(ck, cv)
    sk, sv = _kv(jax.random.fold_in(key, 3), B, Sq, Hkv, D)
    info = _self_info(Sq, valid=jnp.arange(Sq) < Sq - 3)
    none4 = dict(idx=None, seg=None, comp=None, valid=None)
    segs = [dict(k=mk, v=mv, k_scale=None, v_scale=None, layer=None,
                 length=jnp.asarray(17), **none4),
            dict(k=ck8, v=cv8, k_scale=ks, v_scale=vs, layer=None,
                 length=jnp.asarray(70), **none4),
            dict(k=sk, v=sv, k_scale=None, v_scale=None, layer=None,
                 length=None, idx=info.idx, seg=info.seg, comp=info.comp,
                 valid=info.valid)]
    out = ops.segmented_attention(q, segs, info.idx, info.seg,
                                  1 / np.sqrt(D), block_q=16, block_k=32,
                                  interpret=True)
    want = ref.segmented_attention_ref(q, segs, info.idx, info.seg,
                                       1 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_pallas_segmented_layered_cache():
    """Stacked-state segment: the kernel DMAs blocks of one layer via the
    scalar-prefetched layer id."""
    B, Hq, Hkv, D, Lyr, cS, Sq = 1, 4, 2, 32, 3, 64, 8
    key = jax.random.PRNGKey(1)
    q = jax.random.normal(key, (B, Sq, Hq, D))
    CK = jax.random.normal(jax.random.fold_in(key, 1), (Lyr, B, cS, Hkv, D))
    CV = jax.random.normal(jax.random.fold_in(key, 2), (Lyr, B, cS, Hkv, D))
    sk, sv = _kv(jax.random.fold_in(key, 3), B, Sq, Hkv, D)
    info = _self_info(Sq)
    none4 = dict(idx=None, seg=None, comp=None, valid=None)
    for li in (0, 2):
        segs = [dict(k=CK, v=CV, k_scale=None, v_scale=None,
                     layer=jnp.asarray(li), length=jnp.asarray(40), **none4),
                dict(k=sk, v=sv, k_scale=None, v_scale=None, layer=None,
                     length=None, idx=info.idx, seg=info.seg,
                     comp=info.comp, valid=info.valid)]
        out = ops.segmented_attention(q, segs, info.idx, info.seg,
                                      1 / np.sqrt(D), block_q=8, block_k=16,
                                      interpret=True)
        want = ref.segmented_attention_ref(q, segs, info.idx, info.seg,
                                           1 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)


def test_pallas_segmented_layered_quantized():
    """Layered AND int8-quantized — the exact segment the decode path
    emits with attn_impl='pallas' on an int8 cache (stacked scales are
    indexed by the prefetched layer id too)."""
    B, Hq, Hkv, D, Lyr, cS, Sq = 1, 4, 2, 32, 2, 48, 8
    key = jax.random.PRNGKey(3)
    CK = jax.random.normal(jax.random.fold_in(key, 1), (Lyr, B, cS, Hkv, D))
    CV = jax.random.normal(jax.random.fold_in(key, 2), (Lyr, B, cS, Hkv, D))
    ck8, cv8, ks, vs = _quantize(CK, CV)
    q = jax.random.normal(key, (B, Sq, Hq, D))
    sk, sv = _kv(jax.random.fold_in(key, 3), B, Sq, Hkv, D)
    info = _self_info(Sq)
    none4 = dict(idx=None, seg=None, comp=None, valid=None)
    for li in (0, 1):
        segs = [dict(k=ck8, v=cv8, k_scale=ks, v_scale=vs,
                     layer=jnp.asarray(li), length=jnp.asarray(30), **none4),
                dict(k=sk, v=sv, k_scale=None, v_scale=None, layer=None,
                     length=None, idx=info.idx, seg=info.seg,
                     comp=info.comp, valid=info.valid)]
        out = ops.segmented_attention(q, segs, info.idx, info.seg,
                                      1 / np.sqrt(D), block_q=8, block_k=16,
                                      interpret=True)
        want = ref.segmented_attention_ref(q, segs, info.idx, info.seg,
                                           1 / np.sqrt(D))
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5)


# ---------------------------------------------------------------------------
# int8 cache decode: tile-wise dequant == full-dequant concat path
# ---------------------------------------------------------------------------

def test_int8_decode_matches_full_dequant():
    cfg = ModelConfig(name="q8", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=128,
                      compute_dtype="float32", kv_cache_dtype="int8",
                      attn_seg_block=16,
                      ccm=CCMConfig(comp_len=2, max_steps=4))
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 20), 0, 128)
    state = I.init_online_state(cfg, 2, max_cache_len=48)
    _, state = I.prefill(params, cfg, state, toks)
    assert state.cache.quantized and int(state.cache.length) == 20
    lg, _ = I.decode_step(params, cfg, state, toks[:, :1])
    # 'concat' materializes the dequantized full cache before attending —
    # the pre-segmented int8 path
    lg_full, _ = I.decode_step(params, cfg, state, toks[:, :1],
                               impl="concat")
    np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_full),
                               atol=5e-5)


def test_decode_ignores_cache_capacity():
    """Same prefix in a small and a 4x larger cache decodes identically —
    the work (and the numerics) depend on length, not capacity."""
    cfg = ModelConfig(name="cap", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=128,
                      compute_dtype="float32", attn_seg_block=16,
                      ccm=CCMConfig(comp_len=2, max_steps=4))
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 24), 0, 128)
    outs = []
    for cap in (32, 128):
        st = I.init_online_state(cfg, 1, max_cache_len=cap)
        _, st = I.prefill(params, cfg, st, toks)
        lg, _ = I.decode_step(params, cfg, st, toks[:, :1])
        outs.append(np.asarray(lg))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# lane-batched route: per-lane tile skip under vmap
# ---------------------------------------------------------------------------


def _lane_case(key, N=3, Sq=8, Hq=4, Hkv=2, D=32, Lyr=3, Smax=64,
               quant=False):
    """Mixed-occupancy serve-style lane batch: per-lane memory lengths,
    a per-lane (lane-major) stacked cache at per-lane layers, and a
    ragged self segment."""
    q = jax.random.normal(key, (N, Sq, Hq, D))
    CK = jax.random.normal(jax.random.fold_in(key, 1), (N, Lyr, Smax, Hkv, D))
    CV = jax.random.normal(jax.random.fold_in(key, 2), (N, Lyr, Smax, Hkv, D))
    sk = jax.random.normal(jax.random.fold_in(key, 3), (N, Sq, Hkv, D))
    sv = jax.random.normal(jax.random.fold_in(key, 4), (N, Sq, Hkv, D))
    mk = jax.random.normal(jax.random.fold_in(key, 5), (N, 16, Hkv, D))
    mv = jax.random.normal(jax.random.fold_in(key, 6), (N, 16, Hkv, D))
    lens = jnp.array([5, 33, 0], jnp.int32)[:N]
    mlens = jnp.array([16, 4, 7], jnp.int32)[:N]
    layers = jnp.array([0, Lyr - 1, 1], jnp.int32)[:N]
    valid = jnp.arange(Sq)[None] < jnp.array([Sq, 5, 2])[:N, None]
    info = _self_info(Sq)
    cache = dict(k=CK, v=CV, k_scale=None, v_scale=None, layer=layers,
                 lane_major=True, length=lens,
                 idx=None, seg=None, comp=None, valid=None)
    if quant:
        ck8, cv8, ks, vs = _quantize(CK, CV)
        cache.update(k=ck8, v=cv8, k_scale=ks, v_scale=vs)
    segs = [dict(k=mk, v=mv, k_scale=None, v_scale=None, layer=None,
                 length=mlens, idx=None, seg=None, comp=None, valid=None),
            cache,
            dict(k=sk, v=sv, k_scale=None, v_scale=None, layer=None,
                 length=None, idx=info.idx, seg=info.seg, comp=info.comp,
                 valid=valid)]
    return q, segs, info


@pytest.mark.parametrize("quant", [False, True])
def test_lane_kernel_vs_batched_oracle(quant):
    """Lane grid axis + 2-D scalar prefetch: mixed per-lane lengths,
    per-lane layer ids into a lane-major stacked cache, per-lane ragged
    self validity, GQA — fp32 and int8 — against the per-lane oracle."""
    q, segs, info = _lane_case(jax.random.PRNGKey(11), quant=quant)
    D = q.shape[-1]
    out = ops.segmented_attention(q, segs, info.idx, info.seg,
                                  1 / np.sqrt(D), block_q=8, block_k=16,
                                  interpret=True)
    want = ref.segmented_attention_lanes_ref(q, segs, info.idx, info.seg,
                                             1 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_lane_online_vs_batched_oracle():
    """The jnp lane path (_attend_segments_lanes_online) against the
    per-lane oracle on the same mixed-occupancy batch."""
    from repro.models.attention import _attend_segments_lanes_online
    q, segs, info = _lane_case(jax.random.PRNGKey(13))
    D = q.shape[-1]
    cfg = _cfg(4, 2, D).replace(attn_seg_block=16)
    # the jnp path takes a lane-shared layer (serve: same layer for all
    # lanes inside the scanned body) and boolean metadata
    for s in segs:
        if s.get("layer") is not None:
            s["layer"] = jnp.asarray(1, jnp.int32)
        for key in ("comp", "valid"):
            if s.get(key) is not None:
                s[key] = jnp.broadcast_to(jnp.asarray(s[key], bool),
                                          (q.shape[0], s["k"].shape[1]))
        for key in ("idx", "seg"):
            if s.get(key) is not None:
                s[key] = jnp.broadcast_to(jnp.asarray(s[key], jnp.int32),
                                          (q.shape[0], s["k"].shape[1]))
    qidx = jnp.broadcast_to(info.idx, (q.shape[0], q.shape[1]))
    qseg = jnp.broadcast_to(info.seg, (q.shape[0], q.shape[1]))
    out = _attend_segments_lanes_online(cfg, q, segs, qidx, qseg,
                                        1 / np.sqrt(D))
    want = ref.segmented_attention_lanes_ref(q, segs, qidx, qseg,
                                             1 / np.sqrt(D))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_attend_segments_vmap_routes_to_lanes():
    """attend_segments under jax.vmap (the serve session axis): the
    custom_vmap rule must (a) match running every lane unbatched, (b)
    match the legacy select-lowered path, and (c) keep the tile skip a
    real `cond` in the lowered jaxpr."""
    Hq, Hkv, D, Lyr, Smax, N = 4, 2, 16, 3, 96, 4
    cfg = _cfg(Hq, Hkv, D).replace(attn_seg_block=16)
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (N, 1, 1, Hq, D))
    CK = jax.random.normal(jax.random.fold_in(key, 1),
                           (N, Lyr, 1, Smax, Hkv, D))
    CV = jax.random.normal(jax.random.fold_in(key, 2),
                           (N, Lyr, 1, Smax, Hkv, D))
    sk = jax.random.normal(jax.random.fold_in(key, 3), (N, 1, 1, Hkv, D))
    sv = jax.random.normal(jax.random.fold_in(key, 4), (N, 1, 1, Hkv, D))
    lens = jnp.array([7, 45, 0, 96], jnp.int32)
    li = jnp.asarray(1, jnp.int32)
    info = A.KeyInfo(idx=jnp.full((1,), 2 ** 30, jnp.int32),
                     seg=jnp.ones((1,), jnp.int32),
                     comp=jnp.zeros((1,), bool))

    def one(cfg_, q, ck, cv, sk, sv, ln):
        segs = [A.KVSegment(k=ck, v=cv, length=ln, layer=li),
                A.KVSegment(k=sk, v=sv, info=info)]
        return A.attend_segments(cfg_, q, segs, info)

    import functools
    lane = jax.vmap(functools.partial(one, cfg))
    got = lane(q, CK, CV, sk, sv, lens)
    want = jnp.stack([one(cfg, q[i], CK[i], CV[i], sk[i], sv[i], lens[i])
                      for i in range(N)])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    legacy = jax.vmap(functools.partial(
        one, cfg.replace(attn_lane_batched=False)))(q, CK, CV, sk, sv, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(legacy),
                               atol=1e-6)
    jp = str(jax.make_jaxpr(lane)(q, CK, CV, sk, sv, lens))
    assert "cond[" in jp   # tile skip survived the vmap as a real branch


def test_decode_vmap_lane_capacity_invariance():
    """End-to-end: vmapped decode_step over stacked per-lane states is
    numerically identical across cache capacities AND to per-lane decode
    (the lane route changes scheduling, never values)."""
    cfg = ModelConfig(name="lane", n_layers=2, d_model=64, n_heads=4,
                      n_kv_heads=2, d_ff=128, vocab_size=128,
                      compute_dtype="float32", attn_seg_block=16,
                      ccm=CCMConfig(comp_len=2, max_steps=4))
    params = T.init_lm(jax.random.PRNGKey(0), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(1), (3, 20), 0, 128)
    prefix = [4, 12, 20]
    outs = []
    for cap in (32, 128):
        lanes = []
        for i, n in enumerate(prefix):
            st = I.init_online_state(cfg, 1, max_cache_len=cap)
            _, st = I.prefill(params, cfg, st, toks[i:i + 1, :n])
            lanes.append(st)
        stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
        lg, _ = jax.vmap(lambda s, t: I.decode_step(params, cfg, s, t))(
            stacked, toks[:, :1, None])
        outs.append(np.asarray(lg))
        if cap == 32:
            for i in range(3):
                lg1, _ = I.decode_step(params, cfg, lanes[i], toks[i:i+1, :1])
                # 16 float32 ulps at |logit| ~ 3.5: XLA reassociates the
                # vmapped lane matmuls' sums differently from one lane's
                np.testing.assert_allclose(outs[0][i], np.asarray(lg1),
                                           atol=4e-6)
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# O(block) ragged window write == whole-buffer oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("start,valid", [(0, 3), (5, 4), (13, 4), (14, 2),
                                         (10, 0)])
def test_ragged_window_write_matches_oracle(start, valid):
    buf = jnp.arange(16 * 3, dtype=jnp.float32).reshape(16, 3)
    blk = -jnp.ones((4, 3))
    got = M.ragged_block_write(buf, blk, jnp.asarray(start),
                               jnp.asarray(valid), axis=0)
    want = ref.ragged_block_write_ref(buf, blk, start, valid, axis=0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_ragged_window_write_layered():
    """The stacked-state form: only layer li's window changes."""
    buf = jnp.zeros((3, 2, 10, 4))
    blk = jnp.ones((1, 2, 4, 4))
    out = M.ragged_window_write(buf, blk, (1, 0, 6, 0), jnp.asarray(2),
                                axis=2)
    out = np.asarray(out)
    assert out[1, :, 6:8].all() and out[1, :, 8:].sum() == 0
    assert out[0].sum() == 0 and out[2].sum() == 0 and out[1, :, :6].sum() == 0
