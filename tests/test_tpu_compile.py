"""Ahead-of-time compiles of every Pallas kernel for a described TPU v5e.

No chip is needed: the installed TPU compiler lowers each kernel for a
topology that is described, not attached, so a block shape or VMEM use
the chip would refuse fails here.  Interpret mode cannot catch that.

Shapes are Qwen2-0.5B's (14 query heads, 2 KV heads, head_dim 64,
d_model 896) and its serve-arena rows at ``cache_len=256``.  The
topology is described inside a fixture — never at import — so every
test worker collects the same tests and only the one that runs this
file loads the TPU compiler.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config
from repro.kernels import decode_attention as DA
from repro.kernels import ops
from repro.models.attention import KeyInfo
from repro.serve.arena import online_template

QWEN = get_config("qwen2-0.5b")
CACHE_LEN = 256
N_ROWS, LANES = 9, 4         # 8 arena slots + scratch row; a 4-lane batch


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_cache):
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                       # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` -> an abstract array on one v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


def _compiles_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _arena_leaf(which):
    """(shape, dtype) of one Qwen2-0.5B online-arena slab leaf."""
    t = online_template(QWEN, CACHE_LEN)
    leaf = {"kv": t.cache.k, "mem": t.mem.k, "counter": t.cache.length}[which]
    return (N_ROWS,) + leaf.shape, leaf.dtype


@pytest.mark.parametrize("which", ["kv", "mem", "counter"])
def test_session_gather_lowers(spec, which):
    shape, dt = _arena_leaf(which)
    _compiles_kernel(lambda s, i: ops.session_gather(s, i, interpret=False),
                     spec(shape, dt), spec((LANES,), jnp.int32))


@pytest.mark.parametrize("which", ["kv", "mem", "counter"])
def test_session_scatter_lowers(spec, which):
    shape, dt = _arena_leaf(which)
    _compiles_kernel(
        lambda s, i, r: ops.session_scatter(s, i, r, interpret=False),
        spec(shape, dt), spec((LANES,), jnp.int32),
        spec((LANES,) + shape[1:], dt))


@pytest.mark.parametrize("quant", [False, True])
def test_segmented_decode_attention_lowers(spec, quant):
    """The serve lane route: [mem | lane-major stacked cache | self]."""
    Hq, Hkv, D, L, Sq = QWEN.n_heads, QWEN.n_kv_heads, QWEN.hd, \
        QWEN.n_layers, 8
    M = QWEN.ccm.mem_len
    kv_dt = jnp.int8 if quant else jnp.bfloat16

    def fn(q, mk, mv, ck, cv, cks, cvs, sk, sv, lens, layer):
        idx = jnp.arange(Sq, dtype=jnp.int32)
        info = KeyInfo(idx=idx, seg=jnp.ones((Sq,), jnp.int32),
                       comp=jnp.zeros((Sq,), bool))
        segs = [dict(k=mk, v=mv, length=lens),
                dict(k=ck, v=cv, k_scale=cks if quant else None,
                     v_scale=cvs if quant else None, layer=layer,
                     lane_major=True, length=lens),
                dict(k=sk, v=sv, idx=info.idx, seg=info.seg,
                     comp=info.comp, valid=None)]
        return DA.segmented_flash_attention(
            q, segs, info.idx, info.seg, 1 / np.sqrt(D), block_k=128,
            interpret=False)

    B = LANES
    _compiles_kernel(
        fn, spec((B, Sq, Hq, D), jnp.bfloat16),
        spec((B, M, Hkv, D), jnp.bfloat16), spec((B, M, Hkv, D), jnp.bfloat16),
        spec((B, L, CACHE_LEN, Hkv, D), kv_dt),
        spec((B, L, CACHE_LEN, Hkv, D), kv_dt),
        spec((B, L, CACHE_LEN, Hkv), jnp.float32),
        spec((B, L, CACHE_LEN, Hkv), jnp.float32),
        spec((B, Sq, Hkv, D), jnp.bfloat16), spec((B, Sq, Hkv, D),
                                                  jnp.bfloat16),
        spec((B,), jnp.int32), spec((B,), jnp.int32))


def test_ccm_flash_attention_lowers(spec):
    Hq, Hkv, D, S = QWEN.n_heads, QWEN.n_kv_heads, QWEN.hd, 256

    def fn(q, k, v):
        idx = jnp.arange(S, dtype=jnp.int32)
        seg = idx // 32
        info = KeyInfo(idx=idx, seg=seg, comp=(idx % 32) >= 24,
                       valid=jnp.ones((S,), bool))
        return ops.ccm_attention(q, k, v, info, info, 1 / np.sqrt(D),
                                 interpret=False)

    _compiles_kernel(fn, spec((1, S, Hq, D), jnp.bfloat16),
                     spec((1, S, Hkv, D), jnp.bfloat16),
                     spec((1, S, Hkv, D), jnp.bfloat16))


def test_cond_lora_matmul_lowers(spec):
    d, r, M = QWEN.d_model, QWEN.ccm.lora_rank, 256
    _compiles_kernel(
        lambda x, w, a, b, g: ops.cond_lora(x, w, a, b, g, 2.0,
                                            interpret=False),
        spec((M, d), jnp.bfloat16), spec((d, d), jnp.bfloat16),
        spec((r, d), jnp.bfloat16), spec((r, d), jnp.bfloat16),
        spec((M,), jnp.bfloat16))


def test_kv_merge_update_lowers(spec):
    shape = (QWEN.n_layers, QWEN.ccm.comp_len, QWEN.n_kv_heads, QWEN.hd)
    _compiles_kernel(
        lambda m, h, a: ops.kv_merge_update(m, h, a, interpret=False),
        spec(shape, jnp.bfloat16), spec(shape, jnp.bfloat16),
        spec((), jnp.float32))


def test_kv_cummean_lowers(spec):
    shape = (QWEN.ccm.max_steps, QWEN.n_layers, QWEN.ccm.comp_len,
             QWEN.n_kv_heads, QWEN.hd)
    _compiles_kernel(lambda h: ops.kv_cummean(h, interpret=False),
                     spec(shape, jnp.bfloat16))


@pytest.fixture(scope="module")
def mesh4(topo):
    """The 4-chip v5e host as a 1-D session mesh."""
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ("shards",),
                axis_types=(jax.sharding.AxisType.Auto,))


@pytest.fixture
def tpu_branch(monkeypatch):
    """Steer `kernels.ops` to its TPU branch (this process sees the CPU).
    jit caches traces by abstract shapes, so they are cleared on both
    sides: no trace crosses between the steered and the CPU branch."""
    jax.clear_caches()
    monkeypatch.setattr(ops, "_use_interpret", lambda: False)
    yield
    jax.clear_caches()


def _mesh_slabs(mesh, cfg, rows_per_shard=3):
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = NamedSharding(mesh, P("shards"))
    n = mesh.size * rows_per_shard
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype, sharding=sh),
        online_template(cfg, CACHE_LEN))


def _slab_copies(text, n_rows):
    """``copy`` ops in compiled HLO whose result has a slab's row axis."""
    return [line for line in text.splitlines()
            if re.search(rf"= \w+\[{n_rows},[^=]* copy(-start)?\(", line)]


def test_arena_offpath_in_place(spec):
    """Off-hot-path pack/unpack/COW run XLA's gather/scatter, which works
    in the slab's native layout: no relayout copy of a whole slab."""
    from repro.launch.serve import cow_clone_slots
    from repro.serve import arena as AR
    slabs = jax.tree.map(lambda s: spec((N_ROWS,) + s.shape, s.dtype),
                         online_template(QWEN, CACHE_LEN))
    ids = spec((LANES,), jnp.int32)
    rows = jax.tree.map(lambda s: spec((LANES,) + s.shape[1:], s.dtype),
                        slabs)
    for compiled in (AR._pack_slabs.lower(slabs, ids).compile(),
                     AR._scatter_slabs.lower(slabs, ids, rows).compile(),
                     cow_clone_slots.lower(slabs, ids, ids).compile()):
        assert _slab_copies(compiled.as_text(), N_ROWS) == []


def test_mesh_arena_offpath_compiles(mesh4):
    """The same programs on mesh-placed slabs: XLA partitions them (a
    Mosaic kernel could not be partitioned outside shard_map)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.serve import cow_clone_slots
    from repro.serve import arena as AR
    slabs = _mesh_slabs(mesh4, QWEN)
    ids = jax.ShapeDtypeStruct((2,), jnp.int32,
                               sharding=NamedSharding(mesh4, P()))
    rows = jax.tree.map(lambda s: jax.ShapeDtypeStruct((2,) + s.shape[1:],
                                                       s.dtype), slabs)
    AR._pack_slabs.lower(slabs, ids).compile()
    AR._scatter_slabs.lower(slabs, ids, rows).compile()
    cow_clone_slots.lower(slabs, ids, ids).compile()


@pytest.mark.parametrize("op", ["ingest", "query"])
def test_sharded_arena_step_lowers(mesh4, tpu_branch, op):
    """The 4-chip hot path: the arena kernels inside `shard_map`, with
    no collective between chips (smoke widths keep the compile short)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.serve import make_sharded_arena_step
    from repro.models import transformer as T
    cfg = get_config("qwen2-0.5b", smoke=True)
    rep = NamedSharding(mesh4, P())
    sh = NamedSharding(mesh4, P("shards"))
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=rep),
        jax.eval_shape(lambda: T.init_lm(jax.random.PRNGKey(0), cfg)))
    S, B, L = mesh4.size, 2, 16
    args = (params, _mesh_slabs(mesh4, cfg),
            jax.ShapeDtypeStruct((S, B), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((S, B, 1, L), jnp.int32, sharding=sh),
            jax.ShapeDtypeStruct((S, B), jnp.int32, sharding=sh))
    text = make_sharded_arena_step(cfg, op, mesh4).lower(*args) \
        .compile().as_text()
    assert "tpu_custom_call" in text
    for coll in ("all-gather", "all-reduce", "all-to-all",
                 "collective-permute", "reduce-scatter"):
        assert coll not in text, coll
