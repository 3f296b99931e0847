"""Serving: jitted online-inference step builders (pjit/GSPMD).

Three production step programs per architecture (these are what the
dry-run lowers per shape):

  prefill_step — input I(t) over [Mem, self] (prefill_32k)
  decode_step  — one token over [Mem, cache(S)] (decode_32k)
  stream_step  — CCM streaming decode: bounded window + compressed memory
                 (long_500k for attention archs; the paper's unbounded-
                 stream answer, Fig. 8/9)
  ingest_step  — g_comp for a new context chunk (the online compression op)

SSM/hybrid archs decode in O(1) state — long_500k lowers their native
decode_step.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core import inference as I
from repro.core import streaming as STR
from repro.distributed import sharding as SH
from repro.distributed.context import DistContext, divisible
from repro.kernels import ref as KREF
from repro.models.config import ModelConfig


def serve_specs(cfg: ModelConfig, dist: DistContext, *,
                batch_sharded: bool = True, shard_cache_seq: bool = False):
    state_specs = SH.online_state_pspecs(
        cfg, dist, batch_sharded=batch_sharded,
        shard_cache_seq=shard_cache_seq)
    tok_spec = P(dist.batch_axes if batch_sharded else None, None)
    return state_specs, tok_spec


def make_prefill_step(cfg: ModelConfig, dist: Optional[DistContext] = None,
                      impl: Optional[str] = None, **spec_kw) -> Callable:
    def fn(params, state, tokens, patches=None):
        return I.prefill(params, cfg, state, tokens, dist, patches=patches,
                         impl=impl)

    if dist is None:
        return jax.jit(fn)
    return _jit_with_specs(fn, cfg, dist, **spec_kw)


def make_decode_step(cfg: ModelConfig, dist: Optional[DistContext] = None,
                     **spec_kw) -> Callable:
    def fn(params, state, tokens):
        return I.decode_step(params, cfg, state, tokens, dist)

    if dist is None:
        return jax.jit(fn)
    return _jit_with_specs(fn, cfg, dist, **spec_kw)


def make_ingest_step(cfg: ModelConfig, dist: Optional[DistContext] = None,
                     **spec_kw) -> Callable:
    def fn(params, state, tokens):
        return I.ingest_context(params, cfg, state, tokens, dist)

    if dist is None:
        return jax.jit(fn)
    return _jit_with_specs(fn, cfg, dist, ingest=True, **spec_kw)


def make_stream_step(cfg: ModelConfig, params_shapes,
                     dist: Optional[DistContext] = None,
                     batch_sharded: bool = True) -> Callable:
    def fn(params, st, tokens):
        return STR.stream_step(params, cfg, st, tokens)

    if dist is None:
        return jax.jit(fn)
    pspecs = SH.param_pspecs(cfg, params_shapes, dist)
    sspecs = SH.stream_state_pspecs(cfg, dist, batch_sharded)
    tok = P(dist.batch_axes if batch_sharded else None, None)
    mesh = dist.mesh
    vspec = P(dist.batch_axes if batch_sharded else None, None, None)
    return jax.jit(
        fn,
        in_shardings=(SH.named(mesh, pspecs), SH.named(mesh, sspecs),
                      SH.named(mesh, tok)),
        out_shardings=(SH.named(mesh, vspec), SH.named(mesh, sspecs)),
        donate_argnums=(1,))


# ---------------------------------------------------------------------------
# multi-tenant session steps (repro.serve engine)
#
# The batched steps above share one scalar counter (pos/steps/length) per
# batch — fine when one batch IS one user stream, wrong for a batch packed
# from many independent sessions at different timeline points.  The
# session steps vmap the single-session op instead: every state leaf gains
# a leading session axis (arena pack layout) and each lane carries its own
# counters.  `make_arena_step` fuses arena gather -> vmapped op -> scatter
# into one jit per op kind; distinct (B, token_len) shapes each compile
# once, so `fn._cache_size()` is the recompile-churn metric the serve
# engine reports.  `make_sharded_arena_step` is the multi-device variant:
# the arena's session axis is partitioned one row block per device
# (serve.arena) and the same fused step runs under shard_map on every
# shard's local rows — per-session state is independent, so the program
# has NO cross-device collectives on the steady path.
# ---------------------------------------------------------------------------

def ragged_family(cfg: ModelConfig) -> bool:
    """Whether masked token lanes are supported: attention archs only —
    SSM/hybrid recurrent scans cannot skip pad tokens, so their batches
    keep exact token-length grouping."""
    return cfg.family not in ("ssm", "hybrid")


def session_vmap(cfg: ModelConfig, op: str, ragged: bool = False) -> Callable:
    """Unjitted vmapped session op:
    (params, state(B,...), tokens (B,1,l), lengths (B,)).

    'ingest' -> state; 'query'/'stream' -> (logits (B,1,l,V), state).
    Query = prefill of I(t) over [Mem, self] with full per-token logits.

    Per-lane cost stays occupancy-proportional under the vmap: the
    segmented attends reroute through `models.attention`'s lane-batched
    `custom_vmap` rule (per-lane tile skip instead of a capacity-bound
    `select`), and 'stream' dispatches to `streaming.stream_step_lanes`,
    which gates the eviction/compression pass on a batch-level
    "any lane pending" `cond` and re-selects non-overflowing lanes'
    state bit-exactly instead of compressing every lane every step.

    ``ragged``: each lane's tokens are padded up to a shared token bucket
    and ``lengths`` carries the per-request valid length — pad tokens are
    masked out of attention and frozen out of every state write, so a
    padded lane is bit-identical to running the request unpadded.  With
    ``ragged=False`` lengths are accepted but ignored (exact-length
    batches; the only mode for SSM/hybrid)."""
    if ragged and not ragged_family(cfg):
        raise ValueError(
            f"ragged session batching unsupported for family {cfg.family!r}")
    if op == "stream":
        def fn(params, state, tokens, lengths):
            return STR.stream_step_lanes(
                params, cfg, state, tokens,
                lengths=lengths if ragged else None)
        return fn
    if ragged:
        core = {
            "ingest": lambda p, st, tk, vl: I.ingest_context(
                p, cfg, st, tk, valid_len=vl),
            "query": lambda p, st, tk, vl: I.prefill(
                p, cfg, st, tk, full_logits=True, valid_len=vl),
        }[op]
    else:
        core = {
            "ingest": lambda p, st, tk, vl: I.ingest_context(p, cfg, st, tk),
            "query": lambda p, st, tk, vl: I.prefill(p, cfg, st, tk,
                                                     full_logits=True),
        }[op]

    def fn(params, state, tokens, lengths):
        return jax.vmap(lambda st, tk, vl: core(params, st, tk, vl))(
            state, tokens, lengths)
    return fn


def make_arena_step(cfg: ModelConfig, op: str,
                    ragged: bool = False) -> Callable:
    """Fused arena step:
    (params, slabs, ids (B,), tokens (B,1,l), lengths (B,)) ->
    (logits-or-None, slabs).

    Shape contract: ``slabs`` is the arena's state pytree — every leaf
    of the single-session template (inner batch 1) with a leading
    ``(n_slots + 1,)`` slot axis; ``ids`` selects the batch's B slot
    rows (``pad_slot`` for pad lanes); ``tokens`` are (B, 1, token_len)
    bucket-padded token lanes and ``lengths`` the per-lane valid lengths
    (== token_len everywhere when ``ragged=False``).  'query'/'stream'
    return logits (B, 1, token_len, V) — rows past a lane's valid length
    are masked-lane garbage the engine slices off.

    Gather of the batch's slot rows, the vmapped op, and the scatter of
    updated rows run as ONE jitted program over the donated slabs — the
    serve engine's hot path (no intermediate batch materialization, no
    extra dispatch boundaries).  Inside the vmapped op, decode/stream
    attention takes the lane-batched route (per-lane tile skip; see
    `session_vmap`), so the fused program's cost follows per-lane cache
    occupancy rather than arena capacity."""
    from repro.kernels import ops as KOPS
    vf = session_vmap(cfg, op, ragged)

    def fn(params, slabs, ids, tokens, lengths):
        state = jax.tree.map(lambda s: KOPS.session_gather(s, ids), slabs)
        # barrier: without it the remat'd layer scan recomputes the
        # gather every layer (measured ~2x step time on CPU)
        state = jax.lax.optimization_barrier(state)
        if op == "ingest":
            out, new = None, vf(params, state, tokens, lengths)
        else:
            out, new = vf(params, state, tokens, lengths)
        # leaves the op left untouched come back as the SAME tracer
        # (ingest never writes the KV cache, query never writes the
        # memory) — skip their scatter entirely
        slabs = jax.tree.map(
            lambda s, old, r: s if r is old
            else KOPS.session_scatter(s, ids, r),
            slabs, state, new)
        return out, slabs
    return jax.jit(fn, donate_argnums=(1,))


def make_sharded_arena_step(cfg: ModelConfig, op: str, mesh,
                            ragged: bool = False) -> Callable:
    """`make_arena_step` partitioned over the SESSION axis: one arena
    shard (contiguous row block, `serve.arena`) per device of the 1-D
    ``mesh`` (axis ``"shards"``, `launch.mesh.make_session_mesh`).

    Call contract:
    (params, slabs, ids (S, B), tokens (S, B, 1, l), lengths (S, B)) ->
    (logits (S, B, 1, l, V) or None for ingest, slabs).

    ``slabs`` leaves carry the arena's full ``(n_rows, ...)`` row axis
    sharded ``P("shards")`` (each device holds its shard's
    ``slots_per_shard + 1`` rows); ``ids`` row ``s`` holds shard ``s``'s
    LOCAL row indices (``SessionArena.local_row`` — every shard's
    scratch row is ``slots_per_shard`` — NOT global slot ids); params
    are replicated.  Inside `shard_map` each device runs the exact fused
    gather -> vmapped-op -> scatter of `make_arena_step` on its own row
    block: per-session CCM state is independent, so the program contains
    NO cross-device collectives — session state never crosses a device
    boundary on the steady path (the serve engine's
    ``serve_cross_shard_moves_total`` counter stays 0).  Slabs are
    donated, so each shard's rows update in place on their own device.

    One jit per (op, ragged) like the single-shard builder; distinct
    (S, B, token_len) shapes each compile once."""
    from repro.kernels import ops as KOPS
    vf = session_vmap(cfg, op, ragged)

    def body(params, slabs, ids, tokens, lengths):
        # per-device view: slabs leaves hold this shard's row block;
        # ids/tokens/lengths arrive (1, ...) — drop the shard dim
        ids, tokens, lengths = ids[0], tokens[0], lengths[0]
        state = jax.tree.map(lambda s: KOPS.session_gather(s, ids), slabs)
        state = jax.lax.optimization_barrier(state)
        if op == "ingest":
            new = vf(params, state, tokens, lengths)
        else:
            out, new = vf(params, state, tokens, lengths)
        slabs = jax.tree.map(
            lambda s, old, r: s if r is old
            else KOPS.session_scatter(s, ids, r),
            slabs, state, new)
        if op == "ingest":
            # shard_map outputs must be arrays; logits=None stays outside
            return slabs
        return out[None], slabs       # re-attach the shard dim

    shard = P("shards")
    out_specs = shard if op == "ingest" else (shard, shard)
    sharded = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(), shard, shard, shard, shard),
        out_specs=out_specs,
        # per-lane counters make leaves device-varying in ways the
        # static replication checker cannot prove; correctness is pinned
        # by the single-shard bit-exactness tests instead
        check_vma=False)

    def fn(params, slabs, ids, tokens, lengths):
        if op == "ingest":
            return None, sharded(params, slabs, ids, tokens, lengths)
        return sharded(params, slabs, ids, tokens, lengths)
    return jax.jit(fn, donate_argnums=(1,))


@functools.partial(jax.jit, static_argnames=("cfg", "group"),
                   donate_argnums=(0,))
def recompress_arena_slots(mem_slabs, ids, cfg: ModelConfig, group: int):
    """Arena-resident memory recompression: gather the ``ids`` rows of
    the slabs' `MemState` subtree, collapse every ``group`` consecutive
    filled <COMP> groups per lane (`core.memory.recompress_memory`,
    masked per lane via `streaming.recompress_memory_lanes`), and
    scatter the shrunk memories back — one jitted program over the
    donated mem slabs, no model params touched (it runs unchanged under
    the null-step simulation harness).

    Lanes whose memory would not shrink (fewer than two filled groups,
    or pad lanes gathering the scratch row) are re-selected bit-exactly.
    Module-level jit: `ModelConfig` is hashable, so every engine —
    and every fuzzed simulation trace — shares one compile per
    (shape, cfg, group).  Off the hot path, so the rows move by XLA's
    gather/scatter, which also partitions over a mesh-placed arena."""
    mem = jax.tree.map(lambda s: KREF.session_gather_ref(s, ids), mem_slabs)
    # shrink only when it frees at least one group: ceil(g/r) < g
    do = -(-mem.slots // group) < mem.slots
    new = STR.recompress_memory_lanes(cfg, mem, group, do)
    return jax.tree.map(
        lambda s, r: KREF.session_scatter_ref(s, ids, r), mem_slabs, new)


@functools.partial(jax.jit, donate_argnums=(0,))
def cow_clone_slots(slabs, src_ids, dst_ids):
    """Copy-on-write break: clone the ``src_ids`` rows of every slab
    leaf into the freshly-allocated ``dst_ids`` rows — one jitted
    gather/scatter over the donated slabs, batched over all of a shard's
    COW breaks in an activation plan.  Pad lanes pass
    ``src == dst == pad_slot`` (scratch-row self-copy, no effect), so
    the program compiles once per batch bucket.

    This is the only sanctioned way to make a shared arena row writable:
    the caller allocates a fresh slot, clones the shared row here, drops
    its reference on the shared slot, and repoints the session — the
    siblings' view of the original row is never touched.  Module-level
    jit like `recompress_arena_slots`: every arena (engines, fuzzed
    simulation traces) shares one compile per shape.  XLA's
    gather/scatter, as in `recompress_arena_slots`."""
    rows = jax.tree.map(lambda s: KREF.session_gather_ref(s, src_ids), slabs)
    return jax.tree.map(
        lambda s, r: KREF.session_scatter_ref(s, dst_ids, r), slabs, rows)


def make_null_step(cfg: ModelConfig, op: str, ragged: bool = False
                   ) -> Callable:
    """Control-plane-only arena step with `make_arena_step`'s exact
    call contract but NO model compute: returns zero logits of the
    contract shape and the slabs untouched.

    The serve-simulation harness (`tests/simulation.py`) injects this
    as the engine's ``step_factory`` so thousands of fuzzed
    admit->schedule->offload->restore->cancel traces exercise the REAL
    scheduler/arena/session/admission objects — free-list moves, host
    offload transfers, verdicts — without paying model FLOPs or jit
    compiles per trace."""
    del ragged

    def fn(params, slabs, ids, tokens, lengths):
        del params, ids, lengths
        if op == "ingest":
            return None, slabs
        B, _, L = tokens.shape
        return np.zeros((B, 1, L, cfg.vocab_size), np.float32), slabs
    return fn


def _jit_with_specs(fn, cfg: ModelConfig, dist: DistContext,
                    ingest: bool = False, batch_sharded: bool = True,
                    shard_cache_seq: bool = False,
                    params_shapes=None) -> Callable:
    state_specs, tok_spec = serve_specs(
        cfg, dist, batch_sharded=batch_sharded,
        shard_cache_seq=shard_cache_seq)
    mesh = dist.mesh
    pspecs = SH.param_pspecs(cfg, params_shapes, dist) \
        if params_shapes is not None else None
    p_in = SH.named(mesh, pspecs) if pspecs is not None else None
    st_in = SH.named(mesh, state_specs)
    vocab_sharded = dist.model_axis \
        if divisible(cfg.vocab_size, dist.n_model) else None
    logit_spec = P(dist.batch_axes if batch_sharded else None, None,
                   vocab_sharded)
    if ingest:
        out_sh = st_in
    else:
        out_sh = (SH.named(mesh, logit_spec), st_in)
    return jax.jit(fn,
                   in_shardings=(p_in, st_in, SH.named(mesh, tok_spec)),
                   out_shardings=out_sh,
                   donate_argnums=(1,))
