"""Multi-tenant serve subsystem: arena, scheduler, engine, LRU offload,
ragged token-bucket batching (masked lanes)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import inference as I
from repro.core import masks as M
from repro.kernels import ops, ref
from repro.launch import serve as SRV
from repro.models import transformer as T
from repro.serve.arena import ArenaFull, SessionArena
from repro.serve.engine import ServeEngine
from repro.serve.scheduler import Scheduler
from repro.serve.session import SessionManager


def _assert_state_close(got, want, atol=2e-6):
    """Leafwise compare two state pytrees: int leaves (counters, lengths)
    exactly, float leaves to a tight tolerance."""
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        assert g.shape == w.shape
        if np.issubdtype(g.dtype, np.integer):
            np.testing.assert_array_equal(g, w)
        else:
            np.testing.assert_allclose(g, w, atol=atol, rtol=0)


@pytest.fixture(scope="module")
def params(tiny_cfg):
    return T.init_lm(jax.random.PRNGKey(0), tiny_cfg)


def _tokens(key, n, vocab=128):
    return jax.random.randint(jax.random.PRNGKey(key), (n,), 0, vocab)


# ---------------------------------------------------------------------------
# arena
# ---------------------------------------------------------------------------

def test_arena_alloc_free(tiny_cfg):
    arena = SessionArena.for_online(tiny_cfg, n_slots=3, cache_len=16)
    slots = [arena.alloc() for _ in range(3)]
    assert sorted(slots) == [0, 1, 2]
    assert arena.pad_slot == 3 and arena.pad_slot not in slots
    with pytest.raises(ArenaFull):
        arena.alloc()
    arena.free(slots[1])
    assert arena.n_free == 1 and arena.alloc() == slots[1]
    with pytest.raises(ValueError):
        arena.free(99)


def test_arena_pack_unpack_roundtrip(tiny_cfg):
    arena = SessionArena.for_online(tiny_cfg, n_slots=4, cache_len=8)
    for slot in (arena.alloc(), arena.alloc(), arena.alloc()):
        state = jax.tree.map(
            lambda s: jnp.full(s.shape, float(slot + 1), s.dtype)
            if jnp.issubdtype(s.dtype, jnp.floating)
            else jnp.full(s.shape, slot + 1, s.dtype),
            arena.template)
        arena.write_slot(slot, state)
    packed = arena.pack([2, 0, arena.pad_slot])
    assert packed.mem.k.shape[0] == 3
    np.testing.assert_array_equal(np.asarray(packed.mem.k[0]), 3.0)
    np.testing.assert_array_equal(np.asarray(packed.mem.k[1]), 1.0)
    np.testing.assert_array_equal(np.asarray(packed.mem.k[2]), 0.0)  # scratch
    assert int(packed.pos[0]) == 3 and int(packed.pos[1]) == 1
    # mutate and scatter back; untouched slots must be unaffected
    bumped = jax.tree.map(lambda x: x + 1, packed)
    arena.unpack([2, 0, arena.pad_slot], bumped)
    assert float(arena.read_slot(2).mem.k[0, 0, 0, 0, 0]) == 4.0
    assert float(arena.read_slot(0).mem.k[0, 0, 0, 0, 0]) == 2.0
    assert float(arena.read_slot(1).mem.k[0, 0, 0, 0, 0]) == 2.0  # untouched


def test_session_gather_scatter_kernel_matches_ref():
    """Pallas kernel (interpret mode) vs pure-jnp oracle, dup ids incl."""
    key = jax.random.PRNGKey(7)
    slab = jax.random.normal(key, (6, 40))
    ids = jnp.array([5, 0, 5, 3], jnp.int32)
    np.testing.assert_allclose(
        np.asarray(ops.session_gather(slab, ids, interpret=True)),
        np.asarray(ref.session_gather_ref(slab, ids)), atol=0)
    rows = jax.random.normal(jax.random.PRNGKey(8), (2, 40))
    ids2 = jnp.array([1, 4], jnp.int32)
    # ops.session_scatter donates the slab — take the oracle first
    expect = np.asarray(ref.session_scatter_ref(slab, ids2, rows))
    got = np.asarray(ops.session_scatter(slab, ids2, rows, interpret=True))
    np.testing.assert_allclose(got, expect, atol=0)


# ---------------------------------------------------------------------------
# scheduler
# ---------------------------------------------------------------------------

def test_scheduler_groups_by_kind_and_shape():
    sch = Scheduler(batch_buckets=(1, 2, 4))
    for s in range(3):
        sch.submit(f"s{s}", "ingest", np.zeros(8, np.int32))
    sch.submit("s0", "query", np.zeros(4, np.int32))
    sch.submit("s3", "ingest", np.zeros(16, np.int32))  # different shape
    b1 = sch.next_batch()
    assert (b1.kind, b1.token_len, b1.bucket) == ("ingest", 8, 4)
    assert [r.sid for r in b1.requests] == ["s0", "s1", "s2"] and b1.pad == 1
    b2 = sch.next_batch()
    assert (b2.kind, b2.token_len) == ("query", 4) and b2.bucket == 1
    b3 = sch.next_batch()
    assert (b3.kind, b3.token_len) == ("ingest", 16)
    assert sch.next_batch() is None


def test_scheduler_session_program_order():
    """A session's ops never reorder (even across priorities) and never
    co-batch."""
    sch = Scheduler(batch_buckets=(1, 2, 4))
    sch.submit("a", "ingest", np.zeros(8, np.int32), priority=1)
    sch.submit("a", "query", np.zeros(8, np.int32), priority=0)
    sch.submit("a", "ingest", np.zeros(8, np.int32), priority=0)
    kinds = []
    while (b := sch.next_batch()) is not None:
        assert len(b.requests) == 1
        kinds.append(b.kind)
    assert kinds == ["ingest", "query", "ingest"]


def test_scheduler_priority_fifo():
    sch = Scheduler(batch_buckets=(1, 2))
    sch.submit("a", "ingest", np.zeros(8, np.int32), priority=5)
    sch.submit("b", "ingest", np.zeros(8, np.int32), priority=0)
    sch.submit("c", "ingest", np.zeros(8, np.int32), priority=0)
    b1 = sch.next_batch()
    assert [r.sid for r in b1.requests] == ["b", "c"]


# ---------------------------------------------------------------------------
# engine: correctness, compile churn, offload
# ---------------------------------------------------------------------------

def test_engine_matches_single_session(tiny_cfg, params):
    """Batched multi-tenant execution == direct per-session ops."""
    chunks = [np.asarray(_tokens(i, 8)) for i in range(3)]
    query = np.asarray(_tokens(9, 4))
    eng = ServeEngine(params, tiny_cfg, n_slots=4, cache_len=32,
                      batch_buckets=(1, 2, 4))
    for s in range(3):
        eng.create_session(f"s{s}")
        eng.ingest(f"s{s}", chunks[s])
    reqs = [eng.query(f"s{s}", query).request for s in range(3)]
    eng.run()
    for s in range(3):
        st = I.init_online_state(tiny_cfg, 1, max_cache_len=32)
        st = I.ingest_context(params, tiny_cfg, st, chunks[s][None])
        lg, _ = I.prefill(params, tiny_cfg, st, query[None],
                          full_logits=True)
        np.testing.assert_allclose(np.asarray(reqs[s].result),
                                   np.asarray(lg[0]), atol=1e-5)


def test_engine_no_recompile_churn(tiny_cfg, params):
    """Mixed op kinds over bucketed shapes: compile count stays at one
    program per (kind, bucket, token_len) combination."""
    eng = ServeEngine(params, tiny_cfg, n_slots=8, cache_len=64,
                      batch_buckets=(1, 2, 4))
    for s in range(4):
        eng.create_session(f"s{s}")
    for wave in range(3):
        for s in range(4):
            eng.ingest(f"s{s}", np.asarray(_tokens(10 * wave + s, 8)))
        for s in range(wave + 1):   # 1, 2, 3 queries -> buckets 1, 2, 4
            eng.query(f"s{s}", np.asarray(_tokens(99 + s, 4)))
        eng.run()
    stats = eng.compile_stats()
    # ingest: always 4 sessions -> single (B=4, len=8) program
    assert stats["ingest"] == 1
    # query: batches of 1, 2, 3 -> buckets 1, 2, 4 -> three programs
    assert stats["query"] == 3
    assert eng.stats["ingest"]["batches"] == 3
    # re-run same shapes: no new programs
    for s in range(4):
        eng.ingest(f"s{s}", np.asarray(_tokens(500 + s, 8)))
    eng.run()
    assert eng.compile_stats() == stats


def test_lru_offload_restore(tiny_cfg):
    arena = SessionArena.for_online(tiny_cfg, n_slots=2, cache_len=8)
    mgr = SessionManager(arena, max_resident=2)
    for s in ("a", "b", "c"):
        mgr.create(s)
    mgr.activate("a"), mgr.activate("b")
    marked = jax.tree.map(
        lambda s: jnp.full(s.shape, 7, s.dtype), arena.template)
    arena.write_slot(mgr.sessions["a"].slot, marked)
    mgr.activate("c")                       # evicts LRU = "a"
    assert not mgr.sessions["a"].resident
    assert mgr.sessions["a"].n_offloads == 1
    assert mgr.sessions["b"].resident and mgr.sessions["c"].resident
    mgr.activate("a")                       # evicts LRU = "b", restores "a"
    assert not mgr.sessions["b"].resident
    got = arena.read_slot(mgr.sessions["a"].slot)
    for leaf, exp in zip(jax.tree.leaves(got), jax.tree.leaves(marked)):
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(exp))
    # pinned sessions are never evicted
    with pytest.raises(ArenaFull):
        mgr.activate("b", pinned={"a", "c"})


def test_engine_offload_preserves_logits(tiny_cfg, params):
    """offload -> restore roundtrip reproduces query logits exactly."""
    chunk, query = np.asarray(_tokens(1, 8)), np.asarray(_tokens(2, 4))

    def run(offload):
        eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=32,
                          batch_buckets=(1, 2))
        eng.create_session("u")
        eng.ingest("u", chunk)
        eng.run()
        if offload:
            eng.offload_session("u")
            assert not eng._mgr["online"].sessions["u"].resident
        req = eng.query("u", query).request
        eng.run()
        return np.asarray(req.result)

    np.testing.assert_array_equal(run(offload=False), run(offload=True))


def test_engine_stream_sessions(tiny_cfg, params):
    """Streaming sessions run through their own arena and match the
    direct stream_step path."""
    from repro.core import streaming as ST
    cfg = tiny_cfg.replace(ccm=tiny_cfg.ccm.__class__(
        comp_len=2, max_steps=4, stream_window=16, stream_sink=2,
        stream_chunk=4, stream_mem_slots=4))
    params2 = T.init_lm(jax.random.PRNGKey(1), cfg)
    eng = ServeEngine(params2, cfg, n_slots=1, cache_len=8,
                      stream_slots=2, batch_buckets=(1, 2))
    eng.create_session("u", kind="stream")
    toks = [np.asarray(_tokens(40 + i, 4)) for i in range(6)]
    reqs = [eng.stream("u", t).request for t in toks]
    eng.run()
    st = ST.init_stream_state(cfg, 1)
    for t, req in zip(toks, reqs):
        lg, st = ST.stream_step(params2, cfg, st, t[None])
        np.testing.assert_allclose(np.asarray(req.result),
                                   np.asarray(lg[0]), atol=1e-5)
    with pytest.raises(ValueError):
        eng.ingest("u", toks[0])   # wrong op kind for a stream session


def _stream_cfg(tiny_cfg):
    from repro.models.config import CCMConfig
    return tiny_cfg.replace(ccm=CCMConfig(
        comp_len=2, max_steps=4, stream_window=16, stream_sink=2,
        stream_chunk=4, stream_mem_slots=4))


def test_stream_lanes_eviction_gated_per_lane(tiny_cfg, params):
    """stream_step_lanes: a batch where ONE lane overflows must (a) match
    running each lane through the single-session stream_step bit-exactly
    in every state leaf, (b) leave non-overflowing lanes' memory and
    counters untouched by the masked eviction, and (c) keep the whole
    eviction/compression pass under a REAL `cond` (predicated on the
    batch-level any-lane-pending scalar, not a per-lane select)."""
    from repro.core import streaming as ST
    cfg = _stream_cfg(tiny_cfg)
    key = jax.random.PRNGKey(5)
    warm = [4, 1, 0]   # win_len 16 / 4 / 0 -> only lane 0 overflows on +4
    lanes = []
    for i, w in enumerate(warm):
        st = ST.init_stream_state(cfg, 1)
        for j in range(w):
            t = jax.random.randint(jax.random.fold_in(key, i * 10 + j),
                                   (1, 4), 0, 128)
            _, st = ST.stream_step(params, cfg, st, t)
        lanes.append(st)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
    toks = jax.random.randint(jax.random.fold_in(key, 99), (3, 1, 4),
                              0, 128)
    pending = ST.eviction_pending(cfg, stacked, jnp.full((3,), 4))
    assert list(np.asarray(pending)) == [True, False, False]
    fn = jax.jit(lambda s, t: ST.stream_step_lanes(params, cfg, s, t))
    lg, new = fn(stacked, toks)
    for i in range(3):
        lg1, st1 = ST.stream_step(params, cfg, lanes[i], toks[i])
        np.testing.assert_allclose(np.asarray(lg[i]), np.asarray(lg1),
                                   atol=2e-5, rtol=0)
        lane_new = jax.tree.map(lambda a: a[i], new)
        for g, w in zip(jax.tree.leaves(lane_new), jax.tree.leaves(st1)):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # non-overflow lanes: compression never touched memory or counters
    for i in (1, 2):
        np.testing.assert_array_equal(np.asarray(new.mem.k[i]),
                                      np.asarray(stacked.mem.k[i]))
        np.testing.assert_array_equal(np.asarray(new.mem.slots[i]),
                                      np.asarray(stacked.mem.slots[i]))
        assert int(new.pos[i]) == int(stacked.pos[i]) + 4
    jp = str(jax.make_jaxpr(
        lambda s, t: ST.stream_step_lanes(params, cfg, s, t))(stacked, toks))
    assert "cond[" in jp


def test_stream_lanes_no_overflow_skips_compression(tiny_cfg, params):
    """A batch with NO pending lane leaves every memory leaf bit-identical
    to the input — the gated branch was the identity."""
    from repro.core import streaming as ST
    cfg = _stream_cfg(tiny_cfg)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs),
                           *[ST.init_stream_state(cfg, 1) for _ in range(3)])
    toks = jax.random.randint(jax.random.PRNGKey(7), (3, 1, 4), 0, 128)
    _, new = ST.stream_step_lanes(params, cfg, stacked, toks)
    for g, w in zip(jax.tree.leaves(new.mem), jax.tree.leaves(stacked.mem)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_stream_lanes_ragged_matches_unpadded(tiny_cfg, params):
    """Ragged stream lanes through stream_step_lanes: a lane padded into
    a larger token bucket (valid_len < padded width) must match the
    unpadded single-session run bit-exactly — including the per-lane
    eviction trigger, which fires on valid lengths, not bucket widths."""
    from repro.core import streaming as ST
    cfg = _stream_cfg(tiny_cfg)
    key = jax.random.PRNGKey(9)
    # warm lane 0 to the brink: 4 more VALID tokens would overflow, but
    # its next request is only 2 valid tokens -> must NOT evict
    lanes = []
    for i, w in enumerate([4, 2]):
        st = ST.init_stream_state(cfg, 1)
        for j in range(w):
            t = jax.random.randint(jax.random.fold_in(key, i * 10 + j),
                                   (1, 4), 0, 128)
            _, st = ST.stream_step(params, cfg, st, t)
        lanes.append(st)
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
    toks = jax.random.randint(jax.random.fold_in(key, 77), (2, 1, 4), 0, 128)
    vls = jnp.array([2, 4], jnp.int32)
    lg, new = ST.stream_step_lanes(params, cfg, stacked, toks, lengths=vls)
    for i in range(2):
        vl = int(vls[i])
        lg1, st1 = ST.stream_step(params, cfg, lanes[i], toks[i][:, :vl])
        np.testing.assert_allclose(np.asarray(lg[i][:, :vl]),
                                   np.asarray(lg1), atol=2e-5, rtol=0)
        # counters (incl. the eviction trigger) exact; written float rows
        # to tolerance (padded-shape programs fuse matmuls differently)
        _assert_state_close(jax.tree.map(lambda a: a[i], new), st1)


def test_stream_batches_capped_by_stream_arena(tiny_cfg, params):
    """A stream batch must fit the (smaller) stream arena even when the
    online arena is larger — regression for the shared max_batch cap."""
    cfg = tiny_cfg.replace(ccm=tiny_cfg.ccm.__class__(
        comp_len=2, max_steps=4, stream_window=16, stream_sink=2,
        stream_chunk=4, stream_mem_slots=4))
    params2 = T.init_lm(jax.random.PRNGKey(2), cfg)
    eng = ServeEngine(params2, cfg, n_slots=8, cache_len=8,
                      stream_slots=2, batch_buckets=(1, 2, 4, 8))
    reqs = []
    for s in range(3):
        eng.create_session(f"t{s}", kind="stream")
        reqs.append(eng.stream(f"t{s}", np.asarray(_tokens(60 + s, 4))).request)
    eng.run()
    assert all(r.done for r in reqs)
    assert eng.stats["stream"]["requests"] == 3
    assert eng.stats["stream"]["batches"] == 2   # 2 + 1, capped at 2
    # oversized stream chunks are rejected at SUBMIT time, not mid-drain
    with pytest.raises(ValueError, match="stream_chunk"):
        eng.stream("t0", np.asarray(_tokens(70, 8)))   # 8 > stream_chunk 4


def test_close_session_cancels_queued_requests(tiny_cfg, params):
    """Closing a session drops its queued work (flagged cancelled);
    run() must not crash."""
    eng = ServeEngine(params, tiny_cfg, n_slots=4, cache_len=16,
                      batch_buckets=(1, 2, 4))
    eng.create_session("a")
    eng.create_session("b")
    ra = eng.ingest("a", np.asarray(_tokens(0, 8))).request
    rb = eng.ingest("b", np.asarray(_tokens(1, 8))).request
    eng.close_session("a")
    assert ra.cancelled and ra.done and ra.result is None
    assert eng.scheduler.pending == 1
    eng.run()
    assert rb.done and not rb.cancelled


def test_submit_validation_and_buffer_copy():
    """submit() rejects batched token arrays and copies caller buffers."""
    sch = Scheduler(batch_buckets=(1, 2))
    with pytest.raises(ValueError, match="one sequence"):
        sch.submit("a", "ingest", np.zeros((2, 8), np.int32))
    buf = np.arange(8, dtype=np.int32)
    req = sch.submit("a", "ingest", buf)
    buf[:] = -1                      # caller reuses the buffer pre-run
    np.testing.assert_array_equal(req.tokens[0], np.arange(8))


def test_engine_admission_guards(tiny_cfg, params):
    """KV-cache exhaustion and bad stream configs fail fast, not
    mid-drain."""
    eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=8,
                      batch_buckets=(1, 2))
    eng.create_session("u")
    eng.query("u", np.asarray(_tokens(0, 6)))
    with pytest.raises(ValueError, match="cache exhausted"):
        eng.query("u", np.asarray(_tokens(1, 6)))   # 6 + 6 > 8
    bad = tiny_cfg.replace(ccm=tiny_cfg.ccm.__class__(
        comp_len=2, max_steps=4, stream_window=8, stream_sink=4,
        stream_chunk=6))
    with pytest.raises(ValueError, match="stream_window"):
        ServeEngine(params, bad, n_slots=2, cache_len=8, stream_slots=1)


def test_reset_slots_beyond_largest_bucket(tiny_cfg):
    """reset_slots handles more stale slots than the largest batch
    bucket (regression: bucket < n crashed the zeroing scatter)."""
    from repro.launch.specs import SERVE_BATCH_BUCKETS
    n = max(SERVE_BATCH_BUCKETS) + 22
    arena = SessionArena.for_online(tiny_cfg, n_slots=n, cache_len=4)
    slots = [arena.alloc() for _ in range(n)]
    arena.mark_dirty(slots)
    arena.reset_slots(slots)     # must not raise
    assert float(jax.tree.leaves(arena.read_slot(slots[-1]))[0].sum()) == 0


# ---------------------------------------------------------------------------
# ragged token-bucket batching (masked lanes)
# ---------------------------------------------------------------------------

def test_ragged_block_write_matches_ref():
    """core.masks.ragged_block_write vs the kernels.ref oracle, including
    a block that overhangs the buffer end (where dynamic_update_slice
    would clamp-shift and corrupt earlier rows)."""
    key = jax.random.PRNGKey(3)
    buf = jax.random.normal(key, (2, 10, 3))
    blk = jax.random.normal(jax.random.PRNGKey(4), (2, 6, 3))
    got = M.ragged_block_write(buf, blk, 5, 4, axis=1)
    want = ref.ragged_block_write_ref(buf, blk, 5, 4, axis=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # full-valid write == dynamic_update_slice bit-for-bit
    got_full = M.ragged_block_write(buf, blk, 2, 6, axis=1)
    dus = jax.lax.dynamic_update_slice_in_dim(buf, blk, 2, axis=1)
    np.testing.assert_array_equal(np.asarray(got_full), np.asarray(dus))
    # overhang: start+6 > 10 — valid prefix written, rest frozen, no shift
    got_over = M.ragged_block_write(buf, blk, 8, 2, axis=1)
    want_over = ref.ragged_block_write_ref(buf, blk, 8, 2, axis=1)
    np.testing.assert_array_equal(np.asarray(got_over), np.asarray(want_over))
    np.testing.assert_array_equal(np.asarray(got_over)[:, :8], np.asarray(buf)[:, :8])


def test_scheduler_ragged_fill_shares_bucket():
    """Mixed-length requests of one kind share the head's token bucket;
    longer requests wait for their own batch."""
    sch = Scheduler(batch_buckets=(1, 2, 4), token_buckets=(4, 8, 16))
    sch.submit("a", "ingest", np.zeros(5, np.int32))
    sch.submit("b", "ingest", np.zeros(8, np.int32))
    sch.submit("c", "ingest", np.zeros(3, np.int32))
    sch.submit("d", "ingest", np.zeros(11, np.int32))   # > bucket 8: waits
    b1 = sch.next_batch()
    assert b1.token_len == 8 and [r.sid for r in b1.requests] == ["a", "b", "c"]
    assert b1.valid_lens == [5, 8, 3]
    b2 = sch.next_batch()
    assert b2.token_len == 16 and [r.sid for r in b2.requests] == ["d"]
    assert sch.next_batch() is None


def test_aging_prevents_starvation():
    """A low-priority request that can never share the flood's token
    bucket drains once its effective priority ages below the flood's —
    and provably starves with aging disabled (the ROADMAP bug)."""
    def flood_rounds(aging, rounds=60):
        sch = Scheduler(batch_buckets=(1, 2), token_buckets=(8, 16),
                        aging=aging)
        lo = sch.submit("lo", "ingest", np.zeros(16, np.int32), priority=5)
        for i in range(rounds):
            sch.submit(f"hi{2 * i}", "ingest", np.zeros(8, np.int32))
            sch.submit(f"hi{2 * i + 1}", "ingest", np.zeros(8, np.int32))
            batch = sch.next_batch()
            if any(r is lo for r in batch.requests):
                return i
        return None
    assert flood_rounds(aging=None) is None        # starves forever
    drained_at = flood_rounds(aging=4)
    # priority gap 5 x aging 4 -> head within ~20 rounds
    assert drained_at is not None and drained_at <= 24


def test_ragged_ingest_query_equivalence(tiny_cfg, params):
    """Mixed-length requests batched into one token bucket produce
    logits AND post-state numerically identical to unpadded runs."""
    eng = ServeEngine(params, tiny_cfg, n_slots=4, cache_len=32,
                      batch_buckets=(1, 2, 4))
    assert eng.ragged
    lens, qlens = [5, 8, 3], [4, 2, 3]
    chunks = [np.asarray(_tokens(i, L)) for i, L in enumerate(lens)]
    queries = [np.asarray(_tokens(9 + i, L)) for i, L in enumerate(qlens)]
    for s, c in enumerate(chunks):
        eng.create_session(f"s{s}")
        eng.ingest(f"s{s}", c)
    reqs = [eng.query(f"s{s}", q).request for s, q in enumerate(queries)]
    eng.run()
    # all three lengths shared ONE batch per op kind (the point of
    # ragged batching — exact grouping would have taken 3 + 3 batches)
    assert eng.stats["ingest"]["batches"] == 1
    assert eng.stats["query"]["batches"] == 1
    mgr = eng._mgr["online"]
    for s in range(3):
        st = I.init_online_state(tiny_cfg, 1, max_cache_len=32)
        st = I.ingest_context(params, tiny_cfg, st, chunks[s][None])
        lg, st = I.prefill(params, tiny_cfg, st, queries[s][None],
                           full_logits=True)
        assert reqs[s].result.shape[0] == qlens[s]   # sliced by valid_len
        np.testing.assert_allclose(np.asarray(reqs[s].result),
                                   np.asarray(lg[0]), atol=2e-6, rtol=0)
        got = mgr.arena.read_slot(mgr.sessions[f"s{s}"].slot)
        _assert_state_close(got, st)


def test_ragged_stream_equivalence(tiny_cfg, params):
    """Stream chunks padded up to stream_chunk match the unpadded path
    bit-for-bit, including across eviction boundaries."""
    from repro.core import streaming as ST
    cfg = tiny_cfg.replace(ccm=tiny_cfg.ccm.__class__(
        comp_len=2, max_steps=4, stream_window=16, stream_sink=2,
        stream_chunk=4, stream_mem_slots=4))
    params2 = T.init_lm(jax.random.PRNGKey(5), cfg)
    eng = ServeEngine(params2, cfg, n_slots=1, cache_len=8,
                      stream_slots=2, batch_buckets=(1, 2))
    eng.create_session("u", kind="stream")
    # 8 chunks of 3 tokens (padded to the stream_chunk-4 bucket) push the
    # 16-token window through multiple evictions
    toks = [np.asarray(_tokens(70 + i, 3)) for i in range(8)]
    reqs = [eng.stream("u", t).request for t in toks]
    eng.run()
    assert eng.stats["stream"]["pad_tokens"] == 8    # one pad per chunk
    st = ST.init_stream_state(cfg, 1)
    for t, req in zip(toks, reqs):
        lg, st = ST.stream_step(params2, cfg, st, t[None])
        assert req.result.shape[0] == 3
        # 16 float32 ulps at |logit| ~ 3.5: XLA reassociates the vmapped
        # lane matmuls' sums differently from the unbatched step's
        np.testing.assert_allclose(np.asarray(req.result),
                                   np.asarray(lg[0]), atol=4e-6, rtol=0)
    assert int(st.mem.slots) > 0                     # evictions compressed
    mgr = eng._mgr["stream"]
    got = mgr.arena.read_slot(mgr.sessions["u"].slot)
    _assert_state_close(got, st)


def test_ragged_matches_exact_scheduling(tiny_cfg, params):
    """The same mixed-length traffic through token-bucketed vs exact-
    length scheduling yields identical results — padding is semantics-
    free; only the batching (and compile count) differs."""
    lens = [3, 5, 8, 5, 3, 8]

    def run(token_buckets):
        eng = ServeEngine(params, tiny_cfg, n_slots=8, cache_len=32,
                          batch_buckets=(1, 2, 4, 8),
                          token_buckets=token_buckets)
        outs = []
        for s, L in enumerate(lens):
            eng.create_session(f"s{s}")
            eng.ingest(f"s{s}", np.asarray(_tokens(s, L)))
        reqs = [eng.query(f"s{s}", np.asarray(_tokens(50 + s, L))).request
                for s, L in enumerate(lens)]
        eng.run()
        return ([np.asarray(r.result) for r in reqs],
                sum(s["batches"] for s in eng.stats.values()),
                eng.compiled_programs())

    ragged_out, ragged_batches, ragged_progs = run("auto")
    exact_out, exact_batches, exact_progs = run(None)
    for a, b in zip(ragged_out, exact_out):
        np.testing.assert_allclose(a, b, atol=2e-6, rtol=0)
    assert ragged_batches < exact_batches
    assert ragged_progs < exact_progs


def test_make_arena_step_golden_rows(tiny_cfg, params):
    """Golden regression: gather->op->scatter leaves untouched slab rows
    bit-identical, and pad lanes only ever land on the scratch row — the
    silent-corruption class the PR 1 overflow guard fixed."""
    arena = SessionArena.for_online(tiny_cfg, n_slots=4, cache_len=16)
    for slot in range(4):
        arena.alloc()
        state = jax.tree.map(
            lambda s: jnp.full(s.shape, float(slot + 1), s.dtype)
            if jnp.issubdtype(s.dtype, jnp.floating)
            else jnp.full(s.shape, slot + 1, s.dtype),
            arena.template)
        arena.write_slot(slot, state)
    before = [np.array(leaf) for leaf in jax.tree.leaves(arena.slabs)]
    step = SRV.make_arena_step(tiny_cfg, "ingest", ragged=True)
    pad = arena.pad_slot
    ids = jnp.asarray([1, pad, pad], jnp.int32)      # dup pad lanes
    toks = np.zeros((3, 1, 8), np.int32)
    toks[0, 0, :5] = np.asarray(_tokens(30, 5))
    lengths = np.asarray([5, 8, 8], np.int32)
    out, slabs = step(params, arena.slabs, ids, toks, lengths)
    arena.slabs = slabs
    assert out is None
    after = [np.asarray(leaf) for leaf in jax.tree.leaves(arena.slabs)]
    changed = False
    for b, a in zip(before, after):
        # rows 0, 2, 3 were NOT in the batch: bit-identical
        for row in (0, 2, 3):
            np.testing.assert_array_equal(a[row], b[row])
        changed = changed or not np.array_equal(a[1], b[1])
    assert changed                                   # the live row did run
    # the live row's update equals the direct unpadded op on its state
    st = jax.tree.map(
        lambda s: jnp.full(s.shape, 2.0, s.dtype)
        if jnp.issubdtype(s.dtype, jnp.floating)
        else jnp.full(s.shape, 2, s.dtype), arena.template)
    want = I.ingest_context(params, tiny_cfg, st, jnp.asarray(toks[0, :, :5]))
    _assert_state_close(arena.read_slot(1), want)


# ---------------------------------------------------------------------------
# admission verdicts + batched offload (PR 5)
# ---------------------------------------------------------------------------

def test_submit_returns_admitted_verdict(tiny_cfg, params):
    """Default (unbounded) engine: every submit returns Admitted and the
    request handle rides on the verdict."""
    from repro.serve import Admitted
    eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=16,
                      batch_buckets=(1, 2))
    eng.create_session("u")
    v = eng.ingest("u", np.asarray(_tokens(0, 4)))
    assert isinstance(v, Admitted) and not v.shed_victims
    eng.run()
    assert v.request.done and not v.request.shed


def test_offload_structured_noop_statuses(tiny_cfg, params):
    """Offloading an unknown, never-activated, or already-offloaded
    session is a structured no-op — it used to KeyError (unknown) or
    silently pass (already offloaded)."""
    eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=16,
                      batch_buckets=(1, 2))
    assert eng.offload_session("ghost").status == "unknown"
    eng.create_session("u")
    assert eng.offload_session("u").status == "fresh"       # never ran
    eng.ingest("u", np.asarray(_tokens(0, 4)))
    eng.run()
    r = eng.offload_session("u")
    assert r.status == "offloaded" and r.moved and r.n_bytes > 0
    assert eng.offload_session("u").status == "already-offloaded"
    # the SessionManager-level per-victim path agrees
    mgr = eng._mgr["online"]
    assert mgr.offload("u").status == "already-offloaded"
    assert mgr.offload("ghost").status == "unknown"
    # and the session still restores bit-exactly after the no-ops
    q = eng.query("u", np.asarray(_tokens(1, 3))).request
    eng.run()
    assert q.done and q.result.shape == (3, tiny_cfg.vocab_size)


def _offload_interleaved_trace(cfg, params, *, batched, async_off,
                               seed):
    """Shared fuzz body: 5 warm sessions, k-victim offload, interleaved
    cancel() + re-activation of a session mid-offload, final drain.
    Returns (offload statuses, s0 host-state leaves, result logits)."""
    rng = np.random.RandomState(seed)
    lens = rng.randint(2, 9, size=5)
    eng = ServeEngine(params, cfg, n_slots=6, cache_len=32,
                      batch_buckets=(1, 2, 4), batched_offload=batched,
                      async_offload=async_off)
    for s in range(5):
        eng.create_session(f"s{s}")
        eng.ingest(f"s{s}", np.asarray(_tokens(100 * seed + s,
                                               int(lens[s]))))
    eng.run()
    mgr = eng._mgr["online"]
    # k victims at once, with a duplicate and an unknown mixed in
    res = mgr.offload_batch(["s0", "s1", "s2", "s0", "nope"])
    # mid-offload interleavings: queue work on an offloaded session
    # (restore), cancel another's queued work, close one while offloaded
    rq = eng.query("s1", np.asarray(_tokens(50 + seed, 3)))      # restore
    rc = eng.ingest("s3", np.asarray(_tokens(60 + seed, 4)))
    eng.close_session("s3")                                      # cancel
    eng.close_session("s2")                                      # offloaded
    r4 = eng.query("s4", np.asarray(_tokens(70 + seed, 2)))      # resident
    eng.run()
    mgr.sync()
    host0 = [np.asarray(x)
             for x in jax.tree.leaves(mgr.sessions["s0"].host_state)]
    assert rc.request.cancelled and rc.request.result is None
    return ([r.status for r in res], host0,
            [np.asarray(rq.request.result), np.asarray(r4.request.result)])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_offload_bitexact_vs_per_victim(tiny_cfg, params, seed):
    """k-victim stacked offload/restore == per-victim path bit-for-bit:
    same no-op statuses, same host bytes, same post-restore logits —
    including interleaved cancel() and re-activation mid-offload, and
    with the async double-buffer on."""
    base = _offload_interleaved_trace(tiny_cfg, params, batched=False,
                                      async_off=False, seed=seed)
    for batched, async_off in ((True, False), (True, True)):
        got = _offload_interleaved_trace(tiny_cfg, params, batched=batched,
                                         async_off=async_off, seed=seed)
        assert got[0] == base[0] == ["offloaded", "offloaded", "offloaded",
                                     "already-offloaded", "unknown"]
        for a, b in zip(got[1], base[1]):
            np.testing.assert_array_equal(a, b)
        for a, b in zip(got[2], base[2]):
            np.testing.assert_array_equal(a, b)


def test_offload_cost_model_decision():
    """Pure decision function: transfer cost is the round trip, replay
    cost is history tokens at the replay rate."""
    from repro.serve import OffloadCostModel
    cm = OffloadCostModel(host_bandwidth=1e9, replay_tokens_per_s=100.0)
    assert cm.transfer_seconds(5 * 10**8) == pytest.approx(1.0)
    assert cm.replay_seconds(50) == pytest.approx(0.5)
    assert cm.prefers_recompute(5 * 10**8, 50)        # 0.5 s < 1.0 s
    assert not cm.prefers_recompute(5 * 10**8, 200)   # 2.0 s > 1.0 s


def test_recompute_offload_replays_history(tiny_cfg, params):
    """A cost model that always prefers recompute drops the state (no
    host copy) and replays the session's recorded requests on the next
    activation; logits match the transfer path."""
    from repro.serve import OffloadCostModel
    chunk, query = np.asarray(_tokens(3, 6)), np.asarray(_tokens(4, 4))

    def run(cm):
        eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=32,
                          batch_buckets=(1, 2), offload_cost_model=cm)
        eng.create_session("u")
        eng.ingest("u", chunk)
        eng.run()
        r = eng.offload_session("u")
        q = eng.query("u", query).request
        eng.run()
        return r.status, np.asarray(q.result)

    always = OffloadCostModel(host_bandwidth=1.0, replay_tokens_per_s=1e12)
    s1, rec = run(always)
    s2, xfer = run(None)
    assert (s1, s2) == ("recompute", "offloaded")
    # replay runs the same B=1 programs here -> bit-exact; keep a small
    # tolerance anyway (replay is only numerically, not bitwise,
    # guaranteed when the original ops ran at a different batch shape)
    np.testing.assert_allclose(rec, xfer, atol=1e-5, rtol=0)


def test_shed_query_releases_exact_cache_reservation(tiny_cfg, params):
    """Regression: a query shed at SUBMIT time must leave the KV-cache
    token accounting exactly where it was — the old code decremented a
    reservation that was never made, under-counting the cache and
    letting a later oversized query slip past the exhaustion guard."""
    from repro.serve import Shed
    eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=16,
                      batch_buckets=(1, 2),
                      admission_policy="reject-new", max_queued_tokens=6)
    eng.create_session("u")
    v1 = eng.query("u", np.asarray(_tokens(0, 4)))   # cached: 4, queued: 4
    v2 = eng.query("u", np.asarray(_tokens(1, 5)))   # queue 4+5 > 6: shed
    assert isinstance(v2, Shed) and v2.request.shed
    assert eng._cached["u"] == 4      # reservation reversed, not drained
    eng.run()
    assert v1.request.done
    # 4 cached + 13 > cache_len 16: the guard must still fire (the old
    # under-count of 0 would have let this through to corrupt the cache)
    with pytest.raises(ValueError, match="cache exhausted"):
        eng.query("u", np.asarray(_tokens(2, 13)))
    # and a fitting query still passes
    v3 = eng.query("u", np.asarray(_tokens(3, 4)))
    eng.run()
    assert v3.request.done and eng._cached["u"] == 8


def test_explicit_quota_overrides_default_lane_cap(tiny_cfg, params):
    """Regression: a tenant with an explicit TenantQuota whose
    max_resident is None is residency-UNBOUNDED even when default_quota
    caps residency — batch formation must not throttle it to the
    default (one batch of 4, not 4 single-lane batches)."""
    from repro.serve import TenantQuota
    eng = ServeEngine(params, tiny_cfg, n_slots=6, cache_len=16,
                      batch_buckets=(1, 2, 4),
                      tenant_quotas={"vip": TenantQuota(
                          max_queued_tokens=100)},
                      default_quota=TenantQuota(max_resident=1))
    for s in range(4):
        eng.create_session(f"v{s}", tenant="vip")
        eng.ingest(f"v{s}", np.asarray(_tokens(s, 4)))
    eng.run()
    assert eng.stats["ingest"]["batches"] == 1    # one 4-lane batch
    # default-quota tenants ARE capped to one lane per batch
    for s in range(3):
        eng.create_session(f"d{s}")              # tenant="default"
        eng.ingest(f"d{s}", np.asarray(_tokens(10 + s, 4)))
    eng.run()
    assert eng.stats["ingest"]["batches"] == 4    # 1 + three 1-lane


def test_invalid_submit_leaves_no_reservation(tiny_cfg, params):
    """Regression: a shape-validation error at submit must raise with
    ZERO side effects — the old order reserved KV-cache tokens before
    validating, permanently inflating the session's accounting."""
    eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=16,
                      batch_buckets=(1, 2))
    eng.create_session("u")
    with pytest.raises(ValueError, match="one sequence"):
        eng.query("u", np.zeros((2, 5), np.int32))   # batched tokens
    assert eng._cached.get("u", 0) == 0              # nothing leaked
    v = eng.query("u", np.asarray(_tokens(0, 8)))    # 8 <= 16: admitted
    eng.run()
    assert v.request.done and eng._cached["u"] == 8


def test_zero_batch_run_syncs_async_offload(tiny_cfg, params):
    """Regression: run() on an empty queue must still barrier async
    offload transfers — `if n:` used to skip sync(), pinning the
    stacked host buffers of explicit offload_session() calls forever."""
    eng = ServeEngine(params, tiny_cfg, n_slots=2, cache_len=16,
                      batch_buckets=(1, 2), async_offload=True)
    eng.create_session("u")
    eng.ingest("u", np.asarray(_tokens(0, 4)))
    eng.run()
    assert eng.offload_session("u").status == "offloaded"
    mgr = eng._mgr["online"]
    assert len(mgr._inflight) == 1       # transfer in flight
    assert eng.run() == 0                # zero batches popped...
    assert len(mgr._inflight) == 0       # ...but the barrier still ran


# ---------------------------------------------------------------------------
# result delivery: device-side row compaction before the host copy
# ---------------------------------------------------------------------------

def _record_padded(eng):
    """Wrap ``eng._compact`` to keep each batch's padded fused-step
    result on the host, beside whether the engine compacted it."""
    seen = []
    compact = eng._compact

    def record(batch, out):
        padded = None if out is None else np.asarray(out)
        res, offsets = compact(batch, out)
        seen.append((list(batch.requests), padded, offsets is not None))
        return res, offsets
    eng._compact = record
    return seen


def _assert_results_are_padded_rows(seen):
    for reqs, padded, _ in seen:
        for i, r in enumerate(reqs):
            want = padded[i, 0, :r.token_len]
            assert r.result.shape == want.shape
            assert r.result.dtype == want.dtype
            np.testing.assert_array_equal(r.result.view(np.uint8),
                                          want.view(np.uint8))


@pytest.mark.parametrize("token_buckets,lens,compacted", [
    ((4, 8, 16), (2, 3, 1), True),        # 3 real lanes + 1 pad lane
    ((4, 16), (5,), True),                # one real lane, 5 of 16 rows
    ((4, 8, 16), (4, 4, 4, 4), False),    # full batch: fetched whole
    ((4, 8, 16), (3, 1), True),           # 4 rows of 8
    ((4, 8, 16), (9, 16), False),         # 25 real of 32: rung 32
    ((4, 8, 16), (9, 16, 2, 3), True),    # 30 real of 64 rows: rung 32
])
def test_query_results_bit_equal_padded_rows(tiny_cfg, params,
                                             token_buckets, lens,
                                             compacted):
    """A query's result is bit for bit the fused step's own padded rows
    ``out[i, 0, :L]``, in its shape and dtype, whether the batch was
    compacted on the device or fetched whole."""
    eng = ServeEngine(params, tiny_cfg, n_slots=4, cache_len=64,
                      batch_buckets=(1, 2, 4), token_buckets=token_buckets)
    seen = _record_padded(eng)
    reqs = []
    for s, n in enumerate(lens):
        eng.create_session(f"u{s}")
        reqs.append(eng.query(f"u{s}", np.asarray(_tokens(70 + s, n)))
                    .request)
    eng.run()
    assert all(r.done and r.result.shape == (r.token_len, 128)
               for r in reqs)
    _assert_results_are_padded_rows(seen)
    assert any(c for *_, c in seen) == compacted
    fam = eng.obs.registry.get("serve_result_compactions_total")
    assert fam.labels(kind="query").value == sum(c for *_, c in seen)


def test_stream_results_bit_equal_padded_rows(tiny_cfg):
    """Stream batches take the same compaction: results equal the padded
    rows bit for bit, on a ragged batch with a pad lane."""
    cfg = _stream_cfg(tiny_cfg)
    p = T.init_lm(jax.random.PRNGKey(1), cfg)
    eng = ServeEngine(p, cfg, n_slots=1, cache_len=8, stream_slots=4,
                      batch_buckets=(1, 2, 4), token_buckets=(4, 8, 16))
    seen = _record_padded(eng)
    reqs = []
    for s, n in enumerate((1, 2, 1)):
        eng.create_session(f"st{s}", kind="stream")
        reqs.append(eng.stream(f"st{s}", np.asarray(_tokens(80 + s, n)))
                    .request)
    eng.run()
    assert [c for *_, c in seen] == [True]
    assert [r.result.shape for r in reqs] == [(1, 128), (2, 128), (1, 128)]
    _assert_results_are_padded_rows(seen)
    fam = eng.obs.registry.get("serve_result_compactions_total")
    assert fam.labels(kind="stream").value == 1
    assert fam.labels(kind="query").value == 0


@pytest.mark.parametrize("n_real,floor,padded,want", [
    (1, 4, 16, 4), (4, 4, 16, 4), (5, 4, 16, 8), (8, 4, 16, 8),
    (9, 4, 16, None),                     # rung 16 is all 16 rows
    (8, 4, 8, None),
    (4, 4, 8, 4),                         # exactly half: compacted
    (5, 4, 8, None),
    (65, 64, 256, 128), (129, 64, 256, None), (3, 64, 4096, 64),
    (1024, 64, 4096, 1024), (1025, 64, 4096, 2048),
    (2049, 64, 4096, None), (7, 3, 16, 8), (1, 3, 16, 4),
])
def test_result_rung_half_size_rule(n_real, floor, padded, want):
    """The rung is the smallest power of two at least the floor and the
    real rows, taken only while it is at most half the padded rows."""
    from repro.serve.engine import result_rung
    assert result_rung(n_real, floor, padded) == want


@pytest.mark.parametrize("lens,rows", [
    ((2, 2, 2, 2), 8),                    # 8 real of 16: rung 8, half
    ((3, 2, 2, 2), None),                 # 9 real: rung 16, fetched whole
])
def test_result_bytes_at_half_size_boundary(tiny_cfg, params, lens, rows):
    """At the R <= B*T/2 boundary the engine fetches the rung's rows, and
    past it the padded result; the byte counter follows."""
    eng = ServeEngine(params, tiny_cfg, n_slots=4, cache_len=64,
                      batch_buckets=(1, 2, 4), token_buckets=(4, 8, 16))
    for s, n in enumerate(lens):
        eng.create_session(f"u{s}")
        eng.query(f"u{s}", np.asarray(_tokens(90 + s, n)))
    eng.run()
    fetched = (rows if rows is not None else 4 * 4) * 128 * 4   # float32
    reg = eng.obs.registry
    assert reg.get("serve_result_bytes_total").labels(
        kind="query").value == fetched
    assert reg.get("serve_result_compactions_total").labels(
        kind="query").value == (rows is not None)


def test_no_compiles_after_every_fused_shape_seen(tiny_cfg, params):
    """Once each fused query shape has run once, its row gathers exist
    too: random query mixes afterwards compile nothing."""
    import jax.monitoring as mon
    buckets, tbuckets = (1, 2, 4), (4, 8, 16)
    eng = ServeEngine(params, tiny_cfg, n_slots=4, cache_len=1024,
                      batch_buckets=buckets, token_buckets=tbuckets)
    sids = [f"u{s}" for s in range(4)]
    for s in sids:
        eng.create_session(s)
    rng = np.random.default_rng(7)

    def drain(lens):
        for s, n in zip(sids, lens):
            eng.query(s, rng.integers(0, 128, n, dtype=np.int32))
        eng.run()
    for b in buckets:
        for t in tbuckets:
            drain([t] * b)                      # unmasked
            drain([t] + [t - 1] * (b - 1) if b > 1 else [t - 1])  # masked
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)
    mon.register_event_duration_secs_listener(listen)
    try:
        before = eng.obs.registry.get("serve_result_compactions_total") \
            .labels(kind="query").value
        for _ in range(12):
            t = int(rng.choice(tbuckets))
            lo = {4: 1, 8: 5, 16: 9}[t]
            drain(rng.integers(lo, t + 1, int(rng.integers(1, 5))).tolist())
        after = eng.obs.registry.get("serve_result_compactions_total") \
            .labels(kind="query").value
    finally:
        mon.unregister_event_duration_listener(listen)
    assert compiles == []
    assert after > before                       # the mixes did compact
