"""Smoke run of the multi-tenant serve engine on a TPU.

Serves Qwen2-0.5B at its published widths (``configs/qwen2_05b.py``)
with seeded random weights through the entry points a user calls:
``ServeEngine.create_session`` / ``ingest`` / ``query`` / ``stream`` /
``fork_session`` and a prefix-cache hit.  Each served query's logits are
checked against a direct ``ingest_context`` + ``prefill`` of the same
session on the same params and device, and negative controls (a wrong
history) must fail that check.  First, the arena's Pallas gather/scatter
is checked bit for bit against XLA's on every arena leaf.

    python chip_smoke.py            # one chip: arena kernels, serve phase
    python chip_smoke.py --chips 4  # four chips: session-sharded phase only

The four-chip phase serves the same seeded traffic on a 4-shard engine
(one arena shard per chip, `shard_map` hot path) and on a 1-shard engine,
and checks that the results agree (and that another session's result
does not), that no session state crossed a shard, and that each
session's arena row lives on its shard's chip.

Earlier lines report compile seconds, per-phase wall seconds (smoke
timings, not benchmarks), compiled-program counts and peak device
memory.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": n}}``.
Without a TPU the script exits non-zero and prints no result.

The persistent compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says; when that is unset, to ``.jax_cache`` next to this script.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(REPO, "src"))

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402

from repro.configs.registry import get_config         # noqa: E402
from repro.core import inference as I                 # noqa: E402
from repro.models import transformer as T             # noqa: E402
from repro.obs import perf_counter                    # noqa: E402
from repro.serve.engine import ServeEngine            # noqa: E402

ARCH = "qwen2-0.5b"
CACHE_LEN = 256
N_SLOTS = 8
STREAM_SLOTS = 2
CHUNK, QUERY, STREAM_CHUNK = 32, 8, 16      # tokens per request kind
# Engine logits (vmapped, bucketed lanes) vs the direct per-session path,
# both in bfloat16 compute: |diff| <= LOGIT_RTOL * max|direct logit|.
# bf16 keeps 8 mantissa bits (eps 7.8e-3); 24 layers of sums reassociated
# across lane batches stay within a few eps of the logit scale.
LOGIT_RTOL = 5e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileTimer:
    """Sums JAX's backend-compile durations (persistent-cache lookups
    included, so a warm cache shows as fewer seconds)."""

    def __init__(self):
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def log(msg: str) -> None:
    print(msg, flush=True)


def build(cfg, seed: int):
    """Seeded full-width params, initialised on the device."""
    return jax.jit(T.init_lm, static_argnums=1)(jax.random.PRNGKey(seed),
                                                cfg)


class Direct:
    """The reference: one session's state driven through
    ``ingest_context`` / ``prefill(full_logits=True)`` without the
    engine (no arena, no lanes, no buckets)."""

    def __init__(self, cfg, params):
        self.cfg, self.params = cfg, params
        self._ingest = jax.jit(
            lambda p, st, t: I.ingest_context(p, cfg, st, t))
        self._query = jax.jit(
            lambda p, st, t: I.prefill(p, cfg, st, t, full_logits=True))

    def query_logits(self, chunks, query):
        st = I.init_online_state(self.cfg, 1, CACHE_LEN)
        for c in chunks:
            st = self._ingest(self.params, st, jnp.asarray(c)[None])
        logits, _ = self._query(self.params, st, jnp.asarray(query)[None])
        return np.asarray(logits[0], np.float32)


def distance(name: str, got, want):
    """(max|got-want|, the bound) after the shape and finiteness checks."""
    got = np.asarray(got, np.float32)
    check(got.shape == want.shape,
          f"{name}: logits shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite logits")
    diff = np.abs(got - want)
    bound = LOGIT_RTOL * float(np.abs(want).max())
    log(f"check {name}: max|got-want|={float(diff.max()):.6g} "
        f"mean|got-want|={float(diff.mean()):.6g} bound={bound:.6g} "
        f"max|logit|={float(np.abs(want).max()):.6g}")
    return float(diff.max()), bound


def compare(name: str, got, want) -> float:
    err, bound = distance(name, got, want)
    check(err <= bound, f"{name}: engine logits differ from the reference "
                        f"by {err} > {bound}")
    return err


def control(name: str, got, wrong) -> float:
    """Negative control: logits of a wrong state (another session's
    rows, or a write that did not land) must fail `compare`."""
    err, bound = distance(f"control {name}", got, wrong)
    check(err > bound, f"control {name}: a wrong state is within the "
                       f"bound ({err} <= {bound}); the check cannot see it")
    return err


def _random(key, shape, dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)
    info = jnp.iinfo(dtype)
    return jax.random.randint(key, shape, info.min, info.max, dtype)


def kernel_phase(cfg, seed: int = 0) -> int:
    """The arena's Pallas gather/scatter against XLA's take / .at[].set
    on every leaf of the online and stream arenas.  Both are pure data
    movement, so they must agree bit for bit."""
    from repro.kernels import ops, ref
    from repro.serve.arena import online_template, stream_template
    ids = jnp.asarray([3, 0, N_SLOTS, 5], jnp.int32)   # N_SLOTS: scratch
    key = jax.random.PRNGKey(seed)
    leaves = (jax.tree.leaves(online_template(cfg, CACHE_LEN))
              + jax.tree.leaves(stream_template(cfg)))
    for i, leaf in enumerate(leaves):
        key, k_slab, k_rows = jax.random.split(key, 3)
        slab = _random(k_slab, (N_SLOTS + 1,) + leaf.shape, leaf.dtype)
        rows = _random(k_rows, ids.shape + leaf.shape, leaf.dtype)
        got = ops.session_gather(slab, ids, interpret=False)
        check(np.array_equal(np.asarray(got),
                             np.asarray(ref.session_gather_ref(slab, ids))),
              f"arena leaf {i} {leaf.shape}: kernel gather != jnp.take")
        want = np.asarray(ref.session_scatter_ref(slab, ids, rows))
        got = ops.session_scatter(slab, ids, rows, interpret=False)
        check(np.array_equal(np.asarray(got), want),
              f"arena leaf {i} {leaf.shape}: kernel scatter != .at[].set")
    log(f"check arena kernels: gather and scatter equal XLA's bit for bit "
        f"on {len(leaves)} leaves")
    return len(leaves)


def serve_phase(cfg, params, seed: int = 0) -> dict:
    """The one-chip serve phase: ingest / prefix hit / fork / query /
    stream through one ServeEngine, each query checked against the
    direct path.  Returns the engine's counters and phase timings."""
    timings = {}
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n, dtype=np.int32)

    eng = ServeEngine(params, cfg, n_slots=N_SLOTS, cache_len=CACHE_LEN,
                      stream_slots=STREAM_SLOTS)
    sids = ["s0", "s1", "s2"]
    hist = {sid: [toks(CHUNK)] for sid in sids}
    prefix = toks(CHUNK)
    hist["p0"] = [prefix]

    def timed(name, fn):
        t0 = perf_counter()
        fn()
        timings[name] = perf_counter() - t0

    def ingest_round_1():
        for sid in sids:
            eng.create_session(sid)
            eng.ingest(sid, hist[sid][0])
        eng.create_session("p0", prefix_tokens=prefix)   # prefix miss
        eng.run()

    timed("ingest_1", ingest_round_1)
    hits = eng.obs.registry.get("serve_prefix_dedup_hits_total")
    eng.create_session("p1", prefix_tokens=prefix)        # prefix hit
    check(hits.value == 1, f"prefix cache hits {hits.value} != 1")
    hist["p1"] = [prefix]

    def ingest_round_2():
        for sid in sids + ["p0"]:
            c = toks(CHUNK)
            hist[sid].append(c)
            eng.ingest(sid, c)
        eng.run()

    timed("ingest_2", ingest_round_2)
    eng.fork_session("s0", "s0f")
    hist["s0f"] = list(hist["s0"])
    queries = {sid: toks(QUERY) for sid in hist}
    reqs = {}

    def query_round():
        for sid, q in queries.items():
            reqs[sid] = eng.query(sid, q).request
        eng.run()

    timed("fork_query", query_round)
    check(eng.obs.registry.get("serve_fork_total").value == 1,
          "fork did not execute")
    streams = []

    def stream_rounds():
        for sid in ("t0", "t1"):
            eng.create_session(sid, kind="stream")
        for _ in range(3):
            for sid in ("t0", "t1"):
                streams.append(eng.stream(sid, toks(STREAM_CHUNK)).request)
            eng.run()

    timed("stream", stream_rounds)
    for r in streams:
        check(r.done and r.result is not None, "stream request not served")
        check(r.result.shape == (STREAM_CHUNK, cfg.vocab_size),
              f"stream logits shape {r.result.shape}")
        check(bool(np.isfinite(np.asarray(r.result, np.float32)).all()),
              "non-finite stream logits")

    errs = []

    def reference():
        direct = Direct(cfg, params)
        errs.extend(compare(sid, reqs[sid].result,
                            direct.query_logits(hist[sid], queries[sid]))
                    for sid in queries)
        got = reqs["s0"].result
        control("s0 without its last chunk", got,
                direct.query_logits(hist["s0"][:-1], queries["s0"]))
        control("s0 on s1's history", got,
                direct.query_logits(hist["s1"], queries["s0"]))

    timed("direct_check", reference)
    return {"stats": eng.stats, "compile_stats": eng.compile_stats(),
            "compiled_programs": eng.compiled_programs(),
            "max_abs_err": max(errs), "timings": timings}


def sharded_phase(cfg, params, seed: int = 0) -> dict:
    """Four chips: a 4-shard mesh engine against a 1-shard engine on the
    same seeded traffic, including one offload/restore round trip."""
    from repro.launch.mesh import make_session_mesh
    timings = {}
    n_sessions, n_shards = 8, 4
    rng = np.random.default_rng(seed)

    def toks(n):
        return rng.integers(0, cfg.vocab_size, n, dtype=np.int32)

    # every session asks the same queries, so only its own rows tell its
    # logits apart from another session's
    queries = [toks(QUERY), toks(QUERY)]
    traffic = [[toks(CHUNK), toks(CHUNK)] + queries
               for _ in range(n_sessions)]
    mesh = make_session_mesh(n_shards)

    def drive(mesh_or_none):
        eng = ServeEngine(params, cfg, n_slots=N_SLOTS, cache_len=CACHE_LEN,
                          mesh=mesh_or_none)
        out = []
        for i in range(n_sessions):
            eng.create_session(f"s{i}")
        for rnd in range(2):
            for i in range(n_sessions):
                eng.ingest(f"s{i}", traffic[i][rnd])
            eng.run()
        for i in range(n_sessions):
            out.append(eng.query(f"s{i}", traffic[i][2]).request)
        eng.run()
        check(eng.offload_session("s0").moved, "offload of s0 failed")
        out.append(eng.query("s0", traffic[0][3]).request)   # restores s0
        eng.run()
        return eng, out

    t0 = perf_counter()
    eng1, r1 = drive(None)
    timings["one_shard"] = perf_counter() - t0
    t0 = perf_counter()
    eng4, r4 = drive(mesh)
    timings["four_shards"] = perf_counter() - t0
    errs = [compare(f"shard-vs-single s{i % n_sessions}", b.result,
                    np.asarray(a.result, np.float32))
            for i, (a, b) in enumerate(zip(r1, r4))]
    for i in range(n_sessions):
        j = (i + 1) % n_sessions
        control(f"shard s{i} vs single s{j}", r4[i].result,
                np.asarray(r1[j].result, np.float32))
    moves = eng4.obs.registry.get("serve_cross_shard_moves_total").value
    check(moves == 0, f"{moves} cross-shard moves")
    arena = eng4._mgr["online"].arena
    devices = list(mesh.devices.flat)
    for leaf in jax.tree.leaves(arena.slabs):
        rows_of = {d: idx[0] for d, idx in
                   leaf.sharding.devices_indices_map(leaf.shape).items()}
        check(len(rows_of) == n_shards, "arena slab not split per chip")
        for i in range(n_sessions):
            slot = eng4._mgr["online"].sessions[f"s{i}"].slot
            rows = rows_of[devices[eng4.shard_of(f"s{i}")]]
            check(rows.start <= slot < rows.stop,
                  f"s{i}'s row {slot} is not on its shard's chip")
    shards = [eng4.shard_of(f"s{i}") for i in range(n_sessions)]
    log(f"sharded: shards={shards} "
        f"cross_shard_moves={moves}")
    return {"stats": eng4.stats, "compile_stats": eng4.compile_stats(),
            "compiled_programs": eng4.compiled_programs(),
            "max_abs_err": max(errs), "timings": timings}


def _configure_compile_cache() -> None:
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(REPO, ".jax_cache"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _configure_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found ({e})", file=sys.stderr)
        return 2
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{dev.platform!r}); this smoke runs only on a TPU",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache "
        f"{jax.config.jax_compilation_cache_dir}")
    timer = CompileTimer()
    cfg = get_config(ARCH)
    t0 = perf_counter()
    params = build(cfg, args.seed)
    jax.block_until_ready(params)
    init_s = perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"model: {ARCH} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} params={n_params}")
    if args.chips == 1:
        t0 = perf_counter()
        kernel_phase(cfg, seed=args.seed)
        kernel_s = perf_counter() - t0
        report = serve_phase(cfg, params, seed=args.seed)
        report["timings"] = {"arena_kernels": kernel_s, **report["timings"]}
    else:
        report = sharded_phase(cfg, params, seed=args.seed)
    timings = {"init_params": init_s, **report["timings"]}
    for name, sec in timings.items():
        log(f"smoke-timing (not a benchmark) {name}: {sec:.3f}")
    log(f"check: max|got-want| over all checked queries="
        f"{report['max_abs_err']:.6g}")
    log(f"compile: backend_compile_s={timer.seconds:.3f} "
        f"persistent_cache_hits={timer.cache_hits}")
    log(f"engine: compiled_programs={report['compiled_programs']} "
        f"compile_stats={report['compile_stats']}")
    log(f"engine: stats={json.dumps(report['stats'], sort_keys=True)}")
    mem = dev.memory_stats() or {}
    log(f"memory: peak_bytes_in_use={mem.get('peak_bytes_in_use')} "
        f"bytes_limit={mem.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
