"""Observability layer: histogram percentile math (property-tested),
metrics export, request-lifecycle tracing, flight recorder, and the
NullRecorder bit-exactness acceptance criterion.

The percentile properties pin down the fixed-bucket histogram contract
(`repro.obs.metrics.Histogram`): quantiles are bucket upper bounds —
exact at bucket boundaries, monotone in q, and merge is associative
(integer counts; sums associative up to float addition, tested with
integer-valued samples where it is exact).

The tracing properties run the REAL serve stack through the
deterministic simulation harness (`tests/simulation.py`, ManualClock):
every submitted request reaches exactly ONE terminal span, span
timestamps never decrease, and two identical runs produce byte-equal
traces.

The acceptance test proves the default `NullRecorder` path is
bit-exact: the same seeded traffic through a traced and an untraced
engine yields identical verdict sequences, identical results, and
``np.array_equal`` arena slabs.
"""
import json
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.models import transformer as T
from repro.obs import (DEFAULT_TIME_BUCKETS, Histogram, ManualClock,
                       MetricsRegistry, Observability, render_prometheus)
from repro.obs.trace import TERMINALS, FlightRecorder, TraceRecorder
from simulation import ServeSimulation

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYP = True
except ImportError:
    HAVE_HYP = False

needs_hyp = pytest.mark.skipif(not HAVE_HYP, reason="hypothesis not installed")

BOUNDS = (1.0, 2.0, 5.0, 10.0)


# -- histogram percentile math ----------------------------------------

def test_histogram_bucket_boundary_exactness():
    """Samples ON bucket boundaries are recovered exactly by quantile():
    the sample lands in the bucket whose upper bound equals it."""
    h = Histogram(BOUNDS)
    for v in (1.0, 2.0, 5.0, 10.0):
        h.observe(v)
    assert h.quantile(0.25) == 1.0
    assert h.quantile(0.50) == 2.0
    assert h.quantile(0.75) == 5.0
    assert h.quantile(1.00) == 10.0


def test_histogram_empty_and_overflow():
    h = Histogram(BOUNDS)
    assert h.quantile(0.5) == 0.0             # empty -> 0.0
    h.observe(99.0)                           # beyond the largest bound
    assert h.quantile(0.5) == float("inf")    # overflow bucket -> inf
    assert h.counts[-1] == 1


def test_histogram_validation():
    with pytest.raises(ValueError):
        Histogram(())                         # no buckets
    with pytest.raises(ValueError):
        Histogram((1.0, 1.0))                 # not strictly increasing
    with pytest.raises(ValueError):
        Histogram((1.0, float("inf")))        # inf bound is implicit
    h = Histogram(BOUNDS)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        h.merge(Histogram((1.0, 2.0)))        # different ladders


if HAVE_HYP:
    samples = st.lists(
        st.floats(min_value=0.0, max_value=20.0, allow_nan=False,
                  width=32),
        min_size=0, max_size=50)

    @needs_hyp
    @settings(max_examples=60, deadline=None)
    @given(samples)
    def test_histogram_quantiles_monotone(vals):
        """q1 <= q2 implies quantile(q1) <= quantile(q2), any sample set."""
        h = Histogram(BOUNDS)
        for v in vals:
            h.observe(v)
        qs = [0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0]
        got = [h.quantile(q) for q in qs]
        assert got == sorted(got)

    @needs_hyp
    @settings(max_examples=60, deadline=None)
    @given(st.lists(st.lists(st.integers(min_value=0, max_value=15),
                             max_size=20), min_size=3, max_size=3))
    def test_histogram_merge_associative(shards):
        """(a+b)+c == a+(b+c) exactly — integer-valued samples make the
        float sum associative too, so equality is bitwise."""
        hs = []
        for shard in shards:
            h = Histogram(BOUNDS)
            for v in shard:
                h.observe(float(v))
            hs.append(h)
        a, b, c = hs
        left, right = a.merge(b).merge(c), a.merge(b.merge(c))
        assert left.counts == right.counts
        assert left.sum == right.sum
        assert left.count == right.count
        for q in (0.5, 0.95, 0.99):
            assert left.quantile(q) == right.quantile(q)

    @needs_hyp
    @settings(max_examples=60, deadline=None)
    @given(samples)
    def test_histogram_merge_equals_single(vals):
        """Observing a stream into two shards then merging equals
        observing it all into one histogram (counts and quantiles)."""
        one = Histogram(BOUNDS)
        a, b = Histogram(BOUNDS), Histogram(BOUNDS)
        for i, v in enumerate(vals):
            one.observe(v)
            (a if i % 2 == 0 else b).observe(v)
        m = a.merge(b)
        assert m.counts == one.counts
        assert m.count == one.count
        for q in (0.5, 0.95, 0.99):
            assert m.quantile(q) == one.quantile(q)


# -- registry + export -------------------------------------------------

def test_registry_declare_idempotent_and_conflicts():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "h", labels=("kind",))
    assert reg.counter("x_total", "h", labels=("kind",)) is c1
    with pytest.raises(ValueError):
        reg.gauge("x_total")                   # type conflict
    with pytest.raises(ValueError):
        reg.counter("x_total", labels=("other",))   # label conflict
    with pytest.raises(ValueError):
        reg.counter("bad name")                # invalid name
    with pytest.raises(ValueError):
        c1.labels(wrong="a")                   # undeclared label
    with pytest.raises(ValueError):
        c1.inc()                               # labelled family needs labels
    with pytest.raises(ValueError):
        c1.labels(kind="a").inc(-1)            # counters are monotonic


def test_snapshot_and_prometheus_roundtrip():
    reg = MetricsRegistry()
    reg.counter("req_total", "requests", labels=("kind",)).labels(
        kind="query").inc(3)
    reg.gauge("depth", "queue depth").set(7)
    h = reg.histogram("lat_seconds", "latency", buckets=BOUNDS)
    h.observe(1.0)
    h.observe(99.0)
    snap = reg.snapshot()
    json.dumps(snap)                           # JSON-serializable (inf ok)
    assert snap["req_total"]["values"][0] == {
        "labels": {"kind": "query"}, "value": 3}
    hv = snap["lat_seconds"]["values"][0]
    assert hv["count"] == 2 and hv["counts"][-1] == 1
    text = reg.to_prometheus()
    assert 'req_total{kind="query"} 3' in text
    assert "depth 7" in text
    # cumulative buckets + the implicit +Inf bucket equal to _count
    assert 'lat_seconds_bucket{le="+Inf"} 2' in text
    assert "lat_seconds_count 2" in text
    # a saved snapshot re-renders to the identical exposition
    assert render_prometheus(snap) == text


def test_shard_labeled_histogram_merge_keeps_percentiles_exact():
    """The sharded serve engine keeps one histogram child per shard
    label; merging the per-shard children (`aggregate()`) must give the
    EXACT percentiles of a single unsharded histogram fed the same
    stream — counts are integers, so the merge is bitwise, not
    approximate."""
    reg = MetricsRegistry()
    fam = reg.histogram("lat_seconds", "latency", buckets=BOUNDS,
                        labels=("shard",))
    one = Histogram(BOUNDS)
    stream = [0.5, 1.0, 1.5, 2.0, 3.0, 5.0, 7.5, 10.0, 42.0, 0.1,
              2.5, 9.9, 1.0, 5.0]
    for i, v in enumerate(stream):
        fam.labels(shard=str(i % 4)).observe(v)    # round-robin placement
        one.observe(v)
    merged = fam.aggregate()
    assert merged.counts == one.counts
    assert merged.count == one.count
    assert merged.sum == one.sum
    for q in (0.0, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0):
        assert merged.quantile(q) == one.quantile(q)
    # the per-shard children render with their label and survive a
    # snapshot round-trip
    snap = reg.snapshot()
    assert len(snap["lat_seconds"]["values"]) == 4
    text = render_prometheus(snap)
    assert 'lat_seconds_bucket{shard="0",le="+Inf"}' in text


# -- clocks ------------------------------------------------------------

def test_manual_clock():
    c = ManualClock(5.0)
    assert c.now() == 5.0 and c.now() == 5.0   # stable between advances
    assert c.advance(2.5) == 7.5
    with pytest.raises(ValueError):
        c.advance(-1.0)


# -- flight recorder ---------------------------------------------------

def test_flight_recorder_bounded():
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record(float(i), f"ev{i}")
    assert len(fr) == 4
    assert [e[1] for e in fr.events()] == ["ev6", "ev7", "ev8", "ev9"]
    assert fr.lines()[0].startswith("[t=6.000000] ev6")
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


# -- tracing through the simulated serve stack -------------------------

def _trace_events():
    return [
        ("submit", "a", "ingest", 4, 0, "t0"),
        ("submit", "b", "ingest", 8, 1, "t1"),
        ("submit", "a", "query", 4, 0, "t0"),
        ("run", 2),
        ("submit", "c", "ingest", 16, 0, "t0"),   # over the token bound
        ("submit", "b", "query", 2, 0, "t1"),
        ("offload", "a"),
        ("run", 5),
        ("close", "b"),
    ]


def _run_traced_sim(tiny_cfg):
    sim = ServeSimulation(tiny_cfg, n_slots=2, max_queued_tokens=12,
                          policy="block")
    sim.run_trace(_trace_events())
    sim.finish()
    return sim


def test_trace_conservation(tiny_cfg):
    """Every submitted request reaches exactly ONE terminal span; span
    timestamps are non-decreasing; nothing stays active at quiescence."""
    sim = _run_traced_sim(tiny_cfg)
    rec = sim.obs.recorder
    assert rec.active == []                    # quiescent: all terminal
    acc = sim.accounting()
    # cancelled backlog entries (close before pump) also get traces, so
    # completed >= submitted; every SUBMITTED request must have a trace
    assert len(rec.completed) >= len(acc.submitted)
    for req in acc.submitted:
        trace = rec.trace_of(req)
        assert trace is not None, f"no trace for {req.sid}/{req.kind}"
        terminals = [e for e in trace.events if e.name in TERMINALS]
        assert len(terminals) == 1, (
            f"{req.sid}: {[e.name for e in trace.events]}")
        ts = [e.ts for e in trace.events]
        assert ts == sorted(ts)
        assert trace.events[0].name == "submit"
        # outcome flags agree with the trace's terminal event
        expected = ("shed" if req.shed else
                    "cancelled" if req.cancelled else "finished")
        assert trace.terminal == expected


def test_trace_determinism(tiny_cfg):
    """Two identical simulated runs produce byte-identical traces (the
    ManualClock removes all host timing noise)."""
    def fingerprint(sim):
        return [(t.sid, t.kind, t.tenant,
                 tuple((e.name, e.ts) for e in t.events))
                for t in sim.obs.recorder.completed]
    a, b = _run_traced_sim(tiny_cfg), _run_traced_sim(tiny_cfg)
    fa, fb = fingerprint(a), fingerprint(b)
    assert fa == fb and fa                      # equal AND non-empty
    # the latency histograms are therefore identical too
    ha = a.engine.obs.registry.get("serve_queue_wait_seconds").aggregate()
    hb = b.engine.obs.registry.get("serve_queue_wait_seconds").aggregate()
    assert ha.counts == hb.counts and ha.sum == hb.sum


def test_queue_wait_measured_from_last_enqueue(tiny_cfg):
    """A pumped request's queue wait starts at the pump, not the submit
    (backlog time is backpressure, not scheduler queueing)."""
    sim = ServeSimulation(tiny_cfg, n_slots=2, max_queued_tokens=8,
                          policy="block")
    sim.apply(("submit", "a", "ingest", 8, 0, "t0"))   # fills the bound
    sim.apply(("submit", "b", "ingest", 8, 0, "t1"))   # backlogged
    sim.apply(("run", 10))                              # pops a, pumps b, pops b
    sim.finish()
    rec = sim.obs.recorder
    (trace_b,) = [t for t in rec.completed if t.sid == "b"]
    assert trace_b.ts_of("pumped") is not None
    wait = trace_b.ts_of("popped") - trace_b.ts_of("pumped")
    h = sim.engine.obs.registry.get(
        "serve_queue_wait_seconds").labels(kind="ingest")
    # b's observed wait must land in a bucket consistent with pump->pop,
    # not submit->pop; with the manual clock both pops happen in one
    # run event, so wait == 0.0 and lands in the first bucket
    assert wait == 0.0
    assert h.count == 2                                 # a and b


def test_admission_counters_monotonic_and_pump(tiny_cfg):
    """The pump no longer decrements 'admitted': every stats counter is
    monotonic across events, and pumped entries count under 'pumped'
    with 'admitted' covering DIRECT admissions only."""
    sim = ServeSimulation(tiny_cfg, n_slots=2, max_queued_tokens=8,
                          policy="block")
    sim.apply(("submit", "a", "ingest", 8, 0, "t0"))
    sim.apply(("submit", "b", "ingest", 8, 0, "t1"))
    sim.apply(("run", 10))
    sim.finish()
    st = sim.engine.admission.stats
    assert st == {"admitted": 1, "queued": 1, "shed_new": 0,
                  "shed_victims": 0, "pumped": 1}
    # monotone across the snapshot sequence, every key
    prev = None
    for snap in sim.snapshots:
        if prev is not None:
            for k, v in snap.admission_counters.items():
                assert v >= prev[k], (k, prev, snap.admission_counters)
        prev = snap.admission_counters


# -- engine integration (real model weights) ---------------------------

@pytest.fixture(scope="module")
def tiny_params(tiny_cfg):
    return T.init_lm(jax.random.PRNGKey(0), tiny_cfg)


def _drive(eng, cfg, seed=3):
    """Seeded traffic with offload churn; returns the verdict list."""
    rng = np.random.RandomState(seed)
    verdicts = []
    for s in range(4):
        eng.create_session(f"u{s}")
    for rnd in range(3):
        for s in range(4):
            ln = (3, 5)[rng.randint(2)]
            toks = rng.randint(0, cfg.vocab_size, size=ln).astype(np.int32)
            verdicts.append(eng.ingest(f"u{s}", toks,
                                       priority=int(rng.randint(2))))
        eng.run()
    for s in range(4):
        verdicts.append(eng.query(f"u{s}", np.arange(4, dtype=np.int32)))
    eng.run()
    return verdicts


def test_null_recorder_bit_exact(tiny_cfg, tiny_params):
    """ACCEPTANCE: an engine with the default NullRecorder produces
    bit-exact cache state and identical verdicts vs a recorder-enabled
    engine on the same seeded traffic."""
    from repro.serve import ServeEngine
    engs = [ServeEngine(tiny_params, tiny_cfg, n_slots=3, max_resident=2,
                        cache_len=32, batch_buckets=(1, 2, 4), obs=obs)
            for obs in (None, Observability.tracing())]
    outs = []
    for eng in engs:
        verdicts = _drive(eng, tiny_cfg)
        outs.append((
            [type(v).__name__ for v in verdicts],
            [None if v.request.result is None else np.asarray(v.request.result)
             for v in verdicts],
            jax.tree.leaves(eng._mgr["online"].arena.slabs),
        ))
    (v0, r0, s0), (v1, r1, s1) = outs
    assert v0 == v1                            # identical verdict sequence
    for a, b in zip(r0, r1):
        if a is None:
            assert b is None
        else:
            assert np.array_equal(a, b)        # bit-exact results
    for a, b in zip(s0, s1):
        assert np.array_equal(np.asarray(a), np.asarray(b))  # bit-exact slabs
    # and the traced engine actually traced
    assert engs[1].obs.recorder.completed
    assert engs[0].obs.recorder.flight_lines() == []


def test_compile_churn_counter_and_clamp(tiny_cfg, tiny_params):
    from repro.serve import ServeEngine
    eng = ServeEngine(tiny_params, tiny_cfg, n_slots=3, cache_len=32,
                      batch_buckets=(1, 2, 4))
    _drive(eng, tiny_cfg)
    fam = eng.obs.registry.get("serve_compiled_programs_total")
    seen = sum(int(child.value) for _, child in fam.children())
    assert seen == len(eng._seen_shapes) > 0
    # the sentinel clamp lives in compile_stats, nowhere else
    cs = eng.compile_stats()
    assert all(v >= -1 for v in cs.values())
    clamped = eng.compile_stats(clamped=True)
    assert all(v >= 0 for v in clamped.values())
    assert eng.compiled_programs() == sum(clamped.values())
    # stats compat view mirrors the registry counters
    st = eng.stats
    fam_req = eng.obs.registry.get("serve_requests_total")
    for kind in ("ingest", "query", "stream"):
        assert st[kind]["requests"] == int(
            fam_req.labels(kind=kind).value)


def test_metrics_snapshot_shape(tiny_cfg, tiny_params):
    from repro.serve import ServeEngine
    eng = ServeEngine(tiny_params, tiny_cfg, n_slots=3, max_resident=2,
                      cache_len=32, batch_buckets=(1, 2, 4),
                      obs=Observability.tracing())
    _drive(eng, tiny_cfg)
    snap = eng.metrics_snapshot()
    json.dumps(snap)                           # fully JSON-serializable
    m, d = snap["metrics"], snap["derived"]
    for fam in ("serve_requests_total", "serve_tokens_total",
                "admission_verdicts_total", "offload_bytes_total",
                "serve_arena_occupancy", "serve_queue_wait_seconds",
                "serve_e2e_latency_seconds",
                "serve_arena_consistency_errors_total"):
        assert fam in m, fam
    # the integrity probe ran and found nothing
    errs = m["serve_arena_consistency_errors_total"]["values"]
    assert all(v["value"] == 0 for v in errs)
    assert d["queue_depth"] == 0
    assert d["throughput_tok_per_s"] > 0
    assert set(d["admission"]) == {"admitted", "queued", "shed_new",
                                   "shed_victims", "pumped"}
    # prometheus export renders the same registry
    text = eng.metrics_prometheus()
    assert "serve_requests_total" in text and "serve_e2e_latency" in text


def test_flight_dump_on_error(tiny_cfg, capsys):
    """An exception escaping run() dumps the flight recorder to stderr
    (and is re-raised); the NullRecorder path dumps nothing."""
    def boom_factory(cfg, op, masked):
        def step(params, slabs, ids, toks, lens):
            raise RuntimeError("kaboom")
        return step

    from repro.serve import ServeEngine
    for traced in (True, False):
        obs = Observability.tracing(clock=ManualClock()) if traced else None
        eng = ServeEngine(None, tiny_cfg, n_slots=2, cache_len=32,
                          step_factory=boom_factory, obs=obs)
        eng.create_session("u")
        eng.ingest("u", np.arange(3, dtype=np.int32))
        with pytest.raises(RuntimeError, match="kaboom"):
            eng.run()
        err = capsys.readouterr().err
        if traced:
            assert "serve flight recorder" in err
            assert "kaboom" in err and "submit" in err
        else:
            assert err == ""


def test_trace_recorder_memory_bounded(tiny_cfg):
    """Completed traces are a ring: capacity stays bounded under
    sustained traffic (the completed-by-key map is pruned too)."""
    rec = TraceRecorder(clock=ManualClock(), registry=MetricsRegistry(),
                        keep_completed=8)

    class R:
        def __init__(self, i):
            self.sid, self.kind, self.tenant = f"s{i}", "ingest", "t"
            self.token_len = 1
    for i in range(100):
        r = R(i)
        rec.submit(r)
        rec.finished(r)
    assert len(rec.completed) == 8
    assert len(rec._completed_by_key) <= 16    # pruned at 2x maxlen


# -- engine host spans --------------------------------------------------

def test_null_recorder_span_is_reused_and_never_profiles(monkeypatch):
    """A NullRecorder span is one object per name, reused by every call,
    and times into the registry without touching the profiler."""
    import jax.profiler
    from repro.obs.trace import NullRecorder

    def no_profiler(*a, **k):
        raise AssertionError("NullRecorder entered the profiler")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_profiler)
    clock = ManualClock()
    obs = Observability(clock=clock)
    rec = obs.recorder
    assert isinstance(rec, NullRecorder)
    assert rec.span("serve.pop") is rec.span("serve.pop")
    assert rec.span("serve.pop") is not rec.span("serve.fetch")
    with rec.span("serve.pop"):
        clock.advance(2.5)
        with rec.span("serve.pop"):            # nested entries each count
            clock.advance(1.0)
    fam = obs.registry.get("serve_host_seconds_total")
    assert fam.labels(span="serve.pop").value == 4.5


class _SlowResult:
    """A device result whose host copy advances the manual clock."""

    def __init__(self, out, tick):
        self._out, self._tick = out, tick
        self.size, self.dtype = out.size, out.dtype

    def __array__(self, dtype=None, copy=None):
        self._tick("serve.fetch", 16.0)
        return np.asarray(self._out)


@pytest.mark.parametrize("n_shards", [1, 2])
def test_host_spans_hold_the_time_advanced_inside(
        tiny_cfg, tiny_params, monkeypatch, n_shards):
    """Under a ManualClock each serve.* child of serve_host_seconds_total
    holds exactly the time advanced inside its phase, and serve.drain
    the sum of them all, on the single and the sharded (loop) path."""
    import types

    import jax.numpy as jnp

    from repro.serve import ServeEngine
    from repro.serve import engine as E
    clock = ManualClock()
    eng = ServeEngine(tiny_params, tiny_cfg, n_slots=4, cache_len=32,
                      batch_buckets=(1, 2, 4), n_shards=n_shards,
                      obs=Observability(clock=clock))
    advanced = {name: 0.0 for name in E.HOST_SPANS}

    def tick(span, dt):
        advanced[span] += dt
        clock.advance(dt)

    def ticking(span, dt, fn):
        def wrapped(*a, **k):
            tick(span, dt)
            return fn(*a, **k)
        return wrapped

    sched = eng.scheduler
    for name in ("next_batch", "next_sharded_batches"):
        monkeypatch.setattr(sched, name,
                            ticking("serve.pop", 1.0, getattr(sched, name)))
    for mgr in eng._mgr.values():
        monkeypatch.setattr(mgr, "activate_batch", ticking(
            "serve.activate", 2.0, mgr.activate_batch))
        monkeypatch.setattr(mgr, "sync",
                            ticking("serve.sync", 64.0, mgr.sync))
    monkeypatch.setattr(E, "jnp", types.SimpleNamespace(
        asarray=ticking("serve.pack", 4.0, jnp.asarray), int32=jnp.int32))
    real_step = eng._step

    def slow_step(op, masked):
        step = real_step(op, masked)

        def run(*a):
            tick("serve.dispatch", 8.0)
            out, slabs = step(*a)
            return (None if out is None else _SlowResult(out, tick)), slabs
        return run
    monkeypatch.setattr(eng, "_step", slow_step)
    monkeypatch.setattr(eng.obs.recorder, "finished",
                        lambda req: tick("serve.deliver", 32.0))
    for s in range(3):
        eng.create_session(f"u{s}")
        eng.ingest(f"u{s}", np.arange(3 + s, dtype=np.int32))
        eng.query(f"u{s}", np.arange(4, dtype=np.int32))
    assert eng.run() > 0
    fam = eng.obs.registry.get("serve_host_seconds_total")
    got = {key[0]: child.value for key, child in fam.children()}
    drain = got.pop("serve.drain")
    advanced.pop("serve.drain")
    assert got == advanced
    assert all(v > 0 for v in advanced.values()), advanced
    assert drain == sum(advanced.values())


def _fetched_bytes_run(tiny_cfg, tiny_params, lens):
    """Serve one query per length; returns the engine, each query batch's
    (lanes, token bucket, real rows) and the results' itemsize."""
    from repro.serve import ServeEngine
    eng = ServeEngine(tiny_params, tiny_cfg, n_slots=6, cache_len=64,
                      batch_buckets=(1, 2, 4), token_buckets=(4, 8, 16))
    batches = []
    compact = eng._compact

    def record(batch, out):
        if batch.kind == "query":
            batches.append((batch.bucket, batch.token_len,
                            sum(batch.valid_lens)))
        return compact(batch, out)
    eng._compact = record
    verdicts = []
    for s, n in enumerate(lens):
        eng.create_session(f"u{s}")
        verdicts.append(eng.query(f"u{s}", np.arange(n, dtype=np.int32)))
    eng.run()
    return eng, batches, verdicts[0].request.result.dtype.itemsize


def _rung(n_real, lanes, t):
    """Rows fetched: the power of two >= max(n_real, 4) (the smallest
    token bucket) where that is at most half of lanes x t, else None."""
    r = 4
    while r < n_real:
        r *= 2
    return r if 2 * r <= lanes * t else None


def test_result_bytes_count_every_fetched_logit(tiny_cfg, tiny_params):
    """serve_result_bytes_total{query} is the bytes of every query
    batch's fetched logits: the rung's rows x vocab x itemsize for a
    compacted batch, lanes x 1 x token bucket x vocab x itemsize for the
    rest; serve_result_compactions_total{query} counts the batches that
    qualified."""
    eng, batches, itemsize = _fetched_bytes_run(
        tiny_cfg, tiny_params, (3, 5, 9, 2, 16))
    V = tiny_cfg.vocab_size
    want = sum((_rung(n, b, t) or b * 1 * t) * V * itemsize
               for b, t, n in batches)
    fam = eng.obs.registry.get("serve_result_bytes_total")
    assert len(batches) > 1
    assert fam.labels(kind="query").value == want
    assert fam.labels(kind="ingest").value == 0
    comp = eng.obs.registry.get("serve_result_compactions_total")
    assert comp.labels(kind="query").value == sum(
        _rung(n, b, t) is not None for b, t, n in batches)


def test_result_compactions_count_qualified_batches(tiny_cfg, tiny_params):
    """Short queries in wide buckets compact: the counter counts those
    batches, and the bytes are their rungs' rows."""
    eng, batches, itemsize = _fetched_bytes_run(
        tiny_cfg, tiny_params, (1, 2, 1, 3, 6, 5))
    V = tiny_cfg.vocab_size
    qualified = [(b, t, n) for b, t, n in batches
                 if _rung(n, b, t) is not None]
    assert qualified
    comp = eng.obs.registry.get("serve_result_compactions_total")
    assert comp.labels(kind="query").value == len(qualified)
    assert comp.labels(kind="ingest").value == 0
    fam = eng.obs.registry.get("serve_result_bytes_total")
    assert fam.labels(kind="query").value == sum(
        (_rung(n, b, t) or b * t) * V * itemsize for b, t, n in batches)


def test_profiler_trace_holds_engine_spans(tiny_cfg, tiny_params,
                                           tmp_path):
    """A CPU profiler trace of one traced drain holds every engine span,
    each inside serve.drain."""
    import glob

    from repro.serve import ServeEngine
    from repro.serve.engine import HOST_SPANS
    eng = ServeEngine(tiny_params, tiny_cfg, n_slots=3, cache_len=32,
                      batch_buckets=(1, 2, 4),
                      obs=Observability.tracing())
    for s in range(2):
        eng.create_session(f"u{s}")
        eng.ingest(f"u{s}", np.arange(5, dtype=np.int32))
        eng.query(f"u{s}", np.arange(4, dtype=np.int32))
    eng.run()                                   # compile outside the trace
    for s in range(2):
        eng.ingest(f"u{s}", np.arange(5, dtype=np.int32))
        eng.query(f"u{s}", np.arange(4, dtype=np.int32))
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in pd.planes for line in plane.lines
             for ev in line.events if ev.name.startswith("serve.")]
    assert {n for n, _, _ in spans} == set(HOST_SPANS)
    (drain,) = [(a, b) for n, a, b in spans if n == "serve.drain"]
    for name, a, b in spans:
        assert drain[0] <= a <= b <= drain[1], name
    # one fetch and one deliver per query batch, after the dispatches
    fetch = [a for n, a, _ in spans if n == "serve.fetch"]
    dispatch = [b for n, _, b in spans if n == "serve.dispatch"]
    assert len(fetch) == 1 and len(dispatch) == 2
    assert min(fetch) >= max(dispatch)


# -- timer lint --------------------------------------------------------

def test_no_stray_timers_lint(tmp_path):
    """The repo passes its own lint, and the lint actually catches an
    offender."""
    res = subprocess.run(
        [sys.executable, "scripts/check_no_stray_timers.py"],
        capture_output=True, text=True)
    assert res.returncode == 0, res.stdout + res.stderr
    bad = tmp_path / "src" / "repro" / "x"
    bad.mkdir(parents=True)
    (bad / "mod.py").write_text(
        "import time\nt0 = time.perf_counter()  # offender\n")
    res = subprocess.run(
        [sys.executable, "scripts/check_no_stray_timers.py",
         "--root", str(tmp_path)], capture_output=True, text=True)
    assert res.returncode == 1
    assert "mod.py:2" in res.stdout
