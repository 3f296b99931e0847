"""The trace reduction: busy time, idle share, per-op time and idle gaps
named by the harness's host spans, and the whole-slab op finder."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import arena, trace

HERE = os.path.dirname(os.path.abspath(__file__))


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def profile():
    host = NS(name="/host:CPU", stats=[], lines=[NS(name="python", events=[
        ev("bench.window", 0, 1000), ev("bench.run", 100, 500),
        ev("bench.wait", 600, 400), ev("unrelated", 0, 1000)])])
    # as on a TPU: an op is named by its HLO text and carries no module
    ops = NS(name="XLA Ops", events=[
        ev("%fusion.1 = bf16[4,8] fusion()", 100, 100),
        ev("%copy.2 = bf16[9,2,3] copy(bf16[9,6] %p)", 150, 150),
        ev("%fusion.1 = bf16[4,8] fusion()", 700, 100),
        ev("late", 1200, 50)])
    mods = NS(name="XLA Modules", events=[ev("jit_fn(1)", 100, 200),
                                          ev("jit_fn(1)", 700, 100)])
    dev = NS(name="/device:TPU:0", stats=[], lines=[ops, mods])
    return NS(planes=[host, dev])


def test_busy_idle_and_gaps():
    s = trace.reduce_profile(profile())
    assert s.window == (0.0, 1000.0)
    # busy: the union of [100, 300) and [700, 800); the op at 1200 ns
    # lies after the window
    assert s.busy_s == pytest.approx(300e-9)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.op_seconds() == pytest.approx({
        "%fusion.1 = bf16[4,8] fusion()": 200e-9,
        "%copy.2 = bf16[9,2,3] copy(bf16[9,6] %p)": 150e-9})
    assert s.module_seconds()["jit_fn(1)"] == pytest.approx((300e-9, 2))
    # each op is placed in the program run that holds its start
    assert {o.module for o in s.devices[0].ops} == {"jit_fn(1)"}
    # gaps, longest first: [300, 700) is mostly under bench.run,
    # [800, 1000) under bench.wait, [0, 100) under no harness span
    assert [(n, round(t * 1e9)) for n, t in s.gaps] == [
        ("bench.run", 400), ("bench.wait", 200),
        ("outside any span", 100)]


def test_no_device_plane_gives_nothing():
    p = profile()
    p.planes = p.planes[:1]
    assert trace.reduce_profile(p) is None


def test_fused_step_time_from_slab_ops(monkeypatch):
    """The program runs that hold a whole-slab op are the fused steps."""
    ctx = NS(trace=trace.reduce_profile(profile()))
    monkeypatch.setattr(arena, "slab_sizes", lambda ctx: {9 * 6})
    assert arena.slab_op_seconds(ctx) == pytest.approx(150e-9)
    assert arena.fused_step_seconds(ctx) == pytest.approx(300e-9)


def test_recorded_tpu_trace():
    """A trace recorded on one TPU v5e: Qwen2-0.5B through ServeEngine
    with a 65-row arena (64 sessions + scratch), one drain of 4 turns
    (an ingest and a query fused step) under a ``bench.run`` span."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(
        HERE, "data", "tpu_v5e_two_steps.xplane.pb"))
    s = trace.reduce_profile(pd)
    assert [d.name for d in s.devices] == ["/device:TPU:0"]
    runs = s.module_seconds()
    steps = {n: v for n, v in runs.items() if n.startswith("jit_fn(")}
    assert len(steps) == 2 and all(k == 1 for _, k in steps.values())
    assert 0 < s.busy_s <= s.window_s
    assert s.gaps and s.gaps[0][0] == "bench.run"
    # Qwen2-0.5B's arena leaves at 65 rows: the cache's k or v
    # (24 layers x 256 tokens x 2 heads x 64) and the memory's (128 tokens)
    sizes = {65 * 24 * 256 * 2 * 64, 65 * 24 * 128 * 2 * 64}
    slab = [o for d in s.devices for o in d.ops
            if arena.touches_slab(o.long_name, sizes)]
    assert {o.module for o in slab} == set(steps)
    assert sum(o.dur_ns for o in slab) < sum(v for v, _ in steps.values()) \
        * 1e9


def test_slab_ops_found_by_element_count():
    sizes = {9 * 6}
    assert arena.touches_slab("%copy.2 = bf16[9,2,3] copy(bf16[9,6] %p)",
                              sizes)
    assert not arena.touches_slab("%fusion.1 = bf16[4,8] fusion()", sizes)
    assert not arena.touches_slab("", sizes)
