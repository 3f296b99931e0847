"""The benchmark harness: one cell of ``BENCHMARK.json`` per run.

A run builds the cell's configuration through the program's own entry
point (``repro.serve.engine.ServeEngine``), opens every session and
replays its history (set-up), then serves the mix's turns open-loop for
``--seconds``: each turn's ``ingest`` + ``query`` is submitted at its due
time, ``engine.run()`` drains what is queued, and a turn's time to first
token runs from its due time to the moment ``run`` hands back its
query's logits.  After the window the program is freed and a sample of
the window's queries is checked against the plain reference.

Everything a cell is made of is found by name: the configuration
``bench/configs/<config>.json`` (with ``bench/models/<model>.py``, its
plain reference, and ``bench/models/<model>_engine.py``, its mapping to
the program), the mix ``bench/traffic/<traffic>.json``, the cell's fixed
rate ``bench/cells/<cell>.json`` and each per-layer metric's reader
``bench/metrics/<metric>.py``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    spec: dict            # the workloads entry
    config: dict          # bench/configs/<config>.json
    mix: dict             # bench/traffic/<traffic>.json
    rate: float           # turns per second, bench/cells/<cell>.json
    bench: dict           # BENCHMARK.json
    root: str = ROOT      # the checkout the files were read from


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(root, "BENCHMARK.json")
    spec = next((w for w in bench["workloads"] if w["name"] == name), None)
    if spec is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == spec["config"])
    config = load_json(root, entry["file"])
    mix = load_json(root, "bench", "traffic", spec["traffic"] + ".json")
    rate = float(load_json(root, "bench", "cells",
                           name + ".json")["rate_turns_per_s"])
    return Cell(name, spec, config, mix, rate, bench, root)


def model_modules(config: dict):
    """(plain reference, mapping to the program) for a configuration."""
    from importlib import import_module
    return (import_module(f"bench.models.{config['model']}"),
            import_module(f"bench.models.{config['model']}_engine"))


class CompileCounter:
    """Counts XLA compilations and persistent-cache loads as JAX reports
    them."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


class GCPauses:
    """Collections and the seconds they took, from ``gc.callbacks``."""

    def __init__(self):
        self.count, self.seconds, self.longest = 0, 0.0, 0.0
        self._t = None
        gc.callbacks.append(self._cb)

    def _cb(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            d = time.perf_counter() - self._t
            self.count += 1
            self.seconds += d
            self.longest = max(self.longest, d)
            self._t = None

    def close(self):
        gc.callbacks.remove(self._cb)


def drain_lines(drains, buckets) -> str:
    """Drains of the window by the batch bucket their turns fill: count,
    median and longest seconds."""
    by: Dict[int, List[float]] = {}
    for n, sec in drains:
        by.setdefault(next((b for b in sorted(buckets) if n <= b), n),
                      []).append(sec)
    parts = [f"<={b} turns: {len(v)}, median {np.median(v):.3f} s, "
             f"longest {max(v):.3f} s" for b, v in sorted(by.items())]
    return f"drains: {len(drains)}; " + "; ".join(parts)


def check_device(chips: int):
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX finds no accelerator ({e})") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX platform is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds "
                     f"{len(devices)}")
    return devices


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def cache_dir() -> str:
    """Where JAX_COMPILATION_CACHE_DIR says, else a fixed path inside the
    checkout."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(ROOT, ".jax_cache"))


def configure_cache() -> None:
    """JAX's persistent compilation cache at ``cache_dir()``, for every
    program however small."""
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: entries carry no access-time files, whatever the
    # machine's default
    jax.config.update("jax_compilation_cache_max_size", -1)


def counters(eng) -> Dict[str, Dict[str, float]]:
    """The engine's serve counters, by family and op kind."""
    fams = eng.obs.registry.snapshot()
    out: Dict[str, Dict[str, float]] = {}
    for fam in ("serve_tokens_total", "serve_pad_tokens_total",
                "serve_lanes_total", "serve_batches_total",
                "serve_requests_total", "serve_pad_lanes_total"):
        out[fam] = {}
        for s in fams[fam]["values"]:
            out[fam][s["labels"].get("kind", "")] = float(s["value"])
    return out


def counter_delta(a, b):
    return {f: {k: b[f].get(k, 0.0) - a[f].get(k, 0.0) for k in b[f]}
            for f in b}


def seen_shapes(eng) -> set:
    fams = eng.obs.registry.snapshot()
    return {(s["labels"]["kind"], s["labels"]["shape"])
            for s in fams["serve_compiled_programs_total"]["values"]
            if s["value"] > 0}


def shape_label(op: str, b: int, t: int, masked: bool):
    return (op, f"{b}x{t}" + ("/masked" if masked else ""))


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------

def engine_settings(config: dict) -> dict:
    e = dict(config["engine"])
    e["max_steps"] = config["ccm"]["max_steps"]
    return e


def build_engine(cell: Cell, seed: int, traced: bool):
    """The program's params from the seed and its engine, as the
    configuration's deployment sets it up."""
    import jax
    from repro.obs import Observability
    from repro.serve.engine import ServeEngine
    _, adapter = model_modules(cell.config)
    mcfg = adapter.model_config(cell.config)
    params = adapter.program_params(cell.config, mcfg, seed)
    jax.block_until_ready(params)
    e = cell.config["engine"]
    eng = ServeEngine(params, mcfg, n_slots=e["n_slots"],
                      cache_len=e["cache_len"],
                      batch_buckets=tuple(e["batch_buckets"]),
                      token_buckets=tuple(e["token_buckets"]),
                      obs=Observability.tracing(keep_completed=1 << 16)
                      if traced else Observability())
    return eng, mcfg


def set_up(eng, plan, max_group: int, token_buckets) -> Dict[str, float]:
    """Open every session, run the warm-up drains, then replay each
    session's remaining history in drains of at most ``max_group``
    sessions (one turn each), all through the public entry points.  A
    drain takes sessions whose next chunk pads to the same token bucket,
    the bucket most sessions wait on, so that it runs as one fused step.
    Returns the seconds of the warm-up and of the history, and the
    number of history drains."""
    from bench.traffic import bucket_of
    clock = time.perf_counter
    for s in plan.sessions:
        eng.create_session(s)
    done = {s: 0 for s in plan.sessions}
    t0 = clock()
    for drain in plan.warmup:
        for sid, op, toks in drain:
            getattr(eng, op)(sid, toks)
            done[sid] += 1
        eng.run()
    t1 = clock()
    drains = 0
    # the rest of the history, one turn (an ingest and its query, if
    # any) per session per drain
    queues = {s: plan.history[s][done[s]:] for s in plan.sessions}
    while any(queues.values()):
        by: Dict[int, List[str]] = {}
        for s in plan.sessions:
            if queues[s]:
                by.setdefault(bucket_of(queues[s][0][1].size,
                                        token_buckets), []).append(s)
        group = max(by.values(), key=len)[:max_group]
        for s in group:
            q = queues[s]
            op, toks = q.pop(0)
            getattr(eng, op)(s, toks)
            if op == "ingest" and q and q[0][0] == "query":
                eng.query(s, q.pop(0)[1])
        eng.run()
        drains += 1
    return {"warmup_s": t1 - t0, "history_s": clock() - t1,
            "history_drains": drains}


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Served:
    turn: object
    done_at: float = float("nan")     # seconds after the window opened
    ok: bool = False


def serve_window(eng, plan, seconds: float, keep: set):
    """Open loop: submit each turn at its due time, drain what is queued,
    stamp delivery.  Returns (served turns, kept logits by turn index,
    wake-up lateness samples, window start on the perf clock, drains as
    (turns, seconds))."""
    turns = plan.turns
    served = [Served(t) for t in turns]
    kept: Dict[int, np.ndarray] = {}
    late: List[float] = []
    drains: List[tuple] = []
    clock = time.perf_counter
    i, n = 0, len(turns)
    with span("bench.window"):
        t0 = clock()
        while True:
            now = clock() - t0
            pending = []
            with span("bench.submit"):
                while i < n and turns[i].due <= now:
                    t = turns[i]
                    eng.ingest(t.sid, t.chunk)
                    pending.append((i, eng.query(t.sid, t.query).request))
                    i += 1
            if pending:
                with span("bench.run"):
                    eng.run()
                done = clock() - t0
                drains.append((len(pending), done - now))
                with span("bench.deliver"):
                    for j, req in pending:
                        s = served[j]
                        s.done_at = done
                        s.ok = bool(req.done and req.result is not None
                                    and req.result.shape[0]
                                    == turns[j].query.size)
                        if j in keep and s.ok:
                            kept[j] = np.array(req.result, copy=True)
                continue
            if i >= n:
                break
            with span("bench.wait"):
                while True:
                    rest = turns[i].due - (clock() - t0)
                    if rest <= 0:
                        break
                    time.sleep(min(rest, 0.002) if rest > 0.002 else 0)
            late.append(clock() - t0 - turns[i].due)
    return served, kept, late, t0, drains


def throughput(served, seconds: float):
    """(tokens of the served turns, seconds until the window closed).
    Arrivals stop when ``seconds`` are up and the turns still in flight
    are drained; the window closes when the last of them is done, so
    every turn's tokens count over all of the time they took."""
    tokens = sum(s.turn.tokens for s in served if s.ok)
    closed = max([seconds] + [s.done_at for s in served])
    return tokens, closed


def percentile(xs, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    return float(np.percentile(np.asarray(xs, np.float64), q))


# ---------------------------------------------------------------------------
# per-layer metric context
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets."""
    dims: object                   # the reference's Dims of the config
    config: dict
    peaks: dict
    seconds: float
    counters: Dict[str, Dict[str, float]]   # deltas over the window
    trace: Optional[object]        # bench.trace.Summary
    turn_flops: List[int]          # model FLOPs of each window turn
    queue_waits: List[float]       # popped - submit, seconds, requests
    n_devices: int


def turn_flops(m, plan) -> List[int]:
    """Model FLOPs of each window turn, from every session's state when
    the turn runs (memory groups and cached query tokens)."""
    from bench import counts
    groups = {s: 0 for s in plan.sessions}
    cached = {s: 0 for s in plan.sessions}
    for s, evs in plan.history.items():
        for op, toks in evs:
            if op == "ingest":
                groups[s] += 1
            else:
                cached[s] += toks.size
    out = []
    for t in plan.turns:
        s = t.sid
        f = counts.ingest_flops(m, t.chunk.size, groups[s] * m.comp_len,
                                cached[s])
        groups[s] += 1
        f += counts.query_flops(m, t.query.size, groups[s] * m.comp_len,
                                cached[s])
        cached[s] += t.query.size
        out.append(f)
    return out


def queue_waits(eng, t0: float) -> List[float]:
    """popped - submit of every request submitted in the window (the
    recorder's clock is the perf clock the window runs on)."""
    out = []
    for tr in eng.obs.recorder.completed:
        sub = tr.ts_of("submit")
        w = tr.span("submit", "popped")
        if sub is not None and sub >= t0 and w is not None:
            out.append(w)
    return out


def per_layer_metrics(cell: Cell, ctx: Context) -> Dict[str, dict]:
    out = {}
    for spec in cell.bench.get("per_layer", []):
        cells = spec.get("workloads")
        if cells is not None and cell.name not in cells:
            continue
        reader = load_module(os.path.join(cell.root, "bench", "metrics",
                                          spec["name"] + ".py"),
                             "bench_metric_" + spec["name"])
        v = reader.read(ctx)
        if v is not None:
            out[spec["name"]] = {"value": float(v), "unit": spec["unit"]}
    return out


def breakdown(summary) -> dict:
    """Top device ops (named by the start of their HLO text) and the
    longest idle gaps."""
    ops = sorted(summary.op_seconds().items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n[:160], s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:10]]}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, require_tpu: bool = True,
             rate: Optional[float] = None, cache: bool = True) -> dict:
    """One run of a cell.  Returns the result line's object (``check``
    last) plus ``_lines``, informative lines for standard output.
    ``require_tpu=False`` and ``cache=False`` are for the CPU tests:
    they skip the look for a chip and leave JAX's compilation cache as
    the process has it."""
    import jax
    from bench import check, traffic
    from bench import trace as TR
    if require_tpu:
        devices = check_device(int(cell.spec["chips"]))
    else:
        devices = jax.devices()
    dev = devices[0]
    peaks = peaks_for(dev.device_kind) if require_tpu else {}
    if cache:
        configure_cache()
    compiles = CompileCounter()
    ref, _ = model_modules(cell.config)
    m = ref.dims(cell.config)
    eset = engine_settings(cell.config)
    rate = cell.rate if rate is None else rate
    plan = traffic.make_plan(cell.mix, eset, rate, seconds, seed, m.vocab)
    picks = check.sample_turns(plan, cell.config["check"]["sample_turns"],
                               seed)
    t_build = time.perf_counter()
    eng, _ = build_engine(cell, seed, traced=trace)
    t_built = time.perf_counter()
    parts = set_up(eng, plan, max(eset["batch_buckets"]),
                   eset["token_buckets"])
    warm = set(shape_label(*s) for s in
               traffic.warmup_shapes(cell.mix, eset))
    missing = warm - seen_shapes(eng)
    if missing:
        raise RuntimeError(f"warm-up did not reach {sorted(missing)}")
    before = counters(eng)
    c0, h0 = compiles.compiles, compiles.cache_hits
    trace_dir = os.path.join(ROOT, ".bench_trace")
    if trace:
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    gcp = GCPauses()
    served, kept, late, t0, drains = serve_window(eng, plan, seconds,
                                                  set(picks))
    gcp.close()
    setup_s = t0 - t_process
    summary = None
    if trace:
        jax.profiler.stop_trace()
        summary = TR.load(trace_dir)
        import shutil
        shutil.rmtree(trace_dir, ignore_errors=True)
    window_compiles = (compiles.compiles - c0) + (compiles.cache_hits - h0)
    delta = counter_delta(before, counters(eng))
    new_shapes = seen_shapes(eng) - warm
    waits = queue_waits(eng, t0) if trace else []
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    # end-to-end
    ttft = [(s.done_at - s.turn.due) * 1e3 for s in served]
    ok = [s for s in served if s.ok]
    in_window = sum(s.turn.tokens for s in ok if s.done_at <= seconds)
    tokens, closed = throughput(served, seconds)
    metrics_e2e = {
        "setup_s": {"value": setup_s, "unit": "s"},
        "ttft_p50_ms": {"value": percentile(ttft, 50), "unit": "ms"},
        "ttft_p95_ms": {"value": percentile(ttft, 95), "unit": "ms"},
        "tokens_per_s": {"value": tokens / closed, "unit": "tokens/s"},
    }
    third = max(1, len(served) // 3)
    lines = [
        f"turns: {len(served)} attempted, {len(ok)} served, "
        f"rate {rate} turns/s over {seconds} s; tokens served {tokens} "
        f"(counters: {int(sum(delta['serve_tokens_total'].values()))}), "
        f"{in_window} of them done by {seconds} s; the window closed at "
        f"{closed:.3f} s",
        f"generator lateness after a wait: median "
        f"{percentile(late, 50) * 1e3 if late else 0.0:.3f} ms, max "
        f"{max(late) * 1e3 if late else 0.0:.3f} ms over {len(late)} waits",
        f"ttft by third of the window (ms, median): "
        f"{percentile(ttft[:third], 50):.1f} / "
        f"{percentile(ttft[third:2 * third], 50):.1f} / "
        f"{percentile(ttft[2 * third:], 50):.1f}; last turn done "
        f"{max(s.done_at for s in served) - seconds:.3f} s after the "
        f"window closed",
        drain_lines(drains, eset["batch_buckets"]),
        f"garbage collections in the window: {gcp.count}, "
        f"{gcp.seconds * 1e3:.1f} ms in all, longest "
        f"{gcp.longest * 1e3:.1f} ms",
        f"compilations in the window: {window_compiles} "
        f"(new fused-step shapes {sorted(new_shapes)})",
        f"set-up: setup_s {setup_s:.3f}; imports, JAX start and plan "
        f"{t_build - t_process:.1f} s, params and engine "
        f"{t_built - t_build:.1f} s, warm-up drains "
        f"{parts['warmup_s']:.1f} s, history {parts['history_s']:.1f} s "
        f"in {parts['history_drains']} drains; compiled {c0} programs "
        f"in {compiles.compile_s:.1f} s, {h0} persistent-cache hits in "
        f"{cache_dir() if cache else 'the process cache'}",
    ]
    result = {"correct": False, "attempted": len(served),
              "failed": len(served) - len(ok)}
    if trace:
        ctx = Context(m, cell.config, peaks, seconds, delta, summary,
                      turn_flops(m, plan), waits, len(devices))
        result["metrics"] = per_layer_metrics(cell, ctx)
    else:
        result["metrics"] = {k: v for k, v in metrics_e2e.items()
                             if k in cell_e2e(cell)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(mem or 0)}
    if trace and summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = breakdown(summary)
    result["device"] = device

    # the reference, once the program is gone
    del eng
    gc.collect()
    e = cell.config["engine"]
    gaps = check.reference_gaps(
        ref, m, seed, plan, picks, kept, e["cache_len"],
        cell.mix["context_tokens"]["max"], cell.mix["query_tokens"]["max"])
    limit = float(cell.config["check"]["greedy_gap_limit"])
    widest = max((float(g.max()) for g in gaps.values()), default=
                 float("inf"))
    n_pos = sum(g.size for g in gaps.values())
    all_served = len(ok) == len(served) and set(gaps) == set(picks)
    result["correct"] = bool(all_served and widest <= limit
                             and not window_compiles)
    lines.append(f"check: {len(picks)} sampled turns, {n_pos} served "
                 f"tokens compared")
    result["check"] = {
        "greedy_gap": {"value": widest, "limit": limit},
        "unserved_turns": {"value": len(served) - len(ok), "limit": 0},
        "window_compiles": {"value": window_compiles, "limit": 0}}
    result["_lines"] = lines
    result["_stats"] = {
        "served_tokens": sum(s.turn.tokens for s in ok),
        "window_tokens": in_window, "closed_s": closed,
        "counter_tokens": sum(delta["serve_tokens_total"].values()),
        "counter_requests": sum(delta["serve_requests_total"].values()),
        "served": len(ok), "ttft_ms": ttft}
    return result


def cell_e2e(cell: Cell) -> List[str]:
    return [e["name"] for e in cell.bench["end_to_end"]
            if cell.name in e.get("workloads", [cell.name])]
