"""Knee sweep of a cell: one set-up, then open-loop windows at several
offered rates on the same engine.

    python3 bench/sweep.py --workload <cell> --window <s> \
        --points <rate>:<seed>,<rate>:<seed>,...

Each window's arrival times, lengths and tokens come from the mix's
generator at that rate and seed; its turns go to sessions drawn again
among those with room, so that the windows can follow one another on one
engine.  Later windows meet deeper memories than a fresh run would.
Prints one JSON line per window, then its drains by batch bucket.  The
benchmark's own runs never import this file.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def sweep(cell, points, window: float, require_tpu: bool = True):
    """Yields (window summary, drains line) for each (rate, seed)."""
    import numpy as np
    from bench import harness, traffic
    if require_tpu:
        harness.check_device(int(cell.spec["chips"]))
        harness.configure_cache()
    ref, _ = harness.model_modules(cell.config)
    m = ref.dims(cell.config)
    eset = harness.engine_settings(cell.config)
    plan = traffic.make_plan(cell.mix, eset, points[0][0], window,
                             points[0][1], m.vocab)
    eng, _ = harness.build_engine(cell, points[0][1], traced=False)
    parts = harness.set_up(eng, plan, max(eset["batch_buckets"]),
                           eset["token_buckets"])
    yield {"setup_s": time.perf_counter() - T_PROCESS, **parts}, ""
    mem = {s: 0 for s in plan.sessions}
    cached = dict(mem)
    for s, evs in plan.history.items():
        for op, t in evs:
            if op == "ingest":
                mem[s] += 1
            else:
                cached[s] += t.size
    last = {s: -np.inf for s in plan.sessions}
    offset = 0.0
    for rate, seed in points:
        p = traffic.make_plan(cell.mix, eset, rate, window, seed, m.vocab)
        rng = np.random.default_rng([int(seed) % (1 << 63), 11])
        turns = []
        for t in p.turns:
            ok = [s for s in plan.sessions if mem[s] < eset["max_steps"]
                  and cached[s] + t.query.size <= eset["cache_len"]
                  and offset + t.due - last[s] >= cell.mix["think_s"]]
            if not ok:
                raise ValueError(f"no session has room for a turn at "
                                 f"{rate}/s: sweep fewer or shorter windows")
            s = ok[int(rng.integers(len(ok)))]
            mem[s] += 1
            cached[s] += t.query.size
            last[s] = offset + t.due
            turns.append(traffic.Turn(len(turns), t.due, s, t.chunk,
                                      t.query))
        served, _, _, t0, drains = harness.serve_window(
            eng, types.SimpleNamespace(turns=turns), window, set())
        offset += time.perf_counter() - t0
        ttft = [(x.done_at - x.turn.due) * 1e3 for x in served]
        tokens, closed = harness.throughput(served, window)
        third = max(1, len(ttft) // 3)
        yield {"rate": rate, "seed": seed, "turns": len(turns),
               "ttft_p50_ms": harness.percentile(ttft, 50),
               "ttft_p95_ms": harness.percentile(ttft, 95),
               "ttft_p50_ms_by_third": [
                   harness.percentile(v, 50) for v in
                   (ttft[:third], ttft[third:2 * third], ttft[2 * third:])],
               "last_done_after_close_s":
                   max(x.done_at for x in served) - window,
               "tokens_per_s": tokens / closed,
               "offered_tokens_per_s": tokens / window,
               }, harness.drain_lines(drains, eset["batch_buckets"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--window", type=float, required=True)
    ap.add_argument("--points", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    points = [(float(r), int(s)) for r, s in
              (p.split(":") for p in args.points.split(","))]
    try:
        for summary, drains in sweep(harness.load_cell(args.workload),
                                     points, args.window):
            print(json.dumps(summary), flush=True)
            if drains:
                print("    " + drains, flush=True)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
