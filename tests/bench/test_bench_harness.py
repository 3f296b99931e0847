"""The harness driven through its own functions on the CPU, at each
configuration's ``smoke`` size: the chat serve loop, the counts, the
per-layer readers, and the command line's refusal without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, traffic

ROOT = harness.ROOT
CELLS = ["qwen2-0.5b.chat", "codeqwen1.5-7b-8l.chat"]
CONFIGS = ["qwen2-0.5b", "codeqwen1.5-7b-8l"]
SEED = 2 ** 31 + 12345          # wider than a signed 32-bit int


def smoke_cell(name, root=ROOT):
    """The cell at its configuration's smoke size (float32 compute)."""
    cell = harness.load_cell(name, root)
    cfg = dict(cell.config)
    smoke = cfg.pop("smoke")
    cfg.update({k: v for k, v in smoke.items() if k != "why"})
    cell.config = cfg
    return cell


def smoke_run(name, seed=SEED, trace=False):
    return harness.run_cell(smoke_cell(name), seed, 1.5,
                            trace, time.perf_counter(), require_tpu=False,
                            rate=4.0, cache=False)


@pytest.fixture(scope="module", params=CONFIGS)
def run(request):
    """The chat cell of each configuration file."""
    return request.param, smoke_run(request.param + ".chat")


def test_rehearsal_counts_add_up(run):
    name, res = run
    st = res["_stats"]
    assert res["correct"], res["check"]
    assert res["attempted"] == st["served"] + res["failed"]
    assert res["failed"] == 0
    # every served turn is one ingest and one query, and the counters saw
    # exactly the tokens the generator sent
    assert st["counter_requests"] == 2 * st["served"]
    assert st["counter_tokens"] == st["served_tokens"]
    assert st["window_tokens"] <= st["served_tokens"]
    m = res["metrics"]
    assert set(m) == {"setup_s", "ttft_p50_ms", "ttft_p95_ms",
                      "tokens_per_s"}
    # every served turn's tokens, over the window up to the last turn's
    # delivery after arrivals stopped
    assert st["closed_s"] >= 1.5
    assert m["tokens_per_s"]["value"] == pytest.approx(
        st["served_tokens"] / st["closed_s"])
    assert m["ttft_p50_ms"]["value"] > 0
    assert m["ttft_p50_ms"]["value"] == pytest.approx(
        float(np.percentile(st["ttft_ms"], 50)))
    assert m["ttft_p95_ms"]["value"] == pytest.approx(
        float(np.percentile(st["ttft_ms"], 95)))
    assert res["check"]["window_compiles"]["value"] == 0
    assert list(res)[-3:] == ["device", "check", "_lines"] or \
        list(res)[-4:-2] == ["device", "check"]


def test_same_seed_same_inputs():
    cell = smoke_cell(CELLS[0])
    eset = harness.engine_settings(cell.config)
    a, b, c = (traffic.make_plan(cell.mix, eset, 4.0, 3.0, s, 512)
               for s in (SEED, SEED, SEED + 1))
    assert [(t.due, t.sid, t.chunk.tolist(), t.query.tolist())
            for t in a.turns] == [(t.due, t.sid, t.chunk.tolist(),
                                   t.query.tolist()) for t in b.turns]
    # another seed: the same arrival times and the same multiset of
    # lengths, in another order and with other tokens
    assert [t.due for t in a.turns] == [t.due for t in c.turns]
    assert sorted(t.chunk.size for t in a.turns) == \
        sorted(t.chunk.size for t in c.turns)
    assert [t.chunk.tolist() for t in a.turns] != \
        [t.chunk.tolist() for t in c.turns]


def test_traced_run_reads_per_layer_metrics():
    res = smoke_run(CELLS[0], seed=77, trace=True)
    assert res["correct"]
    m = res["metrics"]
    # the CPU trace has no device plane: the device readers find nothing
    # and leave their metrics out; the host and counter readers report
    assert set(m) == {"queue_wait_p95_ms", "pad_token_share"}
    assert 0 < m["pad_token_share"]["value"] < 100
    assert m["queue_wait_p95_ms"]["value"] > 0


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "3", "--seconds", "1"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_cli_refuses_without_tpu():
    p = _cli(ROOT)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
    assert "not a TPU" in p.stderr


def test_cli_fails_with_the_benchmark_files_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path, {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert p.stdout == ""


def test_new_mix_and_metric_are_files_alone(tmp_path):
    """A later change adds a mix, a cell and a per-layer metric by adding
    files and BENCHMARK.json entries; no file of the harness changes."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    b = tmp_path / "bench"
    with open(b / "traffic" / "chat.json") as f:
        mix = json.load(f)
    mix.update(name="chat_short",
               context_tokens=dict(mix["context_tokens"], median=16,
                                   max=64))
    (b / "traffic" / "chat_short.json").write_text(json.dumps(mix))
    (b / "cells" / "qwen2-0.5b.chat_short.json").write_text(
        json.dumps({"rate_turns_per_s": 2.0}))
    (b / "metrics" / "lanes_per_step.py").write_text(
        "def read(ctx):\n"
        "    steps = sum(ctx.counters['serve_batches_total'].values())\n"
        "    lanes = sum(ctx.counters['serve_lanes_total'].values())\n"
        "    return lanes / steps if steps else None\n")
    with open(tmp_path / "BENCHMARK.json") as f:
        bench = json.load(f)
    bench["workloads"].append({"name": "qwen2-0.5b.chat_short",
                               "config": "qwen2-0.5b",
                               "traffic": "chat_short", "chips": 1,
                               "why": "short chunks"})
    bench["per_layer"].append({"name": "lanes_per_step", "unit": "lanes",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "scheduler", "moves": "ttft_p95_ms",
                               "workloads": ["qwen2-0.5b.chat_short"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = smoke_cell("qwen2-0.5b.chat_short", root=str(tmp_path))
    assert cell.rate == 2.0 and cell.mix["name"] == "chat_short"
    eset = harness.engine_settings(cell.config)
    plan = traffic.make_plan(cell.mix, eset, cell.rate, 3.0, 1, 512)
    assert plan.turns and max(t.chunk.size for t in plan.turns) <= 64
    zero = {k: 0.0 for k in ("ingest", "query")}
    ctx = harness.Context(
        None, cell.config, {}, 3.0,
        {"serve_batches_total": dict(zero, ingest=2.0, query=2.0),
         "serve_lanes_total": dict(zero, ingest=8.0, query=4.0),
         "serve_tokens_total": dict(zero, ingest=90.0, query=10.0),
         "serve_pad_tokens_total": dict(zero, ingest=10.0)},
        None, [], [], 1)
    got = harness.per_layer_metrics(cell, ctx)
    assert got["lanes_per_step"] == {"value": 3.0, "unit": "lanes"}
    # a metric whose ``workloads`` leave the new cell out is not read there
    assert "pad_token_share" not in got


def test_cache_dir_follows_the_variable(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert harness.cache_dir() == str(tmp_path)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert harness.cache_dir() == os.path.join(ROOT, ".jax_cache")


def test_drain_lines_group_by_bucket():
    line = harness.drain_lines([(3, 0.5), (4, 0.7), (17, 2.0), (9, 0.6)],
                               [4, 16, 64])
    assert line == ("drains: 4; <=4 turns: 2, median 0.600 s, longest "
                    "0.700 s; <=16 turns: 1, median 0.600 s, longest "
                    "0.600 s; <=64 turns: 1, median 2.000 s, longest "
                    "2.000 s")


def test_sweep_serves_each_point_on_one_engine():
    from bench import sweep
    out = list(sweep.sweep(smoke_cell(CELLS[0]), [(2.0, 5), (4.0, 6)], 1.5,
                           require_tpu=False))
    assert out[0][0]["history_drains"] > 0
    rates = [s["rate"] for s, _ in out[1:]]
    assert rates == [2.0, 4.0]
    for s, drains in out[1:]:
        assert s["turns"] > 0 and s["ttft_p95_ms"] >= s["ttft_p50_ms"] > 0
        assert drains.startswith("drains: ")
