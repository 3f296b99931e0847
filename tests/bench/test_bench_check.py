"""The comparison that decides ``correct``: a run whose timed path is
broken underneath must come out not correct, and so must the control
(the reference computed on float8 operands in the program's place).
Driven through the harness on the CPU at the smoke size, which skips
only the harness's look for a chip."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import check, faults, harness, traffic
from repro.launch import serve as SRV
from test_bench_harness import SEED, smoke_cell

CELL = "qwen2-0.5b.chat"


def _broken_run(monkeypatch, fault):
    monkeypatch.setattr(SRV, "make_arena_step",
                        faults.broken_factory(SRV.make_arena_step, fault))
    return harness.run_cell(smoke_cell(CELL), SEED, 1.5, False,
                            time.perf_counter(), require_tpu=False,
                            rate=4.0, cache=False)


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_broken_timed_path_is_not_correct(monkeypatch, fault):
    res = _broken_run(monkeypatch, fault)
    gap = res["check"]["greedy_gap"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"]
    assert res["failed"] == 0          # every turn was served, wrongly


def test_control_fails_the_check():
    """The reference on float8 operands, put in the program's place at
    the smoke size, reads a gap over the limit."""
    cell = smoke_cell(CELL)
    ref, _ = harness.model_modules(cell.config)
    m = ref.dims(cell.config)
    eset = harness.engine_settings(cell.config)
    # more turns than a smoke run samples: at this size a float8 logit
    # moves the greedy token on a few positions in a hundred
    plan = traffic.make_plan(cell.mix, eset, 8.0, 4.0, SEED, m.vocab)
    picks = check.sample_turns(plan, 32, SEED)
    gaps = check.reference_gaps(
        ref, m, SEED, plan, picks, {}, eset["cache_len"],
        cell.mix["context_tokens"]["max"], cell.mix["query_tokens"]["max"],
        control=True)
    widest = max(float(g.max()) for g in gaps.values())
    assert set(gaps) == set(picks)
    assert widest > 10 * cell.config["check"]["greedy_gap_limit"]
    assert np.isfinite(widest)


def test_faults_cli_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/faults.py", "--workload", CELL, "--seed",
         "3", "--seconds", "1", "--control"], cwd=harness.ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 2, p.stderr
    assert p.stdout == ""
