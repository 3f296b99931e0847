"""Planted faults and the float8 control: each must make ``correct`` come
out false.  The benchmark's own runs never import this file.

    python3 bench/faults.py --workload <cell> --seed <n> --seconds <s> \
        --fault <name>
    python3 bench/faults.py --workload <cell> --seed <n> --seconds <s> \
        --control

``--fault`` runs the cell as ``bench/run.py`` does, with the program's
fused arena step broken underneath (one of ``FAULTS``).  ``--control``
makes the run's plan and sample from the seed and puts the reference,
computed on float8 operands, in the program's place; it needs no program
and no window, only the seconds that size the plan.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

FAULTS = ("state unchanged", "half the batch left out", "answer altered")


def broken_factory(real, fault: str):
    """Wraps ``repro.launch.serve.make_arena_step`` so that its steps carry
    ``fault``: an ingest that returns the slabs unchanged; the second half
    of every batch's lanes sent to the scratch row instead of their
    sessions' rows; or every query's logits rolled by one token id."""
    import jax
    import jax.numpy as jnp
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")

    def factory(cfg, op, ragged=False):
        step = real(cfg, op, ragged)
        if fault == "state unchanged" and op == "ingest":
            return lambda params, slabs, ids, toks, lens: (None, slabs)
        if fault == "half the batch left out":
            def half(params, slabs, ids, toks, lens):
                scratch = jax.tree.leaves(slabs)[0].shape[0] - 1
                b = ids.shape[0]
                ids = jnp.where(jnp.arange(b) < b // 2, ids, scratch)
                return step(params, slabs, ids, toks, lens)
            return half
        if fault == "answer altered" and op == "query":
            def altered(params, slabs, ids, toks, lens):
                out, slabs = step(params, slabs, ids, toks, lens)
                return jnp.roll(out, 1, axis=-1), slabs
            return altered
        return step
    return factory


def control_gap(cell, seed: int, seconds: float) -> dict:
    """The float8 reference in the program's place, on the turns a run
    of this seed and length samples."""
    from bench import check, harness, traffic
    ref, _ = harness.model_modules(cell.config)
    m = ref.dims(cell.config)
    eset = harness.engine_settings(cell.config)
    plan = traffic.make_plan(cell.mix, eset, cell.rate, seconds, seed,
                             m.vocab)
    picks = check.sample_turns(plan, cell.config["check"]["sample_turns"],
                               seed)
    gaps = check.reference_gaps(
        ref, m, seed, plan, picks, {}, eset["cache_len"],
        cell.mix["context_tokens"]["max"], cell.mix["query_tokens"]["max"],
        control=True)
    widest = max(float(g.max()) for g in gaps.values())
    limit = float(cell.config["check"]["greedy_gap_limit"])
    return {"correct": widest <= limit,
            "check": {"greedy_gap": {"value": widest, "limit": limit}},
            "positions": int(sum(g.size for g in gaps.values()))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", choices=FAULTS)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    if bool(args.fault) == args.control:
        ap.error("give one of --fault and --control")
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    cell = harness.load_cell(args.workload)
    try:
        harness.check_device(int(cell.spec["chips"]))
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    if args.control:
        harness.configure_cache()
        out = control_gap(cell, args.seed, args.seconds)
        out["control"] = "float8 reference"
    else:
        from repro.launch import serve as SRV
        SRV.make_arena_step = broken_factory(SRV.make_arena_step,
                                             args.fault)
        out = harness.run_cell(cell, args.seed, args.seconds, False,
                               T_PROCESS)
        for line in out.pop("_lines"):
            print(line, flush=True)
        out = {"fault": args.fault, "correct": out["correct"],
               "check": out["check"], "attempted": out["attempted"],
               "failed": out["failed"]}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
