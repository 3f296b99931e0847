"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Prints informative lines, then the numbers compared for ``correct``
beside their limits (the last lines on standard error), then one JSON
object as the last line of standard output.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the TPU runtime would log under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness
    try:
        cell = harness.load_cell(args.workload)
        result = harness.run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    result.pop("_stats")
    for line in result.pop("_lines"):
        print(line, flush=True)
    for name, c in result["check"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
