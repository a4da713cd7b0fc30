"""Several seeds of one cell in ONE process, with one cold start: what
the limits of `correct` are read from, and where the controls run.

    python benchmark/sweep.py --workload <name> --seeds 1,2,3 --seconds <s> \\
        [--controls N] [--traced N] [--out <file.jsonl>]

Every seed is a fresh deployment (a new genesis, a new stack or
coordinator) driven through `harness.run_cell`, so each line is what
`run.py` would print for that seed, except that `setup_s` leaves out the
process's start.  With --controls N the first N seeds also judge every
control of controls.py on a copy of their records; a control that comes
out correct fails the sweep.  Compiled programs stay in the process, so
only the first seed compiles or hydrates.  Not the benchmark's command:
BENCHMARK.json names run.py.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import bootstrap  # noqa: E402,F401 — before anything imports JAX

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--traced", type=int, default=0,
                        help="the last N seeds run as --trace 1 runs")
    parser.add_argument("--controls", type=int, default=0)
    parser.add_argument("--out")
    args = parser.parse_args(argv)
    import harness
    from common import BenchFailure, err
    from controls import CONTROLS

    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, args.workload)[0]
    try:
        device = harness.find_chip(int(cell["chips"]))
    except BenchFailure as exc:
        err(f"sweep failed: {exc}")
        return 3
    bad = 0
    seeds = [int(s) for s in args.seeds.split(",")]
    for i, seed in enumerate(seeds):
        result = harness.run_cell(
            args.workload, seed, args.seconds,
            i >= len(seeds) - args.traced,
            time.monotonic(), device=dict(device),
            controls=CONTROLS if i < args.controls else None)
        result["seed"] = seed
        line = json.dumps(result)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
        if not result["correct"]:
            bad += 1
        for name, got in result.get("controls", {}).items():
            if got["correct"]:
                err(f"control {name} came out correct on seed {seed}")
                bad += 1
    return 1 if bad else 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
