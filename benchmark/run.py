"""One cell, once:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the chip from its first `import jax` to its
exit; no child touches JAX.  Without a TPU (or with fewer chips than the
cell asks for) the command fails: exit code 3, a reason on standard
error, and no result line — nothing ever runs on the CPU in its place.
The last line of standard output is the result object; everything else
(versions, the set-up split, cache hits and misses, per-batch spans) is
on earlier lines.  With --trace 0 the metrics are the cell's end-to-end
metrics; with --trace 1 the window is the traffic mix's `trace_seconds`,
the profiler records all of it, and the metrics are the cell's
per-layer metrics.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

import bootstrap  # noqa: E402,F401 — before anything imports JAX

def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    from common import BenchFailure, err

    try:
        import ethrex_tpu  # noqa: F401 — the system under test
        import harness

        result = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), _T_PROCESS)
    except BenchFailure as exc:
        err(f"benchmark failed: {exc}")
        return 3
    except BaseException as exc:  # noqa: BLE001 — no result line, ever
        if isinstance(exc, KeyboardInterrupt):
            raise
        traceback.print_exc()
        err(f"benchmark failed: {type(exc).__name__}: {exc}")
        return 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the stack and the runtime's own teardown must
    # not hold the exit: every process the run started has been joined
    os._exit(code)
