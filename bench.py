"""Thin CLI shim over the bench suite (ethrex_tpu/perf/bench_suite.py).

All measurement logic, the no-chip refusal, the append-only
bench_history.jsonl, and the --check-regression gate live in the
package module; this file stays at the repo root so
`python bench.py [--measure|--measure-N|--check-regression]` and the
suite's own child-process re-invocations keep their historical entry
point.  Everything public is re-exported so `import bench` users (tests,
CI scripts) see the same API as before the move.
"""

from __future__ import annotations

from ethrex_tpu.perf.bench_suite import *  # noqa: F401,F403
from ethrex_tpu.perf.bench_suite import cli as _cli

if __name__ == "__main__":
    _cli()
