"""Traffic, found by name.  A traffic mix is a data file of parameters,
`benchmark/traffic/<name>.json`; its `kind` names the generator that
reads it, `benchmark/traffic_kinds/<kind>.py`, which the harness finds
as it finds a deployment.  A cell with new lengths, rates or arrivals of
a kind that is here is a new mix: data only.  A new kind of traffic
(other transactions, another reference) is one new file under
`traffic_kinds/`; nothing that is here needs an edit.

A kind's file gives
  REQUIRED                      the keys a mix of this kind must have
  Traffic(mix, seed)            everything one run sends, from the seed
                                alone: `.mix`, `.seed`, `.genesis()`,
                                `.batch(index)` (blocks of transactions),
                                `.signed(tx)` (the bytes sent)
  expected_states(traffic, upto_index)
                                the kind's plain reference: the state
                                each batch 0..upto_index must leave
  count_state_mismatches(expected, write_log)
                                how far a proof's claimed write log is
                                from one of those states; check.py holds
                                every proof of the window to it

Fields every mix has, whatever its kind:
  kind                  the generator's name
  blocks_per_batch      blocks the committer puts in one batch
  arrival               how batches reach the system, read by the
                        deployment that drives it:
    mode "backlog":     `batches_committed_ahead` batches are committed
                        in set-up, the window only releases the client
  warmup_batches        batches driven through the same path before the
                        window (default 1)
  trace_seconds         with --trace 1 the window is cut to this length
                        (traces are large) and the profiler records all
                        of it (default: --seconds)
"""

from __future__ import annotations

import importlib.util
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_kind(name: str, bench_dir: str = _HERE):
    path = os.path.join(bench_dir, "traffic_kinds", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(_HERE, "traffic_kinds", name + ".py")
    if not os.path.exists(path):
        raise ValueError(f"unknown traffic kind {name!r}: no "
                         f"traffic_kinds/{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_traffic_kind_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_mix(path: str, bench_dir: str = _HERE):
    """(mix, its kind's module) of the mix file at `path`."""
    with open(path) as f:
        mix = json.load(f)
    if "kind" not in mix:
        raise ValueError(f"{path}: traffic mix lacks 'kind'")
    kind = load_kind(mix["kind"], bench_dir)
    for key in ("blocks_per_batch", "arrival", *kind.REQUIRED):
        if key not in mix:
            raise ValueError(f"{path}: traffic mix lacks {key!r}")
    return mix, kind
