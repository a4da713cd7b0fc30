"""A temporary copy of the benchmark's data directories with one more
configuration, traffic kind, traffic mix and per-layer metric dropped in — the way a
later PR adds a cell: files and entries, no edit to a file that is
there.  The `exec` prover stands in for the chip's; nothing these tests
read is ever written under a device metric's name."""

import json
import os
import shutil

import harness

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


POOLED_KIND = '''"""Transfers to a small pool of recipients drawn once from the seed:
another kind of traffic, as one file."""
import traffic

_base = traffic.load_kind("eth_transfers")
REQUIRED = _base.REQUIRED + ("recipient_pool",)
expected_states = _base.expected_states
count_state_mismatches = _base.count_state_mismatches


class Traffic(_base.Traffic):
    def _draw_batch(self):
        if not hasattr(self, "_pool"):
            self._pool = [self._rng.randbytes(20) for _ in
                          range(int(self.mix["recipient_pool"]))]
        blocks = super()._draw_batch()
        return [[t.__class__(**{**t.__dict__,
                                "to": self._rng.choice(self._pool)})
                 for t in block] for block in blocks]
'''


def drop_in(tmp_path, deployment: str = "prover_fleet"):
    bench_dir = str(tmp_path / "benchmark")
    for sub in ("configs", "traffic", "traffic_kinds", "metrics"):
        shutil.copytree(os.path.join(harness.BENCH_DIR, sub),
                        os.path.join(bench_dir, sub))
    before = {os.path.join(r, f): open(os.path.join(r, f), "rb").read()
              for r, _, fs in os.walk(bench_dir) for f in fs}
    config = {
        "name": "exec-standin", "deployment": deployment,
        "window_metric": "batch_prove_s",
        "prover": "exec", "proof_format": "stark", "chips": 1,
        "first_block_timestamp": 1750000000, "block_time_s": 2,
        "guarantees": {"backend": "exec", "verify": True}}
    with open(os.path.join(bench_dir, "configs", "exec-standin.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic_kinds",
                           "pooled_transfers.py"), "w") as f:
        f.write(POOLED_KIND)
    mix = {"kind": "pooled_transfers", "recipient_pool": 4,
           "transfers_per_block": 3,
           "blocks_per_batch": 2,
           "value_wei": {"min": 1, "max": 10**12},
           "max_priority_fee_per_gas": 2, "max_fee_per_gas": 10**10,
           "sender_balance_wei": 10**21,
           "arrival": {"mode": "backlog", "batches_committed_ahead": 6},
           "warmup_batches": 1, "trace_seconds": 2}
    with open(os.path.join(bench_dir, "traffic", "transfer3x2.json"),
              "w") as f:
        json.dump(mix, f)
    metric = {"name": "assign_s", "layer": "coordination",
              "unit": "s/batch", "moves": config["window_metric"],
              "workloads": ["standin"],
              "source": {"kind": "span_sum", "spans": ["prover.assign"],
                         "per": "batch"}}
    with open(os.path.join(bench_dir, "metrics", "assign_s.json"),
              "w") as f:
        json.dump(metric, f)
    with open(os.path.join(bench_dir, "metrics", "proved_batches.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx['counters1']['client.proved'] "
                "- ctx['counters0']['client.proved']\n")
    bench = harness.load_benchmark()
    bench["configs"].append({
        "name": "exec-standin", "source": "test",
        "file": "benchmark/configs/exec-standin.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "standin", "config": "exec-standin",
        "traffic": "transfer3x2", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] == config["window_metric"]:
            m["workloads"].append("standin")
            break
    else:       # the cell brings its end-to-end metric, as an entry
        bench["end_to_end"].append({
            "name": config["window_metric"], "unit": "s/batch",
            "better": "lower", "bound": 0.25, "source": "host_clock",
            "workloads": ["standin"]})
    bench["per_layer"].append({
        "name": "assign_s", "unit": "s/batch", "better": "lower",
        "source": "program_span", "layer": "coordination",
        "moves": config["window_metric"], "workloads": ["standin"]})
    bench["per_layer"].append({
        "name": "proved_batches", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "coordination",
        "moves": config["window_metric"], "workloads": ["standin"]})
    bench_path = str(tmp_path / "BENCHMARK.json")
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    after = {p: open(p, "rb").read() for p in before}
    assert after == before, "a file that was there was edited"
    return bench_path, bench_dir


def run(tmp_path, deployment: str = "prover_fleet",
        seconds: float = 1.5, trace=False,
        seed: int = 2**31 + 99, controls=None):
    import time

    bench_path, bench_dir = drop_in(tmp_path, deployment)
    return harness.run_cell("standin", seed, seconds, trace,
                            time.monotonic(), bench_path=bench_path,
                            bench_dir=bench_dir, prover="exec",
                            device=dict(CPU), controls=controls)
