"""The cell `prove-erc20` came as files and entries: its configuration,
mix, kind and five per-layer readers are found by name; a small mix of
the kind runs through the harness with the `exec` prover standing in
(its proofs given the executor's write log, so that the kind's plain
reference judges every batch of the window); and the readers say
nothing where the program gave them nothing to read."""

import json
import os
import shutil
import time

import pytest

import harness
import helpers
import metrics_lib as ml

CELL = "prove-erc20"
NEW = ("token_stark_s", "token_trace_gen_s", "vm_batch_s", "token_calls",
       "token_commit_hbm_roofline")
METRICS_DIR = os.path.join(harness.BENCH_DIR, "metrics")


def test_the_cells_files_are_found_by_name():
    bench = harness.load_benchmark()
    cell, config, mix, kind = harness.load_cell(bench, CELL)
    assert (cell["chips"], config["name"], config["deployment"]) == \
        (1, "baseline2-prover", "prover_fleet")
    assert mix["kind"] == "erc20_transfers"
    assert kind.__file__.endswith("traffic_kinds/erc20_transfers.py")
    (entry,) = [c for c in bench["configs"] if c["name"] == config["name"]]
    assert entry["source"] == config["source"] and entry["reduced"]
    t = kind.Traffic(mix, 2**31 + 5)
    assert [len(b) for b in t.batch(0)] == [mix["calls_per_block"]]
    assert len(t.holders) == mix["senders"] < mix["calls_per_block"]
    # it reports the window metric, set-up, and its own five readers
    e2e = {m["name"] for m in harness.metrics_of(bench, "end_to_end", CELL)}
    assert e2e == {"batch_prove_s", "setup_s"}
    assert tuple(m["name"] for m in harness.metrics_of(
        bench, "per_layer", CELL, e2e)) == NEW


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_is_found(name):
    bench = harness.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    decl, read = ml.load_metric(METRICS_DIR, name)
    assert callable(read)
    assert (decl["layer"], decl["unit"], decl["moves"], decl["workloads"]) \
        == (entry["layer"], entry["unit"], "batch_prove_s", [CELL])
    # nothing to read (a program without the spans, no trace): left out
    silent = {"spans": [{"name": "bench.batch", "start": 1.0,
                         "seconds": 2.0, "traceId": None}],
              "batches": 1, "table": None, "peaks": None}
    assert read(silent) is None


def test_the_span_readers_by_hand():
    def span(name, seconds, **attrs):
        return {"name": name, "start": 10.0, "seconds": seconds,
                "traceId": "t", "attrs": attrs}

    ctx = {"batches": 2, "table": None, "peaks": None, "spans": [
        span("prove.vm_batch", 3.0, mode="token", txs=30, tok_calls=30,
             acct_rows=61, slot_rows=60),
        span("prove.vm_batch", 1.0, mode="transfer", txs=10, tok_calls=0,
             acct_rows=30, slot_rows=0),
        span("prove.trace_gen", 4.0, air="TransferAir"),
        span("prove.trace_gen", 1.5, air="TokenAir"),
        span("prove.vm_circuits/TokenAir", 5.0),
        span("prove.vm_circuits/TransferAir", 9.0)]}
    got = {name: ml.load_metric(METRICS_DIR, name)[1](ctx) for name in NEW}
    assert got == {"token_stark_s": 2.5, "token_trace_gen_s": 0.75,
                   "vm_batch_s": 2.0, "token_calls": 15.0,
                   "token_commit_hbm_roofline": None}
    # a program whose span has no such attribute: left out, not 0
    for s in ctx["spans"]:
        s["attrs"].pop("tok_calls", None)
    assert ml.load_metric(METRICS_DIR, "token_calls")[1](ctx) is None


def _drop_in(tmp_path):
    """The benchmark's data directories as they are, and beside them a
    stand-in cell: an `exec` configuration and a small mix of the kind,
    with the five readers listed for it."""
    bench_dir = str(tmp_path / "benchmark")
    for sub in ("configs", "traffic", "traffic_kinds", "metrics"):
        shutil.copytree(os.path.join(harness.BENCH_DIR, sub),
                        os.path.join(bench_dir, sub))
    config = {
        "name": "exec-erc20", "deployment": "prover_fleet",
        "window_metric": "batch_prove_s", "prover": "exec",
        "proof_format": "stark", "chips": 1,
        "first_block_timestamp": 1750000000, "block_time_s": 2,
        "guarantees": {"backend": "exec", "verify": True,
                       "reference_state": True}}
    with open(os.path.join(bench_dir, "configs", "exec-erc20.json"),
              "w") as f:
        json.dump(config, f)
    with open(os.path.join(bench_dir, "traffic", "erc20-backlog.json")) as f:
        mix = json.load(f)
    mix.update(calls_per_block=6, senders=2, fresh_recipients_per_block=4,
               blocks_per_batch=2, trace_seconds=2,
               arrival={"mode": "backlog", "batches_committed_ahead": 6})
    with open(os.path.join(bench_dir, "traffic", "erc20-small.json"),
              "w") as f:
        json.dump(mix, f)
    bench = harness.load_benchmark()
    bench["workloads"].append({
        "name": "erc20-standin", "config": "exec-erc20",
        "traffic": "erc20-small", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("erc20-standin")
    bench_path = str(tmp_path / "BENCHMARK.json")
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    return bench_path, bench_dir


def test_a_small_mix_of_the_kind_runs_through_the_harness(tmp_path,
                                                          monkeypatch):
    from ethrex_tpu.guest import access_log
    from ethrex_tpu.guest.execution import execution_program
    from ethrex_tpu.prover.backend import ExecBackend

    sound = ExecBackend.prove
    forged = []

    def with_write_log(self, program_input, proof_format):
        proof = sound(self, program_input, proof_format)
        log: list = []
        execution_program(program_input, write_log=log)
        proof["write_log"] = access_log.raw_log_to_json(log)
        if forged:      # one token unit more on the last slot written
            row = [r for r in proof["write_log"][-1] if r[0] == "s"][-1]
            row[4] = "%064x" % (int(row[4], 16) + 1)
        return proof

    monkeypatch.setattr(ExecBackend, "prove", with_write_log)
    bench_path, bench_dir = _drop_in(tmp_path)

    def run(trace):
        return harness.run_cell(
            "erc20-standin", 2**31 + 99, 1.5, trace, time.monotonic(),
            bench_path=bench_path, bench_dir=bench_dir, prover="exec",
            device=dict(helpers.CPU))

    r = run(trace=True)
    assert r["correct"], r["compared"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["compared"]["state_mismatches"] == [0, 0]
    # the exec prover has no token circuit: the five readers stay silent
    assert not set(NEW) & set(r["metrics"])
    # and the kind's reference is what judged: a forged slot is counted
    forged.append(True)
    r = run(trace=False)
    assert not r["correct"]
    assert r["compared"]["state_mismatches"][0] == r["attempted"] >= 1
    assert r["metrics"]["batch_prove_s"]["value"] > 0


def test_a_program_with_a_quadratic_interpolation_is_refused(monkeypatch):
    """The parent of PR 29 built a (p, p) table for each periodic
    column; at the cell's 2^15-row state circuit that is 8.6 GB a table,
    several at once, beside four compiles: the machine ends the run.
    The kind's pre-flight turns that into a clean failure before
    anything is sent; the program as it is passes."""
    import numpy as np

    from common import BenchFailure
    from ethrex_tpu.ops import babybear as bb
    from ethrex_tpu.ops import ntt

    _, _, mix, kind = harness.load_cell(harness.load_benchmark(), CELL)
    assert "alloc" in kind.Traffic(mix, 3).genesis()

    def by_table(values):
        p = len(values)
        idx = np.arange(p, dtype=np.int64)
        table = bb.powers_host(3, p).astype(np.uint64)[
            np.outer(idx, idx) % p]
        return (table * np.asarray(values, dtype=np.uint64)[None, :]
                % bb.P).sum(axis=1).astype(np.uint32)

    monkeypatch.setattr(ntt, "interpolate_host", by_table)
    with pytest.raises(BenchFailure, match="nothing was run"):
        kind.Traffic(mix, 3)
    # a program without that function is not looked at
    monkeypatch.delattr(ntt, "interpolate_host")
    assert "alloc" in kind.Traffic(mix, 3).genesis()
