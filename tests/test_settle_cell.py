"""The cell `settle-transfer10` (configuration `baseline1-l2dev`, mix
`transfer10-settle-ahead`): the whole `l2 --dev` stack ahead of one
prover.  On the CPU, with the `exec` prover standing in and the stack's
timers shortened, the deployment runs through the harness as the chip
runs it; the settlement reference refuses each planted fault; every
batch the stack seals at this traffic proves at BASELINE-1's shapes; the
two new readers read what they say; and a program whose committer has
no batch gas limit is refused before anything is built.  (Correctness
only: no number here is a device's.)"""

import copy
import json
import os
import shutil
import sys
import time

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402 — benchmark/harness.py
import metrics_lib  # noqa: E402 — benchmark/metrics_lib.py
import settle_reference  # noqa: E402 — benchmark/settle_reference.py

from ethrex_tpu.guest import access_log, transfer_log  # noqa: E402
from ethrex_tpu.guest.execution import (ProgramInput,  # noqa: E402
                                        execution_program)
from ethrex_tpu.guest.witness_oracles import WitnessOracles  # noqa: E402
from ethrex_tpu.models import state_update_air as sua  # noqa: E402
from ethrex_tpu.models import transfer_air as ta  # noqa: E402
from ethrex_tpu.prover import backend, tpu_backend  # noqa: E402

CELL = "settle-transfer10"


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


CONFIG = _json("configs", "baseline1-l2dev.json")
MIX = _json("traffic", "transfer10-settle-ahead.json")


def _standin(tmp_path):
    """The benchmark's data directories with the cell's configuration
    proving on `exec` and its mix's timers at 1.5 s, three batches
    sealed ahead and a 6 s window: the same files, the same code."""
    bench_dir = str(tmp_path / "benchmark")
    for sub in ("configs", "traffic", "traffic_kinds", "metrics",
                "deployments"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(bench_dir, sub))
    config = {**CONFIG, "prover": "exec",
              "guarantees": {"backend": "exec", "verify": True,
                             "settlement": True}}
    mix = {**MIX, "block_time_s": 1.5, "commit_interval_s": 1.5,
           "trace_seconds": 6,
           "arrival": {**MIX["arrival"], "batches_sealed_ahead": 3}}
    for name, data in (("configs/baseline1-l2dev.json", config),
                       ("traffic/transfer10-settle-ahead.json", mix)):
        with open(os.path.join(bench_dir, name), "w") as f:
            json.dump(data, f)
    return bench_dir


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One traced run of the cell; what the deployment handed the
    settlement reference, and the prover inputs the stack committed."""
    held = {}
    violations = settle_reference.violations

    def recording(record):
        held["record"] = copy.deepcopy(record)
        return violations(record)

    settle_reference.violations = recording
    deployment = harness.load_deployment
    inputs = {}

    def loading(name, bench_dir=harness.BENCH_DIR):
        cls = deployment(name, bench_dir)

        class Kept(cls):
            def collect(self):
                super().collect()
                inputs.update({n: r.program_input
                               for n, r in self.records.items()})
        return Kept

    harness.load_deployment = loading
    tmp = tmp_path_factory.mktemp("settle")
    out = tmp / "stdout.txt"
    saved = sys.stdout
    env = pytest.MonkeyPatch()
    # the harness clears the checkpoint directory at its start: this one's
    env.setenv("ETHREX_PROOF_CKPT_DIR", str(tmp / "ckpt"))
    # the stand-in proves slower than the stack seals, as the chip does
    prove = backend.ExecBackend.prove

    def slow(self, *args, **kwargs):
        time.sleep(2.5)
        return prove(self, *args, **kwargs)

    env.setattr(backend.ExecBackend, "prove", slow)
    try:
        with open(out, "w") as f:
            sys.stdout = f
            result = harness.run_cell(
                CELL, 2**31 + 39, 6.0, True, time.monotonic(),
                bench_dir=_standin(tmp),
                device={"platform": "cpu", "kind": "cpu", "count": 1})
    finally:
        sys.stdout = saved
        env.undo()
        settle_reference.violations = violations
        harness.load_deployment = deployment
    return {"result": result, "record": held["record"],
            "inputs": inputs, "log": out.read_text()}


def test_the_stack_runs_the_cell_on_the_cpu(run):
    result, log = run["result"], run["log"]
    assert result["correct"], json.dumps(result["compared"])
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert "each one block of 10 transfers" in log
    assert "nothing broken" in log
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["commit_s"] > 0 and m["settle_host_s"] > 0
    assert m["prover_idle_s"] < 1.5 and m["spans_lost"] == 0
    assert "unspanned_s" not in m
    record = run["record"]
    assert settle_reference.violations(record) == []
    assert record["verified"][-1] >= 2 and record["judged"]
    assert not record["deleted"]
    assert len(record["acks"]) == 10 * len(record["blocks"])


def _first_settled(record):
    call = record["settled"][0]
    return call["first"]


def _drop_a_transaction(record):
    block = record["blocks"][max(record["blocks"], key=int)]
    block["transactions"].pop()


def _alter_a_root(record):
    n = _first_settled(record)
    root = record["l1_roots"][n]
    record["l1_roots"][n] = root[:-1] + ("0" if root[-1] != "0" else "1")


def _verify_without_a_proof(record):
    record["settled"][0]["proofs"][0] = b""


def _delete_a_proof(record):
    record["deleted"].append([_first_settled(record), "exec"])


@pytest.mark.parametrize("plant, says", [
    (_drop_a_transaction, "is in 0 blocks"),
    (_alter_a_root, "the L1's state root"),
    (_verify_without_a_proof, "no verifyBatches carried a proof"),
    (_delete_a_proof, "deleted the exec proof"),
])
def test_each_settlement_fault_is_refused(run, plant, says):
    record = copy.deepcopy(run["record"])
    plant(record)
    wrong = settle_reference.violations(record)
    assert wrong and any(says in line for line in wrong), wrong


def test_a_proof_the_check_did_not_judge_is_refused(run):
    record = copy.deepcopy(run["record"])
    n = min(record["judged"], key=int)
    record["judged"][n] = {**record["judged"][n], "forged": True}
    if not any(c["first"] <= int(n) <= c["last"]
               for c in record["settled"]):
        pytest.skip("the judged batch was not settled in this run")
    assert any("not the stored proof" in line
               for line in settle_reference.violations(record))


def test_every_batch_proves_at_baseline1_shapes(run):
    """The batches the stack sealed at this traffic are each one block of
    ten transfers in transfer mode: the transfer and state circuits at the
    configuration's rows, as `TpuBackend` sizes them (the binding sponge's
    size follows from the mode alone), without proving."""
    sizes, starks = CONFIG["sizes_on_device"], CONFIG["starks"]
    assert sizes == _json("configs", "baseline1-prover.json")[
        "sizes_on_device"]
    inputs = [ProgramInput.from_json(v) for _, v in
              sorted(run["inputs"].items())[:4]]
    assert len(inputs) == 4
    for pi in inputs:
        assert len(pi.blocks) == 1
        assert len(pi.blocks[0].body.transactions) == 10
        assert tpu_backend.expected_vm_mode(pi) == "transfer"
        coarse, receipts = [], []
        output = execution_program(pi, write_log=coarse,
                                   receipts_out=receipts)
        vb = transfer_log.build_vm_batch(
            pi.blocks, coarse, receipts,
            oracles=WitnessOracles(pi.witness, output.initial_state_root))
        records, _, _, depth = access_log.build_access_records(
            access_log.flatten_entries(vb.blocks_log))
        periods = tpu_backend._schedule_for(depth)
        rows = {"vm_proof": ta.segment_count(len(vb.segs)) * ta.SEG_LEN,
                "state_proof": sua.segment_count(len(records)) * periods
                * sua.PERIOD}
        assert rows == {k: 1 << starks[k]["log_n"] for k in rows}
        state = sizes["StateUpdateAir"]
        assert (depth, periods) == (state["depth"], state["seg_periods"])


def _span(name, start, seconds, trace="t", **attrs):
    return {"name": name, "start": start, "seconds": seconds,
            "traceId": trace, "attrs": attrs}


STACK_SPANS = [
    _span("seq.commit", 10.0, 0.25, "t5", batch=5, blocks=1, txs=10,
          gas=210000),
    _span("seq.witness", 10.0, 0.05, "t5"),
    _span("seq.commit", 12.3, 0.35, "t6", batch=6, blocks=1, txs=10,
          gas=210000),
    _span("proof.verify", 11.0, 1.2, "t2", batch=2),
    _span("proof.verify", 12.2, 1.4, "t3", batch=3),
    _span("l1.verify", 13.6, 0.1, "t2", first=2, last=3),
    _span("proof.settle", 13.7, 0.01, "t2", batch=2),
    _span("proof.settle", 13.71, 0.03, "t3", batch=3),
]
PROVER_SPANS = [
    _span("prover.idle", 0.0, 1.0, batch=2, polls=0),
    _span("backend.prove", 1.0, 2.0),
    _span("prove.trace_gen", 1.1, 0.08, air="TransferAir"),
    _span("prover.store_proof", 3.0, 0.01, batch=2),
]


def _read(name, spans):
    _, read = metrics_lib.load_metric(os.path.join(BENCH, "metrics"), name)
    return read({"spans": spans, "batches": 2})


def test_the_stack_readers_read_their_spans_and_nothing_else():
    assert _read("commit_s", STACK_SPANS + PROVER_SPANS) \
        == pytest.approx(0.30)
    assert _read("settle_host_s", STACK_SPANS + PROVER_SPANS) \
        == pytest.approx((1.2 + 1.4 + 0.1 + 0.01 + 0.03) / 2)
    for name in ("commit_s", "settle_host_s"):
        assert _read(name, PROVER_SPANS) is None
        assert _read(name, []) is None
    assert _read("settle_host_s", [
        _span("proof.settle", 1.0, 0.1, batch="2")]) is None


def test_the_cell_is_listed_where_it_reads():
    bench = harness.load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL] == {
        "name": CELL, "config": "baseline1-l2dev",
        "traffic": "transfer10-settle-ahead", "chips": 1,
        "why": cells[CELL]["why"]}
    lists = {m["name"]: m.get("workloads")
             for m in bench["end_to_end"] + bench["per_layer"]}
    assert CELL in lists["batch_prove_s"]
    assert lists["commit_s"] == lists["settle_host_s"] == [CELL]
    # the batch trace holds the proof sender's spans, seconds after the
    # proof was stored: no reader of a trace's extent is listed
    assert CELL not in lists["unspanned_s"]
    for name, cells_of in lists.items():
        if cells_of and "prove-transfer10" in cells_of \
                and name != "unspanned_s":
            assert CELL in cells_of, name


def test_a_program_without_the_gas_limit_is_refused_before_it_builds(
        monkeypatch, tmp_path):
    """A program from before the option: its l2 parser has no
    --committer.batch-gas-limit.  The run ends at once (exit 3 from
    run.py), before a stack is started or a program hydrated."""
    import argparse

    from common import BenchFailure
    from ethrex_tpu import cli

    monkeypatch.setenv("ETHREX_PROOF_CKPT_DIR", str(tmp_path / "ckpt"))
    monkeypatch.setattr(cli, "build_parser", argparse.ArgumentParser)
    monkeypatch.setattr(cli, "start_l2_stack", lambda args: pytest.fail(
        "the stack was started"))
    t0 = time.monotonic()
    with pytest.raises(BenchFailure, match="no batch gas limit"):
        harness.run_cell(CELL, 2**31 + 40, 4.0, False, time.monotonic(),
                         bench_dir=_standin(tmp_path),
                         device={"platform": "cpu", "kind": "cpu",
                                 "count": 1})
    assert time.monotonic() - t0 < 5
