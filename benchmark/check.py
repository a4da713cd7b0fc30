"""What decides `correct`.  Every number compared is a count of
disagreements between what the timed path produced in the window and
what the configuration guarantees; each is an exact comparison, so
every limit is 0.  The comparison runs after the window has closed and
the device's memory peak has been read, outside every timed interval.

Every proof of the window is held to all of these but the last, which
takes a sample:
  * the plain reference of the traffic's kind (reference.py for
    eth_transfers): the accounts each batch must leave, from the seed's
    transactions alone, against the write log each proof claims; the
    public output's block range and the chaining of roots;
  * the plain reference of the proof system's device layers
    (stark_reference.py, nothing of the program): each of the proof's
    STARKs at the size the configuration states, its transcript, its
    Merkle openings, its DEEP values and its FRI layers;
  * the program's verifier on a fresh backend object (`verify`): the
    AIRs' constraint identities and the binding of the public inputs to
    the write log, which are the program's own statement and have no
    plain form;
  * `verify_with_input` against the prover input as the rollup store
    holds it (the witness replay), on `check.witness_replays` proofs
    drawn from the seed, in place of `verify` there (it runs the same
    check first);
and the deployment's own ledgers (the coordinator's quarantine list, the
runtime's degradation counters) are read once.
"""

from __future__ import annotations

import hashlib
import json
import random
import time

import reference
import stark_reference
from common import err, log


def replayed(window: list, seed: int, config: dict) -> set:
    """The batch numbers whose proofs get the witness replay: none where
    the configuration does not guarantee `verify_with_input`; else
    `check.witness_replays` of the window's, drawn from the seed (all of
    them when that is 0 or absent).  Every other comparison runs on
    every proof of the window."""
    if not config["guarantees"].get("verify_with_input"):
        return set()
    numbers = sorted(r.number for r in window)
    size = int(config.get("check", {}).get("witness_replays", 0))
    if not size or size >= len(numbers):
        return set(numbers)
    return set(random.Random(seed).sample(numbers, size))


def _digest(proof: dict) -> str:
    return hashlib.sha256(
        json.dumps(proof, sort_keys=True).encode()).hexdigest()


def judge(records: list, traffic, kind, config: dict,
          prover: str, verifier, counters: dict, exec_proofs: int,
          memo: dict | None = None):
    """({name: [value, limit]}, failed batches) for the window's
    batches.  `kind` is the module of the traffic's kind, whose
    `expected_states` and `count_state_mismatches` are its plain
    reference; `verifier` is a fresh backend instance.  `memo` (the control
    sweep's) keeps a proof's verdicts by the digest of its bytes, so a
    control that breaks one proof pays for that proof alone."""
    g = config["guarantees"]
    window = [r for r in records if r.in_window]
    n = {"unproven_batches": 0, "wrong_backend": 0, "output_mismatches": 0}
    for name, flag in (("missing_vm_component", "vm_component"),
                       ("stark_reference_rejected", "stark_reference"),
                       ("verify_rejected", "verify"),
                       ("verify_with_input_rejected", "verify_with_input"),
                       ("state_mismatches", "reference_state")):
        if g.get(flag):
            n[name] = 0
    bad: set = set()
    spent = {"stark_reference": 0.0, "verify": 0.0, "verify_with_input": 0.0}

    def miss(name: str, rec, by: int = 1) -> None:
        n[name] += by
        bad.add(rec.number)

    def once(what: str, proof: dict, run):
        key = (what, _digest(proof)) if memo is not None else None
        if key is not None and key in memo:
            return memo[key]
        t0 = time.monotonic()
        got = run()
        spent[what] += time.monotonic() - t0
        if key is not None:
            memo[key] = got
        return got

    states = kind.expected_states(
        traffic, max(r.number for r in records) - 1) \
        if records else []
    replays = replayed(window, traffic.seed, config)
    bpb = int(traffic.mix["blocks_per_batch"])
    by_number = {r.number: r for r in records}
    for rec in window:
        proof = rec.proof
        if not isinstance(proof, dict):
            miss("unproven_batches", rec)
            continue
        if proof.get("backend") != prover:
            miss("wrong_backend", rec)
        if "missing_vm_component" in n and not (
                "vm" in proof and proof.get("vm_proof") is not None
                and proof.get("proof") is not None
                and proof.get("state_proof") is not None):
            miss("missing_vm_component", rec)
        if "stark_reference_rejected" in n:
            against = once("stark_reference", proof,
                           lambda: stark_reference.judge_proof(
                               proof, config["starks"],
                               config["stark_params"]))
            for line in against:
                err(f"batch {rec.number}: stark reference: {line}")
            if against:
                miss("stark_reference_rejected", rec, len(against))
        if rec.number in replays:
            # verify_with_input runs verify's own check of every STARK
            # first, then the witness replay and the vm metadata
            from ethrex_tpu.guest.execution import ProgramInput

            audit = getattr(verifier, "verify_with_input", None)
            if audit is None or not once(
                    "verify_with_input", proof, lambda: bool(audit(
                        proof, ProgramInput.from_json(rec.program_input)))):
                miss("verify_with_input_rejected", rec)
        elif "verify_rejected" in n and not once(
                "verify", proof, lambda: bool(verifier.verify(proof))):
            miss("verify_rejected", rec)
        # the public output against the batch the harness sent
        try:
            out = reference.output_fields(proof["output"])
            wrong = int(out["first_block"] != (rec.number - 1) * bpb + 1) \
                + int(out["last_block"] != rec.number * bpb)
            prev = by_number.get(rec.number - 1)
            if prev is not None and isinstance(prev.proof, dict):
                before = reference.output_fields(prev.proof["output"])
                wrong += int(before["final_root"] != out["initial_root"])
        except (KeyError, ValueError, TypeError):
            wrong = 1
        if wrong:
            miss("output_mismatches", rec, wrong)
        if "state_mismatches" in n:
            try:
                wrong = kind.count_state_mismatches(
                    states[rec.number - 1], proof["write_log"])
            except (KeyError, ValueError, TypeError, IndexError):
                wrong = 1
            if wrong:
                miss("state_mismatches", rec, wrong)
    failed = len(bad)
    log("check seconds: " + json.dumps(
        {k: round(v, 2) for k, v in spent.items()}))
    # the deployment's own ledgers: not tied to one batch
    n["exec_proofs"] = exec_proofs
    n["quarantined"] = counters.get("coordinator.quarantined", 0)
    n["rejected_submits"] = counters.get("coordinator.rejected_submits", 0)
    if g.get("no_degradation"):
        for key, name in (("rt.degradations", "degradations"),
                          ("rt.memoryGateShrinks", "gate_shrinks"),
                          ("rt.oomRetries", "oom_retries"),
                          ("rt.deviceLostRetries", "device_lost_retries")):
            n[name] = counters.get(key, 0)
    if not window:
        n["unproven_batches"] = 1      # an empty window proves nothing
    numbers = {name: [value, 0] for name, value in n.items()}
    if failed == 0 and window and not verdict(numbers):
        failed = 1      # a ledger is over its limit: no batch is sound
    return numbers, failed


def verdict(numbers: dict) -> bool:
    return all(value <= limit for value, limit in numbers.values())
