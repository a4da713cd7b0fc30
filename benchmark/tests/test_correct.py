"""`correct` has to come out false when the timed path is broken
underneath it, and when a control breaks a guarantee in what the path
produced.  These drive the harness past its look for a chip, at a size a
test run can hold (the `exec` prover standing in, or records built by
hand with a small real STARK in them); the controls at the cell's own
size run on the chip (`sweep.py --controls`, PERF.md)."""

import copy

import pytest

import helpers


def test_sound_run_is_correct(tmp_path):
    r = helpers.run(tmp_path)
    assert r["correct"] and r["failed"] == 0
    assert r["compared"]["verify_rejected"] == [0, 0]


def test_an_answer_altered_where_it_is_produced(tmp_path, monkeypatch):
    """The prover claims a wrong block range in its public output."""
    from ethrex_tpu.prover.backend import ExecBackend

    sound = ExecBackend.prove

    def altered(self, program_input, proof_format):
        proof = sound(self, program_input, proof_format)
        raw = bytearray.fromhex(proof["output"][2:])
        raw[111] ^= 1                      # last_block_number's low byte
        proof["output"] = "0x" + raw.hex()
        return proof

    monkeypatch.setattr(ExecBackend, "prove", altered)
    r = helpers.run(tmp_path)
    assert not r["correct"]
    assert r["compared"]["output_mismatches"][0] >= 1
    assert r["failed"] == r["attempted"] >= 1


def test_a_step_that_returns_its_state_unchanged(tmp_path, monkeypatch):
    """The prover answers every batch with the first proof it made."""
    from ethrex_tpu.prover.backend import ExecBackend

    sound = ExecBackend.prove
    first = {}

    def stuck(self, program_input, proof_format):
        if not first:
            first.update(sound(self, program_input, proof_format))
        return dict(first)

    monkeypatch.setattr(ExecBackend, "prove", stuck)
    r = helpers.run(tmp_path)
    assert not r["correct"]
    assert r["compared"]["output_mismatches"][0] >= 1
    assert r["failed"] == r["attempted"] >= 1


def test_a_proof_the_verifier_rejects(tmp_path, monkeypatch):
    """Every proof of the window goes to the program's verifier."""
    from ethrex_tpu.prover.backend import ExecBackend

    sound = ExecBackend.verify
    # the coordinator's gate keeps its sound answer, so the proofs are
    # stored; only the audit after the window sees the verifier say no
    monkeypatch.setattr(ExecBackend, "verify_submission",
                        lambda self, proof: sound(self, proof))
    monkeypatch.setattr(ExecBackend, "verify", lambda self, proof: False)
    r = helpers.run(tmp_path)
    assert not r["correct"]
    assert r["compared"]["verify_rejected"][0] == r["attempted"] >= 1


def test_control_replayed_proof_is_not_correct(tmp_path):
    from controls import replay_previous

    r = helpers.run(tmp_path, seconds=2.5,
                    controls={"replay_previous": replay_previous})
    assert r["correct"]
    got = r["controls"]["replay_previous"]
    assert not got["correct"]
    assert "output_mismatches" in got["over"]


# ---------------------------------------------------------------------------
# the controls of the chip's cell, on records small enough to build by
# hand: proofs whose write logs are what the reference expects, whose vm
# STARK is a real one of a small AIR made by the program's prover, and a
# stand-in for the program's verifier that accepts a proof only with its
# binding STARK's trace root intact

PARAMS = {"log_blowup": 3, "num_queries": 40, "log_final_size": 4,
          "grinding_bits": 16}
ROOT = [1, 2, 3, 4, 5, 6, 7, 8]


@pytest.fixture(scope="module")
def small_stark():
    import json

    from ethrex_tpu.models import fibonacci as fib
    from ethrex_tpu.stark import prover
    from ethrex_tpu.stark.prover import StarkParams

    trace = fib.generate_trace(64)
    proof = prover.prove(
        fib.FibonacciAir(), trace, fib.public_inputs(trace),
        StarkParams(log_blowup=3, num_queries=40, log_final_size=4))
    return json.loads(json.dumps(proof))       # as the wire carries it


def _records_and_judge(small_stark, replays=1, stated_log_n=6):
    import check
    import ethtx
    import harness
    from common import BatchRecord
    from traffic import load_mix

    mix, kind = load_mix(
        harness.BENCH_DIR + "/traffic/transfer10-backlog.json")
    traffic = kind.Traffic(mix, 2**31 + 5)
    states = kind.expected_states(traffic, 3)
    root, code = b"\x56" * 32, b"\xc5" * 32
    records, prev_root = [], b"\x00" * 32
    for k, state in enumerate(states):
        rows = [["a", a.hex(), "", ethtx.rlp_encode(
            [nonce, balance, root, code]).hex(), False]
            for a, (nonce, balance) in sorted(state.items())]
        final = ethtx.keccak256(bytes([k]))
        output = (prev_root + final + b"\x00" * 32
                  + (k + 1).to_bytes(8, "big") + (k + 1).to_bytes(8, "big")
                  + b"\x00" * 64)
        prev_root = final
        records.append(BatchRecord(
            number=k + 1, blocks=traffic.batch(k), in_window=k > 0,
            proof={"backend": "tpu", "output": "0x" + output.hex(),
                   "proof": {"trace_root": list(ROOT)},
                   "state_proof": {}, "vm": {"mode": "transfer"},
                   "vm_proof": copy.deepcopy(small_stark),
                   "write_log": [rows]},
            program_input=None))

    class Verifier:
        replayed: list = []

        def verify(self, proof):
            return proof["proof"]["trace_root"] == ROOT

        def verify_with_input(self, proof, program_input):
            self.replayed.append(proof)
            return self.verify(proof)

    config = {"guarantees": {"vm_component": True, "reference_state": True,
                             "stark_reference": True, "verify": True,
                             "verify_with_input": True,
                             "no_degradation": True},
              "starks": {"vm_proof": {"width": 2, "log_n": stated_log_n}},
              "stark_params": PARAMS,
              "check": {"witness_replays": replays}}

    def judge(recs):
        numbers, failed = check.judge(recs, traffic, kind, config, "tpu",
                                      Verifier(), {}, 0)
        return check.verdict(numbers), numbers, failed

    replayed = check.replayed([r for r in records if r.in_window],
                              traffic.seed, config)
    return records, judge, replayed


@pytest.fixture()
def no_program_input(monkeypatch):
    from ethrex_tpu.guest.execution import ProgramInput

    monkeypatch.setattr(ProgramInput, "from_json",
                        classmethod(lambda cls, data: data))


def test_records_the_references_agree_with_are_correct(
        small_stark, no_program_input):
    records, judge, replayed = _records_and_judge(small_stark)
    ok, numbers, failed = judge(records)
    assert ok and failed == 0, numbers
    assert len(replayed) == 1
    assert numbers["stark_reference_rejected"] == [0, 0]


def test_every_control_breaks_one_unsampled_proof_and_is_caught(
        small_stark, no_program_input):
    from controls import CONTROLS

    records, judge, replayed = _records_and_judge(small_stark)
    caught_by = {"flip_trace_root": "verify_rejected",
                 "garble_fri_layer": "stark_reference_rejected",
                 "forge_balance": "state_mismatches",
                 "replay_previous": "output_mismatches"}
    assert set(caught_by) == set(CONTROLS)
    for name, plant in CONTROLS.items():
        broken = copy.deepcopy(records)
        plant(broken, replayed)
        changed = [b.number for a, b in zip(records, broken)
                   if a.proof != b.proof]
        assert len(changed) == 1 and changed[0] not in replayed, name
        ok, numbers, failed = judge(broken)
        assert not ok, name
        assert numbers[caught_by[name]][0] >= 1, (name, numbers)
        assert numbers["verify_with_input_rejected"] == [0, 0], name
        # the broken batch, and with `replay_previous` the batch after
        # it, whose roots no longer chain
        assert 1 <= failed <= 2


def test_half_of_the_batch_left_out(small_stark, no_program_input):
    """A proof whose write log holds every other account only."""
    records, judge, _ = _records_and_judge(small_stark)
    rows = records[2].proof["write_log"][0]
    records[2].proof["write_log"] = [rows[::2]]
    ok, numbers, failed = judge(records)
    assert not ok and failed == 1
    assert numbers["state_mismatches"][0] == len(rows) - len(rows[::2])


def test_a_stark_at_another_size_than_the_configuration_states(
        small_stark, no_program_input):
    records, judge, _ = _records_and_judge(small_stark, stated_log_n=7)
    ok, numbers, failed = judge(records)
    assert not ok and failed == 3
    assert numbers["stark_reference_rejected"][0] == 3
