"""The plain reference of the proof system's device layers, held
against the program at a size a test run can hold: it accepts what the
program's prover makes, it rejects each part of a proof altered, and its
pieces (field, permutation, transcript) agree with the program's where
both are defined."""

import copy
import json
import random

import pytest

import stark_reference as sr

PARAMS = {"log_blowup": 3, "num_queries": 40, "log_final_size": 4,
          "grinding_bits": 16}


@pytest.fixture(scope="module")
def proof():
    from ethrex_tpu.models import fibonacci as fib
    from ethrex_tpu.stark import prover
    from ethrex_tpu.stark.prover import StarkParams

    trace = fib.generate_trace(128)
    made = prover.prove(
        fib.FibonacciAir(), trace, fib.public_inputs(trace),
        StarkParams(log_blowup=3, num_queries=40, log_final_size=4))
    return json.loads(json.dumps(made))


def test_field_and_permutation_against_the_programs():
    from ethrex_tpu.ops import ext
    from ethrex_tpu.ops import poseidon2 as p2

    rng = random.Random(7)
    state = [rng.randrange(sr.P) for _ in range(16)]
    assert sr.permute(list(state)) == p2.permute_ref(state)
    for _ in range(20):
        a = tuple(rng.randrange(sr.P) for _ in range(4))
        b = tuple(rng.randrange(sr.P) for _ in range(4))
        assert sr.e_mul(a, b) == ext.h_mul(a, b)
        assert sr.e_mul(a, sr.e_inv(a)) == (1, 0, 0, 0)


def test_x_to_the_fourth_is_eleven():
    x = (0, 1, 0, 0)
    assert sr.e_mul(sr.e_mul(x, x), sr.e_mul(x, x)) == (11, 0, 0, 0)
    assert pow(sr.root_of_unity(5), 32, sr.P) == 1
    assert pow(sr.root_of_unity(5), 16, sr.P) == sr.P - 1


def test_batched_hashing_is_the_single_hash():
    import numpy as np

    rng = random.Random(3)
    rows = [[rng.randrange(sr.P) for _ in range(11)] for _ in range(5)]
    got = sr.hash_rows(np.array(rows, dtype=np.uint64))
    for k, row in enumerate(rows):
        state = [0] * 16
        padded = row + [0] * 5
        for i in (0, 8):
            for j in range(8):
                state[j] = (state[j] + padded[i + j]) % sr.P
            state = sr.permute(state)
        assert [int(got[j][k]) for j in range(8)] == state[:8]


def test_transcript_follows_the_programs():
    from ethrex_tpu.ops.challenger import Challenger

    theirs, ours = Challenger(), sr.Transcript()
    for elems in ([1, 2, 3], list(range(20)), [5]):
        theirs.absorb_elems(elems)
        ours.absorb(elems)
        assert theirs.sample_ext() == ours.sample_ext()
    nonce = theirs.grind(8)
    assert ours.check_work(nonce, 8)
    assert theirs.sample() == ours.sample()


def test_the_programs_proof_is_accepted(proof):
    from ethrex_tpu.models import fibonacci as fib
    from ethrex_tpu.stark import verifier
    from ethrex_tpu.stark.prover import StarkParams

    shape = sr.check_stark(proof, PARAMS)
    assert shape == {"width": 2, "log_n": 7, "queries": 40,
                     "fri_layers": 6}
    assert verifier.verify(
        fib.FibonacciAir(), proof,
        StarkParams(log_blowup=3, num_queries=40, log_final_size=4))


def _alter(path, flip=True):
    def change(p):
        at = p
        for key in path[:-1]:
            at = at[key]
        at[path[-1]] = at[path[-1]] ^ 1 if flip else "x"
    return change


@pytest.mark.parametrize("path", [
    ("trace_root", 0), ("quotient_root", 3), ("pub_inputs", 0),
    ("trace_at_zeta", 1, 2), ("quotient_at_zeta", 7, 0),
    ("openings", 3, "trace_hi", 1), ("openings", 0, "quotient_lo", 5),
    ("openings", 9, "trace_lo_path", 4, 0),
    ("fri", "roots", 2, 0), ("fri", "final_coeffs", 0, 0),
    ("fri", "queries", 5, 3, "values", 0, 2),
    ("fri", "queries", 39, 0, "path", 0, 7), ("fri", "pow_nonce",),
    ("n",), ("log_blowup",),
], ids=lambda p: ".".join(str(x) for x in p))
def test_an_altered_proof_is_rejected(proof, path):
    bad = copy.deepcopy(proof)
    _alter(path)(bad)
    with pytest.raises(sr.Rejected):
        sr.check_stark(bad, PARAMS)


def test_garbage_is_rejected_not_raised(proof):
    for path in (("fri", "queries", 0, 0, "path", 0, 0),
                 ("openings", 0, "trace_lo", 0), ("trace_root", 0)):
        bad = copy.deepcopy(proof)
        _alter(path, flip=False)(bad)
        with pytest.raises(sr.Rejected):
            sr.check_stark(bad, PARAMS)
    with pytest.raises(sr.Rejected):
        sr.check_stark({}, PARAMS)


def _without_work(proof):
    """With no proof-of-work and the transcript's own indices kept, the
    later checks are reached: each has to bite on its own."""
    return {**PARAMS, "grinding_bits": 0}


def test_fewer_queries_or_another_blowup_than_stated(proof):
    with pytest.raises(sr.Rejected):
        sr.check_stark(proof, {**PARAMS, "num_queries": 41})
    with pytest.raises(sr.Rejected):
        sr.check_stark(proof, {**PARAMS, "log_blowup": 2})
    with pytest.raises(sr.Rejected):
        sr.check_stark(proof, {**PARAMS, "log_final_size": 5})


def test_judge_proof_names_what_it_has_against_a_proof(proof):
    sizes = {"vm_proof": {"width": 2, "log_n": 7}}
    assert sr.judge_proof({"vm_proof": proof}, sizes, PARAMS) == []
    assert sr.judge_proof({"vm_proof": proof},
                          {"vm_proof": {"width": 3, "log_n": 7}}, PARAMS)
    assert sr.judge_proof({}, sizes, PARAMS) == ["vm_proof: not in the proof"]
    bad = copy.deepcopy(proof)
    bad["trace_root"][0] ^= 1
    assert len(sr.judge_proof({"vm_proof": bad}, sizes, PARAMS)) == 1
