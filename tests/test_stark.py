"""End-to-end STARK prove/verify on the Fibonacci AIR, plus soundness probes."""

import copy

import numpy as np
import pytest

from ethrex_tpu.models import fibonacci as fib
from ethrex_tpu.stark import prover, verifier
from ethrex_tpu.stark.prover import StarkParams

PARAMS = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)


def _make_proof(n=64):
    air = fib.FibonacciAir()
    trace = fib.generate_trace(n)
    pub = fib.public_inputs(trace)
    proof = prover.prove(air, trace, pub, PARAMS)
    return air, proof


AIR, PROOF = None, None


def _cached():
    global AIR, PROOF
    if PROOF is None:
        AIR, PROOF = _make_proof()
    return AIR, copy.deepcopy(PROOF)


def test_prove_verify_roundtrip():
    air, proof = _cached()
    assert verifier.verify(air, proof, PARAMS)


def test_wrong_public_input_rejected():
    air, proof = _cached()
    proof["pub_inputs"][2] = (proof["pub_inputs"][2] + 1) % (2**31 - 2**27 + 1)
    with pytest.raises(verifier.VerificationError):
        verifier.verify(air, proof, PARAMS)


def test_tampered_trace_root_rejected():
    air, proof = _cached()
    proof["trace_root"][0] ^= 1
    with pytest.raises(verifier.VerificationError):
        verifier.verify(air, proof, PARAMS)


def test_tampered_opening_rejected():
    air, proof = _cached()
    proof["openings"][0]["trace_lo"][0] ^= 1
    with pytest.raises(verifier.VerificationError):
        verifier.verify(air, proof, PARAMS)


def test_tampered_fri_final_rejected():
    air, proof = _cached()
    proof["fri"]["final_coeffs"][0][0] ^= 1
    with pytest.raises(verifier.VerificationError):
        verifier.verify(air, proof, PARAMS)


def test_tampered_zeta_opening_rejected():
    air, proof = _cached()
    proof["trace_at_zeta"][0] = tuple(
        (x + 1) % (2**31 - 2**27 + 1) for x in proof["trace_at_zeta"][0]
    )
    with pytest.raises(verifier.VerificationError):
        verifier.verify(air, proof, PARAMS)


def test_invalid_trace_rejected():
    # a trace violating the transition constraint must not produce a proof
    # that verifies (the quotient is not a polynomial -> identity fails)
    air = fib.FibonacciAir()
    trace = fib.generate_trace(64)
    trace[10, 1] = (int(trace[10, 1]) + 1) % (2**31 - 2**27 + 1)
    pub = fib.public_inputs(trace)
    proof = prover.prove(air, trace, pub, PARAMS)
    with pytest.raises(verifier.VerificationError):
        verifier.verify(air, proof, PARAMS)


# ---------------------------------------------------------------------------
# query openings: select, then convert (`merkle.open_paths_mont` and the
# row gather of `prove.query`) against the old form, which converted the
# whole array out of Montgomery form and indexed the result


def _whole_array_opening(rows, levels, idx):
    """The form `prove.query` had: canonical copies of everything."""
    from ethrex_tpu.ops import babybear as bb

    rows_c = bb.from_mont_host(rows)
    levels_c = [bb.from_mont_host(level) for level in levels]
    path, i = [], idx
    for level in levels_c[:-1]:
        path.append([int(x) for x in level[i ^ 1]])
        i >>= 1
    return [int(v) for v in rows_c[idx]], path


def _index_sets(n):
    rng = np.random.default_rng(n)
    return {
        "edges": [0, n // 2 - 1, n - 1],
        "repeats": [0, 0, n - 1, n // 2, n // 2, n - 1, 0],
        "pairs": [i for q in rng.integers(0, n // 2, 40).tolist()
                  for i in (q, q + n // 2)],
    }


@pytest.mark.parametrize("which", ["edges", "repeats", "pairs"])
@pytest.mark.parametrize("depth, width", [(1, 1), (3, 2), (6, 32), (10, 278)])
def test_open_paths_mont_matches_whole_array_form(depth, width, which):
    from ethrex_tpu.ops import babybear as bb
    from ethrex_tpu.ops import merkle

    n = 1 << depth
    rng = np.random.default_rng(depth * 1000 + width)
    rows = rng.integers(0, bb.P, (n, width), dtype=np.uint32)
    levels = [rng.integers(0, bb.P, (n >> k, merkle.DIGEST_WIDTH),
                           dtype=np.uint32) for k in range(depth + 1)]
    idxs = _index_sets(n)[which]
    rows_c = bb.from_mont_host(rows[np.array(idxs)])
    paths_c = merkle.open_paths_mont(levels, idxs)
    assert paths_c.shape == (len(idxs), depth, merkle.DIGEST_WIDTH)
    assert paths_c.dtype == np.uint32
    for j, idx in enumerate(idxs):
        want_row, want_path = _whole_array_opening(rows, levels, idx)
        assert rows_c[j].tolist() == want_row
        assert paths_c[j].tolist() == want_path
        assert all(type(x) is int for sib in paths_c[j].tolist()
                   for x in sib)


def test_open_paths_mont_of_a_single_leaf_tree_is_empty():
    from ethrex_tpu.ops import merkle

    root = np.zeros((1, merkle.DIGEST_WIDTH), np.uint32)
    assert merkle.open_paths_mont([root], [0, 0]).shape == (2, 0, 8)


def test_proof_openings_keep_their_wire_form():
    """The entries `query.paths` assembles from the gathered arrays are
    the dicts the old loop built: key order, plain ints, row widths and
    path depths (the verifier's round trip holds their values)."""
    air, proof = _cached()
    depth = (proof["n"] << proof["log_blowup"]).bit_length() - 1
    widths = {"trace": proof["width"],
              "quotient": 4 << proof["log_blowup"]}
    assert len(proof["openings"]) == PARAMS.num_queries
    for entry in proof["openings"]:
        assert list(entry) == [
            f"{name}_{tag}{suffix}" for name in ("trace", "quotient")
            for tag in ("lo", "hi") for suffix in ("", "_path")]
        for name, width in widths.items():
            for tag in ("lo", "hi"):
                row, path = entry[f"{name}_{tag}"], entry[f"{name}_{tag}_path"]
                assert len(row) == width and len(path) == depth
                assert all(type(v) is int for v in row)
                assert all(len(sib) == 8 and all(type(x) is int for x in sib)
                           for sib in path)
    assert verifier.verify(air, proof, PARAMS)


def test_opened_share_of_a_wide_tree_is_under_five_percent():
    """80 rows and their paths out of a 2^14-leaf tree of 32-wide rows:
    what `prove.query` converts, against the arrays it reads from."""
    from ethrex_tpu.ops import babybear as bb
    from ethrex_tpu.ops import merkle

    depth, width = 14, 32
    n = 1 << depth
    rng = np.random.default_rng(14)
    rows = rng.integers(0, bb.P, (n, width), dtype=np.uint32)
    levels = [np.zeros((n >> k, merkle.DIGEST_WIDTH), np.uint32)
              for k in range(depth + 1)]
    idxs = np.array(_index_sets(n)["pairs"])
    opened = bb.from_mont_host(rows[idxs]).nbytes \
        + merkle.open_paths_mont(levels, idxs).nbytes
    assert opened == 80 * (width * 4 + depth * 32)
    assert opened < 0.05 * (rows.nbytes + sum(l.nbytes for l in levels))
