"""The verifier's query phase in bulk: the DEEP cross-check re-associated
(`stark/verifier.deep_values`) and every tree's Merkle openings in one
native call (`ops/merkle.verify_openings` -> `native/poseidon2.c`
`p2_merkle_roots`), held to the per-element forms they replace, which
are kept here as the oracles."""

import copy
import random
import sys
import threading

import numpy as np
import pytest

from ethrex_tpu.models import fibonacci as fib
from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.ops import ext, merkle
from ethrex_tpu.ops import poseidon2 as p2
from ethrex_tpu.stark import prover, verifier
from ethrex_tpu.stark.prover import StarkParams

P = bb.P
PARAMS = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)


def _deep_loop(xs, t_rows, q_rows, t_at_z, t_at_zg, q_at_z, gamma, zeta,
               zeta_g):
    """The per-element DEEP combination the verifier ran before."""
    w, nq = len(t_at_z), len(q_at_z)
    out = []
    for x, t_row, q_row in zip(xs, t_rows, q_rows):
        x_h = ext.h_from_base(x)
        inv_xz = ext.h_inv(ext.h_sub(x_h, zeta))
        inv_xzg = ext.h_inv(ext.h_sub(x_h, zeta_g))
        val = ext.ZERO_H
        g_pow = ext.ONE_H
        for j in range(w):
            diff = ext.h_sub(ext.h_from_base(t_row[j]), t_at_z[j])
            val = ext.h_add(val, ext.h_mul(g_pow, ext.h_mul(inv_xz, diff)))
            g_pow = ext.h_mul(g_pow, gamma)
        for j in range(w):
            diff = ext.h_sub(ext.h_from_base(t_row[j]), t_at_zg[j])
            val = ext.h_add(val, ext.h_mul(g_pow, ext.h_mul(inv_xzg, diff)))
            g_pow = ext.h_mul(g_pow, gamma)
        for i in range(nq):
            q_val = tuple(q_row[i * 4 + k] for k in range(4))
            diff = ext.h_sub(q_val, q_at_z[i])
            val = ext.h_add(val, ext.h_mul(g_pow, ext.h_mul(inv_xz, diff)))
            g_pow = ext.h_mul(g_pow, gamma)
        out.append(val)
    return out


def _deep_inputs(seed: int, w: int, k: int, nq: int = 8):
    rng = random.Random(seed)

    def limb():
        # mostly canonical; now and then a wire value past p or below 0
        v = rng.randrange(P)
        return rng.choice([v, v, v, v + P, v - P, v + 3 * P])

    def ext_el():
        return tuple(limb() for _ in range(4))

    xs = [rng.randrange(1, P) for _ in range(k)]
    t_rows = [[limb() for _ in range(w)] for _ in range(k)]
    q_rows = [[limb() for _ in range(4 * nq)] for _ in range(k)]
    return (xs, t_rows, q_rows, [ext_el() for _ in range(w)],
            [ext_el() for _ in range(w)], [ext_el() for _ in range(nq)],
            ext_el(), ext_el(), ext_el())


@pytest.mark.parametrize("w", [24, 115, 117, 278])
def test_bulk_deep_combination_equals_the_per_element_loop(w):
    for seed in range(3):
        args = _deep_inputs(1000 * w + seed, w, k=6)
        assert verifier.deep_values(*args) == _deep_loop(*args)


def test_a_quotient_of_another_blowup_combines_the_same():
    args = _deep_inputs(7, 5, k=4, nq=4)
    assert verifier.deep_values(*args) == _deep_loop(*args)


def test_ext_multiplies_per_opening_do_not_grow_with_the_width(monkeypatch):
    """Per opening the bulk form runs a fixed handful of ext products,
    whatever the width: the per-width work is the once-a-proof powers
    of gamma and the numpy dot products."""
    calls = [0]
    h_mul = ext.h_mul

    def counting(a, b):
        calls[0] += 1
        return h_mul(a, b)

    monkeypatch.setattr(ext, "h_mul", counting)
    per_opening = {}
    for w in (24, 115, 117, 278):
        counts = []
        for k in (5, 10):
            args = _deep_inputs(w, w, k)
            calls[0] = 0
            verifier.deep_values(*args)
            counts.append(calls[0])
        per_opening[w] = (counts[1] - counts[0]) / 5
    assert len(set(per_opening.values())) == 1, per_opening
    assert per_opening[24] <= 4


# ---------------------------------------------------------------------------
# a real small proof, tampered one limb at a time


@pytest.fixture(scope="module")
def fib_proof():
    air = fib.FibonacciAir()
    trace = fib.generate_trace(64)
    proof = prover.prove(air, trace, fib.public_inputs(trace), PARAMS)
    return air, proof


def _flip(path):
    def tamper(proof):
        obj = proof
        for key in path[:-1]:
            obj = obj[key]
        obj[path[-1]] ^= 1
    return tamper


def _flip_fri_value(query, layer):
    def tamper(proof):
        values = proof["fri"]["queries"][query][layer]["values"]
        lo = list(values[0])
        lo[2] ^= 1
        values[0] = tuple(lo)
    return tamper


TAMPERS = {
    "trace_lo": (_flip(["openings", 3, "trace_lo", 0]), "bad trace opening"),
    "trace_hi": (_flip(["openings", 7, "trace_hi", 1]), "bad trace opening"),
    "quotient_lo": (_flip(["openings", 0, "quotient_lo", 5]),
                    "bad quotient opening"),
    "quotient_hi": (_flip(["openings", 15, "quotient_hi", 15]),
                    "bad quotient opening"),
    "trace_path_sibling": (_flip(["openings", 5, "trace_lo_path", 2, 4]),
                           "bad trace opening"),
    "quotient_path_sibling": (
        _flip(["openings", 9, "quotient_hi_path", 7, 0]),
        "bad quotient opening"),
    "fri_layer_value_0": (_flip_fri_value(2, 0),
                          "FRI: bad merkle opening at layer 0"),
    "fri_layer_value_3": (_flip_fri_value(11, 3),
                          "FRI: bad merkle opening at layer 3"),
    "fri_path": (_flip(["fri", "queries", 4, 1, "path", 0, 3]),
                 "FRI: bad merkle opening at layer 1"),
}


@pytest.mark.parametrize("name", sorted(TAMPERS))
def test_a_one_limb_change_is_rejected_with_its_message(fib_proof, name):
    air, proof = fib_proof
    assert verifier.verify(air, proof, PARAMS)
    tamper, message = TAMPERS[name]
    bad = copy.deepcopy(proof)
    tamper(bad)
    with pytest.raises(verifier.VerificationError) as err:
        verifier.verify(air, bad, PARAMS)
    assert str(err.value) == message


@pytest.mark.parametrize("delta", [P, -P])
def test_a_limb_off_by_p_reads_as_its_residue(fib_proof, delta):
    """Merkle leaves and DEEP terms are taken mod p, as they were one
    opening at a time: a trace limb moved by p still verifies."""
    air, proof = fib_proof
    bad = copy.deepcopy(proof)
    bad["openings"][3]["trace_lo"][0] += delta
    bad["openings"][6]["quotient_hi_path"][1][2] += delta
    assert verifier.verify(air, bad, PARAMS)


def test_a_proofs_check_counts_the_openings_it_folded(fib_proof):
    air, proof = fib_proof
    n_layers = len(proof["fri"]["roots"])
    want = 2 * 2 * PARAMS.num_queries + PARAMS.num_queries * n_layers
    before = merkle.openings_folded()
    verifier.verify(air, proof, PARAMS)
    assert merkle.openings_folded() - before == want
    # the count is the calling thread's own
    seen = []
    t = threading.Thread(target=lambda: seen.append(merkle.openings_folded()))
    t.start()
    t.join()
    assert seen == [0]


@pytest.mark.parametrize("tampered", [False, True])
def test_the_backends_check_marks_the_span_it_runs_in(fib_proof, monkeypatch,
                                                      tampered):
    """`fold` and `openings` go on the span the STARK backend's check runs
    in (the proof sender's `proof.verify`), a rejected proof's too; with
    no span open the check runs the same and marks nothing."""
    from ethrex_tpu.prover import tpu_backend
    from ethrex_tpu.prover.tpu_backend import TpuBackend
    from ethrex_tpu.utils import tracing

    air, proof = fib_proof
    if tampered:
        proof = copy.deepcopy(proof)
        proof["openings"][0]["trace_lo_path"][0][0] ^= 1
    monkeypatch.setattr(tpu_backend, "PARAMS", PARAMS)
    monkeypatch.setattr(TpuBackend, "_reconstruct",
                        lambda self, wire: ([air], [proof], None, b""))
    n_layers = len(proof["fri"]["roots"])
    # FRI's layers, then the trace tree, where a tampered proof stops
    want = PARAMS.num_queries * (n_layers + (2 if tampered else 4))
    with tracing.span("proof.verify") as sp:
        with tracing.span("inner"):
            pass
        assert tracing.current_span() is sp
        assert TpuBackend().verify({}) is not tampered
    assert sp.attrs == {"fold": p2.engine(), "openings": want}
    assert tracing.current_span() is None
    assert TpuBackend().verify({}) is not tampered
    # a trace re-entered inside a span hides it from the code it wraps
    with tracing.span("proof.verify") as sp:
        with tracing.trace_context("t"):
            assert tracing.current_span() is None
        assert tracing.current_span() is sp


# ---------------------------------------------------------------------------
# the native fold against the Python one


def _random_openings(seed: int, k: int, width: int, depth: int):
    rng = random.Random(seed)
    leaves = [[rng.randrange(P) for _ in range(width)] for _ in range(k)]
    idxs = [rng.randrange(1 << depth) for _ in range(k)]
    paths = [[[rng.randrange(P) for _ in range(8)] for _ in range(depth)]
             for _ in range(k)]
    return leaves, idxs, paths


@pytest.mark.parametrize("width,depth", [(0, 3), (1, 0), (8, 1), (24, 9),
                                         (32, 17), (115, 5), (278, 17)])
def test_native_fold_equals_fold_path_canonical(width, depth):
    leaves, idxs, paths = _random_openings(width * 31 + depth, 12, width,
                                           depth)
    got = p2.native_merkle_roots(
        np.array(leaves, dtype=np.uint32).reshape(12, width), idxs,
        np.array(paths, dtype=np.uint32).reshape(12, depth, 8))
    want = [merkle.fold_path_canonical(i, merkle.hash_leaf_ref(leaf), path)
            for i, leaf, path in zip(idxs, leaves, paths)]
    assert got.dtype == np.uint32 and got.tolist() == want


def test_a_committed_tree_opens_through_the_bulk_check():
    """A tree the device builds (Montgomery levels), its paths as the
    prover opens them: every opening holds, one tampered one fails
    alone."""
    rng = np.random.default_rng(5)
    rows = rng.integers(0, P, (64, 12), dtype=np.uint32)
    levels = [np.asarray(lv) for lv in
              merkle.commit_levels(bb.to_mont_host(rows))]
    root = bb.from_mont_host(levels[-1][0]).tolist()
    idxs = [0, 1, 17, 40, 63]
    paths = merkle.open_paths_mont(levels, idxs).tolist()
    leaves = rows[idxs].tolist()
    assert merkle.verify_openings(root, idxs, leaves, paths, 6) == [True] * 5
    paths[2][4][1] ^= 1
    assert merkle.verify_openings(root, idxs, leaves, paths, 6) == \
        [True, True, False, True, True]


def test_the_fallback_folds_in_python_when_the_library_is_absent(
        monkeypatch):
    leaves, idxs, paths = _random_openings(9, 6, 24, 7)
    root = merkle.fold_path_canonical(
        idxs[1], merkle.hash_leaf_ref(leaves[1]), paths[1])
    native = merkle.verify_openings(root, idxs, leaves, paths, 7)
    assert native == [False, True, False, False, False, False]
    assert p2.engine() == "native"

    folds = []
    fold = merkle.fold_path_canonical

    def spy(*args):
        folds.append(args[0])
        return fold(*args)

    monkeypatch.setattr(p2, "_lib", False)
    monkeypatch.setattr(merkle, "fold_path_canonical", spy)
    assert p2.native_merkle_roots(np.zeros((1, 8), np.uint32), [0],
                                  np.zeros((1, 0, 8), np.uint32)) is None
    assert p2.engine() == "python"
    assert merkle.verify_openings(root, idxs, leaves, paths, 7) == native
    assert folds == idxs


# ---------------------------------------------------------------------------
# untrusted input: the verdict one opening at a time gave


def _verify_opening_oracle(root_c, index, leaf_values_c, path_c, depth):
    """The single-opening check the verifier ran before."""
    try:
        if len(path_c) != depth or len(root_c) != 8:
            return False
        if any(len(sib) != 8 for sib in path_c):
            return False
        digest = merkle.hash_leaf_ref(leaf_values_c)
        folded = merkle.fold_path_canonical(index, digest, path_c)
        return folded == [int(x) % P for x in root_c]
    except (TypeError, ValueError):
        return False


def _set(where, pos, value):
    def edit(root, leaf, path):
        target = {"root": root, "leaf": leaf, "sib": path[pos[0]]}[where]
        k = pos[-1]
        target[k] = value(target[k])
    return edit


EDITS = {
    **{f"{where}={name}": _set(where, (2, 3), fn)
       for where in ("sib", "leaf", "root")
       for name, fn in (("p", lambda v: P), ("p+1", lambda v: P + 1),
                        ("2^32", lambda v: 2 ** 32), ("-1", lambda v: -1),
                        ("v+p", lambda v: v + P), ("v-p", lambda v: v - P),
                        ("str", lambda v: "x"), ("none", lambda v: None),
                        ("float", lambda v: float(v)))},
    "short_path": lambda root, leaf, path: path.pop(),
    "long_path": lambda root, leaf, path: path.append([0] * 8),
    "seven_limb_sibling": lambda root, leaf, path: path[4].pop(),
    "nine_limb_sibling": lambda root, leaf, path: path[1].append(0),
    "sibling_not_a_list": lambda root, leaf, path: path.__setitem__(3, 7),
    "seven_limb_root": lambda root, leaf, path: root.pop(),
    "leaf_not_a_list": None,
    "path_not_a_list": None,
}


@pytest.mark.parametrize("case", sorted(EDITS))
def test_a_malformed_opening_gets_the_single_opening_verdict(case):
    depth = 6
    leaves, idxs, paths = _random_openings(3, 4, 16, depth)
    # opening 2 is the one edited; openings 0 and 2 belong to the root
    root = merkle.fold_path_canonical(
        idxs[2], merkle.hash_leaf_ref(leaves[2]), paths[2])
    paths[0] = copy.deepcopy(paths[2])
    leaves[0] = list(leaves[2])
    idxs[0] = idxs[2]
    edit = EDITS[case]
    if case == "leaf_not_a_list":
        leaves[2] = 5
    elif case == "path_not_a_list":
        paths[2] = 5
    else:
        edit(root, leaves[2], paths[2])
    want = [_verify_opening_oracle(root, i, leaf, path, depth)
            for i, leaf, path in zip(idxs, leaves, paths)]
    assert merkle.verify_openings(root, idxs, leaves, paths, depth) == want
    if case.endswith(("v+p", "v-p", "float")):
        assert want[2] is True


def test_threads_fold_their_own_openings_and_count_their_own():
    """The proof sender folds on its thread while the prover's threads
    permute: each native call owns its buffers, each thread its count."""
    jobs = []
    for k in range(8):
        leaves, idxs, paths = _random_openings(100 + k, 5, 24, 9)
        root = merkle.fold_path_canonical(
            idxs[k % 5], merkle.hash_leaf_ref(leaves[k % 5]), paths[k % 5])
        want = [i == k % 5 for i in range(5)]
        jobs.append((root, idxs, leaves, paths, want))
    got: dict = {}

    def worker(k):
        root, idxs, leaves, paths, want = jobs[k]
        before = merkle.openings_folded()
        ok = all(merkle.verify_openings(root, idxs, leaves, paths, 9) == want
                 for _ in range(20))
        got[k] = (ok, merkle.openings_folded() - before)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=worker, args=(k,))
                   for k in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(interval)
    assert got == {k: (True, 100) for k in range(8)}
