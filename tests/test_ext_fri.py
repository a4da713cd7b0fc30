"""Quartic extension field + standalone FRI tests."""

import numpy as np
import jax.numpy as jnp
import pytest

from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.ops import ext, fri, ntt
from ethrex_tpu.ops.challenger import Challenger

RNG = np.random.default_rng(3)


def _rand_ext_h():
    return tuple(int(x) for x in RNG.integers(0, bb.P, size=4))


def test_host_ext_field_axioms():
    a, b, c = _rand_ext_h(), _rand_ext_h(), _rand_ext_h()
    assert ext.h_mul(a, b) == ext.h_mul(b, a)
    assert ext.h_mul(a, ext.h_mul(b, c)) == ext.h_mul(ext.h_mul(a, b), c)
    assert ext.h_mul(a, ext.h_add(b, c)) == ext.h_add(
        ext.h_mul(a, b), ext.h_mul(a, c)
    )
    assert ext.h_mul(a, ext.ONE_H) == a
    inv = ext.h_inv(a)
    assert ext.h_mul(a, inv) == ext.ONE_H


def test_device_ext_matches_host():
    ah, bh = _rand_ext_h(), _rand_ext_h()
    ad, bd = ext.to_device(ah), ext.to_device(bh)
    assert ext.to_host(ext.mul(ad, bd)) == ext.h_mul(ah, bh)
    assert ext.to_host(ext.add(ad, bd)) == ext.h_add(ah, bh)
    assert ext.to_host(ext.sub(ad, bd)) == ext.h_sub(ah, bh)
    assert ext.to_host(ext.ext_pow(ad, 12345)) == ext.h_pow(ah, 12345)


def test_device_ext_inv_and_batch_inv():
    vals_h = [_rand_ext_h() for _ in range(33)]
    dev = jnp.stack([ext.to_device(v) for v in vals_h])
    inv_dev = ext.batch_inv(dev)
    for i, vh in enumerate(vals_h):
        got = ext.to_host(inv_dev[i])
        assert ext.h_mul(vh, got) == ext.ONE_H
    single = ext.ext_inv_device(dev[0])
    assert ext.h_mul(vals_h[0], ext.to_host(single)) == ext.ONE_H


def test_eval_base_poly_at_ext_point():
    coeffs = RNG.integers(0, bb.P, size=(3, 16), dtype=np.uint32)
    pt = _rand_ext_h()
    got = ext.eval_base_poly_at_ext(
        bb.to_mont(jnp.asarray(coeffs)), ext.to_device(pt)
    )
    for j in range(3):
        acc = ext.ZERO_H
        for c in reversed([int(v) for v in coeffs[j]]):
            acc = ext.h_add(ext.h_mul(acc, pt), ext.h_from_base(c))
        assert ext.to_host(got[j]) == acc


def _codeword_from_degree(log_n, log_blowup, rng):
    """Random poly of degree < 2^log_n, evaluated on the blown-up coset."""
    n = 1 << log_n
    coeffs = rng.integers(0, bb.P, size=(4, n), dtype=np.uint32)
    evals = ntt.coset_evals_from_coeffs(
        bb.to_mont(jnp.asarray(coeffs)), n << log_blowup
    )
    return jnp.moveaxis(evals, 0, -1)  # (N, 4)


def test_fri_roundtrip():
    params = fri.FriParams(log_blowup=2, num_queries=10, log_final_size=4)
    cw = _codeword_from_degree(6, 2, RNG)  # N = 256
    proof, indices = fri.FriProver(params).prove(cw, Challenger())
    got_indices, layer0 = fri.verify(proof, 8, Challenger(), params)
    assert got_indices == indices
    assert len(layer0) == 10


def test_fri_rejects_high_degree():
    # degree-n polynomial committed as if degree < n/blowup head-room:
    # make a codeword that is NOT low-degree (random evals)
    params = fri.FriParams(log_blowup=2, num_queries=10, log_final_size=4)
    cw = bb.to_mont(jnp.asarray(RNG.integers(0, bb.P, (256, 4), dtype=np.uint32)))
    ch = Challenger()
    with pytest.raises(ValueError):
        # prover's own degree-bound check trips on garbage input
        fri.FriProver(params).prove(cw, ch)


def test_fri_rejects_tampered_query():
    params = fri.FriParams(log_blowup=2, num_queries=10, log_final_size=4)
    cw = _codeword_from_degree(6, 2, RNG)
    proof, _ = fri.FriProver(params).prove(cw, Challenger())
    proof.queries[0][1]["values"][0] = tuple(
        (x + 1) % bb.P for x in proof.queries[0][1]["values"][0]
    )
    with pytest.raises(ValueError):
        fri.verify(proof, 8, Challenger(), params)


def test_fri_rejects_tampered_pow_nonce():
    # grinding (docs/SOUNDNESS.md): the verifier must enforce the
    # proof-of-work nonce, not just absorb it
    params = fri.FriParams(log_blowup=2, num_queries=4, log_final_size=4,
                           grinding_bits=8)
    cw = _codeword_from_degree(6, 2, RNG)
    proof, _ = fri.FriProver(params).prove(cw, Challenger())
    good = fri.verify(proof, 8, Challenger(), params)
    assert good is not None
    # pick a tampered nonce that provably fails the 8-bit work check (a
    # blindly incremented nonce would pass it with probability 1/256 and
    # turn this into a flaky Merkle-error test instead): mirror the
    # verifier's transcript up to the PoW seed, then search
    from ethrex_tpu.ops.challenger import pow_ok

    ch = Challenger()
    for root in proof.roots:
        ch.absorb_elems(root)
        ch.sample_ext()
    for row in proof.final_coeffs:
        ch.absorb_ext(tuple(row))
    seed = ch._pow_seed()
    bad = proof.pow_nonce
    while True:
        bad += 1
        if not pow_ok(seed, bad, 8):
            break
    proof.pow_nonce = bad
    with pytest.raises(ValueError, match="grinding"):
        fri.verify(proof, 8, Challenger(), params)


def test_grind_check_roundtrip_and_transcript_alignment():
    a, b = Challenger(), Challenger()
    a.absorb_elems([7, 11])
    b.absorb_elems([7, 11])
    nonce = a.grind(10)
    assert b.check_grind(nonce, 10)
    # both transcripts must land in the same state after the PoW phase
    assert a.sample() == b.sample()


def test_ext_powers_blocked_matches_scan():
    pt = ext.to_device(_rand_ext_h())
    for n in (1, 5, 128, 300, 1024):
        a = np.asarray(ext.ext_powers(pt, n))
        b = np.asarray(ext.ext_powers_blocked(pt, n, block=64))
        np.testing.assert_array_equal(a, b)


def test_eval_base_poly_large_uses_blocked_path():
    coeffs = RNG.integers(0, bb.P, size=300, dtype=np.uint32)
    pt = _rand_ext_h()
    got = ext.eval_base_poly_at_ext(
        bb.to_mont(jnp.asarray(coeffs)), ext.to_device(pt))
    acc = ext.ZERO_H
    for c in reversed([int(v) for v in coeffs]):
        acc = ext.h_add(ext.h_mul(acc, pt), ext.h_from_base(c))
    assert ext.to_host(got) == acc


def test_frobenius_is_p_power():
    zh = _rand_ext_h()
    zd = ext.to_device(zh)
    for k in (1, 2, 3):
        expect = ext.h_pow(zh, bb.P ** k)
        assert ext.to_host(ext.frobenius(zd, k)) == expect


def test_inv_x_minus_zeta_matches_batch_inv():
    zeta_h = _rand_ext_h()
    zeta = ext.to_device(zeta_h)
    xs = RNG.integers(0, bb.P, size=257, dtype=np.uint32)
    xm = bb.to_mont(jnp.asarray(xs))
    got = ext.inv_x_minus_zeta(xm, zeta)
    # reference: explicit (x - zeta) then the scan-based batch_inv
    x_ext = jnp.concatenate(
        [bb.sub(xm, jnp.broadcast_to(zeta[0], xm.shape))[:, None],
         jnp.broadcast_to(bb.neg(zeta[1:]), xm.shape + (3,))], axis=-1)
    expect = ext.batch_inv(x_ext)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))


def _whole_array_open_queries(layers, indices):
    """`FriProver.open_queries` as it was: every layer's codeword and
    tree converted whole out of Montgomery form, then indexed."""
    out = []
    for q in indices:
        per_layer, idx = [], q
        for cw_np, levels_np in layers:
            canon = bb.from_mont_host(cw_np)
            levels_c = [bb.from_mont_host(level) for level in levels_np]
            half = canon.shape[0] // 2
            idx %= half
            path, i = [], idx
            for level in levels_c[:-1]:
                path.append([int(x) for x in level[i ^ 1]])
                i >>= 1
            per_layer.append({
                "values": [tuple(int(v) for v in canon[idx]),
                           tuple(int(v) for v in canon[idx + half])],
                "path": path})
        out.append(per_layer)
    return out


@pytest.mark.parametrize("indices", [
    [0, 31, 63], [5, 5, 63, 0, 5], list(range(64)),
], ids=["edges", "repeats", "every-leaf"])
def test_fri_open_queries_match_whole_array_form(indices):
    from ethrex_tpu.utils import tracing

    params = fri.FriParams(log_blowup=2, num_queries=len(indices),
                           log_final_size=4)
    cw = _codeword_from_degree(5, 2, np.random.default_rng(11))  # N = 128
    prover = fri.FriProver(params)
    roots, _ = prover.commit_phase(cw, Challenger())
    assert len(prover.layers) == 3
    # the layers stay as they left the device: the root alone is canonical
    for (cw_np, levels_np), root in zip(prover.layers, roots):
        assert cw_np.dtype == np.uint32 and levels_np[-1].shape == (1, 8)
        assert bb.from_mont_host(levels_np[-1][0]).tolist() == root
        assert all(type(x) is int for x in root)
    with tracing.trace_context(None) as tid:
        got = prover.open_queries(indices)
    assert got == _whole_array_open_queries(prover.layers, indices)
    assert all(type(v) is int for q in got for o in q
               for v in o["values"][0] + o["values"][1])
    # canon_bytes: 2 values of 16 B and depth x 32 B of path per query
    # and layer (leaves 64, 32, 16 -> depths 6, 5, 4)
    (span,) = [s for s in tracing.TRACER.get_trace(tid)["spans"]
               if s["name"] == "fri.open_queries"]
    assert span["attrs"]["canon_bytes"] == \
        len(indices) * (3 * 2 * 16 + (6 + 5 + 4) * 32)
    mirrors = sum(c.nbytes + sum(l.nbytes for l in lv)
                  for c, lv in prover.layers)
    assert mirrors == sum((1 << k) * 16 + ((1 << k) - 1) * 32
                          for k in (7, 6, 5))
