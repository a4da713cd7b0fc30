"""Independent host-side STARK verifier (no JAX on the verification path).

Everything is canonical-integer arithmetic so correctness of the device
prover is checked against a fully independent implementation — the role the
reference gets from its zkVM SDKs' native verifiers (SURVEY.md §4 item on a
"TPU-kernel unit-test layer ... that ethrex gets for free from the zkVM
SDKs").
"""

from __future__ import annotations

import numpy as np

from ..ops.ntt import interpolate_host
from ..ops import babybear as bb
from ..ops import ext
from ..ops import fri
from ..ops import merkle
from ..ops.challenger import Challenger
from .air import Air, HostExtOps
from .prover import StarkParams


class VerificationError(Exception):
    pass


_INTERP_CACHE: dict = {}


def _periodic_interpolants(air: Air, n: int) -> list[list[int]]:
    """Coefficient vectors of the AIR's periodic columns (O(p^2) host
    interpolation done once per (AIR structure, n))."""
    key = (air.cache_key(), n)
    cached = _INTERP_CACHE.get(key)
    if cached is None:
        cached = [
            [int(v) for v in interpolate_host(
                np.asarray(vals, dtype=np.uint32) % bb.P)]
            for vals in air.periodic_columns(n)
        ]
        _INTERP_CACHE[key] = cached
    return cached


def _fail(msg: str):
    raise VerificationError(msg)


def verify(air: Air, proof: dict, params: StarkParams = StarkParams(),
           fri_verify_fn=None):
    """Verify an untrusted proof dict.  Returns True or raises
    VerificationError — structural garbage (missing keys, wrong types) is
    converted to VerificationError, never an unhandled crash.

    `fri_verify_fn(fri_proof, log_N, challenger, fparams) -> (indices,
    layer0)` overrides the FRI query verification step — the aggregation
    path (stark/aggregate.py) substitutes a derivation that defers the
    Merkle-opening work to the outer recursion STARK."""
    try:
        return _verify(air, proof, params, fri_verify_fn)
    except VerificationError:
        raise
    except (KeyError, TypeError, IndexError, ValueError, AttributeError) as e:
        raise VerificationError(f"malformed proof: {type(e).__name__}: {e}")


def _verify(air: Air, proof: dict, params: StarkParams,
            fri_verify_fn=None):
    n = proof["n"]
    w = proof["width"]
    lb = proof["log_blowup"]
    if lb != params.log_blowup:
        _fail("blowup mismatch")
    if w != air.width:
        _fail("width mismatch")
    B = 1 << lb
    log_n = n.bit_length() - 1
    if 1 << log_n != n:
        _fail("bad trace length")
    N = n << lb
    log_N = log_n + lb
    shift = params.shift % bb.P
    g_n = bb.root_of_unity(log_n)
    g_N = bb.root_of_unity(log_N)
    pub = [int(v) % bb.P for v in proof["pub_inputs"]]
    if len(pub) != air.num_pub_inputs:
        _fail("public input count mismatch")

    ch = Challenger()
    ch.absorb_elems([n, w, B])
    ch.absorb_elems(pub)
    ch.absorb_elems(proof["trace_root"])
    alpha = ch.sample_ext()
    ch.absorb_elems(proof["quotient_root"])
    zeta = ch.sample_ext()

    t_at_z = [tuple(int(x) for x in t) for t in proof["trace_at_zeta"]]
    t_at_zg = [tuple(int(x) for x in t) for t in proof["trace_at_zeta_g"]]
    q_at_z = [tuple(int(x) for x in t) for t in proof["quotient_at_zeta"]]
    if len(t_at_z) != w or len(t_at_zg) != w or len(q_at_z) != B:
        _fail("opening count mismatch")
    for tup in t_at_z + t_at_zg + q_at_z:
        ch.absorb_ext(tup)
    gamma = ch.sample_ext()

    # ---- constraint identity at zeta ------------------------------------
    hops = HostExtOps()
    # periodic columns: evaluate the cached interpolants at zeta
    periodic_at_z = []
    for coeffs in _periodic_interpolants(air, n):
        p_len = len(coeffs)
        point = ext.h_pow(zeta, n // p_len)   # f(z) = g(z^{n/p})
        acc = ext.ZERO_H
        for c in reversed(coeffs):
            acc = ext.h_add(ext.h_mul(acc, point), ext.h_from_base(c))
        periodic_at_z.append(acc)
    cons = air.constraints(t_at_z, t_at_zg, periodic_at_z, hops)
    bounds = air.boundaries(pub, n)
    zeta_n = ext.h_pow(zeta, n)
    z_trans_num = ext.h_sub(zeta_n, ext.ONE_H)              # zeta^n - 1
    z_trans_den = ext.h_sub(zeta, ext.h_from_base(pow(g_n, n - 1, bb.P)))
    inv_zt = ext.h_div(z_trans_den, z_trans_num)            # 1/Z_t(zeta)

    acc = ext.ZERO_H
    a_pow = ext.ONE_H
    for c in cons:
        acc = ext.h_add(acc, ext.h_mul(a_pow, c))
        a_pow = ext.h_mul(a_pow, alpha)
    lhs = ext.h_mul(acc, inv_zt)
    for (r, c, v) in bounds:
        num = ext.h_sub(t_at_z[c], ext.h_from_base(v))
        den = ext.h_sub(zeta, ext.h_from_base(pow(g_n, r % n, bb.P)))
        lhs = ext.h_add(lhs, ext.h_mul(a_pow, ext.h_div(num, den)))
        a_pow = ext.h_mul(a_pow, alpha)
    rhs = ext.ZERO_H
    zp = ext.ONE_H
    for i in range(B):
        rhs = ext.h_add(rhs, ext.h_mul(zp, q_at_z[i]))
        zp = ext.h_mul(zp, zeta_n)
    if lhs != rhs:
        _fail("constraint identity fails at zeta")

    # ---- FRI -------------------------------------------------------------
    fparams = fri.FriParams(
        log_blowup=lb, num_queries=params.num_queries,
        log_final_size=params.log_final_size, shift=shift,
        grinding_bits=params.grinding_bits,
    )
    fri_proof = fri.FriProof(
        roots=proof["fri"]["roots"],
        final_coeffs=[tuple(c) for c in proof["fri"]["final_coeffs"]],
        queries=proof["fri"]["queries"],
        pow_nonce=int(proof["fri"].get("pow_nonce", 0)),
    )
    try:
        indices, layer0 = (fri_verify_fn or fri.verify)(
            fri_proof, log_N, ch, fparams)
    except ValueError as e:
        _fail(str(e))

    # ---- openings: Merkle paths per tree, then the DEEP cross-check -----
    openings = proof["openings"]
    if len(openings) != len(indices):
        _fail("opening count != query count")
    half = N // 2
    idxs, t_rows, q_rows, t_paths, q_paths, fri_vals = [], [], [], [], [], []
    for (q, (pair_idx, fri_lo, fri_hi)), entry in zip(
        zip(indices, layer0), openings
    ):
        if pair_idx != q % half:
            _fail("query index mismatch")
        for tag, idx, fri_val in (("lo", q, fri_lo), ("hi", q + half, fri_hi)):
            t_row = [int(v) for v in entry[f"trace_{tag}"]]
            q_row = [int(v) for v in entry[f"quotient_{tag}"]]
            if len(t_row) != w or len(q_row) != B * 4:
                _fail("bad opening row width")
            idxs.append(idx)
            t_rows.append(t_row)
            q_rows.append(q_row)
            t_paths.append(entry[f"trace_{tag}_path"])
            q_paths.append(entry[f"quotient_{tag}_path"])
            fri_vals.append(tuple(fri_val))
    if not all(merkle.verify_openings(proof["trace_root"], idxs, t_rows,
                                      t_paths, log_N)):
        _fail("bad trace opening")
    if not all(merkle.verify_openings(proof["quotient_root"], idxs, q_rows,
                                      q_paths, log_N)):
        _fail("bad quotient opening")
    xs = [shift * pow(g_N, idx, bb.P) % bb.P for idx in idxs]
    deep = deep_values(xs, t_rows, q_rows, t_at_z, t_at_zg, q_at_z, gamma,
                       zeta, ext.h_mul(zeta, ext.h_from_base(g_n)))
    for val, fri_val in zip(deep, fri_vals):
        if val != fri_val:
            _fail("DEEP value mismatch with FRI layer 0")
    return True


# x^b as an ext element: a quotient opening's limb b multiplies x^b * gamma^i
_X_POWS = tuple(tuple(int(b == j) for j in range(4)) for b in range(4))


def _canonical(rows: list, width: int) -> np.ndarray:
    """Rows of wire ints as a (k, width) uint64 array of residues."""
    return np.array([[v % bb.P for v in row] for row in rows],
                    dtype=np.uint64).reshape(len(rows), width)


def _base_dot_ext(rows: np.ndarray, coeffs: list) -> list:
    """sum_j rows[i, j] * coeffs[j] for each row i: canonical base-field
    rows against ext tuples, as k 4-limb tuples.  Each product (< 2^62)
    is reduced before the sum, so a sum of up to 2^32 terms stays exact
    in uint64."""
    c = np.array(coeffs, dtype=np.uint64).reshape(len(coeffs), 4)
    sums = (rows[:, :, None] * c[None] % bb.P).sum(axis=1) % bb.P
    return [tuple(row) for row in sums.tolist()]


def deep_values(xs, t_rows, q_rows, t_at_z, t_at_zg, q_at_z, gamma, zeta,
                zeta_g) -> list:
    """The DEEP quotient's value at each opened point x, exactly
    sum_j g^j (t_j - a_j)/(x - z) + g^(w+j) (t_j - b_j)/(x - zg)
    + sum_i g^(2w+i) (q_i - c_i)/(x - z), for gamma g, the trace row t
    and quotient row q opened at x, and the openings a, b, c at z and
    zg.  Re-associated: the gamma-weighted sums of a, b, c are the same
    for every x and come once; what is left per x is base-by-ext dot
    products (`_base_dot_ext`, all rows at once) and a fixed handful of
    ext operations, whatever the width."""
    w, nq = len(t_at_z), len(q_at_z)
    g_pows = [ext.ONE_H]
    for _ in range(2 * w + nq - 1):
        g_pows.append(ext.h_mul(g_pows[-1], gamma))
    g_z, g_zg, g_q = g_pows[:w], g_pows[w:2 * w], g_pows[2 * w:]

    def weighted(ws, vals):
        acc = ext.ZERO_H
        for g, v in zip(ws, vals):
            acc = ext.h_add(acc, ext.h_mul(g, v))
        return acc

    at_z = ext.h_add(weighted(g_z, t_at_z), weighted(g_q, q_at_z))
    at_zg = weighted(g_zg, t_at_zg)
    # a quotient row is nq ext elements of 4 base limbs each
    q_coeffs = [ext.h_mul(g, xb) for g in g_q for xb in _X_POWS]
    t = _canonical(t_rows, w)
    d_z = _base_dot_ext(t, g_z)
    d_zg = _base_dot_ext(t, g_zg)
    d_q = _base_dot_ext(_canonical(q_rows, 4 * nq), q_coeffs)
    out = []
    for x, dz, dzg, dq in zip(xs, d_z, d_zg, d_q):
        x_h = ext.h_from_base(x)
        inv_xz = ext.h_inv(ext.h_sub(x_h, zeta))
        inv_xzg = ext.h_inv(ext.h_sub(x_h, zeta_g))
        out.append(ext.h_add(
            ext.h_mul(inv_xz, ext.h_sub(ext.h_add(dz, dq), at_z)),
            ext.h_mul(inv_xzg, ext.h_sub(dzg, at_zg))))
    return out
