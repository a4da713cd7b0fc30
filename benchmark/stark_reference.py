"""The plain reference of the proof system's device layers: what a
committed trace, its quotient, the DEEP combination and the FRI layers
have to satisfy, recomputed from the proof's own bytes with nothing of
the program.  It imports nothing of `ethrex_tpu` and takes no table the
program made: BabyBear, its quartic extension, Poseidon2 (the round
constants drawn again from SHAKE-256 of the published domain tags), the
duplex-sponge transcript, the Merkle tree and the FRI fold are all
written out here from their definitions.

For one STARK (`check_stark`) it rebuilds the Fiat-Shamir transcript
(so every challenge and every query index is its own), and then holds
the proof to

  * every opened trace row and quotient row hashing up to the committed
    root (the Merkle kernels: leaf sponge and 2-to-1 compression);
  * the DEEP value of each opened point, from the opened rows and the
    out-of-domain openings, equal to what FRI's first layer opens there
    (the LDE and the quotient are evaluations over the coset the
    transcript fixes: the NTT kernels);
  * every FRI layer's opening hashing up to that layer's root, each
    fold equal to the next layer's opened value, and the last fold equal
    to the final polynomial, whose degree is under the bound (the fold
    kernel and the layers' trees);
  * the proof-of-work nonce.

It does NOT evaluate the AIR's constraints at the out-of-domain point:
the AIR is the program's statement of what a transfer is, not a layer
of the device, and that identity stays with the program's verifier
(check.py runs it on every proof as well).  So this reference says "the
device committed to low-degree columns and opened them honestly", the
program's verifier says "and those columns satisfy the AIR".

Hashing is batched over the 40 queries with numpy (uint64, every product
under 2^62); the transcript and the field arithmetic are Python ints.
"""

from __future__ import annotations

import hashlib

import numpy as np

from ethtx import keccak256

P = 2013265921                  # BabyBear, 15 * 2^27 + 1
GENERATOR = 31                  # of F_p^*, and the LDE coset's shift
TWO_ADICITY = 27
W = 11                          # the extension is F_p[x] / (x^4 - W)
WIDTH, RATE = 16, 8             # Poseidon2 state and sponge rate
ROUNDS_F, ROUNDS_P = 8, 13      # external (4 + 4) and internal rounds
HASH_TAG = b"ethrex-tpu/poseidon2/babybear/w16/v1"
TRANSCRIPT_TAG = b"ethrex-tpu/stark/v1"


class Rejected(Exception):
    """The proof does not satisfy the reference; the message says where."""


# ---------------------------------------------------------------------------
# the base field and its quartic extension (4-tuples of ints)

def root_of_unity(log_n: int) -> int:
    return pow(pow(GENERATOR, (P - 1) >> TWO_ADICITY, P),
               1 << (TWO_ADICITY - log_n), P)


def inv(a: int) -> int:
    return pow(a, P - 2, P)


def e_add(a, b):
    return tuple((x + y) % P for x, y in zip(a, b))


def e_sub(a, b):
    return tuple((x - y) % P for x, y in zip(a, b))


def e_scale(a, s: int):
    return tuple(x * s % P for x in a)


def e_mul(a, b):
    a0, a1, a2, a3 = a
    b0, b1, b2, b3 = b
    return ((a0 * b0 + W * (a1 * b3 + a2 * b2 + a3 * b1)) % P,
            (a0 * b1 + a1 * b0 + W * (a2 * b3 + a3 * b2)) % P,
            (a0 * b2 + a1 * b1 + a2 * b0 + W * a3 * b3) % P,
            (a0 * b3 + a1 * b2 + a2 * b1 + a3 * b0) % P)


def e_inv(a):
    """1/a through the tower F_p[y]/(y^2 - W), y = x^2: with
    a = A + xB, a * (A - xB) = A^2 - y B^2 lies in the quadratic field,
    whose inverse is its conjugate over its norm."""
    a0, a1, a2, a3 = a
    # A = a0 + a2 y, B = a1 + a3 y;  N = A^2 - y B^2 = n0 + n1 y
    n0 = (a0 * a0 + W * a2 * a2 - W * 2 * a1 * a3) % P
    n1 = (2 * a0 * a2 - a1 * a1 - W * a3 * a3) % P
    d = inv((n0 * n0 - W * n1 * n1) % P)
    if d == 0:
        raise Rejected("division by zero in the extension field")
    m0, m1 = n0 * d % P, -n1 * d % P        # 1/N = m0 + m1 y
    # 1/a = (A - xB) * (m0 + m1 y), with y = x^2 and y^2 = W
    return ((a0 * m0 + W * a2 * m1) % P,
            -(a1 * m0 + W * a3 * m1) % P,
            (a0 * m1 + a2 * m0) % P,
            -(a1 * m1 + a3 * m0) % P)


def e_horner(coeffs, x: int):
    """sum coeffs[i] x^i for extension coefficients at a base point."""
    acc = (0, 0, 0, 0)
    for c in reversed(coeffs):
        acc = e_add(e_scale(acc, x), c)
    return acc


# ---------------------------------------------------------------------------
# Poseidon2 over BabyBear, width 16, x^7.  `state` is 16 columns, each a
# Python int (the transcript) or a uint64 array (a batch of hashes): the
# same lines serve both.

def field_stream(tag: bytes, n: int) -> list[int]:
    """n field elements by rejection from SHAKE-256(tag), four bytes
    little-endian at a time; the stream continues at tag + b"/ext<k>"."""
    out: list[int] = []
    stream, pos, k = hashlib.shake_256(tag).digest(8 * n + 1024), 0, 0
    while len(out) < n:
        if pos + 4 > len(stream):
            k += 1
            stream = hashlib.shake_256(tag + b"/ext%d" % k).digest(
                8 * n + 1024)
            pos = 0
        v = int.from_bytes(stream[pos:pos + 4], "little")
        pos += 4
        if v < P:
            out.append(v)
    return out


def _constants():
    ext = field_stream(HASH_TAG + b"/ext-rc", ROUNDS_F * WIDTH)
    ext = [ext[r * WIDTH:(r + 1) * WIDTH] for r in range(ROUNDS_F)]
    internal = field_stream(HASH_TAG + b"/int-rc", ROUNDS_P)
    k = 0
    while True:     # the first diagonal for which J + diag(mu) inverts
        mu = field_stream(HASH_TAG + b"/diag/%d" % k, WIDTH)
        if all(mu) and (1 + sum(inv(m) for m in mu)) % P:
            return ext, internal, mu
        k += 1


EXT_RC, INT_RC, DIAG_MU = _constants()


def _sbox(x):
    x2 = x * x % P
    x4 = x2 * x2 % P
    return x4 * x2 % P * x % P


def _m4(x0, x1, x2, x3):
    t0, t1 = x0 + x1, x2 + x3
    t2, t3 = 2 * x1 + t1, 2 * x3 + t0
    t4, t5 = 4 * t1 + t3, 4 * t0 + t2
    return (t3 + t5) % P, t5 % P, (t2 + t4) % P, t4 % P


def _external(s):
    blocks = [_m4(*s[i:i + 4]) for i in range(0, WIDTH, 4)]
    sums = [(blocks[0][j] + blocks[1][j] + blocks[2][j] + blocks[3][j]) % P
            for j in range(4)]
    return [(b[j] + sums[j]) % P for b in blocks for j in range(4)]


def permute(s: list) -> list:
    s = _external(s)
    for r in range(ROUNDS_F // 2):
        s = _external([_sbox((x + c) % P) for x, c in zip(s, EXT_RC[r])])
    for r in range(ROUNDS_P):
        s[0] = _sbox((s[0] + INT_RC[r]) % P)
        total = sum(s[1:], s[0]) % P
        s = [(total + m * x) % P for x, m in zip(s, DIAG_MU)]
    for r in range(ROUNDS_F // 2, ROUNDS_F):
        s = _external([_sbox((x + c) % P) for x, c in zip(s, EXT_RC[r])])
    return s


def hash_rows(rows: np.ndarray) -> list:
    """Sponge digests of a batch of rows (b, w), zero-padded to the
    rate: 8 columns of b values."""
    b, w = rows.shape
    rows = np.concatenate(
        [rows, np.zeros((b, -w % RATE), dtype=np.uint64)], axis=1)
    state = [np.zeros(b, dtype=np.uint64) for _ in range(WIDTH)]
    for i in range(0, rows.shape[1], RATE):
        for j in range(RATE):
            state[j] = (state[j] + rows[:, i + j]) % P
        state = permute(state)
    return state[:RATE]


def compress(left: list, right: list) -> list:
    out = permute(list(left) + list(right))
    return [(o + l) % P for o, l in zip(out[:RATE], left)]


def _field_array(values, shape: tuple, what: str) -> np.ndarray:
    try:
        arr = np.array(values, dtype=object)
        if arr.shape != shape:
            raise ValueError(f"shape {arr.shape}, not {shape}")
        if not all(isinstance(v, int) and 0 <= v < P for v in arr.flat):
            raise ValueError("a value outside the field")
        return arr.astype(np.uint64)
    except (ValueError, TypeError, OverflowError) as exc:
        raise Rejected(f"{what}: {exc}") from None


def check_openings(root, indices: list[int], rows: np.ndarray, paths,
                   depth: int, what: str) -> None:
    """Every row hashes up its path to `root`: a tree of 2^depth leaves,
    siblings bottom-up."""
    b = len(indices)
    root = _field_array(root, (RATE,), what + " root")
    paths = _field_array(paths, (b, depth, RATE), what + " paths")
    idx = np.array(indices, dtype=np.uint64)
    cur = hash_rows(rows)
    for level in range(depth):
        odd = ((idx >> np.uint64(level)) & np.uint64(1)).astype(bool)
        sib = [paths[:, level, j] for j in range(RATE)]
        cur = compress([np.where(odd, s, c) for s, c in zip(sib, cur)],
                       [np.where(odd, c, s) for s, c in zip(sib, cur)])
    for j in range(RATE):
        if not np.array_equal(cur[j], np.full(b, root[j])):
            raise Rejected(f"{what}: an opening does not hash to the root")


# ---------------------------------------------------------------------------
# the transcript: a duplex sponge over the same permutation

class Transcript:
    def __init__(self):
        self.state = [0] * WIDTH
        self.absorbed = 0
        self.squeezed = RATE        # permute before the first sample
        self.absorb(field_stream(TRANSCRIPT_TAG, RATE))

    def absorb(self, elems) -> None:
        for e in elems:
            if self.absorbed == RATE:
                self.state = permute(self.state)
                self.absorbed = 0
            self.state[self.absorbed] = (self.state[self.absorbed]
                                         + int(e)) % P
            self.absorbed += 1
        self.squeezed = RATE

    def sample(self) -> int:
        if self.squeezed >= RATE or self.absorbed > 0:
            self.state = permute(self.state)
            self.absorbed = self.squeezed = 0
        self.squeezed += 1
        return self.state[self.squeezed - 1]

    def sample_ext(self):
        return tuple(self.sample() for _ in range(4))

    def absorb_int(self, v: int) -> None:
        """An unbounded int as its count of 27-bit limbs, then those."""
        limbs = [v & ((1 << 27) - 1)]
        while v >> 27:
            v >>= 27
            limbs.append(v & ((1 << 27) - 1))
        self.absorb([len(limbs)] + limbs)

    def check_work(self, nonce: int, bits: int) -> bool:
        """keccak256(seed || nonce), big-endian, has `bits` leading zero
        bits; the seed is squeezed, the nonce absorbed."""
        if bits <= 0:
            return True
        if not 0 <= nonce < 1 << 64:
            return False
        seed = b"".join(self.sample().to_bytes(4, "little")
                        for _ in range(8))
        ok = int.from_bytes(keccak256(seed + nonce.to_bytes(8, "little")),
                            "big") < 1 << (256 - bits)
        self.absorb_int(nonce)
        return ok


# ---------------------------------------------------------------------------
# one STARK

def _ext_list(values, count: int, what: str) -> list:
    arr = _field_array(values, (count, 4), what)
    return [tuple(int(v) for v in row) for row in arr]


def _gamma_sum(rows: np.ndarray, powers: list) -> list:
    """sum_j powers[j] * rows[:, j] for base rows (b, w) and extension
    powers: b extension elements."""
    gp = np.array(powers, dtype=np.uint64)                  # (w, 4)
    terms = rows[:, :, None] * gp[None, :, :] % P           # (b, w, 4)
    return [tuple(int(v) for v in row) for row in terms.sum(axis=1) % P]


def check_stark(proof: dict, params: dict) -> dict:
    """Hold one STARK to the reference; Rejected if it fails.  `params`
    gives log_blowup, num_queries, log_final_size, grinding_bits.
    Returns the shape it found: width, log_n, queries, FRI layers."""
    try:
        return _check_stark(proof, params)
    except Rejected:
        raise
    except (KeyError, TypeError, IndexError, ValueError, AttributeError,
            OverflowError) as exc:
        raise Rejected(f"malformed proof: {type(exc).__name__}: {exc}") \
            from None


def _check_stark(proof: dict, params: dict) -> dict:
    n, w, lb = int(proof["n"]), int(proof["width"]), int(proof["log_blowup"])
    if lb != params["log_blowup"]:
        raise Rejected(f"log_blowup {lb}, the configuration states "
                       f"{params['log_blowup']}")
    log_n = n.bit_length() - 1
    if n < 2 or 1 << log_n != n or w < 1:
        raise Rejected("bad trace shape")
    blowup, log_big = 1 << lb, log_n + lb
    big, half = n << lb, (n << lb) // 2
    queries = int(params["num_queries"])
    layers = log_big - int(params["log_final_size"])
    final_size = 1 << int(params["log_final_size"])

    # ---- the transcript, in the prover's order ---------------------------
    ts = Transcript()
    ts.absorb([n, w, blowup])
    ts.absorb(int(v) % P for v in proof["pub_inputs"])
    ts.absorb(_field_array(proof["trace_root"], (RATE,), "trace root"))
    ts.sample_ext()             # alpha: the AIR's, not this reference's
    ts.absorb(_field_array(proof["quotient_root"], (RATE,),
                           "quotient root"))
    zeta = ts.sample_ext()
    at_z = _ext_list(proof["trace_at_zeta"], w, "trace_at_zeta")
    at_zg = _ext_list(proof["trace_at_zeta_g"], w, "trace_at_zeta_g")
    q_at_z = _ext_list(proof["quotient_at_zeta"], blowup,
                       "quotient_at_zeta")
    for opened in at_z + at_zg + q_at_z:
        ts.absorb(opened)
    gamma = ts.sample_ext()
    fri = proof["fri"]
    roots = fri["roots"]
    if len(roots) != layers:
        raise Rejected(f"{len(roots)} FRI layers, not {layers}")
    betas = []
    for root in roots:
        ts.absorb(_field_array(root, (RATE,), "FRI root"))
        betas.append(ts.sample_ext())
    final = _ext_list(fri["final_coeffs"], final_size, "final_coeffs")
    if any(c != (0, 0, 0, 0) for c in final[final_size >> lb:]):
        raise Rejected("the final polynomial is over the degree bound")
    for c in final:
        ts.absorb(c)
    if not ts.check_work(int(fri.get("pow_nonce", 0)),
                         int(params["grinding_bits"])):
        raise Rejected("the proof-of-work nonce fails")
    indices = [ts.sample() & (half - 1) for _ in range(queries)]
    if len(fri["queries"]) != queries or len(proof["openings"]) != queries:
        raise Rejected("wrong number of queries")

    # ---- the trace and quotient openings, and the DEEP values ------------
    points = indices + [q + half for q in indices]      # lo, then hi
    t_rows = _field_array(
        [e["trace_lo"] for e in proof["openings"]]
        + [e["trace_hi"] for e in proof["openings"]],
        (2 * queries, w), "trace rows")
    q_rows = _field_array(
        [e["quotient_lo"] for e in proof["openings"]]
        + [e["quotient_hi"] for e in proof["openings"]],
        (2 * queries, 4 * blowup), "quotient rows")
    check_openings(proof["trace_root"], points, t_rows,
                   [e["trace_lo_path"] for e in proof["openings"]]
                   + [e["trace_hi_path"] for e in proof["openings"]],
                   log_big, "trace")
    check_openings(proof["quotient_root"], points, q_rows,
                   [e["quotient_lo_path"] for e in proof["openings"]]
                   + [e["quotient_hi_path"] for e in proof["openings"]],
                   log_big, "quotient")
    g_pow = [(1, 0, 0, 0)]
    for _ in range(2 * w + blowup - 1):
        g_pow.append(e_mul(g_pow[-1], gamma))
    zero = (0, 0, 0, 0)
    # sum_j gamma^j (row[j] - opened[j]) splits into a part of the row
    # and a part of the openings alone
    c1 = c2 = cq = zero
    for j in range(w):
        c1 = e_add(c1, e_mul(g_pow[j], at_z[j]))
        c2 = e_add(c2, e_mul(g_pow[w + j], at_zg[j]))
    for i in range(blowup):
        cq = e_add(cq, e_mul(g_pow[2 * w + i], q_at_z[i]))
    a1 = _gamma_sum(t_rows, g_pow[:w])
    a2 = _gamma_sum(t_rows, g_pow[w:2 * w])
    g_big, g_n = root_of_unity(log_big), root_of_unity(log_n)
    zeta_g = e_scale(zeta, g_n)
    deep = []
    for k, idx in enumerate(points):
        x = (GENERATOR * pow(g_big, idx, P) % P, 0, 0, 0)
        aq = zero
        for i in range(blowup):
            aq = e_add(aq, e_mul(g_pow[2 * w + i], tuple(
                int(v) for v in q_rows[k, 4 * i:4 * i + 4])))
        at_x = e_mul(e_inv(e_sub(x, zeta)),
                     e_sub(e_add(a1[k], aq), e_add(c1, cq)))
        deep.append(e_add(at_x, e_mul(e_inv(e_sub(x, zeta_g)),
                                      e_sub(a2[k], c2))))

    # ---- FRI: openings, folds, the final polynomial ----------------------
    values = np.zeros((layers, queries, 2 * 4), dtype=np.uint64)
    for qi, per_layer in enumerate(fri["queries"]):
        if len(per_layer) != layers:
            raise Rejected("a query does not open every FRI layer")
        values[:, qi, :] = _field_array(
            [list(o["values"][0]) + list(o["values"][1])
             for o in per_layer], (layers, 2 * 4), "FRI values")
    at = list(indices)           # each query's index in the current layer
    carried: list = [None] * queries
    shift, inv2 = GENERATOR, inv(2)
    for k in range(layers):
        log_k = log_big - k
        half_k = 1 << (log_k - 1)
        pair = [i % half_k for i in at]
        check_openings(roots[k], pair, values[k],
                       [fri["queries"][qi][k]["path"]
                        for qi in range(queries)],
                       log_k - 1, f"FRI layer {k}")
        g_k = root_of_unity(log_k)
        for qi in range(queries):
            lo = tuple(int(v) for v in values[k, qi, :4])
            hi = tuple(int(v) for v in values[k, qi, 4:])
            if k == 0:
                if lo != deep[qi] or hi != deep[queries + qi]:
                    raise Rejected("a DEEP value differs from FRI's "
                                   "first layer")
            elif (lo if at[qi] < half_k else hi) != carried[qi]:
                raise Rejected(f"a fold differs entering FRI layer {k}")
            x = shift * pow(g_k, pair[qi], P) % P
            carried[qi] = e_add(
                e_scale(e_add(lo, hi), inv2),
                e_mul(betas[k], e_scale(e_sub(lo, hi), inv2 * inv(x) % P)))
        at = pair
        shift = shift * shift % P
    g_f = root_of_unity(log_big - layers)
    for qi in range(queries):
        if e_horner(final, shift * pow(g_f, at[qi], P) % P) != carried[qi]:
            raise Rejected("the last fold differs from the final "
                           "polynomial")
    return {"width": w, "log_n": log_n, "queries": queries,
            "fri_layers": layers}


def judge_proof(proof: dict, starks: dict, params: dict) -> list[str]:
    """What the reference has against one batch proof: one line for
    each of the configuration's STARKs (`starks`: key of the proof ->
    {"width", "log_n"}) that it rejects or finds at another size."""
    against = []
    for key, size in starks.items():
        try:
            shape = check_stark(proof[key], params)
        except Rejected as exc:
            against.append(f"{key}: {exc}")
            continue
        except (KeyError, TypeError):
            against.append(f"{key}: not in the proof")
            continue
        for name in ("width", "log_n"):
            if shape[name] != size[name]:
                against.append(f"{key}: {name} {shape[name]}, the "
                               f"configuration states {size[name]}")
    return against
