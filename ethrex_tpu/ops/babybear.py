"""BabyBear prime field arithmetic as uint32 JAX ops.

This is the scalar substrate for the TPU STARK prover (the equivalent of the
field arithmetic that the reference's zkVM SDKs run on CUDA; see SURVEY.md §2.6
and /root/reference/crates/prover — the reference delegates BabyBear NTT /
Poseidon2 / FRI to SP1's GPU kernels, we implement them natively for TPU).

Design notes (TPU-first):
  * Elements live in uint32 lanes in **Montgomery form** (R = 2^32).  The VPU
    has native 32-bit integer multiply (low 32 bits, wrapping); the missing
    `mulhi` is emulated with four 16x16 partial products.  One field mul is
    ~11 VPU multiplies — entirely element-wise, so XLA fuses chains of field
    ops into single kernels and the MXU stays free for the matmul-form NTT.
  * All functions are shape-polymorphic and jit-safe (no data-dependent
    control flow; exponents are static Python ints unrolled at trace time).
"""

from __future__ import annotations

import numpy as np
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Field constants (computed with Python bignums at import time)
# ---------------------------------------------------------------------------

P = 2013265921  # 15 * 2^27 + 1
TWO_ADICITY = 27
GENERATOR = 31  # multiplicative generator of F_p^*

_R = (1 << 32) % P          # Montgomery radix R = 2^32 mod p
_R2 = (_R * _R) % P         # R^2 mod p  (to_mont multiplier)
_NP = (-pow(P, -1, 1 << 32)) % (1 << 32)  # -p^{-1} mod 2^32

# order-2^27 root of unity and its inverse
_ROOT = pow(GENERATOR, (P - 1) >> TWO_ADICITY, P)
_ROOT_INV = pow(_ROOT, P - 2, P)

U32 = jnp.uint32
P_U32 = np.uint32(P)
NP_U32 = np.uint32(_NP)
R_U32 = np.uint32(_R)
R2_U32 = np.uint32(_R2)

MONT_ONE = np.uint32(_R)   # 1 in Montgomery form
MONT_ZERO = np.uint32(0)


def _u32(x):
    return jnp.asarray(x, dtype=U32)


# ---------------------------------------------------------------------------
# 32x32 -> 64 multiply emulation (TPU has wrapping 32-bit mul, no mulhi)
# ---------------------------------------------------------------------------

def mulhi_u32(a, b):
    """High 32 bits of the 64-bit product of two uint32 arrays."""
    a = _u32(a)
    b = _u32(b)
    mask = np.uint32(0xFFFF)
    a_lo = a & mask
    a_hi = a >> 16
    b_lo = b & mask
    b_hi = b >> 16
    ll = a_lo * b_lo          # < 2^32, exact in uint32
    lh = a_lo * b_hi          # < 2^32
    hl = a_hi * b_lo          # < 2^32
    hh = a_hi * b_hi          # < 2^32
    # carry out of bits [16,32) of the full product
    mid = (ll >> 16) + (lh & mask) + (hl & mask)   # <= 3*(2^16-1): fits
    return hh + (lh >> 16) + (hl >> 16) + (mid >> 16)


def mullo_u32(a, b):
    return _u32(a) * _u32(b)  # uint32 wraps mod 2^32


# ---------------------------------------------------------------------------
# Montgomery arithmetic
# ---------------------------------------------------------------------------

def mont_mul(a, b):
    """Montgomery product: returns a*b*R^{-1} mod p, inputs/outputs < p."""
    a = _u32(a)
    b = _u32(b)
    lo = a * b
    hi = mulhi_u32(a, b)
    m = lo * NP_U32
    mp_hi = mulhi_u32(m, P_U32)
    # x + m*p == 0 (mod 2^32); carry into the high word iff lo != 0
    carry = (lo != 0).astype(U32)
    t = hi + mp_hi + carry        # < 2p, no uint32 overflow since p < 2^31
    return jnp.where(t >= P_U32, t - P_U32, t)


def mont_sqr(a):
    return mont_mul(a, a)


def add(a, b):
    s = _u32(a) + _u32(b)
    return jnp.where(s >= P_U32, s - P_U32, s)


def sub(a, b):
    a = _u32(a)
    b = _u32(b)
    return jnp.where(a >= b, a - b, a + P_U32 - b)


def neg(a):
    a = _u32(a)
    return jnp.where(a == 0, a, P_U32 - a)


def to_mont(a):
    """Canonical uint32 (< p) -> Montgomery form."""
    return mont_mul(a, R2_U32)


def from_mont(a):
    """Montgomery form -> canonical uint32 (< p)."""
    return mont_mul(a, np.uint32(1))


def mont_pow(a, e: int):
    """a^e for a *static* Python-int exponent (unrolled square & multiply)."""
    if e < 0:
        raise ValueError("negative exponent; use mont_inv")
    result = jnp.full_like(_u32(a), MONT_ONE)
    base = _u32(a)
    while e:
        if e & 1:
            result = mont_mul(result, base)
        e >>= 1
        if e:
            base = mont_sqr(base)
    return result


def mont_inv(a):
    """Field inverse via Fermat (a^{p-2}); a must be nonzero."""
    return mont_pow(a, P - 2)


def batch_mont_inv(a):
    """Montgomery-trick batch inverse along a flat array (one mont_inv total).

    inv(a_i) = total_inv * prefix_excl_i * suffix_excl_i, with both exclusive
    products computed as log-depth associative scans (XLA-friendly; no
    sequential lax.scan on the hot path).
    """
    import jax

    a = _u32(a)
    flat = a.reshape(-1)
    prefix = jax.lax.associative_scan(mont_mul, flat)           # inclusive
    suffix = jax.lax.associative_scan(mont_mul, flat, reverse=True)
    one = jnp.array([MONT_ONE], dtype=U32)
    prefix_excl = jnp.concatenate([one, prefix[:-1]])
    suffix_excl = jnp.concatenate([suffix[1:], one])
    total_inv = mont_inv(prefix[-1])
    invs = mont_mul(mont_mul(prefix_excl, suffix_excl), total_inv)
    return invs.reshape(a.shape)


def sum_mod(x, axis: int = -1):
    """Mod-p sum along `axis` via log-depth pairwise folding (uint32-safe)."""
    x = jnp.moveaxis(_u32(x), axis, -1)
    while x.shape[-1] > 1:
        n = x.shape[-1]
        if n & 1:
            pad = [(0, 0)] * (x.ndim - 1) + [(0, 1)]
            x = jnp.pad(x, pad)
            n += 1
        x = add(x[..., : n // 2], x[..., n // 2:])
    return x[..., 0]


# ---------------------------------------------------------------------------
# MXU modular matmul (8-bit-limb bf16 matmuls, exact f32 accumulation)
# ---------------------------------------------------------------------------

_LIMBS = 4          # 4 x 8-bit limbs cover p < 2^31
_CHUNK = 128        # max contraction length per f32 accumulation:
#                     128 * 255^2 = 8.3e6 < 2^24 keeps every partial sum
#                     exactly representable in f32 (MXU accumulates f32)


def mod_matmul(a, b, montgomery: bool = True):
    """Exact modular matmul `a @ b mod p` on the MXU.

    a: (..., n, k), b: (k, m), both uint32 arrays of field elements < p.
    Splits each operand into 4 8-bit limbs (bf16 — integers <= 255 are
    exact), runs the 16 limb matmuls on the MXU with f32 accumulation
    (contraction chunked to 128 so every partial product sum stays below
    2^24, the f32 exact-integer bound), then recombines the 7 diagonal
    sums mod p on the VPU.

    With montgomery=True (the default), inputs are Montgomery-form and so
    is the result: the recombination constants absorb the extra R factor
    (sum aR*bR = R^2*sum ab; folding 2^{8s} in CANONICAL form through
    mont_mul strips one R).  With montgomery=False all values are
    canonical and the result is the plain modular product.

    This is the building block for the DEEP gamma-contraction, the
    blocked zeta evaluation, and the radix-128 matmul NTT — the work the
    reference's prover does in CUDA kernels (SURVEY.md §2.6) mapped onto
    the TPU's systolic array instead.
    """
    a = _u32(a)
    b = _u32(b)
    k = a.shape[-1]
    if b.shape[0] != k:
        raise ValueError(f"contraction mismatch: {a.shape} @ {b.shape}")
    a_limbs = [((a >> (8 * i)) & np.uint32(0xFF)).astype(jnp.bfloat16)
               for i in range(_LIMBS)]
    b_limbs = [((b >> (8 * j)) & np.uint32(0xFF)).astype(jnp.bfloat16)
               for j in range(_LIMBS)]

    n_chunks = (k + _CHUNK - 1) // _CHUNK
    # int32 diagonal accumulators: each partial matmul entry < 128*255^2
    # (~2^23) and up to 4 limb pairs land on one diagonal, so up to 64
    # chunks (4 * 64 * 8_323_200 < 2^31) accumulate exactly before the
    # running total must fold into the mod-p accumulator.
    max_group = (1 << 31) // (_LIMBS * 8_323_200)  # 64 chunks

    out = None
    diag = [None] * (2 * _LIMBS - 1)
    chunks_in_diag = 0

    def flush(diag, out):
        for s, c in enumerate(diag):
            if c is None:
                continue
            c = c.astype(jnp.uint32)
            c = jnp.where(c >= P_U32, c - P_U32, c)  # c < 2^31 < 2p
            if montgomery:
                t_s = np.uint32((1 << (8 * s)) % P)       # canonical
            else:
                t_s = np.uint32(int(to_mont_host((1 << (8 * s)) % P)))
            term = mont_mul(c, t_s)
            out = term if out is None else add(out, term)
        return out

    for ci in range(n_chunks):
        sl = slice(ci * _CHUNK, min((ci + 1) * _CHUNK, k))
        for i in range(_LIMBS):
            for j in range(_LIMBS):
                pp = jnp.matmul(
                    a_limbs[i][..., sl], b_limbs[j][sl, :],
                    preferred_element_type=jnp.float32).astype(jnp.int32)
                s = i + j
                diag[s] = pp if diag[s] is None else diag[s] + pp
        chunks_in_diag += 1
        if chunks_in_diag >= max_group:
            out = flush(diag, out)
            diag = [None] * (2 * _LIMBS - 1)
            chunks_in_diag = 0
    return flush(diag, out)


# ---------------------------------------------------------------------------
# Roots of unity / domain helpers (host-side bignum, device arrays out)
# ---------------------------------------------------------------------------

def root_of_unity(log_n: int) -> int:
    """Canonical (non-Montgomery) primitive 2^log_n-th root of unity."""
    if log_n > TWO_ADICITY:
        raise ValueError(f"2-adicity exceeded: {log_n} > {TWO_ADICITY}")
    return pow(_ROOT, 1 << (TWO_ADICITY - log_n), P)


def pow_host(base: int, e: int) -> int:
    return pow(base, e, P)


def inv_host(a: int) -> int:
    return pow(a, P - 2, P)


def batch_inv_host(a: np.ndarray) -> np.ndarray:
    """Elementwise a^(P-2) mod P on the host: canonical uint32 in and
    out, exact in uint64 (P < 2^31, so every product fits)."""
    base = np.asarray(a, dtype=np.uint64) % P
    out = np.ones_like(base)
    e = P - 2
    while e:
        if e & 1:
            out = out * base % P
        base = base * base % P
        e >>= 1
    return out.astype(np.uint32)


def powers_host(base: int, n: int) -> np.ndarray:
    """[1, base, base^2, ...] canonical, as numpy uint32 (host precompute)."""
    out = np.empty(n, dtype=np.uint32)
    acc = 1
    for i in range(n):
        out[i] = acc
        acc = (acc * base) % P
    return out


def to_mont_host(a: np.ndarray | int):
    """Host-side canonical -> Montgomery (numpy)."""
    return ((np.asarray(a, dtype=np.uint64) * _R) % P).astype(np.uint32)


def from_mont_host(a: np.ndarray | int):
    rinv = pow(_R, P - 2, P)
    return ((np.asarray(a, dtype=np.uint64) * rinv) % P).astype(np.uint32)
