"""BabyBear NTT / iNTT / coset LDE over the last axis, jit-safe.

TPU-native replacement for the LDE/NTT stage the reference delegates to SP1's
CUDA kernels (SURVEY.md §2.6, §5 "long-context" note: LDE/NTT sharded along
rows with collectives for transposes — the sharded wrapper lives in
ethrex_tpu/parallel/).

Implementation: iterative radix-2 Cooley-Tukey, stages unrolled at trace time
(log2(n) static).  Each stage is a fully vectorized butterfly over the whole
array — element-wise VPU work that XLA fuses; no data-dependent shapes.
Twiddles are precomputed host-side per (log_n) and closed over as constants in
Montgomery form.
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from . import babybear as bb


@functools.lru_cache(maxsize=None)
def _bitrev_perm(log_n: int) -> np.ndarray:
    n = 1 << log_n
    idx = np.arange(n)
    rev = np.zeros(n, dtype=np.int32)
    for b in range(log_n):
        rev |= ((idx >> b) & 1) << (log_n - 1 - b)
    return rev


@functools.lru_cache(maxsize=None)
def _stage_twiddles(log_n: int, inverse: bool) -> tuple[np.ndarray, ...]:
    """Montgomery twiddles for each DIT stage s: w_{2^{s+1}}^j, j<2^s."""
    root = bb.root_of_unity(log_n)
    if inverse:
        root = bb.inv_host(root)
    tw = []
    for s in range(log_n):
        m = 1 << (s + 1)
        w_m = pow(root, (1 << log_n) // m, bb.P)
        tw.append(bb.to_mont_host(bb.powers_host(w_m, m // 2)))
    return tuple(tw)


@functools.partial(jax.jit, static_argnames=("inverse",))
def ntt(x, inverse: bool = False):
    """In-order NTT (or iNTT) over the last axis.  x: uint32 Montgomery form.

    Length of the last axis must be a power of two.  iNTT includes the 1/n
    scaling.
    """
    n = x.shape[-1]
    log_n = n.bit_length() - 1
    if 1 << log_n != n:
        raise ValueError(f"NTT size must be a power of 2, got {n}")
    if log_n == 0:
        return x
    perm = _bitrev_perm(log_n)
    x = x[..., perm]
    twiddles = _stage_twiddles(log_n, inverse)
    batch = x.shape[:-1]
    for s in range(log_n):
        half = 1 << s
        m = half * 2
        w = jnp.asarray(twiddles[s])                      # (half,)
        xs = x.reshape(batch + (n // m, m))
        u = xs[..., :half]
        t = bb.mont_mul(xs[..., half:], w)
        x = jnp.concatenate([bb.add(u, t), bb.sub(u, t)], axis=-1)
        x = x.reshape(batch + (n,))
    if inverse:
        n_inv = bb.to_mont_host(bb.inv_host(n))
        x = bb.mont_mul(x, jnp.asarray(np.uint32(n_inv)))
    return x


def intt(x):
    return ntt(x, inverse=True)


@functools.lru_cache(maxsize=None)
def _coset_powers(log_n: int, shift: int) -> np.ndarray:
    return bb.to_mont_host(bb.powers_host(shift, 1 << log_n))


@functools.partial(jax.jit, static_argnames=("log_blowup", "shift"))
def coset_lde(x, log_blowup: int, shift: int = bb.GENERATOR):
    """Low-degree extension onto a shifted coset of size n * 2^log_blowup.

    x: evaluations over the size-n subgroup (Montgomery).  Returns evaluations
    over the coset shift*H' where |H'| = n << log_blowup, in natural order.
    """
    n = x.shape[-1]
    log_n = n.bit_length() - 1
    coeffs = intt(x)
    # scale coefficient i by shift^i, then zero-pad to the extended size
    sh = jnp.asarray(_coset_powers(log_n, shift % bb.P))
    coeffs = bb.mont_mul(coeffs, sh)
    pad = [(0, 0)] * (coeffs.ndim - 1) + [(0, (n << log_blowup) - n)]
    coeffs = jnp.pad(coeffs, pad)
    return ntt(coeffs)


@functools.lru_cache(maxsize=None)
def _coset_inv_powers(log_n: int, shift: int) -> np.ndarray:
    return bb.to_mont_host(bb.powers_host(bb.inv_host(shift), 1 << log_n))


@functools.partial(jax.jit, static_argnames=("shift",))
def coset_intt(x, shift: int = bb.GENERATOR):
    """Evaluations over the coset shift*H (natural order) -> coefficients."""
    n = x.shape[-1]
    log_n = n.bit_length() - 1
    coeffs = ntt(x, inverse=True)
    inv_sh = jnp.asarray(_coset_inv_powers(log_n, shift % bb.P))
    return bb.mont_mul(coeffs, inv_sh)


@functools.partial(jax.jit, static_argnames=("n_out", "shift"))
def coset_evals_from_coeffs(coeffs, n_out: int, shift: int = bb.GENERATOR):
    """Coefficient vector (..., m), m <= n_out -> evals on coset shift*H',
    |H'| = n_out, natural order."""
    m = coeffs.shape[-1]
    log_out = n_out.bit_length() - 1
    sh = jnp.asarray(_coset_powers(log_out, shift % bb.P))[:m]
    coeffs = bb.mont_mul(coeffs, sh)
    pad = [(0, 0)] * (coeffs.ndim - 1) + [(0, n_out - m)]
    return ntt(jnp.pad(coeffs, pad))


def interpolate_host(values: np.ndarray) -> np.ndarray:
    """Canonical host interpolation: evaluations over the size-p subgroup
    (natural order) -> coefficient vector.  A radix-2 inverse DFT in
    numpy uint64, O(p log p) time and O(p) memory: an AIR's `sel_first`
    column has the trace's own length, and the (p, p) table this once
    built took 8 p^2 bytes several times over (2 GiB a table at 2^14
    rows, 32 GiB at 2^16)."""
    p_len = len(values)
    log_p = p_len.bit_length() - 1
    if 1 << log_p != p_len:
        raise ValueError("periodic length must be a power of two")
    w_inv = bb.inv_host(bb.root_of_unity(log_p))
    n_inv = bb.inv_host(p_len)
    # out[k] = n^-1 * sum_i vals[i] * w^(-ik): decimation in time over
    # the bit-reversed input; every product is of two values below 2^31
    idx = np.arange(p_len)
    rev = np.zeros(p_len, dtype=np.int64)
    for bit in range(log_p):
        rev |= ((idx >> bit) & 1) << (log_p - 1 - bit)
    acc = (np.asarray(values, dtype=np.uint64) % bb.P)[rev]
    for stage in range(1, log_p + 1):
        half = 1 << (stage - 1)
        twiddles = bb.powers_host(
            pow(w_inv, p_len >> stage, bb.P), half).astype(np.uint64)
        acc = acc.reshape(-1, 2 * half)
        lo, hi = acc[:, :half], acc[:, half:] * twiddles % bb.P
        acc = np.concatenate([(lo + hi) % bb.P, (lo + bb.P - hi) % bb.P],
                             axis=1)
    return (acc.reshape(-1) * n_inv % bb.P).astype(np.uint32)


def domain_points(log_size: int, shift: int) -> np.ndarray:
    """Canonical evaluation-domain points shift * g^i (host numpy)."""
    g = bb.root_of_unity(log_size)
    pts = bb.powers_host(g, 1 << log_size).astype(np.uint64)
    return ((pts * (shift % bb.P)) % bb.P).astype(np.uint32)


def eval_poly_at(coeffs, point):
    """Horner evaluation of a coefficient vector (Montgomery) at a scalar.

    coeffs: (..., n) Montgomery; point: scalar uint32 Montgomery.
    Sequential in n — host/verifier-side helper, not a prover hot path.
    """

    def body(acc, c):
        return bb.add(bb.mont_mul(acc, point), c), None

    rev = jnp.moveaxis(coeffs, -1, 0)[::-1]
    acc0 = jnp.zeros(coeffs.shape[:-1], dtype=jnp.uint32)
    acc, _ = jax.lax.scan(body, acc0, rev)
    return acc
