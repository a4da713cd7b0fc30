"""NTT / LDE vs a naive O(n^2) host DFT."""

import numpy as np
import jax.numpy as jnp
import pytest

from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.ops import ntt

RNG = np.random.default_rng(1)


def _naive_dft(x, root):
    n = len(x)
    w = [pow(root, i, bb.P) for i in range(n)]
    return np.array(
        [sum(int(x[j]) * w[(i * j) % n] for j in range(n)) % bb.P for i in range(n)],
        dtype=np.uint32,
    )


def test_ntt_matches_naive():
    for log_n in (1, 3, 6):
        n = 1 << log_n
        x = RNG.integers(0, bb.P, size=n, dtype=np.uint32)
        root = bb.root_of_unity(log_n)
        expect = _naive_dft(x, root)
        got = np.asarray(bb.from_mont(ntt.ntt(bb.to_mont(jnp.asarray(x)))))
        np.testing.assert_array_equal(got, expect)


def test_ntt_roundtrip_batched():
    x = RNG.integers(0, bb.P, size=(5, 256), dtype=np.uint32)
    xm = bb.to_mont(jnp.asarray(x))
    back = np.asarray(bb.from_mont(ntt.intt(ntt.ntt(xm))))
    np.testing.assert_array_equal(back, x)


def test_coset_lde_extends_polynomial():
    # LDE of degree<n evals must agree with direct evaluation on the coset
    log_n, log_blowup = 4, 2
    n = 1 << log_n
    coeffs = RNG.integers(0, bb.P, size=n, dtype=np.uint32)

    def horner(cs, x):
        acc = 0
        for c in reversed([int(v) for v in cs]):
            acc = (acc * x + c) % bb.P
        return acc

    root = bb.root_of_unity(log_n)
    evals = np.array(
        [horner(coeffs, pow(root, i, bb.P)) for i in range(n)], dtype=np.uint32
    )
    got = np.asarray(
        bb.from_mont(ntt.coset_lde(bb.to_mont(jnp.asarray(evals)), log_blowup))
    )
    big_root = bb.root_of_unity(log_n + log_blowup)
    shift = bb.GENERATOR
    expect = np.array(
        [
            horner(coeffs, shift * pow(big_root, i, bb.P) % bb.P)
            for i in range(n << log_blowup)
        ],
        dtype=np.uint32,
    )
    np.testing.assert_array_equal(got, expect)


def test_eval_poly_at():
    coeffs = RNG.integers(0, bb.P, size=33, dtype=np.uint32)
    pt = 123456789
    got = int(
        bb.from_mont(
            ntt.eval_poly_at(
                bb.to_mont(jnp.asarray(coeffs)),
                bb.to_mont(jnp.asarray(np.uint32(pt))),
            )
        )
    )
    acc = 0
    for c in reversed([int(v) for v in coeffs]):
        acc = (acc * pt + c) % bb.P
    assert got == acc


def _interpolate_by_table(values):
    """`interpolate_host` as it was: one (p, p) table product."""
    p_len = len(values)
    w_inv = bb.inv_host(bb.root_of_unity(p_len.bit_length() - 1))
    idx = np.arange(p_len, dtype=np.int64)
    table = bb.powers_host(w_inv, p_len).astype(np.uint64)[
        np.outer(idx, idx) % p_len]
    acc = (table * (np.asarray(values, dtype=np.uint64) % bb.P)[None, :]
           % bb.P).sum(axis=1) % bb.P
    return (acc * bb.inv_host(p_len) % bb.P).astype(np.uint32)


@pytest.mark.parametrize("log_p", [0, 1, 2, 5, 9, 10])
def test_interpolate_host_matches_the_table_form(log_p):
    """Periodic columns' coefficients are proof bytes' ancestors: the
    O(p log p) transform gives what the table product gave."""
    vals = RNG.integers(0, bb.P, size=1 << log_p, dtype=np.uint32)
    np.testing.assert_array_equal(ntt.interpolate_host(vals),
                                  _interpolate_by_table(vals))
    assert ntt.interpolate_host(vals).dtype == np.uint32


def test_interpolate_host_at_a_trace_length_column():
    """`sel_first` has the trace's own length (2^16 rows for the state
    circuit of a `prove-erc20` batch): the table form took 8 p^2 bytes,
    32 GiB here, several times over, and ran the chip's 40 GiB host out
    of memory (PR 29, chip call 1).  An indicator of row 0 interpolates
    to the constant 1/p in every coefficient."""
    import tracemalloc

    first = np.zeros(1 << 16, dtype=np.uint32)
    first[0] = 1
    tracemalloc.start()
    coeffs = ntt.interpolate_host(first)
    peak = tracemalloc.get_traced_memory()[1]
    tracemalloc.stop()
    assert set(coeffs.tolist()) == {bb.inv_host(1 << 16)}
    assert peak < 64 * (1 << 16)        # a few arrays of p words
    with pytest.raises(ValueError):
        ntt.interpolate_host(np.zeros(12, dtype=np.uint32))
