"""The STARK's proof-of-work search in one native call (`keccak.grind`):
the same smallest nonce as the predicate searched in Python, so proofs
stay byte-identical, and the challenger's Python search where the native
engine is absent."""

from __future__ import annotations

import random

import pytest

from ethrex_tpu.crypto import keccak
from ethrex_tpu.ops.challenger import Challenger, pow_ok

pytestmark = pytest.mark.skipif(not keccak.available(),
                                reason="native keccak did not build")


def _search(seed: bytes, bits: int) -> int:
    nonce = 0
    while not pow_ok(seed, nonce, bits):
        nonce += 1
    return nonce


@pytest.mark.parametrize("seed_len", [0, 1, 7, 8, 9, 31, 32, 33, 120, 127])
def test_native_grind_is_the_smallest_nonce_of_the_predicate(seed_len):
    rng = random.Random(seed_len)
    for bits in (1, 5, 8, 11):
        seed = bytes(rng.randrange(256) for _ in range(seed_len))
        nonce = keccak.grind(seed, bits)
        assert nonce == _search(seed, bits)
        assert pow_ok(seed, nonce, bits)


@pytest.mark.parametrize("seed,bits", [(b"s" * 32, 0), (b"s" * 32, 65),
                                       (b"s" * 128, 8)])
def test_native_grind_declines_what_it_does_not_search(seed, bits):
    assert keccak.grind(seed, bits) is None


def test_challenger_grind_is_the_same_without_the_native_engine(
        monkeypatch):
    def transcript():
        ch = Challenger()
        ch.absorb_elems([3, 5, 8])
        nonce = ch.grind(12)
        return nonce, ch.sample()

    native = transcript()
    monkeypatch.setattr(keccak, "grind", lambda seed, bits: None)
    assert transcript() == native
