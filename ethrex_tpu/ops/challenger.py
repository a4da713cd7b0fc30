"""Fiat-Shamir transcript: duplex Poseidon2 sponge over BabyBear (host side).

The transcript is inherently sequential (a few dozen absorb/sample calls per
proof), so it runs on the host with the host permutation (`permute_ref`:
native/poseidon2.c, Python where it cannot load; the same bits either way);
prover and verifier share this exact code, which is what makes the protocol
non-interactive and deterministic.
"""

from __future__ import annotations

import numpy as np

from . import babybear as bb
from . import poseidon2 as p2


class Challenger:
    def __init__(self, domain: bytes = b"ethrex-tpu/stark/v1"):
        self._state = [0] * p2.WIDTH
        self._absorb_pos = 0
        self._squeeze_pos = p2.RATE  # force permute before first sample
        # bind the domain tag
        seed = p2._sample_field_elems(domain, p2.RATE)
        self.absorb_elems([int(x) for x in seed])

    # -- absorbing ---------------------------------------------------------
    def absorb_elems(self, elems):
        """Absorb canonical base-field ints."""
        for e in elems:
            if self._absorb_pos == p2.RATE:
                self._state = p2.permute_ref(self._state)
                self._absorb_pos = 0
            self._state[self._absorb_pos] = (
                self._state[self._absorb_pos] + int(e)
            ) % bb.P
            self._absorb_pos += 1
        self._squeeze_pos = p2.RATE

    def absorb_digest(self, digest):
        """Absorb a device Merkle digest (Montgomery uint32[8])."""
        canon = bb.from_mont_host(np.asarray(digest))
        self.absorb_elems(int(x) for x in canon)

    def absorb_ext(self, x):
        self.absorb_elems(x)

    def absorb_int(self, v: int):
        """Absorb an unbounded non-negative int as 27-bit limbs."""
        limbs = []
        v = int(v)
        while True:
            limbs.append(v & ((1 << 27) - 1))
            v >>= 27
            if not v:
                break
        self.absorb_elems([len(limbs)] + limbs)

    # -- checkpoint/restore ------------------------------------------------
    # The sponge is the ONLY mutable prover state between device phases,
    # so a phase checkpoint (prover/checkpoint) that snapshots it can
    # resume the transcript mid-proof with every later challenge
    # bit-identical to an uninterrupted run.
    def state(self) -> dict:
        """Plain-data snapshot of the sponge (JSON/pickle-safe)."""
        return {"state": list(self._state),
                "absorb_pos": self._absorb_pos,
                "squeeze_pos": self._squeeze_pos}

    def restore(self, snap: dict) -> None:
        """Resume from a `state()` snapshot."""
        self._state = [int(x) for x in snap["state"]]
        self._absorb_pos = int(snap["absorb_pos"])
        self._squeeze_pos = int(snap["squeeze_pos"])

    # -- sampling ----------------------------------------------------------
    def sample(self) -> int:
        """Sample one canonical base-field element."""
        if self._squeeze_pos >= p2.RATE or self._absorb_pos > 0:
            self._state = p2.permute_ref(self._state)
            self._absorb_pos = 0
            self._squeeze_pos = 0
        out = self._state[self._squeeze_pos]
        self._squeeze_pos += 1
        return out

    def sample_ext(self) -> tuple:
        return tuple(self.sample() for _ in range(4))

    def sample_bits(self, bits: int) -> int:
        """Sample a uniform-ish integer in [0, 2^bits), bits <= 27."""
        assert bits <= 27
        return self.sample() & ((1 << bits) - 1)

    def sample_indices(self, bits: int, n: int) -> list[int]:
        return [self.sample_bits(bits) for _ in range(n)]

    # -- proof-of-work grinding -------------------------------------------
    # Adds `bits` bits of security against transcript-grinding attacks on
    # the query phase (see docs/SOUNDNESS.md): a nonce with
    # keccak256(seed || nonce) having `bits` leading zero bits is found by
    # the prover and bound into the transcript before query sampling.  The
    # seed is squeezed from the sponge, so the nonce commits to everything
    # absorbed so far; the 2^bits-hash search runs on keccak (C
    # extension, one call a search: `keccak.grind`), not on the Poseidon2
    # sponge.

    def _pow_seed(self) -> bytes:
        return b"".join(int(self.sample()).to_bytes(4, "little")
                        for _ in range(8))

    def grind(self, bits: int) -> int:
        """Find, absorb and return a proof-of-work nonce for `bits`."""
        if bits <= 0:
            return 0
        from ..crypto import keccak

        seed = self._pow_seed()
        nonce = keccak.grind(seed, bits)
        if nonce is None:       # no native engine: the same search here
            nonce = 0
            while not pow_ok(seed, nonce, bits):
                nonce += 1
        self.absorb_int(nonce)
        return nonce

    def check_grind(self, nonce: int, bits: int) -> bool:
        """Verify a grinding nonce.  Absorbs any well-formed (u64) nonce —
        pass or fail — so the transcript stays aligned with the prover;
        a structurally invalid nonce (out of u64 range) is rejected
        without absorbing, since no honest transcript can continue from
        it anyway.  The caller rejects on False."""
        if bits <= 0:
            return True
        nonce = int(nonce)
        if not (0 <= nonce < 1 << 64):
            return False
        seed = self._pow_seed()
        ok = pow_ok(seed, nonce, bits)
        self.absorb_int(nonce)
        return ok


def pow_ok(seed: bytes, nonce: int, bits: int) -> bool:
    """The grinding predicate — the ONE definition both prover and
    verifier (and tests) share: keccak256(seed || nonce_le8), read as a
    big-endian integer, has `bits` leading zero bits."""
    from ..crypto.keccak import keccak256

    return int.from_bytes(
        keccak256(seed + nonce.to_bytes(8, "little")), "big"
    ) < (1 << (256 - bits))
