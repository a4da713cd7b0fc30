"""The least bytes a kernel has to move through HBM, computed from its
shapes.  A roofline share is these bytes over the chip's peak bandwidth
(peaks.json) over the device time the profiler trace gives the kernel's
program.  Bytes only: what the result needs read once and written once,
whatever algorithm computes it, so a share can never honestly pass 100%.
"""

from __future__ import annotations

WORD = 4            # BabyBear elements travel as uint32
DIGEST_WORDS = 8    # a Poseidon2 digest is 8 field elements


def commit_phase_bytes(width: int, log_n: int, log_blowup: int) -> int:
    """The commit phase of one STARK (stark/prover.py `phase_commit`:
    low-degree extension of the trace, then its Merkle tree).

      read   the trace once                       width * n words
      write  the extended trace once              width * N words
      read   the extended trace once for leaves   width * N words
      write  the digests: N leaves and the N - 1 nodes above them

    with n = 2^log_n rows and N = n << log_blowup.  Not counted, because
    an ideal implementation need not move them: the row-major copy the
    program also returns, twiddle tables, and re-reads of digests while
    the upper tree levels are hashed (under 1% of the total)."""
    n = 1 << log_n
    big = n << log_blowup
    words = width * n + 2 * width * big + DIGEST_WORDS * (2 * big - 1)
    return words * WORD


FUNCTIONS = {"commit_phase_bytes": commit_phase_bytes}
