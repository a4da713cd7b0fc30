"""Keccak-256 — native C implementation via ctypes, pure-Python fallback.

Mirrors the role of the reference's crypto keccak backends (assembly on
x86/ARM, crates/common/crypto/keccak/); here a -O3 C file compiled on first
use (g++ is in the image), with a spec-derived Python fallback so nothing
hard-fails without a toolchain.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "native")
_SO_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libkeccak.so"))
_SRC_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "keccak.c"))

_lib = None
_lock = threading.Lock()


def _load_native():
    global _lib
    if _lib is not None:  # lock-free fast path once resolved (hot callers)
        return _lib
    with _lock:
        if _lib is not None:
            return _lib
        def build():
            # into a file of this process's own, then renamed: a process
            # that loads the library never sees another one's half-written
            tmp = f"{_SO_PATH}.{os.getpid()}.tmp"
            subprocess.run(
                ["gcc", "-O3", "-shared", "-fPIC", "-o", tmp, _SRC_PATH],
                check=True, capture_output=True,
            )
            os.replace(tmp, _SO_PATH)

        def load():
            lib = ctypes.CDLL(_SO_PATH)
            lib.keccak256.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_char_p
            ]
            lib.keccak256.restype = None
            lib.keccak_grind.argtypes = [
                ctypes.c_char_p, ctypes.c_size_t, ctypes.c_uint
            ]
            lib.keccak_grind.restype = ctypes.c_uint64
            return lib

        try:
            if not os.path.exists(_SO_PATH) or (
                os.path.getmtime(_SRC_PATH) > os.path.getmtime(_SO_PATH)
            ):
                build()
            try:
                _lib = load()
            except OSError:
                # stale/foreign binary (different arch) — rebuild once
                build()
                _lib = load()
        except (OSError, subprocess.CalledProcessError):
            _lib = False  # sentinel: fall back to Python
        return _lib


def available() -> bool:
    """True when the native keccak engine loaded (every native wrapper
    exposes this probe; lint-enforced in tests/test_tooling.py)."""
    return bool(_load_native())


# ---------------------------------------------------------------------------
# Pure-Python fallback (from the Keccak spec)
# ---------------------------------------------------------------------------

_RC = [
    0x0000000000000001, 0x0000000000008082, 0x800000000000808A,
    0x8000000080008000, 0x000000000000808B, 0x0000000080000001,
    0x8000000080008081, 0x8000000000008009, 0x000000000000008A,
    0x0000000000000088, 0x0000000080008009, 0x000000008000000A,
    0x000000008000808B, 0x800000000000008B, 0x8000000000008089,
    0x8000000000008003, 0x8000000000008002, 0x8000000000000080,
    0x000000000000800A, 0x800000008000000A, 0x8000000080008081,
    0x8000000000008080, 0x0000000080000001, 0x8000000080008008,
]
_ROT = [1, 3, 6, 10, 15, 21, 28, 36, 45, 55, 2, 14,
        27, 41, 56, 8, 25, 43, 62, 18, 39, 61, 20, 44]
_PILN = [10, 7, 11, 17, 18, 3, 5, 16, 8, 21, 24, 4,
         15, 23, 19, 13, 12, 2, 20, 14, 22, 9, 6, 1]
_M = (1 << 64) - 1


def _rotl(x, n):
    return ((x << n) | (x >> (64 - n))) & _M


def _f1600(st):
    for rc in _RC:
        bc = [st[i] ^ st[i + 5] ^ st[i + 10] ^ st[i + 15] ^ st[i + 20]
              for i in range(5)]
        for i in range(5):
            t = bc[(i + 4) % 5] ^ _rotl(bc[(i + 1) % 5], 1)
            for j in range(0, 25, 5):
                st[j + i] ^= t
        t = st[1]
        for i in range(24):
            j = _PILN[i]
            st[j], t = _rotl(t, _ROT[i]), st[j]
        for j in range(0, 25, 5):
            row = st[j:j + 5]
            for i in range(5):
                st[j + i] = row[i] ^ ((~row[(i + 1) % 5]) & row[(i + 2) % 5]) & _M
        st[0] ^= rc
    return st


def _keccak256_py(data: bytes) -> bytes:
    rate = 136
    st = [0] * 25
    pad_len = rate - (len(data) % rate)
    padded = data + b"\x01" + b"\x00" * (pad_len - 2) + b"\x80" \
        if pad_len >= 2 else data + b"\x81"
    for off in range(0, len(padded), rate):
        block = padded[off:off + rate]
        for i in range(rate // 8):
            st[i] ^= int.from_bytes(block[8 * i:8 * i + 8], "little")
        _f1600(st)
    return b"".join(st[i].to_bytes(8, "little") for i in range(4))


class IncrementalKeccak256:
    """Streaming keccak-256: absorb incrementally, snapshot digests in O(1)
    amortized per byte (used by the RLPx egress/ingress frame MACs)."""

    RATE = 136

    def __init__(self):
        self._state = [0] * 25
        self._buf = b""

    def update(self, data: bytes):
        self._buf += data
        while len(self._buf) >= self.RATE:
            block = self._buf[:self.RATE]
            self._buf = self._buf[self.RATE:]
            for i in range(self.RATE // 8):
                self._state[i] ^= int.from_bytes(
                    block[8 * i:8 * i + 8], "little")
            _f1600(self._state)

    def digest(self) -> bytes:
        state = list(self._state)
        block = self._buf + b"\x01" + b"\x00" * (
            self.RATE - len(self._buf) - 1)
        block = block[:-1] + bytes([block[-1] | 0x80])
        for i in range(self.RATE // 8):
            state[i] ^= int.from_bytes(block[8 * i:8 * i + 8], "little")
        _f1600(state)
        return b"".join(state[i].to_bytes(8, "little") for i in range(4))


def keccak256(data: bytes) -> bytes:
    lib = _load_native()
    if lib:
        out = ctypes.create_string_buffer(32)
        lib.keccak256(bytes(data), len(data), out)
        return out.raw
    return _keccak256_py(bytes(data))


def grind(seed: bytes, bits: int) -> int | None:
    """The smallest nonce whose keccak256(seed || nonce_le8) has `bits`
    leading zero bits, searched in one native call that releases the
    interpreter lock (ctypes does so around every call): other threads
    run while it searches.  None where the native engine is absent."""
    lib = _load_native()
    if not lib or len(seed) > 127 or not 0 < bits <= 64:
        return None
    return int(lib.keccak_grind(bytes(seed), len(seed), bits))


EMPTY_KECCAK = keccak256(b"")  # hash of empty bytes
