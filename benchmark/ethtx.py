"""Plain Ethereum primitives for the benchmark's traffic generator and
its reference: Keccak-256, RLP, secp256k1 signing (RFC 6979) and the
EIP-1559 transaction envelope.  Pure Python, standard library only —
nothing here imports the program under test, so a later PR that edits
`ethrex_tpu.primitives` or `ethrex_tpu.crypto` cannot move the yardstick.
"""

from __future__ import annotations

import hashlib
import hmac

# ---------------------------------------------------------------------------
# Keccak-256 (the pre-NIST padding Ethereum uses; hashlib's sha3_256 pads
# differently)

_MASK = (1 << 64) - 1
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
_ROT = [[0, 36, 3, 41, 18], [1, 44, 10, 45, 2], [62, 6, 43, 15, 61],
        [28, 55, 25, 21, 56], [27, 20, 39, 8, 14]]


def _rol(x: int, n: int) -> int:
    n %= 64
    return ((x << n) | (x >> (64 - n))) & _MASK if n else x


def _keccak_f(a: list) -> None:
    for rc in _RC:
        c = [a[x][0] ^ a[x][1] ^ a[x][2] ^ a[x][3] ^ a[x][4]
             for x in range(5)]
        d = [c[(x - 1) % 5] ^ _rol(c[(x + 1) % 5], 1) for x in range(5)]
        for x in range(5):
            for y in range(5):
                a[x][y] ^= d[x]
        b = [[0] * 5 for _ in range(5)]
        for x in range(5):
            for y in range(5):
                b[y][(2 * x + 3 * y) % 5] = _rol(a[x][y], _ROT[x][y])
        for x in range(5):
            for y in range(5):
                a[x][y] = b[x][y] ^ (~b[(x + 1) % 5][y] & b[(x + 2) % 5][y])
        a[0][0] ^= rc


def keccak256(data: bytes) -> bytes:
    rate = 136
    msg = bytearray(data)
    msg.append(0x01)
    msg.extend(b"\x00" * (-len(msg) % rate))
    msg[-1] |= 0x80
    a = [[0] * 5 for _ in range(5)]
    for off in range(0, len(msg), rate):
        for i in range(rate // 8):
            a[i % 5][i // 5] ^= int.from_bytes(
                msg[off + 8 * i: off + 8 * i + 8], "little")
        _keccak_f(a)
    return b"".join(a[i % 5][i // 5].to_bytes(8, "little")
                    for i in range(4))


# ---------------------------------------------------------------------------
# RLP

def int_bytes(v: int) -> bytes:
    return v.to_bytes((v.bit_length() + 7) // 8, "big") if v else b""


def _rlp_len(n: int, base: int) -> bytes:
    if n < 56:
        return bytes([base + n])
    nb = int_bytes(n)
    return bytes([base + 55 + len(nb)]) + nb


def rlp_encode(item) -> bytes:
    if isinstance(item, int):
        item = int_bytes(item)
    if isinstance(item, (bytes, bytearray)):
        item = bytes(item)
        if len(item) == 1 and item[0] < 0x80:
            return item
        return _rlp_len(len(item), 0x80) + item
    body = b"".join(rlp_encode(x) for x in item)
    return _rlp_len(len(body), 0xC0) + body


def rlp_decode(data: bytes):
    item, rest = _rlp_item(bytes(data))
    if rest:
        raise ValueError("trailing bytes after the RLP item")
    return item


def _rlp_item(data: bytes):
    if not data:
        raise ValueError("empty RLP")
    b0 = data[0]
    if b0 < 0x80:
        return data[:1], data[1:]
    if b0 < 0xB8:
        n = b0 - 0x80
        return data[1:1 + n], data[1 + n:]
    if b0 < 0xC0:
        ln = b0 - 0xB7
        n = int.from_bytes(data[1:1 + ln], "big")
        return data[1 + ln:1 + ln + n], data[1 + ln + n:]
    if b0 < 0xF8:
        n, off = b0 - 0xC0, 1
    else:
        ln = b0 - 0xF7
        n, off = int.from_bytes(data[1:1 + ln], "big"), 1 + ln
    body, rest = data[off:off + n], data[off + n:]
    if len(body) != n:
        raise ValueError("short RLP list")
    out = []
    while body:
        item, body = _rlp_item(body)
        out.append(item)
    return out, rest


# ---------------------------------------------------------------------------
# secp256k1

P = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEFFFFFC2F
N = 0xFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFFEBAAEDCE6AF48A03BBFD25E8CD0364141
G = (0x79BE667EF9DCBBAC55A06295CE870B07029BFCDB2DCE28D959F2815B16F81798,
     0x483ADA7726A3C4655DA4FBFC0E1108A8FD17B448A68554199C47D08FFB10D4B8)


def _add(p1, p2):
    if p1 is None:
        return p2
    if p2 is None:
        return p1
    (x1, y1), (x2, y2) = p1, p2
    if x1 == x2:
        if (y1 + y2) % P == 0:
            return None
        lam = 3 * x1 * x1 * pow(2 * y1, -1, P) % P
    else:
        lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
    x3 = (lam * lam - x1 - x2) % P
    return x3, (lam * (x1 - x3) - y1) % P


def _mul(k: int, point):
    acc = None
    while k:
        if k & 1:
            acc = _add(acc, point)
        point = _add(point, point)
        k >>= 1
    return acc


def address_of(secret: int) -> bytes:
    x, y = _mul(secret, G)
    return keccak256(x.to_bytes(32, "big") + y.to_bytes(32, "big"))[12:]


def _rfc6979_k(secret: int, digest: bytes) -> int:
    x = secret.to_bytes(32, "big")
    v, k = b"\x01" * 32, b"\x00" * 32
    k = hmac.new(k, v + b"\x00" + x + digest, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    k = hmac.new(k, v + b"\x01" + x + digest, hashlib.sha256).digest()
    v = hmac.new(k, v, hashlib.sha256).digest()
    while True:
        v = hmac.new(k, v, hashlib.sha256).digest()
        cand = int.from_bytes(v, "big")
        if 1 <= cand < N:
            return cand
        k = hmac.new(k, v + b"\x00", hashlib.sha256).digest()
        v = hmac.new(k, v, hashlib.sha256).digest()


def sign(secret: int, digest: bytes) -> tuple[int, int, int]:
    """(y_parity, r, s) with s in the lower half of the group order."""
    k = _rfc6979_k(secret, digest)
    rx, ry = _mul(k, G)
    r = rx % N
    s = pow(k, -1, N) * (int.from_bytes(digest, "big") + r * secret) % N
    parity = ry & 1
    if s > N // 2:
        s, parity = N - s, parity ^ 1
    return parity, r, s


# ---------------------------------------------------------------------------
# EIP-1559 transfer

def signed_transfer(secret: int, chain_id: int, nonce: int, to: bytes,
                    value: int, max_priority_fee: int, max_fee: int,
                    gas_limit: int = 21_000) -> bytes:
    """The canonical (typed-envelope) encoding of a signed EIP-1559
    transaction with no calldata and an empty access list."""
    fields = [chain_id, nonce, max_priority_fee, max_fee, gas_limit, to,
              value, b"", []]
    digest = keccak256(b"\x02" + rlp_encode(fields))
    parity, r, s = sign(secret, digest)
    return b"\x02" + rlp_encode(fields + [parity, r, s])
