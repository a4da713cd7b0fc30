"""The plain reference: what a batch of EIP-1559 ETH transfers does to
the accounts it touches, worked out with Python integers from the
genesis allocation and the transfers alone.  It imports nothing of the
program and takes nothing the program made: the harness hands it the
same transfers it sent (drawn from the seed) and, to be judged, the
write log and public output each proof of the window carried.

Semantics (Ethereum, London and later): the sender pays
`gas_used * min(max_fee, base_fee + max_priority) + value`, the recipient
gains `value`, the block's fee recipient gains the priority part, the
base-fee part is burnt; a plain transfer uses 21,000 gas; the base fee
follows EIP-1559 from the parent block's gas use.
"""

from __future__ import annotations

import dataclasses

import ethtx

TRANSFER_GAS = 21_000
ELASTICITY = 2
BASE_FEE_DENOMINATOR = 8


@dataclasses.dataclass(frozen=True)
class Transfer:
    sender: bytes
    nonce: int
    to: bytes
    value: int
    max_priority_fee: int
    max_fee: int


def next_base_fee(parent_base_fee: int, parent_gas_used: int,
                  parent_gas_limit: int) -> int:
    target = parent_gas_limit // ELASTICITY
    if parent_gas_used == target:
        return parent_base_fee
    if parent_gas_used > target:
        delta = max(parent_base_fee * (parent_gas_used - target)
                    // target // BASE_FEE_DENOMINATOR, 1)
        return parent_base_fee + delta
    delta = (parent_base_fee * (target - parent_gas_used)
             // target // BASE_FEE_DENOMINATOR)
    return parent_base_fee - delta


class Ledger:
    """Balances and nonces of the accounts the traffic can touch."""

    def __init__(self, alloc: dict[bytes, int], base_fee: int,
                 gas_limit: int, fee_recipient: bytes):
        self.balance = dict(alloc)
        self.nonce: dict[bytes, int] = {}
        self.fee_recipient = fee_recipient
        self.gas_limit = gas_limit
        # the genesis block used no gas
        self.base_fee = next_base_fee(base_fee, 0, gas_limit)

    def apply_block(self, transfers: list[Transfer]) -> dict[bytes, tuple]:
        """Apply one block of transfers; returns {address: (nonce,
        balance)} for every account the block touched."""
        touched: set[bytes] = set()
        gas_used = 0
        for t in transfers:
            if self.nonce.get(t.sender, 0) != t.nonce:
                raise ValueError("the reference was given a nonce gap")
            price = min(t.max_fee, self.base_fee + t.max_priority_fee)
            if price < self.base_fee:
                raise ValueError("max fee under the base fee")
            cost = TRANSFER_GAS * price + t.value
            if self.balance.get(t.sender, 0) < cost:
                raise ValueError("the reference sender cannot pay")
            self.balance[t.sender] -= cost
            self.nonce[t.sender] = t.nonce + 1
            self.balance[t.to] = self.balance.get(t.to, 0) + t.value
            tip = TRANSFER_GAS * (price - self.base_fee)
            self.balance[self.fee_recipient] = \
                self.balance.get(self.fee_recipient, 0) + tip
            touched.update((t.sender, t.to, self.fee_recipient))
            gas_used += TRANSFER_GAS
        self.base_fee = next_base_fee(self.base_fee, gas_used,
                                      self.gas_limit)
        return {a: (self.nonce.get(a, 0), self.balance.get(a, 0))
                for a in touched}


def accounts_in_write_log(write_log: list) -> dict[bytes, tuple]:
    """{address: (nonce, balance)} as the LAST account row of each
    address in a proof's claimed write log leaves it (rows are
    ["a", address hex, old account RLP hex, new account RLP hex, ...];
    slot rows and clear markers carry no balance)."""
    out: dict[bytes, tuple] = {}
    for block in write_log:
        for row in block:
            if row[0] != "a":
                continue
            new = bytes.fromhex(row[3])
            if not new:
                out[bytes.fromhex(row[1])] = (0, 0)
                continue
            fields = ethtx.rlp_decode(new)
            out[bytes.fromhex(row[1])] = (
                int.from_bytes(fields[0], "big"),
                int.from_bytes(fields[1], "big"))
    return out


def count_state_mismatches(expected: dict[bytes, tuple],
                           write_log: list) -> int:
    """How many accounts differ between what the reference expects a
    batch to leave and what the proof's write log claims: a missing
    account, an extra account, a wrong nonce or a wrong balance each
    count one."""
    claimed = accounts_in_write_log(write_log)
    return sum(1 for a in set(expected) | set(claimed)
               if expected.get(a) != claimed.get(a))


def output_fields(output_hex: str) -> dict:
    """The proof's public output (176 bytes: initial root, final root,
    last block hash, first and last block number, privileged digest,
    message root) as named fields."""
    raw = bytes.fromhex(output_hex.removeprefix("0x"))
    if len(raw) != 176:
        raise ValueError(f"public output is {len(raw)} bytes, not 176")
    return {"initial_root": raw[0:32], "final_root": raw[32:64],
            "last_block_hash": raw[64:96],
            "first_block": int.from_bytes(raw[96:104], "big"),
            "last_block": int.from_bytes(raw[104:112], "big"),
            "privileged_digest": raw[112:144],
            "messages_root": raw[144:176]}
