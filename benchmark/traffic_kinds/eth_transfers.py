"""Traffic kind `eth_transfers`: plain EIP-1559 transfers from one
funded sender to fresh recipients.  This turns a mix and `--seed` into
everything a run sends: the genesis allocation, the sender key, and each
batch's signed transfers.  The same seed gives the same bytes; a seed
changes keys, recipients and values and nothing about the amount or
shape of the work (every batch is `blocks_per_batch` blocks of
`transfers_per_block` transfers, so every batch touches the same number
of accounts and proves at the same trace sizes).  Its plain reference is
reference.Ledger (`expected_states`), compared account by account with
what a proof's write log claims (`count_state_mismatches`).

Fields of a mix of this kind, besides those of every mix (traffic.py):
  transfers_per_block   plain EIP-1559 transfers in each block
  value_wei             {"min": .., "max": ..} drawn per transfer
  max_priority_fee_per_gas, max_fee_per_gas
  sender_balance_wei    what the genesis gives the sender
"""

from __future__ import annotations

import json
import random

import ethtx
import reference
from reference import Transfer

GENESIS_TEMPLATE = {
    # the chain `l2 --dev` runs (cli.DEV_GENESIS), with the seed's
    # sender funded in place of the well-known dev key
    "config": {
        "chainId": 1337,
        "homesteadBlock": 0, "eip150Block": 0, "eip155Block": 0,
        "byzantiumBlock": 0, "constantinopleBlock": 0, "petersburgBlock": 0,
        "istanbulBlock": 0, "berlinBlock": 0, "londonBlock": 0,
        "mergeNetsplitBlock": 0, "terminalTotalDifficulty": 0,
        "shanghaiTime": 0, "cancunTime": 0, "pragueTime": 0,
    },
    "gasLimit": "0x1c9c380",
    "baseFeePerGas": "0x7",
    "timestamp": "0x0",
}


REQUIRED = ("transfers_per_block", "value_wei", "max_priority_fee_per_gas",
            "max_fee_per_gas", "sender_balance_wei")


class Traffic:
    """Everything one run sends, drawn from (mix, seed)."""

    def __init__(self, mix: dict, seed: int):
        self.mix = mix
        self.seed = int(seed)
        rng = random.Random(self.seed)
        self.secret = rng.randrange(1, ethtx.N)
        self.sender = ethtx.address_of(self.secret)
        self.chain_id = GENESIS_TEMPLATE["config"]["chainId"]
        self._rng = rng
        self._next_nonce = 0
        self._used = {self.sender, b"\x00" * 20}
        self._batches: list[list[list[Transfer]]] = []

    # -- genesis -------------------------------------------------------
    def genesis(self) -> dict:
        g = json.loads(json.dumps(GENESIS_TEMPLATE))
        g["alloc"] = {"0x" + self.sender.hex(): {
            "balance": hex(int(self.mix["sender_balance_wei"]))}}
        return g

    def alloc(self) -> dict[bytes, int]:
        return {self.sender: int(self.mix["sender_balance_wei"])}

    @property
    def base_fee(self) -> int:
        return int(GENESIS_TEMPLATE["baseFeePerGas"], 16)

    @property
    def gas_limit(self) -> int:
        return int(GENESIS_TEMPLATE["gasLimit"], 16)

    # -- batches -------------------------------------------------------
    def batch(self, index: int) -> list[list[Transfer]]:
        """Batch `index` (0-based) as blocks of transfers.  Batches are
        drawn in order, so batch k is the same whatever was asked for
        before."""
        while len(self._batches) <= index:
            self._batches.append(self._draw_batch())
        return self._batches[index]

    def _draw_batch(self) -> list[list[Transfer]]:
        lo = int(self.mix["value_wei"]["min"])
        hi = int(self.mix["value_wei"]["max"])
        blocks = []
        for _ in range(int(self.mix["blocks_per_batch"])):
            block = []
            for _ in range(int(self.mix["transfers_per_block"])):
                to = self._rng.randbytes(20)
                while to in self._used:
                    to = self._rng.randbytes(20)
                self._used.add(to)
                block.append(Transfer(
                    sender=self.sender, nonce=self._next_nonce, to=to,
                    value=self._rng.randint(lo, hi),
                    max_priority_fee=int(
                        self.mix["max_priority_fee_per_gas"]),
                    max_fee=int(self.mix["max_fee_per_gas"])))
                self._next_nonce += 1
            blocks.append(block)
        return blocks

    def signed(self, t: Transfer) -> bytes:
        return ethtx.signed_transfer(
            self.secret, self.chain_id, t.nonce, t.to, t.value,
            t.max_priority_fee, t.max_fee)


count_state_mismatches = reference.count_state_mismatches


def expected_states(traffic: Traffic, upto_index: int) -> list[dict]:
    """The reference's account states after each batch 0..upto_index."""
    ledger = reference.Ledger(traffic.alloc(), traffic.base_fee,
                              traffic.gas_limit,
                              fee_recipient=b"\x00" * 20)
    out = []
    for index in range(upto_index + 1):
        touched: dict = {}
        for block in traffic.batch(index):
            touched.update(ledger.apply_block(block))
        # a batch's log ends with each account's LAST value of the batch
        out.append({a: (ledger.nonce.get(a, 0), ledger.balance.get(a, 0))
                    for a in touched})
    return out
