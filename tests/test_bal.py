"""EIP-7928 Block Access Lists (VERDICT #6): generation from the
journaled executor, canonical RLP/ordering, and BAL-validated import
rejecting a tampered list (reference seat:
crates/common/types/block_access_list.rs, blockchain.rs:552)."""

import dataclasses
import os

import pytest

from ethrex_tpu.crypto import secp256k1
from ethrex_tpu.primitives.bal import (AccountChanges, BlockAccessList)
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.primitives.transaction import Transaction
from ethrex_tpu.blockchain.blockchain import Blockchain, InvalidBlock
from ethrex_tpu.node import Node

SECRET = 0xA11CE
SENDER = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(SECRET))
OTHER = bytes.fromhex("44" * 20)
# reads slot 1, then sstore(0, calldataload(0))
CODE = bytes.fromhex("60015450" + "6000355f5500")
CONTRACT = bytes.fromhex("c0de" * 10)

GENESIS = {
    "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
               "shanghaiTime": 0, "cancunTime": 0},
    "alloc": {
        "0x" + SENDER.hex(): {"balance": hex(10**21)},
        "0x" + CONTRACT.hex(): {"balance": "0x0",
                                "code": "0x" + CODE.hex(),
                                "storage": {hex(1): hex(99)}},
    },
    "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7", "timestamp": "0x0",
}


def _block():
    node = Node(Genesis.from_json(GENESIS))
    node.submit_transaction(Transaction(
        tx_type=2, chain_id=1337, nonce=0, max_priority_fee_per_gas=1,
        max_fee_per_gas=10**10, gas_limit=21000, to=OTHER,
        value=500).sign(SECRET))
    node.submit_transaction(Transaction(
        tx_type=2, chain_id=1337, nonce=1, max_priority_fee_per_gas=1,
        max_fee_per_gas=10**10, gas_limit=90_000, to=CONTRACT, value=0,
        data=(42).to_bytes(32, "big")).sign(SECRET))
    block = node.produce_block()
    assert len(block.body.transactions) == 2
    return node, block


def test_generation_and_contents():
    node, block = _block()
    parent = node.store.get_header(block.header.parent_hash)
    bal = node.chain.generate_bal(block, parent)
    by_addr = {ac.address: ac for ac in bal.accounts}
    # sender: nonce + balance change at both tx indices
    s = by_addr[SENDER]
    assert [i for i, _ in s.nonce_changes] == [1, 2]
    assert [i for i, _ in s.balance_changes] == [1, 2]
    assert s.nonce_changes[-1][1] == 2
    # recipient: balance at index 1
    r = by_addr[OTHER]
    assert r.balance_changes == [(1, 500)]
    # contract: slot 0 written at index 2, slot 1 read-only
    c = by_addr[CONTRACT]
    assert c.storage_changes == {0: [(2, 42)]}
    assert c.storage_reads == {1}
    # coinbase collects tips
    cb = by_addr[block.header.coinbase]
    assert [i for i, _ in cb.balance_changes] == [1, 2]


def test_rlp_roundtrip_and_ordering():
    node, block = _block()
    parent = node.store.get_header(block.header.parent_hash)
    bal = node.chain.generate_bal(block, parent)
    wire = bal.encode()
    back = BlockAccessList.decode(wire)
    back.validate_ordering()
    assert back.encode() == wire
    assert back.hash() == bal.hash()
    # out-of-order accounts are rejected
    shuffled = BlockAccessList(accounts=list(reversed(bal.accounts)))
    # (encode() canonicalizes; the decoder-side validator must reject a
    # hand-built unsorted list)
    if len(shuffled.accounts) > 1:
        with pytest.raises(ValueError, match="out of order"):
            BlockAccessList(
                accounts=list(reversed(sorted(
                    bal.accounts, key=lambda a: a.address)))
            ).validate_ordering()


def test_bal_validated_import_and_tamper_rejection():
    node, block = _block()
    parent = node.store.get_header(block.header.parent_hash)
    bal = node.chain.generate_bal(block, parent)

    # fresh store: BAL-validated import accepts the honest list
    from ethrex_tpu.storage.store import Store

    store = Store()
    store.init_genesis(Genesis.from_json(GENESIS))
    chain = Blockchain(store, node.config)
    chain.add_block(block, bal=bal)
    assert store.get_header(block.hash) is not None

    # tampered post-value: import must reject
    t = node.chain.generate_bal(block, parent)
    for ac in t.accounts:
        if ac.address == CONTRACT:
            ac.storage_changes[0] = [(2, 43)]
    store2 = Store()
    store2.init_genesis(Genesis.from_json(GENESIS))
    chain2 = Blockchain(store2, node.config)
    with pytest.raises(InvalidBlock, match="access list mismatch"):
        chain2.add_block(block, bal=t)

    # omitted read: also a mismatch (the claim must be exact)
    t2 = node.chain.generate_bal(block, parent)
    for ac in t2.accounts:
        if ac.address == CONTRACT:
            ac.storage_reads = set()
    store3 = Store()
    store3.init_genesis(Genesis.from_json(GENESIS))
    chain3 = Blockchain(store3, node.config)
    with pytest.raises(InvalidBlock, match="access list mismatch"):
        chain3.add_block(block, bal=t2)


@pytest.mark.skipif((os.cpu_count() or 1) < 2,
                    reason="single-core host: the prefetch does not fan "
                           "out over threads there")
def test_parallel_warm_import_beats_sequential(monkeypatch):
    """What the BAL prefetch buys, counted and not timed: with `bal=`
    every listed account and slot is read from the state source before
    execution starts (over the thread pool: the block lists 14
    accounts), so execution finds the contract's storage trie open and
    issues fewer reads of its own than the import without a list."""
    from ethrex_tpu.evm.db import TrieSource
    from ethrex_tpu.storage.store import Store

    node, block = _block()
    parent = node.store.get_header(block.header.parent_hash)
    bal = node.chain.generate_bal(block, parent)
    read_account = TrieSource.get_account_state
    read_storage = TrieSource.get_storage

    def run(with_bal):
        store = Store()
        store.init_genesis(Genesis.from_json(GENESIS))
        chain = Blockchain(store, node.config)
        ahead, during = [], []
        log = [ahead]

        def get_account_state(self, address):
            log[0].append((address,))
            return read_account(self, address)

        def get_storage(self, address, slot):
            log[0].append((address, slot))
            return read_storage(self, address, slot)

        def execute_block(*args, **kwargs):
            log[0] = during
            return Blockchain.execute_block(chain, *args, **kwargs)

        monkeypatch.setattr(TrieSource, "get_account_state",
                            get_account_state)
        monkeypatch.setattr(TrieSource, "get_storage", get_storage)
        monkeypatch.setattr(chain, "execute_block", execute_block)
        chain.add_block(block, bal=bal if with_bal else None)
        assert store.get_header(block.hash) is not None
        return ahead, during

    cold_ahead, cold = run(False)
    warm_ahead, warm = run(True)
    assert cold_ahead == []
    listed = {(ac.address,) for ac in bal.accounts} | {
        (ac.address, slot) for ac in bal.accounts
        for slot in ac.storage_reads | set(ac.storage_changes)}
    assert len(bal.accounts) > 8 and (CONTRACT, 1) in listed
    assert listed <= set(warm_ahead)
    # execution reads the same slots either way, and without the list
    # it also has to open the storage trie they live in
    assert [r for r in warm if len(r) == 2] == \
        [r for r in cold if len(r) == 2]
    assert len(warm) < len(cold)


def test_padded_reads_rejected():
    """A BAL padded with bogus storage_reads must NOT self-certify via
    the warming path's journaled loads (review finding)."""
    node, block = _block()
    parent = node.store.get_header(block.header.parent_hash)
    bal = node.chain.generate_bal(block, parent)
    for ac in bal.accounts:
        if ac.address == CONTRACT:
            ac.storage_reads = set(ac.storage_reads) | {777, 888}
    from ethrex_tpu.storage.store import Store

    store = Store()
    store.init_genesis(Genesis.from_json(GENESIS))
    chain = Blockchain(store, node.config)
    with pytest.raises(InvalidBlock, match="access list mismatch"):
        chain.add_block(block, bal=bal)


def test_shared_withdrawal_address_single_index():
    """Two withdrawals to one address must yield ONE post-exec balance
    change entry (duplicate indices would fail ordering validation on an
    honest BAL — review finding)."""
    from ethrex_tpu.blockchain.payload import (build_payload,
                                               create_payload_header)
    from ethrex_tpu.primitives.block import Withdrawal
    from ethrex_tpu.storage.store import Store

    store = Store()
    genesis = Genesis.from_json(GENESIS)
    gh = store.init_genesis(genesis)
    chain = Blockchain(store, genesis.config)
    wds = [Withdrawal(index=0, validator_index=1, address=OTHER, amount=3),
           Withdrawal(index=1, validator_index=2, address=OTHER, amount=4)]
    header = create_payload_header(gh, chain.config, timestamp=12,
                                   coinbase=b"\xee" * 20)
    result = build_payload(chain, gh, header, [], wds)
    bal = chain.generate_bal(result.block, gh)
    bal.validate_ordering()
    by_addr = {ac.address: ac for ac in bal.accounts}
    assert by_addr[OTHER].balance_changes == [(1, 7 * 10**9)]
    # and the BAL-validated import accepts it
    store2 = Store()
    store2.init_genesis(genesis)
    chain2 = Blockchain(store2, genesis.config)
    chain2.add_block(result.block, bal=bal)
