"""The yardstick's own primitives and its plain reference, against
known answers and (as a second witness) the program's primitives."""

import ethtx
import reference
from reference import Transfer

DEV_SECRET = \
    0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8


def test_keccak_known_answers():
    assert ethtx.keccak256(b"").hex() == (
        "c5d2460186f7233c927e7db2dcc703c0e500b653ca82273b7bfad8045d85a470")
    assert ethtx.keccak256(b"abc").hex() == (
        "4e03657aea45a94fc7d47ba826c8d667c0d1e6e33a64a036ec44f58fa12d6c45")


def test_address_of_the_well_known_dev_key():
    assert ethtx.address_of(DEV_SECRET).hex() == \
        "a94f5374fce5edbc8e2a8697c15331677e6ebf0b"


def test_rlp_round_trip():
    item = [b"", b"\x01", b"\x80", b"x" * 60, [b"a", [b"b" * 56]]]
    assert ethtx.rlp_decode(ethtx.rlp_encode(item)) == item


def test_signed_transfer_is_the_programs_byte_for_byte():
    from ethrex_tpu.primitives.transaction import (TYPE_DYNAMIC_FEE,
                                                   Transaction)

    for nonce, value in ((0, 1000), (7, 10**15), (300, 1)):
        to = bytes([0x50 + nonce % 7]) * 20
        mine = ethtx.signed_transfer(DEV_SECRET, 1337, nonce, to, value,
                                     1, 10**10)
        theirs = Transaction(
            tx_type=TYPE_DYNAMIC_FEE, chain_id=1337, nonce=nonce,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=21_000, to=to, value=value).sign(DEV_SECRET)
        assert mine == theirs.encode_canonical()
        assert Transaction.decode_canonical(mine).sender() == \
            ethtx.address_of(DEV_SECRET)


def test_base_fee_follows_eip1559():
    assert reference.next_base_fee(7, 0, 30_000_000) == 7       # 7*1//8=0
    assert reference.next_base_fee(1000, 0, 30_000_000) == 875
    assert reference.next_base_fee(1000, 15_000_000, 30_000_000) == 1000
    assert reference.next_base_fee(1000, 30_000_000, 30_000_000) == 1125


def test_ledger_by_hand():
    a, b, fee = b"\x01" * 20, b"\x02" * 20, b"\x00" * 20
    ledger = reference.Ledger({a: 10**18}, 7, 30_000_000, fee)
    touched = ledger.apply_block([
        Transfer(a, 0, b, 1000, 1, 10**10),
        Transfer(a, 1, b, 5, 1, 10**10)])
    # price = base fee 7 + tip 1; each transfer costs 21,000 * 8 + value
    assert touched[a] == (2, 10**18 - 2 * 21_000 * 8 - 1005)
    assert touched[b] == (0, 1005)
    assert touched[fee] == (0, 2 * 21_000)


def test_write_log_comparison_counts_each_wrong_account():
    a, b = b"\x01" * 20, b"\x02" * 20
    empty_root, empty_code = b"\x11" * 32, b"\x22" * 32

    def acct(nonce, balance):
        return ethtx.rlp_encode([nonce, balance, empty_root,
                                 empty_code]).hex()

    log = [[["a", a.hex(), "", acct(1, 50), False],
            ["a", b.hex(), "", acct(0, 7), False],
            ["a", a.hex(), acct(1, 50), acct(2, 40), False]]]
    assert reference.count_state_mismatches({a: (2, 40), b: (0, 7)},
                                            log) == 0
    assert reference.count_state_mismatches({a: (2, 41), b: (0, 7)},
                                            log) == 1
    assert reference.count_state_mismatches({a: (2, 40)}, log) == 1
