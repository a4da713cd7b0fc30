"""Traffic kind `erc20_transfers`: many funded holders calling one
token's `transfer(address,uint256)`, as upstream's ERC-20 load test does
(`tooling/load_test`, docs/developers/l1/testing/load-tests.md).  This
turns a mix and `--seed` into everything a run sends: the genesis (the
chain of `eth_transfers`, `senders` funded accounts, and ONE token
contract whose code is the bytecode below and whose storage holds each
sender's token balance), the senders' keys, and each batch's signed
EIP-1559 calls.  The same seed gives the same bytes; a seed changes the
keys, the token's address, recipients and amounts and nothing about the
amount or shape of the work: every block is `calls_per_block` calls,
round-robin over the senders (one sender cannot fill a block: the
program's mempool holds 64 transactions a sender), each sender on its
own nonce chain; the first `fresh_recipients_per_block` calls of a block
credit an address never seen before, the rest credit ANOTHER sender
(never the caller: a lone self-transfer nets its slot to nothing, which
the program's token circuit leaves to its claimed-log mode).  Amounts
are >= 1 and far under a holder's balance, so no call reverts, no slot
returns to zero and no gas is refunded.  (A holder whose credits of one
block equal its debits to the unit would net its slot to nothing too:
with amounts up to 10^12 that is a chance of 10^-12 a holder and block,
and is not guarded against.)

Its plain reference (`expected_states`) works out, with Python integers
from the genesis and the calls alone, what each batch must leave: every
token balance by storage slot, and every account's nonce and ETH balance
with the fee from ITS OWN gas model of the bytecode's one `transfer`
path (`call_gas`: nothing is read from a receipt).  It imports nothing
of the program.  `count_state_mismatches` holds a proof's claimed write
log to it exactly, account rows (`"a"`) and slot rows (`"s"`) alike.
What has no plain form here: the token contract's storage ROOT inside
its account row (a Merkle-Patricia root over every slot); its nonce and
balance are compared, its root stays with the program's
`verify_with_input` (the witness replay).

Pre-flight (`refuse_a_quadratic_program`, the first thing `Traffic`
does): a batch of the cell's mix gives the state circuit 2^15 rows, and
every AIR has a selector column of the trace's own length.  A program
that interpolates such a column through a (p, p) table
(`ops.ntt.interpolate_host` before PR 29: 8 p^2 bytes, several live at
once; 8.6 GB a table at 2^15 rows, 34 GB at 2^16) is ended for memory by
the machine, beside its four compiles, a minute or more into its first
batch (PR 29, chip call 1), and a run that is killed is no clean
failure.  The one look this file takes at the program is that function
on 2^10 points under `tracemalloc`; a quadratic one is refused with a
`BenchFailure` (exit 3, no result line) before anything is built or
sent.  A program that has no function of that name is not looked at:
the pre-flight passes.  The reference takes nothing from it.

Fields of a mix of this kind, besides those of every mix (traffic.py):
  calls_per_block              `transfer` calls in each block
  senders                      funded holders, called from in turn
  fresh_recipients_per_block   calls of a block that credit a new address
  amount                       {"min": .., "max": ..} token units a call
  sender_token_balance         what the genesis storage gives each holder
  sender_balance_wei           what the genesis gives each holder in ETH
  max_priority_fee_per_gas, max_fee_per_gas, gas_limit
"""

from __future__ import annotations

import json
import random
import typing

import ethtx
import reference
from common import BenchFailure
from traffic import load_kind

GENESIS_TEMPLATE = load_kind("eth_transfers").GENESIS_TEMPLATE

REQUIRED = ("calls_per_block", "senders", "fresh_recipients_per_block",
            "amount", "sender_token_balance", "sender_balance_wei",
            "max_priority_fee_per_gas", "max_fee_per_gas", "gas_limit")

SELECTOR_TRANSFER = bytes.fromhex("a9059cbb")   # transfer(address,uint256)
COINBASE = b"\x00" * 20

# The token: the program's canonical template (guest/token_template.py
# TEMPLATE_CODE), copied here as bytes so that the yardstick does not
# move with the program (tests/test_erc20_cell.py holds the two equal).
# A dispatcher on the selector, then for `transfer(dst, v)`:
#   kf = keccak(pad32(caller) || pad32(0)); bf = sload(kf)
#   revert if bf < v; sstore(kf, bf - v)
#   kt = keccak(pad32(dst) || pad32(0));    sstore(kt, sload(kt) + v)
#   return true
TOKEN_CODE = bytes.fromhex(
    "60003560e01c8063a9059cbb1461002057806370a082311461007357600080fd"
    "5b5060243560043573ffffffffffffffffffffffffffffffffffffffff163360"
    "00526000602052604060002080548381106100a4578390039055600052604060"
    "002080548201905550600160005260206000f35b5060043573ffffffffffffff"
    "ffffffffffffffffffffffffff16600052600060205260406000205460005260"
    "206000f35b600080fd")

# -- the gas model of that one path ------------------------------------
# Static costs (Yellow Paper appendix G as amended; none of these
# opcodes was repriced after Berlin): the opcodes the `transfer` path
# executes, in order, storage opcodes left out (they are priced below).
_VERYLOW, _BASE, _HIGH, _JUMPDEST = 3, 2, 10, 1
_SHA3_64 = 30 + 6 * 2       # KECCAK256: 30 + 6 a word, two words
_PATH = (
    # dispatcher: PUSH1 0, CALLDATALOAD, PUSH1 e0, SHR, DUP1, PUSH4,
    # EQ, PUSH2, JUMPI (taken)
    [_VERYLOW] * 8 + [_HIGH]
    # JUMPDEST, POP, PUSH1 24, CALLDATALOAD, PUSH1 04, CALLDATALOAD,
    # PUSH20, AND
    + [_JUMPDEST, _BASE] + [_VERYLOW] * 6
    # CALLER, PUSH1 0, MSTORE, PUSH1 0, PUSH1 20, MSTORE, PUSH1 40,
    # PUSH1 0, SHA3
    + [_BASE] + [_VERYLOW] * 7 + [_SHA3_64]
    # DUP1, [SLOAD], DUP4, DUP2, LT, PUSH2, JUMPI (not taken), DUP4,
    # SWAP1, SUB, SWAP1, [SSTORE]
    + [_VERYLOW] * 5 + [_HIGH] + [_VERYLOW] * 4
    # PUSH1 0, MSTORE, PUSH1 40, PUSH1 0, SHA3, DUP1, [SLOAD], DUP3,
    # ADD, SWAP1, [SSTORE], POP
    + [_VERYLOW] * 4 + [_SHA3_64] + [_VERYLOW] * 4 + [_BASE]
    # PUSH1 1, PUSH1 0, MSTORE, PUSH1 20, PUSH1 0, RETURN (0)
    + [_VERYLOW] * 5 + [0])
# memory grows to two words (the two MSTOREs before the first SHA3):
# 3 a word + words^2 / 512
_MEMORY = 3 * 2 + 2 * 2 // 512
PATH_GAS = sum(_PATH) + _MEMORY

TX_GAS = 21_000                 # the intrinsic cost of a transaction
CALLDATA_ZERO, CALLDATA_NONZERO = 4, 16     # EIP-2028, a byte
COLD_SLOAD = 2_100              # EIP-2929: first access of a slot in a tx
SSTORE_RESET = 2_900            # EIP-2929 on EIP-2200: a warm slot whose
#                                 original value is non-zero, changed
SSTORE_SET = 20_000             # EIP-2200: a warm slot from zero to non-zero
FLOOR_PER_TOKEN = 10            # EIP-7623 (Prague): 21000 + 10 a token,
#                                 tokens = zero bytes + 4 x non-zero bytes


def call_gas(calldata: bytes, recipient_had_tokens: bool) -> int:
    """Gas one successful `transfer` uses.  The caller's slot is read
    cold and written warm, from a non-zero balance to a non-zero one;
    the recipient's is read cold and written warm, from zero (SET) or
    from a balance (RESET).  No refund: no slot is cleared or restored
    (EIP-3529 would pay for those).  The EIP-7623 floor is far below
    what the path executes and never binds."""
    zeros = calldata.count(0)
    nonzeros = len(calldata) - zeros
    used = (TX_GAS + CALLDATA_ZERO * zeros + CALLDATA_NONZERO * nonzeros
            + PATH_GAS + 2 * COLD_SLOAD + SSTORE_RESET
            + (SSTORE_RESET if recipient_had_tokens else SSTORE_SET))
    return max(used, TX_GAS + FLOOR_PER_TOKEN * (zeros + 4 * nonzeros))


def balance_slot(holder: bytes) -> int:
    """Key of `holder`'s balance in the slot-0 mapping (Solidity's rule:
    keccak(pad32(key) || pad32(slot)))."""
    return int.from_bytes(
        ethtx.keccak256(b"\x00" * 12 + holder + b"\x00" * 32), "big")


class Call(typing.NamedTuple):
    sender: bytes
    nonce: int
    token: bytes        # the transaction's `to`
    dst: bytes          # who the tokens go to
    amount: int
    max_priority_fee: int
    max_fee: int
    gas_limit: int

    @property
    def calldata(self) -> bytes:
        return (SELECTOR_TRANSFER + b"\x00" * 12 + self.dst
                + self.amount.to_bytes(32, "big"))


class Traffic:
    """Everything one run sends, drawn from (mix, seed)."""

    def __init__(self, mix: dict, seed: int):
        refuse_a_quadratic_program()
        self.mix = mix
        self.seed = int(seed)
        rng = random.Random(self.seed)
        self.secrets: dict[bytes, int] = {}
        while len(self.secrets) < int(mix["senders"]):
            secret = rng.randrange(1, ethtx.N)
            self.secrets[ethtx.address_of(secret)] = secret
        self.holders = list(self.secrets)
        self.token = rng.randbytes(20)
        self.chain_id = GENESIS_TEMPLATE["config"]["chainId"]
        self._rng = rng
        self._nonces = dict.fromkeys(self.holders, 0)
        self._used = {*self.holders, self.token, COINBASE}
        self._batches: list[list[list[Call]]] = []

    # -- genesis -------------------------------------------------------
    def genesis(self) -> dict:
        g = json.loads(json.dumps(GENESIS_TEMPLATE))
        g["alloc"] = {"0x" + a.hex(): {
            "balance": hex(int(self.mix["sender_balance_wei"]))}
            for a in self.holders}
        g["alloc"]["0x" + self.token.hex()] = {
            "balance": "0x0", "code": "0x" + TOKEN_CODE.hex(),
            "storage": {"0x%064x" % balance_slot(a):
                        hex(int(self.mix["sender_token_balance"]))
                        for a in self.holders}}
        return g

    @property
    def base_fee(self) -> int:
        return int(GENESIS_TEMPLATE["baseFeePerGas"], 16)

    @property
    def gas_limit(self) -> int:
        return int(GENESIS_TEMPLATE["gasLimit"], 16)

    # -- batches -------------------------------------------------------
    def batch(self, index: int) -> list[list[Call]]:
        """Batch `index` (0-based) as blocks of calls.  Batches are drawn
        in order, so batch k is the same whatever was asked for before."""
        while len(self._batches) <= index:
            self._batches.append(
                [self._draw_block()
                 for _ in range(int(self.mix["blocks_per_batch"]))])
        return self._batches[index]

    def _draw_block(self) -> list[Call]:
        lo, hi = int(self.mix["amount"]["min"]), int(self.mix["amount"]["max"])
        fresh = int(self.mix["fresh_recipients_per_block"])
        block = []
        for i in range(int(self.mix["calls_per_block"])):
            sender = self.holders[i % len(self.holders)]
            if i < fresh:
                dst = self._rng.randbytes(20)
                while dst in self._used:
                    dst = self._rng.randbytes(20)
                self._used.add(dst)
            else:
                dst = self._rng.choice(
                    [a for a in self.holders if a != sender])
            block.append(Call(
                sender=sender, nonce=self._nonces[sender], token=self.token,
                dst=dst, amount=self._rng.randint(lo, hi),
                max_priority_fee=int(self.mix["max_priority_fee_per_gas"]),
                max_fee=int(self.mix["max_fee_per_gas"]),
                gas_limit=int(self.mix["gas_limit"])))
            self._nonces[sender] += 1
        return block

    def signed(self, c: Call) -> bytes:
        """The canonical (typed-envelope) encoding of the signed
        EIP-1559 call: value 0, the calldata, an empty access list."""
        fields = [self.chain_id, c.nonce, c.max_priority_fee, c.max_fee,
                  c.gas_limit, c.token, 0, c.calldata, []]
        digest = ethtx.keccak256(b"\x02" + ethtx.rlp_encode(fields))
        parity, r, s = ethtx.sign(self.secrets[c.sender], digest)
        return b"\x02" + ethtx.rlp_encode(fields + [parity, r, s])


def refuse_a_quadratic_program(points: int = 1 << 10) -> None:
    """BenchFailure if the program's periodic-column interpolation
    allocates a (p, p) table: such a program cannot build the cell's
    state circuit on the machine's 40 GiB (module docstring)."""
    import tracemalloc

    try:
        from ethrex_tpu.ops.ntt import interpolate_host
    except ImportError:         # renamed or gone: nothing to look at
        return
    column = [1] + [0] * (points - 1)
    tracemalloc.start()
    try:
        interpolate_host(column)
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    if peak >= 8 * points * points:
        raise BenchFailure(
            "this program interpolates a periodic column through a "
            f"(p, p) table ({peak} bytes at p = {points}); the cell's "
            "state circuit has a column of 32768 rows, 8.6 GB a table and "
            "several at once: nothing was run, nothing is measured")


def expected_states(traffic: Traffic, upto_index: int) -> list[dict]:
    """The reference's state after each batch 0..upto_index:
    {"accounts": {address: (nonce, balance)}, "slots": {(address, key):
    value}} over what the batch touched, each at its LAST value of the
    batch."""
    mix = traffic.mix
    balance = dict.fromkeys(traffic.holders, int(mix["sender_balance_wei"]))
    nonce: dict[bytes, int] = {}
    tokens = {balance_slot(a): int(mix["sender_token_balance"])
              for a in traffic.holders}
    # the genesis block used no gas
    base_fee = reference.next_base_fee(traffic.base_fee, 0,
                                       traffic.gas_limit)
    out = []
    for index in range(upto_index + 1):
        accounts: set[bytes] = set()
        slots: set[int] = set()
        for block in traffic.batch(index):
            gas_used = 0
            for c in block:
                if nonce.get(c.sender, 0) != c.nonce:
                    raise ValueError("the reference was given a nonce gap")
                kf, kt = balance_slot(c.sender), balance_slot(c.dst)
                if not 1 <= c.amount <= tokens.get(kf, 0) or kf == kt:
                    raise ValueError("a call the reference has no path for")
                gas = call_gas(c.calldata, tokens.get(kt, 0) != 0)
                price = min(c.max_fee, base_fee + c.max_priority_fee)
                if price < base_fee or gas > c.gas_limit \
                        or balance[c.sender] < c.gas_limit * c.max_fee:
                    raise ValueError("the reference sender cannot pay")
                balance[c.sender] -= gas * price
                nonce[c.sender] = c.nonce + 1
                balance[COINBASE] = balance.get(COINBASE, 0) \
                    + gas * (price - base_fee)
                tokens[kf] -= c.amount
                tokens[kt] = tokens.get(kt, 0) + c.amount
                accounts.update((c.sender, COINBASE, c.token))
                slots.update((kf, kt))
                gas_used += gas
            base_fee = reference.next_base_fee(base_fee, gas_used,
                                               traffic.gas_limit)
        out.append({
            "accounts": {a: (nonce.get(a, 0), balance.get(a, 0))
                         for a in accounts},
            "slots": {(traffic.token, k): tokens[k] for k in slots}})
    return out


def slots_in_write_log(write_log: list) -> dict[tuple, int]:
    """{(address, key): value} as the LAST slot row of each key in a
    proof's claimed write log leaves it (rows are ["s", address hex,
    key hex, old value hex, new value hex])."""
    out: dict[tuple, int] = {}
    for block in write_log:
        for row in block:
            if row[0] == "s":
                out[(bytes.fromhex(row[1]), int(row[2], 16))] = \
                    int(row[4], 16)
            elif row[0] != "a":
                raise ValueError(f"a {row[0]!r} row: not a write this "
                                 "traffic makes")
    return out


def count_state_mismatches(expected: dict, write_log: list) -> int:
    """How many accounts and slots differ between what the reference
    expects a batch to leave and what the proof's write log claims: a
    missing one, an extra one, a wrong nonce, balance or value each count
    one."""
    accounts = reference.accounts_in_write_log(write_log)
    slots = slots_in_write_log(write_log)
    return sum(1 for a in set(expected["accounts"]) | set(accounts)
               if expected["accounts"].get(a) != accounts.get(a)) \
        + sum(1 for k in set(expected["slots"]) | set(slots)
              if expected["slots"].get(k) != slots.get(k))
