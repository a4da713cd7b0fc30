"""The `prove-erc20` cell without a chip and without a proof: the traffic
kind `erc20_transfers` (benchmark/traffic_kinds/) sends, from a seed
alone, batches that the program classes as token mode at the trace
shapes `benchmark/configs/baseline2-prover.json` states, and the kind's
plain reference (its own gas model, its own slot keys) agrees exactly
with what the program's executor writes.  Nothing here proves: the
builders run (`Node` -> `execution_program` -> `build_vm_batch` ->
`build_access_records`), seconds on the CPU."""

import copy
import functools
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import traffic  # noqa: E402 — benchmark/traffic.py

from ethrex_tpu.blockchain import mempool  # noqa: E402
from ethrex_tpu.evm.executor import InvalidTransaction  # noqa: E402
from ethrex_tpu.guest import access_log, transfer_log  # noqa: E402
from ethrex_tpu.guest import token_template  # noqa: E402
from ethrex_tpu.guest.execution import (ProgramInput,  # noqa: E402
                                        execution_program)
from ethrex_tpu.guest.witness import generate_witness  # noqa: E402
from ethrex_tpu.guest.witness_oracles import WitnessOracles  # noqa: E402
from ethrex_tpu.models import state_update_air as sua  # noqa: E402
from ethrex_tpu.models import token_air as tka  # noqa: E402
from ethrex_tpu.models import transfer_air as ta  # noqa: E402
from ethrex_tpu.node import Node  # noqa: E402
from ethrex_tpu.primitives.genesis import Genesis  # noqa: E402
from ethrex_tpu.primitives.transaction import Transaction  # noqa: E402
from ethrex_tpu.prover import tpu_backend  # noqa: E402

CELL_MIX, KIND = traffic.load_mix(
    os.path.join(BENCH, "traffic", "erc20-backlog.json"))
# 2 senders x 3 calls a block, both kinds of recipient
SMALL_MIX = {**CELL_MIX, "calls_per_block": 6, "senders": 2,
             "fresh_recipients_per_block": 4}
MIXES = {"small": SMALL_MIX, "cell": CELL_MIX}
SEEDS = (7, 2**31 + 29, 3_900_000_001)
with open(os.path.join(BENCH, "configs", "baseline2-prover.json")) as f:
    CONFIG = json.load(f)


def _submit(node, t, call):
    node.submit_transaction(Transaction.decode_canonical(t.signed(call)))


@functools.lru_cache(maxsize=None)
def drive(size: str, seed: int, batches: int = 2):
    """The first `batches` batches of (mix, seed) through the program's
    builders, as the deployment commits them: per batch the prover
    input, the executor's write log as the proof would carry it, and the
    VM batch."""
    t = KIND.Traffic(MIXES[size], seed)
    node = Node(Genesis.from_json(t.genesis()))
    ts, out = int(CONFIG["first_block_timestamp"]), []
    for index in range(batches):
        blocks = []
        for calls in t.batch(index):
            for call in calls:
                _submit(node, t, call)
            ts += int(CONFIG["block_time_s"])
            blocks.append(node.produce_block(timestamp=ts))
            assert len(blocks[-1].body.transactions) == len(calls)
        pi = ProgramInput(blocks=blocks, config=node.config,
                          witness=generate_witness(node.chain, blocks))
        coarse, receipts = [], []
        output = execution_program(pi, write_log=coarse,
                                   receipts_out=receipts)
        vm_batch = transfer_log.build_vm_batch(
            blocks, coarse, receipts,
            oracles=WitnessOracles(pi.witness, output.initial_state_root))
        out.append((pi, access_log.raw_log_to_json(coarse), vm_batch))
    return t, out


def test_the_bytes_are_a_function_of_the_seed_alone():
    seed = 2**31 + 1234567
    a, b = KIND.Traffic(SMALL_MIX, seed), KIND.Traffic(SMALL_MIX, seed)
    b.batch(2)                          # asked for in another order
    for k in range(3):
        assert [b.signed(c) for blk in b.batch(k) for c in blk] == \
            [a.signed(c) for blk in a.batch(k) for c in blk]
    assert a.genesis() == b.genesis()
    other = KIND.Traffic(SMALL_MIX, seed + 1)
    assert other.holders != a.holders and other.token != a.token
    # another seed: other keys and amounts, the same amount of work
    assert [[(a.holders.index(c.sender), c.dst in a.holders) for c in blk]
            for blk in a.batch(0)] == \
        [[(other.holders.index(c.sender), c.dst in other.holders)
          for c in blk] for blk in other.batch(0)]
    for c in a.batch(0)[0]:
        assert c.dst != c.sender and len(c.calldata) == 68


@pytest.mark.parametrize("seed", SEEDS)
def test_every_seed_proves_at_the_shapes_the_configuration_states(seed):
    _, batches = drive("cell", seed)
    sizes, starks = CONFIG["sizes_on_device"], CONFIG["starks"]
    for pi, _, vb in batches:
        assert tpu_backend._mode_of(vb) == "token" == CONFIG["vm_mode"]
        assert tpu_backend.expected_vm_mode(pi) == "token"
        assert len(vb.tok_segs) == CELL_MIX["calls_per_block"]
        records, _, _, depth = access_log.build_access_records(
            access_log.flatten_entries(vb.blocks_log))
        periods = tpu_backend._schedule_for(depth)
        rows = {
            "vm_proof": ta.segment_count(len(vb.segs)) * ta.SEG_LEN,
            "tok_proof": tka.segment_count(len(vb.tok_segs)) * tka.SEG_LEN,
            "state_proof": sua.segment_count(len(records)) * periods
            * sua.PERIOD}
        assert {k: 1 << starks[k]["log_n"] for k in rows} == rows
        state = sizes["StateUpdateAir"]
        assert (depth, periods) == (state["depth"], state["seg_periods"])
        # 31 account rows (15 senders, 15 coinbase, the token once) and
        # 30 slot rows
        kinds = [e[0] for b in vb.blocks_log for e in b]
        assert (kinds.count("acct"), kinds.count("slot"),
                len(records)) == (31, 30, 61)
        assert state["records"].startswith("61 of 64")


@pytest.mark.parametrize("size, seed", [("small", SEEDS[0]),
                                        ("small", SEEDS[1]),
                                        ("cell", SEEDS[2])])
def test_the_reference_agrees_with_the_executor(size, seed):
    """Accounts (nonce, ETH balance: so every call's gas) and slots,
    exactly, against the executor's own log and against the per-tx log
    the proof carries."""
    t, batches = drive(size, seed)
    states = KIND.expected_states(t, len(batches) - 1)
    for want, (_, coarse, vb) in zip(states, batches):
        assert KIND.count_state_mismatches(want, coarse) == 0
        assert KIND.count_state_mismatches(
            want, access_log.raw_log_to_json(vb.blocks_log)) == 0
        assert len(want["slots"]) == \
            MIXES[size]["senders"] + MIXES[size]["fresh_recipients_per_block"]
    # the second batch's holder-to-holder credits land on non-zero
    # slots, its fresh ones on zero: both SSTORE prices were needed
    calls = t.batch(1)[0]
    assert {KIND.call_gas(c.calldata, c.dst in t.holders)
            - KIND.call_gas(c.calldata, False) for c in calls} == \
        {0, KIND.SSTORE_RESET - KIND.SSTORE_SET}


def _drop_last(rows, kind):
    del rows[max(i for i, r in enumerate(rows) if r[0] == kind)]


def _bump_slot(rows):
    row = [r for r in rows if r[0] == "s"][-1]
    row[4] = "%064x" % (int(row[4], 16) + 1)


def _bump_balance(rows):
    import ethtx

    # the LAST row of the first sender: the one the reference reads
    row = [r for r in rows if r[0] == "a" and r[1] == rows[0][1]][-1]
    fields = ethtx.rlp_decode(bytes.fromhex(row[3]))
    fields[1] = ethtx.int_bytes(int.from_bytes(fields[1], "big") + 1)
    row[3] = ethtx.rlp_encode(fields).hex()


@pytest.mark.parametrize("plant", [
    pytest.param(_bump_slot, id="wrong_slot_value"),
    pytest.param(_bump_balance, id="wrong_eth_balance"),
    pytest.param(lambda rows: _drop_last(rows, "s"), id="dropped_slot_row"),
])
def test_a_planted_fault_is_counted(plant):
    t, batches = drive("small", SEEDS[0])
    want = KIND.expected_states(t, 0)[0]
    log = copy.deepcopy(access_log.raw_log_to_json(batches[0][2].blocks_log))
    plant(log[0])
    assert KIND.count_state_mismatches(want, log) >= 1


def test_the_kinds_bytecode_is_the_programs_template():
    assert KIND.TOKEN_CODE == token_template.TEMPLATE_CODE
    holder = bytes(range(20))
    assert KIND.balance_slot(holder) == token_template.balance_slot(holder)
    assert KIND.Call(holder, 0, holder, holder[::-1], 5, 1, 2, 3).calldata \
        == token_template.transfer_calldata(holder[::-1], 5)


def test_one_sender_cannot_fill_a_block():
    """Why the mix has several senders: the mempool holds 64 pending
    transactions a sender, and the 65th is refused."""
    mix = {**CELL_MIX, "senders": 1, "fresh_recipients_per_block":
           mempool.MAX_SENDER_SLOTS + 1,
           "calls_per_block": mempool.MAX_SENDER_SLOTS + 1}
    t = KIND.Traffic(mix, 11)
    node = Node(Genesis.from_json(t.genesis()))
    calls = t.batch(0)[0]
    for call in calls[:-1]:
        _submit(node, t, call)
    with pytest.raises(InvalidTransaction) as refused:
        _submit(node, t, calls[-1])
    assert refused.value.reason == mempool.SenderLimitError.reason
