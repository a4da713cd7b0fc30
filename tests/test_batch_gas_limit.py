"""The committer's batch gas limit (upstream's --committer.batch-gas-limit,
docs/l2/deployment/vanilla.md:94) and the spans of the sequencer: a batch
ends at the last whole block within the limit, a lone block over it is a
batch of its own, no limit keeps every range as it was, and the
commit, the blocks and the L1 verification are spanned into the traces
the benchmark reads.  CPU, exec prover, no JAX compile."""

import pytest

from ethrex_tpu import cli
from ethrex_tpu.crypto import secp256k1
from ethrex_tpu.l2.l1_client import InMemoryL1
from ethrex_tpu.l2.rollup_store import RollupStore
from ethrex_tpu.l2.sequencer import Sequencer, SequencerConfig
from ethrex_tpu.node import Node
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.primitives.transaction import TYPE_DYNAMIC_FEE, Transaction
from ethrex_tpu.prover import protocol
from ethrex_tpu.prover.client import ProverClient
from ethrex_tpu.utils import tracing
from ethrex_tpu.utils.tracing import TRACER, critical_path

SECRET = 0x7A5
SENDER = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(SECRET))
GENESIS = {
    "config": {"chainId": 65536999, "terminalTotalDifficulty": 0,
               "shanghaiTime": 0, "cancunTime": 0},
    "alloc": {"0x" + SENDER.hex(): {"balance": hex(10**21)}},
    "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7", "timestamp": "0x0",
}
TRANSFER = 21_000
EXEC = protocol.PROVER_EXEC


def _transfer(nonce):
    return Transaction(
        tx_type=TYPE_DYNAMIC_FEE, chain_id=65536999, nonce=nonce,
        max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
        gas_limit=TRANSFER, to=bytes([0xAA, nonce % 256]) + b"\x00" * 18,
        value=100 + nonce).sign(SECRET)


class Chain:
    """A node, a dev L1 and a sequencer whose actors never run: the
    test calls them."""

    def __init__(self, limit=None, node=None, l1=None, rollup=None):
        self.node = node or Node(Genesis.from_json(GENESIS))
        self.l1 = l1 or InMemoryL1(needed_prover_types=[EXEC])
        self.seq = Sequencer(self.node, self.l1, SequencerConfig(
            needed_prover_types=(EXEC,), batch_gas_limit=limit),
            rollup=rollup)
        self.nonce = 0

    def blocks(self, *sizes):
        """One block a size, each of that many transfers."""
        for size in sizes:
            for _ in range(size):
                self.node.submit_transaction(_transfer(self.nonce))
                self.nonce += 1
            block = self.seq.produce_block()
            assert len(block.body.transactions) == size

    def commit_all(self):
        out = []
        while (batch := self.seq.commit_next_batch()) is not None:
            out.append(batch)
        return out


def _ranges(batches):
    return [(b.first_block, b.last_block) for b in batches]


def test_a_batch_ends_at_the_last_whole_block_within_the_limit():
    chain = Chain(limit=3 * TRANSFER)
    chain.blocks(2, 1, 1, 2, 1)
    assert _ranges(chain.commit_all()) == [(1, 2), (3, 4), (5, 5)]
    # the blocks left over waited for the next commit: each commit takes
    # one batch, and the head moves on under it
    chain.blocks(1, 1, 1, 1)
    assert _ranges(chain.commit_all()) == [(6, 8), (9, 9)]


def test_a_lone_block_over_the_limit_is_a_batch_of_its_own():
    chain = Chain(limit=TRANSFER)
    chain.blocks(2, 3, 1, 1)
    got = chain.commit_all()
    assert _ranges(got) == [(1, 1), (2, 2), (3, 3), (4, 4)]
    assert [chain.l1.get_committed_commitment(b.number) for b in got] \
        == [b.commitment for b in got]


def test_no_limit_keeps_every_range_and_commitment_as_it_was(monkeypatch):
    """A seeded chain (the clock held, so the blocks are the same bytes)
    committed under no limit and under one no batch reaches: the same
    ranges, byte-identical commitments, and each batch is every block up
    to the head."""
    import time

    monkeypatch.setattr(time, "time", lambda: 1_750_000_000.0)
    runs = []
    for limit in (None, 10**12):
        chain = Chain(limit=limit)
        chain.blocks(3, 0, 2)
        first = chain.commit_all()
        chain.blocks(1, 4)
        runs.append(first + chain.commit_all())
    plain, large = runs
    assert _ranges(plain) == [(1, 3), (4, 5)]
    assert [(b.first_block, b.last_block, b.state_root, b.commitment)
            for b in plain] == \
        [(b.first_block, b.last_block, b.state_root, b.commitment)
         for b in large]
    assert SequencerConfig().batch_gas_limit is None


@pytest.mark.parametrize("argv, env, want", [
    (["--committer.batch-gas-limit", "210000"], None, 210_000),
    ([], "63000", 63_000),
    ([], None, None),
])
def test_the_option_reaches_the_sequencer(monkeypatch, argv, env, want):
    if env is None:
        monkeypatch.delenv("ETHREX_COMMITTER_BATCH_GAS_LIMIT",
                           raising=False)
    else:
        monkeypatch.setenv("ETHREX_COMMITTER_BATCH_GAS_LIMIT", env)
    args = cli.build_parser().parse_args(
        ["l2", "--dev", "--http.port", "0", "--provers", EXEC,
         "--block-time", "30", "--commit-interval", "30", *argv])
    assert args.batch_gas_limit == want
    stack = cli.start_l2_stack(args)
    try:
        assert stack.seq.cfg.batch_gas_limit == want
    finally:
        stack.seq.stop()
        stack.server.stop()


def test_a_limited_batch_is_rebuilt_from_the_l1_to_its_range():
    """The commit landed on the L1 and the rollup store lost it: a
    sequencer started on the same chain and L1 rebuilds batch 1 to the
    limited range, not to the head, with the same commitment."""
    chain = Chain(limit=2 * TRANSFER)
    chain.blocks(1, 1, 1, 1)
    batch = chain.seq.commit_next_batch()
    assert (batch.first_block, batch.last_block) == (1, 2)
    again = Chain(limit=2 * TRANSFER, node=chain.node, l1=chain.l1,
                  rollup=RollupStore())
    rebuilt = again.seq.rollup.get_batch(1)
    assert (rebuilt.first_block, rebuilt.last_block) == (1, 2)
    assert rebuilt.commitment == batch.commitment \
        == chain.l1.get_committed_commitment(1)
    assert again.seq.rebuilt_batches_total == 1
    assert _ranges(again.commit_all()) == [(3, 4)]


def test_a_limited_batch_is_recommitted_verbatim_after_a_reorg():
    chain = Chain(limit=2 * TRANSFER)
    chain.blocks(1, 1, 1)
    batch = chain.seq.commit_next_batch()
    assert chain.l1.reorg(1) == 0
    chain.seq.update_state()
    again = chain.seq.commit_next_batch()
    assert again.number == 1 and chain.seq.recommits_total == 1
    assert (again.first_block, again.last_block) == (1, 2)
    assert chain.l1.get_committed_commitment(1) == batch.commitment
    assert _ranges(chain.commit_all()) == [(3, 3)]


def test_the_stack_is_spanned_into_the_batch_traces():
    """`seq.block` a block; `seq.commit` and its four children in the
    trace of the batch they seal; `l1.verify` in the trace of its
    range's first batch, beside `proof.verify` and `proof.settle`.  The
    commit stays off the batch's critical path, the L1's verification is
    on it as settlement, and the trace keeps within the span budget."""
    chain = Chain(limit=2 * TRANSFER)
    chain.seq.coordinator.start()
    try:
        with tracing.trace_context(None) as block_trace:
            chain.blocks(2)
        (block,) = TRACER.get_trace(block_trace)["spans"]
        assert block["name"] == "seq.block"
        assert block["attrs"] == {"txs": 2, "gas": 2 * TRANSFER}
        chain.blocks(1, 1)
        assert len(chain.commit_all()) == 2
        client = ProverClient(EXEC, [("127.0.0.1",
                                      chain.seq.coordinator.port)])
        assert client.poll_once() == 1 and client.poll_once() == 1
        assert chain.seq.send_proofs() == (1, 2)
        traces = chain.seq.coordinator.batch_traces
        spans = TRACER.get_trace(traces[1])["spans"]
        by_name = {}
        for s in spans:
            by_name.setdefault(s["name"], []).append(s)
        (commit,) = by_name["seq.commit"]
        assert commit["attrs"] == {"batch": 1, "blocks": 1, "txs": 2,
                                   "gas": 2 * TRANSFER}
        for name in ("seq.witness", "seq.blobs", "seq.l1_commit",
                     "seq.store"):
            (child,) = by_name[name]
            assert child["parentId"] == commit["spanId"]
            assert commit["start"] <= child["start"] \
                <= child["start"] + child["seconds"] \
                <= commit["start"] + commit["seconds"] + 1e-6
        assert by_name["seq.blobs"][0]["attrs"]["blobs"] >= 1
        (verify,) = by_name["l1.verify"]
        assert verify["attrs"] == {"first": 1, "last": 2}
        assert {"proof.verify", "proof.settle", "prover.assign"} \
            <= set(by_name)
        second = {s["name"] for s in TRACER.get_trace(traces[2])["spans"]}
        assert "seq.commit" in second and "l1.verify" not in second
        cp = critical_path({"traceId": "t", "spans": spans})
        lifecycle = [s for s in spans if not s["name"].startswith("seq.")]
        assert cp == critical_path({"traceId": "t", "spans": lifecycle})
        assert cp["components"].get("settle", 0) > 0
        assert len(spans) <= tracing.BATCH_SPAN_BUDGET
    finally:
        chain.seq.stop()


def test_proof_verify_says_how_its_openings_were_folded(monkeypatch):
    """`proof.verify` carries `fold`, the engine of the Merkle openings,
    and `openings`, how many the STARK backend's check folded; the
    sequencer names neither, so an exec proof's span has none.  Here
    batch 2's audit runs through the STARK backend's check, stood in by
    one that folds three openings."""
    from ethrex_tpu.ops import merkle, poseidon2
    from ethrex_tpu.prover.backend import ExecBackend
    from ethrex_tpu.prover.tpu_backend import TpuBackend

    def folding(self, proof):
        merkle.verify_openings([0] * 8, [0, 1, 2], [[1]] * 3, [[]] * 3, 0)
        return None, b""

    monkeypatch.setattr(TpuBackend, "_check_starks", folding)
    verify = ExecBackend.verify

    def audited(self, proof):
        sp = tracing.current_span()
        if sp is not None and sp.name == "proof.verify" \
                and sp.attrs["batch"] == 2:
            assert TpuBackend().verify(proof)
        return verify(self, proof)

    monkeypatch.setattr(ExecBackend, "verify", audited)
    chain = Chain(limit=TRANSFER)
    chain.seq.coordinator.start()
    try:
        chain.blocks(1, 1)
        assert len(chain.commit_all()) == 2
        client = ProverClient(EXEC, [("127.0.0.1",
                                      chain.seq.coordinator.port)])
        assert client.poll_once() == 1 and client.poll_once() == 1
        assert chain.seq.send_proofs() == (1, 2)
        attrs = {}
        for n in (1, 2):
            trace = chain.seq.coordinator.batch_traces[n]
            (span,) = [s for s in TRACER.get_trace(trace)["spans"]
                       if s["name"] == "proof.verify"]
            attrs[n] = span["attrs"]
        assert "fold" not in attrs[1] and "openings" not in attrs[1]
        assert attrs[2]["fold"] == poseidon2.engine() == "native"
        assert attrs[2]["openings"] == 3
    finally:
        chain.seq.stop()


def test_spans_never_fail_a_commit_or_a_settlement(monkeypatch):
    """Tracing that breaks inside is telemetry lost, not a batch lost."""
    def broken(*args, **kwargs):
        raise RuntimeError("tracer down")

    monkeypatch.setattr(tracing.Span, "to_json", broken)
    chain = Chain(limit=TRANSFER)
    chain.seq.coordinator.start()
    try:
        chain.blocks(1, 1)
        assert _ranges(chain.commit_all()) == [(1, 1), (2, 2)]
        client = ProverClient(EXEC, [("127.0.0.1",
                                      chain.seq.coordinator.port)])
        assert client.poll_once() == 1
        assert chain.seq.send_proofs() == (1, 1)
        assert chain.l1.last_verified_batch() == 1
    finally:
        chain.seq.stop()
