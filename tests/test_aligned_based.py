"""Aligned-mode L1ProofVerifier and the based BlockFetcher follower."""

import pytest

from ethrex_tpu.l2.aligned import AlignedLayer, L1ProofVerifier
from ethrex_tpu.l2.based import BlockFetcher, FetchError
from ethrex_tpu.l2.l1_client import InMemoryL1
from ethrex_tpu.l2.rollup_store import RollupStore
from ethrex_tpu.l2.sequencer import Sequencer, SequencerConfig
from ethrex_tpu.node import Node
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.prover import protocol
from ethrex_tpu.prover.backend import get_backend

from tests.test_l2_pipeline import GENESIS, _transfer


def _setup():
    node = Node(Genesis.from_json(GENESIS))
    l1 = InMemoryL1(needed_prover_types=[protocol.PROVER_EXEC])
    seq = Sequencer(node, l1, SequencerConfig(
        needed_prover_types=(protocol.PROVER_EXEC,)))
    return node, l1, seq


def _commit_one_proven_batch(node, seq):
    node.submit_transaction(_transfer(0))
    seq.produce_block()
    batch = seq.commit_next_batch()
    assert batch is not None
    # prove it directly (skip the TCP fleet for these unit tests)
    backend = get_backend(protocol.PROVER_EXEC)
    from ethrex_tpu.guest.execution import ProgramInput

    stored = seq.rollup.get_prover_input(batch.number,
                                         seq.cfg.commit_hash)
    proof = backend.prove(ProgramInput.from_json(stored),
                          protocol.FORMAT_STARK)
    seq.rollup.store_proof(batch.number, protocol.PROVER_EXEC, proof)
    return batch


def test_aligned_submit_poll_verify():
    node, l1, seq = _setup()
    batch = _commit_one_proven_batch(node, seq)
    aligned = AlignedLayer(latency_polls=2)
    ver = L1ProofVerifier(seq.rollup, l1, aligned,
                          [protocol.PROVER_EXEC])
    assert ver.step() == "submitted"
    assert ver.step() == "pending"
    assert ver.step() == "verified"        # second poll -> included
    assert l1.last_verified_batch() == batch.number
    assert seq.rollup.get_batch(batch.number).verified
    assert ver.step() is None              # nothing left


def test_aligned_lost_submission_resubmits():
    node, l1, seq = _setup()
    _commit_one_proven_batch(node, seq)
    aligned = AlignedLayer(latency_polls=1)
    ver = L1ProofVerifier(seq.rollup, l1, aligned,
                          [protocol.PROVER_EXEC])
    assert ver.step() == "submitted"
    # the aggregation drops the submission behind the verifier's back
    aligned.submissions[ver.inflight["sid"]]["state"] = AlignedLayer.LOST
    assert ver.step() == "resubmitted"
    assert ver.step() == "verified"
    assert l1.last_verified_batch() == 1


def test_aligned_rejects_invalid_proof():
    node, l1, seq = _setup()
    batch = _commit_one_proven_batch(node, seq)
    proof = seq.rollup.get_proof(batch.number, protocol.PROVER_EXEC)
    proof["output"] = "0x" + "00" * 8  # corrupt
    aligned = AlignedLayer()
    ver = L1ProofVerifier(seq.rollup, l1, aligned,
                          [protocol.PROVER_EXEC])
    with pytest.raises(ValueError):
        ver.step()


def test_based_follower_imports_committed_batches():
    node, l1, seq = _setup()
    node.submit_transaction(_transfer(0))
    seq.produce_block()
    assert seq.commit_next_batch() is not None
    node.submit_transaction(_transfer(1))
    seq.produce_block()
    assert seq.commit_next_batch() is not None

    follower = Node(Genesis.from_json(GENESIS))
    rollup = RollupStore()
    fetcher = BlockFetcher(follower, l1, rollup)
    assert fetcher.fetch_once() == 2
    assert follower.store.latest_number() == node.store.latest_number()
    head = follower.store.get_canonical_block(follower.store.latest_number())
    assert head.header.state_root == \
        node.store.get_canonical_block(node.store.latest_number()) \
            .header.state_root
    assert rollup.get_batch(2).committed
    # idempotent: nothing new to fetch
    assert fetcher.fetch_once() == 0


def test_based_follower_detects_root_divergence():
    node, l1, seq = _setup()
    node.submit_transaction(_transfer(0))
    seq.produce_block()
    batch = seq.commit_next_batch()
    # corrupt the committed root on the (hostile) L1 record
    root, comm = l1.commitments[batch.number]
    l1.commitments[batch.number] = (b"\x11" * 32, comm)
    follower = Node(Genesis.from_json(GENESIS))
    fetcher = BlockFetcher(follower, l1)
    with pytest.raises(FetchError):
        fetcher.fetch_once()


def test_based_follower_records_fatal_divergence():
    """A FetchError inside the polling loop must not die as an unhandled
    daemon-thread exception: the fetcher records it and stops, so health
    checks surface the frozen-follower condition."""
    import time

    node, l1, seq = _setup()
    node.submit_transaction(_transfer(0))
    seq.produce_block()
    batch = seq.commit_next_batch()
    root, comm = l1.commitments[batch.number]
    l1.commitments[batch.number] = (b"\x22" * 32, comm)
    follower = Node(Genesis.from_json(GENESIS))
    fetcher = BlockFetcher(follower, l1)
    assert fetcher.healthy()
    fetcher.start(interval=0.01)
    # a guard against a hang, not a budget: the fetch is ~2 s of host
    # KZG alone and missed 5 s beside five other test workers
    deadline = time.time() + 60
    while fetcher.fatal is None and time.time() < deadline:
        time.sleep(0.01)
    assert not fetcher.healthy()
    assert "committed" in str(fetcher.fatal)
    fetcher.stop()


def test_check_coverage_rejects_downgrade():
    """The anti-downgrade hook (review finding): a tpu proof whose vm
    mode differs from the committer-recorded coverage is rejected —
    most importantly a claimed-log proof for a circuit-covered batch."""
    from ethrex_tpu.prover.tpu_backend import TpuBackend

    backend = TpuBackend()
    claimed = {"backend": protocol.PROVER_TPU, "output": "0x"}
    transfer = dict(claimed, vm={"mode": "transfer"})
    generic = dict(claimed, vm={"mode": "generic"})
    assert backend.check_coverage(transfer, "transfer")
    assert backend.check_coverage(generic, "generic")
    assert not backend.check_coverage(claimed, "transfer")
    assert not backend.check_coverage(claimed, "generic")
    assert not backend.check_coverage(transfer, "generic")
    # pre-metadata batches put no constraint
    assert backend.check_coverage(claimed, "")


def test_aligned_rejects_downgraded_transfer_batch():
    """AlignedLayer.submit refuses a claimed-log proof for a batch the
    committer marked transfer-covered, before any settlement."""
    aligned = AlignedLayer()
    downgraded = {"backend": protocol.PROVER_TPU, "format": "stark",
                  "output": "0x", "write_log": [],
                  "depth": 1, "seg_periods": 8,
                  "state_proof": {}, "proof": {}}
    with pytest.raises(ValueError, match="downgrades its vm coverage"):
        aligned.submit(7, 7, {protocol.PROVER_TPU: [downgraded]},
                       expected_modes={7: "transfer"})
