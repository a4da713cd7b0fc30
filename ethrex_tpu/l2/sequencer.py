"""L2 sequencer: the actor set from the reference's
crates/l2/sequencer/mod.rs:47 start_l2 — BlockProducer, L1Committer,
ProofCoordinator (own module), L1ProofSender, L1Watcher, StateUpdater —
re-expressed as timer-driven components over the Node + RollupStore +
L1Client.
"""

from __future__ import annotations

import dataclasses
import logging
import random
import threading
import time
import types

log = logging.getLogger("ethrex_tpu.l2.sequencer")

from ..crypto.keccak import keccak256
from ..guest.execution import ProgramInput
from ..guest.witness import generate_witness
from ..node import Node
from ..primitives.transaction import TYPE_PRIVILEGED, Transaction
from ..prover import protocol
from ..utils import faults, tracing
from ..utils.metrics import observe_actor_iteration
from .eth_client import is_transient
from .l1_client import L1Client
from .leadership import FencedError, LeadershipManager
from .proof_coordinator import ProofCoordinator
from .rollup_store import Batch, RollupStore


class SettlementDivergence(RuntimeError):
    """The local settlement records and the L1 disagree about an
    already-settled batch (same number, different commitment), or a batch
    the L1 holds cannot be reproduced from the canonical chain.
    Deliberately NOT a transient error: continuing would settle the L2 on
    a fork, so the sequencer fails fast with a diagnostic instead."""


@dataclasses.dataclass
class SequencerConfig:
    block_time: float = 1.0
    commit_interval: float = 2.0
    # the committer's bound on one batch (reference:
    # --committer.batch-gas-limit, docs/l2/deployment/vanilla.md:94): a
    # batch ends at the last whole block whose cumulative gas_used stays
    # within it, a lone block over it is a batch of its own, and the
    # blocks left over wait for the next tick.  None: every block up to
    # the head
    batch_gas_limit: int | None = None
    proof_send_interval: float = 2.0
    watcher_interval: float = 1.0
    needed_prover_types: tuple = (protocol.PROVER_TPU,)
    commit_hash: str = protocol.PROTOCOL_VERSION
    # failure handling (reference: the fatal-subsystem cancellation token
    # pattern, cmd/ethrex/ethrex.rs, + per-actor health endpoints).
    # Deterministic errors (L1Error, logic bugs) burn max_actor_failures;
    # transient ones (TransportError/ConnectionError/timeouts — an L1
    # outage) get the much larger max_transient_failures budget plus
    # jittered backoff, so a flaky L1 degrades instead of killing the
    # sequencer (docs/L1_SETTLEMENT_RESILIENCE.md)
    max_actor_failures: int = 10
    max_transient_failures: int = 200
    max_backoff_factor: int = 32
    backoff_jitter: float = 0.25
    # deposits shallower than this many L1 confirmations are not ingested
    # (1 = included in any block; raise for reorg safety)
    l1_confirmation_depth: int = 1
    # prover resilience (docs/PROVER_RESILIENCE.md): assignment lease
    # length (heartbeats extend it), the hard cap on how long heartbeats
    # can keep one assignment alive (None -> coordinator default of
    # 6 leases; bounds hung provers), and how many failed assignments of
    # a batch to its primary prover type trigger the exec fallback
    prover_lease_timeout: float = 600.0
    prover_max_lease_lifetime: float | None = None
    prover_quarantine_threshold: int = 3
    # fleet scheduling (docs/AGGREGATION.md): "fleet" = size-aware
    # placement + p99 hedging + work stealing; "fcfs" pins the original
    # first-come-first-served scan
    scheduler_policy: str = "fleet"
    # recursive proof aggregation (docs/AGGREGATION.md): when enabled,
    # pending runs of >= aggregation_min_batches settle as ONE
    # aggregated proof per prover type (send_proofs defers to the
    # aggregate_proofs actor for those runs and stays the per-batch
    # fallback for everything shorter)
    aggregation_enabled: bool = False
    aggregation_interval: float = 2.0
    aggregation_min_batches: int = 2
    aggregation_max_batches: int = 16
    # sequencer HA (docs/SEQUENCER_HA.md): ha_role None keeps the
    # classic single-sequencer mode (no lease, unfenced writes).
    # "leader" and "follower" pick the starting posture of an HA pair —
    # both run the same candidacy loop against the L1 lease cell; the
    # follower just defers its first bid by one lease ttl so the
    # configured leader wins the uncontested race
    ha_role: str | None = None
    leader_lease: float = 3.0
    ha_node_id: str | None = None


@dataclasses.dataclass
class ActorHealth:
    """Per-actor failure/backoff state, exposed via ethrex_health."""

    name: str
    runs: int = 0
    consecutive_failures: int = 0        # deterministic errors
    consecutive_transient: int = 0       # transport/connection errors
    last_error: str | None = None
    last_error_class: str | None = None  # "transient" | "deterministic"
    last_success: float | None = None
    # loop-iteration latency (failed iterations count too — a slow
    # failure is still a stall)
    timed_runs: int = 0
    last_seconds: float | None = None
    total_seconds: float = 0.0
    max_seconds: float = 0.0

    @property
    def healthy(self) -> bool:
        return self.consecutive_failures == 0 \
            and self.consecutive_transient == 0

    def note_duration(self, seconds: float):
        self.timed_runs += 1
        self.last_seconds = seconds
        self.total_seconds += seconds
        if seconds > self.max_seconds:
            self.max_seconds = seconds

    def to_json(self) -> dict:
        return {
            "healthy": self.healthy,
            "runs": self.runs,
            "consecutiveFailures": self.consecutive_failures,
            "transientFailures": self.consecutive_transient,
            "lastError": self.last_error,
            "lastErrorClass": self.last_error_class,
            "lastSuccess": self.last_success,
            "loop": {
                "lastSeconds": self.last_seconds,
                "avgSeconds": (self.total_seconds / self.timed_runs
                               if self.timed_runs else None),
                "maxSeconds": self.max_seconds if self.timed_runs
                else None,
            },
        }


class Sequencer:
    """Wires all L2 actors (reference: start_l2)."""

    # the timer-driven actor set; start() loops over these names and the
    # admin pause/resume surface validates against them (keeping the RPC
    # and the loop keyed to one registry instead of magic strings)
    ACTOR_NAMES = ("produce_block", "commit_next_batch", "send_proofs",
                   "aggregate_proofs", "watch_l1", "update_state")

    def __init__(self, node: Node, l1: L1Client,
                 config: SequencerConfig | None = None,
                 rollup: RollupStore | None = None):
        self.node = node
        self.l1 = l1
        self.cfg = config or SequencerConfig()
        self.rollup = rollup if rollup is not None else RollupStore()
        self.coordinator = ProofCoordinator(
            self.rollup, needed_types=list(self.cfg.needed_prover_types),
            commit_hash=self.cfg.commit_hash,
            lease_timeout=self.cfg.prover_lease_timeout,
            quarantine_threshold=self.cfg.prover_quarantine_threshold,
            max_lease_lifetime=self.cfg.prover_max_lease_lifetime,
            scheduler_policy=self.cfg.scheduler_policy)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # checkpoint resume (reference: l1_committer.rs:389 per-batch
        # checkpoints): a persistent rollup store carries the batch chain
        # and the deposit cursor across restarts, so a killed sequencer
        # continues at the right batch instead of re-committing from 1
        # the durable cursor counts only INCLUDED deposits; anything the
        # L1 reports beyond it is re-fetched as pending after a restart,
        # so an in-flight deposit is never lost (a crash between block
        # production and the meta write re-creates the privileged tx,
        # which execution then rejects on its fixed nonce = deposit index)
        self._deposit_cursor = int(self.rollup.get_meta(
            "deposit_cursor_included", 0))
        latest = self.rollup.latest_batch_number()
        self.last_batched_block = (
            self.rollup.get_batch(latest).last_block if latest else 0)
        if self.last_batched_block > self.node.store.latest_number():
            # the chain lost its unflushed tail in a crash while the
            # rollup checkpoints survived: regenerate the missing blocks
            # from the stored batch prover inputs (reference:
            # l1_committer.rs:1620 regenerate_state)
            self._regenerate_chain()
        self.pending_privileged: list[Transaction] = []
        self._lock = threading.RLock()
        self.health: dict[str, ActorHealth] = {}
        self.fatal: tuple[str, str] | None = None
        self.on_fatal = None  # callback(actor, error) for orchestrators
        self.started_at: float | None = None  # stall-watchdog baseline
        # admin controls (reference: admin_server.rs — committer
        # start/stop with optional delay, sequencer stop-at-batch)
        self.paused: set[str] = set()
        self._resume_at: dict[str, float] = {}
        self.stop_at_batch: int | None = None
        # L1 settlement resilience (docs/L1_SETTLEMENT_RESILIENCE.md):
        # batches whose commitment an L1 reorg dropped, queued for
        # re-submission, plus the counters ethrex_health exposes
        self._settlement_lock = threading.RLock()
        self._recommit_queue: set[int] = set()
        self.reorgs_total = 0
        self.recommits_total = 0
        self.commits_adopted_total = 0
        self.rebuilt_batches_total = 0
        # the committer's last in-flight commit attempt (number, first
        # block, artifacts): when the L1 accepts a commit but the
        # acknowledgment is lost in-process, the exact artifacts that
        # were settled are still in hand — the rebuild adopts them after
        # checking them against the on-chain record instead of paying a
        # full candidate search while block production races ahead
        self._last_commit_attempt = None
        self._backoff_rng = random.Random(0)
        # startup reconciliation: close the crash window where the L1
        # accepted settlement the local store never recorded, and refuse
        # to run at all on a local/L1 divergence
        self._reconcile_with_l1()
        # the recursive-aggregation stage (docs/AGGREGATION.md) —
        # constructed after reconciliation so a crash-mid-aggregation
        # marker is classified against the L1's recovered verified tip
        from .aggregator import ProofAggregator

        self.aggregator = ProofAggregator(
            self.rollup, self.l1, coordinator=self.coordinator,
            needed_types=list(self.cfg.needed_prover_types),
            commit_hash=self.cfg.commit_hash,
            min_batches=self.cfg.aggregation_min_batches,
            max_batches=self.cfg.aggregation_max_batches,
            epoch_source=self._epoch)
        # sequencer HA (docs/SEQUENCER_HA.md): the leadership manager
        # owns the L1 lease; promotion/demotion park and unpark the
        # actor set through the admin pause surface
        self.leadership: LeadershipManager | None = None
        self.promotions_total = 0
        self.reconciled_at: float | None = time.time()
        if self.cfg.ha_role:
            if self.cfg.ha_role not in ("leader", "follower"):
                raise ValueError(
                    f"ha_role must be 'leader' or 'follower', "
                    f"got {self.cfg.ha_role!r}")
            if not self.l1.supports_leases():
                raise ValueError(
                    "sequencer HA requires an L1 client with a leader-"
                    "lease cell (this one cannot fence a deposed leader)")
            node_id = self.cfg.ha_node_id or \
                f"seq-{self.cfg.ha_role}-{id(self):x}"
            self.leadership = LeadershipManager(
                self.l1, node_id, ttl=self.cfg.leader_lease,
                on_promote=self._promote, on_demote=self._demote,
                candidacy_delay=(0.0 if self.cfg.ha_role == "leader"
                                 else self.cfg.leader_lease))
        # terminal-stop guard (idempotent drain; safe in follower mode
        # where the actor threads were never started)
        self._stopped = False
        self._stop_result = True
        self._stop_guard = threading.Lock()

    def _regenerate_chain(self):
        """Re-import committed-batch blocks the chain store lost (crash
        between batch checkpoint and chain flush).  Every committed batch
        carries its full ProgramInput, so the blocks are replayed through
        normal validation and fork choice."""
        from ..blockchain.fork_choice import apply_fork_choice
        from ..guest.execution import ProgramInput

        for number in sorted(self.rollup.batches):
            batch = self.rollup.batches[number]
            if batch.last_block <= self.node.store.latest_number():
                continue
            stored = self.rollup.get_prover_input(number,
                                                  self.cfg.commit_hash)
            if stored is None:
                raise RuntimeError(
                    f"cannot regenerate batch {number}: no stored input")
            pi = ProgramInput.from_json(stored)
            tip = None
            for block in pi.blocks:
                if block.header.number <= self.node.store.latest_number():
                    continue
                self.node.chain.add_block(block)
                tip = block.hash
            if tip is not None:
                apply_fork_choice(self.node.store, tip, tip, tip)
        log.info("regenerated chain state up to block %d from rollup "
                 "checkpoints", self.node.store.latest_number())

    # ------------------------------------------------------------------
    # startup reconciliation (reference: state_updater.rs settlement
    # reconciliation + l1_committer.rs ensure_checkpoint_for_committed_batch)
    # ------------------------------------------------------------------
    def _reconcile_with_l1(self) -> None:
        """Compare local settlement records against the L1 at boot.

        Three outcomes per batch: (a) L1 is ahead of the local store —
        the commit-crash window; the missing batch record is rebuilt from
        the canonical chain and adopted, instead of re-committing into a
        permanent "out of order" fatal loop.  (b) Local flags lag the L1
        (crash between commit/verify and the flag write) — adopted
        through the store setters.  (c) The two records DIVERGE for the
        same batch number — SettlementDivergence, fail fast."""
        try:
            l1_committed = self.l1.last_committed_batch()
            l1_verified = self.l1.last_verified_batch()
        except NotImplementedError:
            return
        except Exception as e:  # noqa: BLE001 — classify before giving up
            if is_transient(e):
                # L1 unreachable at boot: run anyway; the update_state
                # actor reconciles as soon as it answers again
                log.warning("L1 unreachable during startup "
                            "reconciliation (%s); continuing", e)
                return
            raise
        local = self.rollup.latest_batch_number()
        for n in range(1, min(local, l1_committed) + 1):
            batch = self.rollup.get_batch(n)
            if batch is None or not batch.commitment:
                continue
            onchain = self.l1.get_committed_commitment(n)
            if onchain is not None and onchain != batch.commitment:
                raise SettlementDivergence(
                    f"batch {n}: local commitment "
                    f"{batch.commitment.hex()[:16]} != L1 commitment "
                    f"{onchain.hex()[:16]} — the rollup store and the "
                    f"settlement contract describe different chains; "
                    f"refusing to settle on a fork")
        for n in range(local + 1, l1_committed + 1):
            self._rebuild_batch_from_l1(n)
        for n in range(1, l1_committed + 1):
            self._repair_partial_batch(n)
        for n in sorted(self.rollup.batches):
            b = self.rollup.get_batch(n)
            if n <= l1_committed and not b.committed:
                self.rollup.set_settlement(n, committed=True)
            if n <= l1_verified and not b.verified:
                self.rollup.set_settlement(n, verified=True)

    def _repair_partial_batch(self, number: int) -> None:
        """A narrower crash window: the batch record survived but the
        crash lost its prover input and/or DA bundle (the writes after
        store_batch).  Both are deterministic functions of the canonical
        blocks, so they are recomputed — guarded by the commitment, which
        must reproduce exactly."""
        batch = self.rollup.get_batch(number)
        if batch is None:
            return
        missing_input = self.rollup.get_prover_input(
            number, self.cfg.commit_hash) is None
        missing_blobs = self.rollup.get_blobs_bundle(number) is None
        if not missing_input and not missing_blobs:
            return
        art = self._build_batch_artifacts(number, batch.first_block,
                                          batch.last_block)
        if art is None or (batch.commitment
                           and art.commitment != batch.commitment):
            raise SettlementDivergence(
                f"batch {number} record is missing its "
                f"{'prover input' if missing_input else 'DA bundle'} and "
                f"the canonical chain no longer reproduces its commitment")
        if missing_blobs:
            self.rollup.store_blobs_bundle(number, art.bundle)
        if missing_input:
            self.rollup.store_prover_input(number, self.cfg.commit_hash,
                                           art.program_input.to_json())
        self.rebuilt_batches_total += 1
        log.warning("repaired partial record of batch %d (rebuilt %s)",
                    number,
                    "input+blobs" if missing_input and missing_blobs
                    else "input" if missing_input else "blobs")

    def _rebuild_batch_from_l1(self, number: int) -> None:
        """The verified crash window in commit_next_batch: the L1
        accepted batch `number`, the process died before the rollup store
        heard about it.  The blocks are still canonical, so the whole
        batch record (witness, prover input, DA bundle, commitment) is
        recomputed and checked against what the L1 actually settled."""
        first = self.last_batched_block + 1
        head = self.node.store.latest_number()
        onchain_root = self.l1.get_committed_state_root(number)
        onchain_commitment = self.l1.get_committed_commitment(number)
        if onchain_root is None and onchain_commitment is None:
            raise SettlementDivergence(
                f"L1 has batch {number} committed but exposes neither its "
                f"state root nor its commitment; cannot rebuild the lost "
                f"batch record")
        art = None
        # fast path: the lost acknowledgment happened in THIS process, so
        # the artifacts the L1 just accepted are the committer's last
        # attempt — adopt them if the on-chain record confirms the match
        # (a full candidate search below stays for genuine restarts,
        # where production is not racing the rebuild)
        cached = self._last_commit_attempt
        if (cached is not None and cached[0] == number
                and cached[1] == first
                and (onchain_commitment is None
                     or cached[2].commitment == onchain_commitment)
                and (onchain_root is None
                     or cached[2].state_root == onchain_root)):
            art = cached[2]
        if art is None and onchain_root is not None:
            candidates = [
                b for b in range(first, head + 1)
                if (blk := self.node.store.get_canonical_block(b))
                is not None and blk.header.state_root == onchain_root]
        elif art is None:
            candidates = list(range(first, head + 1))
        else:
            candidates = []
        for last in candidates:
            cand = self._build_batch_artifacts(number, first, last)
            if cand is None:
                continue
            if onchain_commitment is not None \
                    and cand.commitment != onchain_commitment:
                continue
            art = cand
            break
        if art is None:
            raise SettlementDivergence(
                f"L1 has batch {number} committed but no canonical block "
                f"range [{first}..{head}] reproduces it — the chain store "
                f"and the L1 describe different chains (or the chain tail "
                f"was lost beyond recovery)")
        last_block = art.blocks[-1].header.number
        batch = Batch(number=number, first_block=first,
                      last_block=last_block, state_root=art.state_root,
                      commitment=art.commitment, vm_mode=art.vm_mode)
        with self.rollup.write_group(epoch=self._epoch()):
            self.rollup.store_batch(batch)
            self.rollup.store_blobs_bundle(number, art.bundle)
            self.rollup.store_prover_input(number, self.cfg.commit_hash,
                                           art.program_input.to_json())
            self.rollup.set_committed(number, art.commitment)
        self.last_batched_block = last_block
        self.rebuilt_batches_total += 1
        log.warning("rebuilt batch %d (blocks %d..%d) from the canonical "
                    "chain after a commit-crash window", number, first,
                    last_block)

    # ------------------------------------------------------------------
    # sequencer HA: fencing + promotion/demotion (docs/SEQUENCER_HA.md)
    # ------------------------------------------------------------------
    def _epoch(self) -> int | None:
        """The fencing token stamped on externally-visible writes;
        None in single-sequencer (non-HA) mode."""
        leadership = getattr(self, "leadership", None)
        return leadership.epoch if leadership is not None else None

    def _fence(self) -> int | None:
        """Fence checkpoint before an externally-visible write: raises
        FencedError unless this node currently holds the lease (no-op
        without HA).  The returned epoch is captured ONCE per operation
        and stamped on every leg — if the lease moves mid-operation the
        sinks reject the stale token."""
        leadership = getattr(self, "leadership", None)
        if leadership is None:
            faults.inject("seq.fence")
            return None
        return leadership.check()

    def _promote(self):
        """Promotion IS the crash-recovery startup path (Crash-Only
        Software, PAPERS.md): fence the store at the new epoch, refresh
        the committer position from the durable checkpoints the follower
        accumulated while chain-following, run the PR-2 reconciliation
        (journal replay already happened when the store opened), restart
        the proof coordinator so the prover fleet re-homes here, then
        unpark the actors.  At most one uncommitted batch is re-derived
        — everything settled is adopted, never re-committed."""
        epoch = self.leadership.epoch
        if epoch is None:
            raise FencedError("promotion without a lease epoch")
        self.rollup.fence(epoch)
        # the follower's chain advanced via the block fetcher while the
        # actors were parked: recompute the batch cursor before actors
        # resume, or the committer would span an already-settled range
        latest = self.rollup.latest_batch_number()
        self.last_batched_block = (
            self.rollup.get_batch(latest).last_block if latest else 0)
        if self.last_batched_block > self.node.store.latest_number():
            self._regenerate_chain()
        self._deposit_cursor = int(self.rollup.get_meta(
            "deposit_cursor_included", 0))
        self._last_commit_attempt = None
        with self._settlement_lock:
            self._recommit_queue.clear()
        self._reconcile_with_l1()
        self.reconciled_at = time.time()
        # re-home the prover fleet: the coordinator serves assignments
        # from this node now; prover leases and phase checkpoints
        # survive the move (docs/PROVER_RESILIENCE.md), so in-flight
        # proofs resume instead of restarting
        self.coordinator.start()
        for name in self.ACTOR_NAMES:
            self.resume_actor(name)
        self.promotions_total += 1
        log.info("promoted to leader at epoch %d", epoch)

    def _demote(self):
        """Deposed (fenced write, renewal starvation, or clean step-
        down): park every actor and stop serving the prover fleet.  The
        process stays alive as a hot standby — caches warm, chain
        following — and re-enters candidacy through the leadership
        loop."""
        for name in self.ACTOR_NAMES:
            self.pause_actor(name)
        try:
            self.coordinator.stop(timeout=2.0)
        except Exception:  # noqa: BLE001 — may never have started
            pass
        log.warning("demoted to follower; actors parked")

    # ------------------------------------------------------------------
    # BlockProducer (reference: block_producer.rs produce_block)
    # ------------------------------------------------------------------
    def produce_block(self):
        from ..primitives.transaction import TYPE_PRIVILEGED

        with self._lock, tracing.span("seq.block") as sp:
            forced = list(self.pending_privileged)
            block = self.node.produce_block(forced_txs=forced)
            tracing.set_attrs(sp, txs=len(block.body.transactions),
                              gas=block.header.gas_used)
            included = {tx.hash for tx in block.body.transactions}
            self.pending_privileged = [
                tx for tx in self.pending_privileged
                if tx.hash not in included]
            # checkpoint the durable deposit cursor: a privileged tx's
            # nonce IS its deposit index
            done = [tx.nonce + 1 for tx in block.body.transactions
                    if tx.tx_type == TYPE_PRIVILEGED]
            if done:
                cur = int(self.rollup.get_meta(
                    "deposit_cursor_included", 0))
                if max(done) > cur:
                    self.rollup.set_meta("deposit_cursor_included",
                                         max(done))
            return block

    # ------------------------------------------------------------------
    # L1Watcher (reference: l1_watcher.rs — deposits -> privileged txs)
    # ------------------------------------------------------------------
    def watch_l1(self):
        from .l1_client import make_deposit_tx

        with self._lock:
            faults.inject("l1.get_deposits")
            deposits = self.l1.get_deposits(self._deposit_cursor)
            depth = self.cfg.l1_confirmation_depth
            head = None
            if depth > 1:
                try:
                    head = self.l1.get_block_number()
                except NotImplementedError:
                    head = None  # L1 without a block surface: ingest all
            for dep in deposits:
                if head is not None and dep.l1_block:
                    if head - dep.l1_block + 1 < depth:
                        # too shallow — a reorg could still drop it; later
                        # deposits are younger still, so stop here to keep
                        # the cursor contiguous
                        break
                tx = make_deposit_tx(self.node.config.chain_id, dep)
                self.pending_privileged.append(tx)
                self._deposit_cursor += 1

    # ------------------------------------------------------------------
    # L1Committer (reference: l1_committer.rs commit_next_batch_to_l1)
    # ------------------------------------------------------------------
    def _build_batch_artifacts(self, number: int, first: int,
                               last: int) -> types.SimpleNamespace | None:
        """Deterministically recompute everything batch `number` over
        blocks [first, last] carries: witness, prover input, DA bundle,
        commitment, vm mode.  Shared by the committer and startup
        reconciliation — the same block range always reproduces the same
        commitment, which is what makes commits idempotent and lost batch
        records rebuildable."""
        blocks = [self.node.store.get_canonical_block(n)
                  for n in range(first, last + 1)]
        if not blocks or any(b is None for b in blocks):
            return None
        coarse_log: list = []
        batch_receipts: list = []
        with tracing.span("seq.witness"):
            witness = generate_witness(self.node.chain, blocks,
                                       write_log=coarse_log,
                                       receipts_out=batch_receipts)
            program_input = ProgramInput(blocks=blocks, witness=witness,
                                         config=self.node.config)
        state_root = blocks[-1].header.state_root
        privileged_hashes = [
            tx.hash for b in blocks for tx in b.body.transactions
            if tx.tx_type == TYPE_PRIVILEGED]
        # L2->L1 withdrawal messages (from stored receipts of these blocks)
        from .messages import collect_messages, message_root

        receipts = [self.node.store.get_receipts(b.hash) for b in blocks]
        if any(r is None for r in receipts):
            raise RuntimeError("missing receipts for a batched block")
        msgs_root = message_root(collect_messages(blocks, receipts))
        # real KZG sidecar for data availability (reference:
        # l1_committer.rs generate_blobs_bundle + blobs_bundle.rs)
        from .blobs import generate_blobs_bundle

        with tracing.span("seq.blobs") as sp:
            bundle = generate_blobs_bundle(blocks)
            tracing.set_attrs(sp, blobs=len(bundle.versioned_hashes))
        commitment = keccak256(
            b"batch" + number.to_bytes(8, "big") + state_root
            + b"".join(b.hash for b in blocks)
            + b"".join(privileged_hashes) + msgs_root
            + b"".join(bundle.versioned_hashes))
        # VM-circuit coverage this batch admits (anti-downgrade metadata
        # for wire verifiers) — classified from the artifacts captured
        # during witness generation (no second execution), and derived
        # BEFORE the L1 call so a classifier error cannot break the
        # L1-first commit ordering
        vm_mode = ""
        from ..prover import protocol as proto

        if proto.PROVER_TPU in self.cfg.needed_prover_types:
            from ..prover.tpu_backend import vm_mode_from_artifacts

            parent = self.node.store.get_header(
                blocks[0].header.parent_hash)
            vm_mode = vm_mode_from_artifacts(
                blocks, coarse_log, batch_receipts, witness,
                parent.state_root)
        return types.SimpleNamespace(
            blocks=blocks, program_input=program_input,
            state_root=state_root, privileged_hashes=privileged_hashes,
            msgs_root=msgs_root, bundle=bundle, commitment=commitment,
            vm_mode=vm_mode)

    def _settle_commit(self, number: int, commitment: bytes,
                       state_root: bytes, privileged_hashes: list,
                       msgs_root: bytes, bundle,
                       epoch: int | None = None) -> None:
        """Idempotent L1 commit: if the L1 already holds batch `number`
        with OUR commitment (a retry after the commit tx landed but the
        acknowledgment was lost), adopt it as success; a different
        commitment is a divergence and fails fast.  The l1.commit fault
        site fires on both legs — before the call (request lost) and
        after it returns (response lost).  `epoch` is the caller's
        fencing token (sequencer HA): the L1 rejects it when stale, so
        a deposed leader's delayed commit can never land."""
        faults.inject("l1.commit")
        if self.l1.last_committed_batch() >= number:
            onchain = self.l1.get_committed_commitment(number)
            if onchain != commitment:
                raise SettlementDivergence(
                    f"batch {number} already settled on L1 with a "
                    f"different commitment "
                    f"(l1={onchain.hex()[:16] if onchain else None} "
                    f"local={commitment.hex()[:16]}); refusing to settle "
                    f"on a fork")
            with self._settlement_lock:
                self.commits_adopted_total += 1
            from ..utils.metrics import record_commit_adopted

            record_commit_adopted()
            log.warning("batch %d already committed on L1 with a matching "
                        "commitment; adopting it as success", number)
        else:
            self.l1.commit_batch(number, state_root, commitment,
                                 privileged_hashes, msgs_root,
                                 epoch=epoch)
            faults.inject("l1.commit")
        try:
            # publish the DA sidecar alongside the commitment (the commit
            # tx is the blob carrier; based followers re-derive the chain
            # from it — l2/based.py); on the adopt path re-publish only
            # if the first attempt died before the sidecar went out
            if self.l1.get_blob_sidecar(number) is None:
                self.l1.publish_blobs(number, bundle)
        except NotImplementedError:
            pass

    def commit_next_batch(self) -> Batch | None:
        # the fencing token for this WHOLE commit is captured once, up
        # front: if leadership moves mid-commit, the L1 and the store
        # reject the stale token on their own legs (zombie protection)
        epoch = self._fence()
        with self._settlement_lock:
            if self._recommit_queue:
                # reorged-out commitments take priority over new batches
                return self._recommit_batch(min(self._recommit_queue))
        number = self.rollup.latest_batch_number() + 1
        if self.stop_at_batch is not None and number > self.stop_at_batch:
            return None    # admin stop-at: the committer idles here
        if self.l1.last_committed_batch() >= number:
            # the L1 already holds the batch we are about to build: a
            # commit succeeded but its acknowledgment was lost before any
            # local persistence.  Building a fresh batch now would span a
            # WIDER block range (production kept going) and diverge —
            # re-derive the settled record from the L1 instead, exactly
            # like startup reconciliation
            self._rebuild_batch_from_l1(number)
            with self._settlement_lock:
                self.commits_adopted_total += 1
            from ..utils.metrics import record_batch, record_commit_adopted

            record_commit_adopted()
            record_batch(number)
            return self.rollup.get_batch(number)
        head = self.node.store.latest_number()
        first = self.last_batched_block + 1
        if head < first:
            return None
        last = self._batch_end(first, head)
        # the commit joins the batch's trace, as the proof sender's spans
        # do (docs/OBSERVABILITY.md "Spans of the sequencer")
        with tracing.trace_context(self.coordinator.trace_for_batch(number)), \
                tracing.span("seq.commit", batch=number,
                             blocks=last - first + 1) as sp:
            art = self._build_batch_artifacts(number, first, last)
            if art is None:
                return None
            tracing.set_attrs(
                sp, txs=sum(len(b.body.transactions) for b in art.blocks),
                gas=sum(b.header.gas_used for b in art.blocks))
            # L1 first: only persist the batch once the commitment is
            # accepted, otherwise a transient L1 failure would desync the
            # batch counter.  Remember the attempt first: if the L1
            # accepts it but the acknowledgment is lost, the rebuild
            # adopts these artifacts instead of re-deriving the settled
            # range from scratch
            self._last_commit_attempt = (number, first, art)
            with tracing.span("seq.l1_commit"):
                self._settle_commit(number, art.commitment, art.state_root,
                                    art.privileged_hashes, art.msgs_root,
                                    art.bundle, epoch=epoch)
            batch = Batch(number=number, first_block=first,
                          last_block=last, state_root=art.state_root,
                          commitment=art.commitment, vm_mode=art.vm_mode)
            # the local batch record is one journaled unit: a crash
            # between these writes reopens to either the full record or
            # none (and the none case is exactly the commit-crash window
            # reconciliation already rebuilds from L1); the group carries
            # the same fencing token as the L1 leg, so a leader deposed
            # inside the commit crash-window cannot write a record the
            # new leader won't own
            with tracing.span("seq.store"), \
                    self.rollup.write_group(epoch=epoch):
                self.rollup.store_batch(batch)
                self.rollup.store_blobs_bundle(number, art.bundle)
                self.rollup.store_prover_input(number, self.cfg.commit_hash,
                                               art.program_input.to_json())
                self.rollup.set_committed(number, art.commitment)
            self.last_batched_block = last
        from ..utils.metrics import record_batch

        record_batch(number)
        # chain-path X-ray: the sealed blocks leave the batching stage;
        # sampled lifecycles get their batched mark and join the PR-15
        # batch trace by trace ID.  Telemetry — never fails the commit.
        try:
            from ..perf.chain_path import CHAIN_PATH

            CHAIN_PATH.blocks_batched(
                number, first, last,
                trace_id=self.coordinator.trace_for_batch(number))
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        return batch

    def _batch_end(self, first: int, head: int) -> int:
        """The last block of the batch that starts at `first`: the head,
        or under `batch_gas_limit` the last whole block whose cumulative
        gas_used stays within the limit (never before `first`: a lone
        block over the limit is a batch of its own)."""
        limit = self.cfg.batch_gas_limit
        if limit is None:
            return head
        gas = 0
        for n in range(first, head + 1):
            block = self.node.store.get_canonical_block(n)
            if block is None:
                return head     # _build_batch_artifacts refuses the range
            gas += block.header.gas_used
            if gas > limit:
                return max(first, n - 1)
        return head

    def _recommit_batch(self, number: int) -> Batch | None:
        """Re-submit a batch whose L1 commitment a reorg dropped.  The
        stored record is re-committed VERBATIM (same commitment), so the
        stored proofs stay valid and send_proofs can re-verify without
        re-proving."""
        batch = self.rollup.get_batch(number)
        if batch is None:
            self._recommit_queue.discard(number)
            return None
        bundle = self.rollup.get_blobs_bundle(number)
        blocks = [self.node.store.get_canonical_block(n)
                  for n in range(batch.first_block, batch.last_block + 1)]
        if bundle is None or any(b is None for b in blocks):
            # unusable record (partial persistence + reorg): drop it and
            # every batch above, rewind, and re-batch from scratch
            self._drop_batches_from(number)
            return None
        privileged_hashes = [
            tx.hash for b in blocks for tx in b.body.transactions
            if tx.tx_type == TYPE_PRIVILEGED]
        from .messages import collect_messages, message_root

        receipts = [self.node.store.get_receipts(b.hash) for b in blocks]
        if any(r is None for r in receipts):
            self._drop_batches_from(number)
            return None
        msgs_root = message_root(collect_messages(blocks, receipts))
        self._settle_commit(number, batch.commitment, batch.state_root,
                            privileged_hashes, msgs_root, bundle,
                            epoch=self._epoch())
        self.rollup.set_settlement(number, committed=True)
        with self._settlement_lock:
            self._recommit_queue.discard(number)
            self.recommits_total += 1
        from ..utils.metrics import record_recommit

        record_recommit()
        log.info("re-committed batch %d after an L1 reorg", number)
        return batch

    def _drop_batches_from(self, number: int) -> None:
        """Reorg last resort: delete batch records from `number` up and
        rewind last_batched_block so the normal committer re-batches the
        (still canonical) blocks from scratch."""
        with self._settlement_lock:
            latest = self.rollup.latest_batch_number()
            for n in range(number, latest + 1):
                self.rollup.delete_batch(n)
                self._recommit_queue.discard(n)
            prev = self.rollup.get_batch(number - 1)
            self.last_batched_block = prev.last_block if prev else 0
            log.warning("dropped unusable batch records %d..%d after an "
                        "L1 reorg; rewound last_batched_block to %d",
                        number, latest, self.last_batched_block)

    # ------------------------------------------------------------------
    # L1ProofSender (reference: l1_proof_sender.rs — consecutive proven
    # batches -> one verifyBatches tx)
    # ------------------------------------------------------------------
    def send_proofs(self) -> tuple[int, int] | None:
        first = self.l1.last_verified_batch() + 1
        last = first - 1
        needed = list(self.cfg.needed_prover_types)

        def slot_type(n: int, t: str) -> str:
            """The prover type that actually fills type t's proof slot for
            batch n: quarantined batches settle on the coordinator's
            fallback backend (graceful degradation — see
            docs/PROVER_RESILIENCE.md)."""
            eff = self.coordinator.effective_needed_types(n, [t])
            return eff[0] if eff else t

        while self.rollup.get_batch(last + 1) is not None \
                and self.rollup.get_batch(last + 1).committed \
                and self.rollup.batch_fully_proven(
                    last + 1, [slot_type(last + 1, t) for t in needed]):
            last += 1
        if last < first:
            return None
        if self.cfg.aggregation_enabled \
                and last - first + 1 >= self.cfg.aggregation_min_batches:
            # long enough for the recursion stage: defer to the
            # aggregate_proofs actor (N proofs -> one L1 tx); runs
            # shorter than aggregation_min_batches still settle here
            # per-batch, which also keeps settlement moving if the
            # aggregator keeps failing (its audit deletes bad proofs,
            # shrinking the run below the threshold)
            return None
        proofs = {}
        for t in needed:
            from ..prover.backend import get_backend

            def check(n: int) -> bool:
                backend = get_backend(slot_type(n, t))
                proof = self.rollup.get_proof(n, slot_type(n, t))
                # anti-downgrade: the committer recorded the VM-circuit
                # coverage this batch admits; a claimed-log proof for a
                # circuit-covered batch is rejected without the witness
                batch = self.rollup.get_batch(n)
                if batch is not None and not backend.check_coverage(
                        proof, batch.vm_mode):
                    return False
                # full audit when the backend supports it: the stored
                # ProverInput lets the proof's write log be replayed
                # against the witness MPT (no re-execution)
                if hasattr(backend, "verify_with_input"):
                    stored = self.rollup.get_prover_input(
                        n, self.cfg.commit_hash)
                    if stored is not None:
                        from ..guest.execution import ProgramInput

                        return backend.verify_with_input(
                            proof, ProgramInput.from_json(stored))
                return backend.verify(proof)

            results = {}
            for n in range(first, last + 1):
                # join the batch's proving trace (opened at assignment)
                # so verification shows up in the same lifecycle trace
                with tracing.trace_context(
                        self.coordinator.batch_traces.get(n)):
                    with tracing.span("proof.verify", batch=n,
                                      prover_type=slot_type(n, t)):
                        results[n] = check(n)
            if not all(results.values()):
                # invalid proof: delete so the fleet re-proves (reference:
                # distributed_proving.md:70-72)
                for n, ok in results.items():
                    if not ok:
                        self.rollup.delete_proof(n, slot_type(n, t))
                return None
            # per-batch proof bytes: the L1 checks each batch's committed
            # output (state root + messages root) against its records
            proofs[t] = [
                get_backend(slot_type(n, t)).to_proof_bytes(
                    self.rollup.get_proof(n, slot_type(n, t)))
                for n in range(first, last + 1)]
        epoch = self._fence()
        faults.inject("l1.verify")
        # one verifyBatches for the range: its span joins the trace of
        # the range's first batch
        with tracing.trace_context(self.coordinator.batch_traces.get(first)), \
                tracing.span("l1.verify", first=first, last=last):
            self.l1.verify_batches(first, last, proofs, epoch=epoch)
        faults.inject("l1.verify")
        for n in range(first, last + 1):
            with tracing.trace_context(
                    self.coordinator.batch_traces.get(n)):
                with tracing.span("proof.settle", batch=n):
                    self.rollup.set_verified(n)
        from ..utils.metrics import record_verified_batch

        record_verified_batch(last)
        try:
            from ..perf.chain_path import CHAIN_PATH

            CHAIN_PATH.batches_settled(first, last)
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        self._record_lifecycles(first, last)
        return (first, last)

    def _record_lifecycles(self, first: int, last: int) -> None:
        """Post-settlement critical-path attribution: walk each settled
        batch's merged lifecycle trace, feed the
        batch_critical_path_seconds{component} histogram (exemplared
        with the trace ID) and the coordinator's lifecycle timeline.
        Telemetry — never raises into settlement."""
        from ..utils.metrics import observe_critical_path

        try:
            for n in range(first, last + 1):
                tid = self.coordinator.batch_traces.get(n)
                if tid is None:
                    continue
                cp = tracing.critical_path(tracing.TRACER.get_trace(tid))
                if not cp.get("spanCount"):
                    continue
                for component, secs in cp.get("components", {}).items():
                    observe_critical_path(component, secs, trace_id=tid)
                self.coordinator.note_lifecycle(n, {
                    "batch": n,
                    "traceId": tid,
                    "wallSeconds": round(cp.get("wallSeconds") or 0.0, 6),
                    "spanCount": cp.get("spanCount"),
                    "partial": cp.get("partial"),
                    "sources": cp.get("sources"),
                    "components": {k: round(v, 6) for k, v in
                                   cp.get("components", {}).items()},
                })
        except Exception:  # noqa: BLE001 — settlement already succeeded
            log.exception("critical-path attribution failed")

    # ------------------------------------------------------------------
    # ProofAggregator actor (docs/AGGREGATION.md)
    # ------------------------------------------------------------------
    def aggregate_proofs(self) -> tuple[int, int] | None:
        """Settle the next pending run as one aggregated proof; a no-op
        until aggregation is enabled and the run reaches
        aggregation_min_batches (send_proofs remains the fallback)."""
        if not self.cfg.aggregation_enabled:
            return None
        settled = self.aggregator.step()
        if settled is not None:
            # aggregated runs get the same per-batch lifecycle
            # attribution as the per-batch settlement path
            self._record_lifecycles(*settled)
        return settled

    # ------------------------------------------------------------------
    # StateUpdater (reference: state_updater.rs)
    # ------------------------------------------------------------------
    def update_state(self):
        """Reconcile local settlement flags with the L1 — in BOTH
        directions.  Forward: adopt flags the L1 advanced past us (e.g.
        another tooling path verified batches).  Backward: an L1 reorg
        that regressed last_committed/verified drops the affected flags
        through the write-through setters and queues the batches for
        re-commit, so the committer re-settles them verbatim."""
        # fence before touching settlement flags: a deposed leader's
        # state updater must not adopt/rollback flags the new leader owns
        self._fence()
        committed = self.l1.last_committed_batch()
        verified = self.l1.last_verified_batch()
        with self._settlement_lock:
            reorged = False
            for n in sorted(self.rollup.batches, reverse=True):
                batch = self.rollup.get_batch(n)
                if n > committed and batch.committed:
                    # settlement regression: the commit tx reorged out
                    self.rollup.set_settlement(n, committed=False,
                                               verified=False)
                    self._recommit_queue.add(n)
                    reorged = True
                    log.warning("L1 reorg dropped the commitment of batch "
                                "%d; queued for re-commit", n)
            for n in sorted(self.rollup.batches):
                batch = self.rollup.get_batch(n)
                if n <= committed and not batch.committed:
                    onchain = self.l1.get_committed_commitment(n)
                    if onchain is not None and batch.commitment \
                            and onchain != batch.commitment:
                        raise SettlementDivergence(
                            f"batch {n} settled on L1 with a different "
                            f"commitment (l1={onchain.hex()[:16]} "
                            f"local={batch.commitment.hex()[:16]})")
                    self.rollup.set_settlement(n, committed=True)
                if n <= verified and not batch.verified:
                    self.rollup.set_settlement(n, verified=True)
                if n > verified and batch.verified:
                    # the verify tx reorged out (commit may have
                    # survived); send_proofs re-verifies from stored
                    # proofs
                    self.rollup.set_settlement(n, verified=False)
                    reorged = True
                    log.warning("L1 reorg dropped the verification of "
                                "batch %d; will re-verify", n)
            if reorged:
                self.reorgs_total += 1
                from ..utils.metrics import record_l1_reorg

                record_l1_reorg()
        from ..utils.metrics import record_verified_batch

        record_verified_batch(verified)

    # ------------------------------------------------------------------
    def start(self):
        if self.leadership is None:
            self.coordinator.start()
        else:
            # HA mode: actor threads spin up PARKED (follower posture);
            # the coordinator stays down so this node's rollup view
            # cannot hand the prover fleet duplicate work.  Promotion —
            # driven by the leadership manager winning the lease —
            # starts the coordinator and unparks the actors
            for name in self.ACTOR_NAMES:
                self.pause_actor(name)
        self.started_at = time.time()

        def loop(interval, fn):
            st = ActorHealth(fn.__name__)
            self.health[st.name] = st

            def run():
                while True:
                    # exponential backoff while an actor keeps failing —
                    # jittered so a fleet of actors hammered by the same
                    # L1 outage doesn't retry in lockstep
                    steps = min(st.consecutive_failures
                                + st.consecutive_transient, 16)
                    factor = min(1 << steps, self.cfg.max_backoff_factor)
                    delay = interval * factor
                    if factor > 1:
                        delay *= 1 + self._backoff_rng.random() \
                            * self.cfg.backoff_jitter
                    if self._stop.wait(delay):
                        return
                    if st.name in self.paused or \
                            self._resume_at.get(st.name, 0) > time.time():
                        continue
                    t0 = time.perf_counter()
                    try:
                        fn()
                        st.runs += 1
                        st.consecutive_failures = 0
                        st.consecutive_transient = 0
                        st.last_success = time.time()
                    except FencedError as e:
                        # deposed, not failing: a sink refused our stale
                        # epoch.  Demote (park all actors, re-enter
                        # candidacy) without burning any failure budget —
                        # the new leader owns the pipeline now
                        st.last_error = f"FencedError: {e}"
                        st.last_error_class = "fenced"
                        log.warning("sequencer actor %s fenced (deposed "
                                    "leader): %s", st.name, e)
                        if self.leadership is not None:
                            self.leadership.fenced(e)
                    except Exception as e:  # noqa: BLE001 — actors survive
                        # error classification: transient faults (network
                        # flakes, injected drops — an L1 outage) get a far
                        # larger failure budget than deterministic errors,
                        # so an outage degrades instead of killing the
                        # sequencer
                        transient = is_transient(e)
                        if transient:
                            st.consecutive_transient += 1
                            st.last_error_class = "transient"
                            count = st.consecutive_transient
                            budget = self.cfg.max_transient_failures
                            from ..utils.metrics import \
                                record_transient_error

                            record_transient_error()
                        else:
                            st.consecutive_failures += 1
                            st.last_error_class = "deterministic"
                            count = st.consecutive_failures
                            budget = self.cfg.max_actor_failures
                        st.last_error = f"{type(e).__name__}: {e}"
                        log.warning("sequencer actor %s failed "
                                    "[%s %d/%d]: %s",
                                    st.name, st.last_error_class,
                                    count, budget, st.last_error)
                        if count >= budget:
                            # fatal subsystem: cancel the whole sequencer
                            # (reference: cancellation token -> non-zero
                            # exit, ethrex.rs:208)
                            self.fatal = (st.name, st.last_error)
                            log.error("sequencer actor %s is fatally "
                                      "failing; stopping all actors",
                                      st.name)
                            self._stop.set()
                            cb = self.on_fatal
                            if cb is not None:
                                cb(st.name, st.last_error)
                            # flight recorder: capture the dying state
                            # (no-op unless --debug-snapshot-dir is set;
                            # must never raise in the actor loop)
                            try:
                                from ..utils import snapshot as _snapshot

                                _snapshot.on_fatal(st.name, st.last_error,
                                                   node=self.node)
                            except Exception:
                                pass
                            try:
                                self.coordinator.stop()
                            except Exception:  # noqa: BLE001 — not started
                                pass
                            return
                    finally:
                        dt = time.perf_counter() - t0
                        st.note_duration(dt)
                        observe_actor_iteration(st.name, dt)
            t = threading.Thread(target=run, daemon=True)
            t.start()
            self._threads.append(t)

        intervals = {
            "produce_block": self.cfg.block_time,
            "commit_next_batch": self.cfg.commit_interval,
            "send_proofs": self.cfg.proof_send_interval,
            "aggregate_proofs": self.cfg.aggregation_interval,
            "watch_l1": self.cfg.watcher_interval,
            "update_state": self.cfg.watcher_interval,
        }
        for name in self.ACTOR_NAMES:
            loop(intervals[name], getattr(self, name))
        if self.leadership is not None:
            self.leadership.start()
        return self

    # ------------------------------------------------------------------
    # admin controls (reference: l2/sequencer/admin_server.rs)
    # ------------------------------------------------------------------
    def pause_actor(self, name: str) -> None:
        if name not in self.ACTOR_NAMES:
            raise ValueError(f"unknown actor {name!r}")
        self.paused.add(name)
        self._resume_at.pop(name, None)

    def resume_actor(self, name: str, delay: float = 0.0) -> None:
        if name not in self.ACTOR_NAMES:
            raise ValueError(f"unknown actor {name!r}")
        if delay > 0:
            self._resume_at[name] = time.time() + delay
        else:
            self._resume_at.pop(name, None)
        self.paused.discard(name)

    def ready_json(self) -> dict:
        """The ethrex_ready payload: role + gated-on-reconciliation
        readiness, distinct from ethrex_health's liveness.  A follower
        is alive but NOT ready for leader traffic; a promoting node
        turns ready only once reconciliation finished and its actors
        unparked (docs/SEQUENCER_HA.md)."""
        if self.leadership is None:
            return {"ready": self.fatal is None, "role": "leader",
                    "ha": False, "reconciledAt": self.reconciled_at,
                    "promotions": self.promotions_total}
        status = self.leadership.status()
        return {
            "ready": (status["role"] == "leader" and self.fatal is None
                      and self.reconciled_at is not None),
            "role": status["role"],
            "ha": True,
            "reconciledAt": self.reconciled_at,
            "promotions": self.promotions_total,
            "leadership": status,
        }

    def stop(self, timeout: float = 10.0) -> bool:
        """Drain: release the leadership lease (so a standby can win
        immediately instead of waiting out the ttl), signal every actor
        loop, join the actor threads (each finishes its in-flight
        iteration — a mid-commit batch lands or rolls back through its
        write group), then stop the coordinator, which waits for
        in-flight proof submits to land.  Returns True when every actor
        stopped within the deadline.

        Idempotent and follower-safe: repeated invocations (demote →
        shutdown races, the shutdown manager re-running a drain) return
        the first drain's result without re-joining anything, and a
        follower whose actor threads never started drains cleanly."""
        with self._stop_guard:
            if self._stopped:
                return self._stop_result
            self._stopped = True
        if self.leadership is not None:
            self.leadership.stop()
        self._stop.set()
        deadline = time.monotonic() + timeout
        for t in self._threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        stragglers = [t for t in self._threads if t.is_alive()]
        if stragglers:
            log.warning("%d sequencer actor(s) still running after %.1fs "
                        "drain deadline", len(stragglers), timeout)
        self.coordinator.stop(
            timeout=max(0.5, deadline - time.monotonic()))
        self._stop_result = not stragglers
        return self._stop_result
