"""Deployment `prover_fleet`: a prover that is a fleet member.  A real
`ProofCoordinator` listens on TCP over a rollup store that holds
committed batches; one `ProverClient` polls it, proves on its backend
and submits.  The window drives `ProverClient.run_forever`; nothing here
calls a backend directly.

Arrival mode `backlog`: set-up commits `batches_committed_ahead` batches
(each `blocks_per_batch` blocks of the mix's transfers, produced by a
`Node` on the seed's genesis with fixed timestamps, witnessed as the
sequencer's committer witnesses them) and holds the client; the window
releases it.
"""

from __future__ import annotations

import threading
import time

from common import BatchRecord, BenchFailure, log


class Deployment:
    def __init__(self, config: dict, traffic, spans, prover: str,
                 run_dir: str):
        self.config = config
        self.traffic = traffic
        self.spans = spans
        self.prover = prover
        self.records: dict[int, BatchRecord] = {}
        self.coordinator = None
        self.client = None
        self.window_t0 = self.window_t1 = None
        self.setup_split: dict[str, float] = {}

    # ------------------------------------------------------------------
    def setup(self) -> None:
        from ethrex_tpu.guest.execution import ProgramInput
        from ethrex_tpu.guest.witness import generate_witness
        from ethrex_tpu.l2.proof_coordinator import ProofCoordinator
        from ethrex_tpu.l2.rollup_store import RollupStore
        from ethrex_tpu.node import Node
        from ethrex_tpu.primitives.genesis import Genesis
        from ethrex_tpu.primitives.transaction import Transaction
        from ethrex_tpu.prover import protocol
        from ethrex_tpu.prover.client import ProverClient
        from ethrex_tpu.utils import jax_cache

        arrival = self.traffic.mix["arrival"]
        if arrival.get("mode") != "backlog":
            raise BenchFailure("deployment prover_fleet drives arrival "
                               "mode 'backlog' only")
        t0 = time.monotonic()
        # as cli._enable_compile_caches, but every program is kept,
        # not only those that took over a second to compile: the ~130
        # small ones are then read back in a warm process, not compiled
        # again (35-45 s of every warm set-up before, PERF.md PR 26)
        jax_cache.enable_persistent_cache(min_compile_secs=0)
        self.rollup = RollupStore()
        self.coordinator = ProofCoordinator(
            self.rollup, needed_types=[self.prover],
            proof_format=self.config.get("proof_format", "stark")).start()
        # prewarm=False: the harness makes the client's prewarm call
        # itself, below, so that it can wait for it and time it
        self.client = ProverClient(
            self.prover, [("127.0.0.1", self.coordinator.port)],
            prewarm=False)
        node = Node(Genesis.from_json(self.traffic.genesis()))
        ts = int(self.config["first_block_timestamp"])
        step = int(self.config["block_time_s"])
        ahead = int(arrival["batches_committed_ahead"]) \
            + int(self.traffic.mix.get("warmup_batches", 1))
        for index in range(ahead):
            blocks = []
            for transfers in self.traffic.batch(index):
                for t in transfers:
                    node.submit_transaction(Transaction.decode_canonical(
                        self.traffic.signed(t)))
                ts += step
                block = node.produce_block(timestamp=ts)
                if len(block.body.transactions) != len(transfers):
                    raise BenchFailure(
                        f"block {block.header.number} holds "
                        f"{len(block.body.transactions)} of "
                        f"{len(transfers)} transfers")
                blocks.append(block)
            witness = generate_witness(node.chain, blocks)
            pi = ProgramInput(blocks=blocks, witness=witness,
                              config=node.config)
            number = index + 1
            self.rollup.store_prover_input(
                number, protocol.PROTOCOL_VERSION, pi.to_json())
            self.records[number] = BatchRecord(
                number=number, blocks=self.traffic.batch(index),
                program_input=self.rollup.get_prover_input(
                    number, protocol.PROTOCOL_VERSION))
        self.setup_split["inputs_s"] = time.monotonic() - t0
        t1 = time.monotonic()
        self.client.hydrated_groups = int(self.client.backend.prewarm() or 0)
        self.setup_split["prewarm_s"] = time.monotonic() - t1
        log(f"set-up: {ahead} batches committed in "
            f"{self.setup_split['inputs_s']:.2f}s; prewarm hydrated "
            f"{self.client.hydrated_groups} phase-program group(s) in "
            f"{self.setup_split['prewarm_s']:.2f}s")
        # warm-up: the same client, through the same coordinator
        t2 = time.monotonic()
        for _ in range(int(self.traffic.mix.get("warmup_batches", 1))):
            before = len(self.client.proved)
            self.client.poll_once()
            if len(self.client.proved) != before + 1:
                raise BenchFailure("the warm-up batch was not accepted by "
                                   "the coordinator")
        self.setup_split["warmup_s"] = time.monotonic() - t2
        log(f"set-up: warm-up batch(es) {self.setup_split['warmup_s']:.2f}s")

    # ------------------------------------------------------------------
    def run_window(self, seconds: float) -> None:
        """Release the client; at `seconds` tell it to stop.  The window
        runs from the release to the storing of its last batch: the one
        in flight at `seconds`, or, where the client was between batches
        then, the one before (an idle tail with no batch to follow would
        count a cycle that never ends)."""
        warm = set(self.client.proved)
        thread = threading.Thread(target=self.client.run_forever,
                                  name="prover-client", daemon=True)
        self.window_wall0 = time.time()
        self.window_t0 = time.monotonic()
        thread.start()
        seen, last_done, told = len(warm), None, False
        while thread.is_alive():
            now = time.monotonic()
            if len(self.client.proved) != seen:
                seen, last_done = len(self.client.proved), now
            if not told and now - self.window_t0 >= seconds:
                self.client.stop()
                told = True
            if told and now - self.window_t0 > seconds + 600:
                raise BenchFailure("the prover client did not stop within "
                                   "600s of the window's end")
            time.sleep(0.002)
        if len(self.client.proved) != seen:
            last_done = time.monotonic()
        self.window_t1 = last_done if last_done is not None \
            else time.monotonic()
        done = [n for n in self.client.proved if n not in warm]
        left = [n for n in self.records
                if n not in self.client.proved]
        if not left:
            raise BenchFailure(
                "the backlog ran dry inside the window: raise "
                "arrival.batches_committed_ahead in the traffic mix")
        for n in done:
            self.records[n].in_window = True
        log(f"window: {len(done)} batch(es) proven and accepted in "
            f"{self.window_t1 - self.window_t0:.3f}s; {len(left)} left in "
            "the backlog")

    # ------------------------------------------------------------------
    def collect(self) -> None:
        """Fill each handled batch's record with what the timed path
        produced (the proof as the coordinator stored it)."""
        for n in self.client.proved:
            rec = self.records[n]
            rec.proof = self.rollup.get_proof(n, self.prover)

    def trace_ids(self) -> list[str]:
        return [self.coordinator.batch_traces[n]
                for n in self.client.proved
                if n in self.coordinator.batch_traces]

    def counters(self) -> dict:
        c = self.coordinator
        return {"coordinator.quarantined": len(c.quarantined),
                "coordinator.rejected_submits": c.rejected_submits_total,
                "coordinator.reassignments": c.reassignments_total,
                "client.submit_rejections": self.client.submit_rejections,
                "client.proved": len(self.client.proved)}

    def close(self) -> None:
        if self.client is not None:
            self.client.stop()
        if self.coordinator is not None:
            self.coordinator.stop()
