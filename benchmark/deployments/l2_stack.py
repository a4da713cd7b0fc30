"""Deployment `l2_stack`: the whole L2 stack ahead of one prover.  The
stack is what `l2 --dev` starts (`cli.start_l2_stack`, the CLI's own
wiring): the block producer, the committer (witness, KZG blob bundle,
commit to the dev L1, the rollup store's record), the proof coordinator
on TCP, the proof sender (`verify_with_input` on every proof,
`verifyBatches` on the dev L1, `set_verified`) and the JSON-RPC server.
One `ProverClient` on the stack's coordinator proves, as `--run-prover`
wires it; the harness makes its prewarm call and holds it until the
window, as `prover_fleet` does.  No actor is ever paused: the producer,
the committer and the proof sender run on their own timers throughout.

Arrival mode `stack_ahead`: the seed's transfers go in over JSON-RPC
(`eth_sendRawTransaction`), `transfers_per_block` to a block, so the
stack seals blocks and batches faster than one prover clears them.
Block b carries the seed's b-th block of transfers.  On each new head
(`eth_blockNumber`) the feeder sends the lowest nonce of the next block,
its gate, and the other nonces of the block after it, which the mempool
holds back behind their missing gate: a block's transfers become
includable together, whenever the producer's timer fires.  The
committer's `batch_gas_limit` (the mix's) keeps every batch at one
block whatever the phase of the two timers.  Set-up readies the prover
first, as `prover_fleet` does, on a coordinator of its own
(`_ready_the_prover`), then starts the stack and runs until it has
sealed the warm-up batches and `batches_sealed_ahead` more and the
warm-up batches are proven through it; the window releases the client.

A run is refused (`BenchFailure`) where the prover found no committed
batch waiting (a `prover.idle` of the window with `polls` > 0), a
window batch is not one block of the seed's transfers, a sequencer actor
failed, or `settle_reference.py` finds a guarantee broken: it is held to
what the feeder sent and was acknowledged, what the JSON-RPC shows of
blocks, receipts and batches once the stack has stopped, and what the
dev L1 was handed (the deployment records each `verify_batches` the
proof sender makes, and each proof the stack deletes, beside the call).
A program whose committer has no batch gas limit is refused before
anything is built (`refuse_a_program_without_the_gas_limit`).
"""

from __future__ import annotations

import gc
import http.client
import json
import os
import threading
import time

import settle_reference
from common import BatchRecord, BenchFailure, log, wait_for
from harness import load_deployment

Fleet = load_deployment("prover_fleet")

RPC_BATCH = 64          # the server's largest JSON-RPC batch array
HEAD_POLL_S = 0.02      # how often the feeder asks for the head


def refuse_a_program_without_the_gas_limit() -> None:
    """The cell rests on batches of one block: without the committer's
    bound, a commit that catches two blocks proves TransferAir at twice
    the rows, a program no store holds, and compiles in the window."""
    import dataclasses

    from ethrex_tpu import cli
    from ethrex_tpu.l2.sequencer import SequencerConfig

    try:
        args = cli.build_parser().parse_args(
            ["l2", "--committer.batch-gas-limit", "1"])
    except SystemExit:
        args = None
    if "batch_gas_limit" not in {
            f.name for f in dataclasses.fields(SequencerConfig)} \
            or getattr(args, "batch_gas_limit", None) != 1:
        raise BenchFailure(
            "this program's committer has no batch gas limit "
            "(SequencerConfig.batch_gas_limit, --committer.batch-gas-limit): "
            "arrival mode 'stack_ahead' would seal batches of several "
            "blocks, whose programs no store holds")


SERVER_BUSY = -32005    # the server's typed refusal: the call never ran
BUSY_TRIES = 30


class Rpc:
    """JSON-RPC to the stack over one keep-alive connection, for one
    thread at a time."""

    def __init__(self, port: int):
        self.port = port
        self.conn = None
        self.refused: list = []         # (method, reason, shed level)

    def _post(self, calls: list) -> list:
        body = json.dumps([{"jsonrpc": "2.0", "id": i, "method": m,
                            "params": p} for i, (m, p) in enumerate(calls)])
        for attempt in (1, 2):
            if self.conn is None:
                self.conn = http.client.HTTPConnection(
                    "127.0.0.1", self.port, timeout=60)
            try:
                self.conn.request("POST", "/", body,
                                  {"Content-Type": "application/json"})
                answers = {a["id"]: a for a in json.loads(
                    self.conn.getresponse().read())}
                return [answers.get(i, {}) for i in range(len(calls))]
            except (OSError, http.client.HTTPException):
                self.close()
                if attempt == 2:
                    raise

    def __call__(self, calls: list) -> list:
        """[(method, params), ...] as one JSON-RPC batch; the results in
        order.  A call the server refused as busy did not run and is
        sent again after the `retryAfter` it names; any other error
        answer is a BenchFailure."""
        results: dict = {}
        pending = list(range(len(calls)))
        for _ in range(BUSY_TRIES):
            busy, wait = [], 0.0
            for i, answer in zip(pending,
                                 self._post([calls[i] for i in pending])):
                error = answer.get("error")
                if error and error.get("code") == SERVER_BUSY:
                    busy.append(i)
                    data = error.get("data") or {}
                    wait = max(wait, float(data.get("retryAfter", 1.0)))
                    self.refused.append((calls[i][0], data.get("reason"),
                                         data.get("shedLevel")))
                elif error or "result" not in answer:
                    raise BenchFailure(f"{calls[i][0]} answered {answer}")
                else:
                    results[i] = answer["result"]
            if not busy:
                return [results[i] for i in range(len(calls))]
            pending = busy
            time.sleep(min(wait, 1.0))
        raise BenchFailure(f"{calls[pending[0]][0]} was refused as busy "
                           f"{BUSY_TRIES} times")

    def many(self, calls: list) -> list:
        """As a call, in batches the server takes."""
        out = []
        for i in range(0, len(calls), RPC_BATCH):
            out.extend(self(calls[i:i + RPC_BATCH]))
        return out

    def close(self) -> None:
        if self.conn is not None:
            self.conn.close()
            self.conn = None


class Feeder:
    """The traffic, over JSON-RPC, one block's transfers a head."""

    def __init__(self, traffic):
        self.traffic = traffic
        self.acks: list = []            # [tx hash hex, nonce], as sent
        self.hashes: dict = {}          # nonce -> acknowledged hash
        self.verified: list = []        # last_verified_batch as it moved
        self.error: BaseException | None = None
        self._raw: dict = {}            # block -> [raw tx hex], signed
        self._queued = 0                # blocks whose later nonces are in
        self._head = 0
        self._polled = None             # when the head was last read
        self.longest_gap = 0.0          # the longest time between reads
        self._stop = threading.Event()
        self._drain = threading.Event()
        self._thread = None
        for b in (1, 2, 3):             # signed before the stack starts
            self._sign(b)

    def transfers(self, b: int) -> list:
        """The seed's transfers of block b (1-based)."""
        bpb = int(self.traffic.mix["blocks_per_batch"])
        return self.traffic.batch((b - 1) // bpb)[(b - 1) % bpb]

    def _sign(self, b: int) -> list:
        if b not in self._raw:
            self._raw[b] = ["0x" + self.traffic.signed(t).hex()
                            for t in self.transfers(b)]
        return self._raw[b]

    def _send(self, b: int, which: slice) -> None:
        raws = self._sign(b)[which]
        nonces = [t.nonce for t in self.transfers(b)][which]
        for raw, nonce, got in zip(raws, nonces, self.rpc(
                [("eth_sendRawTransaction", [raw]) for raw in raws])):
            self.acks.append([got, nonce])
            self.hashes[nonce] = got

    def _queue(self, b: int) -> None:
        """Block b's later nonces: held back behind the missing gate."""
        self._send(b, slice(1, None))
        self._queued = b

    def _gate(self, b: int) -> None:
        """Block b's lowest nonce: its transfers become includable."""
        self._send(b, slice(0, 1))

    def start(self, port: int, l1) -> None:
        self.rpc, self.l1 = Rpc(port), l1
        self.verified.append(l1.last_verified_batch())
        self._queue(1)
        self._gate(1)
        self._queue(2)
        self._thread = threading.Thread(target=self._run, name="feeder",
                                        daemon=True)
        self._thread.start()

    def _run(self) -> None:
        try:
            while not self._stop.is_set():
                self._sample()
                head = int(self.rpc([("eth_blockNumber", [])])[0], 16)
                now = time.monotonic()
                if self._polled is not None:
                    self.longest_gap = max(self.longest_gap,
                                           now - self._polled)
                self._polled = now
                if head > self._head:
                    if self._queued > head:     # first: the timer runs
                        self._gate(head + 1)
                    self._sealed(head)
                    if self._drain.is_set():
                        if head >= self._queued:
                            return
                    else:
                        self._queue(head + 2)
                        self._sign(head + 3)
                time.sleep(HEAD_POLL_S)
        except Exception as exc:  # noqa: BLE001 — read by the deployment
            self.error = exc
        finally:
            self.rpc.close()

    def _sealed(self, head: int) -> None:
        """The new head is the block whose gate went in last, and holds
        exactly its transfers: else every later block is off by one."""
        got = self.rpc([("eth_getBlockByNumber", [hex(head), False])])
        want = [self.hashes.get(t.nonce) for t in self.transfers(head)]
        if head != self._head + 1 or got[0]["transactions"] != want:
            raise BenchFailure(
                f"block {head} was sealed with {len(got[0]['transactions'])}"
                f" transactions after block {self._head}, not the "
                f"{len(want)} transfers whose gate went in after block "
                f"{head - 1} (the longest time between two reads of the "
                f"head was {self.longest_gap:.2f}s; calls refused as busy: "
                f"{len(self.rpc.refused)}, the last {self.rpc.refused[-1:]})")
        self._head = head

    def _sample(self) -> None:
        v = self.l1.last_verified_batch()
        if v != self.verified[-1]:
            self.verified.append(v)

    def finish(self, timeout: float) -> None:
        """Send no new block; let the queued ones in, gate by gate, and
        stop once the last of them is in a block: every acknowledged
        transfer is then in the chain."""
        self._drain.set()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise BenchFailure(f"the feeder did not drain within "
                                   f"{timeout:.0f}s")
        self._sample()

    def stop(self) -> None:
        self._stop.set()


class Deployment(Fleet):
    def __init__(self, config, traffic, spans, prover, run_dir):
        super().__init__(config, traffic, spans, prover, run_dir)
        self.run_dir = run_dir
        self.stack = None
        self.feeder = None
        self.settled: list = []         # verifyBatches as the L1 took them
        self.deleted: list = []         # proofs the stack deleted

    # ------------------------------------------------------------------
    def setup(self) -> None:
        mix = self.traffic.mix
        arrival = mix["arrival"]
        if arrival.get("mode") != "stack_ahead":
            raise BenchFailure("deployment l2_stack drives arrival mode "
                               "'stack_ahead' only")
        refuse_a_program_without_the_gas_limit()
        from ethrex_tpu import cli
        from ethrex_tpu.prover.client import ProverClient
        from ethrex_tpu.utils import jax_cache

        hydrated = self._ready_the_prover()
        # a cold build leaves millions of objects the collector has not
        # yet traversed; its first full collection stops every thread for
        # seconds, and a producer tick that falls in such a stop seals a
        # block without its transfers: take that collection here, before
        # the stack's timers run
        gc.collect()
        t0 = time.monotonic()
        os.makedirs(self.run_dir, exist_ok=True)
        genesis = os.path.join(self.run_dir, "genesis.json")
        with open(genesis, "w") as f:
            json.dump(self.traffic.genesis(), f)
        self.feeder = Feeder(self.traffic)
        args = cli.build_parser().parse_args([
            "l2", "--dev", "--network", genesis, "--provers", self.prover,
            "--http.port", "0",
            "--block-time", str(mix["block_time_s"]),
            "--commit-interval", str(mix["commit_interval_s"]),
            "--committer.batch-gas-limit", str(mix["batch_gas_limit"])])
        stack = cli.start_l2_stack(args)
        if isinstance(stack, int):
            raise BenchFailure(f"the l2 stack did not start (exit {stack})")
        self.stack = stack
        self.rollup = stack.rollup
        self.coordinator = stack.seq.coordinator
        self._record_settlement()
        self.feeder.start(stack.server.port, stack.l1)
        # as prover_fleet: every program kept, the small ones included
        # (start_l2_stack set the CLI's threshold of a second)
        jax_cache.enable_persistent_cache(min_compile_secs=0)
        # the stack's own client, as --run-prover wires it; the programs
        # the private set-up hydrated or built stay in the process
        self.client = ProverClient(
            self.prover, [("127.0.0.1", self.coordinator.port)],
            prewarm=False)
        self.client.hydrated_groups = hydrated
        self.setup_split["stack_s"] = time.monotonic() - t0
        log(f"set-up: stack started in {self.setup_split['stack_s']:.2f}s")
        t2 = time.monotonic()
        warmups = int(mix.get("warmup_batches", 1))
        interval = float(mix["commit_interval_s"])
        for number in range(1, warmups + 1):
            self._wait_sealed(number, interval)
            before = len(self.client.proved)
            self.client.poll_once()
            if len(self.client.proved) != before + 1:
                raise BenchFailure("the warm-up batch was not accepted by "
                                   "the coordinator")
        self.setup_split["warmup_s"] = time.monotonic() - t2
        ahead = warmups + int(arrival["batches_sealed_ahead"])
        self._wait_sealed(ahead, interval)
        self._held_to_the_stack("set-up")
        log(f"set-up: warm-up batch(es) {self.setup_split['warmup_s']:.2f}s; "
            f"{self.rollup.latest_batch_number()} batch(es) sealed, "
            f"head {self.feeder._head}")

    def _ready_the_prover(self) -> int:
        """prover_fleet's set-up on a coordinator of its own, before the
        stack starts: the prewarm call (hydration from the executable
        store) and the seed's first batch proven through coordinator
        and client, which builds what a cold process lacks.  Both hold
        the interpreter for seconds at a time (one eth_sendRawTransaction
        waited 9.3 s beside a cold build on a TPU v5e host): beside the
        running stack the producer would seal a block without its
        transfers.  Returns the groups the prewarm call hydrated."""
        mix = self.traffic.mix
        self.traffic.mix = {**mix, "warmup_batches": 1, "arrival": {
            "mode": "backlog", "batches_committed_ahead": 0}}
        fleet = Fleet(self.config, self.traffic, self.spans, self.prover,
                      self.run_dir)
        try:
            fleet.setup()
        finally:
            self.traffic.mix = mix
            fleet.close()
        self.setup_split.update({f"prover_{k}": v
                                 for k, v in fleet.setup_split.items()})
        return fleet.client.hydrated_groups

    def _wait_sealed(self, number: int, interval: float) -> None:
        wait_for(lambda: self.rollup.latest_batch_number() >= number
                 or self._failure() is not None,
                 f"the stack to seal batch {number}",
                 timeout=60 + 4 * interval * number, poll=0.05)
        self._held_to_the_stack("set-up")

    def _record_settlement(self) -> None:
        """Beside each call, what the proof sender hands the dev L1 and
        every proof the stack deletes: what the settlement reference
        holds the run to."""
        l1, rollup, prover = self.stack.l1, self.rollup, self.prover
        verify, delete = l1.verify_batches, rollup.delete_proof

        def verify_batches(first, last, proofs, epoch=None):
            out = verify(first, last, proofs, epoch=epoch)
            self.settled.append({"first": first, "last": last,
                                 "proofs": list(proofs.get(prover, ()))})
            return out

        def delete_proof(number, prover_type):
            self.deleted.append([number, prover_type])
            return delete(number, prover_type)

        l1.verify_batches = verify_batches
        rollup.delete_proof = delete_proof

    def _failure(self) -> str | None:
        seq = self.stack.seq
        failed = {name: st.last_error for name, st in seq.health.items()
                  if st.last_error}
        if seq.fatal is not None or failed:
            return f"a sequencer actor failed: {seq.fatal or failed}"
        if self.feeder.error is not None:
            return f"the feeder failed: {self.feeder.error!r}"
        return None

    def _held_to_the_stack(self, when: str) -> None:
        wrong = self._failure()
        if wrong is not None:
            raise BenchFailure(f"in {when}: {wrong}")

    def _note_sealed(self) -> None:
        """A record for every batch the stack has sealed."""
        from ethrex_tpu.prover import protocol

        for number in range(1, self.rollup.latest_batch_number() + 1):
            if number not in self.records:
                self.records[number] = BatchRecord(
                    number=number,
                    blocks=self.traffic.batch(number - 1),
                    program_input=self.rollup.get_prover_input(
                        number, protocol.PROTOCOL_VERSION))

    # ------------------------------------------------------------------
    def run_window(self, seconds: float) -> None:
        self._note_sealed()
        super().run_window(seconds)
        self._held_to_the_stack("the window")
        window = sorted(n for n, r in self.records.items() if r.in_window)
        wrong = self._not_one_block_of_the_seed(window)
        wrong += self._waits_on_an_empty_backlog()
        if wrong:
            raise BenchFailure("the window broke the cell: "
                               + "; ".join(wrong))
        log(f"window: batches {window} each one block of "
            f"{self.traffic.mix['transfers_per_block']} transfers; the "
            f"stack had sealed {self.rollup.latest_batch_number()}")

    def _not_one_block_of_the_seed(self, window: list) -> list:
        """Each window batch is `blocks_per_batch` blocks; the feeder
        held every block to the seed's transfers as it was sealed."""
        bpb = int(self.traffic.mix["blocks_per_batch"])
        wrong = []
        for n in window:
            batch = self.rollup.get_batch(n)
            if (batch.first_block, batch.last_block) != \
                    ((n - 1) * bpb + 1, n * bpb):
                wrong.append(f"batch {n} holds blocks {batch.first_block}"
                             f"..{batch.last_block}")
        return wrong

    def _waits_on_an_empty_backlog(self) -> list:
        from ethrex_tpu.utils.tracing import TRACER

        waits = []
        for tid in self.trace_ids():
            for s in (TRACER.get_trace(tid) or {}).get("spans", ()):
                if s["name"] == "prover.idle" \
                        and s["start"] >= self.window_wall0 - 1e-3 \
                        and (s.get("attrs") or {}).get("polls", 0) > 0:
                    waits.append(f"the prover found no sealed batch "
                                 f"waiting {s['attrs']['polls']} time(s) "
                                 f"before batch {s['attrs'].get('batch')}")
        return waits

    # ------------------------------------------------------------------
    def collect(self) -> None:
        """Let the last acknowledged transfers in, stop the stack (each
        actor finishes the iteration it is in), hold what it did to the
        settlement reference, and fill the records with the proofs as
        the coordinator stored them."""
        self.feeder.finish(timeout=10 * float(
            self.traffic.mix["block_time_s"]) + 30)
        self.stack.seq.stop()
        self._held_to_the_stack("the drain")
        self._note_sealed()
        super().collect()
        record = self._settlement_record()
        wrong = settle_reference.violations(record)
        if wrong:
            raise BenchFailure("settlement broke its reference: "
                               + "; ".join(wrong[:8]))
        log(f"settlement reference: {len(record['acks'])} acknowledged "
            f"transfers in {len(record['blocks'])} blocks, "
            f"{len(record['batches'])} batches committed, verified up to "
            f"{record['verified'][-1]} in {len(self.settled)} "
            f"verifyBatches; nothing broken")

    def _settlement_record(self) -> dict:
        rpc, l1 = Rpc(self.stack.server.port), self.stack.l1
        try:
            head = int(rpc([("eth_blockNumber", [])])[0], 16)
            blocks = rpc.many([("eth_getBlockByNumber", [hex(b), False])
                               for b in range(1, head + 1)])
            acks = self.feeder.acks
            receipts = rpc.many([("eth_getTransactionReceipt", [h])
                                 for h, _ in acks])
            latest = self.rollup.latest_batch_number()
            batches = rpc.many([("ethrex_getBatchByNumber", [hex(n)])
                                for n in range(1, latest + 1)])
        finally:
            rpc.close()
        return {
            "acks": acks,
            "blocks": dict(zip(range(1, head + 1), blocks)),
            "receipts": {h: r for (h, _), r in zip(acks, receipts)},
            "batches": dict(zip(range(1, latest + 1), batches)),
            "l1_roots": {n: "0x" + l1.get_committed_state_root(n).hex()
                         for n in range(1, l1.last_committed_batch() + 1)},
            "verified": self.feeder.verified,
            "settled": self.settled,
            "judged": {r.number: r.proof for r in self.records.values()
                       if r.in_window and r.proof},
            "deleted": self.deleted,
        }

    def trace_ids(self) -> list[str]:
        """Every sealed batch's trace: the window's commits are those of
        batches the prover has not reached."""
        traces = self.coordinator.batch_traces
        return [traces[n] for n in sorted(traces)]

    def close(self) -> None:
        if self.feeder is not None:
            self.feeder.stop()
        if self.client is not None:
            self.client.stop()
        if self.stack is not None:
            self.stack.seq.stop()
            self.stack.server.stop()
