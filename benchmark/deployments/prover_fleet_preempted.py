"""Deployment `prover_fleet_preempted`: `prover_fleet` (a real
`ProofCoordinator` on TCP over a backlog of committed batches, one
`ProverClient` at a time), whose prover is preempted once in every
batch.  What a fleet's operator sees when a preemptible TPU VM is taken
and comes back on the same disk: the prover process dies between two
phases of a proof, a new one starts with the backlog still waiting,
finds the dead one's phase checkpoints, gets the batch back from the
coordinator on the lease token they record, and resumes it.

Arrival mode `backlog_preempt`: set-up and the window are
`prover_fleet`'s (the same backlog, the same release, the window ends at
the storing of the batch in flight at `--seconds`); the seat the window
releases is `PreemptedProver`, below.  For each batch a fault plan kills
the attempt at the phase boundary the mix names (`arrival.kill`); the
seat then does what a death does (the client and its heartbeat thread
stopped, the client and its backend object dropped: the dead client
never polls again) and starts a new `ProverClient(prover, endpoints,
prewarm=False)` with a new backend, which runs `run_forever`.  The
warm-up batch is preempted and resumed too, so whatever a resume builds
lazily is built before the window.

Cut (the configuration's `reduced`): the death is a teardown inside the
harness's process, which holds the chip; the replacement does not
import, hydrate and warm up again, which is what `setup_s` times in
every cell.

What the run is held to besides `check.py`: `recovery_reference.py`,
on the kills and stores as they happened and on the coordinator's and
the runtime's ledgers; a run that broke it is refused (`BenchFailure`),
as `prover_fleet` refuses a backlog that ran dry.  A program whose
coordinator cannot hand a lease back is refused before anything is
built (`refuse_a_program_without_reclaim`).
"""

from __future__ import annotations

import socket
import threading
import time

import recovery_reference
from common import BenchFailure, log
from harness import load_deployment

Fleet = load_deployment("prover_fleet")

PHASES = ("commit", "quotient", "open", "fri")
# occasions of the `backend.phase` drop leg that a live TpuBackend batch
# passes before a job's first phase boundary: one after `execute`, four
# a STARK, the state circuit's job first (tests/test_lease_reclaim.py
# pins the count on the real backend); a job not listed starts at 0
OCCASIONS_BEFORE = {"state_proof": 1, "vm_circuits/TransferAir": 5}


def refuse_a_program_without_reclaim(prover: str) -> None:
    """One batch, one lease, and the question the cell rests on, put to
    a coordinator of the program's own over TCP: does a request that
    presents that lease's token get the batch back?"""
    from ethrex_tpu.l2.proof_coordinator import ProofCoordinator
    from ethrex_tpu.l2.rollup_store import RollupStore
    from ethrex_tpu.prover import protocol

    store = RollupStore()
    store.store_prover_input(1, protocol.PROTOCOL_VERSION, {})
    coordinator = ProofCoordinator(store, needed_types=[prover]).start()

    def ask(**more) -> dict:
        with socket.create_connection(("127.0.0.1", coordinator.port),
                                      timeout=30) as sock:
            protocol.send_msg(sock, {
                "type": protocol.INPUT_REQUEST,
                "commit_hash": protocol.PROTOCOL_VERSION,
                "prover_type": prover, **more})
            return protocol.recv_msg(sock)

    try:
        held = ask(prover_id="dead")
        again = ask(prover_id="restarted", reclaim={
            "batch_id": held.get("batch_id"),
            "lease_token": held.get("lease_token")})
    finally:
        coordinator.stop()
    if again.get("batch_id") != 1 \
            or again.get("lease_token") == held.get("lease_token"):
        raise BenchFailure(
            "this program's coordinator does not hand a restarted prover "
            "its batch back on the lease token its checkpoints record (it "
            f"answered {again.get('type')!r}, batch "
            f"{again.get('batch_id')!r}): arrival mode 'backlog_preempt' "
            "cannot run on it")


class PreemptedProver:
    """One seat of the fleet, as the window sees a `ProverClient`
    (`proved`, `submit_rejections`, `poll_once`, `run_forever`, `stop`):
    whoever sits in it is killed once in every batch and replaced."""

    def __init__(self, deployment, first):
        self.d = deployment
        self.live = first
        self.proved = first.proved      # one list, handed from client to client
        self.events: list = []          # ("kill" | "store", batch), in order
        self.kill_times: dict = {}      # batch -> wall clock of its kill
        self.resumed: dict = {}         # batch -> phases its resume loaded
        self.loads: dict = {}           # batch -> envelopes it read from disk
        self.error: BaseException | None = None
        self._at_birth = (0, 0)         # the program's counters, then
        self._rejections = 0
        self._stop = threading.Event()
        self._supervised = False
        kill = deployment.traffic.mix["arrival"]["kill"]
        self._site = kill["site"]
        self._after = OCCASIONS_BEFORE.get(kill["job"], 0) \
            + PHASES.index(kill["after_phase"])

    @property
    def submit_rejections(self) -> int:
        return self._rejections + self.live.submit_rejections

    def stop(self) -> None:
        self._stop.set()
        if not self._supervised:
            self.live.stop()

    # -- one preemption ------------------------------------------------
    def _arm(self):
        from ethrex_tpu.utils import faults

        return faults.install(faults.FaultPlan(self.d.traffic.seed).drop(
            self._site, times=1, after=self._after))

    def _counts(self) -> tuple:
        from ethrex_tpu.prover import checkpoint
        from ethrex_tpu.prover import runtime_errors as rt

        return rt.STATS["phase_resumes"], checkpoint.STATS["loads"]

    def _restart(self, thread) -> None:
        """The live client has been killed: stop it for good, note whose
        lease died with it, and seat a new client with a new backend."""
        from ethrex_tpu.prover.client import ProverClient
        from ethrex_tpu.utils import faults

        dead = self.live
        dead.stop()
        if thread is not None:
            thread.join(5)
            if thread.is_alive():
                raise BenchFailure("the killed client's thread did not end")
        faults.clear()
        coordinator = self.d.coordinator
        with coordinator.lock:
            held = [num for (num, _), holder
                    in coordinator.lease_holders.items()
                    if holder == dead.prover_id]
        if len(held) != 1:
            raise BenchFailure(f"the killed client {dead.prover_id} held "
                               f"the leases {held}, not one")
        self.events.append(("kill", held[0]))
        self.kill_times[held[0]] = time.time()
        self._rejections += dead.submit_rejections
        born = ProverClient(self.d.new_backend(),
                            [("127.0.0.1", coordinator.port)], prewarm=False)
        # what outlives the process in a fleet: the ledger of what this
        # seat proved (the harness's), and the programs, which here stay
        # in the process (the configuration's `reduced`)
        born.proved = self.proved
        born.hydrated_groups = dead.hydrated_groups
        self._at_birth = self._counts()
        self.live = born

    def _stored(self) -> None:
        batch = self.proved[-1]
        self.events.append(("store", batch))
        resumes, loads = self._counts()
        self.resumed[batch] = resumes - self._at_birth[0]
        self.loads[batch] = loads - self._at_birth[1]

    # -- set-up: one whole preempted batch, no thread, no poll wait ----
    def poll_once(self) -> int:
        from ethrex_tpu.utils import faults

        before = len(self.proved)
        plan = self._arm()
        try:
            self.live.poll_once()
            if not plan.log:
                raise BenchFailure(
                    f"no kill fired at {self._site} occasion "
                    f"{self._after + 1} of the warm-up batch")
            self._restart(None)
            self.live.poll_once()
        finally:
            faults.clear()
        if len(self.proved) == before + 1:
            self._stored()
        return len(self.proved) - before

    # -- the window ----------------------------------------------------
    def run_forever(self) -> None:
        from ethrex_tpu.utils import faults

        self._supervised = True
        try:
            thread = self._spawn()
            while True:
                plan = self._arm()
                while thread.is_alive() and not plan.log:
                    if self._stop.is_set():
                        self.live.stop()
                    time.sleep(0.002)
                if not plan.log:
                    return      # told to stop between two batches
                before = len(self.proved)
                self._restart(thread)
                thread = self._spawn()
                t0 = time.monotonic()
                while len(self.proved) == before:
                    if not thread.is_alive() \
                            or time.monotonic() - t0 > 600:
                        raise BenchFailure(
                            "the restarted client stored nothing: "
                            f"{self.events[-1]} was the last event")
                    time.sleep(0.002)
                self._stored()
        except BaseException as exc:  # noqa: BLE001 — raised by run_window
            self.error = exc
        finally:
            self._supervised = False
            self.live.stop()
            faults.clear()

    def _spawn(self) -> threading.Thread:
        thread = threading.Thread(target=self.live.run_forever,
                                  name="prover-client", daemon=True)
        thread.start()
        return thread


class Deployment(Fleet):
    def new_backend(self):
        """What a new `ProverClient` is given: the prover's name, so that
        the client builds a backend object of its own."""
        return self.prover

    def setup(self) -> None:
        arrival = self.traffic.mix["arrival"]
        if arrival.get("mode") != "backlog_preempt":
            raise BenchFailure("deployment prover_fleet_preempted drives "
                               "arrival mode 'backlog_preempt' only")
        refuse_a_program_without_reclaim(self.prover)
        # `prover_fleet` commits the backlog, hydrates and builds the
        # first client; its own warm-up is left out (0 batches) and made
        # here, preempted.  It reads the mix, so it is shown this one
        mix = self.traffic.mix
        warmups = int(mix.get("warmup_batches", 1))
        self.traffic.mix = {**mix, "warmup_batches": 0, "arrival": {
            "mode": "backlog", "batches_committed_ahead":
                int(arrival["batches_committed_ahead"]) + warmups}}
        try:
            super().setup()
        finally:
            self.traffic.mix = mix
        t0 = time.monotonic()
        self.client = PreemptedProver(self, self.client)
        for _ in range(warmups):
            if self.client.poll_once() != 1:
                raise BenchFailure(
                    "the preempted warm-up batch was not stored by the "
                    f"restarted client: {self.client.events}")
        self.setup_split["warmup_s"] = time.monotonic() - t0
        self._held_to_the_reference("set-up")
        log(f"set-up: {warmups} preempted warm-up batch(es) "
            f"{self.setup_split['warmup_s']:.2f}s")

    def run_window(self, seconds: float) -> None:
        try:
            super().run_window(seconds)
        finally:
            if self.client.error is not None:
                raise self.client.error
        self._held_to_the_reference("the window")

    # ------------------------------------------------------------------
    def _ledgers(self) -> dict:
        c = self.coordinator
        return {"reassignments": c.reassignments_total,
                "quarantined": len(c.quarantined),
                "rejected_submits": c.rejected_submits_total,
                "failures": sum(c.failures.values())}

    def _held_to_the_reference(self, when: str) -> None:
        seat = self.client
        wrong = recovery_reference.violations(
            seat.events, {**self._ledgers(), "resumed_phases": seat.resumed,
                          "disk_loads": seat.loads},
            int(self.traffic.mix["arrival"]["kill"]["per_batch"]))
        wrong += self._kills_off_the_mark()
        if wrong:
            raise BenchFailure(f"recovery broke its reference in {when}: "
                               + "; ".join(wrong))
        log(f"recovery reference, {when}: {len(seat.events) // 2} batch(es) "
            f"killed once and stored next; phases resumed "
            f"{seat.resumed}, envelopes read from disk {seat.loads}")

    def _kills_off_the_mark(self) -> list:
        """Each kill fell where the mix says: the last envelope the dead
        attempt landed is the named job's, of the named phase."""
        from ethrex_tpu.utils.tracing import TRACER

        kill = self.traffic.mix["arrival"]["kill"]
        wrong = []
        for batch, at in self.client.kill_times.items():
            rec = TRACER.get_trace(self.coordinator.batch_traces.get(batch))
            landed = [s for s in (rec or {}).get("spans", ())
                      if s["name"] == "ckpt.store" and s["start"] < at]
            last = max(landed, key=lambda s: s["start"])["attrs"] \
                if landed else {}
            if (last.get("job"), last.get("phase")) \
                    != (kill["job"], kill["after_phase"]):
                wrong.append(
                    f"batch {batch} was killed after {last.get('job')}/"
                    f"{last.get('phase')}, the mix says {kill['job']}/"
                    f"{kill['after_phase']}")
        return wrong

    def counters(self) -> dict:
        return {**super().counters(), "coordinator.reclaims": getattr(
            self.coordinator, "reclaims_total", 0)}
