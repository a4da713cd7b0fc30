"""A preempted prover gets its batch back (docs/PROVER_RESILIENCE.md
"Reclaiming a lease"): the coordinator's reclaim edge, what
`checkpoint.in_flight()` reads off the disk, what a starting client
sends, and the whole path through a real coordinator on TCP and the
real `TpuBackend` with every STARK at test size, held to the plain
reference of the recovery semantics (benchmark/recovery_reference.py),
byte identity of resumed and uninterrupted proofs included.  Then the
cell's files (`prove-transfer10-preempted`) and its deployment, once,
on the CPU.
"""

import hashlib
import json
import os
import shutil
import socket
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402 — benchmark/harness.py
import recovery_reference  # noqa: E402 — benchmark/recovery_reference.py
import traffic  # noqa: E402 — benchmark/traffic.py

from ethrex_tpu.guest.execution import ProgramInput  # noqa: E402
from ethrex_tpu.guest.witness import generate_witness  # noqa: E402
from ethrex_tpu.l2.proof_coordinator import ProofCoordinator  # noqa: E402
from ethrex_tpu.l2.rollup_store import RollupStore  # noqa: E402
from ethrex_tpu.models import fibonacci as fib  # noqa: E402
from ethrex_tpu.node import Node  # noqa: E402
from ethrex_tpu.primitives.genesis import Genesis  # noqa: E402
from ethrex_tpu.primitives.transaction import Transaction  # noqa: E402
from ethrex_tpu.prover import checkpoint as ckpt  # noqa: E402
from ethrex_tpu.prover import protocol, tpu_backend  # noqa: E402
from ethrex_tpu.prover import runtime_errors as rt  # noqa: E402
from ethrex_tpu.prover.client import ProverClient  # noqa: E402
from ethrex_tpu.stark import prover as stark_prover  # noqa: E402
from ethrex_tpu.stark.prover import StarkParams  # noqa: E402
from ethrex_tpu.utils import faults, tracing  # noqa: E402
from ethrex_tpu.utils.faults import FaultPlan  # noqa: E402
from ethrex_tpu.utils.metrics import METRICS  # noqa: E402
from ethrex_tpu.utils.tracing import TRACER  # noqa: E402

TPU = protocol.PROVER_TPU
CELL = "prove-transfer10-preempted"


@pytest.fixture(autouse=True)
def _own_checkpoint_dir(tmp_path):
    ckpt.set_checkpoint_dir(str(tmp_path / "ckpt"))
    yield
    ckpt.set_checkpoint_dir(None)


# ===========================================================================
# (a) the coordinator's reclaim edge

def _coordinator(batches=3, **kw):
    store = RollupStore()
    for n in range(1, batches + 1):
        store.store_prover_input(n, protocol.PROTOCOL_VERSION, {"n": n})
    co = ProofCoordinator(store, needed_types=[TPU], **kw)
    clock = [1000.0]
    co._now = lambda: clock[0]
    return co, clock


def _state(co):
    with co.lock:
        return (dict(co.assignments), dict(co.assigned_at),
                dict(co.lease_tokens), dict(co.lease_holders),
                dict(co.failures), set(co.quarantined),
                co.reassignments_total, co.reclaims_total,
                {k: dict(v) for k, v in co.hedges.items()})


def test_a_matching_token_moves_the_lease_and_counts_nothing():
    co, clock = _coordinator(lease_timeout=600.0)
    batch, token = co.assign(TPU, "dead")
    assert batch == 1
    clock[0] += 250.0
    got, fresh = co.assign(TPU, "restarted", reclaim={
        "batch_id": 1, "lease_token": token})
    key = (1, TPU)
    assert got == 1 and fresh and fresh != token
    assert co.lease_tokens[key] == fresh
    assert co.lease_holders[key] == "restarted"
    assert co.assignments[key] == clock[0] + 600.0      # deadline reset
    assert co.assigned_at[key] == 1000.0                # lifetime anchor kept
    assert co.reassignments_total == 0 and not co.failures
    assert not co.quarantined and co.reclaims_total == 1
    assert co.stats_json()["reclaims"] == 1
    assert METRICS.counters.get("prover_lease_reclaims_total") == 1
    assert [e["event"] for e in co.events] == ["lease-reclaimed"]
    # the dead attempt's token is stale now: it extends and reclaims nothing
    assert co._handle_heartbeat({"batch_id": 1, "prover_type": TPU,
                                 "lease_token": token})["ok"] is False
    assert co.assign(TPU, "again", reclaim={
        "batch_id": 1, "lease_token": token}) == (2, co.lease_tokens[(2, TPU)])
    assert co.reclaims_total == 1


def test_a_crash_loop_is_still_cut_off_by_the_lifetime_cap():
    """Reclaims reset the deadline but never past `max_lease_lifetime`
    from the FIRST assignment; once that is spent no reclaim is granted,
    the lease lapses and its expiry is counted as it is today."""
    co, clock = _coordinator(lease_timeout=100.0, max_lease_lifetime=250.0)
    key = (1, TPU)
    _, token = co.assign(TPU, "p0")
    for k, deadline in ((1, 1190.0), (2, 1250.0)):      # clamped at the cap
        clock[0] += 90.0
        got, token = co.assign(TPU, f"p{k}", reclaim={
            "batch_id": 1, "lease_token": token})
        assert got == 1 and co.assignments[key] == deadline
    assert co.reclaims_total == 2 and co.assigned_at[key] == 1000.0
    clock[0] = 1251.0
    got, _ = co.assign(TPU, "p3", reclaim={"batch_id": 1,
                                           "lease_token": token})
    assert got == 1 and co.reclaims_total == 2      # an ordinary re-lease
    assert co.failures[key] == 1 and co.reassignments_total == 1


def _wrong_token(co, clock, token):
    return {"batch_id": 1, "lease_token": "0" * 32}


def _lapsed(co, clock, token):
    clock[0] += 601.0
    return {"batch_id": 1, "lease_token": token}


def _reassigned(co, clock, token):
    clock[0] += 601.0
    assert co.assign(TPU, "other")[0] == 1      # expiry counted, re-leased
    return {"batch_id": 1, "lease_token": token}


def _proven(co, clock, token):
    co.rollup.store_proof(1, TPU, {"backend": TPU})
    return {"batch_id": 1, "lease_token": token}


def _quarantined(co, clock, token):
    co.quarantined.add(1)
    return {"batch_id": 1, "lease_token": token}


def _hedge_token(co, clock, token):
    co.hedges[(1, TPU)] = {"token": "h" * 32, "assigned_at": clock[0],
                           "expires": clock[0] + 600.0, "prover_id": "h",
                           "reason": "straggler", "warm": None}
    return {"batch_id": 1, "lease_token": "h" * 32}


def _malformed(co, clock, token):
    return {"batch_id": "1", "lease_token": None}


@pytest.mark.parametrize("case", [
    _wrong_token, _lapsed, _reassigned, _proven, _quarantined, _hedge_token,
    _malformed], ids=lambda f: f.__name__.strip("_"))
def test_any_other_reclaim_is_served_as_an_ordinary_request(case):
    """What the same request without the field would have done is what
    happens, to the last counter."""
    answers = []
    for with_field in (True, False):
        co, clock = _coordinator()
        _, token = co.assign(TPU, "dead")
        reclaim = case(co, clock, token)
        before = _state(co)
        got = co.assign(TPU, "restarted",
                        reclaim=reclaim if with_field else None)
        after = _state(co)
        assert co.reclaims_total == 0
        # tokens are random: compare the shape of what changed
        answers.append((got[0], [
            {k: (v if not isinstance(v, str) else "token")
             for k, v in part.items()} if isinstance(part, dict) else part
            for part in after]))
        if case in (_wrong_token, _hedge_token, _malformed):
            # leased elsewhere and live: batch 1 stays as it was
            assert after[2][(1, TPU)] == before[2][(1, TPU)]
            assert got[0] == 2
    assert answers[0] == answers[1]


def test_the_response_says_what_became_of_the_reclaim():
    co, clock = _coordinator(batches=2)
    co.start()
    try:
        def ask(**more):
            with socket.create_connection(("127.0.0.1", co.port)) as sock:
                protocol.send_msg(sock, {
                    "type": protocol.INPUT_REQUEST, "prover_type": TPU,
                    "commit_hash": protocol.PROTOCOL_VERSION, **more})
                return protocol.recv_msg(sock)

        first = ask(prover_id="dead")
        assert "reclaim" not in first
        back = ask(prover_id="new", reclaim={
            "batch_id": 1, "lease_token": first["lease_token"]})
        assert (back["batch_id"], back["reclaim"]) == (1, "granted")
        assert back["trace_id"] == first["trace_id"]    # one batch trace
        stale = ask(prover_id="new", reclaim={
            "batch_id": 1, "lease_token": first["lease_token"]})
        assert (stale["batch_id"], stale["reclaim"]) == (2, "refused")
        co.rollup.store_proof(1, TPU, {"backend": TPU})
        done = ask(prover_id="new", reclaim={
            "batch_id": 1, "lease_token": back["lease_token"]})
        assert done["type"] == protocol.TYPE_NOT_NEEDED
        assert done["reclaim"] == "proven"
    finally:
        co.stop()


# ===========================================================================
# (b) what is in flight on this disk

def _land(batch, token, phases=("commit", "quotient"), job="j"):
    with ckpt.batch_context(batch, lease_token=token):
        with ckpt.job_scope(job):
            store = ckpt.phase_store(("air", 1), 5, (3, 12))
            for phase in phases:
                assert store.store(phase, {"x": phase})
                time.sleep(0.002)       # mtimes in the order written


def test_in_flight_finds_each_batch_with_its_newest_token():
    assert ckpt.in_flight() == []       # no directory yet: nothing, no raise
    _land(7, "first-attempt", ("commit",))
    _land(7, "second-attempt", ("quotient",))   # reclaimed, killed again
    _land(3, "tok-3")
    found = ckpt.in_flight()
    assert [(f.batch_id, f.lease_token, f.envelopes) for f in found] == \
        [(3, "tok-3", 2), (7, "second-attempt", 2)]
    sizes = [sum(os.path.getsize(os.path.join(ckpt._batch_dir(n), name))
                 for name in os.listdir(ckpt._batch_dir(n))) for n in (3, 7)]
    assert [f.disk_bytes for f in found] == sizes
    ckpt.complete(3)
    assert [f.batch_id for f in ckpt.in_flight()] == [7]


def test_in_flight_discards_what_this_code_cannot_resume(monkeypatch):
    from ethrex_tpu.utils import exec_cache

    _land(5, "good", ("commit",))
    # newer than it: a torn envelope and a garbage one
    bdir = ckpt._batch_dir(5)
    (good,) = os.listdir(bdir)
    with open(os.path.join(bdir, good), "rb") as f:
        whole = f.read()
    for name, data in (("a" * 64, whole[:len(whole) // 2]),
                       ("b" * 64, b"not an envelope")):
        time.sleep(0.002)
        with open(os.path.join(bdir, name + ".ckpt"), "wb") as f:
            f.write(data)
    # a batch whose only envelopes another code's fingerprint addressed
    monkeypatch.setattr(exec_cache, "_CODE_FINGERPRINT", "someone-else")
    _land(6, "stale-code")
    monkeypatch.setattr(exec_cache, "_CODE_FINGERPRINT", None)
    discards = ckpt.STATS["discards"]
    found = ckpt.in_flight()
    assert [(f.batch_id, f.lease_token, f.envelopes) for f in found] == \
        [(5, "good", 1)]
    assert ckpt.STATS["discards"] == discards + 4
    assert os.listdir(bdir) == [good]
    assert not os.path.exists(ckpt._batch_dir(6))
    # and the survivor still loads
    with ckpt.batch_context(5, lease_token="fresh"), ckpt.job_scope("j"):
        assert ckpt.phase_store(("air", 1), 5, (3, 12)).load("commit") \
            == {"x": "commit"}


def test_in_flight_is_off_with_the_checkpoints(monkeypatch):
    _land(9, "t")
    monkeypatch.setenv("ETHREX_PROOF_CKPT_OFF", "1")
    assert ckpt.in_flight() == []


# ===========================================================================
# (c) what a starting client sends

class _Asked(ProofCoordinator):
    """A coordinator that keeps the requests it was sent; with
    `knows_reclaim` False it is one from before the field: it never
    sees it, as JSON's readers never see a key they do not ask for."""

    knows_reclaim = True

    def _handle_request(self, msg):
        self.asked = getattr(self, "asked", [])
        if msg.get("type") == protocol.INPUT_REQUEST:
            self.asked.append(msg.get("reclaim"))
            if not self.knows_reclaim:
                msg = {k: v for k, v in msg.items() if k != "reclaim"}
        return super()._handle_request(msg)


def _exec_fleet(batches=3):
    """`batches` committed batches of one transfer behind a coordinator
    that serves the `exec` prover."""
    mix, kind = traffic.load_mix(
        os.path.join(BENCH, "traffic", "transfer10-backlog.json"))
    t = kind.Traffic({**mix, "transfers_per_block": 1}, 2**31 + 34)
    node = Node(Genesis.from_json(t.genesis()))
    store = RollupStore()
    ts = 1750000000
    for index in range(batches):
        for tx in t.batch(index)[0]:
            node.submit_transaction(
                Transaction.decode_canonical(t.signed(tx)))
        ts += 2
        block = node.produce_block(timestamp=ts)
        pi = ProgramInput(blocks=[block], config=node.config,
                          witness=generate_witness(node.chain, [block]))
        store.store_prover_input(index + 1, protocol.PROTOCOL_VERSION,
                                 pi.to_json())
    return store


def test_a_client_presents_what_its_disk_holds_once_and_only_at_start():
    store = _exec_fleet()
    co = _Asked(store, needed_types=[protocol.PROVER_EXEC]).start()
    ends = [("127.0.0.1", co.port)]
    try:
        # nothing on the disk: no field, ever
        first = ProverClient(protocol.PROVER_EXEC, ends,
                             heartbeat_interval=0, prewarm=False)
        assert first.poll_once() == 1 and co.asked == [None]
        # batch 2 leased to a prover that died with two envelopes landed;
        # batch 9's envelopes are of a lease this coordinator never gave
        _, token = co.assign(protocol.PROVER_EXEC, "dead")
        _land(2, token)
        _land(9, "unknown")
        born = ProverClient(protocol.PROVER_EXEC, ends,
                            heartbeat_interval=0, prewarm=False)
        assert born.poll_once() == 1
        assert born.proved == [2] and co.reclaims_total == 1
        assert born.poll_once() == 1 and born.proved == [2, 3]
        assert born.poll_once() == 0 and born.poll_once() == 0
        assert co.asked == [
            None, {"batch_id": 2, "lease_token": token},
            {"batch_id": 9, "lease_token": "unknown"}, None, None]
        # batch 2's envelopes went with its ack, batch 9's were refused
        # and stay for a later ordinary lease to resume; the first
        # client, alive all along, never presented anything
        assert [f.batch_id for f in ckpt.in_flight()] == [9]
        assert first.poll_once() == 0 and co.asked[-1] is None
        assert co.reassignments_total == 0 and not co.failures
        spans = TRACER.get_trace(co.batch_traces[2])["spans"]
        (reclaim,) = [s for s in spans if s["name"] == "prover.reclaim"]
        assert reclaim["attrs"] == {
            "batch": 2, "granted": True, "envelopes": 2,
            "disk_bytes": reclaim["attrs"]["disk_bytes"]}
        assert reclaim["attrs"]["disk_bytes"] > 0
        (fetch,) = [s for s in spans if s["name"] == "prover.fetch_input"]
        assert abs(reclaim["start"] + reclaim["seconds"]
                   - fetch["start"]) < 1e-6      # they tile, not overlap
        assert [s["attrs"]["attempt"] for s in spans
                if s["name"] == "prover.prove"] == [2]
        (refused,) = [s for s in TRACER.get_trace(co.batch_traces[3])["spans"]
                      if s["name"] == "prover.reclaim"]
        assert refused["attrs"]["batch"] == 9
        assert refused["attrs"]["granted"] is False
    finally:
        co.stop()


def test_envelopes_of_a_proven_batch_are_dropped_at_start():
    store = _exec_fleet(batches=1)
    co = _Asked(store, needed_types=[protocol.PROVER_EXEC]).start()
    try:
        _, token = co.assign(protocol.PROVER_EXEC, "dead")
        _land(1, token)
        store.store_proof(1, protocol.PROVER_EXEC, {"backend": "exec"})
        born = ProverClient(protocol.PROVER_EXEC, [("127.0.0.1", co.port)],
                            heartbeat_interval=0, prewarm=False)
        assert born.poll_once() == 0
        assert ckpt.in_flight() == [] and co.reclaims_total == 0
    finally:
        co.stop()


# ===========================================================================
# (d) the whole path: coordinator on TCP, the real TpuBackend with every
# STARK at test size, a backlog of three, a kill, a new client

SMALL = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)


class _SmallStark:
    """`stark/prover.py` as `TpuBackend` sees it, with every AIR swapped
    for the 64-row Fibonacci AIR (the idiom of
    tests/test_distributed_tracing.py): the backend's host work and its
    `execute` envelope are the real batch's, the real `_prove_attempt`
    with its envelopes and kill points runs at test size."""

    def prove(self, air, trace, pub, params, mesh=None):
        trace = fib.generate_trace(64)
        return stark_prover.prove(fib.FibonacciAir(), trace,
                                  fib.public_inputs(trace), SMALL)

    def compile_ahead(self, *args, **kwargs):
        pass

    def warm_fri_programs(self, *args, **kwargs):
        pass


@pytest.fixture
def small_stark(monkeypatch):
    monkeypatch.setattr(tpu_backend, "stark_prover", _SmallStark())


def _digest(proof):
    return hashlib.sha256(
        json.dumps(proof, sort_keys=True).encode()).hexdigest()


def _tpu_fleet(coordinator=ProofCoordinator):
    store = _exec_fleet()
    co = coordinator(store, needed_types=[TPU]).start()
    uninterrupted = {
        n: _digest(tpu_backend.TpuBackend().prove(
            ProgramInput.from_json(store.get_prover_input(
                n, protocol.PROTOCOL_VERSION)), protocol.FORMAT_STARK))
        for n in (1, 2, 3)}
    return store, co, uninterrupted


def _client(co):
    return ProverClient(TPU, [("127.0.0.1", co.port)], prewarm=False,
                        heartbeat_interval=0)


# the kill point of cell `prove-transfer10-preempted`: the seventh
# occasion of the `backend.phase` drop leg in a live batch
KILL = dict(times=1, after=6)


def _kill_one(co):
    dead = _client(co)
    with faults.injected(FaultPlan(34).drop("backend.phase", **KILL)) as plan:
        assert dead.poll_once() == 0
    assert plan.log == [("backend.phase", "drop")]
    return dead


def test_the_seventh_phase_boundary_is_after_the_transfer_quotient(
        small_stark):
    """The benchmark's kill point, pinned on the real backend's path:
    `execute`, the state circuit's four phases, then the transfer
    circuit's commit and quotient have landed, and nothing after."""
    _, co, _ = _tpu_fleet()
    try:
        _kill_one(co)
        landed = [(s["attrs"]["job"], s["attrs"]["phase"])
                  for s in TRACER.get_trace(co.batch_traces[1])["spans"]
                  if s["name"] == "ckpt.store"]
        assert landed == [
            ("backend", "execute"),
            *(("state_proof", p) for p in
              ("commit", "quotient", "open", "fri", "proof")),
            ("vm_circuits/TransferAir", "commit"),
            ("vm_circuits/TransferAir", "quotient")]
        (mine,) = ckpt.in_flight()
        assert (mine.batch_id, mine.envelopes) == (1, 8)
        assert mine.lease_token == co.lease_token(1, TPU)
    finally:
        co.stop()


def test_a_restarted_prover_is_given_its_batch_back_and_resumes_it(
        small_stark):
    store, co, uninterrupted = _tpu_fleet()
    try:
        dead = _kill_one(co)
        events = [("kill", 1)]
        resumes, loads = rt.STATS["phase_resumes"], ckpt.STATS["loads"]
        del dead
        born = _client(co)
        for _ in range(3):
            assert born.poll_once() == 1
            events.append(("store", born.proved[-1]))
            if len(events) == 2:
                resumed = rt.STATS["phase_resumes"] - resumes
                loaded = ckpt.STATS["loads"] - loads
        # the recovery reference, on what happened (batches 2 and 3 were
        # not killed: the reference is told so by kills_per_batch 0 for
        # them being a violation it must name)
        ledgers = {"reassignments": co.reassignments_total,
                   "quarantined": len(co.quarantined),
                   "rejected_submits": co.rejected_submits_total,
                   "failures": sum(co.failures.values()),
                   "resumed_phases": {1: resumed}, "disk_loads": {1: loaded}}
        assert recovery_reference.violations(events[:2], ledgers) == []
        assert recovery_reference.violations(events, ledgers) == [
            "batch 2 was killed 0 time(s), the mix says 1",
            "batch 3 was killed 0 time(s), the mix says 1"]
        assert born.proved == [1, 2, 3] and co.reclaims_total == 1
        # execute, the state circuit's finished proof, the transfer
        # circuit's commit and quotient: loaded, not proved again
        assert resumed == 4 and loaded == 4
        proofs = {n: _digest(store.get_proof(n, TPU)) for n in (1, 2, 3)}
        assert recovery_reference.same_proofs(proofs, uninterrupted) == []
        assert ckpt.in_flight() == []
        spans = TRACER.get_trace(co.batch_traces[1])["spans"]
        proves = [s for s in spans if s["name"] == "prover.prove"]
        assert [(s["attrs"]["attempt"], s["status"]) for s in proves] == \
            [(1, "error"), (2, "ok")]
        assert [s["attrs"]["resumed_phases"] for s in spans
                if s["name"] == "backend.prove"] == [0, 4]
        disk = [s["attrs"]["disk_bytes"] for s in spans
                if s["name"] == "ckpt.load"]
        assert len(disk) == 4 and all(b > 0 for b in disk)
        (reclaim,) = [s for s in spans if s["name"] == "prover.reclaim"]
        assert reclaim["attrs"]["granted"] and \
            reclaim["attrs"]["envelopes"] == 8
        assert sum(1 for s in spans if s["name"] == "prover.assign") == 2
        # both attempts are one trace, and it stays inside the budget
        # with BASELINE-1's 13 + 13 + 8 FRI layers in place of this
        # size's and the two `prover.idle` that `run_forever` adds (136
        # spans on the chip, docs/OBSERVABILITY.md)
        layers = sum(1 for s in spans if s["name"] == "fri.layer")
        assert len(spans) - layers + 34 + 2 == 136 \
            <= tracing.BATCH_SPAN_BUDGET
        assert "lease-reclaimed" in [e["event"] for e in co.events]
    finally:
        co.stop()


def test_a_coordinator_from_before_the_field_hands_out_the_next_batch(
        small_stark):
    """The parent's behaviour, pinned: the field is ignored, the
    restarted prover proves batches 2 and 3 while batch 1 waits out its
    lease; its envelopes are kept, and the ordinary lease that follows
    the expiry (a failure against the batch, as today) resumes from
    them."""

    class Old(_Asked):
        knows_reclaim = False

    store, co, uninterrupted = _tpu_fleet(Old)
    try:
        _kill_one(co)
        born = _client(co)
        assert born.poll_once() == 1 and born.poll_once() == 1
        assert born.proved == [2, 3] and born.poll_once() == 0
        assert [f.batch_id for f in ckpt.in_flight()] == [1]
        assert recovery_reference.violations(
            [("kill", 1), ("store", 2)], {}) != []
        now = co._now()
        co._now = lambda: now + co.lease_timeout + 1.0
        resumes = rt.STATS["phase_resumes"]
        assert born.poll_once() == 1 and born.proved == [2, 3, 1]
        assert rt.STATS["phase_resumes"] - resumes == 4
        assert co.reassignments_total == 1 and co.failures[(1, TPU)] == 1
        assert getattr(co, "reclaims_total", 0) == 0
        assert _digest(store.get_proof(1, TPU)) == uninterrupted[1]
    finally:
        co.stop()


# ===========================================================================
# (e) the cell's files, and its deployment once on the CPU

BENCHMARK = harness.load_benchmark()


def _json(*path):
    with open(os.path.join(BENCH, *path)) as f:
        return json.load(f)


def test_the_configuration_is_baseline1_prover_but_for_the_preemption():
    control = _json("configs", "baseline1-prover.json")
    mine = _json("configs", "baseline1-prover-preempted.json")
    differ = {k for k in set(control) | set(mine)
              if control.get(k) != mine.get(k)}
    assert differ == {"name", "source", "source_part", "deployment",
                      "reduced", "assumed", "guarantees"}
    assert mine["deployment"] == "prover_fleet_preempted"
    assert len(mine["reduced"]) == 2
    # every size, every check and every guarantee of the control stands
    assert {k: v for k, v in mine["guarantees"].items() if k != "stated"} \
        == {k: v for k, v in control["guarantees"].items() if k != "stated"}
    assert mine["guarantees"]["stated"].startswith(
        control["guarantees"]["stated"])
    assert "stored exactly once" in mine["guarantees"]["stated"]
    assert control["assumed"].items() <= mine["assumed"].items()
    (entry,) = [c for c in BENCHMARK["configs"]
                if c["name"] == "baseline1-prover-preempted"]
    assert entry["source"] == mine["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == [r.split()[0].rstrip(":")
                                for r in mine["reduced"]]


def test_batch_k_is_the_same_input_as_in_the_control_cell():
    control = _json("traffic", "transfer10-backlog.json")
    mine = _json("traffic", "transfer10-backlog-preempt.json")
    assert {k for k in set(control) | set(mine)
            if control.get(k) != mine.get(k)} == {"arrival", "trace_seconds"}
    assert mine["arrival"] == {
        "mode": "backlog_preempt", "batches_committed_ahead": 12,
        "kill": {"site": "backend.phase", "job": "vm_circuits/TransferAir",
                 "after_phase": "quotient", "per_batch": 1}}
    assert 0 < mine["trace_seconds"] < BENCHMARK["run_seconds"]
    # so the same seed sends the same bytes
    _, kind = traffic.load_mix(
        os.path.join(BENCH, "traffic", "transfer10-backlog-preempt.json"))
    a, b = kind.Traffic(control, 2**31 + 34), kind.Traffic(mine, 2**31 + 34)
    assert a.genesis() == b.genesis()
    assert [a.signed(t) for k in range(3) for t in a.batch(k)[0]] == \
        [b.signed(t) for k in range(3) for t in b.batch(k)[0]]


def test_the_cell_is_listed_by_every_metric_it_reports():
    (cell,) = [w for w in BENCHMARK["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "baseline1-prover-preempted", "transfer10-backlog-preempt", 1)
    e2e = {m["name"] for m in harness.metrics_of(BENCHMARK, "end_to_end", CELL)}
    assert e2e == {"batch_prove_s", "setup_s"}
    reported = {m["name"]: m for m in
                harness.metrics_of(BENCHMARK, "per_layer", CELL, e2e)}
    new = ("reclaim_s", "resume_load_s", "redo_host_s", "lost_attempt_s",
           "resumed_phases")
    control = {m["name"] for m in harness.metrics_of(
        BENCHMARK, "per_layer", "prove-transfer10", e2e)}
    # the control's readers but the one whose interval two attempts break
    assert set(reported) == (control - {"coord_overhead_s"}) | set(new)
    for name in new:
        assert reported[name]["workloads"] == [CELL]
        assert reported[name]["moves"] == "batch_prove_s"
        decl = _json("metrics", name + ".json")
        assert (decl["layer"], decl["unit"], decl["workloads"]) == (
            reported[name]["layer"], reported[name]["unit"], [CELL])
    # nothing a metric lists was taken away or reordered
    for m in BENCHMARK["per_layer"]:
        if m["name"] in control:
            assert m["workloads"][:2] == ["prove-transfer10", "prove-erc20"]


@pytest.mark.parametrize("events, ledgers, says", [
    ([("kill", 1), ("store", 1), ("kill", 2), ("store", 2)], {}, None),
    ([("kill", 1), ("store", 2)], {}, "stored next after the kill of batch 1"),
    ([("store", 1)], {}, "killed 0 time(s)"),
    ([("kill", 1), ("kill", 1), ("store", 1)], {}, "killed 2 time(s)"),
    ([("kill", 1), ("store", 1), ("store", 1)], {}, "stored twice"),
    ([("kill", 1)], {}, "never stored"),
    ([("kill", 2), ("store", 2), ("kill", 1), ("store", 1)], {},
     "stored after batch 2"),
    ([("kill", 1), ("store", 1)], {"reassignments": 1}, "reassignments 1"),
    ([("kill", 1), ("store", 1)], {"failures": 1}, "failures 1"),
    ([("kill", 1), ("store", 1)], {"resumed_phases": {1: 0}},
     "resumed no phase"),
    ([("kill", 1), ("store", 1)], {"disk_loads": {}},
     "read no envelope from the disk"),
])
def test_the_recovery_reference_names_what_broke(events, ledgers, says):
    killed = {b for what, b in events if what == "kill"}
    full = {"resumed_phases": dict.fromkeys(killed, 4),
            "disk_loads": dict.fromkeys(killed, 4), **ledgers}
    wrong = recovery_reference.violations(events, full)
    if says is None:
        assert wrong == []
    else:
        assert any(says in line for line in wrong), wrong
    assert recovery_reference.same_proofs({1: "a"}, {1: "a", 2: "b"}) == []
    assert recovery_reference.same_proofs({1: "a"}, {1: "b"}) != []


def _standin_cell(tmp_path, deployment, arrival):
    """The cell as files, cut to what the CPU can prove in seconds: one
    transfer a batch, a backlog of three, every STARK at test size (so
    the guarantees that judge a STARK are left out)."""
    bench_dir = str(tmp_path / "benchmark")
    for sub in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(BENCH, sub),
                        os.path.join(bench_dir, sub), dirs_exist_ok=True)
    config = _json("configs", "baseline1-prover-preempted.json")
    config.update(name="standin", deployment=deployment, guarantees={
        "backend": TPU, "reference_state": True, "no_degradation": True})
    mix = {**_json("traffic", "transfer10-backlog-preempt.json"),
           "transfers_per_block": 1, "trace_seconds": 1.5,
           "arrival": {**arrival, "batches_committed_ahead": 3}}
    for sub, doc in (("configs", config), ("traffic", mix)):
        with open(os.path.join(bench_dir, sub, "standin.json"), "w") as f:
            json.dump(doc, f)
    bench = json.loads(json.dumps(BENCHMARK))
    bench["workloads"].append({"name": "standin", "config": "standin",
                               "traffic": "standin", "chips": 1, "why": "-"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            m["workloads"].append("standin")
    bench_path = str(tmp_path / "BENCHMARK.json")
    with open(bench_path, "w") as f:
        json.dump(bench, f)
    return bench_path, bench_dir


def _run_standin(tmp_path, monkeypatch, deployment, arrival, trace):
    import check

    digests = {}
    judge = check.judge

    def judging(records, *args, **kwargs):
        digests.update({r.number: _digest(r.proof) for r in records
                        if r.proof})
        return judge(records, *args, **kwargs)

    monkeypatch.setattr(check, "judge", judging)
    # the harness clears the program's default directory at its start;
    # here that is this test's own
    bench_path, bench_dir = _standin_cell(tmp_path, deployment, arrival)
    result = harness.run_cell(
        "standin", 2**31 + 34, 1.5, trace, time.monotonic(),
        bench_path=bench_path, bench_dir=bench_dir,
        device={"platform": "cpu", "kind": "cpu", "count": 1})
    return result, digests


def test_the_deployment_runs_the_cell_on_the_cpu(tmp_path, monkeypatch,
                                                 small_stark, capsys):
    """Set-up's warm-up batch and the window's batch are each killed
    after the transfer circuit's quotient, reclaimed, resumed from disk
    and stored next; the new readers read their spans; and each proof is
    the control deployment's proof of the same seed and batch, byte for
    byte.  (CPU, test size: correctness only, no number here is a
    device's.)"""
    kill = _json("traffic", "transfer10-backlog-preempt.json")["arrival"]
    result, preempted = _run_standin(
        tmp_path / "p", monkeypatch, "prover_fleet_preempted", kill, True)
    out = capsys.readouterr().out
    assert result["correct"], json.dumps(result["compared"])
    assert result["attempted"] == 1 and result["failed"] == 0
    assert "recovery reference, set-up: 1 batch(es) killed once" in out
    assert "recovery reference, the window: 2 batch(es) killed once" in out
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["resumed_phases"] == 4 and m["spans_lost"] == 0
    assert m["reclaim_s"] > 0 and m["resume_load_s"] > 0
    assert m["lost_attempt_s"] > m["redo_host_s"] > 0
    assert 2.0 <= m["prover_idle_s"] < 2.5      # two 1 s polls a batch
    assert "coord_overhead_s" not in m
    control, uninterrupted = _run_standin(
        tmp_path / "c", monkeypatch, "prover_fleet", {"mode": "backlog"},
        False)
    assert control["correct"], control["compared"]
    assert sorted(preempted) == [1, 2] and sorted(uninterrupted)[:2] == [1, 2]
    assert recovery_reference.same_proofs(preempted, uninterrupted) == []


def test_a_program_without_the_reclaim_edge_is_refused_before_it_builds(
        monkeypatch):
    """The parent of this change: its coordinator ignores the field, so
    the deployment's first question ends the run (exit 3 from run.py)
    before a backlog is committed or a program hydrated."""
    from common import BenchFailure

    refuse = harness.load_deployment("prover_fleet_preempted") \
        .setup.__globals__["refuse_a_program_without_reclaim"]
    monkeypatch.setattr(
        ProofCoordinator, "_handle_request",
        lambda self, msg, old=ProofCoordinator._handle_request: old(
            self, {k: v for k, v in msg.items() if k != "reclaim"}))
    t0 = time.monotonic()
    with pytest.raises(BenchFailure, match="does not hand a restarted"):
        refuse(TPU)
    assert time.monotonic() - t0 < 5
