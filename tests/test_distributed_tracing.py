"""Fleet-wide distributed tracing: span shipping over the prover
protocol, merged cross-process batch trees, critical-path attribution,
Perfetto export, and the chaos drills for partial/hedged subtrees
(docs/OBSERVABILITY.md "Distributed tracing")."""

import json
import os
import subprocess
import sys
import time

import pytest

from ethrex_tpu.crypto import secp256k1
from ethrex_tpu.l2.l1_client import InMemoryL1
from ethrex_tpu.l2.sequencer import Sequencer, SequencerConfig
from ethrex_tpu.node import Node
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.primitives.transaction import TYPE_DYNAMIC_FEE, Transaction
from ethrex_tpu.prover import protocol
from ethrex_tpu.rpc.server import RpcServer
from ethrex_tpu.utils import tracing
from ethrex_tpu.utils.metrics import METRICS
from ethrex_tpu.utils.tracing import (INGEST_SPANS_PER_SOURCE, TRACER,
                                      WIRE_VERSION, Span, Tracer,
                                      critical_path, export_wire,
                                      to_trace_events)

SECRET = 0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8
SENDER = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(SECRET))

GENESIS = {
    "config": {"chainId": 65536999, "terminalTotalDifficulty": 0,
               "shanghaiTime": 0, "cancunTime": 0},
    "alloc": {"0x" + SENDER.hex(): {"balance": hex(10**21)}},
    "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7", "timestamp": "0x0",
}


def _transfer(nonce, value=100):
    return Transaction(
        tx_type=TYPE_DYNAMIC_FEE, chain_id=65536999, nonce=nonce,
        max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
        gas_limit=21000, to=bytes.fromhex("aa" * 20), value=value,
    ).sign(SECRET)


def _committed_sequencer():
    """Node + sequencer with batch 1 committed and the coordinator's TCP
    server running — the fixture every cross-process drill starts from."""
    node = Node(Genesis.from_json(GENESIS))
    l1 = InMemoryL1(needed_prover_types=[protocol.PROVER_EXEC])
    seq = Sequencer(node, l1, SequencerConfig(
        needed_prover_types=(protocol.PROVER_EXEC,)))
    seq.coordinator.start()
    node.submit_transaction(_transfer(0))
    seq.produce_block()
    assert seq.commit_next_batch() is not None
    return node, seq


def _record(tracer, tid, name, start, seconds, parent=None, span_id=None,
            **attrs):
    """Drop one completed span into a scratch tracer."""
    sp = Span(tid, span_id or tracing.new_span_id(), parent, name, attrs)
    sp.start = start
    sp.seconds = seconds
    tracer.record(sp)
    return sp.span_id


# ---------------------------------------------------------------------------
# wire export


def test_export_wire_payload_shape_and_bounds():
    t = Tracer(capacity=8)
    tid = "ab" * 8
    root = _record(t, tid, "root", 100.0, 5.0)
    for i in range(5):
        _record(t, tid, f"leaf{i}", 100.5 + i, 0.1 * (i + 1), parent=root)
    payload = export_wire(tid, tracer=t)
    assert payload["v"] == WIRE_VERSION
    assert payload["truncated"] is False
    starts = [s["start"] for s in payload["spans"]]
    assert starts == sorted(starts)
    assert len(payload["spans"]) == 6
    # over max_spans the LONGEST spans survive (critical-path fodder)
    small = export_wire(tid, max_spans=2, tracer=t)
    assert small["truncated"] is True
    assert {s["name"] for s in small["spans"]} == {"root", "leaf4"}
    # over max_bytes the list is halved until the payload fits
    tiny = export_wire(tid, max_bytes=400, tracer=t)
    assert tiny["truncated"] is True
    assert len(json.dumps(tiny)) < 400 + 100  # envelope slack
    assert any(s["name"] == "root" for s in tiny["spans"])


def test_export_wire_unknown_or_bad_trace_is_none():
    t = Tracer(capacity=4)
    assert export_wire("ff" * 8, tracer=t) is None
    assert export_wire(None, tracer=t) is None
    assert export_wire(1234, tracer=t) is None
    assert export_wire("", tracer=t) is None


# ---------------------------------------------------------------------------
# ingest / merge


def test_ingest_rejects_malformed_payloads_without_raising():
    t = Tracer(capacity=4)
    for junk in (None, "x", 42, [], {}, {"v": 99, "spans": []},
                 {"v": WIRE_VERSION}, {"v": WIRE_VERSION, "spans": "nope"}):
        assert t.ingest(junk) == 0
    assert len(t) == 0 and t.ingested == 0


def test_ingest_merges_dedupes_and_counts():
    t = Tracer(capacity=8)
    tid = "cd" * 8
    good = {"traceId": tid, "spanId": "s1", "parentId": None,
            "name": "prover.prove", "start": 10.0, "seconds": 2.0,
            "attrs": {"batch": 1}, "status": "ok"}
    bad = {"traceId": tid, "name": "no-span-id", "start": 10.0,
           "seconds": 1.0}
    payload = {"v": WIRE_VERSION, "spans": [good, bad]}
    assert t.ingest(payload, source="prover-a") == 1
    assert t.ingested == 1 and t.ingest_dropped == 1
    rec = t.get_trace(tid)
    assert rec["spans"][0]["source"] == "prover-a"
    assert rec["spans"][0]["attrs"] == {"batch": 1}
    # heartbeat payloads are cumulative: re-shipping is an idempotent no-op
    assert t.ingest(payload, source="prover-a") == 0
    assert len(t.get_trace(tid)["spans"]) == 1


def test_ingest_caps_spans_per_source():
    t = Tracer(capacity=8)
    tid = "ee" * 8
    spans = [{"traceId": tid, "spanId": f"s{i}", "name": "n",
              "start": float(i), "seconds": 0.1} for i in range(300)]
    added = t.ingest({"v": WIRE_VERSION, "spans": spans}, source="chatty")
    assert added == INGEST_SPANS_PER_SOURCE
    assert t.ingest_dropped == 300 - INGEST_SPANS_PER_SOURCE
    # a different source still gets its own allowance on the same trace
    other = [{"traceId": tid, "spanId": f"o{i}", "name": "n",
              "start": float(i), "seconds": 0.1} for i in range(10)]
    assert t.ingest({"v": WIRE_VERSION, "spans": other}, source="b") == 10


def test_rootless_trace_renders_partial_without_skewing_slowest():
    t = Tracer(capacity=8)
    # shipped subtree whose parent never made it into this ring: every
    # span has a parentId, so the trace has no root
    tid = "aa" * 8
    spans = [{"traceId": tid, "spanId": "s1", "parentId": "gone",
              "name": "prover.prove", "start": 0.0, "seconds": 2.0},
             {"traceId": tid, "spanId": "s2", "parentId": "s1",
              "name": "stark.fri_fold", "start": 500.0, "seconds": 0.5}]
    assert t.ingest({"v": WIRE_VERSION, "spans": spans}, source="p") == 2
    # a rooted trace of modest extent
    _record(t, "bb" * 8, "root", 0.0, 3.0)
    slowest = t.slowest(5)
    # the rootless trace reports its longest single span (2.0s), NOT the
    # fabricated 500.5s wall extent — so the rooted 3s trace sorts first
    assert [e["traceId"] for e in slowest] == ["bb" * 8, "aa" * 8]
    partial = slowest[1]
    assert partial["partial"] is True and partial["seconds"] == 2.0
    assert "partial" not in slowest[0]


# ---------------------------------------------------------------------------
# critical-path analysis


def _trace(spans):
    return {"traceId": "t1", "spans": spans}


def _span(sid, name, start, seconds, parent=None, source=None, stage=None):
    s = {"traceId": "t1", "spanId": sid, "parentId": parent, "name": name,
         "start": start, "seconds": seconds}
    if source:
        s["source"] = source
    if stage:
        s["attrs"] = {"stage": stage}
    return s


def test_critical_path_components_sum_to_wall():
    cp = critical_path(_trace([
        _span("a", "prover.assign", 0.0, 10.0),
        _span("p", "prover.prove", 2.0, 6.0, parent="a", source="x"),
        _span("l", "stark.trace_lde", 2.0, 3.0, parent="p", source="x",
              stage="trace_lde"),
        _span("q", "stark.quotient", 5.5, 2.0, parent="p", source="x",
              stage="quotient"),
    ]))
    assert cp["wallSeconds"] == 10.0
    assert abs(sum(cp["components"].values()) - 10.0) < 1e-9
    # stage spans are attributed per-stage; uncovered prove time stays
    # with prove; assign owns the head/tail the prove never covered
    assert abs(cp["components"]["prove/trace_lde"] - 3.0) < 1e-9
    assert abs(cp["components"]["prove/quotient"] - 2.0) < 1e-9
    assert cp["sources"] == ["local", "x"]
    assert cp["partial"] is False
    # the chain is ordered by start and carries component labels
    chain = cp["chain"]
    assert [e["start"] for e in chain] == sorted(e["start"] for e in chain)
    assert {"prover.assign", "prover.prove"} <= {e["name"] for e in chain}


def test_critical_path_gap_is_queue_wait():
    cp = critical_path(_trace([
        _span("a", "prover.assign", 0.0, 3.0),
        _span("v", "proof.verify", 5.0, 5.0),
    ]))
    assert cp["wallSeconds"] == 10.0
    assert abs(cp["components"]["queue-wait"] - 2.0) < 1e-9
    assert abs(cp["components"]["verify"] - 5.0) < 1e-9
    assert abs(sum(cp["components"].values()) - 10.0) < 1e-9


def test_critical_path_hedged_overlap_never_double_counts():
    # hedged batch: two prover subtrees racing over overlapping wall time
    cp = critical_path(_trace([
        _span("p1", "prover.prove", 0.0, 6.0, parent="gone-a", source="a"),
        _span("p2", "prover.prove", 4.0, 6.0, parent="gone-b", source="b"),
    ]))
    assert cp["wallSeconds"] == 10.0
    # 12 span-seconds ran, but only 10 wall-seconds are attributed
    assert abs(sum(cp["components"].values()) - 10.0) < 1e-9
    assert cp["sources"] == ["a", "b"]
    # orphans anchor at top level, so the whole wall is covered by prove
    assert abs(cp["components"]["prove"] - 10.0) < 1e-9
    assert cp["partial"] is True  # every span has a (missing) parent


def test_critical_path_is_defensive():
    assert critical_path(None)["spanCount"] == 0
    assert critical_path({})["components"] == {}
    cp = critical_path({"traceId": "x", "spans": [
        "junk", {"spanId": "no-times"},
        {"spanId": "ok", "name": "n", "start": 1.0, "seconds": 1.0}]})
    assert cp["spanCount"] == 1 and cp["wallSeconds"] == 1.0


def _reassigned_batch(idle_seconds):
    """Prover x takes the batch and dies; prover y, whose polls came
    back empty all the while, gets it 30 s later."""
    return [
        _span("a1", "prover.assign", 100.0, 0.01),
        _span("p1", "prover.prove", 100.01, 4.99, parent="a1", source="x"),
        _span("a2", "prover.assign", 135.0, 0.01),
        _span("i2", "prover.idle", 135.0 - idle_seconds, idle_seconds,
              parent="a2", source="y"),
        _span("f2", "prover.fetch_input", 135.0, 0.02, parent="a2",
              source="y"),
        _span("p2", "prover.prove", 135.02, 9.98, parent="a2", source="y"),
        _span("c2", "prover.ckpt_complete", 145.5, 0.5, parent="a2",
              source="y"),
    ]


@pytest.mark.parametrize("idle_seconds", [1.0, 40.0, 7200.0])
def test_critical_path_leaves_the_clients_wait_out(idle_seconds):
    spans = _reassigned_batch(idle_seconds)
    cp = critical_path(_trace(spans))
    bare = critical_path(_trace(
        [s for s in spans if s["name"] not in tracing.OFF_PATH_SPANS]))
    # neither the wait before the batch nor the clean-up after the ack
    # moves the wall or any component, however long the fleet idled
    assert cp == bare
    assert cp["start"] == 100.0 and abs(cp["wallSeconds"] - 45.0) < 1e-9
    assert abs(cp["components"]["prove"] - (4.99 + 9.98)) < 1e-9
    # the reassigned batch keeps its queue-wait: y's empty polls do not
    # cover the 30 s in which nobody held it
    assert abs(cp["components"]["queue-wait"] - 30.0) < 1e-9
    # the fetch is the assignment as the client times it
    assert abs(cp["components"]["assign"] - 0.03) < 1e-9
    assert abs(sum(cp["components"].values()) - 45.0) < 1e-9


def test_trace_summaries_leave_the_clients_wait_out():
    t = Tracer(capacity=8)
    tid = "cd" * 8
    root = _record(t, tid, "prover.assign", 1000.0, 0.01)
    _record(t, tid, "prover.idle", 1000.0 - 7200.0, 7200.0, parent=root)
    _record(t, tid, "prover.prove", 1000.01, 2.0, parent=root)
    _record(t, "ce" * 8, "root", 0.0, 3.0)
    slowest = t.slowest(5)
    assert [e["traceId"] for e in slowest] == ["ce" * 8, tid]
    assert slowest[1]["start"] == 1000.0
    assert abs(slowest[1]["seconds"] - 2.01) < 1e-6
    assert slowest[1]["spanCount"] == 3     # the span is still listed


def test_critical_path_leaf_span_takes_its_stages_component():
    cp = critical_path(_trace([
        _span("p", "prover.prove", 0.0, 10.0),
        _span("b", "backend.prove", 0.0, 10.0, parent="p"),
        _span("v", "prove.vm_batch", 0.0, 1.0, parent="b"),
        _span("j", "prove.vm_circuits/TransferAir", 1.0, 9.0, parent="b",
              stage="vm_circuits"),
        _span("g", "prove.trace_gen", 1.0, 2.0, parent="j"),
        _span("c", "ckpt.store", 3.0, 1.0, parent="j", stage="ckpt"),
        _span("f", "prove.fri_fold", 4.0, 3.0, parent="j",
              stage="fri_fold"),
        _span("l", "fri.layer", 4.0, 1.0, parent="f"),
        _span("r", "fri.grind", 5.0, 2.0, parent="f"),
        _span("q", "prove.query", 7.0, 3.0, parent="j", stage="query"),
        _span("n", "query.canon", 7.0, 2.9, parent="q"),
    ]))
    # the leaf spans name no stage and leave the components where they
    # were before the spans existed; `ckpt` is the one new component
    assert {k: round(v, 9) for k, v in cp["components"].items()} == {
        "prove/vm_circuits": 2.0, "prove/ckpt": 1.0,
        "prove/fri_fold": 3.0, "prove/query": 3.0, "other": 1.0}


# ---------------------------------------------------------------------------
# Perfetto / Chrome trace-event export


def test_trace_events_pids_flows_and_json():
    doc = to_trace_events(_trace([
        _span("a", "prover.assign", 0.0, 10.0),
        _span("p", "prover.prove", 2.0, 6.0, parent="a", source="px"),
        _span("l", "stark.trace_lde", 2.0, 3.0, parent="p", source="px",
              stage="trace_lde"),
    ]))
    events = doc["traceEvents"]
    json.dumps(doc)  # schema-valid JSON all the way down
    xs = {e["name"]: e for e in events if e["ph"] == "X"}
    assert set(xs) == {"prover.assign", "prover.prove", "stark.trace_lde"}
    # local process is pid 1; the remote source gets its own pid
    assert xs["prover.assign"]["pid"] == 1
    assert xs["prover.prove"]["pid"] == xs["stark.trace_lde"]["pid"] == 2
    assert xs["prover.prove"]["dur"] == 6.0 * 1e6
    assert xs["stark.trace_lde"]["args"]["stage"] == "trace_lde"
    metas = {e["args"]["name"] for e in events if e["ph"] == "M"
             and e["name"] == "process_name"}
    assert metas == {"local", "prover:px"}
    # exactly one flow pair crosses the submit seam (assign -> prove);
    # the intra-pid prove -> trace_lde link needs no flow arrow
    starts = [e for e in events if e["ph"] == "s"]
    finishes = [e for e in events if e["ph"] == "f"]
    assert len(starts) == len(finishes) == 1
    assert starts[0]["id"] == finishes[0]["id"]
    assert starts[0]["name"] == finishes[0]["name"] == "submit-seam"
    assert (starts[0]["pid"], finishes[0]["pid"]) == (1, 2)


def test_trace_events_tolerates_garbage():
    # no spans survive filtering: only process metadata remains, and the
    # document still loads
    for junk in (None, {"traceId": "x", "spans": ["junk", {"a": 1}]}):
        doc = to_trace_events(junk)
        assert all(e["ph"] == "M" for e in doc["traceEvents"])
        json.dumps(doc)


# ---------------------------------------------------------------------------
# flagship: a real second process ships its subtree over TCP


_PROVER_SCRIPT = """
import sys, time
from ethrex_tpu.prover import protocol
from ethrex_tpu.prover.client import ProverClient

client = ProverClient(protocol.PROVER_EXEC,
                      [("127.0.0.1", int(sys.argv[1]))],
                      heartbeat_interval=0.05,
                      prover_id="remote-prover", prewarm=False)
deadline = time.monotonic() + 120
while time.monotonic() < deadline:
    if client.poll_once():
        sys.exit(0)
    time.sleep(0.1)
sys.exit(3)
"""


def test_e2e_one_merged_trace_across_processes():
    """The acceptance drill: the prover runs in a SEPARATE process, so
    the spans it ships over TCP are ones this process's ring never saw —
    one batch still renders as one merged cross-process tree, with
    critical-path attribution, a Perfetto export whose flow links cross
    the submit seam, and an exemplar resolving to the trace."""
    node, seq = _committed_sequencer()
    try:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PYTHONPATH=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))))
        proc = subprocess.run(
            [sys.executable, "-c", _PROVER_SCRIPT,
             str(seq.coordinator.port)],
            env=env, timeout=300, capture_output=True)
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        assert seq.send_proofs() == (1, 1)

        tid = seq.coordinator.batch_traces[1]
        trace = TRACER.get_trace(tid)
        spans = trace["spans"]
        names = {s["name"] for s in spans}
        # local lifecycle spans AND the subprocess's shipped subtree,
        # including its per-stage span, under ONE trace ID
        assert {"prover.assign", "prover.store_proof", "proof.verify",
                "proof.settle", "prover.prove", "prover.execute"} <= names
        shipped = [s for s in spans if s.get("source") == "remote-prover"]
        assert {"prover.prove", "prover.execute"} <= \
            {s["name"] for s in shipped}
        stage_spans = [s for s in shipped
                       if (s.get("attrs") or {}).get("stage")]
        assert stage_spans, "shipped subtree lost its stage spans"
        # the remote subtree reattached: prove's parent is the local
        # assign span
        by_name = {s["name"]: s for s in spans}
        assert by_name["prover.prove"]["parentId"] == \
            by_name["prover.assign"]["spanId"]
        assert TRACER.ingested > 0

        # critical path sums to the wall (acceptance: within 5%)
        cp = critical_path(trace)
        assert cp["wallSeconds"] > 0
        assert abs(sum(cp["components"].values()) - cp["wallSeconds"]) \
            <= 0.05 * cp["wallSeconds"]
        assert cp["sources"] == ["local", "remote-prover"]

        node.sequencer = seq
        server = RpcServer(node)
        r = server.handle({"jsonrpc": "2.0", "id": 1,
                           "method": "ethrex_trace_criticalPath",
                           "params": [tid]})
        assert r["result"]["found"] is True
        assert r["result"]["components"] == cp["components"]
        json.dumps(r)

        # Perfetto export: two processes, flow links across the seam
        r = server.handle({"jsonrpc": "2.0", "id": 2,
                           "method": "ethrex_trace_export",
                           "params": [tid]})
        doc = r["result"]
        assert doc["found"] is True
        json.dumps(doc)
        events = doc["traceEvents"]
        metas = {e["args"]["name"] for e in events if e["ph"] == "M"
                 and e["name"] == "process_name"}
        assert metas == {"local", "prover:remote-prover"}
        flows = [e for e in events if e["ph"] in ("s", "f")]
        by_id = {}
        for e in flows:
            by_id.setdefault(e["id"], []).append(e)
        crossing = [pair for pair in by_id.values()
                    if len(pair) == 2 and pair[0]["pid"] != pair[1]["pid"]]
        assert crossing, "no flow link crosses the submit seam"

        # the batch_proving_seconds exemplar resolves to this trace
        text = METRICS.render()
        exline = [ln for ln in text.splitlines()
                  if ln.startswith("batch_proving_seconds_bucket")
                  and f'trace_id="{tid}"' in ln]
        assert exline, "no exemplar pointing at the merged trace"

        # the per-batch lifecycle timeline surfaced in ethrex_health
        r = server.handle({"jsonrpc": "2.0", "id": 3,
                           "method": "ethrex_health", "params": []})
        lifecycle = r["result"]["l2"]["lifecycle"]
        mine = [e for e in lifecycle if e.get("batch") == 1]
        assert mine and mine[0]["traceId"] == tid
        assert mine[0]["components"]
        # ...and the component histogram fed the alert signals
        assert "batch_critical_path_seconds_bucket" in text
    finally:
        seq.stop()


# ---------------------------------------------------------------------------
# chaos drills (coordinator handlers, no TCP needed)


def test_chaos_prover_death_mid_prove_leaves_partial_subtree():
    """A prover that heartbeats its completed stage spans and then dies
    before submitting still leaves a renderable partial subtree in the
    coordinator's merged trace."""
    node, seq = _committed_sequencer()
    try:
        resp = seq.coordinator.handle_request({
            "type": protocol.INPUT_REQUEST,
            "commit_hash": seq.coordinator.commit_hash,
            "prover_type": protocol.PROVER_EXEC, "prover_id": "doomed"})
        assert resp["type"] == protocol.INPUT_RESPONSE
        tid, parent = resp["trace_id"], resp["span_id"]
        now = time.time()
        payload = {"v": WIRE_VERSION, "spans": [
            {"traceId": tid, "spanId": "dd01", "parentId": parent,
             "name": "prover.prove", "start": now, "seconds": 1.5},
            {"traceId": tid, "spanId": "dd02", "parentId": "dd01",
             "name": "stark.trace_lde", "start": now, "seconds": 0.4,
             "attrs": {"stage": "trace_lde"}},
        ]}
        beat = {"type": protocol.HEARTBEAT, "batch_id": resp["batch_id"],
                "prover_type": protocol.PROVER_EXEC,
                "lease_token": resp["lease_token"],
                "prover_id": "doomed", "spans": payload}
        assert seq.coordinator.handle_request(beat)["ok"] is True
        # the beat is cumulative; a second identical one adds nothing
        before = len(TRACER.get_trace(tid)["spans"])
        seq.coordinator.handle_request(beat)
        assert len(TRACER.get_trace(tid)["spans"]) == before
        # ...and the prover dies here: no submit ever arrives.
        trace = TRACER.get_trace(tid)
        names = {s["name"] for s in trace["spans"]}
        assert {"prover.assign", "prover.prove", "stark.trace_lde"} <= names
        assert all(s["source"] == "doomed" for s in trace["spans"]
                   if s.get("source"))
        cp = critical_path(trace)
        assert abs(sum(cp["components"].values()) - cp["wallSeconds"]) \
            < 1e-6
        assert "prove/trace_lde" in cp["components"]
        # the partial trace renders in the summaries without raising
        assert any(e["traceId"] == tid for e in TRACER.slowest(50))
    finally:
        seq.stop()


def test_chaos_hedged_submits_merge_two_subtrees():
    """Both legs of a hedged race land their subtrees: the winner via a
    leased submit, the loser via the duplicate-submit no-op ACK — two
    prover subtrees under one trace, attribution still sums to wall."""
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.prover.backend import ExecBackend

    node, seq = _committed_sequencer()
    try:
        resp = seq.coordinator.handle_request({
            "type": protocol.INPUT_REQUEST,
            "commit_hash": seq.coordinator.commit_hash,
            "prover_type": protocol.PROVER_EXEC, "prover_id": "prover-a"})
        assert resp["type"] == protocol.INPUT_RESPONSE
        tid, parent = resp["trace_id"], resp["span_id"]
        proof = ExecBackend().prove(
            ProgramInput.from_json(resp["input"]), resp["format"])
        now = time.time()

        def _subtree(prefix, t0, dur):
            return {"v": WIRE_VERSION, "spans": [
                {"traceId": tid, "spanId": f"{prefix}1", "parentId": parent,
                 "name": "prover.prove", "start": t0, "seconds": dur}]}

        ack = seq.coordinator.handle_request({
            "type": protocol.PROOF_SUBMIT, "batch_id": resp["batch_id"],
            "prover_type": protocol.PROVER_EXEC, "proof": proof,
            "lease_token": resp["lease_token"], "prover_id": "prover-a",
            "trace_id": tid, "spans": _subtree("aa", now, 2.0)})
        assert ack["type"] == protocol.SUBMIT_ACK
        # the losing leg: overlapping wall time, duplicate submit, no
        # valid lease — its subtree still merges via the no-op ACK path
        ack = seq.coordinator.handle_request({
            "type": protocol.PROOF_SUBMIT, "batch_id": resp["batch_id"],
            "prover_type": protocol.PROVER_EXEC, "proof": proof,
            "lease_token": None, "prover_id": "prover-b",
            "trace_id": tid, "spans": _subtree("bb", now + 1.0, 2.0)})
        assert ack["type"] == protocol.SUBMIT_ACK

        trace = TRACER.get_trace(tid)
        sources = {s.get("source") for s in trace["spans"]
                   if s.get("source")}
        assert sources == {"prover-a", "prover-b"}
        cp = critical_path(trace)
        # overlapping subtrees, yet every wall second is attributed once
        assert abs(sum(cp["components"].values()) - cp["wallSeconds"]) \
            < 1e-6
        assert {"local", "prover-a", "prover-b"} <= set(cp["sources"])
        metas = {e["args"]["name"]
                 for e in to_trace_events(trace)["traceEvents"]
                 if e["ph"] == "M" and e["name"] == "process_name"}
        assert {"local", "prover:prover-a", "prover:prover-b"} <= metas
    finally:
        seq.stop()


# ---------------------------------------------------------------------------
# serving overhead


def test_span_shipping_overhead_under_two_percent():
    """Span shipping must not show up in the serving tail: each hop —
    export_wire in the prover process, ingest in the coordinator process
    (no single serving thread ever pays both) — must cost under 2% of
    the p99@30-connection serving reference (~7.8ms), i.e. ~156us, for
    a realistic ~64-span trace."""
    t = Tracer(capacity=8)
    tid = "ab" * 8
    root = _record(t, tid, "prover.prove", 100.0, 5.0)
    for i in range(63):
        _record(t, tid, f"stark.stage{i}", 100.0 + i * 0.05, 0.05,
                parent=root, stage=f"s{i % 8}")
    budget = 0.02 * 0.0078
    payload = export_wire(tid, tracer=t)
    assert len(payload["spans"]) == 64
    best_export = best_ingest = float("inf")
    for _ in range(100):
        t0 = time.perf_counter()
        export_wire(tid, tracer=t)
        best_export = min(best_export, time.perf_counter() - t0)
        sink = Tracer(capacity=8)
        t0 = time.perf_counter()
        sink.ingest(payload, source="p")
        best_ingest = min(best_ingest, time.perf_counter() - t0)
    assert best_export < budget, \
        f"export cost {best_export * 1e6:.0f}us > 156us budget"
    assert best_ingest < budget, \
        f"ingest cost {best_ingest * 1e6:.0f}us > 156us budget"


# ---------------------------------------------------------------------------
# leaf spans of a batch (PERF.md section 3): every host second of a
# batch under a span that names the work, with bytes and tries as
# attributes


import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

from ethrex_tpu.models import fibonacci as fib  # noqa: E402
from ethrex_tpu.prover import checkpoint as ckpt  # noqa: E402
from ethrex_tpu.prover import tpu_backend  # noqa: E402
from ethrex_tpu.prover.client import ProverClient  # noqa: E402
from ethrex_tpu.stark import prover as stark_prover  # noqa: E402
from ethrex_tpu.stark.prover import StarkParams  # noqa: E402

SMALL = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)
FIB_N = 64
# sha256 of json.dumps(proof, sort_keys=True) of the Fibonacci proof
# below, made at the commit before the leaf spans went in (05733cb)
FIB_PROOF_SHA256 = \
    "2ef55ed21958e7c8cf41396345f444aa9d562ae6831b4a59d19d4c92c37eb68a"
# FRI layers of the three STARKs of a BASELINE-1 batch (codewords 2^17,
# 2^17 and 2^12 down to a final 2^4), which the test-size batch swaps
# for its own before it is held to the budget
BASELINE1_FRI_LAYERS = 13 + 13 + 8

LEAF_SPANS = {
    "prover.idle": ("polls", "batch"),
    "prover.fetch_input": ("batch",),
    "prover.ckpt_complete": ("disk_bytes",),
    "prove.vm_batch": ("p2",),
    "prove.compile_ahead": (),
    "prove.trace_gen": ("air", "rows", "width", "p2"),
    "prove.deep": (),
    "prove.ckpt_copy": ("phase", "d2h_bytes"),
    "ckpt.store": ("phase", "job", "disk_bytes"),
    "fri.layer": ("log_n", "d2h_bytes", "d2h_s"),
    "fri.final": ("d2h_bytes",),
    "fri.grind": ("tries",),
    "fri.open_queries": ("canon_bytes",),
    "query.canon": ("d2h_bytes", "canon_bytes"),
    "query.paths": (),
}


def _fib_material():
    trace = fib.generate_trace(FIB_N)
    return fib.FibonacciAir(), trace, fib.public_inputs(trace)


def _unspanned_reader():
    """benchmark/metrics/unspanned_s.py: the test holds the trace to the
    same arithmetic the benchmark's metric uses."""
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "metrics",
        "unspanned_s.py")
    spec = importlib.util.spec_from_file_location("unspanned_s", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class _SmallStark:
    """`stark/prover.py` as `TpuBackend` sees it, with every AIR swapped
    for the 64-row Fibonacci AIR: the backend's own host work runs on
    the real batch, the real `_prove_attempt` runs at test size."""

    def __init__(self):
        self.airs = []

    def prove(self, air, trace, pub, params, mesh=None):
        self.airs.append(type(air).__name__)
        return stark_prover.prove(*_fib_material(), SMALL)

    def compile_ahead(self, *args, **kwargs):
        pass

    def warm_fri_programs(self, *args, **kwargs):
        pass


@pytest.fixture(scope="module")
def leaf_batch(tmp_path_factory):
    """Two batches, one after the other, through coordinator ->
    ProverClient.run_forever -> TpuBackend on the CPU.  The client
    starts before the first is committed, so its first polls come back
    empty.  The tests read the first; the second is there for what only
    two can show (`next_spans`)."""
    ckpt.set_checkpoint_dir(str(tmp_path_factory.mktemp("ckpt")))
    stark_prover.prove(*_fib_material(), SMALL)     # programs built
    patch = pytest.MonkeyPatch()
    small = _SmallStark()
    patch.setattr(tpu_backend, "stark_prover", small)
    removed = {}
    complete = ckpt.complete

    def counting_complete(batch_id):
        bdir = ckpt._batch_dir(batch_id)
        removed[batch_id] = sorted(os.path.getsize(os.path.join(bdir, n))
                                   for n in os.listdir(bdir))
        return complete(batch_id)

    patch.setattr(ckpt, "complete", counting_complete)
    node = Node(Genesis.from_json(GENESIS))
    l1 = InMemoryL1(needed_prover_types=[protocol.PROVER_TPU])
    seq = Sequencer(node, l1, SequencerConfig(
        needed_prover_types=(protocol.PROVER_TPU,)))
    seq.coordinator.start()
    trimmed0 = TRACER.trimmed
    client = ProverClient(protocol.PROVER_TPU,
                          [("127.0.0.1", seq.coordinator.port)],
                          poll_interval=0.05, heartbeat_interval=0,
                          prewarm=False)

    def prove_batch(number):
        node.submit_transaction(_transfer(number - 1))
        seq.produce_block()
        assert seq.commit_next_batch() is not None
        deadline = time.monotonic() + 240
        while len(client.proved) < number and time.monotonic() < deadline:
            time.sleep(0.02)
        assert client.proved == list(range(1, number + 1))
        return TRACER.get_trace(seq.coordinator.batch_traces[number])["spans"]

    try:
        client.start()
        time.sleep(0.3)
        spans = prove_batch(1)
        airs = list(small.airs)
        next_spans = prove_batch(2)
        client.stop()
        batch = {"spans": spans, "next_spans": next_spans,
                 "proof": seq.rollup.get_proof(1, protocol.PROVER_TPU),
                 "airs": airs, "removed": removed[1],
                 "trimmed": TRACER.trimmed - trimmed0}
    finally:
        client.stop()
        seq.stop()
        patch.undo()
        ckpt.set_checkpoint_dir(None)
    return batch


@pytest.mark.parametrize("name", sorted(LEAF_SPANS))
def test_batch_trace_has_leaf_span_with_attributes(leaf_batch, name):
    found = [s for s in leaf_batch["spans"] if s["name"] == name]
    assert found, f"no {name} span in the batch's trace"
    for s in found:
        attrs = s.get("attrs") or {}
        for key in LEAF_SPANS[name]:
            assert key in attrs, f"{name} lacks {key}: {attrs}"
            if key not in ("air", "phase", "job", "p2"):
                assert isinstance(attrs[key], (int, float)), (name, key)
        assert s["seconds"] >= 0 and s["status"] == "ok"


def test_leaf_spans_count_what_they_say(leaf_batch):
    spans = leaf_batch["spans"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # one STARK per job, each through the real prover at test size
    assert leaf_batch["airs"] == ["StateUpdateAir", "TransferAir",
                                  "Poseidon2SpongeAir"]
    gens = {s["attrs"]["air"]: s["attrs"] for s in by_name["prove.trace_gen"]}
    assert set(gens) == set(leaf_batch["airs"])
    assert gens["TransferAir"]["width"] == 278
    # checkpoint copies, by the arrays' shapes (Fibonacci: w=2, B=4):
    # one layout an array (lde_rows; chunks and q_rows) and the levels
    w, B, n = 2, 1 << SMALL.log_blowup, FIB_N
    N = n * B
    levels = (2 * N - 1) * 8 * 4
    want = {"commit": w * N * 4 + levels,
            "quotient": (B * n * 4 + N * B * 4) * 4 + levels,
            "open": (2 * w + B) * 4 * 4}
    copies = by_name["prove.ckpt_copy"]
    assert len(copies) == 9
    for s in copies:
        assert s["attrs"]["d2h_bytes"] == want[s["attrs"]["phase"]]
    # every envelope written is an envelope the ack removed, to the byte
    stores = by_name["ckpt.store"]
    assert sorted(s["attrs"]["phase"] for s in stores) == sorted(
        ["execute"] + 3 * ["commit", "quotient", "open", "fri", "proof"])
    assert sorted(s["attrs"]["disk_bytes"] for s in stores) == \
        leaf_batch["removed"]
    assert by_name["prover.ckpt_complete"][0]["attrs"]["disk_bytes"] == \
        sum(leaf_batch["removed"])
    # grinding: tries is the nonce found, plus one
    proof = leaf_batch["proof"]
    nonces = [proof[k]["fri"]["pow_nonce"]
              for k in ("state_proof", "vm_proof", "proof")]
    assert [s["attrs"]["tries"] for s in by_name["fri.grind"]] == \
        [nonce + 1 for nonce in nonces]
    # FRI layers: bytes halve from layer to layer (codeword + tree)
    layers = [s["attrs"] for s in by_name["fri.layer"][:4]]
    assert [a["log_n"] for a in layers] == [8, 7, 6, 5]
    assert [a["d2h_bytes"] for a in layers] == \
        [(1 << k) * 16 + ((1 << k) - 1) * 32 for k in (8, 7, 6, 5)]
    # the query phase reads the host mirrors: no copy of its own
    assert all(s["attrs"]["d2h_bytes"] == 0 for s in by_name["query.canon"])


def _fib_opening_bytes():
    """By the arrays' shapes (Fibonacci: w=2, B=4, 16 queries, FRI from
    2^8 down to a final 2^4): what the query phase holds on the host,
    and what it converts out of Montgomery form to open it."""
    w, B, n, queries = 2, 1 << SMALL.log_blowup, FIB_N, SMALL.num_queries
    N = n * B
    depth = N.bit_length() - 1
    tree = (2 * N - 1) * 8 * 4
    stark_mirrors = N * w * 4 + N * B * 4 * 4 + 2 * tree
    stark_opened = 2 * queries * ((w + B * 4) * 4 + 2 * depth * 8 * 4)
    fri_logs = range(depth, SMALL.log_final_size, -1)
    fri_mirrors = sum((1 << k) * 16 + ((1 << k) - 1) * 32 for k in fri_logs)
    fri_opened = queries * sum(2 * 16 + (k - 1) * 32 for k in fri_logs)
    return stark_mirrors, stark_opened, fri_mirrors, fri_opened


def test_query_spans_convert_only_what_they_open(leaf_batch):
    """`canon_bytes` is what went through `from_mont_host`: the opened
    rows and path nodes, never the mirrors.  (At this size 32 of a
    tree's 256 leaves are opened, so the share is a third; at
    BASELINE-1's it is under a hundredth: tests/test_stark.py.)"""
    stark_mirrors, stark_opened, fri_mirrors, fri_opened = \
        _fib_opening_bytes()
    assert (stark_opened, fri_opened) == (18688, 13312)
    canons = [s for s in leaf_batch["spans"] if s["name"] == "query.canon"]
    opens = [s for s in leaf_batch["spans"]
             if s["name"] == "fri.open_queries"]
    assert len(canons) == len(opens) == 3
    for s in canons:
        assert s["attrs"]["canon_bytes"] == stark_opened < stark_mirrors
    for s in opens:
        assert s["attrs"]["canon_bytes"] == fri_opened < fri_mirrors


def test_query_canon_without_checkpoints_copies_whole_and_converts_little():
    """Checkpoints off: the branch still copies the whole arrays off
    the device (`d2h_bytes`), and converts what it opens all the same."""
    stark_mirrors, stark_opened, _, fri_opened = _fib_opening_bytes()
    ckpt.set_checkpoint_dir(None)
    with tracing.trace_context(None) as tid:
        stark_prover.prove(*_fib_material(), SMALL)
    by_name = {s["name"]: s for s in TRACER.get_trace(tid)["spans"]}
    assert by_name["query.canon"]["attrs"]["d2h_bytes"] == stark_mirrors
    assert by_name["query.canon"]["attrs"]["canon_bytes"] == stark_opened
    assert by_name["fri.open_queries"]["attrs"]["canon_bytes"] == fri_opened
    assert by_name["query.paths"]["parentId"] == \
        by_name["query.canon"]["parentId"] == by_name["prove.query"]["spanId"]


def test_idle_joins_the_batch_that_ended_it(leaf_batch):
    by_name = {s["name"]: s for s in leaf_batch["spans"]}
    idle, fetch = by_name["prover.idle"], by_name["prover.fetch_input"]
    assign, prove = by_name["prover.assign"], by_name["prover.prove"]
    assert idle["traceId"] == assign["traceId"]
    assert idle["parentId"] == assign["spanId"]
    assert idle["start"] < assign["start"]
    assert idle["attrs"]["polls"] >= 1 and idle["attrs"]["batch"] == 1
    assert idle["seconds"] >= 0.3
    # the wait ends where the fetch begins, and the fetch holds the
    # coordinator's assignment and ends before the prove
    assert abs(idle["start"] + idle["seconds"] - fetch["start"]) < 1e-6
    assert fetch["start"] <= assign["start"]
    assert fetch["start"] + fetch["seconds"] <= prove["start"] + 1e-3


def test_batch_critical_path_is_the_batchs_own(leaf_batch):
    """The client idled >= 0.3 s before batch 1 and deleted its
    checkpoints after the ack, and the committer sealed the batch before
    any prover could ask for it: none of it is the batch's lifecycle, so
    the wall, `prove` and the stage components read as without them."""
    spans = leaf_batch["spans"]
    on_path = [s for s in spans if s["name"] not in tracing.OFF_PATH_SPANS]
    assert sorted(s["name"] for s in spans if s not in on_path) == [
        "prover.ckpt_complete", "prover.idle", "seq.blobs", "seq.commit",
        "seq.l1_commit", "seq.store", "seq.witness"]
    cp = critical_path({"traceId": "t", "spans": spans})
    assert cp == critical_path({"traceId": "t", "spans": on_path})
    by_name = {s["name"]: s for s in spans}
    assert cp["start"] == min(s["start"] for s in on_path)
    assert cp["start"] >= by_name["prover.idle"]["start"] + 0.3
    assert abs(cp["wallSeconds"] - (
        max(s["start"] + s["seconds"] for s in on_path) - cp["start"])) < 1e-9
    # the leaf spans left the stage components standing
    for component in ("prove/fri_fold", "prove/query", "prove/ckpt",
                      "prove/vm_circuits", "prove/state_proof"):
        assert cp["components"].get(component, 0) > 0, cp["components"]


def test_stark_stage_spans_never_nest(leaf_batch):
    """`StageProfiler.tree()` sums the stark component's stages and
    takes shares of the sum, and `prover_stage_seconds` is read the
    same way: a stark stage inside a stark stage would count its
    seconds twice.  So `fri.grind` (inside `fri_fold`) carries no stage,
    nor does `prove.trace_gen` (inside its job's stage); `ckpt` runs
    between the phases."""
    from ethrex_tpu.perf import profiler

    def stark(s):
        return (s.get("attrs") or {}).get("stage") in profiler._STARK_STAGES

    spans = leaf_batch["spans"]
    ids = {s["spanId"]: s for s in spans}
    staged = [s for s in spans if stark(s)]
    assert {s["attrs"]["stage"] for s in staged} == profiler._STARK_STAGES
    for s in staged:
        up = ids.get(s["parentId"])
        while up is not None:
            assert not stark(up), (s["name"], up["name"])
            up = ids.get(up["parentId"])
    for name in ("fri.grind", "prove.trace_gen"):
        assert all("stage" not in s["attrs"] for s in spans
                   if s["name"] == name)


def test_batch_extents_tile_the_clients_cycle(leaf_batch):
    """The next batch's wait starts where this batch's last span ended
    (its `prover.ckpt_complete`), so the traces' extents lie end to end
    and sum to the window the benchmark times."""
    end = max(s["start"] + s["seconds"] for s in leaf_batch["spans"])
    (idle,) = [s for s in leaf_batch["next_spans"]
               if s["name"] == "prover.idle"]
    assert idle["attrs"]["batch"] == 2
    assert idle["start"] == min(s["start"] for s in leaf_batch["next_spans"])
    assert 0 <= idle["start"] - end < 0.05


def test_batch_trace_stays_inside_the_span_budget(leaf_batch):
    spans = leaf_batch["spans"]
    layers = sum(1 for s in spans if s["name"] == "fri.layer")
    at_full_size = len(spans) - layers + BASELINE1_FRI_LAYERS
    assert at_full_size <= tracing.BATCH_SPAN_BUDGET, at_full_size
    assert tracing.BATCH_SPAN_BUDGET <= 0.75 * tracing.WIRE_MAX_SPANS
    assert leaf_batch["trimmed"] == 0
    assert len({s["spanId"] for s in spans}) == len(spans)


def test_leaf_spans_cover_the_batch(leaf_batch):
    """A piece of work under no span leaves the same hole in every
    batch; a stall of the test's machine (a batch is 1-2 s here) leaves
    it in one.  So the better covered of the two is held to the 90%."""
    reader = _unspanned_reader()
    rows = [reader.batch_rows(leaf_batch[k]) for k in ("spans", "next_spans")]
    shares = [r["covered"] / r["extent"] for r in rows]
    assert max(shares) >= 0.9, (shares, rows)


@pytest.mark.parametrize("checkpoints", [False, True])
def test_proof_is_byte_identical_to_before_the_spans(tmp_path, checkpoints):
    ckpt.set_checkpoint_dir(str(tmp_path / "ckpt"))
    try:
        if checkpoints:
            with ckpt.batch_context(77, lease_token="tok"), \
                    ckpt.job_scope("fib"):
                proof = stark_prover.prove(*_fib_material(), SMALL)
        else:
            proof = stark_prover.prove(*_fib_material(), SMALL)
    finally:
        ckpt.complete(77)
        ckpt.set_checkpoint_dir(None)
    assert hashlib.sha256(json.dumps(proof, sort_keys=True).encode()) \
        .hexdigest() == FIB_PROOF_SHA256


def test_checkpoint_spans_match_the_files(tmp_path):
    """`ckpt.store` says the file's size; `ckpt.load` spans a hit and
    only counts a miss."""
    ckpt.set_checkpoint_dir(str(tmp_path / "ckpt"))
    parts = {"kind": "proof_ckpt", "job": "j", "phase": "commit"}
    payload = {"rows": np.arange(4096, dtype=np.uint32)}
    try:
        misses0 = ckpt.STATS["misses"]
        with tracing.trace_context(None) as tid:
            assert ckpt.load(5, parts) is None
            assert ckpt.store(5, parts, payload)
            size = os.path.getsize(ckpt._entry_path(5, parts))
            assert np.array_equal(ckpt.load(5, parts)["rows"],
                                  payload["rows"])
            assert ckpt.complete(5) == size
        spans = TRACER.get_trace(tid)["spans"]
        assert [s["name"] for s in spans] == ["ckpt.store", "ckpt.load"]
        for s in spans:
            assert s["attrs"]["disk_bytes"] == size
            assert s["attrs"]["phase"] == "commit"
        assert spans[0]["attrs"]["stage"] == "ckpt"
        assert ckpt.STATS["misses"] == misses0 + 1
    finally:
        ckpt.set_checkpoint_dir(None)


def test_resumed_proof_spans_its_load(tmp_path):
    ckpt.set_checkpoint_dir(str(tmp_path / "ckpt"))
    try:
        with ckpt.batch_context(78), ckpt.job_scope("fib"):
            first = stark_prover.prove(*_fib_material(), SMALL)
            with tracing.trace_context(None) as tid:
                again = stark_prover.prove(*_fib_material(), SMALL)
        assert again == first
        loads = [s for s in TRACER.get_trace(tid)["spans"]
                 if s["name"] == "ckpt.load"]
        assert [s["attrs"]["phase"] for s in loads] == ["proof"]
        assert loads[0]["attrs"]["job"] == "fib"
    finally:
        ckpt.complete(78)
        ckpt.set_checkpoint_dir(None)


@pytest.mark.parametrize("name, start, seconds, attrs", [
    (None, None, None, {}),
    ("x", "yesterday", 1.0, {}),
    ("x", 1.0, "long", {}),
    ("x", float("nan"), float("inf"), {"k": object()}),
    (object(), -1.0, -5.0, {"k": [1, 2]}),
])
def test_record_span_never_raises(name, start, seconds, attrs):
    with tracing.trace_context(None) as tid:
        tracing.record_span(name, start, seconds, **attrs)
        tracing.set_attrs(None, k=1)
    rec = TRACER.get_trace(tid)
    for s in (rec or {}).get("spans", []):
        assert s["seconds"] >= 0
        json.dumps(s)


def test_record_span_lands_under_the_current_context():
    with tracing.trace_context("ef" * 8, "parent01"):
        tracing.record_span("waited", 123.5, 2.25, polls=3)
    (s,) = TRACER.get_trace("ef" * 8)["spans"]
    assert (s["name"], s["start"], s["seconds"]) == ("waited", 123.5, 2.25)
    assert s["parentId"] == "parent01" and s["attrs"] == {"polls": 3}


def test_trimmed_counts_spans_cut_from_a_trace():
    t = Tracer(capacity=4)
    tid = "12" * 8
    for i in range(tracing.SPANS_PER_TRACE):
        _record(t, tid, f"s{i}", 100.0 + i, 0.5)
    assert t.trimmed == 0
    for i in range(7):
        _record(t, tid, f"late{i}", 900.0 + i, 0.5)
    assert t.trimmed == 7
    kept = t.get_trace(tid)["spans"]
    assert len(kept) == tracing.SPANS_PER_TRACE
    assert kept[0]["name"] == "s7"          # oldest first out
    t.clear()
    assert t.trimmed == 0


def test_wire_truncation_is_counted():
    t = Tracer(capacity=4)
    tid = "34" * 8
    for i in range(10):
        _record(t, tid, f"s{i}", 100.0 + i, 0.1 * (i + 1))
    assert export_wire(tid, tracer=t)["truncated"] is False
    assert t.wire_truncated == 0
    assert export_wire(tid, max_spans=4, tracer=t)["truncated"] is True
    assert t.wire_truncated == 6
