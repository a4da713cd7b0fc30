"""Chain-path X-ray (docs/OBSERVABILITY.md "Chain-path telemetry"):
StageQueue accounting + Little's-law cross-check, sampled per-tx
lifecycle records, the bottleneck explainer, loadgen typed-rejection
classification, and the end-to-end acceptance run — a real-TCP overload
where the explainer must name the admission/producer stage and a
sampled lifecycle's hop dwells must sum to its admitted→included wall."""

import json

import pytest

from ethrex_tpu.crypto import secp256k1
from ethrex_tpu.node import Node
from ethrex_tpu.perf import loadgen
from ethrex_tpu.perf.chain_path import (
    CHAIN_PATH,
    ChainPath,
    StageQueue,
    explain_chain_path,
)
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.primitives.transaction import TYPE_DYNAMIC_FEE, Transaction
from ethrex_tpu.rpc.server import RpcServer
from ethrex_tpu.utils.metrics import METRICS

SECRET = 0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8
SENDER = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(SECRET))

GENESIS = {
    "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
               "shanghaiTime": 0, "cancunTime": 0},
    "alloc": {"0x" + SENDER.hex(): {"balance": hex(10**21)}},
    "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7", "timestamp": "0x0",
}


def _tx(nonce, secret=SECRET, chain_id=1337, fee=10**10):
    return Transaction(
        tx_type=TYPE_DYNAMIC_FEE, chain_id=chain_id, nonce=nonce,
        max_priority_fee_per_gas=1, max_fee_per_gas=fee,
        gas_limit=21_000, to=bytes([0xAA]) * 20, value=1).sign(secret)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# StageQueue

def test_stage_queue_counts_depth_and_drops():
    clk = FakeClock()
    q = StageQueue("t", window=100.0, clock=clk)
    q.arrive(3)
    q.depart(dwell=0.5)                 # a service completion
    q.depart(dropped=True)              # an eviction
    st = q.stats()
    assert st["depth"] == 1
    assert st["arrivals"] == 3
    assert st["departures"] == 1
    assert st["drops"] == 1
    assert st["errors"] == 0
    assert st["meanDwellSeconds"] == 0.5


def test_stage_queue_windowed_rates_and_utilization():
    clk = FakeClock()
    q = StageQueue("t", window=100.0, clock=clk)
    # 10 arrivals over 10s, 5 services: rho = lambda/mu = 2
    for k in range(10):
        clk.t = float(k)
        q.arrive()
        if k % 2:
            q.depart(dwell=1.0)
    clk.t = 10.0
    st = q.stats()
    assert st["arrivalRate"] == pytest.approx(1.0)
    assert st["serviceRate"] == pytest.approx(0.5)
    assert st["utilization"] == pytest.approx(2.0)


def test_stage_queue_little_law_cross_check():
    """Deterministic M/D/1-ish stream: one arrival per second, each
    resident exactly 2s.  Observed time-averaged depth (the exact
    depth-dt integral) must equal lambda * W."""
    clk = FakeClock()
    q = StageQueue("t", window=100.0, clock=clk)
    for t in range(12):
        clk.t = float(t)
        if t < 10:
            q.arrive()
        if 2 <= t:
            q.depart(dwell=2.0)
    clk.t = 12.0
    st = q.stats()
    ll = st["littleLaw"]
    assert ll["predictedDepth"] == pytest.approx(ll["observedDepth"],
                                                 rel=0.01)
    assert ll["ratio"] == pytest.approx(1.0, abs=0.01)


def test_stage_queue_never_raises_on_bad_input():
    q = StageQueue("t", window=100.0)
    q.arrive("garbage")
    q.depart(dwell="also garbage")
    assert q.errors == 2
    q.depart(n=5)                       # departing an empty queue
    assert q.depth == 0                 # clamped, not negative
    assert isinstance(q.stats(), dict)


def test_stage_queue_saturated_utilization_is_inf():
    clk = FakeClock()
    q = StageQueue("t", window=100.0, clock=clk)
    q.arrive(4)
    clk.t = 5.0
    st = q.stats()
    assert st["utilization"] == float("inf")


# ---------------------------------------------------------------------------
# lifecycle sampling

def test_lifecycle_full_pipeline_marks_and_hops():
    clk = FakeClock(100.0)
    cp = ChainPath(sample=1, ring=8, window=1000.0, clock=clk)
    cp.tx_admitted(b"\x01" * 32)
    clk.t = 101.0
    cp.txs_selected([b"\x01" * 32])
    clk.t = 103.0
    cp.block_produced(7, [b"\x01" * 32], build_seconds=2.0)
    clk.t = 106.0
    cp.blocks_batched(3, 7, 7, trace_id="cafebabe")
    clk.t = 110.0
    cp.batch_proved(3)
    clk.t = 115.0
    cp.batches_settled(3)
    [rec] = cp.lifecycles_json()
    assert set(rec["events"]) == {"admitted", "selected", "included",
                                  "batched", "proved", "settled"}
    assert rec["block"] == 7 and rec["batch"] == 3
    assert rec["traceId"] == "cafebabe"
    assert rec["hops"] == {
        "admitted_to_selected": pytest.approx(1.0),
        "selected_to_included": pytest.approx(2.0),
        "included_to_batched": pytest.approx(3.0),
        "batched_to_proved": pytest.approx(4.0),
        "proved_to_settled": pytest.approx(5.0),
    }
    # hop dwells telescope to the end-to-end wall
    assert sum(rec["hops"].values()) == pytest.approx(
        rec["events"]["settled"] - rec["events"]["admitted"])
    # batching stage saw the seal->commit dwell
    assert cp.queues["batching"].stats()["meanDwellSeconds"] == \
        pytest.approx(3.0)


def test_lifecycle_ring_is_bounded_and_sampling_strides():
    cp = ChainPath(sample=2, ring=3, window=100.0, clock=FakeClock())
    for i in range(10):
        cp.tx_admitted(bytes([i]) * 32)
    j = cp.to_json()
    assert j["lifecycle"]["seen"] == 10
    assert j["lifecycle"]["sampled"] == 5      # every 2nd admission
    assert len(j["lifecycle"]["records"]) == 3  # ring-evicted to capacity


def test_backlog_and_stall_are_none_on_idle_or_l1_only():
    clk = FakeClock()
    cp = ChainPath(sample=1, window=100.0, clock=clk)
    assert cp.backlog_seconds() is None          # empty pool
    assert cp.producer_stall_seconds() is None   # never produced
    cp.tx_admitted(b"\x01" * 32)
    # depth > 0 but zero blocks produced: an L1-only follower's pool is
    # not "backlogged" — the signal stays armed-but-silent
    assert cp.backlog_seconds() is None
    assert cp.producer_stall_seconds() is None


def test_backlog_and_stall_fire_under_pressure():
    clk = FakeClock()
    cp = ChainPath(sample=1, window=100.0, clock=clk)
    for i in range(20):
        cp.tx_admitted(bytes([i]) * 32)
    clk.t = 10.0
    cp.block_produced(1, [bytes([0]) * 32], build_seconds=0.1)
    cp.tx_removed(bytes([0]) * 32, "included", dwell=10.0)
    clk.t = 40.0
    # 19 txs left, service rate 1 removal / 40s window
    backlog = cp.backlog_seconds()
    assert backlog is not None and backlog > 0
    stall = cp.producer_stall_seconds()
    assert stall == pytest.approx(30.0)


# ---------------------------------------------------------------------------
# the explainer

def test_explain_idle_names_no_bottleneck():
    cp = ChainPath(sample=1, window=100.0, clock=FakeClock())
    out = explain_chain_path(cp)
    assert out["bottleneck"] is None
    assert "keeping up" in out["verdict"]


def test_explain_names_admission_when_arrivals_never_drain():
    clk = FakeClock()
    cp = ChainPath(sample=0, window=100.0, clock=clk)
    for i in range(50):
        cp.tx_admitted(bytes([i % 256, i // 256]) * 16)
    clk.t = 10.0
    out = explain_chain_path(cp)
    assert out["bottleneck"] == "admission"
    assert out["pressures"]["admission"] == 50
    assert "admission" in out["verdict"]


def test_explain_names_producer_on_stall():
    clk = FakeClock()
    cp = ChainPath(sample=0, window=100.0, clock=clk)
    cp.tx_admitted(b"\x01" * 32)
    clk.t = 1.0
    cp.block_produced(1, [], build_seconds=0.05)
    clk.t = 50.0   # 49s since the last block with a tx still waiting
    out = explain_chain_path(cp)
    assert out["bottleneck"] == "producer"
    assert "stalled" in out["verdict"]


def test_explain_ignores_batching_until_batching_is_active():
    """Sealed blocks that never drain into batches are normal on an
    L1-only node — batching must not be named while zero batches have
    ever been committed."""
    clk = FakeClock()
    cp = ChainPath(sample=0, window=100.0, clock=clk)
    for b in range(5):
        clk.t = float(b)
        cp.block_produced(b, [], build_seconds=0.01)
    clk.t = 30.0
    out = explain_chain_path(cp)
    assert cp.queues["batching"].depth == 5
    assert out["bottleneck"] is None
    assert out["pressures"]["batching"] == 0


def test_chain_path_json_is_strict_json_under_saturation():
    clk = FakeClock()
    cp = ChainPath(sample=1, window=100.0, clock=clk)
    cp.tx_admitted(b"\x01" * 32)
    clk.t = 5.0
    # admission rho is inf here; every surface must still round-trip
    # through a strict (allow_nan=False) JSON serializer
    for payload in (cp.to_json(), cp.health_json(),
                    explain_chain_path(cp)):
        json.loads(json.dumps(payload, allow_nan=False))


# ---------------------------------------------------------------------------
# loadgen typed-rejection classification

def _rej(reason):
    return {"error": {"code": -32000, "message": "no",
                      "data": {"rejected": True, "reason": reason}}}


def _busy():
    return {"error": {"code": -32005, "message": "busy",
                      "data": {"retryAfter": 0.1}}}


def test_rejection_reason_strict_shape():
    assert loadgen.rejection_reason(
        _rej("sender_limit")["error"]) == "sender_limit"
    # untyped -32000, wrong code, empty reason: all None
    assert loadgen.rejection_reason({"code": -32000, "message": "x"}) is None
    assert loadgen.rejection_reason(
        {"code": -32005, "data": {"reason": "x"}}) is None
    assert loadgen.rejection_reason(
        {"code": -32000, "data": {"reason": ""}}) is None
    assert loadgen.rejection_reason("nope") is None


def test_classify_single_responses():
    assert loadgen._classify(_rej("nonce_gap")) == (False, False,
                                                    "nonce_gap")
    assert loadgen._classify(_busy()) == (False, True, None)
    assert loadgen._classify(
        {"error": {"code": -32000, "message": "x"}}) == (True, False, None)
    assert loadgen._classify({"result": "0x1"}) == (False, False, None)


def test_classify_batch_responses():
    ok = {"result": "0x1"}
    # partial service: some entries refused, some served -> delivered
    assert loadgen._classify([ok, _rej("sender_limit")]) == \
        (False, False, None)
    # every entry typed-rejected -> one rejected slot with its reason
    assert loadgen._classify([_rej("fee_below_floor")] * 3) == \
        (False, False, "fee_below_floor")
    # every entry shed -> shed
    assert loadgen._classify([_busy(), _busy()]) == (False, True, None)
    # any untyped error entry -> the whole request is an error
    assert loadgen._classify(
        [_busy(), {"error": {"code": -32603, "message": "boom"}}]) == \
        (True, False, None)
    assert loadgen._classify([]) == (True, False, None)


# ---------------------------------------------------------------------------
# node wiring

def test_node_wiring_populates_queues_lifecycles_and_spans():
    from ethrex_tpu.perf import profiler

    CHAIN_PATH.configure(sample=1)
    node = Node(Genesis.from_json(GENESIS))
    try:
        for n in range(5):
            node.submit_transaction(_tx(n))
        blk = node.produce_block()
        assert len(blk.body.transactions) == 5
        j = CHAIN_PATH.to_json()
        adm = j["stages"]["admission"]
        assert adm["arrivals"] == 5 and adm["departures"] == 5
        assert adm["depth"] == 0 and adm["drops"] == 0
        prod = j["stages"]["producer"]
        assert prod["departures"] == 1
        assert j["blocksProduced"] == 1 and j["txsIncluded"] == 5
        assert j["inclusionTps"] > 0
        # every sampled record reached `included` and carries hop dwells
        recs = j["lifecycle"]["records"]
        assert len(recs) == 5
        for rec in recs:
            assert {"admitted", "selected", "included"} <= set(rec["events"])
            assert rec["block"] == blk.header.number
        # the live gauge and the payload profiler spans landed
        assert METRICS.snapshot()["gauges"]["block_inclusion_tps"] > 0
        comp = profiler.PROFILER.tree()["components"]["payload"]
        assert {"drain", "select", "execute", "merkleize",
                "seal"} <= set(comp["stages"])
    finally:
        node.stop()


def test_mempool_time_in_pool_labelled_by_reason():
    node = Node(Genesis.from_json(GENESIS))
    try:
        node.submit_transaction(_tx(0, fee=10**10))
        node.submit_transaction(_tx(0, fee=2 * 10**10))  # replacement
        node.produce_block()                             # includes nonce 0
        hist = METRICS.snapshot()["histograms"][
            "mempool_time_in_pool_seconds"]
        reasons = {tuple(s["labels"].items())[0][1]
                   for s in hist["series"]}
        assert {"replaced", "included"} <= reasons
    finally:
        node.stop()


def test_rpc_send_raw_transaction_carries_typed_rejection():
    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node)
    try:
        bad = _tx(0, chain_id=999)
        r = server.handle({
            "jsonrpc": "2.0", "id": 1,
            "method": "eth_sendRawTransaction",
            "params": ["0x" + bad.encode_canonical().hex()]})
        err = r["error"]
        assert err["code"] == loadgen.REJECTION_CODE
        assert err["data"]["reason"] == "wrong_chain_id"
        assert loadgen._classify(r) == (False, False, "wrong_chain_id")
    finally:
        node.stop()


# ---------------------------------------------------------------------------
# acceptance: real-TCP overload run

def test_overload_run_names_bottleneck_and_hop_dwells_sum():
    """Saturate the mempool through a real TCP RPC with no producer
    running: the explainer must name the admission (or producer) stage.
    Then drain one block and check a sampled lifecycle's hop dwells sum
    to its admitted->included wall."""
    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node, host="127.0.0.1", port=0).start()
    try:
        harness = loadgen.Harness(f"http://127.0.0.1:{server.port}",
                                  key=SECRET, senders=4, workers=16,
                                  timeout=5.0, payload="tx")
        harness.setup()
        CHAIN_PATH.configure(sample=1)   # measure the run, not setup
        rep = harness.run(rate=300.0, duration=1.0, arrivals="fixed")
        assert rep["sent"] > 0
        # typed accounting identity survives overload
        assert rep["delivered"] == \
            rep["sent"] - rep["shed"] - rep["rejected"]
        if rep["rejected"]:
            assert rep["rejections"]
            assert sum(rep["rejections"].values()) == rep["rejected"]
        j = CHAIN_PATH.to_json()
        assert j["stages"]["admission"]["depth"] > 0
        out = explain_chain_path(CHAIN_PATH)
        assert out["bottleneck"] in ("admission", "producer")
        assert out["verdict"]

        # drain: seal one block, then audit a sampled included record
        blk = node.produce_block()
        assert len(blk.body.transactions) > 0
        included = [r for r in CHAIN_PATH.lifecycles_json(limit=512)
                    if "included" in r["events"]]
        assert included
        for rec in included:
            wall = rec["events"]["included"] - rec["events"]["admitted"]
            assert sum(rec["hops"].values()) == pytest.approx(
                wall, abs=1e-3)
    finally:
        server.stop()
        node.stop()
