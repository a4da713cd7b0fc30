"""Overload protection units: admission-control cost classes and shed
ladder, typed mempool admission rules (sender caps, nonce gaps, dynamic
fee floor, replacement-by-fee), WS slow-consumer protection, and
loadgen shed classification.

The end-to-end 5x-overload soak lives in tests/test_overload_chaos.py.
"""

import socket
import time
from types import SimpleNamespace

import pytest

from ethrex_tpu.blockchain.mempool import (
    FeeBelowFloorError,
    Mempool,
    NonceGapError,
    ReplacementUnderpricedError,
    SenderLimitError,
    UnderpricedError,
)
from ethrex_tpu.crypto import secp256k1
from ethrex_tpu.node import Node
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.primitives.transaction import TYPE_DYNAMIC_FEE, Transaction
from ethrex_tpu.rpc.server import RpcServer
from ethrex_tpu.rpc.websocket import WsConnection
from ethrex_tpu.utils.metrics import METRICS
from ethrex_tpu.utils.overload import (
    SERVER_BUSY_CODE,
    OverloadController,
    classify,
    is_busy_error,
)

SECRET = 0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8
SENDER = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(SECRET))

GENESIS = {
    "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
               "shanghaiTime": 0, "cancunTime": 0},
    "alloc": {"0x" + SENDER.hex(): {"balance": hex(10**21)}},
    "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7", "timestamp": "0x0",
}


def _tx(nonce, secret=SECRET, fee=10**10, value=1):
    return Transaction(
        tx_type=TYPE_DYNAMIC_FEE, chain_id=1337, nonce=nonce,
        max_priority_fee_per_gas=1, max_fee_per_gas=fee,
        gas_limit=21_000, to=bytes([0xAA]) * 20, value=value).sign(secret)


# ---------------------------------------------------------------------------
# cost classes and the typed busy error

def test_method_cost_classification():
    assert classify("eth_blockNumber") == "read"
    assert classify("eth_getBalance") == "read"
    assert classify("eth_sendRawTransaction") == "submit"
    assert classify("eth_getLogs") == "heavy"
    assert classify("eth_call") == "heavy"
    assert classify("eth_estimateGas") == "heavy"
    assert classify("eth_getProof") == "heavy"
    assert classify("debug_traceTransaction") == "heavy"
    # the control plane must never be shed: consensus driver, operator
    # eyes, and the namespaces behind auth
    assert classify("engine_newPayloadV3") == "control"
    assert classify("ethrex_health") == "control"
    assert classify("ethrex_alerts") == "control"
    assert classify("ethrex_debug_snapshot") == "control"
    assert classify("admin_peers") == "control"
    assert classify("web3_clientVersion") == "control"


def test_is_busy_error_classifier():
    busy = {"code": SERVER_BUSY_CODE, "message": "server busy",
            "data": {"reason": "level", "class": "read",
                     "retryAfter": 1.0, "shedLevel": 3}}
    assert is_busy_error(busy)
    assert not is_busy_error({"code": -32603, "message": "internal"})
    assert not is_busy_error({"code": SERVER_BUSY_CODE, "data": None})
    assert not is_busy_error("server busy")
    assert not is_busy_error(None)


def test_concurrency_limit_sheds_and_releases():
    ctl = OverloadController(read_limit=1, tick_interval=0.0,
                             raise_hold=10.0)
    d1 = ctl.admit("eth_blockNumber")
    assert d1.admitted
    d2 = ctl.admit("eth_blockNumber")
    assert not d2.admitted
    assert d2.reason == "concurrency"
    data = d2.error_data()
    assert data["class"] == "read"
    assert data["retryAfter"] > 0
    ctl.release(d1)
    d3 = ctl.admit("eth_blockNumber")
    assert d3.admitted
    ctl.release(d3)
    assert ctl.shed_total == 1
    assert ctl.shed_by_reason == {"concurrency": 1}


def test_stale_queue_age_sheds_on_deadline():
    ctl = OverloadController(read_deadline=0.2, tick_interval=0.0,
                             raise_hold=10.0)
    d = ctl.admit("eth_blockNumber", queue_age=1.0)
    assert not d.admitted
    assert d.reason == "deadline"
    # fresh requests still pass
    d2 = ctl.admit("eth_blockNumber", queue_age=0.0)
    assert d2.admitted
    ctl.release(d2)


def test_shed_level_ladder_and_hysteresis_recovery():
    ctl = OverloadController(queue_high=0.1, raise_hold=0.0,
                             recover_hold=0.0, tick_interval=0.0,
                             signal_window=0.3)
    for _ in range(20):
        ctl.note_queue_wait(0.5)     # 5x queue_high -> desired level 3
    d = ctl.admit("ethrex_health")   # control: admitted, but ticks
    ctl.release(d)
    assert ctl.level == 3
    assert ctl.state == "shedding"
    for method, expect_shed in (("debug_traceTransaction", True),
                                ("eth_sendRawTransaction", True),
                                ("eth_blockNumber", True),
                                ("ethrex_health", False)):
        dec = ctl.admit(method)
        assert dec.admitted == (not expect_shed), method
        if dec.admitted:
            ctl.release(dec)
        else:
            assert dec.reason == "level"
            assert dec.error_data()["shedLevel"] == 3
    # level sheds back off harder: retryAfter scales with the level
    lvl_shed = ctl.admit("eth_blockNumber")
    assert lvl_shed.retry_after == pytest.approx(ctl.retry_after * 3)
    # let the wait samples age out of the signal window, then recover
    time.sleep(0.35)
    d = ctl.admit("ethrex_health")
    ctl.release(d)
    assert ctl.level == 0
    assert ctl.state == "recovered"
    time.sleep(0.05)
    d = ctl.admit("ethrex_health")
    ctl.release(d)
    assert ctl.state == "ok"


def test_raise_hold_delays_the_ladder():
    """A transient spike shorter than raise_hold must not move the
    level — the same breach-persistence rule the alert engine uses."""
    ctl = OverloadController(queue_high=0.1, raise_hold=30.0,
                             tick_interval=0.0)
    for _ in range(20):
        ctl.note_queue_wait(0.5)
    d = ctl.admit("ethrex_health")
    ctl.release(d)
    assert ctl.level == 0
    assert ctl.state == "ok"


def test_mempool_pressure_sheds_submit_before_reads():
    ctl = OverloadController(mempool_probe=lambda: 0.99,
                             raise_hold=0.0, tick_interval=0.0)
    d = ctl.admit("ethrex_health")   # tick: probe pushes level to 2
    ctl.release(d)
    assert ctl.level == 2
    assert not ctl.admit("eth_sendRawTransaction").admitted
    assert not ctl.admit("debug_traceTransaction").admitted
    rd = ctl.admit("eth_blockNumber")
    assert rd.admitted               # reads survive level 2
    ctl.release(rd)


def test_disabled_controller_admits_everything():
    ctl = OverloadController(enabled=False, read_limit=1,
                             tick_interval=0.0)
    decisions = [ctl.admit("eth_blockNumber", queue_age=100.0)
                 for _ in range(5)]
    assert all(d.admitted for d in decisions)
    for d in decisions:
        ctl.release(d)
    assert ctl.shed_total == 0


def test_controller_to_json_surface():
    ctl = OverloadController(read_limit=7)
    out = ctl.to_json()
    assert out["enabled"] is True
    assert out["level"] == 0
    assert out["state"] == "ok"
    assert out["classes"]["read"]["limit"] == 7
    assert out["classes"]["control"]["deadlineSeconds"] is None
    assert out["classes"]["control"]["shedAtLevel"] == 0


# ---------------------------------------------------------------------------
# RPC integration: the typed busy answer, never-executed contract

def test_rpc_handle_sheds_stale_requests_without_executing():
    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node, port=0)     # not started: handle() direct
    calls = []
    server.methods["test_probe"] = lambda: calls.append(1) or "ok"

    stale = time.monotonic() - 60.0      # way past the read deadline
    resp = server.handle({"jsonrpc": "2.0", "id": 9,
                          "method": "test_probe"}, accepted_at=stale)
    err = resp["error"]
    assert err["code"] == SERVER_BUSY_CODE
    assert err["message"] == "server busy"
    assert err["data"]["reason"] == "deadline"
    assert err["data"]["class"] == "read"
    assert err["data"]["retryAfter"] > 0
    assert is_busy_error(err)
    assert calls == []                   # shed means NEVER executed

    fresh = server.handle({"jsonrpc": "2.0", "id": 10,
                           "method": "test_probe"},
                          accepted_at=time.monotonic())
    assert fresh["result"] == "ok"
    assert calls == [1]


def test_health_surfaces_overload_state():
    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node, port=0)
    out = server.handle({"jsonrpc": "2.0", "id": 1,
                         "method": "ethrex_health"})["result"]
    ov = out["rpc"]["overload"]
    assert ov["state"] == "ok"
    assert set(ov["classes"]) == {"control", "heavy", "read", "submit"}
    assert server.overload is node.rpc_overload


# ---------------------------------------------------------------------------
# mempool admission rules (typed, adversarial-sender caps)

BALANCE = 10**21


def test_nonce_gap_rejected_typed():
    pool = Mempool(capacity=100, max_nonce_gap=2)
    pool.add_transaction(_tx(0), 0, BALANCE, 7)
    pool.add_transaction(_tx(2), 0, BALANCE, 7)   # gap 2: at the limit
    with pytest.raises(NonceGapError, match="nonce gap 5 exceeds"):
        pool.add_transaction(_tx(5), 0, BALANCE, 7)
    assert pool.rejections == {"nonce_gap": 1}
    assert len(pool) == 2


def test_sender_slot_cap_rejected_typed():
    pool = Mempool(capacity=100, max_sender_slots=2)
    pool.add_transaction(_tx(0), 0, BALANCE, 7)
    pool.add_transaction(_tx(1), 0, BALANCE, 7)
    with pytest.raises(SenderLimitError, match="cap 2"):
        pool.add_transaction(_tx(2), 0, BALANCE, 7)
    assert pool.rejections == {"sender_limit": 1}
    # a replacement is exempt: it does not grow the sender's footprint
    pool.add_transaction(_tx(1, fee=2 * 10**10), 0, BALANCE, 7)
    assert len(pool) == 2


def test_dynamic_fee_floor_prices_admission_when_hot():
    pool = Mempool(capacity=4, fee_floor_start=0.5)
    base_fee = 10**10
    assert pool.fee_floor(base_fee) == 0          # cold pool: no floor
    for nonce in range(3):
        pool.add_transaction(_tx(nonce, fee=10**12), 0, BALANCE, base_fee)
    # 3/4 regular slots: span 0.5 of the ramp -> 5.5x base_fee
    floor = pool.fee_floor(base_fee)
    assert floor == int(5.5 * base_fee)
    with pytest.raises(FeeBelowFloorError, match="below dynamic floor"):
        pool.add_transaction(_tx(3, fee=base_fee), 0, BALANCE, base_fee)
    assert pool.rejections == {"fee_below_floor": 1}
    # paying the floor gets in
    pool.add_transaction(_tx(3, fee=floor), 0, BALANCE, base_fee)
    assert len(pool) == 4


def test_replacement_by_fee_typed_and_counted():
    pool = Mempool(capacity=10)
    pool.add_transaction(_tx(0, fee=10**10), 0, BALANCE, 7)
    with pytest.raises(ReplacementUnderpricedError,
                       match="replacement underpriced"):
        pool.add_transaction(_tx(0, fee=10**10 + 1), 0, BALANCE, 7)
    # the typed class IS the legacy class: ledger and surface unchanged
    assert issubclass(ReplacementUnderpricedError, UnderpricedError)
    assert ReplacementUnderpricedError.reason == "underpriced"
    assert pool.rejections == {"underpriced": 1}
    assert pool.replacements == 0
    # >=10% bump replaces in place
    pool.add_transaction(_tx(0, fee=11 * 10**9), 0, BALANCE, 7)
    assert len(pool) == 1
    assert pool.replacements == 1
    stats = pool.stats_json()
    assert stats["replacements"] == 1
    assert stats["senderSlotCap"] == pool.max_sender_slots
    assert stats["nonceGapLimit"] == pool.max_nonce_gap
    assert METRICS.snapshot()["counters"][
        "mempool_replacements_total"] >= 1


# ---------------------------------------------------------------------------
# WS slow-consumer protection

def test_ws_slow_consumer_is_disconnected():
    before = METRICS.snapshot()["counters"].get(
        "ws_slow_consumer_disconnects_total", 0)
    s_srv, s_cli = socket.socketpair()
    s_srv.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 4096)
    s_cli.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
    ws = SimpleNamespace(connections=set(), notify_queue_size=2,
                         slow_consumer_deadline=0.05)
    conn = WsConnection(ws, s_srv)
    ws.connections.add(conn)
    payload = "ff" * 16384    # one frame overflows the socket buffer
    deadline = time.monotonic() + 5.0
    while conn.alive and time.monotonic() < deadline:
        conn.notify("0x1", payload)   # consumer never reads
        time.sleep(0.005)
    assert not conn.alive
    assert conn.notifications_dropped > 0
    assert conn not in ws.connections
    after = METRICS.snapshot()["counters"][
        "ws_slow_consumer_disconnects_total"]
    assert after >= before + 1
    drops = METRICS.snapshot()["counters"][
        "ws_notifications_dropped_total"]
    assert drops >= conn.notifications_dropped
    s_cli.close()
    s_srv.close()


def test_ws_healthy_consumer_keeps_flowing():
    s_srv, s_cli = socket.socketpair()
    ws = SimpleNamespace(connections=set(), notify_queue_size=8,
                         slow_consumer_deadline=5.0)
    conn = WsConnection(ws, s_srv)
    for _ in range(5):
        assert conn.notify("0x1", "0x2a")
    s_cli.settimeout(5.0)
    got = b""
    while got.count(b"eth_subscription") < 5:
        got += s_cli.recv(65536)
    assert conn.alive
    deadline = time.monotonic() + 5.0
    while conn.notifications_sent < 5 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert conn.notifications_sent == 5
    assert conn.notifications_dropped == 0
    assert b"eth_subscription" in got
    conn._sendq.put_nowait(None)
    s_cli.close()
    s_srv.close()


# ---------------------------------------------------------------------------
# loadgen: typed busy answers are shed, not errors; shed is not delivered

@pytest.fixture()
def busy_rpc():
    from ethrex_tpu.perf import loadgen

    node = Node(Genesis.from_json(GENESIS))
    ctl = OverloadController(read_limit=1, raise_hold=30.0,
                             tick_interval=0.0)
    server = RpcServer(node, port=0, overload=ctl).start()
    try:
        yield loadgen, ctl, f"http://127.0.0.1:{server.port}"
    finally:
        server.stop()


def test_loadgen_classifies_shed_separately(busy_rpc):
    loadgen, ctl, url = busy_rpc
    hold = ctl.admit("eth_blockNumber")       # pin the single read slot
    assert hold.admitted
    try:
        h = loadgen.Harness(url, payload="ping", workers=2, timeout=5.0)
        rep = h.run(20.0, duration=0.5)
    finally:
        ctl.release(hold)
    assert rep["sent"] > 0
    assert rep["shed"] == rep["sent"]         # every request was shed
    assert rep["errors"] == 0                 # ...and none is an error
    assert rep["delivered"] == 0
    assert rep["shedRate"] == 1.0
    assert rep["scheduled"] == rep["delivered"] + rep["shed"] + \
        rep["missed"]
    # shed latencies live in their own histogram; the accepted-request
    # histogram stays empty so the serving p99 cannot be gamed
    assert rep["shedLatency"]["count"] == rep["shed"]
    assert rep["latency"]["count"] == 0
    assert rep["latency"]["p99"] is None


def test_sweep_counts_shed_as_not_delivered(busy_rpc):
    loadgen, ctl, url = busy_rpc
    hold = ctl.admit("eth_blockNumber")
    assert hold.admitted
    try:
        h = loadgen.Harness(url, payload="ping", workers=2, timeout=5.0)
        sweep = h.sweep([10.0], duration=0.5)
    finally:
        ctl.release(hold)
    # 100% graceful sheds and 0% errors is still NOT a sustained rate
    assert sweep["rates"][0]["errorRate"] == 0.0
    assert sweep["maxSustainableRate"] is None


# ---------------------------------------------------------------------------
# surfaces: snapshot traffic section and monitor panel

def test_snapshot_traffic_includes_overload():
    from ethrex_tpu.utils import snapshot

    node = Node(Genesis.from_json(GENESIS))
    RpcServer(node, port=0)               # attaches node.rpc_overload
    out = snapshot.collect(node)["traffic"]
    assert out["overload"]["state"] == "ok"
    assert "shed" in out["rpc"]
    assert "shedLevel" in out["rpc"]


def test_monitor_traffic_panel_shows_shedding():
    from ethrex_tpu.utils.monitor import _traffic_lines

    snap = {"health": {"rpc": {
        "accepted": 10, "resets": 0, "eof": 0, "inflight": 1,
        "slowRequests": 0, "listenBacklog": 128, "requestBytes": 100,
        "responseBytes": 200, "wsConnections": 0, "wsNotifications": 0,
        "wsSendFailures": 0, "shed": 7, "shedLevel": 2,
        "wsNotificationsDropped": 3, "wsSlowConsumerDisconnects": 1,
    }}}
    text = "\n".join(_traffic_lines(snap, width=100))
    assert "shed 7" in text
    assert "shed level 2" in text
    assert "slow-consumer kicks 1" in text
    assert "{" not in text                # panels never leak raw dicts


def test_default_alert_rules_cover_shedding_and_churn():
    from ethrex_tpu.utils.alerts import default_rules

    names = {r.name for r in default_rules()}
    assert {"rpc_shed_rate:page", "rpc_shed_rate:warn",
            "mempool_replacement_churn:page",
            "mempool_replacement_churn:warn"} <= names
