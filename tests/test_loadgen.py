"""Open-loop load harness (ethrex_tpu/perf/loadgen.py).

The load-bearing property under test: the generator is OPEN-loop — a
stalled server shows up as rising measured latency while the offered
schedule (attempt count) stays fixed.  A closed-loop generator would
instead quietly send fewer requests and report healthy latencies
(coordinated omission)."""

import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from ethrex_tpu.perf import loadgen


# ---------------------------------------------------------------------------
# schedules

def test_fixed_schedule_spacing_and_length():
    sched = loadgen.build_schedule(10, 1.0, "fixed")
    assert len(sched) == 10
    gaps = [b - a for a, b in zip(sched, sched[1:])]
    assert all(abs(g - 0.1) < 1e-9 for g in gaps)
    assert loadgen.build_schedule(0, 1.0) == []
    assert loadgen.build_schedule(10, 0) == []


def test_poisson_schedule_deterministic_and_rate_shaped():
    a = loadgen.build_schedule(200, 2.0, "poisson", seed=7)
    b = loadgen.build_schedule(200, 2.0, "poisson", seed=7)
    assert a == b
    assert a != loadgen.build_schedule(200, 2.0, "poisson", seed=8)
    # law of large numbers: ~rate*duration arrivals, irregular gaps
    assert 300 < len(a) < 500
    gaps = {round(y - x, 6) for x, y in zip(a, a[1:])}
    assert len(gaps) > 50
    assert all(t <= 2.0 for t in a)


def test_sender_secrets_deterministic_and_in_range():
    from ethrex_tpu.crypto import secp256k1

    s1 = loadgen.derive_secrets(4, seed=3)
    assert s1 == loadgen.derive_secrets(4, seed=3)
    assert len(set(s1)) == 4
    assert all(0 < s < secp256k1.N for s in s1)


# ---------------------------------------------------------------------------
# percentile estimation over cumulative histogram rows

def test_percentile_interpolates_within_bucket():
    buckets = (0.001, 0.002, 0.004, 0.008)
    # 10 observations, all in (0.001, 0.002]
    row = [0, 10, 10, 10, 10, 0.02]
    p50 = loadgen.percentile_from_rows(buckets, [row], 0.50)
    assert 0.001 < p50 <= 0.002
    # median of a bucket interpolates to its midpoint
    assert abs(p50 - 0.0015) < 1e-9
    # p100 caps at the last finite boundary even for +Inf observations
    inf_row = [0, 0, 0, 0, 5, 1.0]
    assert loadgen.percentile_from_rows(buckets, [inf_row], 0.99) == 0.008


def test_percentile_sums_across_series():
    buckets = (1.0, 2.0)
    fast = [8, 8, 8, 4.0]     # 8 obs <= 1.0
    slow = [0, 2, 2, 3.5]     # 2 obs in (1.0, 2.0]
    p50 = loadgen.percentile_from_rows(buckets, [fast, slow], 0.50)
    assert p50 <= 1.0
    p95 = loadgen.percentile_from_rows(buckets, [fast, slow], 0.95)
    assert 1.0 < p95 <= 2.0
    assert loadgen.percentile_from_rows(buckets, [], 0.5) is None
    assert loadgen.percentile_from_rows(buckets, [[0, 0, 0, 0.0]], 0.5) \
        is None


# ---------------------------------------------------------------------------
# open-loop behavior against a deliberately stalled server

class _StalledRpc(BaseHTTPRequestHandler):
    """JSON-RPC endpoint that sleeps `delay` before every response."""

    delay = 0.0

    def do_POST(self):
        body = self.rfile.read(int(self.headers.get("Content-Length", 0)))
        rid = json.loads(body).get("id", 1)
        time.sleep(type(self).delay)
        data = json.dumps({"jsonrpc": "2.0", "id": rid,
                           "result": "0x0"}).encode()
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def log_message(self, *args):
        pass


@pytest.fixture()
def stalled_server():
    class Handler(_StalledRpc):
        delay = 0.0

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    yield Handler, f"http://127.0.0.1:{httpd.server_address[1]}"
    httpd.shutdown()
    httpd.server_close()


def test_open_loop_stall_raises_latency_not_send_rate(stalled_server):
    """The acceptance property: stalling the server must NOT slow the
    generator down.  Attempts stay pinned to the schedule; the stall
    appears in the measured percentiles instead."""
    handler, url = stalled_server
    rate, duration = 40, 1.0
    expected = len(loadgen.build_schedule(rate, duration, "fixed"))

    h = loadgen.Harness(url, payload="ping", workers=64, seed=0)
    fast = h.run(rate, duration, "fixed")
    assert fast["scheduled"] == expected
    assert fast["sent"] + fast["missed"] == expected

    handler.delay = 0.3
    slow = h.run(rate, duration, "fixed")
    # identical offered schedule: the generator did NOT back off
    assert slow["scheduled"] == expected
    assert slow["sent"] + slow["missed"] == expected
    assert slow["sent"] >= expected * 0.9   # 64 workers absorb 12 in-flight
    # the stall surfaces as measured latency
    assert slow["latency"]["p50"] >= 0.3 > (fast["latency"]["p50"] or 0)
    assert slow["latency"]["p99"] >= 0.3


def test_open_loop_misses_are_counted_never_deferred(stalled_server):
    """With a single worker and a 0.3s stall, most send slots find no
    free worker — they must be dropped and counted, not queued behind
    the stalled request (which would serialize sends = closed loop)."""
    handler, url = stalled_server
    handler.delay = 0.3
    h = loadgen.Harness(url, payload="ping", workers=1, seed=0)
    rep = h.run(rate=40, duration=1.0, arrivals="fixed")
    assert rep["scheduled"] == rep["sent"] + rep["missed"]
    # one worker at ~0.3s/req can deliver only ~3-4 of 40 slots
    assert rep["sent"] <= 8
    assert rep["missed"] >= 30
    # and the run still finishes on the schedule's clock, not the
    # server's: 40 slots * 0.3s serialized would take 12s
    assert rep["achievedRate"] < 10


def test_sweep_reports_max_sustainable_rate(stalled_server):
    handler, url = stalled_server
    h = loadgen.Harness(url, payload="ping", workers=32, seed=1)
    sweep = h.sweep([10, 20], duration=0.5, arrivals="poisson")
    assert [r["offeredRate"] for r in sweep["rates"]] == [10, 20]
    assert sweep["maxSustainableRate"] == 20
    for rep in sweep["rates"]:
        assert rep["errorRate"] == 0.0
        assert rep["latency"]["p99"] is not None
    # a stalled server + tiny worker pool drops below the achieved-
    # fraction floor, so nothing qualifies as sustainable
    handler.delay = 0.4
    h1 = loadgen.Harness(url, payload="ping", workers=1, seed=1)
    sweep = h1.sweep([20], duration=0.5)
    assert sweep["maxSustainableRate"] is None


def test_request_latency_histogram_uses_shared_ladder():
    from ethrex_tpu.utils.metrics import DEFAULT_BUCKETS, Metrics

    registry = Metrics()
    loadgen.observe_request_latency(registry, "ping", 0.005)
    snap = registry.snapshot()
    hist = snap["histograms"]["loadgen_request_seconds"]
    assert tuple(hist["buckets"]) == DEFAULT_BUCKETS
    assert hist["series"][0]["labels"] == {"kind": "ping"}
    assert "loadgen_request_seconds" in registry.help


def test_token_initcode_returns_runtime():
    """The deploy wrapper must RETURN exactly the 8-byte runtime (same
    PUSH8/MSTORE/RETURN wrapper the sstore template uses)."""
    assert len(bytes.fromhex(loadgen.TOKEN_RUNTIME)) == 8
    assert loadgen.TOKEN_INITCODE == \
        "67" + loadgen.TOKEN_RUNTIME + "5f5260086018f3"


# ---------------------------------------------------------------------------
# many-sender tx mode: funding must chunk below the mempool's
# per-sender slot cap or the ROOT key evicts its own funding tail and
# later senders never get funded

def test_many_sender_funding_chunks_below_sender_cap():
    from ethrex_tpu.blockchain.mempool import MAX_SENDER_SLOTS
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.rpc.server import RpcServer
    from tests.test_l2_pipeline import GENESIS

    n_senders = MAX_SENDER_SLOTS * 2 + 17  # forces 2+ funding chunks
    node = Node(Genesis.from_json(GENESIS))
    rpc = RpcServer(node, port=0).start()
    try:
        h = loadgen.Harness(f"http://127.0.0.1:{rpc.port}",
                            senders=n_senders, payload="tx",
                            workers=8, timeout=10.0, seed=4)
        h.setup(fund_wei=10 ** 15)
        # chunked funding produced intermediate blocks and funded EVERY
        # sender, including the tail past the per-sender cap
        assert node.store.latest_number() >= 2
        root = node.store.head_header().state_root
        for addr in h.addresses:
            acct = node.store.account_state(root, addr)
            assert acct is not None and acct.balance == 10 ** 15, \
                f"sender 0x{addr.hex()} left unfunded"

        rep = h.run(100.0, duration=0.5)
        # the sender spread is part of the record: 16-sender and
        # 10k-sender serving numbers are different benchmarks
        assert rep["senders"] == n_senders
        assert rep["delivered"] > 0
        assert rep["errors"] == 0
        sweep = h.sweep([50.0], duration=0.2)
        assert sweep["senders"] == n_senders
    finally:
        rpc.stop()
