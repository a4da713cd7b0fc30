"""Open-loop load harness for the JSON-RPC serving layer.

The legacy generator (`run_load`, at the end of this module) is
CLOSED-loop: it fires the next request only after the previous one
returns, so a slow server throttles the generator and the measured
latencies silently omit exactly the stalls that matter
("coordinated omission" — see the Tail at Scale discussion in
docs/PERFORMANCE.md).  This harness is OPEN-loop:

- arrival times are PRECOMPUTED from a fixed or Poisson schedule before
  the clock starts, so response times cannot stretch interarrival gaps;
- a send slot with no free worker is counted as MISSED, never deferred —
  the offered rate is honest even when the server melts;
- per-request latency is measured from the SCHEDULED send instant to the
  response, into the shared exponential-bucket histogram ladder
  (utils/metrics.DEFAULT_BUCKETS), so server stalls surface as rising
  tail latency instead of a quietly reduced send rate;
- sweep mode replays the schedule at several offered rates over real TCP
  and reports max-sustainable-rate plus p50/p95/p99/error-rate per rate.

Traffic is a configurable mix of value transfers and token-template
calls (a per-caller balance-increment contract) from many simulated
funded senders, all pre-signed before the clock starts so signing cost
never pollutes the schedule.

Usage (open-loop):
    python -m ethrex_tpu.perf.loadgen --url http://127.0.0.1:8545 \
        --key <hex> --rates 10,25,50 --duration 5 --arrivals poisson

The legacy closed-loop flags (--txs/--mode) still work and run the old
inclusion-throughput measurement unchanged.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import http.client
import json
import random
import threading
import time
import urllib.request
from urllib.parse import urlparse

from ..crypto import secp256k1
from ..primitives.transaction import TYPE_DYNAMIC_FEE, Transaction
from ..utils.metrics import Metrics
from ..utils.overload import is_busy_error

DEFAULT_KEY = 0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8

# counter contract: every call increments slot 0 (the "IO" load shape)
SSTORE_RUNTIME = "5f546001015f5500"
SSTORE_INITCODE = "67" + SSTORE_RUNTIME + "5f5260086018f3"

# token template: every call increments the CALLER-keyed storage slot —
# the balance-update shape of an ERC20 transfer without the calldata
# decoding (CALLER SLOAD 1 ADD CALLER SSTORE STOP)
TOKEN_RUNTIME = "3354600101335500"
TOKEN_INITCODE = "67" + TOKEN_RUNTIME + "5f5260086018f3"

# a run is "sustainable" at an offered rate when errors stay under 1%
# and the generator actually delivered ≥95% of the schedule (missed
# sends mean the local worker pool, not the server, was the bottleneck)
MAX_ERROR_RATE = 0.01
MIN_ACHIEVED_FRAC = 0.95


class LoadgenError(RuntimeError):
    """Transport failure or JSON-RPC error response during a run."""


def observe_request_latency(registry, kind: str, seconds: float):
    """Record one send-timestamp→response latency into the run's
    registry (same exponential-bucket ladder as the server side, so the
    client-observed and server-observed histograms are joinable)."""
    registry.observe("loadgen_request_seconds", seconds, {"kind": kind},
                     help_text="Open-loop request latency measured from "
                               "the SCHEDULED send instant to the "
                               "response, so server stalls surface as "
                               "latency, never as a reduced send rate")


def observe_shed_latency(registry, kind: str, seconds: float):
    """Latency of typed server-busy (shed) responses, kept in its OWN
    histogram: the accepted-request percentiles must measure work the
    server actually did, so shedding cannot game the serving p99
    gate."""
    registry.observe("loadgen_shed_seconds", seconds, {"kind": kind},
                     help_text="Latency of typed server-busy (shed) "
                               "responses from the scheduled send "
                               "instant — fast sheds are the overload "
                               "contract (docs/OVERLOAD.md)")


def observe_rejection_latency(registry, kind: str, seconds: float):
    """Latency of typed mempool rejections (per-sender cap, nonce gap,
    fee floor, ...), kept apart from both accepted work and sheds: the
    server answered fast and deliberately — admission control working
    as designed is neither served work nor an error."""
    registry.observe("loadgen_rejection_seconds", seconds,
                     {"kind": kind},
                     help_text="Latency of typed mempool-rejection "
                               "responses (error data carries the "
                               "admission reason) from the scheduled "
                               "send instant — admission control "
                               "pushing back, not a failure")


def build_schedule(rate: float, duration: float, arrivals: str = "fixed",
                   seed: int = 0) -> list[float]:
    """Arrival offsets (seconds from run start), precomputed so nothing
    the server does can stretch the interarrival gaps.

    fixed: deterministic 1/rate spacing.  poisson: exponential
    interarrival gaps (seeded), the memoryless arrival process real
    traffic approximates."""
    if rate <= 0 or duration <= 0:
        return []
    out: list[float] = []
    t = 0.0
    rng = random.Random(seed)
    while True:
        t += (1.0 / rate) if arrivals == "fixed" else rng.expovariate(rate)
        if t > duration:
            return out
        out.append(t)


def percentile_from_rows(buckets, rows, q: float) -> float | None:
    """Percentile estimate from cumulative-per-bucket histogram rows
    (the _Histogram layout), interpolated inside the winning bucket and
    capped at the last finite boundary for +Inf — the same estimator as
    timeseries.percentiles, over absolute counts instead of deltas."""
    if not rows:
        return None
    nb = len(buckets)
    counts = [0] * (nb + 1)
    for row in rows:
        for i in range(nb + 1):
            counts[i] += row[i]
    total = counts[nb]
    if total <= 0:
        return None
    rank = q * total
    value = buckets[-1]
    lower, prev = 0.0, 0
    for i, le in enumerate(buckets):
        if counts[i] >= rank:
            span = counts[i] - prev
            frac = (rank - prev) / span if span else 1.0
            value = lower + frac * (le - lower)
            break
        lower, prev = le, counts[i]
    return value


def derive_secrets(n: int, seed: int = 0) -> list[int]:
    """Deterministic simulated-sender keys (never real funds)."""
    out = []
    for i in range(n):
        h = hashlib.sha256(f"ethrex-loadgen-{seed}-{i}".encode()).digest()
        out.append(int.from_bytes(h, "big") % (secp256k1.N - 1) + 1)
    return out


class RpcConn:
    """One persistent JSON-RPC HTTP connection (keep-alive), with a
    single reconnect retry so a server-side idle close between runs does
    not read as a request error."""

    def __init__(self, url: str, timeout: float = 30.0):
        u = urlparse(url)
        self.host = u.hostname or "127.0.0.1"
        self.port = u.port or 80
        self.path = u.path or "/"
        self.timeout = timeout
        self._conn: http.client.HTTPConnection | None = None

    def close(self):
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass
            self._conn = None

    def post(self, body: bytes) -> dict:
        data = None
        for attempt in (0, 1):
            try:
                if self._conn is None:
                    self._conn = http.client.HTTPConnection(
                        self.host, self.port, timeout=self.timeout)
                self._conn.request("POST", self.path, body,
                                   {"Content-Type": "application/json"})
                resp = self._conn.getresponse()
                data = resp.read()
                if resp.status != 200:
                    raise LoadgenError(f"HTTP {resp.status}")
                break
            except (http.client.HTTPException, OSError) as exc:
                self.close()
                if attempt:
                    raise LoadgenError(f"transport: {exc}") from exc
        try:
            return json.loads(data)
        except (json.JSONDecodeError, TypeError) as exc:
            raise LoadgenError(f"bad response: {exc}") from exc

    def call(self, method: str, params: list):
        out = self.post(json.dumps(
            {"jsonrpc": "2.0", "id": 1, "method": method,
             "params": params}).encode())
        if "error" in out:
            raise LoadgenError(f"{method}: {out['error']}")
        return out.get("result")


def _body(method: str, params: list, rid: int = 1) -> bytes:
    return json.dumps({"jsonrpc": "2.0", "id": rid, "method": method,
                       "params": params}).encode()


class _AsyncConn:
    """One persistent keep-alive JSON-RPC connection on the client
    event loop, with a single reconnect retry (mirroring RpcConn.post)
    so a server-side idle close does not read as a request error.
    Handles HTTP/1.0 close-per-response servers by reconnecting."""

    __slots__ = ("host", "port", "path", "timeout", "reader", "writer")

    def __init__(self, host: str, port: int, path: str, timeout: float):
        self.host = host
        self.port = port
        self.path = path
        self.timeout = timeout
        self.reader: asyncio.StreamReader | None = None
        self.writer: asyncio.StreamWriter | None = None

    def close(self):
        if self.writer is not None:
            try:
                self.writer.close()
            except Exception:  # noqa: BLE001 — teardown
                pass
            self.reader = self.writer = None

    async def connect(self):
        self.reader, self.writer = await asyncio.open_connection(
            self.host, self.port)

    async def _roundtrip(self, body: bytes) -> bytes:
        if self.writer is None:
            await self.connect()
        self.writer.write(
            b"POST %s HTTP/1.1\r\n"
            b"Host: %s\r\n"
            b"Content-Type: application/json\r\n"
            b"Content-Length: %d\r\n\r\n"
            % (self.path.encode(), self.host.encode(), len(body)) + body)
        await self.writer.drain()
        head = await self.reader.readuntil(b"\r\n\r\n")
        status_line, _, header_block = head.partition(b"\r\n")
        parts = status_line.split(None, 2)
        status = int(parts[1])
        headers: dict[bytes, bytes] = {}
        for line in header_block.split(b"\r\n"):
            if b":" in line:
                k, v = line.split(b":", 1)
                headers[k.strip().lower()] = v.strip()
        data = await self.reader.readexactly(
            int(headers.get(b"content-length", b"0")))
        connection = headers.get(b"connection", b"").lower()
        if b"close" in connection or (parts[0] == b"HTTP/1.0"
                                      and b"keep-alive" not in connection):
            self.close()
        if status != 200:
            raise LoadgenError(f"HTTP {status}")
        return data

    async def post(self, body: bytes):
        data = None
        for attempt in (0, 1):
            try:
                data = await asyncio.wait_for(self._roundtrip(body),
                                              self.timeout)
                break
            except (OSError, ConnectionError, ValueError, IndexError,
                    asyncio.IncompleteReadError,
                    asyncio.TimeoutError) as exc:
                self.close()
                if attempt:
                    raise LoadgenError(f"transport: {exc}") from exc
        try:
            return json.loads(data)
        except (json.JSONDecodeError, TypeError) as exc:
            raise LoadgenError(f"bad response: {exc}") from exc


REJECTION_CODE = -32000


def rejection_reason(err) -> str | None:
    """The typed mempool-rejection reason carried in a JSON-RPC error's
    structured data (rpc/eth.py send_raw_transaction), or None when the
    error is anything else.  Strict shape check mirrors is_busy_error:
    an untyped -32000 stays a generic error."""
    if not isinstance(err, dict) or err.get("code") != REJECTION_CODE:
        return None
    data = err.get("data")
    if not isinstance(data, dict):
        return None
    reason = data.get("reason")
    if isinstance(reason, str) and reason:
        return reason
    return None


def _classify(out) -> tuple[bool, bool, str | None]:
    """(err, shed, rejection_reason) from a decoded response.  A typed
    server-busy answer is graceful shedding and a typed mempool
    rejection is admission control doing its job — both counted apart
    from errors so sweeps distinguish degradation modes instead of
    folding cap pushback into a meaningless error rate.  A batch
    response counts as shed/rejected only when EVERY entry was typed
    (partial service delivered work); any untyped error entry makes the
    whole request an error."""
    if isinstance(out, list):
        if not out:
            return True, False, None
        errors = [e["error"] for e in out
                  if isinstance(e, dict) and "error" in e]
        if any(not is_busy_error(e) and rejection_reason(e) is None
               for e in errors):
            return True, False, None
        if errors and len(errors) == len(out):
            reason = next((rejection_reason(e) for e in errors
                           if rejection_reason(e)), None)
            if reason is not None:
                return False, False, reason
            return False, True, None
        return False, False, None
    if isinstance(out, dict) and "error" in out:
        reason = rejection_reason(out["error"])
        if reason is not None:
            return False, False, reason
        if is_busy_error(out["error"]):
            return False, True, None
        return True, False, None
    return False, False, None


class Harness:
    """Open-loop load harness against one JSON-RPC endpoint.

    payload="tx" sends pre-signed transactions from `senders` simulated
    accounts (mix of transfers and token-template calls; requires
    setup() against a funded root key).  payload="ping" sends
    eth_blockNumber — serving-layer load with no chain setup, which is
    what the open-loop unit tests and read-path sweeps use.
    payload="batch" sends JSON-RPC arrays of `batch_size`
    eth_blockNumber calls, exercising the server's concurrent batch
    dispatch; one array is one scheduled send slot.

    Send/receive runs on an asyncio client loop over `workers`
    persistent connections, so the generator outruns the server: the
    open-loop guarantees (scheduled-send latency base, missed-slot
    accounting) are unchanged — a slot with no free connection is a
    MISS, never deferred."""

    def __init__(self, url: str, key: int = DEFAULT_KEY, senders: int = 8,
                 token_frac: float = 0.25, workers: int = 64,
                 timeout: float = 10.0, seed: int = 0,
                 payload: str = "tx", batch_size: int = 8):
        self.url = url
        self.key = key
        self.senders = senders
        self.token_frac = token_frac
        self.workers = workers
        self.timeout = timeout
        self.seed = seed
        self.payload = payload
        self.batch_size = max(1, int(batch_size))
        self.secrets = derive_secrets(senders, seed) if payload == "tx" \
            else []
        self.addresses = [secp256k1.pubkey_to_address(
            secp256k1.pubkey_from_secret(s)) for s in self.secrets]
        self.chain_id: int | None = None
        self.token_address: bytes | None = None

    # -- setup (closed-loop, before any clock starts) -------------------
    def setup(self, fund_wei: int = 10 ** 18,
              produce: bool = True, fund_chunk: int | None = None) -> None:
        """Fund the simulated senders from the root key and deploy the
        token template.  Runs closed-loop: setup cost must never pollute
        the measured schedule.

        Funding is chunked: every `fund_chunk` transfers a block is
        produced to drain the mempool, so a 10k-sender sweep never
        piles 10k pending funding txs into admission.  The chunk
        defaults to the mempool's per-sender slot cap — the ROOT key is
        one sender, and admission rejects its 65th pending funding tx,
        which would leave every later sender unfunded."""
        if self.payload != "tx":
            return
        if fund_chunk is None:
            from ..blockchain.mempool import MAX_SENDER_SLOTS

            fund_chunk = MAX_SENDER_SLOTS
        rpc = RpcConn(self.url, timeout=30.0)
        try:
            self.chain_id = int(rpc.call("eth_chainId", []), 16)
            root = secp256k1.pubkey_to_address(
                secp256k1.pubkey_from_secret(self.key))
            nonce = int(rpc.call("eth_getTransactionCount",
                                 ["0x" + root.hex(), "pending"]), 16)
            for i, addr in enumerate(self.addresses):
                tx = Transaction(
                    tx_type=TYPE_DYNAMIC_FEE, chain_id=self.chain_id,
                    nonce=nonce, max_priority_fee_per_gas=1,
                    max_fee_per_gas=10 ** 10, gas_limit=21_000,
                    to=addr, value=fund_wei).sign(self.key)
                rpc.call("eth_sendRawTransaction",
                         ["0x" + tx.encode_canonical().hex()])
                nonce += 1
                if produce and fund_chunk and (i + 1) % fund_chunk == 0:
                    rpc.call("ethrex_produceBlock", [])
            deploy = Transaction(
                tx_type=TYPE_DYNAMIC_FEE, chain_id=self.chain_id,
                nonce=nonce, max_priority_fee_per_gas=1,
                max_fee_per_gas=10 ** 10, gas_limit=200_000, to=b"",
                data=bytes.fromhex(TOKEN_INITCODE)).sign(self.key)
            rpc.call("eth_sendRawTransaction",
                     ["0x" + deploy.encode_canonical().hex()])
            if produce:
                rpc.call("ethrex_produceBlock", [])
            receipt = None
            deadline = time.time() + 30
            while receipt is None and time.time() < deadline:
                receipt = rpc.call("eth_getTransactionReceipt",
                                   ["0x" + deploy.hash.hex()])
                if receipt is None:
                    time.sleep(0.2)
            if receipt is None or receipt.get("status") != "0x1":
                raise LoadgenError("token template deploy failed")
            self.token_address = bytes.fromhex(
                receipt["contractAddress"][2:])
        finally:
            rpc.close()

    # -- request pre-build ---------------------------------------------
    def _build_requests(self, n: int) -> list[tuple[str, bytes]]:
        """Pre-sign/pre-encode every request body before the clock
        starts, so signing cost cannot eat into send slots."""
        if self.payload == "batch":
            size = self.batch_size
            return [("batch", json.dumps(
                [{"jsonrpc": "2.0", "id": i * size + j,
                  "method": "eth_blockNumber", "params": []}
                 for j in range(size)]).encode())
                    for i in range(n)]
        if self.payload != "tx":
            return [("ping", _body("eth_blockNumber", [], i))
                    for i in range(n)]
        if self.chain_id is None:
            raise LoadgenError("setup() must run before a tx-mode run")
        rpc = RpcConn(self.url, timeout=30.0)
        try:
            nonces = [int(rpc.call("eth_getTransactionCount",
                                   ["0x" + a.hex(), "pending"]), 16)
                      for a in self.addresses]
        finally:
            rpc.close()
        rng = random.Random(self.seed + n)
        out: list[tuple[str, bytes]] = []
        for i in range(n):
            s = i % len(self.secrets)
            token = (self.token_address is not None
                     and rng.random() < self.token_frac)
            tx = Transaction(
                tx_type=TYPE_DYNAMIC_FEE, chain_id=self.chain_id,
                nonce=nonces[s], max_priority_fee_per_gas=1,
                max_fee_per_gas=10 ** 10,
                gas_limit=100_000 if token else 21_000,
                to=self.token_address if token else bytes([0xAA]) * 20,
                value=0 if token else 1).sign(self.secrets[s])
            nonces[s] += 1
            out.append(("token" if token else "transfer",
                        _body("eth_sendRawTransaction",
                              ["0x" + tx.encode_canonical().hex()], i)))
        return out

    # -- the open loop --------------------------------------------------
    def run(self, rate: float, duration: float = 5.0,
            arrivals: str = "fixed") -> dict:
        """One open-loop run at a single offered rate over real TCP."""
        schedule = build_schedule(rate, duration, arrivals, self.seed)
        requests = self._build_requests(len(schedule))
        registry = Metrics()
        stats = {"sent": 0, "errors": 0, "shed": 0, "missed": 0,
                 "rejected": 0}
        kinds: dict[str, int] = {}
        rejections: dict[str, int] = {}
        asyncio.run(self._run_async(schedule, requests, registry,
                                    stats, kinds, rejections))
        missed = stats["missed"]

        snap = registry.snapshot()

        def _lat(hist_name: str) -> dict:
            hist = snap["histograms"].get(hist_name)
            out: dict = {"count": 0, "meanSeconds": None,
                         "p50": None, "p95": None, "p99": None}
            if hist is not None:
                rows = [s["counts"] for s in hist["series"]]
                buckets = hist["buckets"]
                count = sum(r[-1] for r in rows)
                total = sum(s["sum"] for s in hist["series"])
                out["count"] = count
                out["meanSeconds"] = (total / count) if count else None
                for q in (0.50, 0.95, 0.99):
                    out[f"p{int(q * 100)}"] = percentile_from_rows(
                        buckets, rows, q)
            return out

        lat = _lat("loadgen_request_seconds")
        sent = stats["sent"]
        shed = stats["shed"]
        rejected = stats["rejected"]
        # accounting identity: every scheduled slot ends up in exactly
        # one of delivered / shed / rejected / missed
        # (sent = delivered + shed + rejected)
        return {
            "offeredRate": rate,
            "arrivals": arrivals,
            "durationSeconds": duration,
            "senders": self.senders if self.payload == "tx" else None,
            "scheduled": len(schedule),
            "sent": sent,
            "missed": missed,
            "errors": stats["errors"],
            "shed": shed,
            "rejected": rejected,
            "rejections": dict(sorted(rejections.items())),
            "delivered": sent - shed - rejected,
            "achievedRate": round(sent / duration, 3) if duration else 0.0,
            "errorRate": round(stats["errors"] / sent, 6) if sent else 0.0,
            "shedRate": round(shed / sent, 6) if sent else 0.0,
            "rejectionRate": round(rejected / sent, 6) if sent else 0.0,
            "kinds": dict(sorted(kinds.items())),
            "latency": lat,
            "shedLatency": _lat("loadgen_shed_seconds"),
            "rejectionLatency": _lat("loadgen_rejection_seconds"),
        }

    async def _run_async(self, schedule, requests, registry, stats,
                         kinds, rejections):
        """The open loop on an asyncio client: `workers` persistent
        connections in a free pool, one task per send slot."""
        u = urlparse(self.url)
        host = u.hostname or "127.0.0.1"
        port = u.port or 80
        path = u.path or "/"
        conns = [_AsyncConn(host, port, path, self.timeout)
                 for _ in range(self.workers)]
        # pre-connect OUTSIDE the measured schedule so handshake cost
        # cannot eat send slots (failures fall back to lazy reconnect)
        await asyncio.gather(*(c.connect() for c in conns),
                             return_exceptions=True)
        free = list(conns)
        inflight: set = set()

        async def one(conn, target, kind, body):
            err = shed = False
            reason = None
            try:
                out = await conn.post(body)
                err, shed, reason = _classify(out)
            except LoadgenError:
                err = True
            except Exception:  # noqa: BLE001 — a client bug must not
                err = True     # break the accounting identity
            latency = time.monotonic() - target
            if shed:
                observe_shed_latency(registry, kind, latency)
            elif reason is not None:
                observe_rejection_latency(registry, kind, latency)
            else:
                observe_request_latency(registry, kind, latency)
            stats["sent"] += 1
            kinds[kind] = kinds.get(kind, 0) + 1
            if err:
                stats["errors"] += 1
            if shed:
                stats["shed"] += 1
            if reason is not None:
                stats["rejected"] += 1
                rejections[reason] = rejections.get(reason, 0) + 1
            free.append(conn)

        start = time.monotonic() + 0.02
        for offset, (kind, body) in zip(schedule, requests):
            target = start + offset
            delay = target - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            # open-loop contract: a slot with no free connection is
            # counted and DROPPED — deferring it would serialize sends
            # behind server latency, which is exactly coordinated
            # omission
            if not free:
                stats["missed"] += 1
                continue
            conn = free.pop()
            task = asyncio.ensure_future(one(conn, target, kind, body))
            inflight.add(task)
            task.add_done_callback(inflight.discard)
        if inflight:
            await asyncio.wait(inflight, timeout=self.timeout + 5.0)
        for conn in conns:
            conn.close()

    def sweep(self, rates, duration: float = 5.0,
              arrivals: str = "fixed",
              max_error_rate: float = MAX_ERROR_RATE,
              min_achieved_frac: float = MIN_ACHIEVED_FRAC) -> dict:
        """Run the schedule at each offered rate (ascending) and report
        the highest rate the server sustained: errors under
        max_error_rate and ≥ min_achieved_frac of the schedule actually
        delivered.  A typed busy response is graceful but still NOT
        delivered work, so shed slots count against sustainability —
        and typed mempool rejections are treated exactly the same way
        (admission control refusing work is not work done), without
        ever inflating the error rate."""
        results = [self.run(r, duration, arrivals)
                   for r in sorted(rates)]
        sustainable = None
        for rep in results:
            offered = rep["offeredRate"]
            delivered = rep.get("delivered", rep["sent"]) / rep["scheduled"] \
                if rep["scheduled"] else 0.0
            if (rep["errorRate"] <= max_error_rate
                    and delivered >= min_achieved_frac):
                sustainable = offered
        return {
            "arrivals": arrivals,
            "durationSeconds": duration,
            "senders": self.senders if self.payload == "tx" else None,
            "maxSustainableRate": sustainable,
            "maxErrorRate": max_error_rate,
            "minAchievedFrac": min_achieved_frac,
            "rates": results,
        }


# ---------------------------------------------------------------------------
# legacy closed-loop generator (measures inclusion throughput, NOT
# serving tail — see module docstring)


def _rpc(url: str, method: str, *params):
    payload = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                          "params": list(params)}).encode()
    req = urllib.request.Request(
        url, data=payload, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        out = json.loads(resp.read())
    if "error" in out:
        raise RuntimeError(f"{method}: {out['error']}")
    return out["result"]


def run_load(url: str, secret: int, num_txs: int,
             mode: str = "transfer") -> dict:
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    chain_id = int(_rpc(url, "eth_chainId"), 16)
    nonce = int(_rpc(url, "eth_getTransactionCount",
                     "0x" + sender.hex(), "pending"), 16)
    target = bytes.fromhex("aa" * 20)
    gas_limit = 21000
    data = b""
    if mode == "sstore":
        deploy = Transaction(
            tx_type=TYPE_DYNAMIC_FEE, chain_id=chain_id, nonce=nonce,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=200_000, to=b"",
            data=bytes.fromhex(SSTORE_INITCODE)).sign(secret)
        _rpc(url, "eth_sendRawTransaction",
             "0x" + deploy.encode_canonical().hex())
        receipt = None
        deadline = time.time() + 30
        while receipt is None and time.time() < deadline:
            receipt = _rpc(url, "eth_getTransactionReceipt",
                           "0x" + deploy.hash.hex())
            time.sleep(0.2)
        if receipt is None:
            raise RuntimeError("deploy was not mined")
        if receipt["status"] != "0x1":
            raise RuntimeError("counter deploy reverted")
        target = bytes.fromhex(receipt["contractAddress"][2:])
        gas_limit = 100_000
        nonce += 1

    start_block = int(_rpc(url, "eth_blockNumber"), 16)
    t0 = time.time()
    for i in range(num_txs):
        tx = Transaction(
            tx_type=TYPE_DYNAMIC_FEE, chain_id=chain_id, nonce=nonce + i,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=gas_limit, to=target, value=1 if mode == "transfer"
            else 0, data=data).sign(secret)
        _rpc(url, "eth_sendRawTransaction",
             "0x" + tx.encode_canonical().hex())
    submit_time = time.time() - t0

    # wait for full inclusion (incremental scan: only NEW blocks per poll)
    deadline = time.time() + 120
    included = 0
    gas_used = 0
    scanned = start_block
    while time.time() < deadline:
        head = int(_rpc(url, "eth_blockNumber"), 16)
        for n in range(scanned + 1, head + 1):
            blk = _rpc(url, "eth_getBlockByNumber", hex(n), False)
            included += len(blk["transactions"])
            gas_used += int(blk["gasUsed"], 16)
        scanned = max(scanned, head)
        if included >= num_txs:  # the sstore deploy mines BEFORE start_block
            break
        time.sleep(0.3)
    total = time.time() - t0
    return {
        "mode": mode,
        "txs_submitted": num_txs,
        "txs_included": included,
        "submit_tps": round(num_txs / submit_time, 1),
        "end_to_end_tps": round(included / total, 1),
        "mgas_per_s": round(gas_used / total / 1e6, 3),
        "wall_s": round(total, 2),
    }


# ---------------------------------------------------------------------------
# reorg chaos driver (docs/CHAIN_RESILIENCE.md "The reorg storm")


class ReorgDriver:
    """Periodic depth-k fork-choice flips while open-loop load runs —
    the reorg-storm half of the chaos harness (tests/test_reorg_chaos.py
    soak; reusable by future batteries).

    Works over the engine API alone: each flip records the current tip,
    rolls the head back `depth` blocks with engine_forkchoiceUpdatedV3
    (orphaning the top of the chain and re-injecting its txs), then
    re-adopts the recorded tip.  Blocks produced between the two legs
    turn the rollback into a genuine sibling-branch reorg.  `call` is
    any `call(method, *params) -> result` reaching an engine-authorized
    endpoint: tests pass an in-process dispatcher; the CLI builds a
    JWT-bearing HTTP caller from --engine-url/--jwt-hex."""

    def __init__(self, call, interval: float = 1.0, depth: int = 2):
        self.call = call
        self.interval = interval
        self.depth = max(1, int(depth))
        self.flips = 0
        self.errors = 0
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def flip_once(self) -> bool:
        """One rollback + re-adopt pair; returns False while the chain
        is still shorter than the flip depth."""
        head = self.call("eth_getBlockByNumber", "latest", False)
        number = int(head["number"], 16)
        if number < self.depth:
            return False
        ancestor = self.call("eth_getBlockByNumber",
                             hex(number - self.depth), False)
        zero = "0x" + "00" * 32
        for target in (ancestor["hash"], head["hash"]):
            self.call("engine_forkchoiceUpdatedV3",
                      {"headBlockHash": target, "safeBlockHash": zero,
                       "finalizedBlockHash": zero})
        self.flips += 1
        return True

    def _loop(self):
        while not self._stop.wait(self.interval):
            try:
                self.flip_once()
            except Exception:  # noqa: BLE001 — the storm must outlive
                self.errors += 1  # transient RPC errors under load

    def start(self) -> "ReorgDriver":
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()
        return self

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None

    def stats(self) -> dict:
        return {"flips": self.flips, "errors": self.errors,
                "intervalSeconds": self.interval, "depth": self.depth}


def engine_caller(url: str, jwt_secret: bytes):
    """call(method, *params) against an engine-authorized endpoint,
    minting a fresh JWT per request (the iat claim must stay within
    the server's drift window across a long storm)."""
    from ..rpc.engine import jwt_encode

    def call(method, *params):
        payload = json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                              "params": list(params)}).encode()
        req = urllib.request.Request(
            url, data=payload,
            headers={"Content-Type": "application/json",
                     "Authorization": "Bearer " + jwt_encode(jwt_secret)})
        with urllib.request.urlopen(req, timeout=30) as resp:
            out = json.loads(resp.read())
        if "error" in out:
            raise RuntimeError(f"{method}: {out['error']}")
        return out["result"]

    return call


# ---------------------------------------------------------------------------
# CLI — open-loop when --rate/--rates given, legacy closed-loop otherwise


def main(argv=None):
    parser = argparse.ArgumentParser(prog="ethrex-tpu-loadgen")
    parser.add_argument("--url", default="http://127.0.0.1:8545")
    parser.add_argument("--key", default=hex(DEFAULT_KEY),
                        help="funded root key (hex) used to fund the "
                             "simulated senders")
    # open-loop flags
    parser.add_argument("--rate", type=float, default=0.0,
                        help="open-loop offered rate (req/s); 0 = use "
                             "--rates or the legacy closed-loop path")
    parser.add_argument("--rates", default="",
                        help="comma-separated offered rates for a sweep "
                             "(e.g. 10,25,50)")
    parser.add_argument("--duration", type=float, default=5.0,
                        help="seconds per offered rate")
    parser.add_argument("--arrivals", choices=("fixed", "poisson"),
                        default="fixed")
    parser.add_argument("--senders", type=int, default=8,
                        help="simulated funded sender accounts")
    parser.add_argument("--token-frac", type=float, default=0.25,
                        dest="token_frac",
                        help="fraction of requests that call the token "
                             "template instead of a plain transfer")
    parser.add_argument("--workers", type=int, default=64,
                        help="persistent connections = max concurrent "
                             "in-flight requests; a full pool at a send "
                             "slot counts a miss")
    parser.add_argument("--timeout", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--payload", choices=("tx", "ping", "batch"),
                        default="tx",
                        help="tx = signed transfers/token calls (needs a "
                             "funded --key); ping = eth_blockNumber "
                             "only; batch = JSON-RPC arrays of "
                             "--batch-size eth_blockNumber calls")
    parser.add_argument("--batch-size", type=int, default=8,
                        dest="batch_size",
                        help="entries per JSON-RPC batch array when "
                             "--payload batch")
    # reorg-storm chaos driver (depth-k fork-choice flips during load)
    parser.add_argument("--reorg-interval", type=float, default=0.0,
                        dest="reorg_interval",
                        help="seconds between depth-k fork-choice flips "
                             "while the load runs (0 = off); needs "
                             "--engine-url and --jwt-hex")
    parser.add_argument("--reorg-depth", type=int, default=2,
                        dest="reorg_depth",
                        help="blocks rolled back per flip")
    parser.add_argument("--engine-url", default="",
                        dest="engine_url",
                        help="engine-authorized endpoint the reorg "
                             "driver flips through")
    parser.add_argument("--jwt-hex", default="", dest="jwt_hex",
                        help="hex JWT secret for --engine-url")
    # legacy closed-loop flags
    parser.add_argument("--txs", type=int, default=200)
    parser.add_argument("--mode", choices=("transfer", "sstore"),
                        default="transfer")
    args = parser.parse_args(argv)

    rates = [float(r) for r in args.rates.split(",") if r.strip()]
    if args.rate > 0:
        rates.append(args.rate)
    driver = None
    if args.reorg_interval > 0:
        if not args.engine_url or not args.jwt_hex:
            parser.error("--reorg-interval needs --engine-url and "
                         "--jwt-hex")
        driver = ReorgDriver(
            engine_caller(args.engine_url, bytes.fromhex(args.jwt_hex)),
            interval=args.reorg_interval, depth=args.reorg_depth).start()
    try:
        if rates:
            harness = Harness(args.url, key=int(args.key, 16),
                              senders=args.senders,
                              token_frac=args.token_frac,
                              workers=args.workers, timeout=args.timeout,
                              seed=args.seed, payload=args.payload,
                              batch_size=args.batch_size)
            harness.setup()
            if len(rates) == 1:
                result = harness.run(rates[0], args.duration,
                                     args.arrivals)
            else:
                result = harness.sweep(rates, args.duration,
                                       args.arrivals)
        else:
            result = run_load(args.url, int(args.key, 16), args.txs,
                              args.mode)
    finally:
        if driver is not None:
            driver.stop()
    if driver is not None:
        result["reorgStorm"] = driver.stats()
    print(json.dumps(result, indent=2))


if __name__ == "__main__":
    main()
