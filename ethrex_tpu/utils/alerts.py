"""Declarative SLO/alert engine over the time-series windows.

Rules are plain data: a signal callable (engine, node) -> float | None,
a threshold, and hysteresis counts.  The state machine per rule is

    ok -> pending -> firing -> ok

with two flap guards: a rule must breach `for_count` consecutive
evaluations before it fires (a single bad sample never pages), and must
clear `resolve_count` consecutive evaluations before it resolves (a
boundary-hugging series cannot strobe).  A signal returning None (cold
start, no samples, no data in window) is always treated as not-breached.

Burn-rate severities follow the multi-window convention: each SLO
yields a "page" rule (short window, high threshold — fast burn) and a
"warn" rule (long window, lower threshold — slow burn).  Transitions
are logged, counted (alert_transitions_total / alerts_firing), kept in
a bounded history ring, and surfaced through the ethrex_alerts RPC, the
ethrex_health alerts section, and the monitor panel.

evaluate() never raises — a broken rule records its error on the rule
state and evaluation moves on.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import threading
import time
from typing import Callable

from . import timeseries
from .metrics import record_alert_transition, record_alerts_firing

log = logging.getLogger("ethrex_tpu.alerts")

HISTORY = 64


@dataclasses.dataclass
class AlertRule:
    name: str
    severity: str                      # "page" | "warn"
    signal: Callable                   # (engine, node) -> float | None
    threshold: float
    window: float = 60.0               # informational: the signal's window
    for_count: int = 2                 # consecutive breaches before firing
    resolve_count: int = 2             # consecutive clears before resolving
    description: str = ""
    runbook: str = ""
    below: bool = False                # breach when value <= threshold
                                       # (throughput floors, not ceilings)


class _RuleState:
    __slots__ = ("state", "breach_streak", "ok_streak", "since",
                 "last_value", "last_error")

    def __init__(self):
        self.state = "ok"
        self.breach_streak = 0
        self.ok_streak = 0
        self.since = None
        self.last_value = None
        self.last_error = None


class AlertEngine:
    """Evaluates a rule set against a TimeSeriesEngine; never raises."""

    def __init__(self, engine=None, rules=(), node=None,
                 history: int = HISTORY):
        self.engine = engine if engine is not None else timeseries.ENGINE
        self.node = node
        self.rules = list(rules)
        self.states = {r.name: _RuleState() for r in self.rules}
        self.history: collections.deque = collections.deque(maxlen=history)
        self.transitions_total = 0
        self.eval_errors = 0
        self.lock = threading.Lock()

    # ------------------------------------------------------------------
    def evaluate(self, now: float | None = None):
        try:
            self._evaluate(time.time() if now is None else now)
        except Exception:
            self.eval_errors += 1

    def _evaluate(self, now: float):
        with self.lock:
            for rule in self.rules:
                st = self.states[rule.name]
                try:
                    value = rule.signal(self.engine, self.node)
                    st.last_error = None
                except Exception as exc:
                    value = None
                    st.last_error = f"{type(exc).__name__}: {exc}"
                    self.eval_errors += 1
                st.last_value = value
                if rule.below:
                    breached = value is not None and value <= rule.threshold
                else:
                    breached = value is not None and value >= rule.threshold
                if breached:
                    st.breach_streak += 1
                    st.ok_streak = 0
                    if st.state != "firing":
                        if st.breach_streak >= rule.for_count:
                            self._transition(rule, st, "firing", now, value)
                        else:
                            st.state = "pending"
                else:
                    st.ok_streak += 1
                    st.breach_streak = 0
                    if st.state == "firing":
                        if st.ok_streak >= rule.resolve_count:
                            self._transition(rule, st, "resolved", now, value)
                    elif st.state == "pending":
                        st.state = "ok"
            firing = sum(1 for s in self.states.values()
                         if s.state == "firing")
        record_alerts_firing(firing)

    def _transition(self, rule, st, event, now, value):
        st.state = "firing" if event == "firing" else "ok"
        st.since = now
        self.transitions_total += 1
        self.history.append({
            "rule": rule.name, "severity": rule.severity, "event": event,
            "ts": now, "value": value})
        record_alert_transition(rule.name, event)
        log.log(logging.WARNING if event == "firing" else logging.INFO,
                "alert %s: %s [%s] value=%s threshold=%s",
                event, rule.name, rule.severity, value, rule.threshold)

    # ------------------------------------------------------------------
    def _alert_json(self, rule, st):
        return {"name": rule.name, "severity": rule.severity,
                "state": st.state, "value": st.last_value,
                "threshold": rule.threshold, "window": rule.window,
                "below": rule.below,
                "since": st.since, "description": rule.description,
                "runbook": rule.runbook, "error": st.last_error}

    def active(self) -> list:
        with self.lock:
            return [self._alert_json(r, self.states[r.name])
                    for r in self.rules
                    if self.states[r.name].state == "firing"]

    def to_json(self) -> dict:
        with self.lock:
            rules = [self._alert_json(r, self.states[r.name])
                     for r in self.rules]
            recent = list(self.history)
        return {"rules": rules,
                "active": [r for r in rules if r["state"] == "firing"],
                "recent": recent,
                "transitions": self.transitions_total,
                "evalErrors": self.eval_errors}


# ---------------------------------------------------------------------------
# signal helpers (each returns (engine, node) -> float | None)

def rate_signal(counter: str, window: float = 60.0):
    return lambda eng, node: eng.rate(counter, window=window)


def p95_signal(histogram: str, window: float = 300.0):
    def sig(eng, node):
        p = eng.percentiles(histogram, qs=(0.95,), window=window)
        return None if p is None else p.get("p95")
    return sig


def p99_signal(histogram: str, window: float = 300.0):
    def sig(eng, node):
        p = eng.percentiles(histogram, qs=(0.99,), window=window)
        return None if p is None else p.get("p99")
    return sig


def gauge_signal(gauge: str):
    """Latest sampled value of a plain gauge (None before the first
    sample, so a node that never touched the subsystem never alerts)."""
    return lambda eng, node: eng.gauge(gauge)


def component_p95_signal(histogram: str, component: str,
                         window: float = 300.0):
    """p95 of ONE component series of a labelled histogram — e.g. the
    queue-wait leg of batch_critical_path_seconds.  None until that
    component has samples in the window, so nodes that never settle a
    batch (or predate critical-path attribution) never alert."""
    def sig(eng, node):
        p = eng.percentiles(histogram, qs=(0.95,), window=window,
                            labels={"component": component})
        return None if p is None else p.get("p95")
    return sig


def settlement_lag_signal(eng, node):
    """Batches committed but not yet verified on the L1."""
    latest = eng.gauge("ethrex_l2_latest_batch")
    if latest is None:
        return None
    verified = eng.gauge("ethrex_l2_last_verified_batch") or 0.0
    return latest - verified


def aggregation_lag_signal(eng, node):
    """Batches past the last aggregated settlement.  None until the first
    aggregation lands (`ethrex_l2_last_aggregated_batch` is only sampled
    by the aggregation path), so nodes running per-batch settlement —
    or no L2 at all — never alert."""
    aggregated = eng.gauge("ethrex_l2_last_aggregated_batch")
    if aggregated is None:
        return None
    latest = eng.gauge("ethrex_l2_latest_batch")
    if latest is None:
        return None
    return latest - aggregated


def snap_stall_signal(window: float = 60.0):
    """Snap-sync progress rate, armed only while a sync is actually
    running (`snap_sync_phase` gauge is 1=accounts or 2=healing).  Idle
    nodes and completed syncs return None so they never alert; a running
    sync whose range throughput collapses to ~0 is stalled — usually a
    partition (see snap_sync_paused) or every peer refusing the pivot."""
    def sig(eng, node):
        phase = eng.gauge("snap_sync_phase")
        if phase is None or phase not in (1.0, 2.0):
            return None
        return eng.rate("snap_ranges_synced_total", window=window)
    return sig


def actor_stall_signal(eng, node):
    """Seconds since the least-recently-successful sequencer actor made
    progress (no-progress watchdog; every healthy actor iteration —
    including an idle no-op — counts as a success)."""
    seq = getattr(node, "sequencer", None)
    if seq is None or not getattr(seq, "health", None):
        return None
    now = time.time()
    started = getattr(seq, "started_at", None)
    worst = None
    for st in seq.health.values():
        last = getattr(st, "last_success", None)
        if last is None:
            if (not getattr(st, "runs", 0)
                    and not getattr(st, "consecutive_failures", 0)):
                continue            # actor never scheduled yet
            last = started
        if last is None:
            continue
        stall = now - last
        if worst is None or stall > worst:
            worst = stall
    return worst


def inclusion_backlog_signal(eng, node):
    """Estimated seconds to drain the mempool admission backlog at the
    chain path's current inclusion rate (perf/chain_path.py).  None
    while the backlog is empty or on nodes that never produce blocks
    (L1-only followers) — armed but silent, never false-paging."""
    try:
        from ..perf.chain_path import CHAIN_PATH

        return CHAIN_PATH.backlog_seconds()
    except Exception:  # noqa: BLE001 — a signal must never raise
        return None


def producer_stall_signal(eng, node):
    """Seconds since the last sealed block while admitted transactions
    wait in the pool.  None when the pool is empty or before this node's
    first block (an idle or L1-only node is not a stalled producer)."""
    try:
        from ..perf.chain_path import CHAIN_PATH

        return CHAIN_PATH.producer_stall_seconds()
    except Exception:  # noqa: BLE001 — a signal must never raise
        return None


def sequencer_leaderless_signal(eng, node):
    """1.0 when, from this node's view, NO sequencer holds a live leader
    lease; 0.0 while somebody (us included) does.  None unless this node
    runs HA leader election (`--ha-role`), so single-sequencer deploys
    never arm the rule (docs/SEQUENCER_HA.md)."""
    seq = getattr(node, "sequencer", None)
    leadership = getattr(seq, "leadership", None)
    if leadership is None:
        return None
    return 1.0 if leadership.leaderless() else 0.0


def default_rules(node=None) -> list:
    """The stock SLO set (documented in docs/OBSERVABILITY.md)."""
    mk = AlertRule
    return [
        # batch proving latency (tail) — fast/slow burn over p95
        mk("batch_proving_p95:page", "page",
           p95_signal("batch_proving_seconds", window=120.0), 480.0,
           window=120.0, for_count=2, resolve_count=3,
           description="Batch proof p95 over 2m exceeds 480s",
           runbook="Check prover fleet health (ethrex_health l2.prover) "
                   "and TPU compile churn (prover_kernel_retraces_total)."),
        mk("batch_proving_p95:warn", "warn",
           p95_signal("batch_proving_seconds", window=600.0), 120.0,
           window=600.0, for_count=3, resolve_count=3,
           description="Batch proof p95 over 10m exceeds 120s",
           runbook="Inspect prover_stage_seconds for the regressing stage."),
        # prover runtime degradation — the mesh ladder demoting provers
        # (OOM / device loss) trades throughput for liveness; any
        # sustained rate means the fleet is running under capacity
        mk("prover_runtime_degraded:page", "page",
           rate_signal("prover_mesh_degradations_count", window=60.0),
           0.1, window=60.0, for_count=2, resolve_count=3,
           description="Mesh degradations above 0.1/s over 1m",
           runbook="Provers are repeatedly OOMing or losing devices and "
                   "falling down the ladder; see docs/PROVER_RESILIENCE.md "
                   "'Runtime failures' and ethrex_health l2.prover.runtime."),
        mk("prover_runtime_degraded:warn", "warn",
           rate_signal("prover_mesh_degradations_count", window=600.0),
           0.002, window=600.0, for_count=2, resolve_count=3,
           description="Any mesh degradation in the last 10m",
           runbook="A prover demoted its mesh; check "
                   "prover_oom_retries_total vs the memory gate headroom "
                   "(ETHREX_MEM_GATE_HEADROOM, docs/PROVER_RESILIENCE.md)."),
        # prover lease-loss / reassignment rate
        mk("prover_reassignment_rate:page", "page",
           rate_signal("proof_reassignments_total", window=60.0), 0.2,
           window=60.0, for_count=2, resolve_count=3,
           description="Lease losses/rejections above 0.2/s over 1m",
           runbook="Provers are dying or submitting bad proofs; check "
                   "quarantined_batches and the coordinator log."),
        mk("prover_reassignment_rate:warn", "warn",
           rate_signal("proof_reassignments_total", window=600.0), 0.02,
           window=600.0, for_count=3, resolve_count=3,
           description="Lease losses/rejections above 0.02/s over 10m",
           runbook="A prover endpoint is flapping; check breaker metrics."),
        # store corruption — any corruption warrants a look
        mk("store_corruption_rate:page", "page",
           rate_signal("store_corruption_total", window=60.0), 0.1,
           window=60.0, for_count=2, resolve_count=3,
           description="Checksum failures above 0.1/s over 1m",
           runbook="Disk is actively corrupting records; stop writes and "
                   "inspect backend.quarantined."),
        mk("store_corruption_rate:warn", "warn",
           rate_signal("store_corruption_total", window=600.0), 0.001,
           window=600.0, for_count=2, resolve_count=3,
           description="Any checksum failure in the last 10m",
           runbook="See docs/STORAGE_RESILIENCE.md quarantine flow."),
        # execution-chain reorg depth — a deep reorg orphans many
        # blocks at once (consensus trouble or a hostile fork); a
        # sustained multi-block reorg rate means the chain is churning
        mk("deep_reorg:page", "page",
           p95_signal("chain_reorg_depth", window=120.0), 5.0,
           window=120.0, for_count=2, resolve_count=3,
           description="Reorg depth p95 over 2m at or above 5 blocks",
           runbook="A deep reorg just orphaned 5+ blocks; check the "
                   "chain section of ethrex_health (reinjected/"
                   "evictions) and docs/CHAIN_RESILIENCE.md."),
        mk("deep_reorg:warn", "warn",
           p95_signal("chain_reorg_depth", window=600.0), 2.0,
           window=600.0, for_count=2, resolve_count=3,
           description="Reorg depth p95 over 10m at or above 2 blocks",
           runbook="Multi-block reorgs are recurring; check peer "
                   "health and mempool_reinjections_total churn "
                   "(docs/CHAIN_RESILIENCE.md)."),
        # L1 settlement lag (gauge-derived; windows are evaluation-paced)
        mk("l1_settlement_lag:page", "page",
           settlement_lag_signal, 20.0,
           window=60.0, for_count=3, resolve_count=3,
           description="20+ committed batches await L1 verification",
           runbook="Verifier is stalled or L1 is rejecting proofs; check "
                   "l2.l1 in ethrex_health."),
        mk("l1_settlement_lag:warn", "warn",
           settlement_lag_signal, 5.0,
           window=600.0, for_count=5, resolve_count=3,
           description="5+ committed batches await L1 verification",
           runbook="Settlement is falling behind proving; check "
                   "send_proofs actor latency."),
        # aggregation lag (gauge-derived like settlement lag, but
        # anchored to the last AGGREGATED settlement: only armed once an
        # aggregation has landed, so per-batch-settling nodes stay quiet)
        mk("aggregation_lag:page", "page",
           aggregation_lag_signal, 48.0,
           window=60.0, for_count=3, resolve_count=3,
           description="48+ batches produced past the last aggregated "
                       "settlement",
           runbook="The aggregator stalled or its proofs are being "
                   "rejected; check l2.aggregation.lastError in "
                   "ethrex_health and docs/AGGREGATION.md."),
        mk("aggregation_lag:warn", "warn",
           aggregation_lag_signal, 16.0,
           window=600.0, for_count=5, resolve_count=3,
           description="16+ batches produced past the last aggregated "
                       "settlement",
           runbook="Aggregation is falling behind proving; check the "
                   "aggregate_proofs actor latency and whether the run "
                   "keeps failing its pre-settlement audit."),
        # critical-path queue-wait — batches spending their lifecycle
        # WAITING for a prover while the fleet reports idle capacity is
        # a scheduler bug, not a capacity problem: cross-check
        # scheduler_queue_depth and liveAssignments in ethrex_health
        # (docs/OBSERVABILITY.md "Distributed tracing")
        mk("batch_queue_wait_p95:page", "page",
           component_p95_signal("batch_critical_path_seconds",
                                "queue-wait", window=120.0), 240.0,
           window=120.0, for_count=2, resolve_count=3,
           description="Queue-wait leg of the batch critical path p95 "
                       "over 2m exceeds 240s",
           runbook="Batches sit unassigned while provers poll: check "
                   "scheduler_queue_depth vs l2.prover.liveAssignments "
                   "in ethrex_health, the hedging deadline "
                   "(docs/AGGREGATION.md), and "
                   "ethrex_trace_criticalPath for the dominated trace."),
        mk("batch_queue_wait_p95:warn", "warn",
           component_p95_signal("batch_critical_path_seconds",
                                "queue-wait", window=600.0), 60.0,
           window=600.0, for_count=3, resolve_count=3,
           description="Queue-wait leg of the batch critical path p95 "
                       "over 10m exceeds 60s",
           runbook="Queue time dominating proving time usually means "
                   "too few provers for the batch rate or a cold fleet "
                   "being deferred; see prover_cold_deferrals_total."),
        # chain-path inclusion backlog — the admission stage queue is
        # deeper than the producer can drain (perf/chain_path.py);
        # None on empty pools and L1-only nodes keeps them silent
        mk("inclusion_backlog:page", "page",
           inclusion_backlog_signal, 120.0,
           window=60.0, for_count=2, resolve_count=3,
           description="Mempool backlog needs 120s+ to drain at the "
                       "current inclusion rate",
           runbook="Offered load exceeds chain-path capacity: check "
                   "ethrex_chainPath (explain.bottleneck) and "
                   "block_inclusion_tps vs the admission arrivalRate; "
                   "docs/OBSERVABILITY.md 'Chain-path telemetry'."),
        mk("inclusion_backlog:warn", "warn",
           inclusion_backlog_signal, 20.0,
           window=60.0, for_count=3, resolve_count=3,
           description="Mempool backlog needs 20s+ to drain at the "
                       "current inclusion rate",
           runbook="Sustained arrival/service imbalance; compare the "
                   "payload stage spans (ethrex_perf) against "
                   "ethrex_chainPath's queue rates "
                   "(docs/OBSERVABILITY.md 'Chain-path telemetry')."),
        # chain-path producer stall — txs wait but no block seals;
        # distinct from sequencer_stall (which watches actor loops):
        # this watches the block producer itself
        mk("producer_stall:page", "page",
           producer_stall_signal, 30.0,
           window=60.0, for_count=2, resolve_count=3,
           description="No block sealed for 30s while transactions "
                       "wait in the mempool",
           runbook="The producer loop is stuck or crashing: check the "
                   "node log for 'block production failed', the "
                   "producer stage in ethrex_chainPath, and the "
                   "payload stage spans in ethrex_perf."),
        mk("producer_stall:warn", "warn",
           producer_stall_signal, 10.0,
           window=60.0, for_count=2, resolve_count=3,
           description="No block sealed for 10s while transactions "
                       "wait in the mempool",
           runbook="Block time is stretching under load; check "
                   "build_payload execute/merkleize spans and prewarm "
                   "effectiveness (docs/OBSERVABILITY.md)."),
        # sequencer actor stall — no-progress watchdog
        mk("sequencer_stall:page", "page",
           actor_stall_signal, 120.0,
           window=60.0, for_count=2, resolve_count=3,
           description="A sequencer actor made no progress for 120s",
           runbook="Check l2.actors in ethrex_health for the stalled "
                   "actor and its lastError."),
        mk("sequencer_stall:warn", "warn",
           actor_stall_signal, 30.0,
           window=60.0, for_count=3, resolve_count=3,
           description="A sequencer actor made no progress for 30s",
           runbook="Often an L1 outage burning the transient budget; see "
                   "sequencer_transient_errors_total."),
        # sequencer loop latency (tail) — slow-burn warn only
        mk("sequencer_loop_p95:warn", "warn",
           p95_signal("sequencer_actor_seconds", window=600.0), 5.0,
           window=600.0, for_count=3, resolve_count=3,
           description="Actor loop p95 over 10m exceeds 5s",
           runbook="An actor body is slow; sequencer_actor_seconds is "
                   "labelled per actor."),
        # throughput floors (below=True: a gauge COLLAPSING is the
        # breach; None before the first sample never alerts, so L1-only
        # or idle nodes stay quiet — docs/PERFORMANCE.md)
        mk("l1_import_throughput_floor:warn", "warn",
           gauge_signal("l1_import_mgas_per_sec"), 0.1,
           window=60.0, for_count=3, resolve_count=3, below=True,
           description="L1 import throughput below 0.1 Mgas/s",
           runbook="Check block_import_stage_seconds (execute vs "
                   "merkleize vs store_write) and ethrex_perf's l1_import "
                   "attribution for the collapsed stage."),
        mk("prover_throughput_floor:warn", "warn",
           gauge_signal("prover_trace_cells_per_sec"), 1e4,
           window=60.0, for_count=3, resolve_count=3, below=True,
           description="Prover throughput below 10k trace cells/s",
           runbook="Compare ethrex_perf roofline utilization against "
                   "PERF.md section 5 (the last benchmark/ run on the "
                   "chip); a collapsed kernel usually means "
                   "recompilation churn or a fallen-back backend."),
        # RPC serving tail (the front-door SLO)
        mk("rpc_request_p99:page", "page",
           p99_signal("rpc_request_seconds", window=120.0), 2.0,
           window=120.0, for_count=2, resolve_count=3,
           description="JSON-RPC p99 over 2m exceeds 2s",
           runbook="Check rpc_queue_wait_seconds (thread-pool backlog) "
                   "vs rpc_request_seconds per method, and "
                   "rpc_inflight_requests for a concurrency pile-up."),
        mk("rpc_request_p99:warn", "warn",
           p99_signal("rpc_request_seconds", window=600.0), 0.5,
           window=600.0, for_count=3, resolve_count=3,
           description="JSON-RPC p99 over 10m exceeds 0.5s",
           runbook="Compare against a perf/loadgen.py sweep of this "
                   "node (no benchmark/ cell measures serving yet, "
                   "PERF.md section 7); see ethrex_health rpc section "
                   "for resets/EOFs under load."),
        # mempool saturation — sustained occupancy near capacity means
        # admissions are evicting (pool churn, dropped txs)
        mk("mempool_saturation:page", "page",
           gauge_signal("mempool_utilization"), 0.98,
           window=60.0, for_count=3, resolve_count=3,
           description="Mempool at 98%+ of capacity for 3 evals",
           runbook="Check ethrex_health mempoolFlow topSenders for a "
                   "spammer and mempool_evictions_by_reason for churn."),
        mk("mempool_saturation:warn", "warn",
           gauge_signal("mempool_utilization"), 0.8,
           window=300.0, for_count=3, resolve_count=3,
           description="Mempool above 80% of capacity",
           runbook="Inclusion is falling behind admission; compare "
                   "mempool_time_in_pool_seconds against the block "
                   "interval."),
        # RPC load shedding — admission control actively rejecting;
        # some shedding under a spike is the design working, sustained
        # shedding means capacity or a stuck shed level
        mk("rpc_shed_rate:page", "page",
           rate_signal("rpc_requests_shed_total", window=60.0), 5.0,
           window=60.0, for_count=2, resolve_count=3,
           description="RPC shedding above 5 req/s over 1m",
           runbook="Check ethrex_health rpc.overload for the shed level "
                   "and byReason split; see docs/OVERLOAD.md for the "
                   "level ladder and tuning knobs."),
        mk("rpc_shed_rate:warn", "warn",
           rate_signal("rpc_requests_shed_total", window=600.0), 0.5,
           window=600.0, for_count=3, resolve_count=3,
           description="RPC shedding above 0.5 req/s over 10m",
           runbook="Sustained low-grade shedding: compare "
                   "rpc_queue_wait_seconds against ETHREX_SHED_QUEUE_HIGH "
                   "and check mempool utilization (level>=2 couples "
                   "to it — docs/OVERLOAD.md)."),
        # snap-sync stall — armed only while a sync runs (phase gauge);
        # below=True: zero range throughput during an active sync is the
        # breach (docs/P2P_RESILIENCE.md)
        mk("snap_sync_stall:page", "page",
           snap_stall_signal(window=120.0), 0.01,
           window=120.0, for_count=3, resolve_count=3, below=True,
           description="Snap sync made no range progress for 3 evals",
           runbook="Check snap_sync_paused (partition: zero live peers) "
                   "and p2p_request_timeouts_total in ethrex_health p2p; "
                   "see docs/P2P_RESILIENCE.md."),
        mk("snap_sync_stall:warn", "warn",
           snap_stall_signal(window=300.0), 0.05,
           window=300.0, for_count=3, resolve_count=3, below=True,
           description="Snap sync range throughput below 0.05/s over 5m",
           runbook="Peers are slow or flapping; compare "
                   "p2p_peer_rtt_seconds per peer and "
                   "p2p_request_retries_total (docs/P2P_RESILIENCE.md)."),
        # sequencer leaderless — HA deploys only (signal is None without
        # --ha-role, so the pair never arms elsewhere).  The lease cell
        # on the L1 says nobody leads: nothing is producing blocks
        mk("sequencer_leaderless:page", "page",
           sequencer_leaderless_signal, 1.0,
           window=60.0, for_count=3, resolve_count=2,
           description="No sequencer holds the leader lease for 3 evals",
           runbook="Every candidate is failing acquire_lease or dying "
                   "during promotion; check leadership.lastError in "
                   "ethrex_ready on each standby and the L1 lease cell "
                   "(docs/SEQUENCER_HA.md runbook)."),
        mk("sequencer_leaderless:warn", "warn",
           sequencer_leaderless_signal, 1.0,
           window=60.0, for_count=2, resolve_count=2,
           description="Leader lease momentarily unheld (failover window)",
           runbook="Expected for up to one lease TTL during a failover; "
                   "sustained flapping means renewal starvation — check "
                   "leadership_transitions_total and the lease TTL vs L1 "
                   "latency (docs/SEQUENCER_HA.md)."),
        # mempool replacement churn — high replacement-by-fee rates are
        # a fee-bidding war or a deliberate repricing spam pattern
        mk("mempool_replacement_churn:page", "page",
           rate_signal("mempool_replacements_total", window=60.0), 10.0,
           window=60.0, for_count=2, resolve_count=3,
           description="Tx replacements above 10/s over 1m",
           runbook="Check mempoolFlow topSenders for a single sender "
                   "repricing in a loop; the >=10% bump rule makes this "
                   "expensive for them (docs/OVERLOAD.md)."),
        mk("mempool_replacement_churn:warn", "warn",
           rate_signal("mempool_replacements_total", window=600.0), 1.0,
           window=600.0, for_count=3, resolve_count=3,
           description="Tx replacements above 1/s over 10m",
           runbook="Persistent repricing churn; compare against base-fee "
                   "movement and the dynamic fee floor in "
                   "ethrex_health mempool stats."),
        # scaling autopsy (PR 18): the two regressor classes the sweep
        # names — idle devices and collective-dominated kernel walls.
        # Both gauges only exist after a prove (gauge_signal answers
        # None before the first sample), so L1-only nodes never fire.
        mk("prover_occupancy_floor:warn", "warn",
           gauge_signal("prover_device_occupancy"), 0.5,
           window=60.0, for_count=3, resolve_count=3, below=True,
           description="Device occupancy of the last proves below 50%",
           runbook="Read ethrex_perf's occupancy section (per-lane busy "
                   "vs idle) and the Perfetto device-lane view; a low "
                   "fraction with large idleGapSeconds means the mesh "
                   "slices are starved between jobs — the cross-batch "
                   "pipelining signal (docs/PERFORMANCE.md \"Reading "
                   "the scaling autopsy\")."),
        mk("prover_collective_share:warn", "warn",
           gauge_signal("prover_collective_wall_share"), 0.4,
           window=60.0, for_count=3, resolve_count=3,
           description="Estimated collective share of a kernel wall "
                       "above 40%",
           runbook="ethrex_perf's collectives section names the kernel "
                   "and op mix (all-gather vs all-reduce bytes); "
                   "re-check _MeshPlan's phase-boundary shardings "
                   "against PERF.md section 5 and the device trace of "
                   "a benchmark/ run."),
    ]


def build_default_engine(node=None, engine=None) -> AlertEngine:
    return AlertEngine(engine=engine, rules=default_rules(node), node=node)
