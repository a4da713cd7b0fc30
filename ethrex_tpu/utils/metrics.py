"""Prometheus metrics (parity target: the reference's ethrex-metrics crate,
crates/blockchain/metrics — text exposition format, stdlib only)."""

from __future__ import annotations

import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

# Fixed exponential buckets: 1ms * 2^i, spanning ~1ms .. ~524s.  One
# shared ladder keeps every latency histogram comparable and the
# exposition size bounded.
DEFAULT_BUCKETS = tuple(0.001 * 2 ** i for i in range(20))

# Label sets one family may hold (mirrors the profiler's MAX_KEYS):
# adversarial reject reasons or per-air labels cannot grow the
# exposition unboundedly; overflow series are dropped and counted in
# metrics_dropped_label_sets_total.
MAX_LABEL_SETS = 512


def _escape_label(value: str) -> str:
    return (str(value).replace("\\", "\\\\").replace('"', '\\"')
            .replace("\n", "\\n"))


def _fmt_labels(labels: tuple) -> str:
    return ",".join(f'{k}="{_escape_label(v)}"' for k, v in labels)


def _fmt_le(le) -> str:
    """Canonical shortest-float bucket boundary: coerce to float first so
    numpy scalars / ints / Decimals all render identically ("0.004",
    "5.0"), keeping le labels stable and joinable across scrapes."""
    return repr(float(le))


class _Histogram:
    """One named histogram family: per-labelset bucket counts + sum."""

    __slots__ = ("buckets", "series", "exemplars")

    def __init__(self, buckets):
        self.buckets = tuple(sorted(buckets))
        # labels tuple -> [bucket counts..., +Inf count, sum]
        self.series: dict[tuple, list] = {}
        # (labels tuple, bucket index) -> (trace_id, value): the most
        # recent exemplar observed into that bucket, rendered in
        # OpenMetrics exemplar syntax so a tail bucket links straight to
        # a loadable trace (docs/OBSERVABILITY.md "Distributed tracing")
        self.exemplars: dict[tuple, tuple] = {}

    def observe(self, value: float, labels: tuple, exemplar=None):
        row = self.series.get(labels)
        if row is None:
            row = [0] * (len(self.buckets) + 1) + [0.0]
            self.series[labels] = row
        landed = len(self.buckets)       # +Inf unless a bucket matches
        for i, le in enumerate(self.buckets):
            if value <= le:
                row[i] += 1
                landed = min(landed, i)
        row[len(self.buckets)] += 1      # +Inf == total count
        row[-1] += value                 # running sum
        if exemplar:
            self.exemplars[(labels, landed)] = (str(exemplar), float(value))


class Metrics:
    """Process-wide metric registry (counters + gauges + histograms)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.counters: dict[str, float] = {}
        self.gauges: dict[str, float] = {}
        # labelled counter families: name -> {sorted labels tuple: value}
        self.lcounters: dict[str, dict[tuple, float]] = {}
        # labelled gauge families: name -> {sorted labels tuple: value}
        self.lgauges: dict[str, dict[tuple, float]] = {}
        self.histograms: dict[str, _Histogram] = {}
        self.help: dict[str, str] = {}
        self.started = time.time()

    def inc(self, name: str, value: float = 1.0, help_text: str = ""):
        with self.lock:
            self.counters[name] = self.counters.get(name, 0.0) + value
            if help_text:
                self.help[name] = help_text

    def set(self, name: str, value: float, help_text: str = ""):
        with self.lock:
            self.gauges[name] = value
            if help_text:
                self.help[name] = help_text

    def _clamped(self, fam: dict, key: tuple) -> bool:
        """Caller holds the lock.  True when a NEW label set would push
        one family past MAX_LABEL_SETS: the series is dropped (existing
        series keep updating) and the drop is counted."""
        if key in fam or len(fam) < MAX_LABEL_SETS:
            return False
        self.counters["metrics_dropped_label_sets_total"] = \
            self.counters.get("metrics_dropped_label_sets_total", 0.0) + 1
        self.help.setdefault(
            "metrics_dropped_label_sets_total",
            "Series dropped by the per-family label-set clamp "
            "(MAX_LABEL_SETS) — cardinality protection against "
            "unbounded label values")
        return True

    def inc_labeled(self, name: str, labels: dict, value: float = 1.0,
                    help_text: str = ""):
        """Increment one series of a labelled counter family (e.g.
        per-reason mempool rejections)."""
        key = tuple(sorted((labels or {}).items()))
        with self.lock:
            fam = self.lcounters.setdefault(name, {})
            if self._clamped(fam, key):
                return
            fam[key] = fam.get(key, 0.0) + float(value)
            if help_text:
                self.help[name] = help_text

    def set_labeled(self, name: str, labels: dict, value: float,
                    help_text: str = ""):
        """Set one series of a labelled gauge family (e.g. per-kernel
        roofline gauges, prover_kernel_flops{air,stage})."""
        key = tuple(sorted((labels or {}).items()))
        with self.lock:
            fam = self.lgauges.setdefault(name, {})
            if self._clamped(fam, key):
                return
            fam[key] = float(value)
            if help_text:
                self.help[name] = help_text

    def observe(self, name: str, value: float,
                labels: dict | None = None, help_text: str = "",
                buckets=DEFAULT_BUCKETS, exemplar: str | None = None):
        """Record one observation into a labelled histogram.

        ``exemplar`` optionally attaches a trace ID to the bucket this
        value lands in, surfaced in OpenMetrics exemplar syntax by
        ``render`` so tail buckets link to a loadable trace."""
        key = tuple(sorted((labels or {}).items()))
        with self.lock:
            hist = self.histograms.get(name)
            if hist is None:
                hist = self.histograms[name] = _Histogram(buckets)
            if self._clamped(hist.series, key):
                return
            hist.observe(float(value), key, exemplar=exemplar)
            if help_text:
                self.help[name] = help_text

    def snapshot(self) -> dict:
        """Point-in-time plain-data copy of the registry (JSON-safe).

        The time-series engine samples this periodically; histogram rows
        keep the cumulative-per-bucket layout so window deltas can be
        taken bucket-by-bucket."""
        with self.lock:
            hists = {}
            for name, hist in self.histograms.items():
                nb = len(hist.buckets)
                hists[name] = {
                    "buckets": [float(b) for b in hist.buckets],
                    "series": [
                        {"labels": dict(labels),
                         "counts": [int(c) for c in row[:nb + 1]],
                         "sum": float(row[-1])}
                        for labels, row in hist.series.items()],
                }
            return {"ts": time.time(),
                    "counters": dict(self.counters),
                    "gauges": dict(self.gauges),
                    "labeled_counters": {
                        name: [{"labels": dict(labels), "value": value}
                               for labels, value in fam.items()]
                        for name, fam in self.lcounters.items()},
                    "labeled_gauges": {
                        name: [{"labels": dict(labels), "value": value}
                               for labels, value in fam.items()]
                        for name, fam in self.lgauges.items()},
                    "histograms": hists}

    def reset(self):
        """Drop every series and restart the uptime clock (test isolation
        and simulated process restarts)."""
        with self.lock:
            self.counters.clear()
            self.gauges.clear()
            self.lcounters.clear()
            self.lgauges.clear()
            self.histograms.clear()
            self.help.clear()
            self.started = time.time()

    def _render_histograms(self, lines: list):
        for name, hist in sorted(self.histograms.items()):
            if name in self.help:
                lines.append(f"# HELP {name} {self.help[name]}")
            lines.append(f"# TYPE {name} histogram")
            nb = len(hist.buckets)
            for labels, row in sorted(hist.series.items()):
                base = _fmt_labels(labels)
                sep = "," if base else ""

                def _ex(i, labels=labels):
                    # OpenMetrics exemplar: `... 5 # {trace_id="x"} 0.23`
                    # (no timestamp — keeps goldens and diffs stable)
                    ex = hist.exemplars.get((labels, i))
                    if not ex:
                        return ""
                    return (f' # {{trace_id="{_escape_label(ex[0])}"}}'
                            f" {ex[1]}")

                for i, le in enumerate(hist.buckets):
                    lines.append(
                        f'{name}_bucket{{{base}{sep}le="{_fmt_le(le)}"}} '
                        f"{row[i]}{_ex(i)}")
                lines.append(
                    f'{name}_bucket{{{base}{sep}le="+Inf"}} '
                    f"{row[nb]}{_ex(nb)}")
                brace = f"{{{base}}}" if base else ""
                lines.append(f"{name}_sum{brace} {row[-1]}")
                lines.append(f"{name}_count{brace} {row[nb]}")

    def render(self) -> str:
        with self.lock:
            lines = []
            for name, value in sorted(self.counters.items()):
                if name in self.help:
                    lines.append(f"# HELP {name} {self.help[name]}")
                lines.append(f"# TYPE {name} counter")
                lines.append(f"{name} {value}")
            for name, fam in sorted(self.lcounters.items()):
                if name in self.help:
                    lines.append(f"# HELP {name} {self.help[name]}")
                lines.append(f"# TYPE {name} counter")
                for labels, value in sorted(fam.items()):
                    lines.append(f"{name}{{{_fmt_labels(labels)}}} {value}")
            for name, value in sorted(self.gauges.items()):
                if name in self.help:
                    lines.append(f"# HELP {name} {self.help[name]}")
                lines.append(f"# TYPE {name} gauge")
                lines.append(f"{name} {value}")
            for name, fam in sorted(self.lgauges.items()):
                if name in self.help:
                    lines.append(f"# HELP {name} {self.help[name]}")
                lines.append(f"# TYPE {name} gauge")
                for labels, value in sorted(fam.items()):
                    lines.append(f"{name}{{{_fmt_labels(labels)}}} {value}")
            self._render_histograms(lines)
            lines.append("# TYPE process_uptime_seconds gauge")
            lines.append(
                f"process_uptime_seconds {time.time() - self.started}")
            return "\n".join(lines) + "\n"


METRICS = Metrics()  # global registry, like the reference's statics


def record_block(block, elapsed: float):
    METRICS.inc("ethrex_blocks_imported_total", 1,
                "Blocks imported through add_block")
    METRICS.inc("ethrex_gas_used_total", block.header.gas_used,
                "Cumulative gas executed")
    METRICS.inc("ethrex_transactions_total",
                len(block.body.transactions), "Transactions executed")
    METRICS.set("ethrex_head_block", block.header.number,
                "Current head block number")
    if elapsed > 0:
        METRICS.set("ethrex_last_block_mgas_per_s",
                    block.header.gas_used / elapsed / 1e6,
                    "Execution throughput of the last imported block")


def record_reassignment(batch_number: int, prover_type: str):
    METRICS.inc("proof_reassignments_total", 1,
                "Prover assignments re-issued after lease expiry or a "
                "rejected proof")


def record_lease_reclaim():
    METRICS.inc("prover_lease_reclaims_total", 1,
                "Leases moved to a restarted prover that presented the "
                "token its phase checkpoints record (no failure counted)")


def record_quarantine(count: int):
    METRICS.set("quarantined_batches", count,
                "Batches quarantined off their primary prover type onto "
                "the fallback backend")


def record_poll_error():
    METRICS.inc("prover_poll_errors_total", 1,
                "Prover client poll passes that failed on an endpoint")


def record_breaker(open_count: int, transition: bool = False):
    METRICS.set("prover_breaker_open", open_count,
                "Coordinator endpoints currently skipped by an open "
                "circuit breaker")
    if transition:
        METRICS.inc("prover_breaker_transitions_total", 1,
                    "Circuit breaker state transitions "
                    "(closed/open/half-open)")


def record_heartbeat():
    METRICS.inc("prover_heartbeats_total", 1,
                "Lease-extending heartbeats accepted by the coordinator")


def record_stale_submit():
    METRICS.inc("proof_stale_submits_total", 1,
                "Proof submits refused for missing or non-current lease "
                "tokens (left lease and failure state untouched)")


def record_submit_rejected():
    METRICS.inc("prover_submit_rejections_total", 1,
                "Proof submits the coordinator rejected at the "
                "application level (endpoint healthy; not a breaker "
                "failure)")


def record_hedged_assignment():
    METRICS.inc("prover_hedged_assignments_total", 1,
                "Speculative (hedged) re-assignments of straggler "
                "batches past the p99-derived deadline, plus "
                "work-stealing grants; first result wins, the loser's "
                "submit is a deduplicated no-op")


def record_cold_deferral():
    METRICS.inc("prover_cold_deferrals_total", 1,
                "Assignments withheld from provers that reported "
                "themselves cold (AOT kernels not yet hydrated) while "
                "recently-seen warm provers could absorb the queue")


def record_scheduler_queue_depth(depth: int):
    METRICS.set("scheduler_queue_depth", depth,
                "Provable batches awaiting an assignment at the last "
                "scheduling decision (unleased work the fleet has not "
                "picked up yet)")


def record_aggregation(count: int, last_batch: int):
    METRICS.inc("proofs_aggregated_total", count,
                "Per-batch proofs folded into aggregated settlement "
                "proofs (the N of every N-to-1 recursion step)")
    METRICS.set("aggregation_ratio", count,
                "Batch proofs covered by the most recent aggregated "
                "settlement (the amortization factor N of that L1 tx)")
    METRICS.set("ethrex_l2_last_aggregated_batch", last_batch,
                "Highest L2 batch settled through the aggregation "
                "pipeline (the aggregation-lag alert reads latest_batch "
                "minus this on nodes that aggregate)")


def record_l1_reorg():
    METRICS.inc("l1_reorgs_total", 1,
                "L1 reorgs detected through a settlement regression "
                "(last_committed/verified moved backwards)")


def record_chain_reorg(depth: int):
    METRICS.inc("chain_reorgs_total", 1,
                "Execution-chain reorgs applied by fork choice (at "
                "least one formerly-canonical block was orphaned)")
    _observe_safe("chain_reorg_depth", float(depth), None,
                  "Blocks orphaned per execution-chain reorg (the "
                  "deep_reorg alert pair reads the p95 of this)")


def record_mempool_reinjection():
    METRICS.inc("mempool_reinjections_total", 1,
                "Transactions re-injected into the mempool from "
                "orphaned blocks after a reorg (the typed reinjected "
                "path: admission fee-floor/sender-cap rules bypassed)")


def record_mempool_reorg_eviction(reason: str):
    METRICS.inc("mempool_reorg_evictions_total", 1,
                "Pool entries dropped by a reorg transition, any reason")
    METRICS.inc_labeled("mempool_reorg_evictions_by_reason",
                        {"reason": reason}, 1.0,
                        help_text="Reorg-driven mempool drops by reason "
                                  "(adopted = included on the winning "
                                  "branch, nonce_below_account / "
                                  "insufficient_balance = revalidation "
                                  "prunes, blob_unrecoverable = orphaned "
                                  "blob tx whose sidecar is gone)")


def record_txloc_stale_read():
    METRICS.inc("txloc_stale_reads_total", 1,
                "Transaction-location lookups that referenced a "
                "non-canonical block and were refused (verify-on-read "
                "guard; should stay 0 while fork choice prunes txlocs "
                "in the same write group)")


def record_recommit():
    METRICS.inc("batches_recommitted_total", 1,
                "Batches re-committed verbatim after an L1 reorg dropped "
                "their commitment")


def record_commit_adopted():
    METRICS.inc("l1_commits_adopted_total", 1,
                "Commit attempts adopted as success because the L1 "
                "already held a matching commitment (retry after a lost "
                "acknowledgment)")


def record_transient_error():
    METRICS.inc("sequencer_transient_errors_total", 1,
                "Sequencer actor iterations that failed with a transient "
                "(network-class) error and were retried with backoff")


def record_store_corruption():
    METRICS.inc("store_corruption_total", 1,
                "Persistent-store records whose checksum failed on read "
                "(detected, quarantined, never served)")


def record_store_rebuild():
    METRICS.inc("store_rebuilds_total", 1,
                "Quarantined records re-derived from surviving chain data "
                "(canonical index rebuilt by parent-hash walk)")


def record_journal_replay():
    METRICS.inc("store_journal_replays_total", 1,
                "Write-ahead journals replayed into the KV log on reopen "
                "(crash landed after the journal was durable)")


def record_journal_discard():
    METRICS.inc("store_journal_discards_total", 1,
                "Torn or corrupt write-ahead journals discarded on reopen "
                "(crash landed mid-journal; the batch never committed)")


def record_shutdown_duration(seconds: float):
    METRICS.set("shutdown_duration_seconds", seconds,
                "Wall-clock of the last coordinated shutdown drain")


def record_batch(batch_number: int, proving_time: float | None = None,
                 trace_id: str | None = None):
    METRICS.set("ethrex_l2_latest_batch", batch_number,
                "Latest committed L2 batch")
    if proving_time is not None:
        METRICS.set("ethrex_l2_batch_proving_seconds", proving_time,
                    "Wall-clock of the last batch proof")
        _observe_safe("batch_proving_seconds", proving_time, None,
                      "Batch proof wall-clock distribution (drives the "
                      "proving-latency p95 SLO)", exemplar=trace_id)


def record_verified_batch(batch_number: int):
    METRICS.set("ethrex_l2_last_verified_batch", batch_number,
                "Highest L2 batch verified on the L1 (settlement-lag "
                "alert reads latest_batch minus this)")


# sequencer HA roles encoded as a numeric gauge (docs/SEQUENCER_HA.md)
_ROLE_VALUES = {"follower": 0.0, "candidate": 1.0, "promoting": 2.0,
                "leader": 3.0}


def record_leadership_role(role: str):
    METRICS.set("sequencer_role", _ROLE_VALUES.get(role, -1.0),
                "Sequencer HA role of this node "
                "(0=follower 1=candidate 2=promoting 3=leader)")


def record_leadership_epoch(epoch: int):
    METRICS.set("leadership_epoch", float(epoch),
                "Fencing epoch of this node's current leader lease "
                "(monotonic across the deployment; stamped on every "
                "externally-visible sequencer write)")


def record_leadership_transition(frm: str, to: str):
    METRICS.inc_labeled("leadership_transitions_by_edge", {
                        "from": frm, "to": to}, 1,
                        help_text="Sequencer HA role transitions by "
                        "from/to edge (failover forensics)")
    METRICS.inc("leadership_transitions_total", 1,
                "Sequencer HA role transitions (unlabelled companion of "
                "leadership_transitions_by_edge; a churning value means "
                "the lease is flapping)")


def record_leadership_fenced():
    METRICS.inc("leadership_fenced_writes_total", 1,
                "Writes refused by the L1 or the rollup store because "
                "they carried a stale fencing epoch (a deposed zombie "
                "leader was stopped from corrupting shared state)")


def record_leadership_promotion(downtime: float):
    METRICS.set("leadership_promotion_downtime_seconds", downtime,
                "Wall-clock of the last follower-to-leader promotion "
                "(lease win to actors unparked: reconciliation + "
                "journal replay + prover-fleet re-home)")
    _observe_safe("leadership_promotion_seconds", downtime, None,
                  "Promotion wall-clock distribution (failover drill "
                  "budget: must stay within the lease ttl)")


def record_kernel_build(air: str, seconds: float, mesh: str = "none"):
    # labelled by mesh shape ("none", "4", "2x4") so mesh<->no-mesh
    # switches and sub-slice churn show up as distinct retrace series
    METRICS.inc_labeled("prover_kernel_retraces_total", {"mesh": mesh}, 1,
                        help_text="STARK phase-program builds (jit "
                        "retraces) by mesh shape: cache misses in the "
                        "in-process phase cache")
    _observe_safe("prover_kernel_build_seconds", seconds,
                  {"air": air, "mesh": mesh},
                  "Wall-clock to build+stage the jitted STARK phase "
                  "programs for one AIR shape (AOT compile included)")


def record_phase_compile(air: str, kernel: str, seconds: float,
                         mesh: str = "none", source: str = "compiled"):
    _observe_safe("prover_phase_compile_seconds", seconds,
                  {"air": air, "kernel": kernel, "mesh": mesh,
                   "source": source},
                  "Per-phase-program build wall by AIR, kernel, mesh "
                  "shape and source (compiled = fresh AOT lower+compile "
                  "— the cold-start baseline; deserialized = hydrated "
                  "from the on-disk executable cache)")


def record_phase_resume(phase: str):
    METRICS.inc("prover_phase_resumes_total", 1,
                "Completed prove phases skipped on restart: loaded from "
                "an on-disk phase checkpoint instead of re-proven")
    METRICS.inc_labeled("prover_phase_resumes_by_phase", {"phase": phase},
                        1, help_text="Checkpoint-resumed prove phases by "
                        "phase name (which phase a restarted prover "
                        "picked up from)")


def record_oom_retry(phase: str):
    METRICS.inc("prover_oom_retries_total", 1,
                "Prove phases retried after a transient runtime failure "
                "(XLA RESOURCE_EXHAUSTED or device loss) via the "
                "degraded-mesh fallback ladder")


def record_mesh_degradation(frm: str, to: str):
    METRICS.inc_labeled("prover_mesh_degradations_total",
                        {"from": frm, "to": to}, 1,
                        help_text="Mesh-layout downgrades by from/to "
                        "shape: the fallback ladder or the pre-prove "
                        "memory gate moved a prove to a smaller layout")
    METRICS.inc("prover_mesh_degradations_count", 1,
                "Mesh-layout downgrades (unlabelled companion of "
                "prover_mesh_degradations_total, feeds the "
                "prover_runtime_degraded alert rate)")


def record_nan_poison(phase: str):
    METRICS.inc("prover_nan_poison_total", 1,
                "Prove phases whose outputs were non-finite or out of "
                "field: the batch is quarantined immediately, never "
                "retried")


def record_mesh_devices(n: int):
    METRICS.set("prover_mesh_devices", float(n),
                help_text="Devices in the prover backend's JAX mesh "
                "(1 = unsharded single-device proving)")


def record_vm_parallelism(n: int):
    METRICS.set("prover_vm_circuits_parallel", float(n),
                help_text="Concurrent mesh slices used for the last "
                "batch's VM-circuit STARK proofs (1 = serial)")


def record_device_occupancy(fraction: float, idle_gap_seconds: float,
                            devices: int = 1):
    METRICS.set("prover_device_occupancy", float(fraction),
                help_text="Device-occupancy fraction of the last prove: "
                "busy-device-seconds / (mesh devices x wall).  The "
                "serial fallback on an N-device mesh is bounded by 1/N "
                "(prover_occupancy_floor alert)")
    METRICS.set("prover_device_idle_gap_seconds", float(idle_gap_seconds),
                help_text="Wall-clock of the last prove's VM batch "
                "during which no mesh slice was busy — the "
                "between-phase bubbles cross-batch pipelining would "
                "fill (ROADMAP item 1c)")


def record_jax_compile(seconds: float):
    METRICS.inc("jax_backend_compiles_total", 1,
                "XLA backend compilations observed via jax.monitoring")
    _observe_safe("jax_backend_compile_seconds", seconds, None,
                  "XLA backend compile wall-clock per compilation")


def record_jax_cache_event(hit: bool):
    if hit:
        METRICS.inc("jax_compilation_cache_hits_total", 1,
                    "Persistent XLA compilation-cache hits")
    else:
        METRICS.inc("jax_compilation_cache_misses_total", 1,
                    "Persistent XLA compilation-cache misses")


def record_jax_device_memory(bytes_in_use: float, peak_bytes: float):
    METRICS.set("jax_device_bytes_in_use", bytes_in_use,
                "Accelerator memory currently allocated, summed over "
                "local devices")
    METRICS.set("jax_device_peak_bytes_in_use", peak_bytes,
                "Peak accelerator memory allocated, summed over local "
                "devices")


def record_jax_live_arrays(count: float):
    METRICS.set("jax_live_arrays", count,
                "Live JAX arrays currently tracked by the runtime")


def record_telemetry_sample():
    METRICS.inc("telemetry_samples_total", 1,
                "Registry samples taken by the time-series engine")


def record_alert_transition(rule: str, event: str):
    METRICS.inc("alert_transitions_total", 1,
                "Alert state transitions (firing or resolved) across all "
                "rules")


def record_alerts_firing(count: int):
    METRICS.set("alerts_firing", count,
                "Alert rules currently in the firing state")


def record_snapshot_written():
    METRICS.inc("debug_snapshots_total", 1,
                "Flight-recorder debug snapshots written to disk")


def _observe_safe(name, value, labels, help_text, exemplar=None):
    # Telemetry sits inside hot/traced paths; it must never raise there.
    try:
        METRICS.observe(name, value, labels, help_text, exemplar=exemplar)
    except Exception:
        pass


def observe_rpc_request(method: str, seconds: float,
                        trace_id: str | None = None):
    _observe_safe("rpc_request_seconds", seconds, {"method": method},
                  "JSON-RPC request latency by method", exemplar=trace_id)


def observe_critical_path(component: str, seconds: float,
                          trace_id: str | None = None):
    _observe_safe("batch_critical_path_seconds", seconds,
                  {"component": component},
                  "Per-component critical-path attribution of a settled "
                  "batch's merged lifecycle trace (queue-wait / assign / "
                  "prove stages / transport / verify / settle; "
                  "docs/OBSERVABILITY.md)", exemplar=trace_id)


def record_trace_ingest(added: int, dropped: int = 0):
    if added:
        METRICS.inc("trace_spans_ingested_total", added,
                    "Remote spans merged into the local trace ring "
                    "(span shipping over ProofSubmit/Heartbeat)")
    if dropped:
        METRICS.inc("trace_spans_ingest_dropped_total", dropped,
                    "Shipped spans dropped at ingestion: malformed, "
                    "over the per-source cap, or over the per-trace "
                    "span budget")


def observe_rpc_queue_wait(seconds: float):
    _observe_safe("rpc_queue_wait_seconds", seconds, None,
                  "Accept-to-handler queue wait: time a connection sat "
                  "between the accept loop and its handler thread "
                  "picking it up (rises when the thread pool or the "
                  "accept loop saturates)")


def record_rpc_accept():
    METRICS.inc("rpc_connections_accepted_total", 1,
                "TCP connections accepted by the JSON-RPC listener")


def record_rpc_reset():
    METRICS.inc("rpc_connections_reset_total", 1,
                "RPC connections that died mid-request "
                "(ECONNRESET/EPIPE) — the backlog-pressure signal: "
                "kernel RSTs from an overflowing listen queue land "
                "here")


def record_rpc_eof():
    METRICS.inc("rpc_connections_eof_total", 1,
                "RPC connections closed before a complete request "
                "arrived (short body or empty read)")


def record_rpc_bytes(request_bytes: int, response_bytes: int):
    METRICS.inc("rpc_request_bytes_total", request_bytes,
                "Cumulative JSON-RPC request body bytes read")
    METRICS.inc("rpc_response_bytes_total", response_bytes,
                "Cumulative JSON-RPC response body bytes written")


def record_rpc_inflight(count: int):
    METRICS.set("rpc_inflight_requests", count,
                "JSON-RPC requests currently executing in handler "
                "threads")


def record_rpc_method_inflight(method: str, count: int):
    METRICS.set_labeled("rpc_method_inflight", {"method": method}, count,
                        help_text="Concurrent executions of one JSON-RPC "
                                  "method right now")


def record_rpc_backlog(size: int):
    METRICS.set("rpc_listen_backlog", size,
                "Configured TCP listen backlog of the JSON-RPC server "
                "(--rpc-backlog / ETHREX_RPC_BACKLOG)")


def record_rpc_slow_request():
    METRICS.inc("rpc_slow_requests_total", 1,
                "Requests slower than the slow-request threshold "
                "(ETHREX_RPC_SLOW_SECONDS); each emits a structured "
                "log line carrying its trace ID")


def record_rpc_batch(entries: int):
    METRICS.inc("rpc_batch_requests_total", 1,
                "JSON-RPC batch arrays received (entries dispatched "
                "concurrently on the event loop, responses reassembled "
                "in order; capped by ETHREX_RPC_MAX_BATCH)")
    METRICS.inc("rpc_batch_entries_total", entries,
                "Individual requests carried inside JSON-RPC batch "
                "arrays (each still admitted and measured on its own)")


def record_rpc_executor_workers(count: int):
    METRICS.set("rpc_executor_workers", count,
                "Bound of the RPC execution-stage thread pool "
                "(ETHREX_RPC_EXECUTOR_WORKERS): blocking handler "
                "bodies run here so they never stall the event loop")


def record_rpc_shed(reason: str, cost_class: str):
    METRICS.inc("rpc_requests_shed_total", 1,
                "Requests refused by admission control with the typed "
                "server-busy error, any reason (the shed-rate alert "
                "reads this; docs/OVERLOAD.md)")
    METRICS.inc_labeled("rpc_requests_shed_by_reason",
                        {"reason": reason, "class": cost_class}, 1.0,
                        help_text="Admission-control sheds by reason "
                                  "(deadline, concurrency, level) and "
                                  "cost class (read, submit, heavy)")


def record_shed_level(level: int):
    METRICS.set("rpc_shed_level", level,
                "Current adaptive shed level of the RPC admission "
                "controller (0 = admit everything, 1 = shed heavy, "
                "2 = +submit, 3 = shed all but control)")


def record_ws_connections(count: int):
    METRICS.set("ws_connections", count,
                "WebSocket subscription connections currently open")


def record_ws_accept():
    METRICS.inc("ws_connections_accepted_total", 1,
                "WebSocket connections accepted (successful RFC 6455 "
                "handshakes)")


def record_ws_notification(count: int = 1):
    METRICS.inc("ws_notifications_total", count,
                "Subscription notification frames pushed to WebSocket "
                "clients")


def record_ws_send_failure():
    METRICS.inc("ws_send_failures_total", 1,
                "Notification pushes that failed on a dead WebSocket "
                "(connection dropped from the fan-out set)")


def record_ws_notification_drop():
    METRICS.inc("ws_notifications_dropped_total", 1,
                "Subscription notifications dropped because a "
                "consumer's bounded send queue was full (the slow "
                "consumer keeps its connection until the deadline)")


def record_ws_slow_consumer_disconnect():
    METRICS.inc("ws_slow_consumer_disconnects_total", 1,
                "WebSocket connections force-closed because the "
                "consumer stayed full past the slow-consumer deadline "
                "instead of blocking fan-out for healthy subscribers")


def record_mempool_admission():
    METRICS.inc("mempool_admitted_total", 1,
                "Transactions admitted into the mempool")


def record_mempool_rejection(reason: str):
    METRICS.inc("mempool_rejections_total", 1,
                "Transactions rejected by mempool admission, any reason")
    METRICS.inc_labeled("mempool_rejections_by_reason", {"reason": reason},
                        1.0,
                        help_text="Mempool admission rejections by typed "
                                  "reason (nonce_too_low, underpriced, "
                                  "insufficient_funds, invalid_signature, "
                                  "pool_full, blobs_missing, privileged, "
                                  "wrong_chain_id, nonce_gap, "
                                  "sender_limit, fee_below_floor)")


def record_mempool_replacement():
    METRICS.inc("mempool_replacements_total", 1,
                "Replacement-by-fee admissions (same sender+nonce with "
                "a >=10% fee bump); the replacement-churn alert reads "
                "this — a fee-bump war churns the pool without adding "
                "throughput")


def record_mempool_eviction(reason: str):
    METRICS.inc("mempool_evictions_total", 1,
                "Transactions evicted from the mempool after admission, "
                "any reason")
    METRICS.inc_labeled("mempool_evictions_by_reason", {"reason": reason},
                        1.0,
                        help_text="Mempool evictions by reason (fifo "
                                  "capacity, blob_pool_full, replaced, "
                                  "invalid_at_build)")


def record_mempool_occupancy(size: int, utilization: float):
    METRICS.set("mempool_size", size,
                "Transactions currently resident in the mempool")
    METRICS.set("mempool_utilization", utilization,
                "Mempool occupancy over capacity — the max of the "
                "regular and blob sub-pool fill fractions (1.0 = every "
                "new tx evicts another; the saturation alert reads "
                "this)")


def observe_time_in_pool(seconds: float, reason: str = "included"):
    # labelled by removal reason so inclusion dwell is not polluted by
    # eviction/prune/reorg dwell (they answer different questions:
    # "how long until a block?" vs "how long do we hold junk?")
    _observe_safe("mempool_time_in_pool_seconds", seconds,
                  {"reason": reason},
                  "Admission-to-removal dwell time of mempool "
                  "transactions, labelled by removal reason (included "
                  "vs evicted/pruned/reorg/...)")


def observe_prover_stage(stage: str, seconds: float):
    _observe_safe("prover_stage_seconds", seconds, {"stage": stage},
                  "Per-stage prover latency (block_until_ready-bounded)")


def observe_block_execution(seconds: float):
    _observe_safe("block_execution_seconds", seconds, None,
                  "EVM execution time per block (execute_block)")


def observe_block_import(seconds: float):
    _observe_safe("block_import_seconds", seconds, None,
                  "End-to-end block import time (add_block)")


def observe_actor_iteration(actor: str, seconds: float):
    _observe_safe("sequencer_actor_seconds", seconds, {"actor": actor},
                  "Sequencer actor loop iteration latency")


def observe_import_stage(stage: str, seconds: float):
    """Sub-stage attribution of block import (execute / merkleize /
    store_write), both the per-block and the pipelined path."""
    _observe_safe("block_import_stage_seconds", seconds, {"stage": stage},
                  "Block import sub-stage latency (execute / merkleize / "
                  "store_write legs of add_block and the pipelined "
                  "importer)")


def record_kernel_flops(air: str, kernel: str, flops: float,
                        achieved: float | None = None,
                        utilization: float | None = None):
    """Roofline gauges for one compiled STARK phase program (never
    raises: called from the prover hot path)."""
    try:
        labels = {"air": air, "stage": kernel}
        METRICS.set_labeled(
            "prover_kernel_flops", labels, flops,
            help_text="XLA cost-model FLOPs of the compiled STARK phase "
                      "program (static, per air+stage)")
        if achieved is not None:
            METRICS.set_labeled(
                "prover_kernel_achieved_flops_per_sec", labels, achieved,
                help_text="Cost-model FLOPs divided by the last measured "
                          "stage wall-clock")
        if utilization is not None:
            METRICS.set_labeled(
                "prover_kernel_utilization", labels, utilization,
                help_text="Achieved-FLOP/s over the estimated backend "
                          "peak (see docs/PERFORMANCE.md caveats)")
    except Exception:
        pass


def record_import_throughput(mgas_per_sec: float):
    METRICS.set("l1_import_mgas_per_sec", mgas_per_sec,
                "Execution throughput of the last pipelined block-batch "
                "import (Mgas/s; the bench headline L1 number, live)")


def record_prover_throughput(cells_per_sec: float):
    METRICS.set("prover_trace_cells_per_sec", cells_per_sec,
                "Trace cells proven per second in the last STARK prove "
                "(n x width over end-to-end prove wall-clock)")


def record_senders_recovered(count: int):
    METRICS.inc("senders_recovered_total", count,
                "Transaction senders recovered by the batched "
                "sender-recovery stage (either engine; excludes "
                "cache hits)")


def observe_sender_recovery_batch(seconds: float):
    _observe_safe("sender_recovery_batch_seconds", seconds, None,
                  "Wall-clock of one batched sender-recovery call "
                  "(whole tx list, all pool workers joined)")


def record_proof_wall(seconds: float):
    """Derive the proofs_per_hour throughput gauge from one end-to-end
    backend prove wall-clock."""
    if seconds > 0:
        METRICS.set("proofs_per_hour", 3600.0 / seconds,
                    "Extrapolated proofs per hour from the last "
                    "end-to-end backend prove wall-clock")


# -- p2p request resilience + snap-sync (docs/P2P_RESILIENCE.md) -----------

def record_p2p_timeout(klass: str):
    METRICS.inc("p2p_request_timeouts_total", 1,
                "P2P requests that outlived their adaptive (phi-accrual) "
                "timeout, across all request classes")
    METRICS.inc_labeled("p2p_request_class_timeouts", {"class": klass}, 1,
                        help_text="P2P request timeouts by request class "
                                  "(headers/ranges/trie/...)")


def record_p2p_retry(klass: str):
    METRICS.inc("p2p_request_retries_total", 1,
                "P2P request retry attempts (fresh request id, jittered "
                "exponential backoff) after a timeout or dropped frame")


def record_p2p_ban():
    METRICS.inc("p2p_peer_bans_total", 1,
                "Peers banned after dropping to SCORE_DISCONNECT; bans "
                "persist in store.meta['p2p_bans'] across restarts")


def record_p2p_broadcast_failure():
    METRICS.inc("p2p_broadcast_failures_total", 1,
                "Block/hash broadcast sends that failed (dead or stalled "
                "peer); each also costs the peer a score penalty")


def record_p2p_peer_rtt(peer: str, seconds: float):
    METRICS.set_labeled("p2p_peer_rtt_seconds", {"peer": peer}, seconds,
                        help_text="EWMA request round-trip time per peer "
                                  "(the phi-accrual estimator mean)")


def record_snap_phase(phase: int):
    METRICS.set("snap_sync_phase", phase,
                "Snap-sync phase: 0 idle, 1 accounts, 2 healing, 3 done")


def record_snap_range():
    METRICS.inc("snap_ranges_synced_total", 1,
                "Account-range windows fetched, proof-verified and "
                "checkpointed by snap-sync (each is one leased unit; "
                "kill-restart re-fetches at most one)")


def record_snap_paused(paused: bool):
    METRICS.set("snap_sync_paused", 1 if paused else 0,
                "1 while snap-sync is paused with zero live peers "
                "(network partition), 0 otherwise")
    if paused:
        METRICS.inc("snap_partition_pauses_total", 1,
                    "Times snap-sync paused on a total peer partition "
                    "and waited for a peer to return")


def record_snap_progress_reset():
    METRICS.inc("snap_progress_resets_total", 1,
                "Torn/garbage snap_sync checkpoint blobs discarded at "
                "load (sync restarted from scratch instead of crashing)")


class MetricsServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 9090):
        self.host = host
        self.port = port
        self._httpd = None

    def start(self):
        class Handler(BaseHTTPRequestHandler):
            def do_GET(self):
                # A scraper may abort mid-response; a dead socket is the
                # scraper's problem, never the server thread's.
                try:
                    if self.path != "/metrics":
                        body = b"not found\n"
                        self.send_response(404)
                        self.send_header("Content-Type",
                                         "text/plain; charset=utf-8")
                        self.send_header("Content-Length", str(len(body)))
                        self.end_headers()
                        self.wfile.write(body)
                        return
                    body = METRICS.render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                except (BrokenPipeError, ConnectionResetError):
                    pass

            def log_message(self, *args):
                pass

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        threading.Thread(target=self._httpd.serve_forever,
                         daemon=True).start()
        return self

    def stop(self):
        if self._httpd:
            self._httpd.shutdown()
            self._httpd.server_close()
