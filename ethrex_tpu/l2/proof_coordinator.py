"""Proof coordinator: TCP server assigning batches to pull-based provers
(parity with the reference's ProofCoordinator actor,
crates/l2/sequencer/proof_coordinator.rs — per-(batch, prover_type)
assignment map with timeout reassignment, version gating, duplicate-proof
no-op storage), extended with the resilience layer:

  * leases instead of a fixed timeout — Heartbeat messages from a prover
    mid-proof extend its assignment deadline, so a slow TPU proof is not
    reassigned out from under a live prover;
  * per-batch failure tracking — every lease expiry and every rejected
    submit counts against the (batch, prover_type) pair;
  * poison-batch quarantine — a batch that keeps failing on its primary
    prover type is handed to the fallback backend (the reference's
    multi-prover model as graceful degradation) and surfaced via metrics
    and the health endpoint;
  * submit-time proof validation — a corrupt proof frees the assignment
    slot immediately instead of poisoning the stored-proof map until the
    proof sender's full audit;
  * lease tokens — every assignment carries an unguessable token that
    Heartbeat and ProofSubmit must echo; the wire protocol carries no
    prover identity, so the token is what ties lease mutations (extension,
    invalid-proof eviction, failure accounting) to the prover that was
    actually granted the lease instead of to any connection that names the
    right (batch, prover_type) pair;
  * a bounded lease lifetime — heartbeats extend a lease only up to
    `max_lease_lifetime` past first assignment, so a prover whose prove
    call hangs (rather than crashes) is still eventually reassigned and
    counted as a failure instead of pinning the batch forever;
  * lease reclaim — a prover that restarts on the disk its dead
    incarnation left phase checkpoints on presents the lease token they
    record (`reclaim` on InputRequest); where that is the batch's
    current primary token the lease moves to it under a new token, with
    no failure charged and the lifetime cap still anchored at the first
    assignment, instead of the batch waiting out `lease_timeout` while
    the restarted prover is handed the next one;

and, on top of the lease substrate, a **fleet scheduler**
(docs/AGGREGATION.md) replacing the original FCFS scan:

  * per-prover throughput tracking — provers may volunteer a stable
    `prover_id` on the wire; the coordinator keeps an EWMA of each
    prover's proving wall-clock and its live-lease count;
  * batch-size-aware placement — the fastest provers are steered toward
    the heaviest unleased batches and the slowest toward the lightest
    (with no stats the scan degrades to the FCFS order, and
    `scheduler_policy="fcfs"` pins the original behavior outright);
  * speculative hedged re-assignment — once every candidate batch is
    leased, a requester can be granted a *hedge lease* on a straggler
    whose elapsed time exceeds a p99-derived deadline ("The Tail at
    Scale", Dean & Barroso, CACM 2013).  First result wins: the hedge
    carries its own token, either holder's valid submit settles the
    batch, and the loser's later submit is deduplicated into a no-op
    SUBMIT_ACK without touching lease or quarantine state;
  * work stealing — an idle prover may likewise be granted a hedge on a
    batch held by a prover sitting on a deep backlog of live leases
    (Blumofe & Leiserson's steal-from-the-loaded rule, run as a race
    rather than a revocation so the existing token safety applies);
  * warm-aware handoff — provers may report an advisory `warm` flag on
    InputRequest (their AOT kernels hydrated from the on-disk executable
    cache, docs/PERFORMANCE.md "Cold start").  A cold prover is asked to
    sit out a bounded number of polls while recently-seen warm provers
    can absorb the queue, so the first post-restart batches land on
    provers that prove at steady-state wall; and a batch assigned to a
    cold prover is excluded from the duration samples and that prover's
    EWMA, so one compile-inclusive first proof cannot poison the
    placement and hedging signals.
"""

from __future__ import annotations

import collections
import logging
import secrets
import socketserver
import threading
import time

from ..prover import protocol
from ..utils import faults, tracing
from .rollup_store import RollupStore

log = logging.getLogger("ethrex_tpu.l2.proof_coordinator")

ASSIGNMENT_TIMEOUT = 600.0  # default lease, like the reference's 10 minutes
QUARANTINE_THRESHOLD = 3    # failed assignments before exec fallback
LEASE_LIFETIME_FACTOR = 6   # max heartbeat-extended lifetime, in leases
HEDGE_MIN_SAMPLES = 8       # completed proofs before p99 hedging arms
HEDGE_FACTOR = 1.5          # hedge once elapsed > p99 * factor
STEAL_THRESHOLD = 4         # live leases that mark a prover "overloaded"
EWMA_ALPHA = 0.3            # per-prover proving-time smoothing
WARM_PEER_WINDOW = 60.0     # a warm prover seen this recently can absorb
COLD_DEFERRAL_CAP = 3       # polls a cold prover sits out before it's fed


class ProofCoordinator:
    def __init__(self, rollup_store: RollupStore,
                 needed_types: list[str] | None = None,
                 commit_hash: str = protocol.PROTOCOL_VERSION,
                 host: str = "127.0.0.1", port: int = 0,
                 proof_format: str = protocol.FORMAT_STARK,
                 lease_timeout: float = ASSIGNMENT_TIMEOUT,
                 quarantine_threshold: int = QUARANTINE_THRESHOLD,
                 fallback_type: str = protocol.PROVER_EXEC,
                 verify_submissions: bool = True,
                 max_lease_lifetime: float | None = None,
                 scheduler_policy: str = "fleet",
                 hedge_min_samples: int = HEDGE_MIN_SAMPLES,
                 hedge_factor: float = HEDGE_FACTOR,
                 steal_threshold: int = STEAL_THRESHOLD):
        if scheduler_policy not in ("fleet", "fcfs"):
            raise ValueError(
                f"unknown scheduler policy {scheduler_policy!r}")
        self.rollup = rollup_store
        self.needed_types = needed_types or [protocol.PROVER_TPU]
        self.commit_hash = commit_hash
        self.proof_format = proof_format
        self.lease_timeout = lease_timeout
        self.quarantine_threshold = quarantine_threshold
        self.fallback_type = fallback_type
        self.verify_submissions = verify_submissions
        # total lifetime a lease may be heartbeat-extended to, measured
        # from first assignment; a hung (not crashed) prover is reassigned
        # once this is spent
        self.max_lease_lifetime = (
            max_lease_lifetime if max_lease_lifetime is not None
            else LEASE_LIFETIME_FACTOR * lease_timeout)
        # (batch_number, prover_type) -> lease deadline; an expired entry
        # stays until reassignment so a late-but-finished proof still lands
        self.assignments: dict[tuple[int, str], float] = {}
        # (batch_number, prover_type) -> first-assignment time (metrics +
        # the max_lease_lifetime anchor)
        self.assigned_at: dict[tuple[int, str], float] = {}
        # (batch_number, prover_type) -> token of the current lease holder;
        # Heartbeat/ProofSubmit must echo it to mutate lease state
        self.lease_tokens: dict[tuple[int, str], str] = {}
        # (batch_number, prover_type) -> failed assignments (expiry/reject)
        self.failures: dict[tuple[int, str], int] = {}
        # batch_number -> trace ID; one trace follows the batch through
        # assign -> prove -> submit -> verify -> settle (docs/OBSERVABILITY.md)
        self.batch_traces: dict[int, str] = {}
        self.quarantined: set[int] = set()
        self.reassignments_total = 0
        self.reclaims_total = 0
        self.heartbeats_total = 0
        self.rejected_submits_total = 0
        self.unsolicited_submits_total = 0
        self.stale_submits_total = 0
        # -- fleet scheduler state -------------------------------------
        self.scheduler_policy = scheduler_policy
        self.hedge_min_samples = max(1, hedge_min_samples)
        self.hedge_factor = hedge_factor
        self.steal_threshold = max(1, steal_threshold)
        # (batch, prover_type) -> hedge lease racing the primary holder:
        # {token, assigned_at, expires, prover_id, reason}; its token is
        # accepted by Heartbeat/ProofSubmit exactly like the primary's
        self.hedges: dict[tuple[int, str], dict] = {}
        # (batch, prover_type) -> prover_id of the primary holder (None
        # for provers that do not volunteer an identity)
        self.lease_holders: dict[tuple[int, str], str | None] = {}
        # prover_id -> {completed, ewma, last_seen, warm, cold_deferrals};
        # fed by assigns and successful submits that carry a prover_id
        self.prover_stats: dict[str, dict] = {}
        # (batch, prover_type) -> the holder's warm flag at grant time
        # (None for provers that did not report one); a cold-assigned
        # batch's proving wall includes compile time, so _handle_submit
        # keeps it out of the durations deque and the holder's EWMA
        self.lease_warm: dict[tuple[int, str], bool | None] = {}
        # (batch, prover_type) -> (in-flight phase, transition time on
        # THIS clock) from heartbeats; the hedging deadline re-anchors on
        # every phase transition so a proof making phase progress is
        # never hedged as a straggler (the prover's own phase_started is
        # advisory/observability only — clock skew never feeds hedging)
        self.lease_phase: dict[tuple[int, str], tuple[str, float]] = {}
        self.poison_reports_total = 0
        self.cold_deferrals_total = 0
        # recent completed proving wall-clocks, the p99 hedging source
        self.durations: collections.deque = collections.deque(maxlen=256)
        self.hedged_assignments_total = 0
        self.duplicate_submits_total = 0
        self.queue_depth = 0
        self.lock = threading.RLock()
        self.host = host
        self.port = port
        self._server: socketserver.ThreadingTCPServer | None = None
        # requests currently inside handle_request; stop() waits for
        # them so an in-flight proof submit lands before the drain
        # proceeds (a submit that misses the window leases back on
        # restart via normal lease expiry)
        self._inflight = 0
        self._inflight_cv = threading.Condition()
        # bounded ring of recent lease events (assign/expire/reject/
        # quarantine/proof) for the flight recorder: the raw counters say
        # HOW MANY leases churned, this says WHICH and WHEN
        self.events: collections.deque = collections.deque(maxlen=64)
        # batch -> critical-path summary of its settled lifecycle trace,
        # written by the sequencer after verify/settle and surfaced in
        # ethrex_health (`l2.lifecycle`) and the monitor timeline
        self.batch_lifecycles: "collections.OrderedDict[int, dict]" = \
            collections.OrderedDict()

    def note_lifecycle(self, batch: int, summary: dict) -> None:
        """Record one settled batch's critical-path summary (bounded;
        telemetry, so it never raises into settlement)."""
        try:
            with self.lock:
                self.batch_lifecycles[batch] = summary
                self.batch_lifecycles.move_to_end(batch)
                while len(self.batch_lifecycles) > 16:
                    self.batch_lifecycles.popitem(last=False)
        except Exception:
            pass

    def lifecycles_json(self) -> list:
        """Recent settled batches' lifecycle timeline, oldest first."""
        with self.lock:
            return [dict(v) for v in self.batch_lifecycles.values()]

    def _note_event(self, event: str, batch: int, prover_type: str,
                    detail: str | None = None):
        """Caller holds self.lock (or accepts best-effort ordering)."""
        entry = {"ts": time.time(), "event": event, "batch": batch,
                 "proverType": prover_type}
        if detail:
            entry["detail"] = detail
        self.events.append(entry)

    @staticmethod
    def _now() -> float:
        """Lease clock; an instance attribute in tests to fake expiry."""
        return time.monotonic()

    # ------------------------------------------------------------------
    # failure accounting + quarantine
    # ------------------------------------------------------------------
    def _record_failure(self, batch: int, prover_type: str, reason: str):
        """Caller holds self.lock."""
        from ..utils.metrics import record_quarantine, record_reassignment

        key = (batch, prover_type)
        self.failures[key] = self.failures.get(key, 0) + 1
        self.reassignments_total += 1
        record_reassignment(batch, prover_type)
        self._note_event("lease-failure", batch, prover_type, reason)
        log.warning("batch %d assignment to %s failed (%s), %d/%d before "
                    "quarantine", batch, prover_type, reason,
                    self.failures[key], self.quarantine_threshold)
        if (prover_type != self.fallback_type
                and self.failures[key] >= self.quarantine_threshold
                and batch not in self.quarantined):
            self.quarantined.add(batch)
            record_quarantine(len(self.quarantined))
            self._note_event("quarantine", batch, prover_type)
            log.error("batch %d quarantined off %r after %d failed "
                      "assignments; falling back to %r", batch,
                      prover_type, self.failures[key], self.fallback_type)

    def _allowed_types(self) -> set[str]:
        """Prover types this coordinator currently serves: the configured
        set, plus the fallback backend while any batch is quarantined."""
        allowed = set(self.needed_types)
        if self.quarantined:
            allowed.add(self.fallback_type)
        return allowed

    def effective_needed_types(self, batch_number: int,
                               base: list[str] | None = None) -> list[str]:
        """The prover types that actually settle this batch: quarantined
        batches substitute the fallback type for every primary type
        (graceful degradation — the proof sender and L1 path consume
        this, so settlement keeps moving on the fallback proof)."""
        types = list(base if base is not None else self.needed_types)
        if batch_number in self.quarantined:
            types = [self.fallback_type for _ in types]
        return list(dict.fromkeys(types))

    # ------------------------------------------------------------------
    # fleet scheduler
    # ------------------------------------------------------------------
    def _batch_weight(self, num: int) -> int:
        """Rough batch size for placement: block/tx counts out of the
        stored prover input.  Opaque inputs weigh 1, which collapses the
        size-aware pick back to the FCFS order."""
        inp = self.rollup.get_prover_input(num, self.commit_hash)
        if not isinstance(inp, dict):
            return 1
        blocks = inp.get("blocks")
        if not isinstance(blocks, list):
            return 1
        weight = 0
        for b in blocks:
            weight += 1
            if isinstance(b, dict):
                txs = b.get("transactions")
                if isinstance(txs, list):
                    weight += len(txs)
        return max(1, weight)

    def _hedge_deadline(self) -> float | None:
        """p99 of recent proving wall-clocks times `hedge_factor`; None
        until `hedge_min_samples` proofs have completed (hedging stays
        disarmed while the fleet has no latency signal).  Caller holds
        self.lock."""
        if len(self.durations) < self.hedge_min_samples:
            return None
        ordered = sorted(self.durations)
        p99 = ordered[min(len(ordered) - 1,
                          int(0.99 * (len(ordered) - 1) + 0.5))]
        return p99 * self.hedge_factor

    def _live_leases_held(self, prover_id: str, now: float) -> int:
        """Caller holds self.lock."""
        return sum(1 for key, deadline in self.assignments.items()
                   if deadline > now
                   and self.lease_holders.get(key) == prover_id)

    def _pick_unleased(self, unleased: list[int],
                       prover_id: str | None) -> int:
        """Batch-size-aware placement: relative to the rest of the
        fleet's EWMA proving times, a fastest prover takes the heaviest
        waiting batch and a slowest takes the lightest; everyone else —
        and every prover without stats — takes the oldest (FCFS)."""
        if self.scheduler_policy != "fleet" or prover_id is None \
                or len(unleased) == 1:
            return unleased[0]
        st = self.prover_stats.get(prover_id)
        if st is not None and st.get("degraded") is not None:
            # runtime-degraded prover (OOM/device-loss demoted its mesh):
            # steer it to the lightest waiting batch regardless of EWMA —
            # its historical speed no longer predicts its capacity
            weights = {num: self._batch_weight(num) for num in unleased}
            return min(unleased, key=lambda n: (weights[n], n))
        ewma = st.get("ewma") if st else None
        others = [s["ewma"] for pid, s in self.prover_stats.items()
                  if pid != prover_id and s.get("ewma") is not None]
        if ewma is None or not others:
            return unleased[0]
        weights = {num: self._batch_weight(num) for num in unleased}
        if len(set(weights.values())) == 1:
            return unleased[0]
        if ewma <= min(others):
            # ties break toward the oldest batch, keeping settlement
            # (which walks batches in order) fed
            return max(unleased, key=lambda n: (weights[n], -n))
        if ewma >= max(others):
            return min(unleased, key=lambda n: (weights[n], n))
        return unleased[0]

    def next_batch_to_assign(self, prover_type: str,
                             prover_id: str | None = None) -> int | None:
        """Back-compat wrapper over `assign` (the original FCFS scan's
        signature); callers that need the granted lease token — a hedge
        grant carries its own — use `assign` directly."""
        return self.assign(prover_type, prover_id)[0]

    def assign(self, prover_type: str, prover_id: str | None = None,
               warm: bool | None = None, reclaim: dict | None = None
               ) -> tuple[int | None, str | None]:
        """One scheduling decision: (batch, lease_token) or (None,
        None); `_schedule` without its third answer."""
        return self._schedule(prover_type, prover_id, warm, reclaim)[:2]

    def _schedule(self, prover_type: str, prover_id: str | None,
                  warm: bool | None, reclaim: dict | None
                  ) -> tuple[int | None, str | None, bool]:
        """One scheduling decision: returns (batch, lease_token,
        reclaimed) or (None, None, False).

        `reclaim` ({batch_id, lease_token}) is a restarted prover's
        claim to the lease its dead incarnation held: granted by
        `_reclaim` where the token is that lease's, else ignored and
        the request is scheduled like any other.

        Scans batches with a stored prover input and no proof of this
        type (reference: next_batch_to_assign:149-215).  Expired leases
        are counted as failed assignments — enough of them quarantines
        the batch onto the fallback backend.  Unleased work is placed
        size-aware under the fleet policy (FCFS under `fcfs`); a
        requester that reports itself cold (`warm=False`) may first be
        deferred while recently-seen warm provers can absorb the queue
        (bounded by COLD_DEFERRAL_CAP so a warm-less fleet never
        starves); when everything is leased, the fleet policy may grant
        a *hedge* on a straggler past the p99-derived deadline or steal
        from an overloaded holder — a second lease racing the first,
        dedup'd at submit time."""
        faults.inject("coordinator.schedule")
        if prover_type not in self._allowed_types():
            return None, None, False
        now = self._now()
        with self.lock:
            if prover_id is not None:
                st = self.prover_stats.setdefault(
                    prover_id, {"completed": 0, "ewma": None,
                                "last_seen": now})
                st["last_seen"] = now
                if warm is not None:
                    st["warm"] = warm
                    if warm:
                        st["cold_deferrals"] = 0
            if reclaim is not None:
                granted = self._reclaim(reclaim, prover_type, prover_id,
                                        now, warm)
                if granted is not None:
                    return (*granted, True)
            candidates = sorted({
                num for (num, ver) in self.rollup.prover_inputs
                if ver == self.commit_hash
            })
            unleased: list[int] = []
            leased: list[int] = []
            for num in candidates:
                if num in self.quarantined:
                    # quarantined batches go only to the fallback backend
                    if prover_type != self.fallback_type:
                        continue
                elif prover_type not in self.needed_types:
                    continue  # fallback prover: nothing else for it here
                if self.rollup.get_proof(num, prover_type) is not None:
                    continue
                key = (num, prover_type)
                deadline = self.assignments.get(key)
                if deadline is not None:
                    if deadline > now:
                        leased.append(num)
                        continue  # live lease elsewhere
                    # lease expired: the holder crashed or stalled
                    self._clear_lease(key)
                    self._record_failure(num, prover_type, "lease expired")
                    if num in self.quarantined and \
                            prover_type != self.fallback_type:
                        continue  # this expiry tipped it into quarantine
                unleased.append(num)
            self.queue_depth = len(unleased)
            if unleased:
                if self._defer_cold(prover_id, warm, len(unleased), now):
                    self._report_queue_depth()
                    return None, None, False
                num = self._pick_unleased(unleased, prover_id)
                token = self._grant(num, prover_type, prover_id, now,
                                    warm)
                self.queue_depth -= 1   # the grant is no longer waiting
                self._report_queue_depth()
                return num, token, False
            granted = self._maybe_hedge(leased, prover_type, prover_id,
                                        now, warm)
            self._report_queue_depth()
            return (*granted, False)

    def _defer_cold(self, prover_id: str | None, warm: bool | None,
                    queue_len: int, now: float) -> bool:
        """Warm-aware handoff: should this requester sit out the poll?
        Only a prover that EXPLICITLY reports warm=False is deferred
        (warm=None — an older client — is never penalized), only while
        enough recently-seen warm peers exist to absorb the whole queue,
        and only COLD_DEFERRAL_CAP times in a row — so the first batches
        after a restart land on provers that prove at steady-state wall,
        without ever starving a fleet that has no warm capacity.  The
        deferred prover keeps polling (and hydrating in the background);
        its next InputRequest is a fresh decision.  Caller holds
        self.lock."""
        from ..utils.metrics import record_cold_deferral

        if self.scheduler_policy != "fleet" or warm is not False \
                or prover_id is None:
            return False
        st = self.prover_stats.get(prover_id)
        deferrals = st.get("cold_deferrals", 0) if st else 0
        if deferrals >= COLD_DEFERRAL_CAP:
            return False
        warm_peers = sum(
            1 for pid, s in self.prover_stats.items()
            if pid != prover_id and s.get("warm")
            and now - s.get("last_seen", 0.0) <= WARM_PEER_WINDOW)
        if warm_peers == 0 or queue_len > warm_peers:
            return False    # not enough warm capacity; feed the cold one
        if st is not None:
            st["cold_deferrals"] = deferrals + 1
        self.cold_deferrals_total += 1
        record_cold_deferral()
        log.info("deferring cold prover %s (%d/%d): %d warm peer(s) can "
                 "absorb the %d-batch queue", prover_id, deferrals + 1,
                 COLD_DEFERRAL_CAP, warm_peers, queue_len)
        return True

    def _grant(self, num: int, prover_type: str, prover_id: str | None,
               now: float, warm: bool | None = None) -> str:
        """Issue the primary lease. Caller holds self.lock."""
        key = (num, prover_type)
        token = secrets.token_hex(16)
        self.assignments[key] = now + self.lease_timeout
        self.assigned_at[key] = now
        self.lease_tokens[key] = token
        self.lease_holders[key] = prover_id
        self.lease_warm[key] = warm
        return token

    def _reclaim(self, reclaim: dict, prover_type: str,
                 prover_id: str | None, now: float,
                 warm: bool | None) -> tuple[int, str] | None:
        """Move a live primary lease to the restarted prover that
        presents its token: a new token, the deadline reset, the holder
        replaced.  Nothing is counted against the batch (its prover
        died, the batch did nothing wrong) and `assigned_at` stays, so
        `max_lease_lifetime` still cuts off a prover that crash-loops
        on one batch.  None in every other case: a proven or
        quarantined batch, a lapsed or reassigned lease, a wrong, stale
        or hedge token.  Caller holds self.lock."""
        from ..utils.metrics import record_lease_reclaim

        num, token = reclaim.get("batch_id"), reclaim.get("lease_token")
        key = (num, prover_type)
        if not isinstance(num, int) or not isinstance(token, str) \
                or num in self.quarantined \
                or prover_type not in self.needed_types \
                or self.assignments.get(key, 0.0) <= now \
                or not secrets.compare_digest(
                    token.encode(), self.lease_tokens.get(key, "").encode()) \
                or self.rollup.get_proof(num, prover_type) is not None:
            return None
        hard = self.assigned_at.get(key, now) + self.max_lease_lifetime
        if now >= hard:
            return None     # lifetime spent: the lease lapses as it stands
        fresh = secrets.token_hex(16)
        self.assignments[key] = min(now + self.lease_timeout, hard)
        self.lease_tokens[key] = fresh
        self.lease_holders[key] = prover_id
        self.lease_warm[key] = warm
        # a resuming prover is making progress: the straggler clock
        # starts again here, as on a phase transition
        self.lease_phase[key] = ("reclaimed", now)
        self.reclaims_total += 1
        record_lease_reclaim()
        self._note_event("lease-reclaimed", num, prover_type)
        log.info("batch %d/%s reclaimed by %s on its old lease token",
                 num, prover_type, prover_id or "<anon>")
        return num, fresh

    def _reclaim_outcome(self, reclaim: dict, prover_type: str,
                         granted: bool) -> str:
        """What an InputResponse tells a prover about the reclaim it
        presented: its envelopes are garbage once the batch is proven,
        worth keeping otherwise (a later ordinary lease resumes)."""
        if granted:
            return "granted"
        num = reclaim.get("batch_id")
        if isinstance(num, int) \
                and self.rollup.get_proof(num, prover_type) is not None:
            return "proven"
        return "refused"

    def _maybe_hedge(self, leased: list[int], prover_type: str,
                     prover_id: str | None, now: float,
                     warm: bool | None = None
                     ) -> tuple[int | None, str | None]:
        """Every candidate batch is leased: under the fleet policy, grant
        a hedge lease on a straggler past the p99 deadline, or steal from
        a holder with a deep live backlog when this requester is idle.
        Caller holds self.lock."""
        from ..utils.metrics import record_hedged_assignment

        if self.scheduler_policy != "fleet":
            return None, None
        deadline = self._hedge_deadline()
        requester_idle = (prover_id is not None
                          and self._live_leases_held(prover_id, now) == 0)
        for num in leased:
            key = (num, prover_type)
            hedge = self.hedges.get(key)
            if hedge is not None:
                if hedge["expires"] > now:
                    continue  # one hedge at a time per batch
                self.hedges.pop(key, None)  # hedge holder crashed too
            if prover_id is not None \
                    and self.lease_holders.get(key) == prover_id:
                continue  # never hedge a prover against itself
            reason = None
            # straggler clock anchors on the LAST phase transition the
            # holder reported (stamped with this coordinator's clock at
            # heartbeat ingestion), not first assignment: a prover
            # resuming from checkpoints or grinding through a long FRI
            # phase is making progress, and hedging it would only burn a
            # second prover on work the first will finish
            anchor = self.assigned_at.get(key, now)
            phase_info = self.lease_phase.get(key)
            if phase_info is not None:
                anchor = max(anchor, phase_info[1])
            if deadline is not None and now - anchor > deadline:
                reason = "straggler"
            elif requester_idle:
                holder = self.lease_holders.get(key)
                if holder is not None and holder != prover_id \
                        and self._live_leases_held(holder, now) \
                        >= self.steal_threshold:
                    reason = "steal"
            if reason is None:
                continue
            token = secrets.token_hex(16)
            self.hedges[key] = {
                "token": token, "assigned_at": now,
                "expires": now + self.lease_timeout,
                "prover_id": prover_id, "reason": reason,
                "warm": warm,
            }
            self.hedged_assignments_total += 1
            record_hedged_assignment()
            self._note_event("hedge", num, prover_type, reason)
            log.info("hedged batch %d/%s to %s (%s): first result wins",
                     num, prover_type, prover_id or "<anon>", reason)
            return num, token
        return None, None

    def _report_queue_depth(self):
        from ..utils.metrics import record_scheduler_queue_depth

        record_scheduler_queue_depth(self.queue_depth)

    def _clear_lease(self, key: tuple[int, str]) -> float | None:
        """Drop a lease and its token; returns the first-assignment time
        (None if it was never live). Caller holds self.lock."""
        self.assignments.pop(key, None)
        self.lease_tokens.pop(key, None)
        self.lease_holders.pop(key, None)
        self.lease_warm.pop(key, None)
        self.lease_phase.pop(key, None)
        return self.assigned_at.pop(key, None)

    def trace_for_batch(self, batch: int) -> str:
        """The trace ID following this batch's proving lifecycle (created
        on first assignment, reused on reassignment so retries land in
        the same trace)."""
        with self.lock:
            tid = self.batch_traces.get(batch)
            if tid is None:
                tid = tracing.new_trace_id()
                self.batch_traces[batch] = tid
                if len(self.batch_traces) > 4096:
                    for old in sorted(self.batch_traces)[:1024]:
                        del self.batch_traces[old]
            return tid

    def lease_token(self, batch: int, prover_type: str) -> str | None:
        """Token of the current lease holder for (batch, prover_type)."""
        with self.lock:
            return self.lease_tokens.get((batch, prover_type))

    # ------------------------------------------------------------------
    def _handle_heartbeat(self, msg: dict) -> dict:
        from ..utils.metrics import record_heartbeat

        # merge any piggybacked span subtree BEFORE lease logic: even a
        # beat whose lease already lapsed leaves its partial spans, so a
        # prover that later dies mid-prove still renders in the batch's
        # merged trace (never raises, deduped, capped per source)
        tracing.TRACER.ingest(msg.get("spans"),
                              source=msg.get("prover_id"))
        batch = msg.get("batch_id")
        prover_type = msg.get("prover_type")
        token = msg.get("lease_token")
        ok = False
        with self.lock:
            key = (batch, prover_type)
            deadline = self.assignments.get(key)
            now = self._now()
            if (deadline is not None and deadline > now
                    and token is not None
                    and token == self.lease_tokens.get(key)):
                # only the granted holder may extend, and only up to
                # max_lease_lifetime past first assignment — a hung prover
                # cannot keep a batch pinned forever
                hard = self.assigned_at.get(key, now) \
                    + self.max_lease_lifetime
                if now < hard:
                    self.assignments[key] = \
                        min(now + self.lease_timeout, hard)
                    self.heartbeats_total += 1
                    ok = True
                # else: lifetime spent; the lease lapses at its current
                # deadline, expiry reassigns and counts the failure
            else:
                # a hedge holder extends its own lease with its own
                # token, under the same hard-lifetime clamp
                hedge = self.hedges.get(key)
                if (hedge is not None and hedge["expires"] > now
                        and token is not None
                        and token == hedge["token"]):
                    hard = hedge["assigned_at"] + self.max_lease_lifetime
                    if now < hard:
                        hedge["expires"] = \
                            min(now + self.lease_timeout, hard)
                        self.heartbeats_total += 1
                        ok = True
            if ok:
                self._ingest_runtime_advisory(key, msg, now)
        if ok:
            record_heartbeat()
        return {"type": protocol.HEARTBEAT_ACK, "batch_id": batch, "ok": ok}

    def _ingest_runtime_advisory(self, key: tuple[int, str], msg: dict,
                                 now: float) -> None:
        """Consume a token-validated heartbeat's runtime fields: the
        in-flight phase (stamped with THIS clock on transition — the
        hedging re-anchor), any mesh downgrade (scheduler steering), and
        a poison report (immediate quarantine naming the phase).  Caller
        holds self.lock."""
        batch, prover_type = key
        phase = msg.get("phase")
        if isinstance(phase, str) and phase:
            prev = self.lease_phase.get(key)
            if prev is None or prev[0] != phase:
                self.lease_phase[key] = (phase, now)
        prover_id = msg.get("prover_id")
        degraded = msg.get("degraded")
        if prover_id is not None and isinstance(degraded, dict):
            st = self.prover_stats.setdefault(
                prover_id, {"completed": 0, "ewma": None,
                            "last_seen": now})
            st["degraded"] = {"from": str(degraded.get("from")),
                              "to": str(degraded.get("to"))}
        poison = msg.get("poison")
        if isinstance(poison, dict):
            from ..utils.metrics import record_quarantine

            self.poison_reports_total += 1
            detail = f"nan_poison in phase {poison.get('phase')!r}"
            self._clear_lease(key)
            self._note_event("poison-report", batch, prover_type, detail)
            log.error("batch %d reported poisoned by its %s prover (%s)",
                      batch, prover_type, detail)
            if prover_type != self.fallback_type \
                    and batch not in self.quarantined:
                # a poisoned batch cannot be proven by ANY amount of
                # retrying on this backend: quarantine on the FIRST
                # report instead of burning the failure budget
                self.quarantined.add(batch)
                record_quarantine(len(self.quarantined))
                self._note_event("quarantine", batch, prover_type, detail)
                log.error("batch %d quarantined off %r on first poison "
                          "report; falling back to %r", batch,
                          prover_type, self.fallback_type)

    def _handle_submit(self, msg: dict) -> dict:
        # merge the shipped span subtree FIRST: a duplicate submit is the
        # losing leg of a hedged race, and its subtree still belongs in
        # the batch's merged trace (two prover subtrees under one trace);
        # ingestion never raises and is deduped + capped per source
        tracing.TRACER.ingest(msg.get("spans"),
                              source=msg.get("prover_id"))
        batch = msg.get("batch_id")
        prover_type = msg.get("prover_type")
        proof = msg.get("proof")
        token = msg.get("lease_token")
        with self.lock:
            allowed = self._allowed_types()
            if batch in self.quarantined:
                allowed.add(self.fallback_type)
        if not isinstance(batch, int) or prover_type not in allowed \
                or not isinstance(proof, dict):
            return {"type": protocol.ERROR, "message": "bad submit"}
        key = (batch, prover_type)
        with self.lock:
            duplicate = self.rollup.get_proof(batch, prover_type) \
                is not None
            if duplicate:
                self.duplicate_submits_total += 1
                self._note_event("duplicate-submit", batch, prover_type)
        if duplicate:
            # duplicate submit -> no-op ACK (reference parity: the store
            # keeps the first proof; the prover moves on).  This is also
            # the losing leg of a hedged assignment — first result wins,
            # and the loser's work is acknowledged without touching
            # lease, failure, or quarantine state.
            faults.inject("submit.duplicate", proof)
            return {"type": protocol.SUBMIT_ACK, "batch_id": batch}
        with self.lock:
            hedge = self.hedges.get(key)
            if key not in self.assignments and hedge is None:
                # unsolicited: never assigned (or already settled and
                # cleaned up) — do not let an arbitrary connection write
                # into the proof store
                self.unsolicited_submits_total += 1
                return {"type": protocol.ERROR,
                        "message": f"no assignment for batch {batch}"}
            # the wire protocol carries no prover identity — the lease
            # token is what distinguishes the granted holder (primary or
            # hedge) from a stale evicted prover or an arbitrary third
            # party
            holds_primary = (token is not None
                             and token == self.lease_tokens.get(key))
            holds_hedge = (token is not None and hedge is not None
                           and token == hedge["token"])
            holds_lease = holds_primary or holds_hedge
        if self.verify_submissions:
            from ..prover.backend import get_backend

            try:
                ok = get_backend(prover_type).verify_submission(proof)
            except Exception:  # noqa: BLE001 — untrusted wire input
                ok = False
            if not ok:
                with self.lock:
                    # re-check under the lock: verification ran outside
                    # it, and the lease may have expired and been
                    # re-granted to a new holder in the meantime
                    hedge = self.hedges.get(key)
                    holds_primary = (token is not None and
                                     token == self.lease_tokens.get(key))
                    holds_hedge = (token is not None and hedge is not None
                                   and token == hedge["token"])
                    holds_lease = holds_primary or holds_hedge
                    if holds_primary:
                        self._clear_lease(key)
                        self.rejected_submits_total += 1
                        self._record_failure(batch, prover_type,
                                             "invalid proof")
                    elif holds_hedge:
                        # the hedge loses its lease, but the primary is
                        # still proving: no failure against the batch
                        self.hedges.pop(key, None)
                        self.rejected_submits_total += 1
                        self._note_event("hedge-rejected", batch,
                                         prover_type, "invalid proof")
                    else:
                        # an invalid proof from a non-holder must not
                        # evict the live holder's lease or burn the
                        # batch's quarantine budget (unauthenticated
                        # downgrade vector)
                        self.stale_submits_total += 1
                if holds_lease:
                    return {"type": protocol.ERROR,
                            "message": f"invalid proof for batch {batch}"}
                from ..utils.metrics import record_stale_submit

                record_stale_submit()
                return {"type": protocol.ERROR,
                        "message": f"stale lease token for batch "
                                   f"{batch}; proof rejected"}
        elif not holds_lease:
            # without submit-time verification the token is the only gate
            # keeping arbitrary connections out of the proof store
            with self.lock:
                self.stale_submits_total += 1
            from ..utils.metrics import record_stale_submit

            record_stale_submit()
            return {"type": protocol.ERROR,
                    "message": f"stale lease token for batch {batch}"}
        with tracing.trace_context(msg.get("trace_id")
                                   or self.batch_traces.get(batch),
                                   msg.get("span_id")):
            with tracing.span("prover.store_proof", batch=batch,
                              prover_type=prover_type):
                proof = faults.inject("coordinator.store_proof", proof)
                self.rollup.store_proof(batch, prover_type, proof)
        with self.lock:
            warm_at_grant = self.lease_warm.get(key)
            started = self._clear_lease(key)
            hedge = self.hedges.pop(key, None)
            if holds_hedge and hedge is not None:
                # the hedge won the race: its own start time is the
                # proving clock, not the straggler's
                started = hedge["assigned_at"]
                warm_at_grant = hedge.get("warm")
            self._note_event("proof-stored", batch, prover_type,
                             "hedge won" if holds_hedge else None)
        # chain-path X-ray: sampled lifecycles of this batch's txs get
        # their proved mark (never raises — telemetry only)
        try:
            from ..perf.chain_path import CHAIN_PATH

            CHAIN_PATH.batch_proved(batch)
        except Exception:  # noqa: BLE001 — telemetry only
            pass
        if started is not None and holds_lease:
            # proving-time metric (reference: set_batch_proving_time,
            # proof_coordinator.rs:286-296) — only meaningful when the
            # submitter is the prover the clock was started for
            from ..utils.metrics import record_batch

            duration = self._now() - started
            # the exemplar ties this observation's bucket to the batch's
            # merged trace in the OpenMetrics exposition
            record_batch(batch, duration,
                         trace_id=self.batch_traces.get(batch))
            prover_id = msg.get("prover_id")
            with self.lock:
                # feed the fleet scheduler: the p99 hedging deadline and
                # this prover's EWMA placement signal.  A batch granted
                # to a prover that reported itself cold is excluded from
                # both — its wall includes AOT compile time, and one
                # such sample would poison the EWMA placement and the
                # p99 hedge deadline for dozens of proofs after
                if warm_at_grant is not False:
                    self.durations.append(duration)
                if prover_id is not None:
                    st = self.prover_stats.setdefault(
                        prover_id, {"completed": 0, "ewma": None,
                                    "last_seen": self._now()})
                    st["completed"] += 1
                    if warm_at_grant is not False:
                        st["ewma"] = duration if st["ewma"] is None else \
                            EWMA_ALPHA * duration \
                            + (1.0 - EWMA_ALPHA) * st["ewma"]
        return {"type": protocol.SUBMIT_ACK, "batch_id": batch}

    def handle_request(self, msg: dict) -> dict:
        with self._inflight_cv:
            self._inflight += 1
        try:
            return self._handle_request(msg)
        finally:
            with self._inflight_cv:
                self._inflight -= 1
                self._inflight_cv.notify_all()

    def _handle_request(self, msg: dict) -> dict:
        mtype = msg.get("type")
        if mtype == protocol.INPUT_REQUEST:
            if msg.get("commit_hash") != self.commit_hash:
                return {"type": protocol.VERSION_MISMATCH,
                        "expected": self.commit_hash}
            prover_type = msg.get("prover_type")
            if prover_type not in self._allowed_types():
                return {"type": protocol.TYPE_NOT_NEEDED}
            warm = msg.get("warm")
            reclaim = msg.get("reclaim")
            if not isinstance(reclaim, dict):
                reclaim = None
            batch, token, reclaimed = self._schedule(
                prover_type, msg.get("prover_id"),
                warm if isinstance(warm, bool) else None, reclaim)
            told = {} if reclaim is None else {
                "reclaim": self._reclaim_outcome(reclaim, prover_type,
                                                 reclaimed)}
            if batch is None:
                return {"type": protocol.TYPE_NOT_NEEDED, **told}
            trace_id = self.trace_for_batch(batch)
            assign_span = None
            with tracing.trace_context(trace_id):
                with tracing.span("prover.assign", batch=batch,
                                  prover_type=prover_type) as sp:
                    program_input = self.rollup.get_prover_input(
                        batch, self.commit_hash)
                    assign_span = sp.span_id if sp else None
            with self.lock:
                self._note_event("assign", batch, prover_type)
            return {"type": protocol.INPUT_RESPONSE, "batch_id": batch,
                    "input": program_input, "format": self.proof_format,
                    "lease_token": token,
                    "trace_id": trace_id, "span_id": assign_span, **told}
        if mtype == protocol.HEARTBEAT:
            return self._handle_heartbeat(msg)
        if mtype == protocol.PROOF_SUBMIT:
            return self._handle_submit(msg)
        return {"type": protocol.ERROR, "message": f"unknown type {mtype}"}

    # ------------------------------------------------------------------
    def stats_json(self) -> dict:
        """Health-endpoint view of the resilience state."""
        with self.lock:
            return {
                "liveAssignments": sum(
                    1 for d in self.assignments.values()
                    if d > self._now()),
                "reassignments": self.reassignments_total,
                "reclaims": self.reclaims_total,
                "heartbeats": self.heartbeats_total,
                "rejectedSubmits": self.rejected_submits_total,
                "unsolicitedSubmits": self.unsolicited_submits_total,
                "staleSubmits": self.stale_submits_total,
                "quarantined": sorted(self.quarantined),
                "failures": {f"{num}/{ptype}": count
                             for (num, ptype), count
                             in sorted(self.failures.items())},
                "recentEvents": list(self.events),
                "scheduler": self._scheduler_stats_locked(),
                "runtime": self._runtime_stats_locked(),
            }

    def _runtime_stats_locked(self) -> dict:
        """This process's prover-runtime counters (resumes, ladder
        retries, checkpoint traffic) plus what the fleet's heartbeats
        reported: which provers run degraded and which phase each live
        lease is in.  Caller holds self.lock."""
        from ..prover import runtime_errors as rt_mod

        now = self._now()
        stats = rt_mod.runtime_stats()
        stats["poisonReports"] = self.poison_reports_total
        stats["degradedProvers"] = {
            pid: st["degraded"]
            for pid, st in sorted(self.prover_stats.items())
            if st.get("degraded") is not None}
        stats["livePhases"] = [
            {"batch": num, "proverType": ptype, "phase": phase,
             "sincePhaseSeconds": max(0.0, now - since)}
            for (num, ptype), (phase, since)
            in sorted(self.lease_phase.items())
            if self.assignments.get((num, ptype), 0.0) > now
            or ((num, ptype) in self.hedges
                and self.hedges[(num, ptype)]["expires"] > now)]
        return stats

    def _scheduler_stats_locked(self) -> dict:
        """Caller holds self.lock."""
        now = self._now()
        deadline = self._hedge_deadline()
        return {
            "policy": self.scheduler_policy,
            "queueDepth": self.queue_depth,
            "hedgedAssignments": self.hedged_assignments_total,
            "duplicateSubmits": self.duplicate_submits_total,
            "coldDeferrals": self.cold_deferrals_total,
            "hedgeDeadlineSeconds": deadline,
            "liveHedges": [
                {"batch": num, "proverType": ptype,
                 "reason": h.get("reason"),
                 "proverId": h.get("prover_id")}
                for (num, ptype), h in sorted(self.hedges.items())
                if h["expires"] > now],
            "provers": {
                pid: {"completed": st["completed"],
                      "ewmaSeconds": st["ewma"],
                      "liveLeases": self._live_leases_held(pid, now),
                      "idleSeconds": max(0.0, now - st["last_seen"]),
                      "warm": st.get("warm"),
                      "coldDeferrals": st.get("cold_deferrals", 0),
                      "degraded": st.get("degraded")}
                for pid, st in sorted(self.prover_stats.items())},
        }

    # ------------------------------------------------------------------
    def start(self):
        coordinator = self

        class Handler(socketserver.StreamRequestHandler):
            def handle(self):
                while True:
                    try:
                        msg = protocol.recv_msg_file(self.rfile)
                    except (ValueError, ConnectionError):
                        break
                    if msg is None:
                        break
                    try:
                        resp = coordinator.handle_request(msg)
                    except Exception as e:  # noqa: BLE001 — internal
                        # failure (or an injected one): drop the
                        # connection, keep the lease; expiry re-assigns
                        log.warning("coordinator request failed: %s", e)
                        break
                    try:
                        protocol.send_msg(self.connection, resp)
                    except (ConnectionError, OSError):
                        break

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        if self._server is not None:
            return self    # idempotent: Sequencer.start() re-enters here
        self._server = Server((self.host, self.port), Handler)
        self.port = self._server.server_address[1]
        threading.Thread(target=self._server.serve_forever,
                         daemon=True).start()
        return self

    def stop(self, timeout: float = 5.0) -> bool:
        """Stop accepting connections, then wait (bounded) for in-flight
        requests to finish so a proof submit already past the wire lands
        in the rollup store instead of being dropped mid-handler.
        Returns True when the drain completed inside the deadline."""
        if self._server:
            self._server.shutdown()
            self._server.server_close()
            # allow stop -> start cycles (sequencer HA re-homes the
            # prover fleet across demote/promote): a later start()
            # rebinds the SAME port (self.port was pinned at first
            # bind), so prover endpoint lists stay valid
            self._server = None
        deadline = time.monotonic() + timeout
        with self._inflight_cv:
            while self._inflight > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    log.warning("%d coordinator request(s) still in flight "
                                "after %.1fs drain deadline; their leases "
                                "will expire and reassign", self._inflight,
                                timeout)
                    return False
                self._inflight_cv.wait(remaining)
        return True
