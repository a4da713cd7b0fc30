"""Continuous stage-attribution profiler (docs/PERFORMANCE.md).

One process-wide accumulator keyed (component, stage): every timed leg
of the prover and the L1 import path lands here, either directly
(``record_stage`` from the import/EVM/trie hot paths, which pre-date
tracing spans at that granularity) or through the tracing observer
installed below (the existing block_until_ready-bounded prover stage
spans flow in with zero changes to the prover).

Components in the stock build:

- ``stark``    — the DEEP-FRI phase stages (trace_lde, merkle_commit,
                 quotient, openings, fri_fold, query)
- ``prover``   — TpuBackend's coarse pipeline stages (execute,
                 state_proof, vm_circuits, binding, aggregate,
                 groth16_wrap)
- ``l1_import``— execute / merkleize / store_write legs of add_block
                 and the pipelined importer
- ``evm``      — sig_recovery vs opcode_loop split inside execute_tx
- ``trie``     — sorted bulk commit (build_from_sorted)

Contract: ``record`` is a dict update under one lock (~1us) and NEVER
raises; with nothing recording the profiler costs nothing.  The
``jax.profiler`` capture is opt-in via ``configure()`` /
``ETHREX_PROFILE_DIR`` and equally never-raise — a broken profiler
plugin degrades to no trace file, not a failed prove.
"""

from __future__ import annotations

import logging
import os
import threading
import time

log = logging.getLogger("ethrex_tpu.perf")

# bound on distinct (component, stage) keys — runaway-cardinality guard
MAX_KEYS = 512

# tracing-span stage -> component for the observer (spans carry a stage
# attr but no component; the split mirrors where each span lives).  A
# component's stages never nest in one another, so their seconds add up
# and `tree()`'s shares are shares: `ckpt` (the checkpoint copies and
# writes) runs between the STARK's phases, under none of them
_STARK_STAGES = frozenset(
    ("trace_lde", "merkle_commit", "quotient", "openings", "fri_fold",
     "query", "ckpt"))
_BACKEND_STAGES = frozenset(
    ("execute", "state_proof", "vm_circuits", "binding", "aggregate",
     "groth16_wrap"))


class StageProfiler:
    """Thread-safe (component, stage) -> count/total/max/last wall-clock
    accumulator."""

    def __init__(self):
        self._lock = threading.Lock()
        # (component, stage) -> [count, total, max, last, last_ts]
        self._cells: dict[tuple[str, str], list] = {}
        self.dropped = 0

    def record(self, component: str, stage: str, seconds: float) -> None:
        try:
            key = (str(component), str(stage))
            sec = float(seconds)
            now = time.time()
            with self._lock:
                cell = self._cells.get(key)
                if cell is None:
                    if len(self._cells) >= MAX_KEYS:
                        self.dropped += 1
                        return
                    self._cells[key] = [1, sec, sec, sec, now]
                    return
                cell[0] += 1
                cell[1] += sec
                if sec > cell[2]:
                    cell[2] = sec
                cell[3] = sec
                cell[4] = now
        except Exception:
            pass

    def stage_totals(self, component: str) -> dict[str, float]:
        """{stage: total seconds} for one component (bench attribution
        takes before/after deltas of this)."""
        with self._lock:
            return {stage: cell[1]
                    for (comp, stage), cell in self._cells.items()
                    if comp == component}

    def tree(self) -> dict:
        """The attribution tree: component -> stages with count / total /
        mean / max / last / share-of-component."""
        with self._lock:
            cells = {k: list(v) for k, v in self._cells.items()}
            dropped = self.dropped
        out: dict = {}
        for (comp, stage), (count, total, mx, last, last_ts) in \
                sorted(cells.items()):
            node = out.setdefault(
                comp, {"totalSeconds": 0.0, "stages": {}})
            node["totalSeconds"] += total
            node["stages"][stage] = {
                "count": count,
                "totalSeconds": round(total, 6),
                "meanSeconds": round(total / count, 6) if count else 0.0,
                "maxSeconds": round(mx, 6),
                "lastSeconds": round(last, 6),
                "lastTs": last_ts,
            }
        for node in out.values():
            tot = node["totalSeconds"]
            node["totalSeconds"] = round(tot, 6)
            for st in node["stages"].values():
                st["share"] = round(st["totalSeconds"] / tot, 4) \
                    if tot > 0 else 0.0
        return {"components": out, "droppedKeys": dropped}

    def reset(self) -> None:
        with self._lock:
            self._cells.clear()
            self.dropped = 0


PROFILER = StageProfiler()


def record_stage(component: str, stage: str, seconds: float) -> None:
    """Module-level hook used by the import/EVM/trie hot paths.  Never
    raises (hot-path contract)."""
    PROFILER.record(component, stage, seconds)


def _span_observer(name, stage, seconds):
    """Fold tracing stage spans into the attribution tree.  Stage names
    unknown to the static maps land under component 'other' so a new
    span is visible the day it ships."""
    if stage in _STARK_STAGES:
        PROFILER.record("stark", stage, seconds)
    elif stage in _BACKEND_STAGES or stage.startswith("vm_circuits/"):
        # per-slice vm_circuits/<air> spans (parallel mesh proving)
        # attribute to the prover component alongside the aggregate
        PROFILER.record("prover", stage, seconds)
    else:
        PROFILER.record("other", stage, seconds)


def _install() -> None:
    from ..utils import tracing

    if _span_observer not in tracing.STAGE_OBSERVERS:
        tracing.STAGE_OBSERVERS.append(_span_observer)


try:
    _install()
except Exception:
    pass


# ---------------------------------------------------------------------------
# opt-in jax.profiler trace capture

_PROFILE_DIR: str | None = os.environ.get("ETHREX_PROFILE_DIR") or None
_TRACE_LOCK = threading.Lock()
_TRACE_ACTIVE = False


def configure(profile_dir: str | None) -> None:
    """Set (or clear, with None) the jax.profiler trace destination."""
    global _PROFILE_DIR
    _PROFILE_DIR = profile_dir or None


def configured_dir() -> str | None:
    return _PROFILE_DIR


class capture:
    """Context manager wrapping a region in a ``jax.profiler`` trace
    when a destination is configured; a transparent no-op otherwise.

    Single-flight: nested/concurrent captures degrade to no-ops (the
    profiler cannot nest traces).  Never raises — start/stop failures
    log at debug and the wrapped body always runs.
    """

    __slots__ = ("_name", "_started")

    def __init__(self, name: str = "prove"):
        self._name = name
        self._started = False

    def __enter__(self):
        global _TRACE_ACTIVE
        directory = _PROFILE_DIR
        if not directory:
            return self
        try:
            with _TRACE_LOCK:
                if _TRACE_ACTIVE:
                    return self
                _TRACE_ACTIVE = True
            self._started = True
            import jax

            os.makedirs(directory, exist_ok=True)
            jax.profiler.start_trace(directory)
            log.info("jax.profiler trace started (%s) -> %s",
                     self._name, directory)
        except Exception as exc:
            log.debug("jax.profiler start failed: %s", exc)
            if self._started:
                with _TRACE_LOCK:
                    _TRACE_ACTIVE = False
                self._started = False
        return self

    def __exit__(self, exc_type, exc, tb):
        global _TRACE_ACTIVE
        if self._started:
            try:
                import jax

                jax.profiler.stop_trace()
            except Exception as e:
                log.debug("jax.profiler stop failed: %s", e)
            with _TRACE_LOCK:
                _TRACE_ACTIVE = False
            self._started = False
        return False
