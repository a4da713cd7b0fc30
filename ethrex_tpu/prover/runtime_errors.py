"""Runtime-error classification and the degraded-mesh fallback ladder.

A crash inside the 86-96s `TpuBackend.prove` wall used to be
indistinguishable from a poison batch: any exception burned the
coordinator's quarantine budget and could downgrade a perfectly
provable batch to the exec fallback.  This module classifies what the
accelerator runtime actually threw and routes each class differently:

    oom          XLA RESOURCE_EXHAUSTED / allocation failure — the
                 batch does not fit the current mesh.  Transient:
                 retry the failed phase down the degradation ladder
                 (mesh/2 -> single device of the same mesh); never
                 burns quarantine budget.  Below one device there is
                 nowhere to fall: the device's own error propagates.
    device_lost  a device or slice dropped out (connection to the
                 accelerator lost, slice health check failed, or the
                 injected `device.lost` fault).  Transient: same
                 ladder.
    nan_poison   a phase produced non-finite or out-of-field outputs —
                 the trace itself is poisoned, retrying cannot help.
                 Quarantined immediately with the offending phase
                 named; zero retries.
    unknown      everything else propagates unchanged (a genuine bug
                 should fail loudly, not hide behind a retry loop).

The ladder reuses the existing machinery end to end: rungs are built
with `parallel.mesh` device slicing, phase programs for a fallback
layout hydrate through the same `stark/prover._phases` path (PR-12
exec-cache hydration applies), and completed-phase checkpoints
(prover/checkpoint) carry across rungs because proofs are
bit-identical on any layout.  A `memory_gate` compares the per-device
working set XLA's `memory_analysis()` reported when the AIR's phase
programs were compiled (`perf/hlo_introspect`) against live free device
memory (`utils/jax_cache.runtime_telemetry`) to walk the same ladder
BEFORE an OOM instead of after.  Neither the ladder nor the gate ever
leaves the mesh's platform: a layout that fits nowhere raises
`MemoryGateError`.

Env knobs (documented in docs/PROVER_RESILIENCE.md):
  ETHREX_MESH_DEGRADE_OFF    "1" disables the ladder and the memory
                             gate (transient errors propagate)
  ETHREX_MEM_GATE_HEADROOM   fraction of free device memory the
                             estimated working set may fill before the
                             gate shrinks the mesh (default 0.8)
"""

from __future__ import annotations

import os
import threading

from ..utils import faults


_LOCK = threading.Lock()
STATS = {"oom_retries": 0, "device_lost_retries": 0, "nan_poisons": 0,
         "degradations": 0, "memory_gate_shrinks": 0, "phase_resumes": 0}
_LAST_DEGRADATION: dict | None = None

_OOM_MARKERS = ("resource_exhausted", "out of memory", "out_of_memory",
                "failed to allocate", "allocation failure", "oom")
_DEVICE_LOST_MARKERS = ("device.lost", "device lost", "device_lost",
                        "device failed", "device halted", "data loss",
                        "dataloss", "tpu slice", "slice health",
                        "ici failure", "lost connection to the device")


class NanPoisonError(RuntimeError):
    """A phase emitted non-finite / out-of-field values: the batch is
    poisoned, not the runtime.  Carries the offending phase so the
    quarantine reason names it."""

    def __init__(self, phase: str, detail: str = ""):
        self.phase = phase
        self.detail = detail
        super().__init__(
            f"non-finite/out-of-field output in phase {phase!r}"
            + (f": {detail}" if detail else ""))


class TransientPhaseError(RuntimeError):
    """Internal routing signal: a phase failed with a transient class
    (`oom` / `device_lost`); the prove loop retries it down the
    degradation ladder instead of failing the lease."""

    def __init__(self, kind: str, phase: str, cause: BaseException):
        self.kind = kind
        self.phase = phase
        self.cause = cause
        super().__init__(f"{kind} in phase {phase!r}: {cause}")


def classify(exc: BaseException) -> str:
    """Map an exception from a device phase onto the classification."""
    if isinstance(exc, NanPoisonError):
        return "nan_poison"
    if isinstance(exc, TransientPhaseError):
        return exc.kind
    msg = str(exc).lower()
    for marker in _OOM_MARKERS:
        if marker in msg:
            return "oom"
    for marker in _DEVICE_LOST_MARKERS:
        if marker in msg:
            return "device_lost"
    if isinstance(exc, MemoryError):
        return "oom"
    return "unknown"


def _walk_values(value):
    """Yield every scalar reachable in a phase-artifact structure."""
    import numpy as np

    if isinstance(value, dict):
        for v in value.values():
            yield from _walk_values(v)
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from _walk_values(v)
    elif isinstance(value, np.ndarray):
        yield value
    elif isinstance(value, (int, float, np.integer, np.floating)):
        yield value


def check_phase_outputs(phase: str, arts) -> None:
    """Validate the host-visible artifacts of a completed phase: every
    field element canonical-range (< BabyBear P), every float finite.
    A violation is a poisoned batch, raised as NanPoisonError."""
    import numpy as np

    from ..ops import babybear as bb

    if isinstance(arts, dict) and arts.get("__corrupt__"):
        _note_nan_poison(phase)
        raise NanPoisonError(phase, "corrupted artifact envelope")
    for v in _walk_values(arts):
        if isinstance(v, np.ndarray):
            if np.issubdtype(v.dtype, np.floating):
                if not np.all(np.isfinite(v)):
                    _note_nan_poison(phase)
                    raise NanPoisonError(phase, "non-finite array value")
            elif np.issubdtype(v.dtype, np.integer):
                if v.size and int(v.max(initial=0)) >= bb.P:
                    _note_nan_poison(phase)
                    raise NanPoisonError(phase, "out-of-field array value")
        elif isinstance(v, float):
            if v != v or v in (float("inf"), float("-inf")):
                _note_nan_poison(phase)
                raise NanPoisonError(phase, "non-finite value")
        else:
            if not 0 <= int(v) < bb.P:
                _note_nan_poison(phase)
                raise NanPoisonError(phase, "out-of-field value")


def guard_phase(phase: str, air_name: str, fn):
    """Run one device phase under the fault legs and the classification.

    Fires the `backend.phase` error/delay legs and the `device.lost`
    site on entry (an error rule there simulates a slice dropping out
    mid-phase), then classifies anything `fn` raises: transient
    classes re-raise as TransientPhaseError for the ladder, poison and
    unknown classes propagate.  Stamps the in-flight phase on the
    active batch context so heartbeats report it (and the hedging
    deadline re-anchors on every transition)."""
    from . import checkpoint

    ctx = checkpoint.current_context()
    if ctx is not None:
        job = checkpoint.current_job()
        ctx.set_phase(f"{job}.{phase}" if job else phase)
    try:
        faults.inject("backend.phase", {"phase": phase, "air": air_name},
                      kinds=("error", "delay"))
        faults.inject("device.lost")
        return fn()
    except (NanPoisonError, TransientPhaseError):
        raise
    except Exception as exc:
        kind = classify(exc)
        if kind in ("oom", "device_lost"):
            raise TransientPhaseError(kind, phase, exc) from exc
        raise


def screen_outputs(phase: str, arts):
    """The nan/corrupt leg: offer the phase's host artifacts to the
    `backend.phase` corrupt rules, then range-check what (possibly
    mangled) came back.  Returns the artifacts for downstream use."""
    arts = faults.inject("backend.phase", arts, kinds=("corrupt", "torn"))
    check_phase_outputs(phase, arts)
    return arts


# -- degradation ladder -----------------------------------------------------

def ladder_enabled() -> bool:
    return os.environ.get("ETHREX_MESH_DEGRADE_OFF") != "1"


def degradation_ladder(mesh) -> list:
    """The fallback rungs below `mesh`, best first: half the devices,
    then a single device — always devices of `mesh` itself, never
    another platform.  Rungs equal to the current layout are dropped;
    an empty list (no mesh, or one device) means nowhere left to fall
    and the caller re-raises the device's own error."""
    if mesh is None or not ladder_enabled():
        return []
    import numpy as np

    from jax.sharding import Mesh

    from ..parallel import mesh as mesh_lib

    devs = list(mesh.devices.flat)
    rungs = []
    if len(devs) >= 4:
        rungs.append(Mesh(np.array(devs[: len(devs) // 2]),
                          (mesh_lib.AXIS,)))
    if len(devs) >= 2:
        rungs.append(Mesh(np.array(devs[:1]), (mesh_lib.AXIS,)))
    return rungs


def note_resume(phase: str) -> None:
    """One completed phase skipped on restart (loaded from checkpoint)."""
    with _LOCK:
        STATS["phase_resumes"] += 1
    from ..utils.metrics import record_phase_resume

    record_phase_resume(phase)
    from . import checkpoint

    ctx = checkpoint.current_context()
    if ctx is not None:
        with ctx.lock:
            ctx.resumes += 1


def note_transient_retry(kind: str, phase: str) -> None:
    with _LOCK:
        key = "oom_retries" if kind == "oom" else "device_lost_retries"
        STATS[key] += 1
    from ..utils.metrics import record_oom_retry

    record_oom_retry(phase)


def note_degradation(frm_label: str, to_label: str,
                     reason: str = "ladder") -> None:
    global _LAST_DEGRADATION
    with _LOCK:
        STATS["degradations"] += 1
        if reason == "memory_gate":
            STATS["memory_gate_shrinks"] += 1
        _LAST_DEGRADATION = {"from": frm_label, "to": to_label,
                             "reason": reason}
    from ..utils.metrics import record_mesh_degradation

    record_mesh_degradation(frm_label, to_label)
    from . import checkpoint

    ctx = checkpoint.current_context()
    if ctx is not None:
        ctx.note_degraded(frm_label, to_label)


def _note_nan_poison(phase: str) -> None:
    with _LOCK:
        STATS["nan_poisons"] += 1
    from ..utils.metrics import record_nan_poison

    record_nan_poison(phase)


# -- pre-prove memory gate --------------------------------------------------

class MemoryGateError(RuntimeError):
    """The AIR's working set fits no layout of its own platform.  The
    gate never moves a proof to another platform; it says what it
    compared and lets the caller decide."""

    def __init__(self, air_name: str, est_bytes: float,
                 free_bytes: float, layout: str):
        self.air_name = air_name
        self.est_bytes = est_bytes
        self.free_bytes = free_bytes
        super().__init__(
            f"memory gate: {air_name} needs an estimated "
            f"{int(est_bytes)} bytes per device but layout {layout} has "
            f"{int(free_bytes)} bytes free per device, and no smaller "
            "layout of the same platform fits")


def _estimated_bytes(air_name: str):
    """Largest per-device working set among this AIR's compiled phase
    programs: XLA's memory_analysis() (argument + output + temp + alias
    bytes) as captured by perf/hlo_introspect at the same compile that
    built them.  None before the AIR's first compile."""
    from ..perf import hlo_introspect

    peaks = [cell["memory"]["peakBytes"]
             for cell in hlo_introspect.REGISTRY.report().get("kernels", [])
             if cell.get("air") == air_name
             and (cell.get("memory") or {}).get("peakBytes")]
    return max(peaks) if peaks else None


def _available_bytes(mesh):
    """Free memory of the layout's tightest device (the estimate is per
    device) from live telemetry; None when the backend does not report
    limits (CPU)."""
    from ..utils.jax_cache import runtime_telemetry

    devices = runtime_telemetry().get("devices", [])
    if mesh is None:
        devices = devices[:1]               # the default device
    else:
        ids = {int(d.id) for d in mesh.devices.flat}
        devices = [d for d in devices if d.get("id") in ids]
    free = [max(0, int(m["bytes_limit"]) - int(m.get("bytes_in_use", 0)))
            for m in (d.get("memory") or {} for d in devices)
            if m.get("bytes_limit")]
    return min(free) if free else None


def memory_gate(air_name: str, mesh, est_bytes=None, avail_fn=None):
    """Shrink the mesh BEFORE an OOM: if the AIR's estimated per-device
    working set exceeds the headroom share of free memory on the
    current layout, walk the degradation ladder — smaller meshes of the
    same devices, whose tightest device may have more room; the
    estimate stays the one compiled for the current layout, so a rung
    is taken optimistically and the OOM ladder still stands behind it —
    until a rung fits.  Returns the layout to prove on;
    identical to `mesh` when data is missing (first compile, or a
    backend that reports no limit) or everything fits.  Raises
    MemoryGateError when nothing on the ladder fits: the proof never
    leaves its platform."""
    if not ladder_enabled():
        return mesh
    est = est_bytes if est_bytes is not None else _estimated_bytes(air_name)
    if est is None:
        return mesh
    try:
        headroom = float(os.environ.get("ETHREX_MEM_GATE_HEADROOM", "0.8"))
    except ValueError:
        headroom = 0.8
    avail_of = avail_fn or _available_bytes
    from ..parallel import mesh as mesh_lib

    avail = avail_of(mesh)
    if avail is None or est <= headroom * avail:
        return mesh
    for rung in degradation_ladder(mesh):
        rung_avail = avail_of(rung)
        if rung_avail is None or est <= headroom * rung_avail:
            note_degradation(mesh_lib.shape_label(mesh),
                             mesh_lib.shape_label(rung),
                             reason="memory_gate")
            return rung
    raise MemoryGateError(air_name, est, avail,
                          mesh_lib.shape_label(mesh))


def runtime_stats() -> dict:
    """Live classification/ladder counters for ethrex_health
    (l2.prover.runtime) and the monitor panel."""
    with _LOCK:
        out = {"oomRetries": STATS["oom_retries"],
               "deviceLostRetries": STATS["device_lost_retries"],
               "nanPoisons": STATS["nan_poisons"],
               "degradations": STATS["degradations"],
               "memoryGateShrinks": STATS["memory_gate_shrinks"],
               "phaseResumes": STATS["phase_resumes"]}
        if _LAST_DEGRADATION is not None:
            out["lastDegradation"] = dict(_LAST_DEGRADATION)
    try:
        from . import checkpoint

        out["checkpoints"] = checkpoint.runtime_stats()
    except Exception:
        pass
    return out


def reset_stats() -> None:
    """Test hook: zero the module counters."""
    global _LAST_DEGRADATION
    with _LOCK:
        for key in STATS:
            STATS[key] = 0
        _LAST_DEGRADATION = None
