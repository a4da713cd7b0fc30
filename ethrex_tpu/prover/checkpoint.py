"""Phase-level proof checkpoints: crash-only proving for `TpuBackend`.

A prove is a sequence of device phases (execute -> per-AIR
commit/quotient/open/fri -> binding/aggregate) stitched together by a
host Fiat-Shamir transcript.  Each completed phase persists ONE
content-addressed envelope here — the phase's host-visible artifacts,
numpy copies of the device intermediates the later phases consume, and
a snapshot of the transcript sponge — so a restarted `ProverClient`
holding a *fresh lease for the same batch* replays the transcript from
the last completed phase instead of re-proving from scratch.  Bounded
loss is <= 1 phase (the one in flight when the process died) and the
resumed proof is byte-identical: all arithmetic is exact u32 and the
sponge snapshot pins every later challenge.

Key schema (docs/PROVER_RESILIENCE.md "Runtime failures"): an entry's
filename is the SHA-256 over the JSON-canonical key parts — batch id,
job name, AIR cache key, trace shape, STARK params, phase — joined
with the environment half (code fingerprint, jax/jaxlib versions,
shared with utils/exec_cache).  The *mesh layout* and *lease token*
are deliberately recorded as envelope metadata, NOT key material:
proofs are bit-identical across mesh layouts, so the degradation
ladder (prover/runtime_errors) must be able to resume a phase prefix
written at mesh=2x4 on a single device, and a restarted client always
holds a fresh token for the same batch.  The token an envelope
records is also how the restarted client gets that lease back without
waiting out the dead one: `in_flight()` reads it off the disk and the
client presents it to the coordinator (docs/PROVER_RESILIENCE.md
"Reclaiming a lease").

What an envelope holds: every large array ONCE, in the row layout the
query phase gathers from (`commit`: `lde_rows`, the trace tree's
levels; `quotient`: `chunks`, `q_rows`, the quotient tree's levels).
The column layouts the next device phase consumes (`lde_cols`,
`q_lde`) are exact rearrangements of those, rebuilt on the host only
when a phase is resumed (stark/prover.py, span `ckpt.rebuild`).

Records are written atomically (tempfile + os.replace in the entry's
own directory, on the calling thread, landed before `store` returns)
and framed as
  MAGIC | crc32 | count, header length, buffer lengths (u64 each)
        | header | buffer 0 | buffer 1 | ...
The header is a protocol-5 pickle of the record with every array's
memory taken out of band: it holds no array data.  The buffers are the
arrays' own memory, checksummed in place (the crc32 runs over
everything behind it in the file, buffer by buffer) and written
straight to the file: no blob, no frame copy.  A torn, truncated or
garbage file fails the frame check and is discarded for a clean fresh
prove (`proof_ckpt_discards_total`) — the loader never raises.

Env knobs (documented in docs/PROVER_RESILIENCE.md):
  ETHREX_PROOF_CKPT_DIR  checkpoint directory (default
                         <cache root>/proof_ckpt, utils/jax_cache)
  ETHREX_PROOF_CKPT_OFF  "1" disables checkpoint stores and loads
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import os
import pickle
import struct
import tempfile
import threading
import time
import typing
import zlib

from ..utils import tracing

_SCHEMA = 2
_MAGIC = b"ETPC"
# magic, crc32, count of buffers, header length; a u64 length for each
# buffer follows.  The crc covers the file from `_CRC_FROM` on.
_HEAD = struct.Struct(">4sI2Q")
_CRC_FROM = 8
_SUFFIX = ".ckpt"

_LOCK = threading.Lock()
_CONFIGURED_DIR: str | None = None
# `misses` counts loads that found no envelope (every phase of a fresh
# prove probes once); a hit is a `ckpt.load` span, a miss only this.
STATS = {"stores": 0, "loads": 0, "discards": 0, "misses": 0}

# The per-thread prove context: ProverClient activates one around
# backend.prove; TpuBackend re-activates it on its job worker threads
# (threading.local does not inherit across ThreadPoolExecutor workers,
# same re-entry discipline as tracing.trace_context).
_TLS = threading.local()


class BatchContext:
    """Mutable per-batch prove state shared between the prove thread(s)
    and the heartbeat thread: identity (batch id + the lease token that
    granted this attempt), the in-flight phase for heartbeat stamping,
    and any mesh downgrade the degradation ladder applied."""

    def __init__(self, batch_id, lease_token=None):
        self.batch_id = batch_id
        self.lease_token = lease_token
        self.lock = threading.Lock()
        self.phase: str | None = None
        self.phase_started: float | None = None
        self.degraded: dict | None = None
        self.resumes = 0

    def set_phase(self, phase: str | None) -> None:
        with self.lock:
            if phase != self.phase:
                self.phase = phase
                self.phase_started = time.time()

    def note_degraded(self, frm: str, to: str) -> None:
        with self.lock:
            if self.degraded is None:
                self.degraded = {"from": frm, "to": to}
            else:
                # ladder walked further down: keep the original rung as
                # the origin, report the latest rung as the floor
                self.degraded = {"from": self.degraded["from"], "to": to}

    def snapshot(self) -> dict:
        """Heartbeat-safe copy of the advisory fields."""
        with self.lock:
            out = {"phase": self.phase, "phase_started": self.phase_started}
            if self.degraded is not None:
                out["degraded"] = dict(self.degraded)
            return out


def current_context() -> BatchContext | None:
    return getattr(_TLS, "ctx", None)


def current_job() -> str | None:
    return getattr(_TLS, "job", None)


@contextlib.contextmanager
def activate(ctx: BatchContext | None, job: str | None = None):
    """Bind a batch context (and optionally a job name) to this thread.
    `batch_context` uses it on the client thread; TpuBackend's job
    workers re-enter with the parent's context."""
    prev_ctx = getattr(_TLS, "ctx", None)
    prev_job = getattr(_TLS, "job", None)
    _TLS.ctx = ctx
    if job is not None:
        _TLS.job = job
    try:
        yield ctx
    finally:
        _TLS.ctx = prev_ctx
        _TLS.job = prev_job


@contextlib.contextmanager
def batch_context(batch_id, lease_token=None):
    """Open (or reopen, after a restart) the checkpointed prove of one
    batch.  The yielded context carries the advisory state the
    heartbeat thread reports (in-flight phase, degradation)."""
    ctx = BatchContext(batch_id, lease_token=lease_token)
    with activate(ctx):
        yield ctx


@contextlib.contextmanager
def job_scope(job: str):
    """Name the prove job (state_proof / vm_circuits/TransferAir /
    binding / ...) for every checkpoint written under it."""
    prev = getattr(_TLS, "job", None)
    _TLS.job = job
    try:
        yield
    finally:
        _TLS.job = prev


# -- store layout -----------------------------------------------------------

def set_checkpoint_dir(path: str | None) -> None:
    """Explicit directory override (tests); beats the env knob."""
    global _CONFIGURED_DIR
    with _LOCK:
        _CONFIGURED_DIR = path


def checkpoint_dir() -> str:
    with _LOCK:
        configured = _CONFIGURED_DIR
    if configured:
        return configured
    env = os.environ.get("ETHREX_PROOF_CKPT_DIR")
    if env:
        return env
    from ..utils.jax_cache import cache_dir as _cache_root

    return os.path.join(_cache_root(), "proof_ckpt")


def enabled() -> bool:
    return os.environ.get("ETHREX_PROOF_CKPT_OFF") != "1"


def record_ckpt_store() -> None:
    from ..utils.metrics import METRICS

    METRICS.inc("proof_ckpt_stores_total", 1,
                "Proof phase checkpoints persisted: completed prove "
                "phases a restarted prover can resume from")


def record_ckpt_load() -> None:
    from ..utils.metrics import METRICS

    METRICS.inc("proof_ckpt_loads_total", 1,
                "Proof phase checkpoints loaded on resume: phases "
                "skipped instead of re-proven after a restart")


def record_ckpt_discard() -> None:
    from ..utils.metrics import METRICS

    METRICS.inc("proof_ckpt_discards_total", 1,
                "Proof phase checkpoints discarded as torn, truncated "
                "or garbage: the prove falls back to a fresh run")


def _batch_dir(batch_id) -> str:
    tag = hashlib.sha256(repr(batch_id).encode()).hexdigest()[:16]
    return os.path.join(checkpoint_dir(), f"batch_{tag}")


def _entry_path(batch_id, parts: dict) -> str:
    from ..utils import exec_cache

    key = {"schema": _SCHEMA, "parts": parts,
           "env": {"code": exec_cache._code_fingerprint(),
                   **{k: v for k, v in exec_cache._env_parts().items()
                      if k in ("jax", "jaxlib")}}}
    blob = json.dumps(key, sort_keys=True, separators=(",", ":"),
                      default=str)
    digest = hashlib.sha256(blob.encode()).hexdigest()
    return os.path.join(_batch_dir(batch_id), digest + _SUFFIX)


def store(batch_id, parts: dict, payload, meta: dict | None = None) -> bool:
    """Persist one phase envelope; atomic and never raises.  Returns
    True when the record landed."""
    if not enabled():
        return False
    # one span for the whole write (header pickle, crc, file): the
    # execute envelope and every per-proof phase pass through here
    with tracing.span("ckpt.store", stage="ckpt") as sp:
        try:
            tracing.set_attrs(sp, phase=parts.get("phase"),
                              job=parts.get("job"))
            pieces = _frame({"schema": _SCHEMA, "batch_id": batch_id,
                             "parts": parts, "meta": dict(meta or {}),
                             "payload": payload})
            path = _entry_path(batch_id, parts)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    for piece in pieces:
                        f.write(piece)
                os.replace(tmp, path)
            except BaseException:
                with contextlib.suppress(OSError):
                    os.unlink(tmp)
                raise
            tracing.set_attrs(sp, disk_bytes=sum(len(p) for p in pieces))
            with _LOCK:
                STATS["stores"] += 1
            record_ckpt_store()
            return True
        except Exception:
            return False


def _frame(rec: dict) -> list:
    """The pieces of one envelope file, in file order.  The arrays'
    buffers are views of the arrays' own memory: nothing is copied."""
    out_of_band: list = []
    header = pickle.dumps(rec, protocol=5,
                          buffer_callback=out_of_band.append)
    raws = [buf.raw() for buf in out_of_band]
    table = struct.pack(f">{2 + len(raws)}Q", len(raws), len(header),
                        *(len(raw) for raw in raws))
    crc = 0
    for piece in (table, header, *raws):
        crc = zlib.crc32(piece, crc)
    return [_MAGIC, crc.to_bytes(4, "big"), table, header, *raws]


def _unframe(frame: bytes) -> dict:
    """The record of one envelope file, its arrays views of `frame`;
    raises on anything but a whole file as `_frame` laid it out."""
    view = memoryview(frame)
    magic, crc, count, header_len = _HEAD.unpack_from(view)
    if magic != _MAGIC:
        raise ValueError("bad magic")
    if zlib.crc32(view[_CRC_FROM:]) != crc:
        raise ValueError("torn record")
    lengths = struct.unpack_from(f">{count}Q", view, _HEAD.size)
    at = _HEAD.size + 8 * count
    if at + header_len + sum(lengths) != len(view):
        raise ValueError("torn record")
    pieces = []
    for length in (header_len, *lengths):
        pieces.append(view[at:at + length])
        at += length
    return pickle.loads(pieces[0], buffers=pieces[1:])


def load(batch_id, parts: dict):
    """Load one phase envelope's payload, or None; its arrays are
    read-only views of the bytes read.  A torn/garbage file
    is unlinked and counted (`proof_ckpt_discards_total`) — the caller
    simply re-proves the phase; this never raises."""
    if not enabled():
        return None
    path = _entry_path(batch_id, parts)
    wall0 = time.time()
    try:
        with open(path, "rb") as f:
            frame = f.read()
    except OSError:
        with _LOCK:
            STATS["misses"] += 1
        return None
    try:
        rec = _unframe(frame)
        if rec.get("schema") != _SCHEMA or rec.get("parts") != parts:
            raise ValueError("key mismatch")
        with _LOCK:
            STATS["loads"] += 1
        record_ckpt_load()
        # spanned only when an envelope was found: a fresh prove's
        # probes are failed opens, counted above
        tracing.record_span("ckpt.load", wall0, time.time() - wall0,
                            phase=parts.get("phase"), job=parts.get("job"),
                            disk_bytes=len(frame))
        return rec["payload"]
    except Exception:
        _discard(path)
        return None


def _discard(path: str) -> None:
    """An envelope nothing can resume from (torn, garbage, another
    code's): unlinked and counted."""
    with contextlib.suppress(OSError):
        os.unlink(path)
    with _LOCK:
        STATS["discards"] += 1
    record_ckpt_discard()


class InFlight(typing.NamedTuple):
    """A batch this disk holds envelopes for: the batch a prover that
    died here was proving."""

    batch_id: int
    lease_token: str        # of the attempt that wrote the newest envelope
    envelopes: int
    disk_bytes: int


def in_flight() -> list[InFlight]:
    """The batches with envelopes under `checkpoint_dir()` that this
    code can resume, oldest batch first, each with the lease token its
    newest envelope records (a batch reclaimed once and killed again
    carries two tokens; the coordinator knows the later one).  Per
    batch directory the envelopes are read newest first until one is
    whole and addressed by this code's fingerprint; the ones read
    before it (torn, garbage, or written by other code, which `load`
    could never find) are unlinked and counted as discards, older ones
    are left for `load` to judge.  Never raises."""
    if not enabled():
        return []
    found = []
    try:
        dirs = [d for d in os.scandir(checkpoint_dir())
                if d.name.startswith("batch_") and d.is_dir()]
    except OSError:
        return []
    for d in dirs:
        try:
            entries = [(st.st_mtime_ns, st.st_size, e.path)
                       for e in os.scandir(d.path)
                       if e.name.endswith(_SUFFIX)
                       for st in (e.stat(),)]
        except OSError:
            continue
        entries.sort(reverse=True)
        for at, (_, _, path) in enumerate(entries):
            try:
                with open(path, "rb") as f:
                    rec = _unframe(f.read())
                batch_id = rec["batch_id"]
                token = rec["meta"]["lease_token"]
                if rec.get("schema") != _SCHEMA or not os.path.samefile(
                        _entry_path(batch_id, rec["parts"]), path):
                    raise ValueError("another code's envelope")
            except Exception:
                _discard(path)
                continue
            if isinstance(batch_id, int) and isinstance(token, str):
                found.append(InFlight(
                    batch_id, token, len(entries) - at,
                    sum(size for _, size, _ in entries[at:])))
            break
        else:
            with contextlib.suppress(OSError):
                os.rmdir(d.path)        # nothing of it was left
    return sorted(found)


def complete(batch_id) -> int:
    """Drop every checkpoint of a settled batch (proof accepted): the
    envelope is recovery state, not an artifact.  Returns the bytes
    removed."""
    bdir = _batch_dir(batch_id)
    removed = 0
    try:
        entries = list(os.scandir(bdir))
    except OSError:
        return removed
    for entry in entries:
        with contextlib.suppress(OSError):
            size = entry.stat().st_size
            os.unlink(entry.path)
            removed += size
    with contextlib.suppress(OSError):
        os.rmdir(bdir)
    return removed


def runtime_stats() -> dict:
    """Live view for ethrex_health (l2.prover.runtime.checkpoints)."""
    with _LOCK:
        out = dict(STATS)
    out["enabled"] = enabled()
    try:
        out["batches"] = sum(
            1 for n in os.listdir(checkpoint_dir())
            if n.startswith("batch_"))
    except OSError:
        out["batches"] = 0
    return out


class PhaseStore:
    """Checkpoint handle for one job's phase sequence: fixes the
    identity parts (batch, job, air, shape, params) so the prover only
    names the phase.  `meta` (lease token, mesh label) is recorded on
    every envelope for forensics but never addresses it."""

    def __init__(self, ctx: BatchContext, job: str, air_key, log_n: int,
                 params_key, mesh_label: str):
        self.ctx = ctx
        self.batch_id = ctx.batch_id
        self.base = {"kind": "proof_ckpt", "job": job,
                     "air": repr(air_key), "log_n": int(log_n),
                     "params": repr(params_key)}
        self.meta = {"lease_token": ctx.lease_token, "mesh": mesh_label}

    def _parts(self, phase: str) -> dict:
        parts = dict(self.base)
        parts["phase"] = phase
        return parts

    def load(self, phase: str):
        return load(self.batch_id, self._parts(phase))

    def store(self, phase: str, payload, mesh_label: str | None = None):
        meta = dict(self.meta)
        if mesh_label is not None:
            meta["mesh"] = mesh_label
        return store(self.batch_id, self._parts(phase), payload, meta=meta)


def phase_store(air_key, log_n: int, params_key,
                mesh_label: str = "none") -> PhaseStore | None:
    """The stark prover's entry point: a PhaseStore bound to the active
    batch context and job scope, or None when checkpointing is off or
    the prove runs outside a batch (bench, direct API use)."""
    if not enabled():
        return None
    ctx = current_context()
    if ctx is None:
        return None
    job = current_job() or "-"
    return PhaseStore(ctx, job, air_key, log_n, params_key, mesh_label)
