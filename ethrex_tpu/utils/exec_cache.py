"""On-disk cache of serialized AOT executables: compiled prover kernels
as durable, shippable artifacts.

The in-process phase cache (stark/prover._PHASE_CACHE) amortizes
compiles within one process; this store amortizes them across processes
and hosts of the same shape.  Every AOT `lower().compile()` result the
prover produces is serialized through
`jax.experimental.serialize_executable` into a content-addressed entry,
and every phase-program build asks this store first — a restarting
prover hydrates in deserialize time (milliseconds per kernel) instead
of recompiling for minutes.  Ship the cache directory in a deploy image
and the first proof after a restart runs at steady-state wall.

Key schema: an entry's filename is the SHA-256 of its JSON-canonical
key parts — the program identity (AIR cache key, log_n, blowup, shift,
kernel, mesh device layout) — joined with the environment parts
(backend platform, device kind and count, jax/jaxlib versions).  A
jaxlib upgrade, a backend switch or another host layout therefore
changes every key: stale entries are structurally unreachable, not a
correctness hazard.  A file is two pickles in a row: a head (schema,
key parts, environment), which is all the hydration walk reads of it,
then the body with the executable.  Corruption, truncation, or an
unpicklable payload is a clean miss (plus `executable_cache_errors_total`
and a best-effort unlink); retention is bounded by pruning
least-recently-used entries past a cap.

Env knobs (documented in docs/PERFORMANCE.md "Cold start"):
  ETHREX_EXEC_CACHE_DIR  cache directory (default <cache root>/exec,
                         see utils/jax_cache.cache_dir)
  ETHREX_EXEC_CACHE_MAX  max entries retained after a store (default 512)
  ETHREX_EXEC_CACHE_OFF  "1" disables both lookup and store
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
import threading

_SCHEMA = 2
_SUFFIX = ".exe.pkl"
_DEFAULT_MAX_ENTRIES = 512

_LOCK = threading.Lock()
_CONFIGURED_DIR: str | None = None
STATS = {"hits": 0, "misses": 0, "errors": 0, "stores": 0}


def record_exec_cache_hit() -> None:
    from .metrics import METRICS

    METRICS.inc("executable_cache_hits_total", 1,
                "Serialized-executable cache hits: AOT prover kernels "
                "hydrated from disk instead of recompiled")


def record_exec_cache_miss() -> None:
    from .metrics import METRICS

    METRICS.inc("executable_cache_misses_total", 1,
                "Serialized-executable cache misses: AOT prover kernels "
                "that had to be compiled from scratch")


def record_exec_cache_error() -> None:
    from .metrics import METRICS

    METRICS.inc("executable_cache_errors_total", 1,
                "Serialized-executable cache failures: entries dropped as "
                "corrupt, truncated or unloadable, and stores rejected "
                "because the payload failed its round-trip validation")


def set_cache_dir(path: str | None) -> None:
    """Explicit cache directory (the `--executable-cache-dir` CLI flag);
    overrides ETHREX_EXEC_CACHE_DIR and the <cache root>/exec default."""
    global _CONFIGURED_DIR
    with _LOCK:
        _CONFIGURED_DIR = path


def cache_dir() -> str:
    with _LOCK:
        configured = _CONFIGURED_DIR
    if configured:
        return configured
    env = os.environ.get("ETHREX_EXEC_CACHE_DIR")
    if env:
        return env
    from .jax_cache import cache_dir as _cache_root

    return os.path.join(_cache_root(), "exec")


def enabled() -> bool:
    return os.environ.get("ETHREX_EXEC_CACHE_OFF") != "1"


def mesh_fingerprint(mesh) -> tuple | None:
    """Cache identity of a mesh: exact device ids, axis names and layout
    shape (a compiled executable is bound to its devices).  None (no
    mesh) is its own key."""
    if mesh is None:
        return None
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), tuple(mesh.devices.shape))


def _execution_devices(parts: dict) -> list:
    """The devices an entry's executable is bound to: its mesh's (by
    id), or the default device for a single-device program.  Left to
    itself, deserialize_and_load binds the executable to EVERY device
    of the backend — a single-device program stored on a one-chip host
    and hydrated on a four-chip host then demands four shards of every
    argument."""
    import jax

    mesh = parts.get("mesh")
    if mesh is None:
        return [jax.devices()[0]]
    by_id = {int(d.id): d for d in jax.devices()}
    return [by_id[int(i)] for i in mesh[0]]


_CODE_FINGERPRINT: str | None = None


def _code_fingerprint() -> str:
    """Hash of the kernel-defining sources (ops/, stark/prover.py,
    parallel/core.py + mesh.py).  The program-identity parts are
    *semantic* (AIR key, shapes) and cannot see function bodies, so a
    code change that alters what a compiled program computes must
    invalidate every entry through the environment half of the key.
    Computed once per process; unreadable sources degrade to their
    names so the fingerprint still exists."""
    global _CODE_FINGERPRINT
    with _LOCK:
        if _CODE_FINGERPRINT is not None:
            return _CODE_FINGERPRINT
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [os.path.join(pkg, "stark", "prover.py"),
             os.path.join(pkg, "parallel", "core.py"),
             os.path.join(pkg, "parallel", "mesh.py")]
    try:
        ops = os.path.join(pkg, "ops")
        paths.extend(os.path.join(ops, n) for n in sorted(os.listdir(ops))
                     if n.endswith(".py"))
    except OSError:
        pass
    h = hashlib.sha256()
    for path in paths:
        h.update(os.path.basename(path).encode())
        try:
            with open(path, "rb") as f:
                h.update(f.read())
        except OSError:
            pass
    digest = h.hexdigest()[:16]
    with _LOCK:
        _CODE_FINGERPRINT = digest
    return digest


def _env_parts() -> dict:
    """Environment half of the key: anything that makes a serialized
    executable unloadable or wrong when it changes."""
    import jax
    import jaxlib

    devs = jax.devices()
    # the host's device layout is part of the environment: an
    # executable stored where the backend had one device has failed to
    # run when hydrated where it has four (PR 25's --chips 4 rehearsal)
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "backend": jax.default_backend(),
            "devices": [devs[0].device_kind, len(devs)],
            "code": _code_fingerprint()}


def entry_key(parts: dict) -> str:
    """Content address of an entry: SHA-256 over the canonical JSON of
    the program-identity parts joined with the environment parts, so a
    jaxlib/backend change can never serve a stale executable."""
    material = {"schema": _SCHEMA, "parts": parts, "env": _env_parts()}
    blob = json.dumps(material, sort_keys=True, default=repr)
    return hashlib.sha256(blob.encode()).hexdigest()


def _entry_path(parts: dict) -> str:
    return os.path.join(cache_dir(), entry_key(parts) + _SUFFIX)


def load(parts: dict):
    """Deserialize-first lookup: the loaded executable for `parts`, or
    None on any miss (absent, corrupt, schema/env drift).  Never raises."""
    if not enabled():
        return None
    path = _entry_path(parts)
    try:
        f = open(path, "rb")
    except OSError:
        with _LOCK:
            STATS["misses"] += 1
        record_exec_cache_miss()
        return None
    try:
        with f:
            head = pickle.load(f)
            if head.get("schema") != _SCHEMA \
                    or head.get("env") != _env_parts():
                raise ValueError("executable cache entry schema/env drift")
            body = pickle.load(f)
        from jax.experimental import serialize_executable

        compiled = serialize_executable.deserialize_and_load(
            body["payload"], body["in_tree"], body["out_tree"],
            execution_devices=_execution_devices(parts))
    except Exception:
        # corruption / truncation / version drift inside the payload:
        # count the error, drop the entry, and report a clean miss
        with _LOCK:
            STATS["errors"] += 1
            STATS["misses"] += 1
        record_exec_cache_error()
        record_exec_cache_miss()
        try:
            os.unlink(path)
        except OSError:
            pass
        return None
    with _LOCK:
        STATS["hits"] += 1
    record_exec_cache_hit()
    try:
        os.utime(path)                      # LRU touch for retention
    except OSError:
        pass
    return compiled


def store(parts: dict, compiled) -> bool:
    """Serialize `compiled` under `parts` (atomic rename), then prune to
    the retention cap.  Returns whether the entry landed; never raises."""
    if not enabled():
        return False
    try:
        from jax.experimental import serialize_executable

        payload, in_tree, out_tree = serialize_executable.serialize(compiled)
        # An executable whose compile was served from the XLA persistent
        # compilation cache serializes WITHOUT its jit-compiled symbols
        # (jaxlib CPU: a later deserialize fails with "Symbols not
        # found"), so validate the round-trip before publishing — a
        # poisoned entry must never land on disk.  The rejection counts
        # as an error; a warm XLA cache + empty executable cache
        # therefore stays unpopulated (cold starts are still XLA-cache
        # fast) until a genuinely fresh compile comes along.
        serialize_executable.deserialize_and_load(
            payload, in_tree, out_tree,
            execution_devices=_execution_devices(parts))
        # two pickles in a row: a head that says what the entry is
        # (all `scan` reads) and the body with the executable
        head = {"schema": _SCHEMA, "parts": parts, "env": _env_parts()}
        body = {"payload": payload, "in_tree": in_tree,
                "out_tree": out_tree}
        directory = cache_dir()
        os.makedirs(directory, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(head, f)
                pickle.dump(body, f)
            os.replace(tmp, _entry_path(parts))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    except Exception:
        with _LOCK:
            STATS["errors"] += 1
        record_exec_cache_error()
        return False
    with _LOCK:
        STATS["stores"] += 1
    prune()
    return True


def scan(kind: str | None = None) -> list[dict]:
    """Metadata of every loadable entry for the CURRENT environment
    (optionally filtered by parts["kind"]), oldest first — the hydration
    walk.  Reads each entry's head alone, not its executable (up to
    240 MB).  Unreadable entries are skipped silently; pass each
    returned parts dict to load() for the executable itself."""
    try:
        names = [n for n in os.listdir(cache_dir()) if n.endswith(_SUFFIX)]
    except OSError:
        return []
    env = None
    out = []
    for name in sorted(names):
        path = os.path.join(cache_dir(), name)
        try:
            with open(path, "rb") as f:
                entry = pickle.load(f)
            if entry.get("schema") != _SCHEMA:
                continue
            if env is None:
                env = _env_parts()
            if entry.get("env") != env:
                continue
            parts = entry["parts"]
            if kind is not None and parts.get("kind") != kind:
                continue
            out.append((os.path.getmtime(path), parts))
        except Exception:
            continue
    return [parts for _, parts in sorted(out, key=lambda p: p[0])]


def prune(max_entries: int | None = None) -> int:
    """Drop least-recently-used entries beyond the cap.  Returns how
    many were removed; never raises."""
    if max_entries is None:
        try:
            max_entries = int(os.environ.get("ETHREX_EXEC_CACHE_MAX",
                                             _DEFAULT_MAX_ENTRIES))
        except ValueError:
            max_entries = _DEFAULT_MAX_ENTRIES
    try:
        directory = cache_dir()
        names = [n for n in os.listdir(directory) if n.endswith(_SUFFIX)]
        if len(names) <= max_entries:
            return 0
        aged = []
        for name in names:
            path = os.path.join(directory, name)
            try:
                aged.append((os.path.getmtime(path), path))
            except OSError:
                continue
        aged.sort()
        removed = 0
        for _, path in aged[:max(0, len(aged) - max_entries)]:
            try:
                os.unlink(path)
                removed += 1
            except OSError:
                pass
        return removed
    except Exception:
        return 0


def entry_count() -> int:
    try:
        return sum(1 for n in os.listdir(cache_dir())
                   if n.endswith(_SUFFIX))
    except OSError:
        return 0


def clear_stats() -> None:
    """Reset the in-process counters (test isolation)."""
    with _LOCK:
        for k in STATS:
            STATS[k] = 0


def runtime_stats() -> dict:
    """Point-in-time cache facts for ethrex_perf / ethrex_health / the
    monitor perf panel.  Never raises."""
    with _LOCK:
        out = dict(STATS)
    out["enabled"] = enabled()
    try:
        out["dir"] = cache_dir()
        out["entries"] = entry_count()
    except Exception:
        out["dir"] = None
        out["entries"] = 0
    return out
