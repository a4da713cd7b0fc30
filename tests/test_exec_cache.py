"""Serialized-executable cache (utils/exec_cache + stark hydration +
client pre-warm): store/load/corruption/retention unit drills with a
stubbed serializer, hydration grouping against the in-process phase
cache, the telemetry surfaces, and the slow cross-process warm-restart
drill (two real subprocesses sharing one cache directory)."""

import json
import os
import pickle
import subprocess
import sys

import pytest

from ethrex_tpu.prover.client import ProverClient
from ethrex_tpu.stark import prover
from ethrex_tpu.utils import exec_cache
from ethrex_tpu.utils.metrics import METRICS


class _FakeExecutable:
    """Picklable stand-in for a compiled XLA executable."""

    def __init__(self, tag):
        self.tag = tag


def _fake_serializer(monkeypatch):
    """Reroute jax.experimental.serialize_executable through pickle:
    load/store import the module object, so patching its attributes
    covers the real call sites without compiling anything."""
    from jax.experimental import serialize_executable as se

    monkeypatch.setattr(
        se, "serialize",
        lambda compiled: (pickle.dumps(compiled), "it", "ot"))

    def _deserialize(payload, in_tree, out_tree, execution_devices=None):
        assert (in_tree, out_tree) == ("it", "ot")
        # never left to the default (every device of the backend)
        assert execution_devices
        return pickle.loads(payload)

    monkeypatch.setattr(se, "deserialize_and_load", _deserialize)


@pytest.fixture
def cache_env(monkeypatch, tmp_path):
    monkeypatch.setenv("ETHREX_EXEC_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("ETHREX_EXEC_CACHE_OFF", raising=False)
    monkeypatch.delenv("ETHREX_EXEC_CACHE_MAX", raising=False)
    monkeypatch.setattr(exec_cache, "_CONFIGURED_DIR", None)
    exec_cache.clear_stats()
    _fake_serializer(monkeypatch)
    yield tmp_path
    exec_cache.clear_stats()


def _path_for(parts):
    return os.path.join(exec_cache.cache_dir(),
                        exec_cache.entry_key(parts) + exec_cache._SUFFIX)


# ===========================================================================
# store / load / corruption / retention
# ===========================================================================

def _counter(name):
    # earlier tests' real proves may already have bumped the global
    # registry (the cache is default-on): assert deltas, not absolutes
    return METRICS.counters.get(name, 0.0)


def test_store_load_roundtrip_and_counters(cache_env):
    base = {n: _counter(f"executable_cache_{n}_total")
            for n in ("hits", "misses", "errors")}
    parts = {"kind": "phase", "kernel": "commit", "log_n": 4}
    assert exec_cache.load(parts) is None            # cold: a clean miss
    assert exec_cache.store(parts, _FakeExecutable("a")) is True
    got = exec_cache.load(parts)
    assert isinstance(got, _FakeExecutable) and got.tag == "a"
    assert exec_cache.STATS == {"hits": 1, "misses": 1, "errors": 0,
                                "stores": 1}
    assert _counter("executable_cache_hits_total") == base["hits"] + 1
    assert _counter("executable_cache_misses_total") == base["misses"] + 1
    assert _counter("executable_cache_errors_total") == base["errors"]


def test_distinct_parts_are_distinct_entries(cache_env):
    exec_cache.store({"kind": "phase", "kernel": "commit"},
                     _FakeExecutable("x"))
    exec_cache.store({"kind": "phase", "kernel": "deep"},
                     _FakeExecutable("y"))
    assert exec_cache.entry_count() == 2
    assert exec_cache.load({"kind": "phase", "kernel": "deep"}).tag == "y"


def test_env_drift_makes_entries_structurally_unreachable(
        cache_env, monkeypatch):
    """A jaxlib upgrade changes the key, so a stale entry is a plain
    miss — never an error, and invisible to the hydration scan."""
    parts = {"kind": "phase", "kernel": "open"}
    exec_cache.store(parts, _FakeExecutable("x"))
    real = exec_cache._env_parts()
    monkeypatch.setattr(exec_cache, "_env_parts",
                        lambda: dict(real, jaxlib="99.0"))
    assert exec_cache.load(parts) is None
    assert exec_cache.STATS["errors"] == 0
    assert exec_cache.scan() == []
    assert exec_cache.entry_count() == 1             # still on disk, benign


def test_env_mismatch_inside_entry_is_dropped_as_error(cache_env):
    """An entry whose recorded env no longer matches (e.g. a file copied
    from another host into the right filename) is counted as an error, a
    miss, and unlinked."""
    parts = {"kind": "phase", "kernel": "quotient"}
    exec_cache.store(parts, _FakeExecutable("z"))
    path = _path_for(parts)
    entry = pickle.loads(open(path, "rb").read())
    entry["env"] = dict(entry["env"], jax="0.0.0")
    with open(path, "wb") as f:
        f.write(pickle.dumps(entry))
    assert exec_cache.load(parts) is None
    assert exec_cache.STATS["errors"] == 1
    assert exec_cache.STATS["misses"] == 1
    assert not os.path.exists(path)


def test_corrupt_entry_is_error_plus_miss_then_plain_miss(cache_env):
    base_errors = _counter("executable_cache_errors_total")
    parts = {"kind": "phase", "kernel": "deep"}
    exec_cache.store(parts, _FakeExecutable("y"))
    path = _path_for(parts)
    with open(path, "wb") as f:
        f.write(b"\x00truncated-garbage")
    assert exec_cache.load(parts) is None
    assert exec_cache.STATS["errors"] == 1
    assert exec_cache.STATS["misses"] == 1
    assert _counter("executable_cache_errors_total") == base_errors + 1
    assert not os.path.exists(path)                  # dropped
    # the NEXT lookup finds nothing: a plain miss, no second error
    assert exec_cache.load(parts) is None
    assert exec_cache.STATS == {"hits": 0, "misses": 2, "errors": 1,
                                "stores": 1}


def test_unloadable_payload_is_rejected_at_store_time(cache_env,
                                                      monkeypatch):
    """serialize() of an executable whose compile was served from the
    XLA persistent compilation cache yields a payload missing its jit
    symbols — a later deserialize fails with "Symbols not found".
    store() round-trips the payload before publishing, so such an entry
    is rejected (error counted, nothing on disk) instead of poisoning
    every subsequent hydration."""
    from jax.experimental import serialize_executable as se

    def _symbols_lost(payload, in_tree, out_tree, execution_devices=None):
        raise RuntimeError("Symbols not found: [concatenate_fusion.12]")

    monkeypatch.setattr(se, "deserialize_and_load", _symbols_lost)
    base_errors = _counter("executable_cache_errors_total")
    parts = {"kind": "phase", "kernel": "commit", "log_n": 5}
    assert exec_cache.store(parts, _FakeExecutable("poisoned")) is False
    assert exec_cache.STATS == {"hits": 0, "misses": 0, "errors": 1,
                                "stores": 0}
    assert _counter("executable_cache_errors_total") == base_errors + 1
    assert exec_cache.entry_count() == 0
    assert not os.path.exists(_path_for(parts))


def test_code_fingerprint_participates_in_the_key(cache_env, monkeypatch):
    """A change to the kernel-defining sources must orphan every entry:
    the semantic parts cannot see function bodies, so the code hash in
    the env half of the key is what keeps a stale executable from ever
    being served after a deploy."""
    parts = {"kind": "phase", "kernel": "commit"}
    exec_cache.store(parts, _FakeExecutable("old-code"))
    monkeypatch.setattr(exec_cache, "_code_fingerprint", lambda: "deadbeef")
    assert exec_cache.load(parts) is None            # clean miss
    assert exec_cache.STATS["errors"] == 0
    assert exec_cache.scan() == []


def test_off_switch_disables_lookup_and_store(cache_env, monkeypatch):
    monkeypatch.setenv("ETHREX_EXEC_CACHE_OFF", "1")
    parts = {"kind": "phase", "kernel": "commit"}
    assert exec_cache.store(parts, _FakeExecutable("n")) is False
    assert exec_cache.load(parts) is None
    assert exec_cache.entry_count() == 0
    assert exec_cache.STATS == {"hits": 0, "misses": 0, "errors": 0,
                                "stores": 0}
    assert exec_cache.runtime_stats()["enabled"] is False


def test_retention_prunes_least_recently_used(cache_env):
    paths = {}
    for i in range(5):
        parts = {"kind": "phase", "i": i}
        exec_cache.store(parts, _FakeExecutable(i))
        paths[i] = _path_for(parts)
        os.utime(paths[i], (100 + i, 100 + i))       # deterministic LRU order
    assert exec_cache.prune(max_entries=3) == 2
    assert [i for i in range(5) if os.path.exists(paths[i])] == [2, 3, 4]


def test_store_prunes_via_env_cap(cache_env, monkeypatch):
    monkeypatch.setenv("ETHREX_EXEC_CACHE_MAX", "2")
    for i in range(4):
        exec_cache.store({"kind": "phase", "i": i}, _FakeExecutable(i))
    assert exec_cache.entry_count() <= 2


def test_scan_filters_kind_and_orders_oldest_first(cache_env):
    for i, kind in enumerate(["phase", "core_step", "phase"]):
        parts = {"kind": kind, "i": i}
        exec_cache.store(parts, _FakeExecutable(i))
        # reverse mtimes so insertion order != age order
        os.utime(_path_for(parts), (200 - i, 200 - i))
    got = exec_cache.scan("phase")
    assert [p["i"] for p in got] == [2, 0]
    assert all(p["kind"] == "phase" for p in got)
    assert len(exec_cache.scan()) == 3               # no filter: everything


def test_runtime_stats_shape(cache_env):
    parts = {"kind": "phase", "i": 1}
    exec_cache.store(parts, _FakeExecutable(1))
    exec_cache.load(parts)
    stats = exec_cache.runtime_stats()
    assert stats["enabled"] is True
    assert stats["entries"] == 1
    assert stats["hits"] == 1 and stats["stores"] == 1
    assert stats["dir"] == str(cache_env)


# ===========================================================================
# hydration into the in-process phase cache
# ===========================================================================

def _phase_parts(kernel, air="stub-air", log_n=4, mesh=None, **over):
    parts = {"kind": "phase", "air": air, "air_name": "StubAir",
             "width": 2, "nb": 3, "log_n": log_n, "log_blowup": 2,
             "shift": 7, "mesh": mesh, "kernel": kernel}
    parts.update(over)
    return parts


@pytest.fixture
def phase_cache_isolation():
    saved = dict(prover._PHASE_CACHE)
    prover._PHASE_CACHE.clear()
    yield
    prover._PHASE_CACHE.clear()
    prover._PHASE_CACHE.update(saved)


def test_hydrate_installs_only_complete_matching_groups(
        monkeypatch, phase_cache_isolation):
    monkeypatch.delenv("ETHREX_EXEC_CACHE_OFF", raising=False)
    entries = (
        [_phase_parts(k) for k in prover._KERNELS]             # complete
        + [_phase_parts(k, air="other-air", log_n=5)
           for k in ("commit", "quotient", "open")]            # incomplete
        + [_phase_parts(k, air="mesh-air", mesh=[[0, 1], ["fri"], [2]])
           for k in prover._KERNELS])                          # wrong mesh
    monkeypatch.setattr(exec_cache, "scan", lambda kind=None: list(entries))
    monkeypatch.setattr(exec_cache, "load",
                        lambda parts: f"exe:{parts['kernel']}")
    with METRICS.lock:
        hist0 = METRICS.histograms.get("prover_phase_compile_seconds")
        rows_before = set(hist0.series) if hist0 else set()
    assert prover.hydrate_phase_cache(None) == 1
    progs = prover._PHASE_CACHE[("stub-air", 4, 2, 7, None)]
    assert (progs.commit, progs.quotient, progs.open, progs.deep) == \
        ("exe:commit", "exe:quotient", "exe:open", "exe:deep")
    assert progs.plan is None
    assert progs.put_cols("x") == "x"        # identity on the 1-device path
    assert len(prover._PHASE_CACHE) == 1     # nothing else was installed
    # deserialize walls land in the compile histogram as source=deserialized
    with METRICS.lock:
        hist = METRICS.histograms["prover_phase_compile_seconds"]
        new = [dict(labels) for labels in hist.series
               if labels not in rows_before]
    assert {r["source"] for r in new} == {"deserialized"}
    assert {r["kernel"] for r in new} == set(prover._KERNELS)
    # idempotent: the group is already in-process, a second pass is a no-op
    assert prover.hydrate_phase_cache(None) == 0


def test_hydrate_skips_group_when_one_kernel_fails_to_load(
        monkeypatch, phase_cache_isolation):
    monkeypatch.delenv("ETHREX_EXEC_CACHE_OFF", raising=False)
    entries = [_phase_parts(k) for k in prover._KERNELS]
    monkeypatch.setattr(exec_cache, "scan", lambda kind=None: list(entries))
    monkeypatch.setattr(
        exec_cache, "load",
        lambda parts: None if parts["kernel"] == "open" else "exe")
    assert prover.hydrate_phase_cache(None) == 0
    assert prover._PHASE_CACHE == {}         # never partially installed


def test_hydrate_is_noop_when_disabled_or_unscannable(
        monkeypatch, phase_cache_isolation):
    monkeypatch.setenv("ETHREX_EXEC_CACHE_OFF", "1")
    assert prover.hydrate_phase_cache(None) == 0
    monkeypatch.delenv("ETHREX_EXEC_CACHE_OFF")

    def _boom(kind=None):
        raise OSError("cache dir unreadable")

    monkeypatch.setattr(exec_cache, "scan", _boom)
    assert prover.hydrate_phase_cache(None) == 0


# ===========================================================================
# client pre-warm and the advisory warm flag
# ===========================================================================

def test_prover_client_prewarm_sets_warm_flag():
    class Hydrating:
        prover_type = "exec"

        def prewarm(self):
            return 2

    client = ProverClient(Hydrating(), [])
    assert client._prewarm_done.wait(10.0)
    assert client.hydrated_groups == 2
    assert client.warm is True


def test_prover_client_prewarm_failure_is_cold_not_fatal():
    class Boom:
        prover_type = "exec"

        def prewarm(self):
            raise RuntimeError("cache exploded")

    client = ProverClient(Boom(), [])
    assert client._prewarm_done.wait(10.0)   # the failure never hangs polls
    assert client.hydrated_groups == 0
    assert client.warm is False


def test_prover_client_warm_after_first_proof_without_hydration():
    client = ProverClient("exec", [], prewarm=False)
    assert client._prewarm_done.is_set()
    assert client.warm is False              # nothing hydrated, nothing proven
    client.proved.append(1)
    assert client.warm is True               # a completed proof implies warm


def test_backend_default_prewarm_is_zero():
    from ethrex_tpu.prover.backend import get_backend

    assert get_backend("exec").prewarm() == 0


# ===========================================================================
# telemetry surfaces (ethrex_perf / ethrex_health / monitor)
# ===========================================================================

def test_perf_and_health_surface_exec_cache(cache_env):
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.rpc.server import RpcServer

    exec_cache.store({"kind": "phase", "i": 0}, _FakeExecutable(0))
    exec_cache.load({"kind": "phase", "i": 0})
    sender = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(0xA11CE))
    server = RpcServer(Node(Genesis.from_json({
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0"})))
    perf = server.handle({"jsonrpc": "2.0", "id": 1, "method": "ethrex_perf",
                          "params": []})["result"]
    assert perf["executableCache"]["hits"] == 1
    assert perf["executableCache"]["stores"] == 1
    assert perf["executableCache"]["entries"] == 1
    health = server.handle({"jsonrpc": "2.0", "id": 2,
                            "method": "ethrex_health",
                            "params": []})["result"]
    assert health["perf"]["executableCache"]["hits"] == 1
    assert health["perf"]["executableCache"]["enabled"] is True


def test_monitor_perf_panel_shows_exec_cache_line():
    from ethrex_tpu.utils.monitor import _perf_lines

    snap = {"perf": {"enabled": True,
                     "executableCache": {"enabled": True, "hits": 8,
                                         "misses": 1, "errors": 0,
                                         "entries": 12}}}
    text = "\n".join(_perf_lines(snap, 100))
    assert "exec cache [on]" in text
    assert "hits" in text and "8" in text
    # a degraded section renders no cache line rather than crashing
    snap["perf"]["executableCache"] = {"error": "boom"}
    assert "exec cache" not in "\n".join(_perf_lines(snap, 100))


# ===========================================================================
# the real thing: cross-process warm restart (slow tier)
# ===========================================================================

_DRILL = r"""
import hashlib, json, os, time
os.environ.setdefault("JAX_PLATFORMS", "cpu")
from ethrex_tpu.models import fibonacci as fib
from ethrex_tpu.stark import prover
from ethrex_tpu.stark.prover import StarkParams
from ethrex_tpu.utils import exec_cache
from ethrex_tpu.utils.metrics import METRICS

hydrated = prover.hydrate_phase_cache(None)
params = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)
air = fib.FibonacciAir()
trace = fib.generate_trace(64)
pub = fib.public_inputs(trace)
t0 = time.perf_counter()
proof = prover.prove(air, trace, pub, params)
prove_s = time.perf_counter() - t0
digest = hashlib.sha256(
    json.dumps(proof, sort_keys=True, default=repr).encode()).hexdigest()
by_source, build_s = {}, {}
with METRICS.lock:
    hist = METRICS.histograms.get("prover_phase_compile_seconds")
    if hist is not None:
        for labels, row in hist.series.items():
            src = dict(labels).get("source")
            by_source[src] = by_source.get(src, 0) + 1
            build_s[src] = build_s.get(src, 0.0) + row[-1]
print(json.dumps({"hydrated": hydrated, "digest": digest,
                  "prove_s": round(prove_s, 3), "by_source": by_source,
                  "build_s": {k: round(v, 3) for k, v in build_s.items()},
                  "exec_stats": dict(exec_cache.STATS)}))
"""


@pytest.mark.slow
def test_cross_process_warm_restart_drill(tmp_path):
    """The tentpole's acceptance drill: process A proves cold and
    populates the cache; a fresh process B sharing only the cache
    directory hydrates every phase program and every FRI layer program
    (four layer sizes of three) from disk, recompiles neither (no
    source="compiled" rows), and produces a byte-identical proof — with the phase build wall collapsing by far
    more than the 10x warmup target."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, ETHREX_EXEC_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get("PYTHONPATH", ""))
    env.pop("ETHREX_EXEC_CACHE_OFF", None)

    def child():
        run = subprocess.run([sys.executable, "-c", _DRILL], env=env,
                             capture_output=True, text=True, timeout=1200)
        assert run.returncode == 0, run.stderr[-4000:]
        return json.loads(run.stdout.strip().splitlines()[-1])

    cold = child()
    assert cold["hydrated"] == 0
    # one series a phase kernel and one a layer kernel (all sizes)
    assert cold["by_source"] == {"compiled": 4 + 3}
    assert cold["exec_stats"]["stores"] == 4 + 4 * 3

    warm = child()
    assert warm["hydrated"] == 1                     # one 4-kernel group
    assert warm["digest"] == cold["digest"]          # byte-identical proof
    assert warm["by_source"] == {"deserialized": 4 + 3}  # no recompile
    assert warm["exec_stats"] == {"hits": 4 + 4 * 3, "misses": 0,
                                  "errors": 0, "stores": 0}
    assert warm["build_s"]["deserialized"] * 5 < cold["build_s"]["compiled"]
