"""The FRI layer programs as stored executables (ops/fri.layer_programs,
stark/prover.hydrate_phase_cache): what the table's programs prove
against what the lazy jits prove, a simulated restart that compiles and
lowers nothing, entries that cannot be used, and the background warm-up
that a full table makes unnecessary.  Small sizes, each case at two
layer depths."""

import dataclasses
import json
import os
import pickle

import jax.numpy as jnp
import numpy as np
import pytest

from ethrex_tpu.models import fibonacci as fib
from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.ops import fri, ntt
from ethrex_tpu.ops.challenger import Challenger
from ethrex_tpu.stark import prover
from ethrex_tpu.utils import exec_cache, jax_cache, tracing

LOG_FINAL = 4
FRI_PARAMS = fri.FriParams(log_blowup=2, num_queries=8,
                           log_final_size=LOG_FINAL, grinding_bits=4)
STARK_PARAMS = prover.StarkParams(log_blowup=2, num_queries=8,
                                  log_final_size=LOG_FINAL, grinding_bits=4)


@pytest.fixture
def store(monkeypatch, tmp_path):
    """An empty executable store and empty program tables.  The store's
    files are real; an executable's bytes in them are a ticket for the
    executable itself, kept here: on the CPU an executable serialized in
    a process whose persistent cache has seen the same program (any
    worker that ran another test first) loads and then fails to run,
    so the real serializer is left to the two fresh processes of
    `test_cross_process_warm_restart_drill` and to the chip."""
    from jax.experimental import serialize_executable as se

    held = {}

    def serialize(compiled):
        ticket = f"executable-{len(held)}".encode()
        held[ticket] = compiled
        return ticket, "in_tree", "out_tree"

    def deserialize_and_load(payload, in_tree, out_tree,
                             execution_devices=None):
        assert (in_tree, out_tree) == ("in_tree", "out_tree")
        assert execution_devices
        return held[payload]

    monkeypatch.setattr(se, "serialize", serialize)
    monkeypatch.setattr(se, "deserialize_and_load", deserialize_and_load)
    monkeypatch.setenv("ETHREX_EXEC_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("ETHREX_EXEC_CACHE_OFF", raising=False)
    monkeypatch.setattr(exec_cache, "_CONFIGURED_DIR", None)
    phases, layers = dict(prover._PHASE_CACHE), dict(fri._LAYER_PROGRAMS)
    prover.clear_phase_cache()
    fri.clear_layer_programs()
    exec_cache.clear_stats()
    yield tmp_path
    prover._PHASE_CACHE.clear()
    prover._PHASE_CACHE.update(phases)
    fri._LAYER_PROGRAMS.clear()
    fri._LAYER_PROGRAMS.update(layers)
    exec_cache.clear_stats()


def _codeword(log_size: int):
    """A random polynomial of degree < 2^(log_size - 2) on the coset of
    2^log_size points."""
    rng = np.random.default_rng(log_size)
    coeffs = rng.integers(0, bb.P, size=(4, 1 << (log_size - 2)),
                          dtype=np.uint32)
    evals = ntt.coset_evals_from_coeffs(bb.to_mont(jnp.asarray(coeffs)),
                                        1 << log_size)
    return jnp.moveaxis(evals, 0, -1)


def _fri_bytes(codeword) -> bytes:
    proof, indices = fri.FriProver(FRI_PARAMS).prove(codeword, Challenger())
    return json.dumps([dataclasses.asdict(proof), indices],
                      sort_keys=True).encode()


def _span_names(trace_id) -> list:
    return [s["name"] for s in tracing.TRACER.get_trace(trace_id)["spans"]]


@pytest.mark.parametrize("log_size", (6, 8))
def test_stored_programs_prove_what_the_lazy_jits_prove(
        store, monkeypatch, log_size):
    codeword = _codeword(log_size)
    layers = log_size - LOG_FINAL
    with monkeypatch.context() as m:
        # the mesh path's programs, here on one device
        m.setattr(fri, "layer_programs", lambda log_k: fri._LAZY_PROGRAMS)
        lazy = _fri_bytes(codeword)
    assert exec_cache.STATS["stores"] == 0
    compiled = _fri_bytes(codeword)
    assert exec_cache.STATS["stores"] == 3 * layers
    assert sorted(fri._LAYER_PROGRAMS) == list(range(LOG_FINAL + 1,
                                                     log_size + 1))
    fri.clear_layer_programs()
    assert prover.hydrate_phase_cache(None) == 0    # no phase group
    assert exec_cache.STATS["hits"] == 3 * layers
    restored = _fri_bytes(codeword)
    assert exec_cache.STATS["hits"] == 3 * layers   # the table served it
    assert compiled == lazy
    assert restored == lazy


@pytest.mark.parametrize("rows", (16, 32))
def test_a_restart_restores_every_program_and_compiles_nothing(store, rows):
    from ethrex_tpu.prover.tpu_backend import TpuBackend

    air = fib.FibonacciAir()
    trace = fib.generate_trace(rows)
    pub = fib.public_inputs(trace)
    cold = prover.prove(air, trace, pub, STARK_PARAMS)
    layers = (rows.bit_length() - 1) + 2 - LOG_FINAL
    entries = 4 + 3 * layers
    assert exec_cache.entry_count() == entries
    assert exec_cache.STATS["stores"] == entries

    prover.clear_phase_cache()
    fri.clear_layer_programs()
    exec_cache.clear_stats()
    jax_cache.install_monitoring()
    assert TpuBackend().prewarm() == 1
    hits = {"hits": entries, "misses": 0, "errors": 0, "stores": 0}
    assert exec_cache.STATS == hits
    before = dict(jax_cache.STATS)
    with tracing.span("test.restart") as root:
        warm = prover.prove(air, trace, pub, STARK_PARAMS)
    after = dict(jax_cache.STATS)
    assert after["compiles"] == before["compiles"]
    assert after["lower_seconds"] == before["lower_seconds"]
    names = _span_names(root.trace_id)
    assert "prove.fri_build" not in names
    assert "prove.phase_build" not in names
    assert names.count("fri.layer") == layers
    assert exec_cache.STATS == hits
    assert warm == cold


def _rewrite(path: str, how: str) -> None:
    if how == "corrupt":
        with open(path, "r+b") as f:
            f.truncate(os.path.getsize(path) // 2)
        return
    # foreign: a sound entry of another environment
    with open(path, "rb") as f:
        head, body = pickle.load(f), pickle.load(f)
    head["env"] = dict(head["env"], jaxlib="0.0.0")
    with open(path, "wb") as f:
        pickle.dump(head, f)
        pickle.dump(body, f)


@pytest.mark.parametrize("how", ("corrupt", "foreign"))
@pytest.mark.parametrize("log_size", (6, 7))
def test_an_unusable_fri_entry_is_a_clean_miss_that_recompiles(
        store, log_size, how):
    codeword = _codeword(log_size)
    sound = _fri_bytes(codeword)
    layers = log_size - LOG_FINAL
    path = os.path.join(
        exec_cache.cache_dir(),
        exec_cache.entry_key(fri.layer_parts(log_size, "levels"))
        + exec_cache._SUFFIX)
    _rewrite(path, how)
    fri.clear_layer_programs()
    exec_cache.clear_stats()
    prover.hydrate_phase_cache(None)
    # the broken size is not installed by halves; the others are
    assert sorted(fri._LAYER_PROGRAMS) == list(range(LOG_FINAL + 1,
                                                     log_size))
    with tracing.span("test.unusable") as root:
        assert _fri_bytes(codeword) == sound
    builds = [s for s in tracing.TRACER.get_trace(root.trace_id)["spans"]
              if s["name"] == "prove.fri_build"]
    assert [(s["attrs"]["log_n"], s["attrs"]["source"])
            for s in builds] == [(log_size, "compiled")]
    assert exec_cache.STATS["errors"] == 1
    assert exec_cache.STATS["stores"] == 1          # stored again
    assert exec_cache.entry_count() == 3 * layers


@pytest.mark.parametrize("rows", (16, 64))
def test_a_full_table_starts_no_warm_thread(store, monkeypatch, rows):
    log_size = (rows.bit_length() - 1) + STARK_PARAMS.log_blowup
    started = []
    real_thread = prover.threading.Thread

    def thread(*args, **kwargs):
        if kwargs.get("name") == "fri-warm":
            started.append("fri-warm")
        return real_thread(*args, **kwargs)

    monkeypatch.setattr(prover.threading, "Thread", thread)
    # one size short: the thread runs and builds that size alone
    for log_k in range(LOG_FINAL + 1, log_size):
        fri.install_layer_programs(log_k, ("leaves", "levels", "fold"))
    prover.warm_fri_programs(rows, STARK_PARAMS)
    assert started == ["fri-warm"]
    for t in prover.threading.enumerate():
        if t.name == "fri-warm":
            t.join(300)
    assert sorted(fri._LAYER_PROGRAMS) == list(range(LOG_FINAL + 1,
                                                     log_size + 1))
    assert exec_cache.STATS["stores"] == 3
    # full: no thread, nothing traced or lowered
    jax_cache.install_monitoring()
    before = dict(jax_cache.STATS)
    prover.warm_fri_programs(rows, STARK_PARAMS)
    assert started == ["fri-warm"]
    assert dict(jax_cache.STATS) == before
