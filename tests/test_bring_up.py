"""The places where a proof could leave the chip, or a failure pass as
success, are closed — one pin each (ISSUE 25): chip_smoke.py refuses to
run without a TPU, the compile cache can be placed from outside and sits
in the checkout otherwise, and `_aot_phases` propagates a compile error.
(The degradation ladder and the memory gate are pinned in
tests/test_runtime_chaos.py, the peak table in tests/test_perf.py.)"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from ethrex_tpu.models import fibonacci as fib
from ethrex_tpu.stark import prover as stark_prover
from ethrex_tpu.utils import exec_cache, jax_cache

REPO = pathlib.Path(__file__).resolve().parent.parent
SHIFT = stark_prover.StarkParams().shift


def _run(argv, cwd=REPO, **env):
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=full,
                          capture_output=True, text=True, timeout=300)


# ---------------------------------------------------------------------------
# chip_smoke.py

def _import_chip_smoke():
    sys.path.insert(0, str(REPO))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    return chip_smoke


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_chip_smoke_without_a_tpu_fails_and_says_so(argv):
    proc = _run([str(REPO / "chip_smoke.py"), *argv])
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "no TPU" in last["error"]
    # it did no work: the stack never started
    assert "l2 stack started" not in proc.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    """The script without the program proves nothing: it must fail."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py"], cwd=tmp_path, PYTHONPATH="")
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


@pytest.mark.slow
def test_chip_smoke_work_function_on_the_cpu(monkeypatch):
    """Step-0 rehearsal: the very function main() runs on the chip,
    with one transfer a batch, on the CPU — finds wrong wiring.  Slow:
    the 278-column TransferAir takes many minutes to compile on
    XLA:CPU, so the batch timeout is the one thing stretched."""
    chip_smoke = _import_chip_smoke()
    monkeypatch.setattr(chip_smoke, "BATCH_TIMEOUT", 7200.0)
    chip_smoke.run_batches(1)


def test_chip_smoke_sums_compile_seconds_per_air_and_kernel(monkeypatch):
    """Seconds of the phase-compile histogram summed per "Air/kernel"
    over both sources, a mesh build under its own key, in the order the
    rows were first recorded."""
    from ethrex_tpu.utils import metrics

    chip_smoke = _import_chip_smoke()
    monkeypatch.setattr(metrics, "METRICS", metrics.Metrics())
    assert chip_smoke._compile_counts()[1] == {}
    metrics.record_phase_compile("TransferAir", "quotient", 1.5)
    metrics.record_phase_compile("TransferAir", "quotient", 2.25)
    metrics.record_phase_compile("TransferAir", "quotient", 0.125,
                                 source="deserialized")
    metrics.record_phase_compile("FibAir", "commit", 0.5, mesh="2x1")
    metrics.record_phase_compile("FibAir", "commit", 0.25,
                                 source="deserialized")
    walls = chip_smoke._compile_counts()[1]
    assert list(walls.items()) == [("TransferAir/quotient", 3.875),
                                   ("FibAir/commit@2x1", 0.5),
                                   ("FibAir/commit", 0.25)]


# ---------------------------------------------------------------------------
# one cache root, placeable from outside

def _config_updates(monkeypatch):
    seen = {}
    real = jax.config.update

    def spy(name, value):
        seen[name] = value
        if name != "jax_compilation_cache_dir":
            real(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    return seen


def test_cache_dir_from_outside_is_left_to_jax(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, JAX reads it itself; the
    code sets no directory, and every other cache follows the root."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("ETHREX_EXEC_CACHE_DIR", raising=False)
    seen = _config_updates(monkeypatch)
    jax_cache.enable_persistent_cache()
    assert "jax_compilation_cache_dir" not in seen
    assert jax_cache.cache_dir() == str(tmp_path)
    assert exec_cache.cache_dir() == str(tmp_path / "exec")


def test_cache_dir_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("ETHREX_EXEC_CACHE_DIR", raising=False)
    seen = _config_updates(monkeypatch)
    jax_cache.enable_persistent_cache()
    want = str(REPO / ".jax_cache")
    assert seen["jax_compilation_cache_dir"] == want
    assert exec_cache.cache_dir() == os.path.join(want, "exec")
    ignored = (REPO / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_cache_dir_is_the_same_in_every_process():
    """The path is part of a cache entry's key: no pid, time, temporary
    name or host hash may enter it."""
    code = ("from ethrex_tpu.utils import exec_cache, jax_cache;"
            "print(jax_cache.cache_dir()); print(exec_cache.cache_dir())")
    first = _run(["-c", code], TMPDIR="/tmp/a", HOME="/tmp/home-a")
    second = _run(["-c", code], TMPDIR="/tmp/b", HOME="/tmp/home-b")
    assert first.returncode == 0, first.stderr
    assert first.stdout == second.stdout
    assert first.stdout.split() == [str(REPO / ".jax_cache"),
                                    str(REPO / ".jax_cache" / "exec")]


def test_hydrated_executable_is_bound_to_its_own_devices(
        monkeypatch, tmp_path):
    """Found by the --chips 4 rehearsal: deserialize_and_load binds an
    executable to EVERY device of the backend unless told otherwise, so
    a single-device phase program hydrated on a multi-device host
    demanded one shard per device.  The store names the devices: the
    entry's mesh, or the default device."""
    import jax.numpy as jnp
    import numpy as np

    monkeypatch.setenv("ETHREX_EXEC_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("ETHREX_EXEC_CACHE_OFF", raising=False)
    monkeypatch.setattr(exec_cache, "_CONFIGURED_DIR", None)
    assert len(jax.devices()) > 1           # the suite's virtual mesh
    spec = jax.ShapeDtypeStruct((4, 4), jnp.uint32)
    compiled = jax.jit(lambda x: x + 1).lower(spec).compile()
    parts = {"kind": "phase", "kernel": "commit", "mesh": None}
    assert exec_cache.store(parts, compiled)
    loaded = exec_cache.load(parts)
    assert loaded.runtime_executable().local_devices() == \
        [jax.devices()[0]]
    out = loaded(jnp.zeros((4, 4), jnp.uint32))
    assert np.array_equal(np.asarray(out), np.ones((4, 4), np.uint32))
    # and a mesh entry is bound to exactly its mesh's devices, by id
    pair = (tuple(int(d.id) for d in jax.devices()[2:4]), ("shard",),
            (2,))
    assert exec_cache._execution_devices({"mesh": pair}) == \
        jax.devices()[2:4]


# ---------------------------------------------------------------------------
# _aot_phases: a compile error propagates

@pytest.mark.parametrize("kernel", stark_prover._KERNELS)
def test_aot_phases_propagates_a_compile_error(kernel):
    """A phase the compiler refuses fails the build with the compiler's
    own error: no replicated re-compile, no lazy-jit substitute."""
    air = fib.FibonacciAir()
    bodies, plan = stark_prover._build_phases(air, 4, 2, SHIFT)

    def refused(*args):
        raise NotImplementedError(f"{kernel}: refused by the compiler")

    broken = tuple(refused if k == kernel else b
                   for k, b in zip(stark_prover._KERNELS, bodies))
    with pytest.raises(NotImplementedError, match=kernel):
        stark_prover._aot_phases(air, 4, 2, SHIFT, broken, plan, None)()
    assert not hasattr(stark_prover, "_shard_map_program")


def test_phase_programs_build_once_and_compile_ahead_hands_over(
        monkeypatch):
    """compile_ahead starts a build on a background thread; the prove
    that follows waits for the build in flight instead of starting a
    second one, and both see the same programs."""
    import threading

    air = fib.FibonacciAir()
    builds = []
    gate = threading.Event()
    real = stark_prover._build_phases

    def counted(*args, **kw):
        builds.append(args[1])
        gate.wait(30)
        return real(*args, **kw)

    monkeypatch.setattr(stark_prover, "_build_phases", counted)
    stark_prover.clear_phase_cache()
    params = stark_prover.StarkParams(log_blowup=2)
    stark_prover.compile_ahead([(air, 32)], params)
    for _ in range(200):                    # until the build is in flight
        if builds:
            break
        threading.Event().wait(0.01)
    got = []
    waiter = threading.Thread(target=lambda: got.append(
        stark_prover._phases(air, 5, 2, SHIFT)))
    waiter.start()
    gate.set()
    waiter.join(600)       # bounds a hang; the build is a minute alone
    assert builds == [5]                    # one build, not two
    assert got and got[0] is stark_prover._phases(air, 5, 2, SHIFT)
    assert not stark_prover._PHASE_BUILDS


def test_compile_ahead_queues_builds_in_the_order_asked(monkeypatch):
    """The AIR asked for first has its builds first in the pool's queue,
    however long its `_build_phases` takes beside the others': the job
    that runs first must not wait for every other AIR's programs (a cold
    token batch's state circuit did; PR 29).  A build that fails leaves
    the ones asked for after it their turn."""
    import threading
    import time

    queued, done = [], threading.Event()

    def slow_for_the_first(air, log_n, *args, **kw):
        if log_n == 5:
            time.sleep(0.5)             # the wider AIR's host work
        return (log_n,) * 4, None       # four "bodies" that say whose

    class Pool:
        def submit(self, build, kernel, fn):
            queued.append(fn)
            if fn == 6:
                done.set()
            raise NotImplementedError("nothing compiles here")

    monkeypatch.setattr(stark_prover, "_build_phases", slow_for_the_first)
    monkeypatch.setattr(stark_prover, "_COMPILE_POOL", Pool())
    monkeypatch.setattr(stark_prover, "_jit_programs",
                        lambda bodies, plan: bodies)
    stark_prover.clear_phase_cache()
    params = stark_prover.StarkParams(log_blowup=2)
    air = fib.FibonacciAir()
    stark_prover.compile_ahead([(air, 32), (air, 64)], params)
    assert done.wait(30)
    # the slow AIR's submit came first; its failure cost the second
    # nothing, and no build is left in flight
    assert queued == [5, 6]
    for _ in range(200):
        if not stark_prover._PHASE_BUILDS:
            break
        time.sleep(0.01)
    assert not stark_prover._PHASE_BUILDS


def test_a_failed_compile_ahead_fails_the_prove_that_waited(monkeypatch):
    import threading

    air = fib.FibonacciAir()
    gate = threading.Event()

    def refused(*args, **kw):
        gate.wait(30)
        raise NotImplementedError("refused by the compiler")

    monkeypatch.setattr(stark_prover, "_build_phases", refused)
    stark_prover.clear_phase_cache()
    stark_prover.compile_ahead([(air, 64)], stark_prover.StarkParams(
        log_blowup=2))
    for _ in range(200):
        if stark_prover._PHASE_BUILDS:
            break
        threading.Event().wait(0.01)
    errors = []

    def prove_side():
        try:
            stark_prover._phases(air, 6, 2, SHIFT)
        except NotImplementedError as exc:
            errors.append(str(exc))

    waiter = threading.Thread(target=prove_side)
    waiter.start()
    gate.set()
    waiter.join(60)
    assert errors == ["refused by the compiler"]
    assert not stark_prover._PHASE_BUILDS and not any(
        k[1] == 6 for k in stark_prover._PHASE_CACHE)


def test_prove_asks_ahead_for_every_air_of_the_batch(monkeypatch):
    """A token batch lays four jobs (state -> transfer -> token ->
    binding).  `_prove_impl` asks ahead for the programs of all four
    AIRs, in the order their jobs run, before the first job runs, and
    warms the FRI
    programs from the batch's largest trace down: the token circuit's
    programs must not start building only when the transfer circuit has
    proved.  `prove.vm_batch` says what the batch was.  Spies only:
    nothing compiles, nothing proves."""
    from ethrex_tpu.prover import tpu_backend
    from tests.test_erc20_cell import SEEDS, drive

    asked, warmed, recorded = [], [], {}

    class Stop(Exception):
        pass

    def jobs_reached(jobs, mesh):
        raise Stop([name for name, _, _ in jobs])

    monkeypatch.setattr(
        stark_prover, "compile_ahead",
        lambda asks, params, mesh=None: asked.extend(
            (type(air).__name__, n) for air, n in asks))
    monkeypatch.setattr(stark_prover, "warm_fri_programs",
                        lambda n, params: warmed.append(n))
    monkeypatch.setattr(tpu_backend, "_run_proof_jobs", jobs_reached)
    monkeypatch.setattr(
        tpu_backend.tracing, "record_span",
        lambda name, start, seconds, **attrs: recorded.update(
            {name: attrs}))
    with pytest.raises(Stop) as stopped:
        # one block of 6 token calls from 2 senders
        batch = drive("small", SEEDS[0])[1][0][0]
        tpu_backend.TpuBackend()._prove_impl(batch, "stark")
    assert stopped.value.args[0] == [
        "state_proof", "vm_circuits/TransferAir", "vm_circuits/TokenAir"]
    # 25 access records -> 32 segments x 16 periods x 32 rows; 6 calls:
    # 13 of 16 transfer segments, 7 of 8 token segments, x 512
    assert asked == [("StateUpdateAir", 16384), ("TransferAir", 8192),
                     ("TokenAir", 4096), ("Poseidon2SpongeAir", 512)]
    # the largest trace is the state circuit's
    assert warmed == [16384]
    # 6 senders' rows, 6 coinbase rows and the token's; 2 slots a call
    assert recorded["prove.vm_batch"] == {
        "mode": "token", "p2": tpu_backend.p2.engine(), "txs": 6,
        "tok_calls": 6, "acct_rows": 13, "slot_rows": 12}


@pytest.mark.parametrize("event, key", [
    ("/jax/core/compile/jaxpr_trace_duration", "trace_seconds"),
    ("/jax/core/compile/jaxpr_to_mlir_module_duration", "lower_seconds"),
    ("/jax/compilation_cache/cache_retrieval_time_sec",
     "cache_retrieval_seconds"),
    ("/jax/core/compile/backend_compile_duration", "compile_seconds"),
])
def test_duration_listener_splits_a_programs_way_to_the_device(event, key):
    """Trace, lower, backend compile and cache retrieval each have a
    key of their own in jax_cache.STATS (the set-up log's split); an
    event of another name moves none of them."""
    before = dict(jax_cache.STATS)
    jax_cache._on_duration(event, 0.25)
    jax_cache._on_duration("/jax/some/other_duration", 9.0)
    moved = {k for k in before if jax_cache.STATS[k] != before[k]}
    assert key in moved and moved <= {key, "compiles"}
    assert jax_cache.STATS[key] == pytest.approx(before[key] + 0.25)
    assert jax_cache.STATS["compiles"] - before["compiles"] == \
        (1 if key == "compile_seconds" else 0)


def test_a_real_jit_moves_the_trace_and_lower_seconds():
    import jax
    import jax.numpy as jnp

    jax_cache.install_monitoring()
    before = dict(jax_cache.STATS)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert jax_cache.STATS["trace_seconds"] > before["trace_seconds"]
    assert jax_cache.STATS["lower_seconds"] > before["lower_seconds"]
    assert jax_cache.STATS["compiles"] > before["compiles"]
