"""Preemption-tolerant proving battery (docs/PROVER_RESILIENCE.md
"Runtime failures, phase checkpoints, and the degradation ladder"):
the error classification, the phase-checkpoint envelope (kill at every phase
boundary -> resume with at most one phase recomputed, byte-identical
proof; torn/garbage blobs discarded to a fresh prove), the OOM /
device-loss degradation ladder, nan-poison zero-retry quarantine, the
pre-prove memory gate, and the coordinator side: phase-transition
hedge re-anchoring, degraded-prover steering, and first-report poison
quarantine — all driven by seeded FaultPlans at the "backend.phase"
and "device.lost" sites.

Select alone with `-m chaos`; the drills that run a full STARK prove
(the crash loop and the ladder walks) are `slow` like the PR-14 soak —
the classification/envelope/coordinator units stay in the fast tier.
"""

import os
import pickle

import numpy as np
import pytest

from ethrex_tpu.l2.proof_coordinator import ProofCoordinator
from ethrex_tpu.l2.rollup_store import RollupStore
from ethrex_tpu.models import merkle_air as mair
from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.ops.merkle import fold_path_canonical
from ethrex_tpu.prover import checkpoint as ckpt
from ethrex_tpu.prover import protocol
from ethrex_tpu.prover import runtime_errors as rt
from ethrex_tpu.prover.client import ProverClient
from ethrex_tpu.stark import prover
from ethrex_tpu.stark.prover import StarkParams
from ethrex_tpu.utils import faults
from ethrex_tpu.utils.faults import FaultPlan, InjectedFault

pytestmark = pytest.mark.chaos

EXEC = protocol.PROVER_EXEC
TPU = protocol.PROVER_TPU
RNG = np.random.default_rng(61)
PARAMS = StarkParams(log_blowup=3, num_queries=12, log_final_size=4)
DEPTH = 3
PHASES = ("commit", "quotient", "open", "fri")


@pytest.fixture(autouse=True)
def _runtime_isolation(tmp_path):
    """Every test gets its own checkpoint dir and zeroed counters; no
    fault plan or context leaks out."""
    ckpt.set_checkpoint_dir(str(tmp_path / "ckpt"))
    rt.reset_stats()
    with ckpt._LOCK:
        for key in ckpt.STATS:
            ckpt.STATS[key] = 0
    yield
    faults.clear()
    ckpt.set_checkpoint_dir(None)
    rt.reset_stats()


def _material(depth=DEPTH):
    leaf = [int(v) for v in RNG.integers(0, bb.P, 8)]
    siblings = [[int(v) for v in RNG.integers(0, bb.P, 8)]
                for _ in range(depth)]
    index = int(RNG.integers(0, 1 << depth))
    bits = [(index >> j) & 1 for j in range(depth)]
    root = fold_path_canonical(index, leaf, siblings)
    air = mair.Poseidon2MerkleAir(depth)
    trace = mair.generate_merkle_trace(leaf, siblings, bits)
    pub = mair.merkle_public_inputs(leaf, root)
    return air, trace, pub


# ===========================================================================
# classification units
# ===========================================================================

def test_classify_classification():
    assert rt.classify(RuntimeError(
        "RESOURCE_EXHAUSTED: failed to allocate 4.2G")) == "oom"
    assert rt.classify(MemoryError()) == "oom"
    assert rt.classify(RuntimeError(
        "INTERNAL: lost connection to the device")) == "device_lost"
    assert rt.classify(RuntimeError("TPU slice health check failed")) \
        == "device_lost"
    assert rt.classify(rt.NanPoisonError("commit", "x")) == "nan_poison"
    assert rt.classify(ValueError("anything else")) == "unknown"
    wrapped = rt.TransientPhaseError("oom", "open", MemoryError())
    assert rt.classify(wrapped) == "oom"


def test_check_phase_outputs_names_the_phase():
    # clean artifacts pass through
    rt.check_phase_outputs("commit", {"rows": np.array([1, 2], np.uint32),
                                      "wall": 0.25, "n": 7})
    # a NaN anywhere poisons, naming the phase
    with pytest.raises(rt.NanPoisonError) as ei:
        rt.check_phase_outputs("open", {"vals": np.array([1.0, float("nan")])})
    assert ei.value.phase == "open"
    # out-of-field integers poison too (exact-arithmetic invariant)
    with pytest.raises(rt.NanPoisonError):
        rt.check_phase_outputs("fri", np.array([bb.P + 3], np.uint64))
    # the corrupt-rule envelope marker
    with pytest.raises(rt.NanPoisonError):
        rt.check_phase_outputs("commit", {"__corrupt__": True})
    assert rt.STATS["nan_poisons"] == 3


def test_guard_phase_classifies_and_wraps():
    """Transient classes come out as TransientPhaseError for the ladder;
    unknown exceptions propagate untouched; the injected legs at
    "backend.phase" and "device.lost" classify like real failures."""
    with faults.injected(FaultPlan(seed=1).error(
            "backend.phase",
            exc=RuntimeError("RESOURCE_EXHAUSTED: oom"), times=1)):
        with pytest.raises(rt.TransientPhaseError) as ei:
            rt.guard_phase("commit", "air", lambda: 1)
    assert (ei.value.kind, ei.value.phase) == ("oom", "commit")
    # the bare device.lost rule's message carries its own marker
    with faults.injected(FaultPlan(seed=2).error("device.lost", times=1)):
        with pytest.raises(rt.TransientPhaseError) as ei:
            rt.guard_phase("quotient", "air", lambda: 1)
    assert ei.value.kind == "device_lost"

    def boom():
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        rt.guard_phase("open", "air", boom)
    assert rt.guard_phase("fri", "air", lambda: 41 + 1) == 42


def _mesh8():
    from ethrex_tpu.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(8)


def test_degradation_ladder_and_kill_switch(monkeypatch):
    """8 -> 4 -> 1, always devices of the mesh itself: no rung is None
    and none sits on another platform; below one device (or with no
    mesh) there is nowhere to fall."""
    mesh = _mesh8()
    rungs = rt.degradation_ladder(mesh)
    assert [r.devices.size for r in rungs] == [4, 1]
    own = set(mesh.devices.flat)
    for rung in rungs:
        assert rung is not None
        assert set(rung.devices.flat) <= own
    assert rt.degradation_ladder(rungs[-1]) == []
    assert rt.degradation_ladder(None) == []
    monkeypatch.setenv("ETHREX_MESH_DEGRADE_OFF", "1")
    assert rt.degradation_ladder(mesh) == []
    assert rt.ladder_enabled() is False


def test_memory_gate_shrinks_before_oom(monkeypatch):
    mesh = _mesh8()
    # fits in headroom: layout untouched, nothing counted
    assert rt.memory_gate("air", mesh, est_bytes=100,
                          avail_fn=lambda m: 10_000) is mesh
    assert rt.STATS["memory_gate_shrinks"] == 0
    # the full mesh's tightest device lacks room, the 4-device rung's
    # does not — one pre-emptive degradation, no OOM thrown
    gated = rt.memory_gate(
        "air", mesh, est_bytes=100,
        avail_fn=lambda m: 10 if m.devices.size == 8 else 10_000)
    assert gated.devices.size == 4
    assert set(gated.devices.flat) <= set(mesh.devices.flat)
    assert rt.STATS["memory_gate_shrinks"] == 1
    assert rt.runtime_stats()["lastDegradation"]["reason"] == "memory_gate"
    # the kill switch disables the gate with the ladder
    monkeypatch.setenv("ETHREX_MESH_DEGRADE_OFF", "1")
    assert rt.memory_gate("air", mesh, est_bytes=100,
                          avail_fn=lambda m: 1) is mesh
    # unknown availability -> never shrink on a guess
    monkeypatch.delenv("ETHREX_MESH_DEGRADE_OFF")
    assert rt.memory_gate("air", mesh, est_bytes=100,
                          avail_fn=lambda m: None) is mesh


@pytest.mark.parametrize("layout", ["none", "mesh8"])
def test_memory_gate_raises_instead_of_leaving_the_platform(layout):
    """Nothing on the ladder fits: the gate names the AIR, its estimate
    and the free bytes in a typed error — it never answers with a CPU
    device, None-for-a-mesh, or any layout outside the mesh."""
    mesh = None if layout == "none" else _mesh8()
    with pytest.raises(rt.MemoryGateError) as ei:
        rt.memory_gate("TransferAir", mesh, est_bytes=2_000,
                       avail_fn=lambda m: 1_000)
    err = ei.value
    assert (err.air_name, err.est_bytes, err.free_bytes) == \
        ("TransferAir", 2_000, 1_000)
    assert "TransferAir" in str(err) and "2000" in str(err) \
        and "1000" in str(err)
    assert rt.STATS["memory_gate_shrinks"] == 0


def test_memory_gate_estimate_is_the_compiled_working_set():
    """The estimate is memory_analysis() of the AIR's own phase
    programs, captured at their compile (perf/hlo_introspect) — not
    cost_analysis bytes accessed.  No compile yet -> no estimate."""
    from ethrex_tpu.perf import hlo_introspect

    assert rt._estimated_bytes("NeverCompiledAir") is None

    class Mem:
        argument_size_in_bytes = 100
        output_size_in_bytes = 20
        temp_size_in_bytes = 3
        alias_size_in_bytes = 0

    class Compiled:
        def __init__(self, scale):
            self.scale = scale

        def as_text(self):
            return ""

        def memory_analysis(self):
            m = Mem()
            m.temp_size_in_bytes = 3 * self.scale
            return m

    try:
        hlo_introspect.record("GateAir", "commit", Compiled(1))
        hlo_introspect.record("GateAir", "quotient", Compiled(10))
        assert rt._estimated_bytes("GateAir") == 150.0
    finally:
        hlo_introspect.REGISTRY.reset()


# ===========================================================================
# checkpoint envelope units
# ===========================================================================

PARTS = {"kind": "proof_ckpt", "job": "j", "phase": "commit"}


def _commit_envelope(w=5, log_N=6):
    """A commit envelope in small: one row layout, the tree's levels as
    a nested list, the sponge snapshot."""
    N = 1 << log_N
    rows = RNG.integers(0, bb.P, size=(N, w), dtype=np.uint32)
    levels = [RNG.integers(0, bb.P, size=(N >> k, 8), dtype=np.uint32)
              for k in range(log_N + 1)]
    return {"lde_rows": rows, "levels_t": levels,
            "ch": {"state": list(range(16)), "absorb_pos": 3,
                   "squeeze_pos": 0}}


def test_checkpoint_roundtrip_torn_and_garbage(monkeypatch):
    parts = PARTS
    payload = _commit_envelope()
    assert ckpt.store(7, parts, payload, meta={"lease_token": "tok"})
    got = ckpt.load(7, parts)
    # several arrays, a nested list of levels and the sponge dict all
    # come back equal, dtype and shape included
    assert sorted(got) == sorted(payload) and got["ch"] == payload["ch"]
    for want, have in zip([payload["lde_rows"]] + payload["levels_t"],
                          [got["lde_rows"]] + got["levels_t"]):
        assert have.dtype == want.dtype and have.shape == want.shape
        assert np.array_equal(have, want)
    assert ckpt.STATS["stores"] == 1 and ckpt.STATS["loads"] == 1
    # different parts address a different (absent) envelope — no discard
    assert ckpt.load(7, {**parts, "phase": "open"}) is None
    assert ckpt.STATS["discards"] == 0

    path = ckpt._entry_path(7, parts)
    # torn write: truncated frame is discarded and unlinked, never raises
    with open(path, "r+b") as f:
        f.truncate(9)
    assert ckpt.load(7, parts) is None
    assert ckpt.STATS["discards"] == 1 and not os.path.exists(path)
    # garbage bytes: same fate
    assert ckpt.store(7, parts, payload)
    with open(path, "wb") as f:
        f.write(b"\x00garbage" * 64)
    assert ckpt.load(7, parts) is None
    assert ckpt.STATS["discards"] == 2 and not os.path.exists(path)
    # settled batch: complete() drops the whole envelope dir
    assert ckpt.store(7, parts, payload)
    ckpt.complete(7)
    assert ckpt.load(7, parts) is None
    assert not os.path.exists(ckpt._batch_dir(7))
    # kill switch: no stores, no loads
    monkeypatch.setenv("ETHREX_PROOF_CKPT_OFF", "1")
    assert ckpt.store(7, parts, payload) is False
    assert ckpt.load(7, parts) is None
    assert ckpt.enabled() is False


def _header_span(path):
    """(start, end) of the pickled header inside an envelope file."""
    with open(path, "rb") as f:
        _, _, count, length = ckpt._HEAD.unpack(f.read(ckpt._HEAD.size))
    start = ckpt._HEAD.size + 8 * count
    return start, start + length


def _flip(path, at):
    with open(path, "r+b") as f:
        f.seek(at)
        byte = f.read(1)
        f.seek(at)
        f.write(bytes([byte[0] ^ 0x40]))


def _damage_truncated_in_buffer(path):
    _, header_end = _header_span(path)
    with open(path, "r+b") as f:        # inside lde_rows' own bytes
        f.truncate(header_end + 100)


def _damage_flipped_buffer_byte(path):
    _flip(path, os.path.getsize(path) - 5)      # the last level's bytes


def _damage_flipped_header_byte(path):
    start, end = _header_span(path)
    _flip(path, (start + end) // 2)


def _damage_flipped_length(path):
    _flip(path, ckpt._HEAD.size + 7)    # the first buffer's length


def _damage_flipped_crc(path):
    _flip(path, 5)


def _damage_trailing_bytes(path):
    with open(path, "ab") as f:
        f.write(b"\x00" * 8)


def _damage_garbage(path):
    with open(path, "wb") as f:
        f.write(os.urandom(4096))


def _damage_empty(path):
    with open(path, "wb"):
        pass


@pytest.mark.parametrize("damage", [
    _damage_truncated_in_buffer, _damage_flipped_buffer_byte,
    _damage_flipped_header_byte, _damage_flipped_length,
    _damage_flipped_crc, _damage_trailing_bytes, _damage_garbage,
    _damage_empty], ids=lambda fn: fn.__name__[len("_damage_"):])
def test_damaged_envelope_is_discarded_never_raised(damage):
    """Whatever happens to an envelope's bytes (the header, an array's
    own buffer, the table of lengths), `load` unlinks it, counts it and
    returns None; the next store lands and loads as if nothing was."""
    payload = _commit_envelope()
    assert ckpt.store(7, PARTS, payload)
    path = ckpt._entry_path(7, PARTS)
    damage(path)
    assert ckpt.load(7, PARTS) is None
    assert ckpt.STATS["discards"] == 1 and ckpt.STATS["loads"] == 0
    assert not os.path.exists(path)
    assert ckpt.store(7, PARTS, payload)
    assert np.array_equal(ckpt.load(7, PARTS)["lde_rows"],
                          payload["lde_rows"])
    assert ckpt.STATS["discards"] == 1


def test_store_never_raises_and_leaves_no_temp_file():
    assert ckpt.store(7, {**PARTS, "phase": "open"}, {"x": 1})
    assert ckpt.store(7, PARTS, {"f": lambda: 0}) is False   # unpicklable
    assert ckpt.STATS["stores"] == 1
    names = os.listdir(ckpt._batch_dir(7))
    assert len(names) == 1 and names[0].endswith(".ckpt")
    assert ckpt.load(7, PARTS) is None and ckpt.STATS["misses"] == 1


def test_commit_envelope_is_one_layout_written_from_its_buffers():
    """The file is the row layout and the levels, once each, plus a
    header that holds no array data: an array stored twice, or pickled
    in band, would show in its size."""
    payload = _commit_envelope(w=278, log_N=10)
    arrays = [payload["lde_rows"]] + payload["levels_t"]
    assert ckpt.store(7, PARTS, payload, meta={"mesh": "none"})
    size = os.path.getsize(ckpt._entry_path(7, PARTS))
    data = sum(a.nbytes for a in arrays)
    assert data == (278 + 2 * 8) * 1024 * 4 - 8 * 4
    assert 0 < size - data < 4096
    start, end = _header_span(ckpt._entry_path(7, PARTS))
    assert start == ckpt._HEAD.size + 8 * len(arrays)
    assert size == end + data
    # the store reports what it wrote, and the ack removes the same
    assert ckpt.complete(7) == size


# the cells' trace widths (TransferAir, StateUpdateAir, TokenAir, the
# sponge binding STARK) at log_blowup 3, in small
@pytest.mark.parametrize("w,log_n", [(278, 5), (115, 6), (117, 4), (24, 3)])
def test_resume_rebuilds_invert_the_phase_layouts(w, log_n):
    """`lde_rows -> lde_cols` and `q_rows -> q_lde` are the exact
    inverses of `phase_commit`'s transpose and `phase_quotient`'s
    moveaxis + reshape: u32 data moved, none changed."""
    B, N = 8, 8 << log_n
    lde_cols = RNG.integers(0, bb.P, size=(w, N), dtype=np.uint32)
    lde_rows = np.ascontiguousarray(lde_cols.T)         # phase_commit
    back = prover.lde_cols_from_rows(lde_rows)
    assert back.dtype == np.uint32 and back.flags.c_contiguous
    assert np.array_equal(back, lde_cols)
    q_lde = RNG.integers(0, bb.P, size=(B, 4, N), dtype=np.uint32)
    q_rows = np.ascontiguousarray(                      # phase_quotient
        np.moveaxis(q_lde, -1, 0).reshape(N, B * 4))
    back = prover.q_lde_from_rows(q_rows)
    assert back.dtype == np.uint32 and back.flags.c_contiguous
    assert back.shape == (B, 4, N) and np.array_equal(back, q_lde)
    # through an envelope: the rebuilt layout of what `load` hands back
    assert ckpt.store(7, PARTS, {"lde_rows": lde_rows, "q_rows": q_rows})
    got = ckpt.load(7, PARTS)
    assert np.array_equal(prover.lde_cols_from_rows(got["lde_rows"]),
                          lde_cols)
    assert np.array_equal(prover.q_lde_from_rows(got["q_rows"]), q_lde)


def test_rebuild_is_a_leaf_span_with_phase_and_bytes():
    """A resume's rebuild is its own leaf under the consuming phase's
    stage span, with no `stage=` of its own (a stage may not run inside
    another stage of its component)."""
    from ethrex_tpu.utils import tracing

    q_rows = np.arange(64 * 32, dtype=np.uint32).reshape(64, 32)
    with tracing.span("prove.fri_fold", stage="fri_fold") as root:
        out = prover._ckpt_rebuild("quotient", prover.q_lde_from_rows,
                                   q_rows)
    spans = tracing.TRACER.get_trace(root.trace_id)["spans"]
    (leaf,) = [sp for sp in spans if sp["name"] == "ckpt.rebuild"]
    assert leaf["parentId"] == root.span_id
    assert leaf["attrs"] == {"phase": "quotient", "bytes": out.nbytes}
    assert out.shape == (8, 4, 64)


def test_phase_store_requires_batch_context():
    assert ckpt.phase_store(("air", 1), 5, (3, 12)) is None
    with ckpt.batch_context(11, lease_token="tok"):
        store = ckpt.phase_store(("air", 1), 5, (3, 12), mesh_label="1")
        assert store is not None
        assert store.store("commit", {"x": 1})
        assert store.load("commit") == {"x": 1}
        # lease token travels as metadata, NOT key material: a restarted
        # client's fresh token still addresses the same envelope
        store.meta["lease_token"] = "fresh-token"
        assert store.load("commit") == {"x": 1}
    ckpt.complete(11)


# ===========================================================================
# prove-level drills (real proofs; programs shared per-process)
# ===========================================================================

@pytest.mark.slow
def test_kill_at_every_phase_boundary_resumes_byte_identical():
    """The tentpole acceptance drill: SIGKILL (simulated by the
    "backend.phase" drop leg firing at the first phase BOUNDARY after a
    live phase completes) at every boundary in sequence.  Each restart
    loses at most the in-flight phase: exactly one new phase completes
    per cycle, the rest load from the envelope, and the final proof is
    byte-identical to an uncheckpointed run."""
    air, trace, pub = _material()
    baseline = prover.prove(air, trace, pub, PARAMS)

    # checkpointed but undisturbed: identical bytes, one store per phase
    with ckpt.batch_context(901, lease_token="t0"):
        p1 = prover.prove(air, trace, pub, PARAMS)
    assert pickle.dumps(p1) == pickle.dumps(baseline)
    assert ckpt.STATS["stores"] == len(PHASES) + 1      # + final proof

    cycles, proof = 0, None
    with ckpt.batch_context(902, lease_token="t1"):
        while proof is None:
            cycles += 1
            assert cycles <= len(PHASES) + 2, "crash loop diverged"
            faults.install(
                FaultPlan(seed=cycles).drop("backend.phase", times=1))
            try:
                proof = prover.prove(air, trace, pub, PARAMS)
            except InjectedFault:
                pass    # the simulated preemption kill
            finally:
                faults.clear()
    # one boundary kill per completed phase, then one clean pass
    assert cycles == len(PHASES) + 1
    assert pickle.dumps(proof) == pickle.dumps(baseline)
    # resumed-phase arithmetic: cycle k replays its k-1 finished phases
    assert rt.STATS["phase_resumes"] == sum(range(len(PHASES) + 1))

    # a restarted prover that already finished sees the stored proof
    before = rt.STATS["phase_resumes"]
    with ckpt.batch_context(902, lease_token="t2-after-restart"):
        p2 = prover.prove(air, trace, pub, PARAMS)
    assert pickle.dumps(p2) == pickle.dumps(baseline)
    assert rt.STATS["phase_resumes"] == before + 1
    ckpt.complete(901)
    ckpt.complete(902)


@pytest.mark.slow
def test_torn_checkpoints_fall_back_to_fresh_prove():
    """Mangling every stored envelope (torn tail, garbage bytes) never
    breaks a re-prove: bad blobs are discarded + counted, the phases
    recompute, and the proof stays byte-identical."""
    air, trace, pub = _material()
    with ckpt.batch_context(903, lease_token="t"):
        p0 = prover.prove(air, trace, pub, PARAMS)
    bdir = ckpt._batch_dir(903)
    names = sorted(os.listdir(bdir))
    assert len(names) == len(PHASES) + 1
    for i, name in enumerate(names):
        path = os.path.join(bdir, name)
        if i % 2:
            with open(path, "r+b") as f:       # torn mid-frame
                f.truncate(max(1, os.path.getsize(path) // 2))
        else:
            with open(path, "wb") as f:        # arbitrary garbage
                f.write(b"\xde\xad" * 37)
    with ckpt.batch_context(903, lease_token="t"):
        p1 = prover.prove(air, trace, pub, PARAMS)
    assert pickle.dumps(p1) == pickle.dumps(p0)
    # the proof short-circuit and the first phase were both tried and
    # thrown out; the contiguous-prefix scan stops at the first miss
    assert ckpt.STATS["discards"] >= 2
    assert rt.STATS["phase_resumes"] == 0
    ckpt.complete(903)


@pytest.mark.slow
def test_oom_walks_the_ladder_byte_identical():
    """A RESOURCE_EXHAUSTED mid-phase classifies as oom, burns no
    quarantine budget, and retries the attempt on the next rung (8
    devices -> 4 of them); exact u32 arithmetic keeps the proof
    byte-identical across layouts."""
    air, trace, pub = _material()
    baseline = prover.prove(air, trace, pub, PARAMS)
    faults.install(FaultPlan(seed=5).error(
        "backend.phase",
        exc=RuntimeError("RESOURCE_EXHAUSTED: failed to allocate"),
        times=1))
    try:
        p = prover.prove(air, trace, pub, PARAMS, mesh=_mesh8())
    finally:
        faults.clear()
    assert pickle.dumps(p) == pickle.dumps(baseline)
    stats = rt.runtime_stats()
    assert stats["oomRetries"] == 1
    assert stats["degradations"] == 1
    assert stats["lastDegradation"] == {"from": "8", "to": "4",
                                        "reason": "ladder"}


@pytest.mark.slow
def test_device_loss_retries_on_next_rung():
    air, trace, pub = _material()
    baseline = prover.prove(air, trace, pub, PARAMS)
    faults.install(FaultPlan(seed=6).error("device.lost", times=1))
    try:
        p = prover.prove(air, trace, pub, PARAMS, mesh=_mesh8())
    finally:
        faults.clear()
    assert pickle.dumps(p) == pickle.dumps(baseline)
    assert rt.runtime_stats()["deviceLostRetries"] == 1
    assert rt.runtime_stats()["lastDegradation"]["to"] == "4"


def test_oom_on_one_device_reraises_the_devices_own_error(monkeypatch):
    """With no mesh there is no rung to fall to: a chip OOM surfaces as
    the device's own error — it does not end as a proof from somewhere
    else — and nothing is counted as a degradation."""
    air, trace, pub = _material()
    boom = RuntimeError("RESOURCE_EXHAUSTED: failed to allocate 1.4G")
    attempts = []

    def attempt(air_, trace_, pub_, params_, mesh_):
        attempts.append(mesh_)
        raise rt.TransientPhaseError("oom", "quotient", boom)

    monkeypatch.setattr(prover, "_prove_attempt", attempt)
    with pytest.raises(RuntimeError) as ei:
        prover.prove(air, trace, pub, PARAMS)
    assert ei.value is boom
    assert attempts == [None]
    stats = rt.runtime_stats()
    assert stats["degradations"] == 0 and stats["oomRetries"] == 0


@pytest.mark.slow
def test_ladder_kill_switch_propagates_the_failure(monkeypatch):
    """ETHREX_MESH_DEGRADE_OFF=1: a transient failure has nowhere to
    fall and surfaces as the original exception (lease expiry handles
    it), not an infinite retry."""
    monkeypatch.setenv("ETHREX_MESH_DEGRADE_OFF", "1")
    air, trace, pub = _material()
    faults.install(FaultPlan(seed=7).error(
        "backend.phase", exc=RuntimeError("out of memory"), times=1))
    try:
        with pytest.raises(RuntimeError, match="out of memory"):
            prover.prove(air, trace, pub, PARAMS)
    finally:
        faults.clear()
    assert rt.STATS["degradations"] == 0


@pytest.mark.slow
def test_nan_poison_quarantines_without_retry():
    """A corrupt phase artifact raises NanPoisonError naming the phase
    on the FIRST attempt — the ladder never retries poison (recomputing
    garbage yields garbage) and no transient counter moves."""
    air, trace, pub = _material()
    faults.install(FaultPlan(seed=8).corrupt("backend.phase", times=1))
    try:
        with pytest.raises(rt.NanPoisonError) as ei:
            prover.prove(air, trace, pub, PARAMS)
    finally:
        faults.clear()
    assert ei.value.phase == "commit"       # first screened phase
    stats = rt.runtime_stats()
    assert stats["nanPoisons"] == 1
    assert stats["oomRetries"] == 0 and stats["degradations"] == 0


# ===========================================================================
# coordinator: phase re-anchoring, degraded steering, poison reports
# ===========================================================================

def _bare_coordinator(batches=1, **kw):
    store = RollupStore()
    for n in range(1, batches + 1):
        store.store_prover_input(n, protocol.PROTOCOL_VERSION, {"stub": n})
    kw.setdefault("needed_types", [EXEC])
    kw.setdefault("verify_submissions", False)
    return store, ProofCoordinator(store, **kw)


def _beat(co, batch, token, ptype=EXEC, **extra):
    msg = {"type": protocol.HEARTBEAT, "batch_id": batch,
           "prover_type": ptype, "lease_token": token}
    msg.update(extra)
    return co.handle_request(msg)


def test_phase_transition_reanchors_hedging(monkeypatch):
    """A prover grinding through long phases is NOT a straggler: every
    reported phase TRANSITION re-anchors the hedge clock (with the
    coordinator's own clock — phase_started is advisory), while a
    prover stuck inside one phase still gets hedged."""
    store, co = _bare_coordinator(hedge_min_samples=4, hedge_factor=1.5)
    t = [0.0]
    monkeypatch.setattr(co, "_now", lambda: t[0])
    co.durations.extend([1.0, 1.0, 1.0, 1.0])    # p99=1s -> deadline 1.5s

    batch, tok = co.assign(EXEC, "steady")
    assert batch == 1
    t[0] = 1.2
    assert _beat(co, 1, tok, phase="state_proof.commit",
                 phase_started=0.9)["ok"] is True
    assert co.lease_phase[(1, EXEC)] == ("state_proof.commit", 1.2)
    # 2.0s after assignment but only 0.8s after the transition: no hedge
    t[0] = 2.0
    assert co.assign(EXEC, "idle") == (None, None)
    # a repeat of the SAME phase does not re-anchor...
    t[0] = 2.4
    assert _beat(co, 1, tok, phase="state_proof.commit")["ok"] is True
    assert co.lease_phase[(1, EXEC)][1] == 1.2
    # ...so 1.6s of silence within one phase crosses the deadline
    t[0] = 2.8
    hbatch, htok = co.assign(EXEC, "idle")
    assert hbatch == 1 and htok not in (None, tok)
    assert co.hedges[(1, EXEC)]["reason"] == "straggler"
    # submit clears the per-lease phase record with the lease
    assert co.handle_request({
        "type": protocol.PROOF_SUBMIT, "batch_id": 1, "prover_type": EXEC,
        "lease_token": tok, "proof": {"backend": EXEC},
    })["type"] == protocol.SUBMIT_ACK
    assert (1, EXEC) not in co.lease_phase


def test_degraded_prover_steered_to_lightest_batch(monkeypatch):
    """A heartbeat-reported mesh downgrade makes the scheduler hand that
    prover the LIGHTEST waiting batch instead of trusting its stale
    EWMA."""
    store = RollupStore()

    def inp(txs):
        return {"blocks": [{"transactions": [{}] * txs}]}

    for num, txs in ((1, 1), (2, 5), (3, 3), (4, 7)):
        store.store_prover_input(num, protocol.PROTOCOL_VERSION, inp(txs))
    co = ProofCoordinator(store, needed_types=[EXEC],
                          verify_submissions=False)
    t = [0.0]
    monkeypatch.setattr(co, "_now", lambda: t[0])
    batch, tok = co.assign(EXEC, "deg")
    assert batch == 1                            # FCFS before any report
    assert _beat(co, 1, tok, prover_id="deg",
                 degraded={"from": "8", "to": "1"})["ok"] is True
    assert co.prover_stats["deg"]["degraded"] == {"from": "8", "to": "1"}
    # unleased = [2, 3, 4] with weights 6, 4, 8: the degraded prover
    # gets batch 3, not the FCFS pick (2)
    assert co.assign(EXEC, "deg")[0] == 3
    # surfaced through health for the monitor panel
    stats = co.stats_json()
    assert stats["runtime"]["degradedProvers"]["deg"]["to"] == "1"
    assert stats["scheduler"]["provers"]["deg"]["degraded"]["to"] == "1"


def test_poison_report_quarantines_first_report(monkeypatch):
    """A token-gated poison heartbeat quarantines the batch onto the
    fallback backend immediately — no failure budget burned, no second
    attempt on the poisoned backend — and the event names the phase."""
    store, co = _bare_coordinator(needed_types=[TPU])
    t = [0.0]
    monkeypatch.setattr(co, "_now", lambda: t[0])
    batch, tok = co.assign(TPU, "p1")
    assert batch == 1
    # a forged token reports nothing
    assert _beat(co, 1, "forged", ptype=TPU,
                 poison={"phase": "state_proof.commit"})["ok"] is False
    assert co.quarantined == set() and co.poison_reports_total == 0
    # the holder's report quarantines on the spot
    assert _beat(co, 1, tok, ptype=TPU,
                 poison={"phase": "state_proof.commit",
                         "detail": "non-finite array value"})["ok"] is True
    assert co.quarantined == {1}
    assert co.poison_reports_total == 1
    assert co.failures == {}                     # zero budget burned
    assert (1, TPU) not in co.assignments        # lease released
    assert any(e["event"] == "quarantine"
               and "state_proof.commit" in e.get("detail", "")
               for e in co.events)
    # the fallback backend picks the batch straight up
    assert co.assign(EXEC, "fb")[0] == 1
    # a fallback-type poison report never quarantines (nowhere to fall)
    assert co.stats_json()["runtime"]["poisonReports"] == 1


def test_client_reports_poison_and_stops_retrying():
    """Full loop over real TCP: a backend that poisons loses exactly one
    attempt — the client reports the phase via heartbeat, the
    coordinator quarantines onto the fallback type, and the client
    never re-polls the batch on the poisoned backend."""
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.l2.l1_client import InMemoryL1
    from ethrex_tpu.l2.sequencer import Sequencer, SequencerConfig
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import (TYPE_DYNAMIC_FEE,
                                                   Transaction)

    secret = 0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    genesis = {
        "config": {"chainId": 65536999, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }

    class PoisonBackend:
        prover_type = TPU

        def prove(self, program_input, proof_format):
            raise rt.NanPoisonError("state_proof.open",
                                    "non-finite array value")

    node = Node(Genesis.from_json(genesis))
    l1 = InMemoryL1([TPU])
    seq = Sequencer(node, l1, SequencerConfig(needed_prover_types=(TPU,)))
    seq.coordinator.start()
    try:
        node.submit_transaction(Transaction(
            tx_type=TYPE_DYNAMIC_FEE, chain_id=65536999, nonce=0,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=21000, to=bytes.fromhex("aa" * 20), value=5,
        ).sign(secret))
        seq.produce_block()
        assert seq.commit_next_batch() is not None
        co = seq.coordinator
        client = ProverClient(PoisonBackend(),
                              [("127.0.0.1", co.port)],
                              heartbeat_interval=0, backoff_base=0.01,
                              rng_seed=3)
        assert client.poll_once() == 0
        assert client.poisoned == [1]
        assert co.quarantined == {1}
        assert co.poison_reports_total == 1
        assert co.failures == {}
        # nothing left for the poisoned backend; fallback takes it
        assert client.poll_once() == 0
        assert co.assign(EXEC, "fb")[0] == 1
    finally:
        seq.stop()


def test_stats_json_runtime_section(monkeypatch):
    store, co = _bare_coordinator()
    t = [0.0]
    monkeypatch.setattr(co, "_now", lambda: t[0])
    batch, tok = co.assign(EXEC, "p1")
    t[0] = 0.5
    assert _beat(co, 1, tok, phase="state_proof.fri")["ok"] is True
    t[0] = 2.0
    run = co.stats_json()["runtime"]
    for key in ("oomRetries", "deviceLostRetries", "nanPoisons",
                "degradations", "memoryGateShrinks", "phaseResumes",
                "poisonReports", "degradedProvers", "livePhases",
                "checkpoints"):
        assert key in run, key
    live, = run["livePhases"]
    assert live["batch"] == 1 and live["phase"] == "state_proof.fri"
    assert abs(live["sincePhaseSeconds"] - 1.5) < 1e-9
