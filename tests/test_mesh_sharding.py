"""Mesh sharding: the pjit'd phase-program path of stark/prover.py and
the slice-parallel backend proving of prover/tpu_backend.py.

Two invariants are locked here:

1. Sharding is layout-only.  All prover arithmetic is exact u32 work,
   so a proof produced on an N-device mesh must be BYTE-identical to
   the single-device proof — same Merkle roots, same FRI openings,
   same verifier outcome.  The differential tests assert full
   JSON-equality of the proof dicts (conftest.py forces 8 virtual CPU
   devices via --xla_force_host_platform_device_count).
2. The split_mesh slice policy (parallel/mesh.py): min(jobs, devices)
   contiguous disjoint slices, every device used, sizes within one.

The fast tier keeps one cheap differential (Fibonacci) plus the pure
unit tests; the per-AIR sweep over the heavier circuits is slow-marked
(mesh phase programs are fresh XLA SPMD compiles).
"""

import json

import numpy as np
import pytest

from ethrex_tpu.ops import babybear as bb
from ethrex_tpu.parallel import mesh as mesh_lib
from ethrex_tpu.stark import prover as stark_prover
from ethrex_tpu.stark import verifier as stark_verifier
from ethrex_tpu.stark.prover import StarkParams

RNG = np.random.default_rng(11)


# ---------------------------------------------------------------------------
# split_mesh / sharding_for unit tests (pure host work)

def test_split_mesh_policy_odd_counts():
    """3 AIRs (jobs) on 2/4/8 devices: slice sizes within one, earlier
    slices take the extra device, every device used exactly once, in
    order."""
    expect = {2: [1, 1], 4: [2, 1, 1], 8: [3, 3, 2]}
    for ndev, sizes in expect.items():
        m = mesh_lib.make_mesh(ndev)
        slices = mesh_lib.split_mesh(m, 3)
        got = [len(list(s.devices.flat)) for s in slices]
        assert got == sizes, (ndev, got)
        flat = [d.id for s in slices for d in s.devices.flat]
        assert flat == [d.id for d in m.devices.flat], \
            "slices must be disjoint, contiguous and cover the mesh"
        for s in slices:
            assert s.axis_names == (mesh_lib.AXIS,)


def test_split_mesh_degenerate_cases():
    m8 = mesh_lib.make_mesh(8)
    # 1 job -> the whole mesh, unchanged
    assert mesh_lib.split_mesh(m8, 1) == [m8]
    # more jobs than devices -> one slice per device, never more
    assert [len(list(s.devices.flat))
            for s in mesh_lib.split_mesh(m8, 12)] == [1] * 8
    # 1 device -> serial fallback regardless of job count
    m1 = mesh_lib.make_mesh(1)
    assert mesh_lib.split_mesh(m1, 5) == [m1]


def test_sharding_for_drops_ragged_axes():
    """The shared partition-or-replicate policy: an AXIS entry survives
    only when the dimension splits evenly across the mesh."""
    from jax.sharding import PartitionSpec

    m4 = mesh_lib.make_mesh(4)
    A = mesh_lib.AXIS
    keep = mesh_lib.sharding_for(m4, (64, 8), (A, None))
    assert keep.spec == PartitionSpec(A, None)
    # 6 % 4 != 0 -> replicated
    ragged = mesh_lib.sharding_for(m4, (6, 8), (A, None))
    assert ragged.spec == PartitionSpec(None, None)
    # dim < ndev -> replicated
    small = mesh_lib.sharding_for(m4, (2, 8), (A, None))
    assert small.spec == PartitionSpec(None, None)
    # 1-device mesh shards nothing
    m1 = mesh_lib.make_mesh(1)
    none = mesh_lib.sharding_for(m1, (64, 8), (A, None))
    assert none.spec == PartitionSpec(None, None)


def test_mesh_key_distinguishes_layouts():
    """_mesh_key must separate no-mesh, different sizes and different
    device subsets — a stale compiled program must never be served
    across a mesh switch."""
    k_none = stark_prover._mesh_key(None)
    k2 = stark_prover._mesh_key(mesh_lib.make_mesh(2))
    k4 = stark_prover._mesh_key(mesh_lib.make_mesh(4))
    k4b = stark_prover._mesh_key(mesh_lib.make_mesh(4))
    sub = stark_prover._mesh_key(
        mesh_lib.split_mesh(mesh_lib.make_mesh(4), 2)[1])
    assert k_none is None
    assert len({k2, k4, sub}) == 3
    assert k4 == k4b, "identical layout must hit the cache"


# ---------------------------------------------------------------------------
# sharded-vs-single differential proving

FAST_PARAMS = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)


def _fib_case():
    from ethrex_tpu.models import fibonacci as fib

    air = fib.FibonacciAir()
    trace = fib.generate_trace(64)
    return air, trace, fib.public_inputs(trace), FAST_PARAMS


def _sponge_case():
    from ethrex_tpu.models import poseidon2_air as pair

    msg = [int(v) for v in RNG.integers(0, bb.P, 17)]
    air = pair.Poseidon2SpongeAir(num_chunks=3)
    trace = pair.generate_sponge_trace(msg)
    pub = pair.sponge_public_inputs(msg)
    return air, trace, pub, StarkParams(log_blowup=3, num_queries=25,
                                        log_final_size=4)


def _poseidon2_case():
    from ethrex_tpu.models import poseidon2_air as pair

    limbs = [int(v) for v in RNG.integers(0, bb.P, 8)]
    air = pair.Poseidon2Air()
    trace = pair.generate_trace(limbs)
    pub = pair.public_inputs(limbs)
    return air, trace, pub, StarkParams(log_blowup=3, num_queries=25,
                                        log_final_size=4)


def _merkle_case():
    from ethrex_tpu.models import merkle_air as mair
    from ethrex_tpu.ops.merkle import fold_path_canonical

    depth = 1
    leaf = [int(v) for v in RNG.integers(0, bb.P, 8)]
    siblings = [[int(v) for v in RNG.integers(0, bb.P, 8)]
                for _ in range(depth)]
    bits = [0]
    root = fold_path_canonical(0, leaf, siblings)
    air = mair.Poseidon2MerkleAir(depth)
    trace = mair.generate_merkle_trace(leaf, siblings, bits)
    pub = mair.merkle_public_inputs(leaf, root)
    return air, trace, pub, StarkParams(log_blowup=3, num_queries=25,
                                        log_final_size=4)


def _state_update_case():
    from ethrex_tpu.models import state_update_air as sua
    from ethrex_tpu.stark import state_tree

    rng = np.random.default_rng(3)

    def word(tag):
        return bytes(rng.integers(0, 256, 32, dtype=np.uint8))

    entries = {word(i): word(i + 100) for i in range(4)}
    tree = state_tree.TouchedStateTree(entries, 2)
    r_pre = tree.root
    keys = list(entries)
    accesses = [tree.update(keys[int(rng.integers(0, len(keys)))],
                            word(w + 200)) for w in range(3)]
    depth, S = 2, 8
    air = sua.StateUpdateAir(depth, seg_periods=S)
    trace = sua.generate_state_update_trace(accesses, r_pre, depth, S)
    pub = sua.state_update_public_inputs(accesses, r_pre, tree.root, S)
    return air, trace, pub, StarkParams(log_blowup=3, num_queries=25,
                                        log_final_size=4)


def _transfer_case():
    from ethrex_tpu.models import transfer_air as ta
    from ethrex_tpu.primitives.account import AccountState

    value, fee, tip = 1000, 21000 * 7, 21000 * 2
    s_old = AccountState(nonce=4, balance=10**18)
    s_new = AccountState(nonce=5, balance=10**18 - value - fee)
    r_old = AccountState(nonce=1, balance=500)
    r_new = AccountState(nonce=1, balance=500 + value)
    tx = ta.TxSeg(bytes.fromhex("11" * 20), bytes.fromhex("22" * 20),
                  s_old, s_new, r_old, r_new, value, fee, tip,
                  r_created=False, r_noop=False)
    air = ta.TransferAir()
    trace = ta.generate_transfer_trace([tx])
    pub = ta.transfer_public_inputs([tx])
    return air, trace, pub, StarkParams(log_blowup=3, num_queries=25,
                                        log_final_size=4)


def _token_case():
    from ethrex_tpu.guest.transfer_log import TokSeg
    from ethrex_tpu.models import token_air as tka

    v1 = 12345
    kf = int.from_bytes(b"\x11" * 32, "big")
    kt = int.from_bytes(b"\x22" * 32, "big")
    segs = [TokSeg(v1, kf, 10**6, 10**6 - v1, kt, 500, 500 + v1),
            TokSeg(0, 0, 0, 0, 0, 0, 0, noop=True)]
    air = tka.TokenAir()
    trace = tka.generate_token_trace(segs)
    pub = tka.token_public_inputs(segs)
    return air, trace, pub, StarkParams(log_blowup=3, num_queries=25,
                                        log_final_size=4)


def _assert_differential(case):
    air, trace, pub, params = case()
    single = stark_prover.prove(air, trace, pub, params)
    sharded = stark_prover.prove(air, trace, pub, params,
                                 mesh=mesh_lib.make_mesh(4))
    # byte-identical: same roots, same FRI layers, same query openings
    assert json.dumps(single, sort_keys=True) == \
        json.dumps(sharded, sort_keys=True)
    assert stark_verifier.verify(air, sharded, params)


@pytest.mark.slow
def test_sharded_prove_bit_identical_fibonacci():
    _assert_differential(_fib_case)


@pytest.mark.slow
@pytest.mark.parametrize("case", [
    _sponge_case, _poseidon2_case, _merkle_case, _state_update_case,
    _transfer_case, _token_case,
], ids=["sponge", "poseidon2", "merkle", "state_update", "transfer",
        "token"])
def test_sharded_prove_bit_identical(case):
    _assert_differential(case)


@pytest.mark.slow
def test_sharded_prove_bit_identical_bytecode():
    from ethrex_tpu.guest import bytecode_vm as bv
    from ethrex_tpu.models import bytecode_air as bca

    # registry-with-guard contract, store branch — mirrors the
    # test_bytecode_vm prove recipe
    code = bytes([
        0x60, 0x00, 0x35, 0x60, 0x20, 0x35, 0x80, 0x82, 0x54, 0x10,
        0x61, 0x00, 0x14, 0x57, 0x61, 0x03, 0xE8, 0x55, 0x50, 0x00,
        0x5B, 0x90, 0x55, 0x00,
    ])
    cd = (5).to_bytes(32, "big") + (42).to_bytes(32, "big")
    pre = {5: 10}
    steps, snaps, _writes = bv.run_trace(code, cd, b"\x11" * 20, 0,
                                         lambda s: pre.get(s, 0))
    air = bca.BytecodeAir()
    trace = bca.generate_bytecode_trace(steps, snaps)
    pub = bca.bytecode_public_inputs(steps)
    params = StarkParams(log_blowup=3, num_queries=40, log_final_size=4)
    single = stark_prover.prove(air, trace, pub, params)
    sharded = stark_prover.prove(air, trace, pub, params,
                                 mesh=mesh_lib.make_mesh(4))
    assert json.dumps(single, sort_keys=True) == \
        json.dumps(sharded, sort_keys=True)


@pytest.mark.slow
def test_aggregate_outer_proof_accepts_mesh():
    """FriVerifyAir differential through the aggregation entry point:
    the outer recursion proof must be byte-identical with and without a
    mesh, and verify_aggregated must accept the mesh-built aggregate."""
    from ethrex_tpu.models.fibonacci import FibonacciAir, generate_trace
    from ethrex_tpu.stark import aggregate

    params = StarkParams(log_blowup=2, num_queries=2, log_final_size=4)
    airs, proofs = [], []
    for i in range(2):
        air = FibonacciAir()
        trace = generate_trace(16, a0=1, b0=2 + i)
        pub = [1, 2 + i, int(trace[-1, 1])]
        proofs.append(stark_prover.prove(air, trace, pub, params))
        airs.append(air)
    outer_params = StarkParams(log_blowup=3, num_queries=8,
                               log_final_size=4)
    plain = aggregate.aggregate(airs, proofs, params, outer_params)
    meshed = aggregate.aggregate(airs, proofs, params, outer_params,
                                 mesh=mesh_lib.make_mesh(4))
    assert json.dumps(plain.outer, sort_keys=True) == \
        json.dumps(meshed.outer, sort_keys=True)
    assert aggregate.verify_aggregated(airs, meshed, params,
                                       outer_params)


# ---------------------------------------------------------------------------
# phase-cache staleness + retrace accounting

def _retrace_counts():
    from ethrex_tpu.utils.metrics import METRICS

    snap = METRICS.snapshot()
    fam = snap.get("labeled_counters", {}).get(
        "prover_kernel_retraces_total", [])
    return {row["labels"].get("mesh"): row["value"] for row in fam}


@pytest.mark.slow
def test_phase_cache_mesh_switches_never_stale():
    """no-mesh -> mesh(2) -> no-mesh -> mesh(4) -> mesh(2) again on one
    AIR shape: every proof byte-identical, each NEW layout is a counted
    retrace (labelled with its mesh shape), and revisiting a layout is
    a cache hit (no extra retrace)."""
    air, trace, pub, params = _fib_case()
    m2 = mesh_lib.make_mesh(2)
    m4 = mesh_lib.make_mesh(4)

    before = _retrace_counts()
    ref = stark_prover.prove(air, trace, pub, params)
    for mesh in (m2, None, m4, m2):
        proof = stark_prover.prove(air, trace, pub, params, mesh=mesh)
        assert json.dumps(proof, sort_keys=True) == \
            json.dumps(ref, sort_keys=True)
    after = _retrace_counts()

    def delta(label):
        return after.get(label, 0) - before.get(label, 0)

    # one build per distinct layout at most (zero when a previous test
    # in this process already compiled it), never one per prove
    assert delta("2") <= 1
    assert delta("4") <= 1
    assert delta("none") <= 1
    # the second mesh(2) prove and the second no-mesh prove were hits:
    # 5 proves, at most 3 builds
    total = sum(after.values()) - sum(before.values())
    assert total <= 3


# ---------------------------------------------------------------------------
# slice-parallel proof jobs (backend machinery)

@pytest.mark.slow
def test_parallel_proof_jobs_bit_identical_and_instrumented():
    """_run_proof_jobs: the mesh-sliced concurrent path returns the
    same proofs as the serial path, publishes the mesh gauges, and
    records per-slice vm_circuits/<air> stage walls."""
    from ethrex_tpu.prover.tpu_backend import _run_proof_jobs
    from ethrex_tpu.utils import tracing
    from ethrex_tpu.utils.metrics import METRICS

    air, trace, pub, params = _fib_case()

    def mk(name, group):
        def job(job_mesh):
            return stark_prover.prove(air, trace, pub, params,
                                      mesh=job_mesh)
        return (name, group, job)

    jobs = [mk("state_proof", "state_proof"),
            mk("vm_circuits/TransferAir", "vm_circuits"),
            mk("vm_circuits/TokenAir", "vm_circuits")]
    with tracing.span("backend.prove"):
        serial = _run_proof_jobs(jobs, None)
    snap = METRICS.snapshot()
    assert snap["gauges"]["prover_mesh_devices"] == 1.0
    assert snap["gauges"]["prover_vm_circuits_parallel"] == 1.0

    with tracing.span("backend.prove"):
        par = _run_proof_jobs(jobs, mesh_lib.make_mesh(2))
    assert json.dumps(serial, sort_keys=True) == \
        json.dumps(par, sort_keys=True)
    snap = METRICS.snapshot()
    assert snap["gauges"]["prover_mesh_devices"] == 2.0
    assert snap["gauges"]["prover_vm_circuits_parallel"] == 2.0
    hist = snap["histograms"].get("prover_stage_seconds", {})
    stages = {row["labels"]["stage"] for row in hist.get("series", [])}
    assert {"state_proof", "vm_circuits", "vm_circuits/TransferAir",
            "vm_circuits/TokenAir"} <= stages


def test_perf_rpc_exposes_mesh_gauges():
    """ethrex_perf carries the mesh section next to throughput."""
    from ethrex_tpu.rpc import server as rpc_server
    from ethrex_tpu.utils.metrics import (record_mesh_devices,
                                          record_vm_parallelism)

    record_mesh_devices(4)
    record_vm_parallelism(3)
    out = rpc_server._perf(None)
    assert out["mesh"]["devices"] == 4.0
    assert out["mesh"]["vmCircuitsParallel"] == 3.0
