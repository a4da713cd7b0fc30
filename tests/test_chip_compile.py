"""The main path's kernels through the TPU compiler, with no chip.

libtpu compiles for a chip that is DESCRIBED (a v5e 2x2 topology), not
attached: what the compiler refuses here it would refuse on the machine
with the chip, and here it costs no chip time.  Nothing runs, so these
tests say nothing about results or speed — only that every program of a
BASELINE-1 batch (10 transfers, `tpu_backend.PARAMS`), and the token
circuit's of a `prove-erc20` batch, lowers, partitions and fits at its
real width.

This is the ONE file of TPU-compiler tests: only one process may hold
libtpu, a pytest-xdist worker that described the topology keeps it until
it exits, and a second file could land on another worker.  For the same
reason the topology is described inside a fixture — never at import, in
a skipif or in a parametrize argument.

Each case prints its compile seconds and memory_analysis() (`pytest -s`):
that is the compile bill a cold prover pays on the chip's host.  Tier-1
keeps the cases that compile in about a minute or less; the slow-marked
ones (TransferAir's four phases on one chip and on a two-chip slice,
StateUpdateAir's commit, quotient and open, TokenAir's quotient and open,
the width-278 Merkle tree, the Groth16 MSM at 13 minutes) are run by hand
before a chip call:

    JAX_PLATFORMS=cpu python -m pytest tests/test_chip_compile.py -s -m slow
"""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from ethrex_tpu.models import poseidon2_air as pair
from ethrex_tpu.models import state_update_air as sua
from ethrex_tpu.models import token_air as tka
from ethrex_tpu.models import transfer_air as ta
from ethrex_tpu.ops import bn254_msm, fri, merkle, ntt, poseidon2
from ethrex_tpu.parallel import mesh as mesh_lib
from ethrex_tpu.perf import hlo_introspect
from ethrex_tpu.prover.tpu_backend import PARAMS
from ethrex_tpu.stark import prover as stark_prover

U32 = jnp.uint32
LB = PARAMS.log_blowup                  # 3
LOG_N = 14                              # 10 transfers x 2 segments x 512 rows
LOG_LDE = LOG_N + LB                    # 17
TRANSFER_WIDTH = 278

# the AIRs of a BASELINE-1 batch at the shapes TpuBackend._prove_impl
# gives them (10 transfers: 30 access records -> depth 4, 16 segment
# periods, 2^14 rows; 104 binding limbs -> 13 sponge chunks, 2^9 rows)
AIRS = {
    "StateUpdateAir": (lambda: sua.StateUpdateAir(4, seg_periods=16), 14),
    "Poseidon2SpongeAir": (lambda: pair.Poseidon2SpongeAir(num_chunks=13),
                           9),
    "TransferAir": (ta.TransferAir, 14),
    # the fourth AIR of a `prove-erc20` batch (15 token calls: 16
    # segments x 512 rows)
    "TokenAir": (tka.TokenAir, 13),
}


@pytest.fixture(scope="module")
def topo():
    """The described v5e 2x2, with the persistent compile cache off
    around every compile of this module: an entry written for a
    described chip cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(name, fn, *specs, **static):
    t0 = time.perf_counter()
    compiled = fn.lower(*specs, **static).compile()
    mem = hlo_introspect.parse_memory_analysis(compiled.memory_analysis())
    print(f"\n[v5e described] {name}: compiled in "
          f"{time.perf_counter() - t0:.1f}s; arg {mem['argBytes']:.0f} "
          f"out {mem['outputBytes']:.0f} temp {mem['tempBytes']:.0f} bytes")
    # one program must fit a v5e's 16 GB on its own
    assert mem["peakBytes"] < 16e9
    return compiled


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, U32, sharding=sharding)


def _phase(air_name, kernel, mesh, one_chip):
    """One phase program of `air_name`, built as _build_phases builds it
    and given the argument shapes _aot_phases gives it."""
    make, log_n = AIRS[air_name]
    air = make()
    shift = PARAMS.shift
    bodies, plan = stark_prover._build_phases(air, log_n, LB, shift, mesh)
    fns = stark_prover._jit_programs(bodies, plan)
    specs = stark_prover._phase_arg_specs(air, log_n, LB)[kernel]
    if mesh is None:
        specs = tuple(_spec(s.shape, one_chip) for s in specs)
    fn = fns[stark_prover._KERNELS.index(kernel)]
    label = f"{air_name}/{kernel}" + \
        ("" if mesh is None else f"@{mesh.devices.size}")
    return _compile(label, fn, *specs)


def test_coset_lde_transfer_width(topo, one_chip):
    fn = jax.jit(lambda cols: ntt.coset_lde(cols, LB, shift=PARAMS.shift))
    out = _compile("coset_lde (278, 2^14) -> 2^17", fn,
                   _spec((TRANSFER_WIDTH, 1 << LOG_N), one_chip))
    assert out.output_shardings is not None


@pytest.mark.slow
def test_merkle_build_levels_transfer_width(topo, one_chip):
    """The trace-commitment tree at TransferAir's full leaf width.
    Slow: 76-79 s to compile, and a shorter tree is no quicker (2^14
    rows took 76 s) — the width-278 leaf hash is what costs."""
    _compile("merkle._build_levels (2^17, 278)", merkle._build_levels,
             _spec((1 << LOG_LDE, TRANSFER_WIDTH), one_chip))


def test_fri_fold_first_layer(topo, one_chip):
    n = 1 << LOG_LDE
    _compile("fri._fold at 2^17", fri._fold,
             _spec((n, 4), one_chip), _spec((4,), one_chip),
             _spec((n // 2,), one_chip), _spec((), one_chip))


def test_fri_layer_commit(topo, one_chip):
    """The per-layer FRI commitment: pair the codeword's halves into
    leaves and build their Merkle tree (first, largest layer)."""
    n = 1 << LOG_LDE
    _compile("fri._pair_leaves at 2^17", fri._pair_leaves,
             _spec((n, 4), one_chip))
    _compile("merkle._build_levels (2^16, 8)", merkle._build_levels,
             _spec((n // 2, 8), one_chip))


def test_poseidon2_permute(topo, one_chip):
    _compile("poseidon2.permute (2^17, 16)", poseidon2.permute,
             _spec((1 << LOG_LDE, 16), one_chip))


def _phase_cases():
    # slow: over about a minute here (seconds measured in this sandbox,
    # PR 25) — TransferAir 83/280/131/47, StateUpdateAir commit 66 (149
    # beside five other test workers), quotient 191 and open 103
    # TokenAir (PR 29, beside another compile job, at 2^14 rows; 2^13
    # is no quicker: quotient 175): commit 81, quotient 258, open 144,
    # deep 40
    slow = {("StateUpdateAir", "commit"), ("StateUpdateAir", "quotient"),
            ("StateUpdateAir", "open"), ("TokenAir", "quotient"),
            ("TokenAir", "open")}
    for air_name in AIRS:
        for kernel in stark_prover._KERNELS:
            marks = [pytest.mark.slow] if (
                air_name == "TransferAir" or (air_name, kernel) in slow) \
                else []
            yield pytest.param(air_name, kernel, marks=marks,
                               id=f"{air_name}-{kernel}")


@pytest.mark.parametrize("air_name, kernel", _phase_cases())
def test_phase_program_one_chip(topo, one_chip, air_name, kernel):
    _phase(air_name, kernel, None, one_chip)


@pytest.mark.slow
def test_bn254_msm_device_at_groth16_wrap_size(topo, one_chip):
    """`_msm_device` has only ever run its numpy twin (the CPU branch of
    `_run_msm`); this is its first trip through the TPU compiler, at the
    Groth16 wrap circuit's size: 2897 R1CS variables, 254-bit scalars.
    Accepted, 324 MB temp — after 771 s of compile (this sandbox,
    PR 25), hence slow."""
    n, bits = 2897, 254
    limbs = _spec((n, bn254_msm.L), one_chip)
    _compile("bn254_msm._msm_device (2897 points, 254 bits)",
             bn254_msm._msm_device, limbs, limbs, limbs,
             _spec((n, bits), one_chip), bits=bits)


def _slice_mesh(topo, n):
    return Mesh(np.array(topo.devices[:n]), (mesh_lib.AXIS,))


def test_sharded_phase_has_a_collective(topo, one_chip):
    """The binding AIR's commit phase over all four described chips, as
    TpuBackend(mesh=make_mesh(4)) proves it: column-parallel LDE, then a
    transpose into row-parallel Merkle hashing — the compiler must put a
    collective between the two."""
    compiled = _phase("Poseidon2SpongeAir", "commit", _slice_mesh(topo, 4),
                      one_chip)
    ops = hlo_introspect.count_collectives(compiled.as_text())
    found = {k: v["count"] for k, v in ops.items()
             if k in hlo_introspect.COLLECTIVE_KINDS and v["count"]}
    print(f"collectives: {found}")
    assert found, "no collective in the sharded commit phase"
    lde_cols = compiled.output_shardings[0]
    assert isinstance(lde_cols, NamedSharding)
    assert lde_cols.spec == P(mesh_lib.AXIS, None)
    assert len(lde_cols.device_set) == 4


@pytest.mark.slow
@pytest.mark.parametrize("kernel", [
    pytest.param(k, marks=pytest.mark.xfail(
        strict=True, raises=jax.errors.JaxRuntimeError,
        reason="PR 25 finding: GSPMD cannot partition TransferAir's "
               "quotient over a two-chip slice — the compiler wants "
               "72.11 GB of a chip's 15.75 GB HBM (RESOURCE_EXHAUSTED). "
               "`chip_smoke.py --chips 4` stops here on real chips; "
               "before PR 25 a replicated shard_map re-compile hid it."))
    if k == "quotient" else k for k in stark_prover._KERNELS])
def test_transfer_phase_on_a_two_chip_slice(topo, one_chip, kernel):
    """split_mesh gives TransferAir a two-chip slice of a four-chip
    host (2 jobs over 4 devices -> 2+2)."""
    compiled = _phase("TransferAir", kernel, _slice_mesh(topo, 2), one_chip)
    if kernel == "commit":
        ops = hlo_introspect.count_collectives(compiled.as_text())
        assert any(ops[k]["count"]
                   for k in hlo_introspect.COLLECTIVE_KINDS)
