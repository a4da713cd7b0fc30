"""DEEP-FRI STARK prover: all heavy phases are batched device (TPU) work.

Pipeline per proof (SURVEY.md §7 step 5; replaces the CUDA STARK inside the
reference's SP1 backend, /root/reference/crates/prover/src/backend/sp1.rs):

  1. commit trace LDE               (NTT + Poseidon2 Merkle, device)
  2. alpha <- transcript; build + commit the constraint quotient (device)
  3. zeta <- transcript; open trace/quotient at zeta, zeta*g (device)
  4. gamma <- transcript; build the DEEP composition codeword (device)
  5. FRI fold/commit layers         (device)  + query openings (host)

The transcript (Fiat-Shamir) runs on host between device phases.  Each phase
is ONE jitted call (cached per AIR + shape): an eager op costs a host
dispatch and a compile of its own, and leaves XLA nothing to fuse, so
everything heavy lives inside the four phase programs below.

Proof-of-work grinding runs before query sampling (Challenger.grind);
parameter choices and the resulting soundness budget are documented in
docs/SOUNDNESS.md.
"""

from __future__ import annotations

import ctypes
import dataclasses
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor

import numpy as np
import jax
import jax.numpy as jnp

from ..ops import babybear as bb
from ..ops import ext
from ..ops import fri
from ..ops import merkle
from ..ops import ntt
from ..ops.challenger import Challenger
from ..utils import faults
from ..utils import tracing
from ..utils.metrics import record_kernel_build, record_phase_compile
from .air import Air, DeviceOps


@dataclasses.dataclass(frozen=True)
class StarkParams:
    log_blowup: int = 2
    num_queries: int = 40
    log_final_size: int = 5
    shift: int = bb.GENERATOR
    grinding_bits: int = 16


_domain_points = ntt.domain_points


def _canon(arr) -> np.ndarray:
    return bb.from_mont_host(np.asarray(arr))


def _periodic_coeffs(vals: np.ndarray) -> np.ndarray:
    return ntt.interpolate_host(vals)


def _stretch_coeffs(coeffs: np.ndarray, n: int, p_len: int) -> np.ndarray:
    """Spread period-p coefficients onto the size-n domain:
    f(x) = g(x^{n/p}) has coeff k*(n/p) = g_k."""
    out = np.zeros(n, dtype=np.uint32)
    out[:: n // p_len] = coeffs
    return out


_PHASE_CACHE: dict = {}
_PHASE_LOCK = threading.Lock()
_PHASE_BUILDS: dict = {}        # key -> Future of the build in flight


def _mesh_key(mesh):
    """Cache identity of a mesh: the exact device set, axis names AND
    layout shape.  A compiled (or pjit-sharded) program is bound to its
    devices, so two meshes are interchangeable only when all three
    match; None (no mesh) is its own key.  Keying on this — not object
    identity — means switching mesh <-> no-mesh, resizing the mesh, or
    proving on a different sub-slice can never be served a stale
    program, while re-building an identical Mesh object stays a hit."""
    if mesh is None:
        return None
    return (tuple(int(d.id) for d in mesh.devices.flat),
            tuple(mesh.axis_names), tuple(mesh.devices.shape))


def clear_phase_cache() -> None:
    """Drop every cached phase program (tests / simulated restarts;
    `fri.clear_layer_programs` drops the FRI layer programs)."""
    _PHASE_CACHE.clear()


class PhasePrograms:
    """The four compiled phase programs plus the input-placement plan.

    `put_cols` / `put_small` commit leaf inputs to the shardings the
    programs were compiled against (identity on the single-device
    path); intermediates already carry matched shardings because each
    program's out_shardings equal the next program's in_shardings."""

    __slots__ = ("commit", "quotient", "open", "deep", "plan")

    def __init__(self, programs, plan):
        self.commit, self.quotient, self.open, self.deep = programs
        self.plan = plan

    def put_cols(self, x):
        if self.plan is None:
            return x
        return jax.device_put(x, self.plan.cols)

    def put_small(self, x):
        if self.plan is None:
            return x
        return jax.device_put(x, self.plan.repl)

    def put_named(self, name, x):
        """Commit a checkpoint-restored intermediate (numpy) to the
        sharding the consuming program was compiled against; identity
        placement on the single-device path."""
        x = jnp.asarray(x)
        if self.plan is None:
            return x
        return jax.device_put(x, self.plan.named[name])


def _phases(air: Air, log_n: int, lb: int, shift: int,
            mesh=None) -> PhasePrograms:
    """Phase programs, cached by *structural* AIR identity.

    Keyed on (type, width, degree, pub-count) rather than object identity so
    `prove(MixerAir(16), ...)` in a loop reuses compiled programs.  AIRs with
    extra structure-affecting parameters must reflect them in `cache_key()`.
    The mesh participates in the key via `_mesh_key` (device set + layout).

    Programs are AOT-compiled (lower + compile against ShapeDtypeStructs)
    on BOTH the single-device and mesh paths, so the XLA cost model is
    captured for roofline accounting either way; `record_kernel_build`
    therefore times trace + staging + backend compile for a cache miss,
    labelled with the mesh shape.
    """
    return _queue_phases(air, log_n, lb, shift, mesh)()


def _queue_phases(air: Air, log_n: int, lb: int, shift: int, mesh=None):
    """`_phases` up to the point where a miss has its four compiles on
    the pool; returns the rest of it, a call that waits for them and
    caches the set.  `compile_ahead` runs this for every AIR of a batch
    before it waits for any, so the pool has the builds in the order
    the AIRs were asked for."""
    key = (air.cache_key(), log_n, lb, shift, _mesh_key(mesh))
    air_name = type(air).__name__
    # one build per key: a second caller (the prove that compile_ahead
    # ran in front of) waits for the build in flight, and gets its
    # error if it fails
    with _PHASE_LOCK:
        cached = _PHASE_CACHE.get(key)
        if cached is not None:
            return lambda: cached
        pending = _PHASE_BUILDS.get(key)
        if pending is None:
            mine = _PHASE_BUILDS[key] = Future()
    # a miss is spanned (a hit is a dictionary look-up): the build
    # itself, or the wait for the one compile_ahead has in flight
    if pending is not None:
        def wait():
            with tracing.span("prove.phase_build", air=air_name):
                return pending.result()
        return wait

    def failed(exc):
        with _PHASE_LOCK:
            del _PHASE_BUILDS[key]
        mine.set_exception(exc)

    start, t0 = time.time(), time.perf_counter()
    try:
        bodies, plan = _build_phases(air, log_n, lb, shift, mesh)
        compiles = _aot_phases(air, log_n, lb, shift, bodies, plan, mesh)
    except BaseException as exc:
        failed(exc)
        raise

    def finish():
        try:
            built = PhasePrograms(compiles(), plan)
        except BaseException as exc:
            failed(exc)
            raise
        # retrace telemetry: every miss here is a fresh set of programs
        from ..parallel import mesh as mesh_lib

        seconds = time.perf_counter() - t0
        tracing.record_span("prove.phase_build", start, seconds,
                            air=air_name)
        record_kernel_build(air_name, seconds,
                            mesh=mesh_lib.shape_label(mesh))
        with _PHASE_LOCK:
            _PHASE_CACHE[key] = built
            del _PHASE_BUILDS[key]
        mine.set_result(built)
        return built
    return finish


def compile_ahead(asks, params: "StarkParams", mesh=None) -> None:
    """Start building the phase programs of every `(air, n)` of `asks`
    (`n` the rows of the trace) in the background and return at once.
    A cold prover spends most of its first proof compiling, one AIR
    after the other; XLA compiles outside the GIL, so the AIRs a batch
    will need next can build while the first one proves.  One thread
    traces each AIR's programs and puts their compiles on the shared
    pool in the order of `asks`, then waits for them: ask first for the
    AIR whose job runs first.  The later prove() finds the programs in
    the cache, or waits for the build in flight — a failed build fails
    that prove with the compiler's own error."""
    def run():
        waits = []
        for air, n in asks:
            try:
                waits.append(_queue_phases(
                    air, n.bit_length() - 1, params.log_blowup,
                    params.shift % bb.P, mesh))
            except BaseException:   # noqa: BLE001 — the waiting prove
                pass                # builds again and has the error
        for wait in waits:
            try:
                wait()
            except BaseException:   # noqa: BLE001 — the waiting prove has it
                pass

    threading.Thread(target=run, name="compile-ahead", daemon=True).start()


def warm_fri_programs(n: int, params: "StarkParams") -> None:
    """Build, in the background, the per-layer FRI programs of an
    `n`-row trace's codeword (`fri.layer_programs`: pair leaves, Merkle
    tree, fold at each layer size) that the process does not have yet.
    Left to the first proof of a cold process they compile one after
    the other inside its FRI loop, thirteen layer sizes deep for a 2^17
    codeword, with every phase program built and waiting.  A process
    that has them all (a warm one, after `hydrate_phase_cache`) starts
    no thread."""
    log_size = (n << params.log_blowup).bit_length() - 1
    missing = [log_k for log_k in range(log_size, params.log_final_size, -1)
               if log_k not in fri._LAYER_PROGRAMS]
    if not missing:
        return

    def run():
        for log_k in missing:
            try:
                fri.layer_programs(log_k)
            except Exception:   # noqa: BLE001 — the FRI loop that needs
                pass            # this size builds again and has the error

    threading.Thread(target=run, name="fri-warm", daemon=True).start()


_KERNELS = ("commit", "quotient", "open", "deep")
_BUILD_ORDER = ("quotient", "open", "commit", "deep")   # costliest first
# Every phase-program build of the process runs on these few threads.
# Few on purpose: one XLA:TPU compile of a wide AIR's quotient peaks near
# 10 GiB of host memory, and what a compile thread's allocator arena
# grows to it keeps — twelve builds on twelve threads ran a 40 GiB host
# out of memory (PR 25, second chip run).
_COMPILE_POOL = ThreadPoolExecutor(max_workers=4,
                                   thread_name_prefix="phase-build")


def _record_phase_cost(air_name: str, kernel: str, compiled,
                       devices: int = 1) -> None:
    # roofline hooks are telemetry: a failing cost_analysis (None where
    # a backend has no cost model) can never fail a prove
    try:
        from ..perf import roofline

        roofline.record_cost(air_name, kernel, compiled.cost_analysis(),
                             devices=devices)
    except Exception:
        pass
    # collective accounting rides the same compiled handle: HLO text +
    # memory_analysis, per (air, kernel, devices) — never-raise
    try:
        from ..perf import hlo_introspect

        hlo_introspect.record(air_name, kernel, compiled, devices=devices)
    except Exception:
        pass


def _record_phase_wall(air_name: str, kernel: str, seconds: float) -> None:
    try:
        from ..perf import roofline

        roofline.record_wall(air_name, kernel, seconds)
    except Exception:
        pass
    try:
        from ..perf import hlo_introspect

        hlo_introspect.record_collective_share(air_name, kernel, seconds)
    except Exception:
        pass


def _nbytes(arrays) -> int:
    """Bytes of the host arrays in a (nested) tuple or list: attribute
    reads, no pass over the data."""
    return sum(a.nbytes for a in jax.tree_util.tree_leaves(arrays))


def _ckpt_copy(phase: str, arrays):
    """The device-to-host copy of a phase's outputs for its checkpoint
    (and for the query phase's host mirrors), under its leaf span."""
    with tracing.span("prove.ckpt_copy", stage="ckpt", phase=phase) as sp:
        got = jax.device_get(arrays)
        tracing.set_attrs(sp, d2h_bytes=_nbytes(got))
    return got


def lde_cols_from_rows(lde_rows: np.ndarray) -> np.ndarray:
    """The (w, N) column layout from the stored (N, w) rows: the exact
    inverse of `phase_commit`'s transpose."""
    return np.ascontiguousarray(lde_rows.T)


def q_lde_from_rows(q_rows: np.ndarray) -> np.ndarray:
    """The (B, 4, N) quotient LDE from the stored (N, B * 4) rows: the
    exact inverse of `phase_quotient`'s moveaxis + reshape."""
    N = q_rows.shape[0]
    return np.ascontiguousarray(np.moveaxis(q_rows.reshape(N, -1, 4), 0, -1))


def _ckpt_rebuild(phase: str, rebuild, stored: np.ndarray) -> np.ndarray:
    """The layout a resumed phase's consumer needs, rearranged on the
    host from the one layout its envelope holds (u32 data moved, no
    arithmetic: a resumed proof stays byte-identical), under its leaf
    span (no `stage=`: it runs inside the consuming phase's stage).
    Only a resume pays it."""
    with tracing.span("ckpt.rebuild", phase=phase) as sp:
        out = rebuild(stored)
        tracing.set_attrs(sp, bytes=out.nbytes)
    return out


def _record_prove_throughput(cells: int, seconds: float) -> None:
    try:
        if seconds > 0:
            from ..utils.metrics import record_prover_throughput

            record_prover_throughput(cells / seconds)
    except Exception:
        pass


def _jit_programs(bodies, plan):
    """Wrap the phase bodies as (lazily) jitted programs.

    Single-device (`plan is None`): plain jit, exactly the legacy path.
    Mesh: pjit-style jit with explicit in/out shardings matched between
    pipeline stages and the big consumed buffers donated (lde_cols into
    quotient, chunks into open, q_lde into deep — each is dead after
    its consuming phase; cols and lde_rows are reused by later stages
    and the host query openings, so they are never donated)."""
    if plan is None:
        return tuple(jax.jit(b) for b in bodies)
    return tuple(
        jax.jit(body,
                in_shardings=plan.in_shardings[kernel],
                out_shardings=plan.out_shardings[kernel],
                donate_argnums=plan.donate[kernel])
        for kernel, body in zip(_KERNELS, bodies))


def _exec_cache_parts(air: Air, log_n: int, lb: int, shift: int,
                      mesh, kernel: str) -> dict:
    """On-disk executable-cache identity of one phase program.  Carries
    everything hydrate_phase_cache needs to rebuild the in-process
    cache entry without the AIR object (width/nb for the mesh plan,
    air_name for telemetry) on top of the _PHASE_CACHE key parts."""
    n = 1 << log_n
    return {"kind": "phase", "air": air.cache_key(),
            "air_name": type(air).__name__, "width": air.width,
            "nb": len(air.boundaries([0] * air.num_pub_inputs, n)),
            "log_n": log_n, "log_blowup": lb, "shift": shift,
            "mesh": _mesh_key(mesh), "kernel": kernel}


def _trim_host_heap() -> None:
    """Hand freed heap back to the OS.  One XLA:TPU compile of a wide
    AIR's quotient peaks near 10 GiB of host memory, and glibc keeps
    what each compile thread's arena grew to: after a dozen builds a
    40 GiB host was out of memory with a single compile running
    (PR 25, chip runs 2 and 3)."""
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except (OSError, AttributeError):
        pass                        # not glibc: nothing to trim


def _phase_arg_specs(air: Air, log_n: int, lb: int) -> dict:
    """The (statically known) argument shapes of the four phase
    programs, by kernel name."""
    n = 1 << log_n
    w = air.width
    B = 1 << lb
    N = n << lb
    nb = len(air.boundaries([0] * air.num_pub_inputs, n))
    u32 = jnp.uint32
    S = jax.ShapeDtypeStruct
    e = S((4,), u32)
    return {
        "commit": (S((w, n), u32),),
        "quotient": (S((w, N), u32), e, S((nb,), u32)),
        "open": (S((w, n), u32), S((B, n, 4), u32), e, e),
        "deep": (S((N, w), u32), S((B, 4, N), u32), S((w, 4), u32),
                 S((w, 4), u32), S((B, 4), u32), e, e, e),
    }


def _aot_phases(air: Air, log_n: int, lb: int, shift: int, bodies, plan,
                mesh):
    """AOT-compile the four phase programs against their (statically
    known) argument shapes and register each executable's XLA cost
    analysis with the roofline registry — mesh and single-device paths
    alike, so sharded programs get the same roofline cost records.
    Puts the four compiles on the pool and returns a call that waits
    for them and gives the executables in `_KERNELS` order.

    Each kernel asks the on-disk executable cache first
    (utils/exec_cache): a hit hydrates the serialized executable in
    milliseconds instead of recompiling, and a fresh compile is
    serialized back so the NEXT process restart hydrates.

    A compile error propagates: a phase the compiler refuses, or a
    sharding it cannot partition, fails the prove with the compiler's
    own message.  There is no replicated re-compile and no lazy-jit
    substitute."""
    specs = _phase_arg_specs(air, log_n, lb)
    from ..parallel import mesh as mesh_lib
    from ..utils import exec_cache

    air_name = type(air).__name__
    devices = 1 if mesh is None else int(mesh.devices.size)
    mesh_label = mesh_lib.shape_label(mesh)

    def build(kernel, fn):
        parts = _exec_cache_parts(air, log_n, lb, shift, mesh, kernel)
        t_c = time.perf_counter()
        compiled = exec_cache.load(parts)
        source = "deserialized"
        if compiled is None:
            source = "compiled"
            compiled = fn.lower(*specs[kernel]).compile()
            exec_cache.store(parts, compiled)
        # per-program build wall: the cold-start baseline each warmup
        # pays per phase program (bench measure_config4 reports these);
        # source tells hydration apart from a fresh compile.  The four
        # walls overlap, so their sum exceeds the AIR's build wall
        # (record_kernel_build has that).
        record_phase_compile(air_name, kernel,
                             time.perf_counter() - t_c, mesh=mesh_label,
                             source=source)
        _record_phase_cost(air_name, kernel, compiled, devices)
        _trim_host_heap()
        return compiled

    # the four programs are independent, and XLA compiles outside the
    # GIL: built side by side, a cold AIR costs about its slowest phase
    # instead of the sum.  Costliest first (TransferAir's quotient is
    # half of its sum), on the one shared pool.
    fns = dict(zip(_KERNELS, _jit_programs(bodies, plan)))
    futures = {kernel: _COMPILE_POOL.submit(build, kernel, fns[kernel])
               for kernel in _BUILD_ORDER}
    return lambda: tuple(futures[kernel].result() for kernel in _KERNELS)


def hydrate_phase_cache(mesh=None) -> int:
    """Pre-warm the in-process program tables from the on-disk
    executable cache: every complete four-kernel phase group recorded
    for this environment and mesh layout is deserialized and installed
    into _PHASE_CACHE, and (single-device call) every complete set of
    FRI layer programs into `fri`'s table, so the first prove of those
    shapes runs at steady-state wall and lowers nothing.  Never
    compiles — an empty or foreign cache is a no-op — and never raises.
    Returns the number of phase-program sets hydrated (the ProverClient
    warm flag flips once this returns)."""
    from ..utils import exec_cache

    if not exec_cache.enabled():
        return 0
    try:
        entries = exec_cache.scan()
    except Exception:
        return 0
    mesh_key = _mesh_key(mesh)
    phase_groups: dict = {}     # _PHASE_CACHE key -> kernel -> parts
    layer_groups: dict = {}     # log_k -> kernel -> parts
    for parts in entries:
        try:
            if parts.get("mesh") != mesh_key:
                continue
            if parts.get("kind") == "phase":
                gkey = (parts["air"], parts["log_n"], parts["log_blowup"],
                        parts["shift"], parts["mesh"])
                phase_groups.setdefault(gkey, {})[parts["kernel"]] = parts
            elif parts.get("kind") == "fri":
                layer_groups.setdefault(
                    parts["log_n"], {})[parts["kernel"]] = parts
        except Exception:
            continue
    from ..parallel import mesh as mesh_lib

    mesh_label = mesh_lib.shape_label(mesh)

    def load_group(group):
        """The group's executables in order, or None at the first one
        that does not load.  One after the other: four at once on the
        build pool took longer on the chip (PERF.md, PR 30)."""
        programs = []
        for parts in group:
            t_c = time.perf_counter()
            compiled = exec_cache.load(parts)
            if compiled is None:
                return None
            record_phase_compile(parts["air_name"], parts["kernel"],
                                 time.perf_counter() - t_c,
                                 mesh=mesh_label, source="deserialized")
            programs.append(compiled)
        return tuple(programs)

    hydrated = 0
    for gkey, kernels in phase_groups.items():
        if gkey in _PHASE_CACHE or set(kernels) != set(_KERNELS):
            continue
        try:
            programs = load_group([kernels[kernel] for kernel in _KERNELS])
            if programs is None:
                continue
            p0 = kernels["commit"]
            plan = None if mesh is None else _MeshPlan(
                mesh, p0["log_n"], p0["log_blowup"], p0["width"], p0["nb"])
            _PHASE_CACHE[gkey] = PhasePrograms(programs, plan)
            hydrated += 1
        except Exception:
            continue
    for log_k, kernels in layer_groups.items():
        if log_k in fri._LAYER_PROGRAMS \
                or set(kernels) != set(fri.LAYER_KERNELS):
            continue
        try:
            programs = load_group(
                [kernels[kernel] for kernel in fri.LAYER_KERNELS])
            if programs is not None:
                fri.install_layer_programs(log_k, programs)
        except Exception:
            continue
    return hydrated


class _MeshPlan:
    """Per-kernel pjit shardings + donation, and leaf-input placements.

    in_shardings/out_shardings are keyed by kernel name and MATCHED
    between pipeline stages: commit's lde_cols out == quotient's in,
    commit's lde_rows out == deep's in, quotient's chunks out == open's
    in, quotient's q_lde out == deep's in — so no phase boundary ever
    forces a resharding collective."""

    __slots__ = ("in_shardings", "out_shardings", "donate", "cols",
                 "repl", "devices", "named")

    def __init__(self, mesh, log_n: int, lb: int, w: int, nb: int):
        from ..parallel import mesh as mesh_lib

        A = mesh_lib.AXIS
        n = 1 << log_n
        B = 1 << lb
        N = n << lb

        def sh(shape, *spec):
            return mesh_lib.sharding_for(mesh, shape, spec)

        self.devices = int(mesh.devices.size)
        self.cols = sh((w, n), A, None)
        self.repl = mesh_lib.replicated(mesh)
        e = self.repl                       # small (4,) transcript values
        cols = self.cols                    # (w, n) trace columns
        lde_cols = sh((w, N), A, None)      # column-parallel NTT layout
        lde_rows = sh((N, w), A, None)      # row-parallel Merkle/DEEP
        chunks = sh((B, n, 4), A, None, None)
        q_lde = sh((B, 4, N), None, None, A)
        q_rows = sh((N, B * 4), A, None)
        # Merkle levels: (N >> k, 8) rows; sharding_for replicates the
        # small tail levels automatically (dim < ndev)
        levels_t = tuple(sh((N >> k, 8), A, None)
                         for k in range((N.bit_length() - 1) + 1))
        self.in_shardings = {
            "commit": (cols,),
            "quotient": (lde_cols, e, e),
            "open": (cols, chunks, e, e),
            "deep": (lde_rows, q_lde, e, e, e, e, e, e),
        }
        self.out_shardings = {
            "commit": (lde_cols, lde_rows, levels_t),
            "quotient": (chunks, q_lde, q_rows, levels_t),
            "open": (e, e, e),
            "deep": sh((N, 4), A, None),
        }
        # donate only buffers dead after their consuming phase: cols is
        # reused by open, lde_rows/q_rows by the host query openings
        self.donate = {"commit": (), "quotient": (0,), "open": (1,),
                       "deep": (1,)}
        # shardings by intermediate name, for re-placing checkpoint
        # payloads on resume (PhasePrograms.put_named)
        self.named = {"lde_cols": lde_cols, "lde_rows": lde_rows,
                      "chunks": chunks, "q_lde": q_lde}


def _build_phases(air: Air, log_n: int, lb: int, shift: int, mesh=None):
    """Build the four phase BODIES for a given AIR and trace shape, plus
    the mesh partition plan (None on the single-device path); returns
    (bodies, plan).

    Boundary structure (rows/cols) must not depend on public-input *values*
    (values are traced inputs; structure is baked into the program).

    With `mesh`, the bodies stay annotation-free: partitioning is
    expressed ONCE at each pjit boundary via the plan's matched
    in/out shardings (trace columns and LDE rows over the mesh's
    "shard" axis — the same layout as the fused demo core,
    parallel/core.py — small commitments replicated) and GSPMD
    propagates through the program interior, inserting the ICI
    collectives.  This is the PRODUCTION prover's multi-chip path
    (SURVEY.md §5 "shard the STARK trace across the slice"); the host
    transcript and query openings are unchanged and proofs are
    bit-identical to single-device runs (all arithmetic is exact u32).
    """
    n = 1 << log_n
    w = air.width
    B = 1 << lb
    N = n << lb
    log_N = log_n + lb
    g_n = bb.root_of_unity(log_n)
    K = air.num_constraints
    bounds_struct = [(r % n, c) for (r, c, _) in
                     air.boundaries([0] * air.num_pub_inputs, n)]  # structure only
    nb = len(bounds_struct)

    # host-precomputed divisor evaluation tables (canonical -> Montgomery)
    pts = _domain_points(log_N, shift).astype(np.int64)
    x_minus_glast = ((pts - pow(g_n, n - 1, bb.P)) % bb.P).astype(np.uint32)
    s_n = pow(shift, n, bb.P)
    uB = pow(bb.root_of_unity(log_N), n, bb.P)
    xn_minus_1 = np.array(
        [(s_n * pow(uB, i, bb.P) - 1) % bb.P for i in range(B)],
        dtype=np.uint32,
    )
    bound_divs = [
        ((pts - pow(g_n, r, bb.P)) % bb.P).astype(np.uint32)
        for (r, _) in bounds_struct
    ]
    # periodic (preprocessed) columns: LDE baked in as program constants
    periodic_np = []
    for vals in air.periodic_columns(n):
        vals = np.asarray(vals, dtype=np.uint32) % bb.P
        p_len = len(vals)
        if n % p_len:
            raise ValueError("periodic column length must divide n")
        coeffs = bb.to_mont_host(_periodic_coeffs(vals))
        evals = np.asarray(ntt.coset_evals_from_coeffs(
            jnp.asarray(_stretch_coeffs(coeffs, n, p_len)), N, shift=shift))
        periodic_np.append(evals)
    if len(periodic_np) != air.num_periodic:
        raise ValueError("periodic_columns does not match num_periodic")
    # divisor inverses depend only on structure: invert ONCE at build
    # time, not inside the per-proof jitted phase — and on the host:
    # the un-jitted device batch inversion this replaces ran ~600
    # one-op XLA programs, each compiled for the chip first (PR 25: the
    # first chip run spent 18 minutes here and built nothing)
    inv_stack_np = bb.to_mont_host(bb.batch_inv_host(
        np.concatenate([xn_minus_1, x_minus_glast] + bound_divs)))
    pts_m_np = bb.to_mont_host(_domain_points(log_N, shift))

    def phase_commit(cols):
        lde_cols = ntt.coset_lde(cols, lb, shift=shift)
        lde_rows = lde_cols.T               # transpose: all-to-all
        levels = merkle.build_levels_with(lde_rows)
        return lde_cols, lde_rows, levels

    def phase_quotient(lde_cols, alpha, bound_vals):
        dev = DeviceOps()
        rolled = jnp.roll(lde_cols, -B, axis=1)
        local = [lde_cols[j] for j in range(w)]
        nxt = [rolled[j] for j in range(w)]
        periodic = [jnp.asarray(p) for p in periodic_np]
        cons = jnp.stack(air.constraints(local, nxt, periodic, dev))  # (K, N)
        apow = ext.ext_powers(alpha, K + nb)                      # (K+nb, 4)
        # random-linear-combination of constraint columns: an MXU matmul
        # (N, K) @ (K, 4) instead of materializing a (K, N, 4) product
        acc = bb.mod_matmul(cons.T, apow[:K])                      # (N, 4)
        inv_stack = jnp.asarray(inv_stack_np)
        inv_xn1 = jnp.tile(inv_stack[:B], N // B)
        xm = jnp.asarray(bb.to_mont_host(x_minus_glast))
        q_acc = ext.scalar_mul(acc, bb.mont_mul(xm, inv_xn1))
        base_off = B + N
        for j, (r, c) in enumerate(bounds_struct):
            diff = bb.sub(lde_cols[c], bound_vals[j])
            inv_x = inv_stack[base_off + j * N: base_off + (j + 1) * N]
            q_acc = ext.add(q_acc, bb.mont_mul(
                bb.mont_mul(diff, inv_x)[:, None], apow[K + j][None, :]
            ))
        qc = ntt.coset_intt(q_acc.T, shift=shift).T                # (N, 4)
        chunks = jnp.stack([qc[i * n:(i + 1) * n] for i in range(B)])
        q_lde = ntt.coset_evals_from_coeffs(
            jnp.moveaxis(chunks, -1, 1), N, shift=shift
        )                                                          # (B, 4, N)
        q_rows = jnp.moveaxis(q_lde, -1, 0).reshape(N, B * 4)
        levels = merkle.build_levels_with(q_rows)
        return chunks, q_lde, q_rows, levels

    def phase_open(cols, chunks, zeta, zeta_g):
        tcoeffs = ntt.intt(cols)
        t_z = ext.eval_base_poly_at_ext(tcoeffs, zeta)
        t_zg = ext.eval_base_poly_at_ext(tcoeffs, zeta_g)
        q_z = ext.eval_ext_poly_at_ext(chunks, zeta)
        return t_z, t_zg, q_z

    def phase_deep(lde_rows, q_lde, t_z, t_zg, q_z, zeta, zeta_g, gamma):
        # sum_w gamma^w*(T_w(x) - T_w(z)) = (lde_rows @ gamma-powers) minus
        # a per-z constant: the contraction over columns runs as a base-
        # field MXU matmul (bb.mod_matmul) and 1/(x-z) uses the scan-free
        # minimal-polynomial inverse — same restructure as the fused
        # prove step (parallel/core.py), avoiding (N, w, 4) ext tensors.
        pts_m = jnp.asarray(pts_m_np)
        inv_xz = ext.inv_x_minus_zeta(pts_m, zeta)
        inv_xzg = ext.inv_x_minus_zeta(pts_m, zeta_g)
        gpow = ext.ext_powers(gamma, 2 * w + B)
        s1 = ext.sub(bb.mod_matmul(lde_rows, gpow[:w]),
                     bb.sum_mod(ext.mul(t_z, gpow[:w]), axis=0)[None])
        s2 = ext.sub(bb.mod_matmul(lde_rows, gpow[w:2 * w]),
                     bb.sum_mod(ext.mul(t_zg, gpow[w:2 * w]), axis=0)[None])
        q_ext = jnp.moveaxis(q_lde, 1, -1)                         # (B, N, 4)
        d3 = ext.sub(q_ext, q_z[:, None])
        s3 = bb.sum_mod(ext.mul(d3, gpow[2 * w:, None]), axis=0)
        return ext.add(ext.mul(ext.add(s1, s3), inv_xz),
                       ext.mul(s2, inv_xzg))

    bodies = (phase_commit, phase_quotient, phase_open, phase_deep)
    plan = None if mesh is None else _MeshPlan(mesh, log_n, lb, w, nb)
    return bodies, plan


def prove(air: Air, trace: np.ndarray, pub_inputs: list[int],
          params: StarkParams = StarkParams(), mesh=None) -> dict:
    """Prove one AIR.  `mesh` (optional jax.sharding.Mesh) runs every
    device phase sharded across the mesh — the production multi-chip
    path; proofs are bit-identical to single-device runs."""
    n, w = trace.shape
    if w != air.width:
        raise ValueError(f"trace width {w} != AIR width {air.width}")
    log_n = n.bit_length() - 1
    if 1 << log_n != n:
        raise ValueError("trace length must be a power of two")
    lb = params.log_blowup
    B = 1 << lb
    if air.max_degree > B:
        raise ValueError("constraint degree exceeds blowup")
    if len(pub_inputs) != air.num_pub_inputs:
        raise ValueError("public input count mismatch")
    from ..parallel import mesh as mesh_lib
    from ..prover import runtime_errors as rt

    air_name = type(air).__name__
    # pre-prove memory gate: if the AOT roofline bytes for this AIR do
    # not fit the live free device memory, shrink the layout BEFORE
    # the OOM instead of after (docs/PROVER_RESILIENCE.md)
    mesh = rt.memory_gate(air_name, mesh)
    # Degraded-mesh fallback ladder: a phase that dies with a transient
    # runtime class (oom / device_lost) is retried on the next rung
    # down.  Completed phases carry across rungs through the on-disk
    # checkpoints (proofs are bit-identical on any layout), so with
    # checkpointing on only the failed phase is recomputed; with it off
    # the prove restarts from scratch on the smaller layout — slower,
    # still correct, and still zero quarantine-budget burn.
    ladder = None
    while True:
        try:
            return _prove_attempt(air, trace, pub_inputs, params, mesh)
        except rt.TransientPhaseError as err:
            if ladder is None:
                ladder = rt.degradation_ladder(mesh)
            if not ladder:
                raise err.cause from err
            nxt = ladder.pop(0)
            rt.note_transient_retry(err.kind, err.phase)
            rt.note_degradation(mesh_lib.shape_label(mesh),
                                mesh_lib.shape_label(nxt))
            mesh = nxt


def _prove_attempt(air: Air, trace: np.ndarray, pub_inputs: list[int],
                   params: StarkParams, mesh=None) -> dict:
    """One pass over the phase pipeline at a fixed mesh layout.

    Every phase consults the checkpoint store first (a no-op outside a
    batch context or with ETHREX_PROOF_CKPT_OFF=1): a completed phase
    loads its host-visible artifacts, numpy intermediates and the
    transcript sponge snapshot instead of re-running the device work,
    so a restarted prover — or a ladder retry on a smaller mesh —
    recomputes at most the one phase that was in flight.  Device work
    runs under runtime_errors.guard_phase (fault legs + classification), and
    each live phase persists its envelope before the `backend.phase`
    drop leg fires — the kill-at-every-boundary drill's kill point."""
    from ..parallel import mesh as mesh_lib
    from ..prover import checkpoint as ckpt_mod
    from ..prover import runtime_errors as rt

    n, w = trace.shape
    log_n = n.bit_length() - 1
    lb = params.log_blowup
    B = 1 << lb
    N = n << lb
    shift = params.shift % bb.P
    g_n = bb.root_of_unity(log_n)
    progs = _phases(air, log_n, lb, shift, mesh)
    p_commit, p_quotient, p_open, p_deep = (
        progs.commit, progs.quotient, progs.open, progs.deep)
    air_name = type(air).__name__
    mesh_label = mesh_lib.shape_label(mesh)
    t_prove0 = time.perf_counter()

    params_key = (lb, params.num_queries, params.log_final_size, shift,
                  params.grinding_bits)
    store = ckpt_mod.phase_store(air.cache_key(), log_n, params_key,
                                 mesh_label)

    # finished-proof short-circuit: the whole job already completed
    # before the restart; nothing to recompute
    if store is not None:
        done = store.load("proof")
        if done is not None:
            rt.note_resume("proof")
            with tracing.span("prove.resumed", air=air_name,
                              resumed=True, phase="proof"):
                pass
            return done

    # contiguous completed-phase prefix (commit -> quotient -> open ->
    # fri); a later phase without its predecessors is unusable because
    # the query openings need the earlier Merkle levels
    resume: dict = {}
    if store is not None:
        for phase in ("commit", "quotient", "open", "fri"):
            payload = store.load(phase)
            if payload is None:
                break
            resume[phase] = payload

    ch = Challenger()
    ch.absorb_elems([n, w, B])
    ch.absorb_elems([v % bb.P for v in pub_inputs])

    def get_cols():
        # leaf input placement: recomputed from the host trace on
        # demand (cheap transform, not a checkpointed phase)
        return progs.put_cols(
            bb.to_mont(jnp.asarray(trace.T.astype(np.uint32))))     # (w, n)

    # host numpy mirrors of the cross-phase intermediates: filled from
    # checkpoint payloads (resumed phases) or at store time (live
    # phases); the query phase reads these instead of device_get when
    # checkpointing is on.  Each large array is held (and stored) once,
    # in the row layout the query phase gathers from; a resumed phase's
    # consumer gets the column layout back through `_ckpt_rebuild`
    host: dict = {}

    # Stage spans are block_until_ready()-bounded so JAX async dispatch
    # cannot attribute device time to the wrong stage.  The LDE and the
    # Merkle tree are fused into one XLA program (p_commit), so the
    # merkle_commit span measures the residual wait after the LDE
    # outputs are ready — near zero when the fusion wins.
    # ---- 1. trace commitment --------------------------------------------
    cols = lde_cols = lde_rows = levels_t = None
    commit_pay = resume.get("commit")
    if commit_pay is not None:
        with tracing.span("prove.trace_lde", stage="trace_lde", width=w,
                          n=n, resumed=True):
            rt.note_resume("commit")
            ch.restore(commit_pay["ch"])
            host.update(lde_rows=commit_pay["lde_rows"],
                        levels_t=commit_pay["levels_t"])
        trace_root = host["levels_t"][-1][0]
    else:
        with tracing.span("prove.trace_lde", stage="trace_lde",
                          width=w, n=n):
            # leaf inputs are committed to the shardings the programs
            # were compiled against (no-op on the single-device path);
            # every intermediate already flows stage-to-stage with
            # matched out_shardings == in_shardings
            cols = get_cols()
            t_k = time.perf_counter()
            lde_cols, lde_rows, levels_t = rt.guard_phase(
                "commit", air_name, lambda: p_commit(cols))
            jax.block_until_ready((lde_cols, lde_rows))
        with tracing.span("prove.merkle_commit", stage="merkle_commit"):
            jax.block_until_ready(levels_t)
            # the commit kernel's roofline wall spans both bounded
            # waits (the LDE and Merkle tree are ONE fused executable)
            _record_phase_wall(air_name, "commit",
                               time.perf_counter() - t_k)
            trace_root = levels_t[-1][0]
            rt.screen_outputs("commit", {
                "trace_root": [int(x) for x in _canon(trace_root)]})
            ch.absorb_digest(trace_root)
        if store is not None:
            lr_np, lt_np = _ckpt_copy(
                "commit", (lde_rows, tuple(levels_t)))
            host.update(lde_rows=lr_np, levels_t=list(lt_np))
            store.store("commit", {"lde_rows": lr_np,
                                   "levels_t": list(lt_np),
                                   "ch": ch.state()},
                        mesh_label=mesh_label)
        faults.inject("backend.phase", None, kinds=("drop",))
    alpha = ch.sample_ext()

    # ---- 2. constraint quotient -----------------------------------------
    chunks = q_lde = q_rows = levels_q = None
    quot_pay = resume.get("quotient")
    if quot_pay is not None:
        with tracing.span("prove.quotient", stage="quotient",
                          resumed=True):
            rt.note_resume("quotient")
            ch.restore(quot_pay["ch"])
            host.update(chunks=quot_pay["chunks"],
                        q_rows=quot_pay["q_rows"],
                        levels_q=quot_pay["levels_q"])
        q_root = host["levels_q"][-1][0]
    else:
        with tracing.span("prove.quotient", stage="quotient"):
            bounds = air.boundaries(pub_inputs, n)
            bound_vals = progs.put_small(bb.to_mont(jnp.asarray(
                np.array([v % bb.P for (_, _, v) in bounds],
                         dtype=np.uint32))))
            if lde_cols is None:        # commit was resumed: re-place
                lde_cols = progs.put_named("lde_cols", _ckpt_rebuild(
                    "commit", lde_cols_from_rows, host["lde_rows"]))
            alpha_dev = progs.put_small(ext.to_device(alpha))
            t_k = time.perf_counter()
            chunks, q_lde, q_rows, levels_q = rt.guard_phase(
                "quotient", air_name,
                lambda: p_quotient(lde_cols, alpha_dev, bound_vals))
            jax.block_until_ready(levels_q)
            _record_phase_wall(air_name, "quotient",
                               time.perf_counter() - t_k)
            q_root = levels_q[-1][0]
            rt.screen_outputs("quotient", {
                "quotient_root": [int(x) for x in _canon(q_root)]})
            ch.absorb_digest(q_root)
        if store is not None:
            ck_np, qr_np, lq_np = _ckpt_copy(
                "quotient", (chunks, q_rows, tuple(levels_q)))
            host.update(chunks=ck_np, q_rows=qr_np, levels_q=list(lq_np))
            store.store("quotient", {"chunks": ck_np, "q_rows": qr_np,
                                     "levels_q": list(lq_np),
                                     "ch": ch.state()},
                        mesh_label=mesh_label)
        faults.inject("backend.phase", None, kinds=("drop",))
    zeta = ch.sample_ext()

    # ---- 3. out-of-domain openings --------------------------------------
    t_z_dev = t_zg_dev = q_z_dev = None
    zeta_g = ext.h_mul(zeta, ext.h_from_base(g_n))
    open_pay = resume.get("open")
    if open_pay is not None:
        with tracing.span("prove.openings", stage="openings",
                          resumed=True):
            rt.note_resume("open")
            ch.restore(open_pay["ch"])
            t_at_z = [tuple(v) for v in open_pay["t_at_z"]]
            t_at_zg = [tuple(v) for v in open_pay["t_at_zg"]]
            q_at_z = [tuple(v) for v in open_pay["q_at_z"]]
            host.update(t_z=open_pay["t_z"], t_zg=open_pay["t_zg"],
                        q_z=open_pay["q_z"])
    else:
        with tracing.span("prove.openings", stage="openings"):
            if cols is None:
                cols = get_cols()
            if chunks is None:          # quotient was resumed
                chunks = progs.put_named("chunks", host["chunks"])
            zeta_dev = progs.put_small(ext.to_device(zeta))
            zeta_g_dev = progs.put_small(ext.to_device(zeta_g))
            t_k = time.perf_counter()
            t_z_dev, t_zg_dev, q_z_dev = rt.guard_phase(
                "open", air_name,
                lambda: p_open(cols, chunks, zeta_dev, zeta_g_dev))
            t_at_z = [tuple(int(x) for x in row)
                      for row in _canon(t_z_dev)]
            t_at_zg = [tuple(int(x) for x in row)
                       for row in _canon(t_zg_dev)]
            q_at_z = [tuple(int(x) for x in row)
                      for row in _canon(q_z_dev)]
            # _canon host-transfers force the sync: the wall is bounded
            _record_phase_wall(air_name, "open",
                               time.perf_counter() - t_k)
            arts = rt.screen_outputs("open", {
                "t_at_z": t_at_z, "t_at_zg": t_at_zg, "q_at_z": q_at_z})
            t_at_z, t_at_zg, q_at_z = (
                arts["t_at_z"], arts["t_at_zg"], arts["q_at_z"])
            for tup in t_at_z + t_at_zg + q_at_z:
                ch.absorb_ext(tup)
        if store is not None:
            tz_np, tzg_np, qz_np = _ckpt_copy(
                "open", (t_z_dev, t_zg_dev, q_z_dev))
            host.update(t_z=tz_np, t_zg=tzg_np, q_z=qz_np)
            store.store("open", {"t_z": tz_np, "t_zg": tzg_np,
                                 "q_z": qz_np, "t_at_z": t_at_z,
                                 "t_at_zg": t_at_zg, "q_at_z": q_at_z,
                                 "ch": ch.state()},
                        mesh_label=mesh_label)
        faults.inject("backend.phase", None, kinds=("drop",))
    gamma = ch.sample_ext()

    # ---- 4. DEEP composition + 5. FRI ------------------------------------
    fri_pay = resume.get("fri")
    if fri_pay is not None:
        with tracing.span("prove.fri_fold", stage="fri_fold",
                          resumed=True):
            rt.note_resume("fri")
            fri_dict = fri_pay["fri"]
            indices = fri_pay["indices"]
    else:
        with tracing.span("prove.fri_fold", stage="fri_fold"):
            if lde_rows is None:        # commit was resumed
                lde_rows = progs.put_named("lde_rows", host["lde_rows"])
            if q_lde is None:           # quotient was resumed
                q_lde = progs.put_named("q_lde", _ckpt_rebuild(
                    "quotient", q_lde_from_rows, host["q_rows"]))
            if t_z_dev is None:         # open was resumed
                t_z_dev = progs.put_small(jnp.asarray(host["t_z"]))
                t_zg_dev = progs.put_small(jnp.asarray(host["t_zg"]))
                q_z_dev = progs.put_small(jnp.asarray(host["q_z"]))
            zeta_dev = progs.put_small(ext.to_device(zeta))
            zeta_g_dev = progs.put_small(ext.to_device(zeta_g))
            gamma_dev = progs.put_small(ext.to_device(gamma))
            with tracing.span("prove.deep") as deep:
                F = rt.guard_phase(
                    "fri", air_name,
                    lambda: p_deep(lde_rows, q_lde, t_z_dev, t_zg_dev,
                                   q_z_dev, zeta_dev, zeta_g_dev, gamma_dev))
                jax.block_until_ready(F)
            if deep is not None:
                _record_phase_wall(air_name, "deep", deep.seconds)
            fparams = fri.FriParams(
                log_blowup=lb, num_queries=params.num_queries,
                log_final_size=params.log_final_size, shift=shift,
                grinding_bits=params.grinding_bits,
            )
            fprover = fri.FriProver(fparams, mesh=mesh)
            # FriProver.prove returns host-side data, so the span is
            # implicitly device-bounded
            fri_proof, indices = fprover.prove(F, ch)
            fri_dict = {
                "roots": fri_proof.roots,
                "final_coeffs": [list(c) for c in fri_proof.final_coeffs],
                "queries": fri_proof.queries,
                "pow_nonce": fri_proof.pow_nonce,
            }
            rt.screen_outputs("fri", {"roots": fri_dict["roots"],
                                      "final_coeffs":
                                          fri_dict["final_coeffs"]})
        if store is not None:
            store.store("fri", {"fri": fri_dict, "indices": indices,
                                "ch": ch.state()},
                        mesh_label=mesh_label)
        faults.inject("backend.phase", None, kinds=("drop",))

    # ---- openings of trace/quotient at the query indices -----------------
    with tracing.span("prove.query", stage="query",
                      num_queries=params.num_queries):
        with tracing.span("query.canon") as canon:
            d2h_bytes = 0
            if all(k in host for k in ("lde_rows", "levels_t", "q_rows",
                                       "levels_q")):
                rows_np, q_rows_np = host["lde_rows"], host["q_rows"]
                lt_np, lq_np = host["levels_t"], host["levels_q"]
            else:
                rows_np, q_rows_np, lt_np, lq_np = jax.device_get(
                    (lde_rows, q_rows, tuple(levels_t), tuple(levels_q)))
                d2h_bytes = _nbytes((rows_np, q_rows_np, lt_np, lq_np))
            # select, then convert: the rows and siblings the queries
            # open are gathered out of the Montgomery-form arrays, and
            # only those go through `from_mont_host`
            half = N // 2
            idxs = np.array([i for q in indices for i in (q, q + half)],
                            dtype=np.int64)
            opened = {
                name: (bb.from_mont_host(rows[idxs]),
                       merkle.open_paths_mont(levels, idxs))
                for name, rows, levels in (("trace", rows_np, lt_np),
                                           ("quotient", q_rows_np, lq_np))}
            tracing.set_attrs(canon, d2h_bytes=d2h_bytes,
                              canon_bytes=_nbytes(opened))
        openings = []
        with tracing.span("query.paths"):
            for j in range(len(indices)):
                entry = {}
                for name, (rows_c, paths_c) in opened.items():
                    for tag, k in (("lo", 2 * j), ("hi", 2 * j + 1)):
                        entry[f"{name}_{tag}"] = rows_c[k].tolist()
                        entry[f"{name}_{tag}_path"] = paths_c[k].tolist()
                openings.append(entry)

    # live throughput gauge: trace cells proven per end-to-end second
    # (transcript + host query openings included — the honest number)
    _record_prove_throughput(n * w, time.perf_counter() - t_prove0)
    proof = {
        "n": n, "width": w, "log_blowup": lb,
        "pub_inputs": [int(v) % bb.P for v in pub_inputs],
        "trace_root": [int(x) for x in _canon(trace_root)],
        "quotient_root": [int(x) for x in _canon(q_root)],
        "trace_at_zeta": [tuple(v) for v in t_at_z],
        "trace_at_zeta_g": [tuple(v) for v in t_at_zg],
        "quotient_at_zeta": [tuple(v) for v in q_at_z],
        "fri": fri_dict,
        "openings": openings,
    }
    if store is not None:
        store.store("proof", proof, mesh_label=mesh_label)
    return proof
