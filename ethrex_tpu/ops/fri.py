"""FRI low-degree test over the BabyBear quartic extension.

The fold/commit phases are batched device work (each layer is one jitted
fold + one Merkle build); the query phase and verification are host-side
canonical arithmetic.  This replaces the FRI stage the reference gets from
its zkVM SDKs' CUDA provers (SURVEY.md §2.6, §5).

Codeword convention: evaluations of an ext-field polynomial over the
multiplicative coset shift*<g> of size N in natural order (index i holds
f(shift * g^i)).  One fold step pairs index i with i + N/2 (g^{N/2} = -1):

    f'(y_i) = (f(x_i) + f(-x_i))/2 + beta * (f(x_i) - f(-x_i)) / (2 x_i)

with y_i = x_i^2, giving the codeword of f' over coset shift^2*<g^2>.

Merkle leaves pair (f[i], f[i+N/2]) as 8 base limbs so each query opens one
leaf per layer.  Transcript order per layer: absorb root, sample beta.
"""

from __future__ import annotations

import dataclasses
import functools
import threading
import time
from concurrent.futures import Future

import numpy as np
import jax
import jax.numpy as jnp

from . import babybear as bb
from . import ext
from . import merkle
from . import ntt as _ntt
from ..utils import exec_cache
from ..utils import tracing
from ..utils.metrics import record_phase_compile
from .challenger import Challenger

_INV2 = int(bb.inv_host(2))


@functools.lru_cache(maxsize=None)
def _fold_inv_points(log_n: int, shift: int) -> np.ndarray:
    """Montgomery inverses of the first half of the coset domain points."""
    n = 1 << log_n
    g_inv = bb.inv_host(bb.root_of_unity(log_n))
    s_inv = bb.inv_host(shift % bb.P)
    pows = bb.powers_host(g_inv, n // 2)
    return bb.to_mont_host((pows.astype(np.uint64) * s_inv) % bb.P)


@jax.jit
def _fold(codeword, beta, inv_pts, inv2):
    half = codeword.shape[0] // 2
    lo = codeword[:half]
    hi = codeword[half:]
    s = ext.scalar_mul(ext.add(lo, hi), inv2)
    d = ext.scalar_mul(ext.sub(lo, hi), bb.mont_mul(inv2, inv_pts))
    return ext.add(s, ext.mul(jnp.broadcast_to(beta, d.shape), d))


@jax.jit
def _pair_leaves(codeword):
    half = codeword.shape[0] // 2
    return jnp.concatenate([codeword[:half], codeword[half:]], axis=-1)


LAYER_KERNELS = ("leaves", "levels", "fold")
# The commit loop's three programs at each layer size, compiled ahead
# of time for one device: log_k -> (leaves, levels, fold).  Filled from
# the executable store by `stark.prover.hydrate_phase_cache`, and on a
# miss by `layer_programs`.
_LAYER_PROGRAMS: dict = {}
_LAYER_LOCK = threading.Lock()
_LAYER_BUILDS: dict = {}        # log_k -> Future of the build in flight
# what the mesh path calls: the jits inherit the codeword's sharding
_LAZY_PROGRAMS = (_pair_leaves, merkle._build_levels, _fold)


def clear_layer_programs() -> None:
    """Drop every layer program (tests / simulated restarts)."""
    with _LAYER_LOCK:
        _LAYER_PROGRAMS.clear()


def layer_parts(log_k: int, kernel: str) -> dict:
    """Executable-store identity of one layer program (`air_name` is
    its label in the build telemetry, as a phase program's is)."""
    return {"kind": "fri", "air_name": "FriLayer", "kernel": kernel,
            "log_n": log_k, "mesh": None}


def install_layer_programs(log_k: int, programs) -> None:
    """`programs` in `LAYER_KERNELS` order (the hydration walk)."""
    with _LAYER_LOCK:
        _LAYER_PROGRAMS.setdefault(log_k, tuple(programs))


def _build_layer_program(log_k: int, kernel: str):
    """One layer program at codeword length 2^log_k, from the executable
    store or else compiled against its (statically known) argument
    shapes and stored; returns it with its source."""
    size = 1 << log_k
    S = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    fn, specs = {
        "leaves": (_pair_leaves, (S((size, 4)),)),
        "levels": (merkle._build_levels, (S((size // 2, 8)),)),
        "fold": (_fold, (S((size, 4)), S((4,)), S((size // 2,)), S(()))),
    }[kernel]
    parts = layer_parts(log_k, kernel)
    t_c = time.perf_counter()
    compiled = exec_cache.load(parts)
    source = "deserialized"
    if compiled is None:
        source = "compiled"
        compiled = fn.lower(*specs).compile()
        exec_cache.store(parts, compiled)
    record_phase_compile(parts["air_name"], kernel,
                         time.perf_counter() - t_c, source=source)
    return compiled, source


def layer_programs(log_k: int):
    """(leaves, levels, fold) executables for a 2^log_k codeword on one
    device.  A miss builds the three under a `prove.fri_build` span, as
    `_aot_phases` builds a phase program; one build per size, a second
    caller waits for the one in flight."""
    with _LAYER_LOCK:
        programs = _LAYER_PROGRAMS.get(log_k)
        if programs is not None:
            return programs
        pending = _LAYER_BUILDS.get(log_k)
        if pending is None:
            mine = _LAYER_BUILDS[log_k] = Future()
    if pending is not None:
        with tracing.span("prove.fri_build", log_n=log_k, source="waited"):
            return pending.result()
    try:
        with tracing.span("prove.fri_build", log_n=log_k) as sp:
            built = [_build_layer_program(log_k, kernel)
                     for kernel in LAYER_KERNELS]
            sources = {source for _, source in built}
            tracing.set_attrs(sp, source="compiled" if "compiled" in sources
                              else "deserialized")
        programs = tuple(compiled for compiled, _ in built)
    except BaseException as exc:
        with _LAYER_LOCK:
            del _LAYER_BUILDS[log_k]
        mine.set_exception(exc)
        raise
    with _LAYER_LOCK:
        programs = _LAYER_PROGRAMS.setdefault(log_k, programs)
        del _LAYER_BUILDS[log_k]
    mine.set_result(programs)
    return programs


@dataclasses.dataclass
class FriParams:
    log_blowup: int = 2
    num_queries: int = 40
    log_final_size: int = 5   # stop folding at codeword length 32
    shift: int = bb.GENERATOR
    grinding_bits: int = 16   # proof-of-work bits before query sampling


@dataclasses.dataclass
class FriProof:
    roots: list            # canonical digests, one per committed layer
    final_coeffs: list     # canonical ext tuples, len = final codeword size
    queries: list          # per query, per layer: {"values": [lo, hi], "path"}
    pow_nonce: int = 0     # grinding nonce (see Challenger.grind)


class FriProver:
    """Holds per-layer state so queries can be opened after index sampling.

    `mesh` (optional) shards each layer's codeword across the mesh's
    row axis; the fold/hash jits inherit the input sharding, so XLA runs
    the layer work distributed (production multi-chip path).  Without
    one the same three programs come from `layer_programs`: compiled
    ahead of time and restored from the executable store by a warm
    process, which then traces and lowers nothing here."""

    def __init__(self, params: FriParams, mesh=None):
        self.params = params
        self.mesh = mesh

    def _shard(self, codeword):
        if self.mesh is None:
            return codeword
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel import mesh as mesh_lib

        if codeword.shape[0] < len(self.mesh.devices.flat):
            return codeword
        return jax.device_put(
            codeword, NamedSharding(self.mesh, P(mesh_lib.AXIS, None)))

    def commit_phase(self, codeword, challenger: Challenger):
        """Fold and commit layer by layer.  One `fri.layer` span per
        layer: its `d2h_s` is the `device_get` alone, which blocks on the
        layer's Merkle program (and the fold before it), so it is the
        layer's device wait plus the copy, not the copy's bandwidth."""
        p = self.params
        log_n = codeword.shape[0].bit_length() - 1
        shift = p.shift % bb.P
        inv2 = jnp.asarray(np.uint32(int(bb.to_mont_host(_INV2))))
        # (np_codeword, np_levels) per layer, in Montgomery form as they
        # left the device: `open_queries` converts only what it opens
        self.layers = []
        self.roots = []
        codeword = self._shard(codeword)
        while log_n > p.log_final_size:
            pair_leaves, build_levels, fold = (
                layer_programs(log_n) if self.mesh is None
                else _LAZY_PROGRAMS)
            with tracing.span("fri.layer", log_n=log_n) as sp:
                levels = build_levels(pair_leaves(codeword))
                # one bulk device->host transfer per layer (codeword +
                # levels)
                t_get = time.perf_counter()
                cw_np, levels_np = jax.device_get((codeword, tuple(levels)))
                tracing.set_attrs(
                    sp, d2h_s=time.perf_counter() - t_get,
                    d2h_bytes=cw_np.nbytes + sum(l.nbytes
                                                 for l in levels_np))
                root = bb.from_mont_host(levels_np[-1][0]).tolist()
                challenger.absorb_elems(root)
                self.layers.append((cw_np, levels_np))
                self.roots.append(root)
                beta = ext.to_device(challenger.sample_ext())
                inv_pts = jnp.asarray(_fold_inv_points(log_n, shift))
                # the fold is dispatched, not waited for: the next
                # layer's device_get (or fri.final's copy) pays for it
                codeword = self._shard(fold(codeword, beta, inv_pts, inv2))
                shift = (shift * shift) % bb.P
                log_n -= 1
        with tracing.span("fri.final") as sp:
            coeffs_dev = _ntt.coset_intt(codeword.T, shift=shift).T
            coeffs_np = np.asarray(coeffs_dev)
            tracing.set_attrs(sp, d2h_bytes=coeffs_np.nbytes)
            coeffs = bb.from_mont_host(coeffs_np)
            self.final_coeffs = [tuple(int(v) for v in row)
                                 for row in coeffs]
            deg_bound = (1 << p.log_final_size) >> p.log_blowup
            for row in self.final_coeffs[deg_bound:]:
                if row != (0, 0, 0, 0):
                    raise ValueError(
                        "FRI final polynomial exceeds degree bound "
                        "(input codeword was not low-degree)")
            for row in self.final_coeffs:
                challenger.absorb_ext(row)
        return self.roots, self.final_coeffs

    def open_queries(self, indices) -> list:
        """Per query, per layer: the pair of codeword values and the
        Merkle path of their leaf.  Each layer's 2 x len(indices) values
        and their siblings are gathered first and only those converted
        out of Montgomery form (`canon_bytes`)."""
        with tracing.span("fri.open_queries") as sp:
            out = [[] for _ in indices]
            idx = np.asarray(indices, dtype=np.int64)
            canon_bytes = 0
            for cw_np, levels_np in self.layers:
                half = cw_np.shape[0] // 2
                idx = idx % half
                pairs = bb.from_mont_host(
                    cw_np[np.stack([idx, idx + half], axis=1)])
                paths = merkle.open_paths_mont(levels_np, idx)
                canon_bytes += pairs.nbytes + paths.nbytes
                for per_layer, pair, path in zip(out, pairs.tolist(),
                                                 paths.tolist()):
                    per_layer.append({"values": [tuple(v) for v in pair],
                                      "path": path})
            tracing.set_attrs(sp, canon_bytes=canon_bytes)
        return out

    def prove(self, codeword, challenger: Challenger):
        """Full FRI round.  Returns (FriProof, query_indices); the caller
        (the STARK prover) opens its own commitments at the same indices."""
        self.commit_phase(codeword, challenger)
        # no `stage=`: it runs inside the `fri_fold` stage, whose seconds
        # hold it (the profiler sums a component's stages)
        with tracing.span("fri.grind") as sp:
            nonce = challenger.grind(self.params.grinding_bits)
            tracing.set_attrs(sp, tries=nonce + 1)
        n0 = self.layers[0][0].shape[0]
        bits = (n0 // 2).bit_length() - 1
        indices = challenger.sample_indices(bits, self.params.num_queries)
        queries = self.open_queries(indices)
        return (FriProof(self.roots, self.final_coeffs, queries, nonce),
                indices)


def verify(proof: FriProof, log_n0: int, challenger: Challenger,
           params: FriParams):
    """Host-side FRI verification (canonical arithmetic only).

    Returns (query_indices, layer0_values) where layer0_values[i] =
    (pair_index, lo, hi) accepted for query i — the STARK verifier
    cross-checks these against trace-derived DEEP values.
    Raises ValueError on failure.
    """
    p_ = params
    num_layers = log_n0 - p_.log_final_size
    if len(proof.roots) != num_layers:
        raise ValueError("FRI: wrong number of layer roots")

    # transcript: per layer absorb root then sample beta (mirrors the prover)
    betas = []
    shifts = []
    shift = p_.shift % bb.P
    for root in proof.roots:
        challenger.absorb_elems(root)
        betas.append(challenger.sample_ext())
        shifts.append(shift)
        shift = (shift * shift) % bb.P
    final_shift = shift
    final_size = 1 << p_.log_final_size
    if len(proof.final_coeffs) != final_size:
        raise ValueError("FRI: wrong final coefficient count")
    deg_bound = final_size >> p_.log_blowup
    for row in proof.final_coeffs[deg_bound:]:
        if tuple(row) != (0, 0, 0, 0):
            raise ValueError("FRI: final polynomial exceeds degree bound")
    for row in proof.final_coeffs:
        challenger.absorb_ext(row)
    if not challenger.check_grind(proof.pow_nonce, p_.grinding_bits):
        raise ValueError("FRI: proof-of-work grinding check failed")

    bits = log_n0 - 1
    indices = challenger.sample_indices(bits, p_.num_queries)
    if len(proof.queries) != p_.num_queries:
        raise ValueError("FRI: wrong query count")

    inv2 = bb.inv_host(2)
    layer0_values = []
    for q, per_layer in zip(indices, proof.queries):
        if len(per_layer) != num_layers:
            raise ValueError("FRI: wrong layer count in query")
        carried = None
        raw = q  # index of the folded value inside the current layer
        for k, opening in enumerate(per_layer):
            log_nk = log_n0 - k
            half = 1 << (log_nk - 1)
            idx = raw % half
            lo, hi = (tuple(int(v) for v in x) for x in opening["values"])
            if len(lo) != 4 or len(hi) != 4:
                raise ValueError("FRI: opening values must be 4-limb ext elements")
            if not merkle.verify_opening(
                proof.roots[k], idx, list(lo) + list(hi), opening["path"],
                log_nk - 1,
            ):
                raise ValueError(f"FRI: bad merkle opening at layer {k}")
            if carried is not None:
                got = lo if raw < half else hi
                if got != carried:
                    raise ValueError(f"FRI: fold mismatch entering layer {k}")
            if k == 0:
                layer0_values.append((idx, lo, hi))
            x = shifts[k] * pow(bb.root_of_unity(log_nk), idx, bb.P) % bb.P
            s = ext.h_scalar_mul(ext.h_add(lo, hi), inv2)
            d = ext.h_scalar_mul(
                ext.h_sub(lo, hi), inv2 * bb.inv_host(x) % bb.P
            )
            carried = ext.h_add(s, ext.h_mul(betas[k], d))
            raw = idx
        # `carried` is the value at index `raw` of the final codeword
        log_nf = log_n0 - num_layers
        x_f = final_shift * pow(bb.root_of_unity(log_nf), raw, bb.P) % bb.P
        acc = ext.ZERO_H
        for c in reversed(proof.final_coeffs):
            acc = ext.h_add(ext.h_mul(acc, ext.h_from_base(x_f)), tuple(c))
        if acc != carried:
            raise ValueError("FRI: final polynomial mismatch")
    return indices, layer0_values
