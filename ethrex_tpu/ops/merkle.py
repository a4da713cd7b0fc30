"""Poseidon2 Merkle tree commitment over BabyBear vectors.

Equivalent of the trace-commitment Merkle hashing inside the reference's zkVM
provers (SURVEY.md §2.6 "Poseidon2 Merkle hashing").  The device builds every
tree level as one batched compression call (perfect VPU vectorization); proofs
(authentication paths) are opened host-side from the level arrays.
"""

from __future__ import annotations

import threading

import numpy as np

from . import babybear as bb
from . import poseidon2 as p2

DIGEST_WIDTH = p2.RATE  # 8 limbs


import jax


def build_levels_with(leaves, shard=None):
    """Traceable level build with an optional sharding-constraint hook:
    `shard(digests)` is applied to every level (the mesh-threaded STARK
    phases pass a row-sharding constrainer; levels smaller than the mesh
    pass through unchanged inside the hook).  The ONE level-build loop —
    _build_levels is its jitted no-hook form."""
    sh = shard if shard is not None else (lambda d: d)
    digests = sh(p2.hash_leaves(leaves))
    levels = [digests]
    while digests.shape[0] > 1:
        digests = sh(p2.compress(digests[0::2], digests[1::2]))
        levels.append(digests)
    return tuple(levels)


@jax.jit
def _build_levels(leaves):
    return build_levels_with(leaves)


def commit_levels(leaves):
    """Build a Merkle tree over `leaves` (n, w) Montgomery field elements.

    n must be a power of two.  Returns a list of level digest arrays,
    levels[0] = leaf digests (n, 8) ... levels[-1] = root (1, 8).
    One jitted call per leaf shape: a single device dispatch, with every
    level's hash fused into one XLA program.
    """
    n = leaves.shape[0]
    if n & (n - 1):
        raise ValueError("leaf count must be a power of two")
    return list(_build_levels(leaves))


def root(levels):
    return levels[-1][0]


def batched_roots(digests, sizes: tuple[int, ...]):
    """Roots of MANY Merkle trees from one flat digest array.

    `digests`: (sum(sizes), 8) leaf digests, trees concatenated in order;
    every size a power of two.  Each global level runs ONE batched
    compression over every still-active tree (finished roots ride along
    untouched), so committing the whole FRI layer chain costs
    max(log2(sizes)) kernels instead of sum(log2(sizes)) — the
    small-kernel serialization in the fused prove step was one of its
    hotspots.  Index plans are static numpy, traced once per shape.

    Returns a list of (8,) root digests, one per tree.
    """
    import jax.numpy as jnp

    sizes = [int(s) for s in sizes]
    for s in sizes:
        if s & (s - 1):
            raise ValueError("tree sizes must be powers of two")
    cur = list(sizes)
    state = digests
    while any(s > 1 for s in cur):
        left = []
        right = []
        passthrough = []
        off = 0
        new_sizes = []
        for s in cur:
            if s > 1:
                left.extend(range(off, off + s, 2))
                right.extend(range(off + 1, off + s, 2))
                new_sizes.append(s // 2)
            else:
                passthrough.append(off)
                new_sizes.append(1)
            off += s
        li = jnp.asarray(np.array(left, dtype=np.int32))
        ri = jnp.asarray(np.array(right, dtype=np.int32))
        compressed = p2.compress(state[li], state[ri])
        # reassemble in tree order: compressed rows and passthrough rows
        # interleave by segment; build the permutation statically
        pieces = []
        c_off = 0
        p_iter = iter(passthrough)
        for s, ns in zip(cur, new_sizes):
            if s > 1:
                pieces.append(("c", c_off, ns))
                c_off += ns
            else:
                pieces.append(("p", next(p_iter), 1))
        if all(kind == "c" for kind, _, _ in pieces):
            state = compressed
        else:
            parts = []
            for kind, start, count in pieces:
                if kind == "c":
                    parts.append(compressed[start:start + count])
                else:
                    parts.append(state[start:start + 1])
            state = jnp.concatenate(parts, axis=0)
        cur = new_sizes
    return [state[i] for i in range(len(sizes))]


def open_path(levels, index: int):
    """Host-side: sibling digests bottom-up for leaf `index`."""
    path = []
    idx = index
    for level in levels[:-1]:
        path.append(np.asarray(level[idx ^ 1]))
        idx >>= 1
    return path


def open_paths_mont(levels, idxs) -> np.ndarray:
    """Sibling digests bottom-up for every leaf in `idxs`, out of host
    levels still in Montgomery form -> canonical (len(idxs), depth, 8).

    Select, then convert: the Montgomery map is elementwise, so only the
    gathered siblings go through `from_mont_host`; `.tolist()` of a row
    is the wire-format path."""
    idx = np.asarray(idxs, dtype=np.int64)
    sibs = np.empty((idx.size, len(levels) - 1, DIGEST_WIDTH), np.uint32)
    for k, level in enumerate(levels[:-1]):
        sibs[:, k] = np.asarray(level)[(idx >> k) ^ 1]
    return bb.from_mont_host(sibs)


def verify_path(root_digest, index: int, leaf_digest, path,
                depth: int | None = None) -> bool:
    """Host-side verification with the host permutation (`permute_ref`).

    Inputs are device digests in Montgomery form; since the permutation is
    built only from adds and mont-muls by mont-form constants, it commutes
    with the Montgomery map — we convert to canonical once and run the
    canonical reference.

    `depth` (log2 of the leaf count) binds the path length; without it an
    inner-node digest would verify as a "leaf" with a truncated path.
    """
    if depth is not None and len(path) != depth:
        return False
    cur = [int(x) for x in bb.from_mont_host(np.asarray(leaf_digest))]
    root_c = [int(x) for x in bb.from_mont_host(np.asarray(root_digest))]
    path_c = [[int(x) for x in bb.from_mont_host(np.asarray(sib))]
              for sib in path]
    return fold_path_canonical(index, cur, path_c) == root_c


def compress_ref(left, right) -> list[int]:
    """Canonical host 2-to-1 compression (matches p2.compress)."""
    state = p2.permute_ref(list(left) + list(right))
    return [(state[i] + left[i]) % bb.P for i in range(DIGEST_WIDTH)]


def fold_path_canonical(index: int, leaf_digest, path):
    """Fold a canonical leaf digest up a canonical path to a root digest."""
    cur = list(leaf_digest)
    idx = index
    for sib in path:
        sib = [int(x) for x in sib]
        if idx & 1:
            cur = compress_ref(sib, cur)
        else:
            cur = compress_ref(cur, sib)
        idx >>= 1
    return cur


_FOLDED = threading.local()   # .n: this thread's `openings_folded`


def verify_openings(root_c, indices, leaves_c, paths_c,
                    depth: int) -> list[bool]:
    """Fully canonical check of k openings of one tree: hash each leaf
    row, fold it up its path, compare with the root.  One verdict per
    opening, in order.

    root_c / paths_c / leaves_c are canonical ints (what proofs carry on
    the wire); `depth` binds the path length.  This runs on untrusted
    proof data, so each opening is checked here first (path length,
    sibling width, int limbs, reduced mod p): a malformed one is False,
    never an exception, and never reaches the native engine, which
    hashes and folds the rest in one call (`p2.native_merkle_roots`;
    `fold_path_canonical(hash_leaf_ref(...))` where it did not load).
    The leaf rows are of one length: the caller checks the width its
    tree commits to."""
    _FOLDED.n = openings_folded() + len(indices)
    verdicts = [False] * len(indices)
    try:
        if len(root_c) != DIGEST_WIDTH:
            return verdicts
    except TypeError:
        return verdicts
    good, rows, sibs = [], [], []
    for i, (leaf, path) in enumerate(zip(leaves_c, paths_c)):
        try:
            if len(path) != depth or any(len(sib) != DIGEST_WIDTH
                                         for sib in path):
                continue
            row = [int(x) % bb.P for x in leaf]
            path_c = [[int(x) % bb.P for x in sib] for sib in path]
        except (TypeError, ValueError):
            continue
        good.append(i)
        rows.append(row)
        sibs.append(path_c)
    if not good:
        return verdicts
    try:
        root = [int(x) % bb.P for x in root_c]
    except (TypeError, ValueError):
        return verdicts
    idx = [indices[i] for i in good]
    folded = p2.native_merkle_roots(
        np.array(rows, dtype=np.uint32).reshape(len(good), -1), idx,
        np.array(sibs, dtype=np.uint32).reshape(len(good), depth,
                                                DIGEST_WIDTH))
    if folded is None:
        folded = [fold_path_canonical(j, hash_leaf_ref(row), path)
                  for j, row, path in zip(idx, rows, sibs)]
    else:
        folded = folded.tolist()
    for i, got in zip(good, folded):
        verdicts[i] = got == root
    return verdicts


def openings_folded() -> int:
    """How many openings this thread's `verify_openings` calls have
    checked so far: a caller reads it before and after a proof's check
    (attribute `openings` of the proof sender's `proof.verify` span)."""
    return getattr(_FOLDED, "n", 0)


def hash_leaf_ref(leaf) -> list[int]:
    """Numpy reference of p2.hash_leaves for a single canonical-int row."""
    vals = [int(x) % bb.P for x in leaf]
    pad = (-len(vals)) % p2.RATE
    vals = vals + [0] * pad
    state = [0] * p2.WIDTH
    for i in range(0, len(vals), p2.RATE):
        for j in range(p2.RATE):
            state[j] = (state[j] + vals[i + j]) % bb.P
        state = p2.permute_ref(state)
    return state[:p2.RATE]
