"""Which package of ethrex_tpu may import which.

Upstream's rule is "types flow down, behavior flows up" (SURVEY.md
section 1).  ALLOWED is that layer map for this tree, written bottom-up;
DEBTS is every arrow that points up today, one line each with the
ROADMAP item that owns it.  A package's imports (every Import and
ImportFrom node of every module, the lazy ones inside functions
included) must lie in ALLOWED | DEBTS; a DEBTS line nothing uses any
more must go with the arrow.  Pure ast: nothing of the program, and no
JAX, is imported here."""

import ast
import functools
import pathlib
import re

import pytest

PKG = pathlib.Path(__file__).resolve().parent.parent / "ethrex_tpu"

# what measures or smoke-tests the program imports it, never the reverse
OUTSIDE = {"benchmark", "bench", "chip_smoke"}

BASE = {"crypto", "primitives"}         # layer 0: hashes, curves, types, RLP
SHARED = BASE | {"utils", "perf"}       # + telemetry: anyone may import it
CHAIN = {"config", "trie", "storage", "evm", "blockchain"}
KERNELS = {"ops", "parallel", "stark", "models"}

ALLOWED = {
    # layer 0, and the telemetry that stands beside the stack
    "crypto": set(),
    "primitives": {"crypto"},
    "config": BASE,
    "utils": BASE,
    "perf": BASE | {"utils"},
    # the chain column (SURVEY layers 1-4)
    "trie": SHARED,
    "storage": SHARED | {"trie"},
    "evm": SHARED | {"trie"},
    "blockchain": SHARED | {"trie", "storage", "evm"},
    # the kernel column (what upstream buys as a zkVM SDK)
    "ops": SHARED,
    "parallel": SHARED | {"ops"},
    "stark": SHARED | {"ops", "parallel"},
    "models": SHARED | {"ops", "parallel", "stark"},
    # guest program, proving, sequencer (SURVEY layers 6-8)
    "guest": SHARED | CHAIN | KERNELS,
    "prover": SHARED | KERNELS | {"guest"},
    "l2": SHARED | CHAIN | KERNELS | {"guest", "prover"},
    # networking (SURVEY layer 5; the L2 RPC extensions sit over l2)
    "p2p": SHARED | CHAIN,
    "rpc": SHARED | CHAIN | {"guest", "l2"},
    # the wiring and the entry point
    "node": SHARED | CHAIN | {"p2p", "rpc"},
    "cli": SHARED | CHAIN | KERNELS | {"guest", "prover", "l2", "p2p", "rpc",
                                       "node"},
}

# package -> {package it imports against the map: "ROADMAP item  where, why"}
DEBTS = {
    "crypto": {
        "ops": "R7  groth16.py runs its MSM through ops/bn254_msm"},
    "guest": {
        "l2": "D8  execution.py takes the message root from l2/messages"},
    "l2": {
        "node": "D8  sequencer.py builds on the Node wiring"},
    "models": {
        "guest": "D11  the VM AIRs take limb and step layouts from guest/"},
    "ops": {
        "parallel": "D3  fri.py asks parallel/mesh for its mesh FRI loop"},
    "p2p": {
        "rpc": "D8  connection.py takes the client name from rpc/eth"},
    "perf": {
        "blockchain": "R4  loadgen.py reads the mempool's sender cap",
        "rpc": "R4  loadgen.py signs its engine JWT with rpc/engine"},
    "stark": {
        "models": "R13  aggregate.py builds the FRI-verifier AIR",
        "prover": "D12  prover.py spells out checkpoints and fault legs"},
    "storage": {
        "evm": "D8  store.py implements the evm/db interfaces in place"},
    "utils": {
        "blockchain": "D8  ef_blockchain.py is a test runner, not a utility",
        "evm": "D8  ef_state.py is a test runner, not a utility",
        "guest": "D8  replay.py is a tool, not a utility",
        "perf": "D6  alerts.py and snapshot.py read perf's registries",
        "rpc": "D6  snapshot.py reads the RPC health section; replay.py",
        "storage": "D6  snapshot.py reads storage stats; the EF runners"},
}


def _modules(pkg: str):
    """(path, dotted package the module's relative imports start from)
    for every module of a top-level package, or the one top-level module."""
    single = PKG / f"{pkg}.py"
    if single.exists():
        yield single, ["ethrex_tpu"]
        return
    for path in sorted((PKG / pkg).rglob("*.py")):
        rel = path.relative_to(PKG.parent).with_suffix("")
        yield path, list(rel.parts[:-1])


@functools.lru_cache(maxsize=None)
def _imported(pkg: str) -> tuple[dict, set]:
    """({sibling package: [file:line, ...]}, {(outside name, file:line)})
    over all modules of `pkg`."""
    siblings: dict = {}
    outside = set()
    for path, base in _modules(pkg):
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.Import):
                names = [a.name.split(".") for a in node.names]
            elif isinstance(node, ast.ImportFrom):
                mod = node.module.split(".") if node.module else []
                if node.level:
                    mod = base[:len(base) - (node.level - 1)] + mod
                # `from .. import x` names packages in its alias list
                names = ([mod + [a.name] for a in node.names]
                         if mod == ["ethrex_tpu"] else [mod])
            else:
                continue
            where = f"{path.relative_to(PKG)}:{node.lineno}"
            for name in names:
                if name[0] in OUTSIDE:
                    outside.add((name[0], where))
                elif name[0] == "ethrex_tpu" and len(name) > 1 \
                        and name[1] in ALLOWED and name[1] != pkg:
                    siblings.setdefault(name[1], []).append(where)
    return siblings, outside


@pytest.mark.parametrize("pkg", sorted(ALLOWED))
def test_package_imports_stay_in_their_layer(pkg):
    siblings, outside = _imported(pkg)
    assert not outside, f"the program imports what measures it: {outside}"
    stray = {dep: sites for dep, sites in siblings.items()
             if dep not in ALLOWED[pkg] | set(DEBTS.get(pkg, {}))}
    assert not stray, (
        f"{pkg} imports against the layer map (ALLOWED, or a DEBTS line "
        f"with its ROADMAP item): {stray}")


def test_every_debt_is_still_owed_and_every_package_has_a_layer():
    on_disk = ({p.name for p in PKG.iterdir() if (p / "__init__.py").exists()}
               | {p.stem for p in PKG.glob("*.py")}) - {"__init__"}
    assert on_disk == set(ALLOWED)
    for pkg, owed in DEBTS.items():
        used = _imported(pkg)[0]
        paid = sorted(set(owed) - set(used))
        assert not paid, f"{pkg} no longer imports {paid}: drop the line"
        assert not set(owed) & ALLOWED[pkg]
        for label in owed.values():
            assert re.match(r"[SRDB]\d+  \S", label), label
