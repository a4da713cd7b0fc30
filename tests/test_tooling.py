"""REPL, monitor and prewarm tooling (reference inventory rows:
tooling/repl, tooling/monitor, crates/blockchain/prewarm.rs)."""

from ethrex_tpu.blockchain.prewarm import prewarm_transactions
from ethrex_tpu.crypto import secp256k1
from ethrex_tpu.node import Node
from ethrex_tpu.primitives.genesis import Genesis
from ethrex_tpu.primitives.transaction import Transaction
from ethrex_tpu.rpc.server import RpcServer
from ethrex_tpu.utils.monitor import render_lines, snapshot
from ethrex_tpu.utils.repl import RpcSession, dispatch

SECRET = 0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8
SENDER = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(SECRET))
GENESIS = {
    "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
               "shanghaiTime": 0, "cancunTime": 0},
    "alloc": {"0x" + SENDER.hex(): {"balance": hex(10**21)}},
    "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7", "timestamp": "0x0",
}


def _tx(nonce, value=100):
    return Transaction(
        tx_type=2, chain_id=1337, nonce=nonce,
        max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
        gas_limit=21_000, to=bytes([0x42]) * 20, value=value).sign(SECRET)


def _node_with_rpc():
    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node, host="127.0.0.1", port=0).start()
    return node, server, f"http://127.0.0.1:{server.port}"


def test_repl_dispatch_commands():
    node, server, url = _node_with_rpc()
    try:
        node.submit_transaction(_tx(0))
        node.produce_block()
        rpc = RpcSession(url)
        assert dispatch(rpc, "bn") == "1"
        assert "#1" in dispatch(rpc, "head")
        assert dispatch(rpc, f"bal 0x{'42' * 20}") == "100"
        assert "gasUsed" in dispatch(rpc, "block 1")
        assert "pending" in dispatch(rpc, "raw txpool_status")
        assert dispatch(rpc, "eth_chainId") == "0x539"
        assert "unknown command" in dispatch(rpc, "nosuch")
        assert "bn" in dispatch(rpc, "help")
    finally:
        server._httpd.shutdown()


def test_monitor_snapshot_and_render():
    node, server, url = _node_with_rpc()
    try:
        for n in range(3):
            node.submit_transaction(_tx(n))
            node.produce_block()
        snap = snapshot(RpcSession(url), blocks=4)
        assert snap["head"]["number"] == 3
        assert [b["number"] for b in snap["recent"]] == [0, 1, 2, 3]
        assert snap["txpool"] == {"pending": 0, "queued": 0}
        lines = render_lines(snap, width=80)
        assert any("head #3" in ln for ln in lines)
        assert any("recent blocks" in ln for ln in lines)
    finally:
        server._httpd.shutdown()


def test_prewarm_is_side_effect_free_and_counts():
    node = Node(Genesis.from_json(GENESIS))
    parent = node.store.head_header()
    txs = [_tx(n) for n in range(5)]
    root_before = node.head_state_root()
    ran = prewarm_transactions(node.chain, parent, txs)
    assert ran == 5
    # canonical state untouched
    assert node.head_state_root() == root_before
    assert node.store.head_header().number == 0
    # the real block still builds and includes the txs
    for t in txs:
        node.submit_transaction(t)
    blk = node.produce_block()
    assert len(blk.body.transactions) == 5


def test_every_fault_site_has_chaos_coverage():
    """Every registered fault-injection site must be exercised by at
    least one chaos test, so a new site cannot land without battery
    coverage."""
    import glob
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    corpus = ""
    # the HA leader-kill battery is a chaos battery in all but filename
    paths = glob.glob(os.path.join(here, "test_*chaos*.py"))
    paths.append(os.path.join(here, "test_sequencer_ha.py"))
    for path in paths:
        with open(path) as f:
            corpus += f.read()
    missing = [s for s in sorted(faults.SITES) if f'"{s}"' not in corpus]
    assert not missing, f"fault sites without chaos coverage: {missing}"


def test_ha_fault_sites_covered_by_ha_battery():
    """The leadership sites are the HA battery's contract: each must be
    exercised in tests/test_sequencer_ha.py specifically (not merely
    mentioned somewhere in another battery)."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_sequencer_ha.py")) as f:
        corpus = f.read()
    ha_sites = ["l1.lease", "seq.fence"]
    missing = [s for s in ha_sites if s not in faults.SITES]
    assert not missing, \
        f"HA fault sites missing from faults.SITES: {missing}"
    missing = [s for s in ha_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"HA sites without HA-battery coverage: {missing}"


def test_store_fault_sites_covered_by_storage_battery():
    """The store.* sites are the storage battery's contract: each must be
    exercised in tests/test_storage_chaos.py specifically (not merely
    mentioned somewhere in another battery)."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_storage_chaos.py")) as f:
        corpus = f.read()
    store_sites = [s for s in sorted(faults.SITES)
                   if s.startswith("store.")]
    assert store_sites, "store.* fault sites missing from faults.SITES"
    missing = [s for s in store_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"store sites without storage-battery coverage: {missing}"


def test_serving_fault_sites_covered_by_overload_battery():
    """The serving-path sites (rpc.*, mempool.*) are the overload
    battery's contract: each must be exercised in
    tests/test_overload_chaos.py specifically."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_overload_chaos.py")) as f:
        corpus = f.read()
    serving_sites = [s for s in sorted(faults.SITES)
                     if s.startswith(("rpc.", "mempool."))
                     # the reorg re-injection path belongs to the reorg
                     # battery's contract, not the serving path's
                     and s != "mempool.reinject"]
    assert serving_sites, \
        "serving fault sites missing from faults.SITES"
    missing = [s for s in serving_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"serving sites without overload-battery coverage: {missing}"


def test_scheduler_fault_sites_covered_by_scheduler_battery():
    """The scheduling/aggregation sites are the scheduler battery's
    contract: each must be exercised in tests/test_scheduler_chaos.py
    specifically (coordinator.store_proof predates the fleet scheduler
    and stays with the prover battery)."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_scheduler_chaos.py")) as f:
        corpus = f.read()
    sched_sites = ["coordinator.schedule", "aggregate.prove",
                   "submit.duplicate"]
    missing = [s for s in sched_sites if s not in faults.SITES]
    assert not missing, \
        f"scheduler fault sites missing from faults.SITES: {missing}"
    missing = [s for s in sched_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"scheduler sites without scheduler-battery coverage: {missing}"


def test_p2p_fault_sites_covered_by_p2p_battery():
    """The p2p-path sites (net.*, peer.*, snap.*) are the p2p battery's
    contract: each must be exercised in tests/test_p2p_chaos.py
    specifically, so a new wire fault site cannot land without a drill."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_p2p_chaos.py")) as f:
        corpus = f.read()
    p2p_sites = [s for s in sorted(faults.SITES)
                 if s.startswith(("net.", "peer.", "snap."))]
    assert p2p_sites, "p2p fault sites missing from faults.SITES"
    missing = [s for s in p2p_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"p2p sites without p2p-battery coverage: {missing}"


def test_runtime_fault_sites_covered_by_runtime_battery():
    """The prover-runtime sites ("backend.phase", "device.lost") are the
    runtime battery's contract: each must be exercised in
    tests/test_runtime_chaos.py specifically — a new phase-level fault
    site cannot land without a checkpoint/ladder drill."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_runtime_chaos.py")) as f:
        corpus = f.read()
    runtime_sites = ["backend.phase", "device.lost"]
    missing = [s for s in runtime_sites if s not in faults.SITES]
    assert not missing, \
        f"runtime fault sites missing from faults.SITES: {missing}"
    missing = [s for s in runtime_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"runtime sites without runtime-battery coverage: {missing}"


def test_reorg_fault_sites_covered_by_reorg_battery():
    """The reorg-lifecycle sites ("forkchoice.apply", "mempool.reinject")
    are the reorg battery's contract: each must be exercised in
    tests/test_reorg_chaos.py specifically — the two-leg fork-choice
    crash window and the mid-settlement re-injection crash cannot lose
    their drills (docs/CHAIN_RESILIENCE.md)."""
    import os

    from ethrex_tpu.utils import faults

    here = os.path.dirname(__file__)
    with open(os.path.join(here, "test_reorg_chaos.py")) as f:
        corpus = f.read()
    reorg_sites = ["forkchoice.apply", "mempool.reinject"]
    missing = [s for s in reorg_sites if s not in faults.SITES]
    assert not missing, \
        f"reorg fault sites missing from faults.SITES: {missing}"
    missing = [s for s in reorg_sites if f'"{s}"' not in corpus]
    assert not missing, \
        f"reorg sites without reorg-battery coverage: {missing}"


def test_no_bare_print_in_library_modules():
    """Library diagnostics go through the structured logger
    (utils/tracing.py setup_logging), never bare print().  Terminal
    front-ends (cli, repl, monitor) own stdout and are allowlisted."""
    import pathlib
    import re

    import ethrex_tpu

    root = pathlib.Path(ethrex_tpu.__file__).parent
    # loadgen is the load-harness CLI: it prints its JSON report on
    # stdout, so it owns stdout like cli/repl
    allow = {"cli.py", "repl.py", "monitor.py", "loadgen.py"}
    pat = re.compile(r"(?<![A-Za-z0-9_.])print\(")
    offenders = []
    for path in sorted(root.rglob("*.py")):
        if path.name in allow:
            continue
        for lineno, line in enumerate(path.read_text().splitlines(), 1):
            if pat.search(line):
                offenders.append(f"{path.relative_to(root)}:{lineno}")
    assert not offenders, \
        f"bare print() in library modules (use logging): {offenders}"


def test_every_native_source_has_probed_fallback():
    """Every native/*.c / *.cpp engine must have a Python wrapper module
    with an `available()` probe, so callers can gate on the native path
    uniformly and nothing hard-fails without a toolchain.  A new native
    source must be registered here with its wrapper."""
    import importlib
    import os
    import pathlib

    import ethrex_tpu

    wrappers = {
        "evm.cpp": "ethrex_tpu.evm.native_vm",
        "keccak.c": "ethrex_tpu.crypto.keccak",
        "kvstore.cpp": "ethrex_tpu.storage.persistent",
        "mpt.cpp": "ethrex_tpu.trie.native_mpt",
        "poseidon2.c": "ethrex_tpu.ops.poseidon2",
        "secp256k1.c": "ethrex_tpu.crypto.native_secp256k1",
    }
    native_dir = pathlib.Path(ethrex_tpu.__file__).parent.parent / "native"
    sources = sorted(p.name for p in native_dir.iterdir()
                     if p.suffix in (".c", ".cpp"))
    unmapped = [s for s in sources if s not in wrappers]
    assert not unmapped, \
        f"native sources without a registered Python wrapper: {unmapped}"
    for src, mod_name in sorted(wrappers.items()):
        assert os.path.exists(native_dir / src), \
            f"{mod_name} wraps native/{src}, which does not exist"
        mod = importlib.import_module(mod_name)
        probe = getattr(mod, "available", None)
        assert callable(probe), \
            f"{mod_name} (wrapper for native/{src}) lacks available()"
        assert isinstance(probe(), bool), \
            f"{mod_name}.available() must return a bool"


def test_every_metric_helper_has_help_text():
    """Every record_*/observe_* helper in utils/metrics.py AND the perf
    package must attach non-empty help text to each metric it touches —
    an undocumented family in the exposition is a family nobody can
    alert on.  A metric call carries its help as the second (or later)
    string literal, so each METRICS.inc/set/observe/set_labeled or
    _observe_safe call inside a helper must contain at least two
    non-empty string constants (name + help) or an explicit help_text=
    keyword."""
    import ast
    import inspect

    from ethrex_tpu.blockchain import fork_choice, mempool
    from ethrex_tpu.l2 import leadership
    from ethrex_tpu.perf import (chain_path, hlo_introspect, loadgen,
                                 occupancy, profiler, roofline)
    from ethrex_tpu.prover import checkpoint, runtime_errors
    from ethrex_tpu.utils import exec_cache, metrics, overload

    from ethrex_tpu.utils import tracing

    offenders = []
    for mod in (metrics, tracing, profiler, roofline, hlo_introspect,
                occupancy, loadgen, chain_path,
                mempool, fork_choice, overload, exec_cache, checkpoint,
                runtime_errors, leadership):
        tree = ast.parse(inspect.getsource(mod))
        for fn in ast.walk(tree):
            if not isinstance(fn, ast.FunctionDef):
                continue
            if not (fn.name.startswith("record_")
                    or fn.name.startswith("observe_")):
                continue
            for call in ast.walk(fn):
                if not isinstance(call, ast.Call):
                    continue
                f = call.func
                is_metric = (
                    (isinstance(f, ast.Attribute)
                     and f.attr in ("inc", "set", "observe", "set_labeled",
                                    "inc_labeled")
                     and isinstance(f.value, ast.Name)
                     # "registry" covers helpers writing into a run-local
                     # Metrics() instead of the global singleton (loadgen)
                     and f.value.id in ("METRICS", "registry"))
                    or (isinstance(f, ast.Name) and f.id == "_observe_safe"))
                if not is_metric:
                    continue
                strings = [a.value for a in call.args
                           if isinstance(a, ast.Constant)
                           and isinstance(a.value, str) and a.value.strip()]
                kw_help = any(
                    k.arg == "help_text"
                    and isinstance(k.value, ast.Constant)
                    and isinstance(k.value.value, str)
                    and k.value.value.strip()
                    for k in call.keywords)
                if len(strings) < 2 and not kw_help:
                    offenders.append(f"{mod.__name__}.{fn.name} "
                                     f"(line {call.lineno})")
    assert not offenders, \
        f"metric calls without help text: {offenders}"


def test_histogram_exemplar_golden_exposition_line():
    """OpenMetrics exemplar syntax, golden: the bucket an observation
    lands in carries `# {trace_id="..."} value` (no timestamp — keeps
    this golden stable), other buckets stay bare."""
    from ethrex_tpu.utils.metrics import Metrics

    m = Metrics()
    tid = "ab" * 8
    m.observe("batch_proving_seconds", 0.003, None, "batch proving wall",
              exemplar=tid)
    lines = m.render().splitlines()
    assert ('batch_proving_seconds_bucket{le="0.004"} 1'
            f' # {{trace_id="{tid}"}} 0.003') in lines
    # the cumulative buckets above it count the observation WITHOUT
    # inheriting the exemplar
    assert 'batch_proving_seconds_bucket{le="0.008"} 1' in lines
    assert 'batch_proving_seconds_bucket{le="0.002"} 0' in lines
    # an over-ladder value exemplars the +Inf bucket
    m.observe("batch_proving_seconds", 10**6, None, "batch proving wall",
              exemplar="ff" * 8)
    text = m.render()
    assert (f'batch_proving_seconds_bucket{{le="+Inf"}} 2'
            f' # {{trace_id="{"ff" * 8}"}} 1000000.0') in text


def test_label_set_cardinality_clamp():
    """Unbounded label values cannot grow a family past MAX_LABEL_SETS
    (mirror of the profiler's MAX_KEYS): overflow series are dropped and
    counted, existing series keep updating."""
    from ethrex_tpu.utils.metrics import MAX_LABEL_SETS, Metrics

    m = Metrics()
    for i in range(MAX_LABEL_SETS + 88):
        m.observe("h_seconds", 0.1, {"k": f"v{i}"}, "h")
    assert len(m.histograms["h_seconds"].series) == MAX_LABEL_SETS
    assert m.counters["metrics_dropped_label_sets_total"] == 88
    # an existing series still updates after the clamp engages
    m.observe("h_seconds", 0.1, {"k": "v0"}, "h")
    row = m.histograms["h_seconds"].series[(("k", "v0"),)]
    assert row[len(m.histograms["h_seconds"].buckets)] == 2
    # labelled counters and gauges sit behind the same clamp
    for i in range(MAX_LABEL_SETS + 1):
        m.inc_labeled("c_total", {"k": f"v{i}"}, 1, "c")
        m.set_labeled("g", {"k": f"v{i}"}, 1.0, "g")
    assert len(m.lcounters["c_total"]) == MAX_LABEL_SETS
    assert len(m.lgauges["g"]) == MAX_LABEL_SETS
    # the drop counter itself is documented in the exposition
    assert "# HELP metrics_dropped_label_sets_total" in m.render()


def test_trace_analysis_rpcs_degrade_gracefully(monkeypatch):
    """ethrex_trace_criticalPath / ethrex_trace_export on an unknown
    trace or an empty ring (L1-only / pre-tracing node) answer with a
    found=False stub, never an error."""
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.rpc.server import RpcServer
    from ethrex_tpu.utils.tracing import Tracer

    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node)
    r = server.handle({"jsonrpc": "2.0", "id": 1,
                       "method": "ethrex_trace_criticalPath",
                       "params": ["ff" * 8]})
    assert r["result"] == {"found": False, "traceId": "ff" * 8,
                           "components": {}, "chain": []}
    r = server.handle({"jsonrpc": "2.0", "id": 2,
                       "method": "ethrex_trace_export",
                       "params": ["ff" * 8]})
    assert r["result"]["found"] is False
    assert r["result"]["traceEvents"] == []
    # empty ring + no trace-id argument: nothing to resolve
    monkeypatch.setattr("ethrex_tpu.rpc.server.TRACER", Tracer())
    for method in ("ethrex_trace_criticalPath", "ethrex_trace_export"):
        r = server.handle({"jsonrpc": "2.0", "id": 3, "method": method,
                           "params": []})
        assert r["result"]["found"] is False


def test_chain_path_rpc_degrades_on_idle_l1_node():
    """ethrex_chainPath on a fresh L1-only node (no traffic, no
    sequencer) answers a truthful idle stub — enabled, all three stage
    queues present at depth 0, no sampled lifecycles, bottleneck null —
    never an error.  The ethrex_health chainPath section degrades the
    same way."""
    node = Node(Genesis.from_json(GENESIS))
    server = RpcServer(node)
    try:
        r = server.handle({"jsonrpc": "2.0", "id": 1,
                           "method": "ethrex_chainPath", "params": []})
        out = r["result"]
        assert out["enabled"] is True
        assert "error" not in out
        assert set(out["stages"]) == {"admission", "producer", "batching"}
        for st in out["stages"].values():
            assert st["depth"] == 0 and st["arrivals"] == 0
        assert out["lifecycle"]["records"] == []
        assert out["explain"]["bottleneck"] is None
        h = server.handle({"jsonrpc": "2.0", "id": 2,
                           "method": "ethrex_health", "params": []})
        cp = h["result"]["chainPath"]
        assert cp["bottleneck"] is None
        assert cp["blocksProduced"] == 0
        assert cp["backlogSeconds"] is None
        assert cp["producerStallSeconds"] is None
    finally:
        node.stop()


def test_every_env_knob_is_documented():
    """Every ETHREX_* environment variable the code reads must appear in
    docs/*.md — an undocumented knob is one an operator cannot discover.
    A new env var lands with its documentation or not at all."""
    import pathlib
    import re

    import ethrex_tpu

    pkg = pathlib.Path(ethrex_tpu.__file__).parent
    repo = pkg.parent
    pat = re.compile(r"ETHREX_[A-Z0-9_]+")
    used = set()
    for path in sorted(pkg.rglob("*.py")):
        if "__pycache__" in path.parts:
            continue
        used.update(pat.findall(path.read_text()))
    # cli.py builds names as f"ETHREX_{name}"; the prefix alone is not a knob
    used.discard("ETHREX_")
    documented = set()
    for path in sorted((repo / "docs").glob("*.md")):
        documented.update(pat.findall(path.read_text()))
    missing = sorted(used - documented)
    assert not missing, \
        f"env vars read by code but absent from docs/*.md: {missing}"


def test_async_front_door_never_blocks_the_loop():
    """rpc/server.py is event-loop code: every blocking primitive
    (time.sleep, socket recv/accept/sendall, socket file objects) must
    live behind the executor boundary (handlers run in _execute on the
    pool), never in the module itself — one blocking call on the loop
    stalls every connection at once."""
    import pathlib
    import re

    import ethrex_tpu

    src = (pathlib.Path(ethrex_tpu.__file__).parent / "rpc"
           / "server.py").read_text()
    banned = [r"time\.sleep\(", r"\.recv\(", r"\.accept\(",
              r"\.sendall\(", r"\.makefile\("]
    offenders = []
    for pat in banned:
        for m in re.finditer(pat, src):
            lineno = src.count("\n", 0, m.start()) + 1
            offenders.append(f"rpc/server.py:{lineno} {m.group(0)}")
    assert not offenders, \
        f"blocking calls in the asyncio server module: {offenders}"


def test_serving_knobs_have_cli_flags_with_help():
    """Each serving tuning knob lands as BOTH an env var and a CLI flag
    with real help text — an operator reading --help must be able to
    discover the knob (the docs guard above holds the docs side of the
    same contract)."""
    import ast
    import pathlib

    import ethrex_tpu

    src = (pathlib.Path(ethrex_tpu.__file__).parent
           / "cli.py").read_text()
    tree = ast.parse(src)
    flags = {}
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == "add_argument" and node.args
                and isinstance(node.args[0], ast.Constant)):
            continue
        helps = [k.value for k in node.keywords if k.arg == "help"]
        flags[node.args[0].value] = (
            helps[0].value if helps
            and isinstance(helps[0], ast.Constant) else None)
    for flag, env in [("--rpc-executor-workers", "RPC_EXECUTOR_WORKERS"),
                      ("--rpc-max-batch", "RPC_MAX_BATCH"),
                      ("--rpc-backlog", "RPC_BACKLOG")]:
        assert flag in flags, f"missing CLI flag {flag}"
        assert flags[flag], f"{flag} has no help text"
        assert f'_env_int("{env}"' in src, \
            f"{flag} lacks its ETHREX_{env} env mirror"


def test_stark_partition_specs_reference_mesh_axis():
    """Every PartitionSpec built under stark/ must name the mesh axis
    through parallel.mesh.AXIS (or be fully replicated) — a
    string-literal axis name silently diverges from the shared
    partitioning policy the moment the mesh axis is renamed."""
    import ast
    import pathlib

    import ethrex_tpu

    stark_dir = pathlib.Path(ethrex_tpu.__file__).parent / "stark"
    offenders = []
    for path in sorted(stark_dir.rglob("*.py")):
        tree = ast.parse(path.read_text())
        aliases = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and "sharding" in node.module:
                for a in node.names:
                    if a.name == "PartitionSpec":
                        aliases.add(a.asname or a.name)
        if not aliases:
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            if name not in aliases:
                continue
            args = list(node.args) + [k.value for k in node.keywords]
            for arg in args:
                for sub in ast.walk(arg):
                    if isinstance(sub, ast.Constant) \
                            and isinstance(sub.value, str):
                        offenders.append(f"{path.name}:{node.lineno}")
    assert not offenders, (
        "string-literal axis names in stark/ PartitionSpec calls "
        f"(use parallel.mesh.AXIS): {sorted(set(offenders))}")


def test_fault_rule_after_skips_leading_occasions():
    """after=N arms a rule only from the N+1th matching occasion — the
    handle the chaos battery uses to hit the response leg of a two-leg
    site like l1.commit."""
    from ethrex_tpu.utils.faults import FaultPlan, InjectedFault

    plan = FaultPlan(seed=0).drop("l1.commit", times=1, after=1)
    assert plan.fire("l1.commit") is None          # leg 1: skipped
    try:
        plan.fire("l1.commit")                     # leg 2: fires
        raise AssertionError("expected InjectedFault")
    except InjectedFault:
        pass
    assert plan.fire("l1.commit") is None          # budget exhausted
    assert plan.log == [("l1.commit", "drop")]
