"""Performance-observability battery (docs/PERFORMANCE.md): the
continuous stage profiler, roofline accounting, the perf surfaces
(metrics exposition, snapshot, ethrex_perf RPC, monitor panel, alert
floors).

The never-raise drills matter most: every perf hook sits inside the
prover or import hot path, so a malformed cost_analysis() or a broken
jax.profiler must degrade to missing telemetry, never a failed prove."""

import pytest

from ethrex_tpu.perf import profiler, roofline
from ethrex_tpu.utils import tracing
from ethrex_tpu.utils.metrics import (
    METRICS, observe_import_stage, record_import_throughput,
    record_kernel_flops, record_proof_wall, record_prover_throughput)


# ---------------------------------------------------------------------------
# stage profiler

def test_profiler_accumulates_and_builds_tree():
    p = profiler.StageProfiler()
    p.record("l1_import", "execute", 0.5)
    p.record("l1_import", "execute", 1.5)
    p.record("l1_import", "merkleize", 2.0)
    tree = p.tree()
    comp = tree["components"]["l1_import"]
    assert comp["totalSeconds"] == pytest.approx(4.0)
    ex = comp["stages"]["execute"]
    assert ex["count"] == 2
    assert ex["totalSeconds"] == pytest.approx(2.0)
    assert ex["meanSeconds"] == pytest.approx(1.0)
    assert ex["maxSeconds"] == pytest.approx(1.5)
    assert ex["lastSeconds"] == pytest.approx(1.5)
    assert ex["share"] == pytest.approx(0.5)
    assert p.stage_totals("l1_import") == {
        "execute": pytest.approx(2.0), "merkleize": pytest.approx(2.0)}
    assert tree["droppedKeys"] == 0
    p.reset()
    assert p.tree() == {"components": {}, "droppedKeys": 0}


def test_profiler_never_raises_and_bounds_cardinality():
    p = profiler.StageProfiler()
    # garbage seconds must be swallowed, not raised (hot-path contract)
    p.record("c", "s", "not-a-number")
    p.record("c", "s", None)
    p.record(object(), object(), 1.0)   # coerced via str(), still lands
    assert "c" not in p.tree()["components"]  # bad rows never landed
    # runaway label cardinality is clamped at MAX_KEYS
    p2 = profiler.StageProfiler()
    for i in range(profiler.MAX_KEYS + 7):
        p2.record("c", f"stage{i}", 0.001)
    tree = p2.tree()
    assert len(tree["components"]["c"]["stages"]) == profiler.MAX_KEYS
    assert tree["droppedKeys"] == 7


def test_span_observer_folds_stages_by_component():
    with tracing.span("prove.quotient", stage="quotient"):
        pass
    with tracing.span("backend.execute", stage="execute"):
        pass
    with tracing.span("novel.thing", stage="brand_new_stage"):
        pass
    comps = profiler.PROFILER.tree()["components"]
    assert "quotient" in comps["stark"]["stages"]
    assert "execute" in comps["prover"]["stages"]
    assert "brand_new_stage" in comps["other"]["stages"]


def test_raising_stage_observer_cannot_break_spans():
    def bomb(name, stage, seconds):
        raise RuntimeError("observer bomb")

    tracing.STAGE_OBSERVERS.append(bomb)
    try:
        with tracing.span("prove.quotient", stage="quotient"):
            pass
    finally:
        tracing.STAGE_OBSERVERS.remove(bomb)
    # the well-behaved observer after/before the bomb still recorded
    comps = profiler.PROFILER.tree()["components"]
    assert "quotient" in comps.get("stark", {}).get("stages", {})


def test_capture_is_noop_without_destination_and_never_raises(
        tmp_path, monkeypatch):
    import jax

    profiler.configure(None)
    assert profiler.configured_dir() is None
    with profiler.capture("prove") as cap:
        assert cap._started is False          # no dir -> transparent no-op

    # a broken jax.profiler must not break the wrapped body
    def boom(*a, **kw):
        raise RuntimeError("profiler plugin broken")

    monkeypatch.setattr(jax.profiler, "start_trace", boom)
    profiler.configure(str(tmp_path / "traces"))
    ran = []
    with profiler.capture("prove"):
        ran.append(True)
    assert ran == [True]
    assert profiler._TRACE_ACTIVE is False    # slot released for next try


def test_capture_is_single_flight(tmp_path, monkeypatch):
    import jax

    calls = {"start": 0, "stop": 0}
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.__setitem__(
                            "start", calls["start"] + 1))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop", calls["stop"] + 1))
    profiler.configure(str(tmp_path))
    with profiler.capture("outer"):
        with profiler.capture("inner"):   # nested: degrades to no-op
            pass
        assert calls == {"start": 1, "stop": 0}
    assert calls == {"start": 1, "stop": 1}


# ---------------------------------------------------------------------------
# roofline

def test_parse_cost_reads_the_installed_jax_shape():
    """jax 0.9: cost_analysis() of a compiled executable is one dict
    (or None where the backend has no cost model)."""
    pc = roofline._parse_cost
    assert pc(None) == {"flops": None, "bytes": None}
    assert pc({}) == {"flops": None, "bytes": None}
    assert pc({"flops": "NaN-ish"}) == {"flops": None, "bytes": None}
    assert pc({"flops": 5.0}) == {"flops": 5.0, "bytes": None}
    assert pc({"bytes accessed": 7}) == {"flops": None, "bytes": 7.0}
    assert pc({"flops": 2, "bytes accessed": 3,
               "bytes accessed0{}": 1}) == {"flops": 2.0, "bytes": 3.0}
    # and a real one, from the installed jax
    import jax
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x @ x).lower(
        jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    real = pc(compiled.cost_analysis())
    assert real["flops"] and real["bytes"]


def test_roofline_partial_cost_yields_null_fields_not_errors():
    roofline.record_cost("A", "commit", None)
    roofline.record_cost("A", "quotient", {"bytes accessed": 64.0})
    roofline.record_wall("A", "commit", 0.25)
    rep = roofline.ROOFLINE.report()
    by_kernel = {k["kernel"]: k for k in rep["kernels"]
                 if k["air"] == "A"}
    commit = by_kernel["commit"]
    assert commit["flops"] is None
    assert commit["wallLastSeconds"] == pytest.approx(0.25)
    assert commit["achievedFlopsPerSec"] is None
    assert commit["utilizationVsPeak"] is None
    quotient = by_kernel["quotient"]
    assert quotient["bytes"] == 64.0
    assert quotient["intensityFlopsPerByte"] is None
    # module-level hooks swallow even structurally hostile input
    roofline.record_cost("A", "open", object())
    roofline.record_wall("A", "open", "not-a-float")


def test_roofline_report_and_gauges_against_the_table_peak(monkeypatch):
    """Utilization is a share of the device_kind table's peak — here a
    stand-in kind with a round number, so the arithmetic is visible."""
    monkeypatch.setitem(roofline.DEVICE_PEAKS, "cpu",
                        {"bf16_flops": 1e9})
    roofline.record_cost(
        "FibAir", "commit", {"flops": 2.0e9, "bytes accessed": 1.0e6})
    roofline.record_wall("FibAir", "commit", 2.0)
    rep = roofline.ROOFLINE.report()
    assert rep["peakFlopsEstimate"] == 1e9
    assert rep["peakSource"] == "device_kind table"
    (k,) = [k for k in rep["kernels"] if k["air"] == "FibAir"]
    assert k["achievedFlopsPerSec"] == pytest.approx(1.0e9)
    assert k["utilizationVsPeak"] == pytest.approx(1.0)
    assert k["intensityFlopsPerByte"] == pytest.approx(2000.0)
    # the live gauges were exported with full labels
    text = METRICS.render()
    assert ('prover_kernel_flops{air="FibAir",stage="commit"} '
            "2000000000.0") in text
    assert ('prover_kernel_achieved_flops_per_sec'
            '{air="FibAir",stage="commit"}') in text
    assert ('prover_kernel_utilization{air="FibAir",stage="commit"} '
            "1.0") in text


def test_peak_table_is_keyed_by_device_kind():
    """A v5e reports itself as "TPU v5 lite": 197 TFLOP/s bf16 (Google
    Cloud, "TPU v5e").  The platform name alone is not a key."""
    assert roofline.peak_flops_estimate("TPU v5 lite") == 197.0e12
    assert roofline.DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes_per_sec"] \
        == 819.0e9
    assert roofline.peak_flops_estimate("tpu") is None


@pytest.mark.parametrize("kind", ["cpu", "TPU v99", "quantum", ""])
def test_unknown_device_kind_has_no_peak_and_no_utilization(kind):
    """No default, no environment override: a kind that is not in the
    table yields no peak, and the report says "not measured"."""
    assert roofline.peak_flops_estimate(kind) is None
    assert roofline.peak_flops_estimate() is None    # this host: "cpu"
    roofline.record_cost("A", "commit", {"flops": 1.0e9})
    roofline.record_wall("A", "commit", 1.0)
    rep = roofline.ROOFLINE.report()
    assert rep["peakFlopsEstimate"] is None
    assert rep["peakSource"] == "not measured"
    (k,) = [k for k in rep["kernels"] if k["air"] == "A"]
    assert k["achievedFlopsPerSec"] == pytest.approx(1.0e9)
    assert k["utilizationVsPeak"] is None


# ---------------------------------------------------------------------------
# metrics exposition (golden lines)

def test_perf_metric_families_render_with_help_text():
    observe_import_stage("execute", 0.1)
    observe_import_stage("merkleize", 0.2)
    record_import_throughput(12.5)
    record_prover_throughput(3.0e6)
    record_proof_wall(7200.0)
    record_kernel_flops("Air", "deep", 1000.0, 500.0, 0.25)
    text = METRICS.render()
    assert "# HELP block_import_stage_seconds" in text
    assert '# TYPE block_import_stage_seconds histogram' in text
    # exposition shape, not exact counts: the process-global registry
    # may carry residue recorded between tests (thread teardown etc.)
    assert 'block_import_stage_seconds_bucket{stage="execute"' in text
    assert 'block_import_stage_seconds_count{stage="merkleize"}' in text
    assert "# HELP l1_import_mgas_per_sec" in text
    assert "l1_import_mgas_per_sec 12.5" in text
    assert "prover_trace_cells_per_sec 3000000.0" in text
    assert "proofs_per_hour 0.5" in text
    assert "# HELP prover_kernel_flops" in text
    assert 'prover_kernel_flops{air="Air",stage="deep"} 1000.0' in text


def test_record_proof_wall_guards_nonpositive():
    before = METRICS.gauges.get("proofs_per_hour")
    record_proof_wall(0.0)
    record_proof_wall(-5.0)
    assert METRICS.gauges.get("proofs_per_hour") == before


# ---------------------------------------------------------------------------
# import-path stage attribution (pipelined)

def test_pipelined_import_attributes_substages():
    from ethrex_tpu.blockchain.blockchain import Blockchain
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.storage.store import Store

    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }
    node = Node(Genesis.from_json(genesis))
    nonce = 0
    blocks = []
    for _ in range(3):
        for _ in range(4):
            node.submit_transaction(Transaction(
                tx_type=2, chain_id=1337, nonce=nonce,
                max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
                gas_limit=21_000, to=bytes([0x42]) * 20,
                value=100 + nonce).sign(secret))
            nonce += 1
        blocks.append(node.produce_block())

    store = Store()
    store.init_genesis(Genesis.from_json(genesis))
    chain = Blockchain(store, node.config)
    before = profiler.PROFILER.stage_totals("l1_import")
    chain.add_blocks_pipelined(blocks)
    after = profiler.PROFILER.stage_totals("l1_import")
    for stage in ("execute", "merkleize", "store_write"):
        assert after.get(stage, 0.0) > before.get(stage, 0.0), stage
    # the same legs flow into the labelled histogram
    hist = METRICS.histograms["block_import_stage_seconds"]
    seen = {dict(labels)["stage"] for labels in hist.series}
    assert {"execute", "merkleize", "store_write"} <= seen
    # and the pipelined wall updates the live throughput gauge
    assert METRICS.gauges.get("l1_import_mgas_per_sec", 0.0) > 0.0
    # the EVM split recorded under the evm component during execution
    evm_stages = profiler.PROFILER.stage_totals("evm")
    assert evm_stages.get("sig_recovery", 0.0) > 0.0
    assert evm_stages.get("opcode_loop", 0.0) > 0.0


# ---------------------------------------------------------------------------
# a real (tiny) prove populates roofline + profiler + throughput

def test_tiny_prove_populates_roofline_and_profiler():
    from ethrex_tpu.models import fibonacci as fib
    from ethrex_tpu.stark import prover, verifier
    from ethrex_tpu.stark.prover import StarkParams

    params = StarkParams(log_blowup=2, num_queries=16, log_final_size=4)
    # force an AOT rebuild so cost_analysis lands even when an earlier
    # test already compiled these phases (cost is recorded at build)
    prover._PHASE_CACHE.clear()
    air = fib.FibonacciAir()
    trace = fib.generate_trace(64)
    proof = prover.prove(air, trace, fib.public_inputs(trace), params)
    assert verifier.verify(air, proof, params)

    rep = roofline.ROOFLINE.report()
    kernels = {k["kernel"]: k for k in rep["kernels"]
               if k["air"] == "FibonacciAir"}
    assert set(kernels) >= {"commit", "quotient", "open", "deep"}
    with_cost = [k for k in kernels.values() if k["flops"]]
    assert with_cost, "no kernel captured a static cost"
    assert all(k["wallCount"] >= 1 for k in kernels.values())
    assert any(k["achievedFlopsPerSec"] for k in with_cost)

    comps = profiler.PROFILER.tree()["components"]
    assert {"merkle_commit", "quotient", "fri_fold", "query"} <= set(
        comps["stark"]["stages"])
    assert METRICS.gauges.get("prover_trace_cells_per_sec", 0.0) > 0.0
    # the full stack shows up on every surface: exposition...
    assert 'prover_kernel_flops{air="FibonacciAir"' in METRICS.render()
    # ...the flight-recorder snapshot...
    from ethrex_tpu.utils import snapshot
    bundle = snapshot.collect(None, reason="test")
    assert "stark" in bundle["perf"]["profiler"]["components"]
    assert bundle["perf"]["roofline"]["kernels"]


# ---------------------------------------------------------------------------
# RPC + health + monitor surfaces

def _l1_node():
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis

    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    return Node(Genesis.from_json({
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }))


def test_ethrex_perf_rpc_degrades_gracefully_on_l1_only_node():
    from ethrex_tpu.rpc.server import RpcServer

    server = RpcServer(_l1_node())
    resp = server.handle({"jsonrpc": "2.0", "id": 1,
                          "method": "ethrex_perf", "params": []})
    perf = resp["result"]
    assert perf["enabled"] is True
    # an L1-only node that never proved still answers with valid,
    # merely-empty sections — never an RPC error
    assert "components" in perf["profiler"]
    assert perf["roofline"]["kernels"] == []
    assert set(perf["throughput"]) == {
        "l1_import_mgas_per_sec", "prover_trace_cells_per_sec",
        "proofs_per_hour"}
    assert all(v is None for v in perf["throughput"].values())

    # once gauges exist they flow through verbatim
    record_import_throughput(42.0)
    perf = server.handle({"jsonrpc": "2.0", "id": 2,
                          "method": "ethrex_perf",
                          "params": []})["result"]
    assert perf["throughput"]["l1_import_mgas_per_sec"] == 42.0

    health = server.handle({"jsonrpc": "2.0", "id": 3,
                            "method": "ethrex_health",
                            "params": []})["result"]
    assert health["perf"]["kernelsProfiled"] == 0
    assert health["perf"]["maxUtilizationVsPeak"] is None
    assert isinstance(health["perf"]["componentsProfiled"], list)


def test_monitor_perf_panel_renders_and_degrades():
    from ethrex_tpu.utils.monitor import _perf_lines

    # no ethrex_perf (older node) and disabled both yield no panel
    assert _perf_lines({"perf": None}, 100) == []
    assert _perf_lines({"perf": {"enabled": False}}, 100) == []
    snap = {"perf": {
        "enabled": True,
        "throughput": {"l1_import_mgas_per_sec": 12.5,
                       "prover_trace_cells_per_sec": 3.1e6,
                       "proofs_per_hour": None},
        "profiler": {"components": {
            "stark": {"totalSeconds": 8.0, "stages": {
                "fri_fold": {"totalSeconds": 6.0, "share": 0.75},
                "quotient": {"totalSeconds": 2.0, "share": 0.25}}}}},
        "roofline": {"kernels": [
            {"air": "FibonacciAir", "kernel": "quotient",
             "flops": 3.9e7, "utilizationVsPeak": 0.37}]},
    }}
    lines = _perf_lines(snap, 100)
    text = "\n".join(lines)
    assert " performance" in text
    assert "12.5 Mgas/s" in text
    assert "stark" in text and "fri_fold 75%" in text
    assert "FibonacciAir" in text and "37.0%" in text


def test_throughput_floor_alerts_fire_below_not_above():
    from ethrex_tpu.utils.alerts import AlertEngine, AlertRule

    value = {"v": None}
    rule = AlertRule(
        name="floor:warn", severity="warn",
        signal=lambda eng, node: value["v"], threshold=0.1,
        for_count=2, resolve_count=1, below=True)
    eng = AlertEngine(rules=[rule])
    eng.evaluate()                      # None: a never-sampled gauge
    assert eng.active() == []           # must not alert (idle L1 node)
    value["v"] = 5.0
    eng.evaluate()
    eng.evaluate()
    assert eng.active() == []           # healthy throughput, above floor
    value["v"] = 0.05
    eng.evaluate()
    assert eng.active() == []           # first breach: pending only
    eng.evaluate()
    (alert,) = eng.active()
    assert alert["name"] == "floor:warn"
    assert alert["below"] is True
    value["v"] = 5.0
    eng.evaluate()
    assert eng.active() == []           # recovered


def test_default_rules_include_throughput_floors():
    from ethrex_tpu.utils.alerts import default_rules

    by_name = {r.name: r for r in default_rules(None)}
    assert by_name["l1_import_throughput_floor:warn"].below is True
    assert by_name["prover_throughput_floor:warn"].below is True
