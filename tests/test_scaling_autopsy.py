"""Mesh-scaling autopsy battery (PR 18, docs/PERFORMANCE.md "Reading
the scaling autopsy"): HLO collective accounting (perf/hlo_introspect),
device-occupancy timelines (perf/occupancy + the parallel prover
wiring), and every surface the autopsy flows through — gauges, ethrex_perf/ethrex_health stubs, the monitor
panel, the Perfetto device-lane view, and the occupancy/collective
alert pair.

The degradation drills matter as much as the goldens: every hook rides
the AOT-compile and prove hot paths, so a jaxlib that reshapes
memory_analysis() or an opaque executable must degrade to partial rows,
never a failed prove (never-raise contract)."""

import pytest

from ethrex_tpu.perf import hlo_introspect, occupancy
from ethrex_tpu.perf.roofline import _parse_cost
from ethrex_tpu.utils.metrics import METRICS


@pytest.fixture(autouse=True)
def _fresh_registries():
    hlo_introspect.REGISTRY.reset()
    occupancy.REGISTRY.reset()
    yield
    hlo_introspect.REGISTRY.reset()
    occupancy.REGISTRY.reset()


# ---------------------------------------------------------------------------
# roofline._parse_cost: the one shape jax 0.9 returns


@pytest.mark.parametrize("cost, want", [
    ({"flops": 2.0e6, "bytes accessed": 4.0e3},
     {"flops": 2.0e6, "bytes": 4.0e3}),
    ({"flops": 1.0}, {"flops": 1.0, "bytes": None}),
    ({"bytes accessed": 8.0}, {"flops": None, "bytes": 8.0}),
])
def test_parse_cost_reads_the_dict(cost, want):
    assert _parse_cost(cost) == want


@pytest.mark.parametrize("cost", [
    None, {}, [], [{"flops": 1.0}], 3, "junk", object()])
def test_parse_cost_degrades_to_partial_rows(cost):
    # absent fields and foreign shapes -> None, not zero and not an
    # exception (the recording hooks ride the prove path)
    assert _parse_cost(cost) == {"flops": None, "bytes": None}


@pytest.mark.parametrize("bad", [-5, True, "7", None, float("-inf")])
def test_parse_cost_rejects_non_numeric_and_negative_values(bad):
    assert _parse_cost({"flops": bad, "bytes accessed": 3}) == \
        {"flops": None, "bytes": 3.0}


# ---------------------------------------------------------------------------
# hlo_introspect: memory_analysis shapes


class _AttrMem:
    argument_size_in_bytes = 1000
    output_size_in_bytes = 200
    temp_size_in_bytes = 30
    alias_size_in_bytes = 4
    generated_code_size_in_bytes = 99


def test_parse_memory_analysis_tolerates_every_shape():
    full = hlo_introspect.parse_memory_analysis(_AttrMem())
    assert full["argBytes"] == 1000.0
    assert full["peakBytes"] == 1234.0
    assert full["codeBytes"] == 99.0

    as_dict = hlo_introspect.parse_memory_analysis(
        {"argument_size_in_bytes": 10, "temp_size_in_bytes": 5})
    assert as_dict["argBytes"] == 10.0
    assert as_dict["outputBytes"] is None
    assert as_dict["peakBytes"] == 15.0

    listed = hlo_introspect.parse_memory_analysis([_AttrMem(), _AttrMem()])
    assert listed["peakBytes"] == 2468.0

    empty = hlo_introspect.parse_memory_analysis(None)
    assert empty["peakBytes"] is None
    assert hlo_introspect.parse_memory_analysis(object())["peakBytes"] \
        is None
    assert hlo_introspect.parse_memory_analysis([None, "x"])["peakBytes"] \
        is None


# ---------------------------------------------------------------------------
# hlo_introspect: collective counting golden

_HLO = """\
HloModule prove_step, entry_computation_layout={...}

ENTRY %main (p0: u32[64,512]) -> u32[64,512] {
  %p0 = u32[64,512]{1,0} parameter(0)
  %ag-start = u32[64,4096]{1,0} all-gather-start(%p0), dimensions={1}
  %ag-done = u32[64,4096]{1,0} all-gather-done(%ag-start)
  %ar = u32[64,512]{1,0} all-reduce(%p0), to_apply=%add
  %cp = u32[64,512]{1,0} collective-permute(%ar), source_target_pairs={{0,1}}
  %copy.1 = u32[64,512]{1,0} copy(%cp)
  %small = bf16[8]{0} all-reduce(%junk), to_apply=%add
  ROOT %out = u32[64,512]{1,0} copy(%copy.1)
}
"""


def test_count_collectives_golden():
    ops = hlo_introspect.count_collectives(_HLO)
    # async pair counts ONCE, on the -start leg
    assert ops["all-gather"]["count"] == 1
    assert ops["all-gather"]["bytes"] == 64 * 4096 * 4
    assert ops["all-reduce"]["count"] == 2
    assert ops["all-reduce"]["bytes"] == 64 * 512 * 4 + 8 * 2
    assert ops["collective-permute"]["count"] == 1
    assert ops["copy"]["count"] == 2
    assert ops["reduce-scatter"]["count"] == 0
    # non-string input degrades to a zero table
    zeros = hlo_introspect.count_collectives(None)
    assert all(v == {"count": 0, "bytes": 0} for v in zeros.values())


def test_introspect_rolls_up_cross_device_bytes():
    class Fake:
        def as_text(self):
            return _HLO

        def memory_analysis(self):
            return _AttrMem()

    row = hlo_introspect.introspect(Fake())
    assert row["collectiveOps"] == 4           # copies NOT included
    assert row["copyOps"] == 2
    expected = (64 * 4096 * 4) + (64 * 512 * 4 + 8 * 2) + (64 * 512 * 4)
    assert row["crossDeviceBytes"] == expected
    assert row["memory"]["peakBytes"] == 1234.0


def test_registry_records_real_compiled_program():
    """End-to-end on a real jax AOT executable: whatever this jaxlib
    returns for as_text/memory_analysis must land as a row, not an
    exception (the stark _aot_phases hook path)."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    compiled = jax.jit(lambda x: x * 2 + 1).lower(
        jnp.arange(16, dtype=jnp.uint32)).compile()
    hlo_introspect.record("TestAir", "commit", compiled, devices=1)
    rep = hlo_introspect.REGISTRY.report()
    rows = {(k["air"], k["kernel"]): k for k in rep["kernels"]}
    assert ("TestAir", "commit") in rows
    assert rows[("TestAir", "commit")]["devices"] == 1
    # gauges rendered with help text
    text = METRICS.render()
    assert "# HELP prover_kernel_collective_ops" in text


def test_record_never_raises_on_opaque_executables():
    hlo_introspect.record("A", "k", object(), devices=3)
    hlo_introspect.record("A", "k2", None, devices="garbage")
    rep = hlo_introspect.REGISTRY.report()
    rows = {(k["air"], k["kernel"]) for k in rep["kernels"]}
    assert ("A", "k") in rows  # zero-row, but present


def test_collective_share_gauge_and_ici_override(monkeypatch):
    class Fake:
        def as_text(self):
            return _HLO

        def memory_analysis(self):
            return None

    monkeypatch.setenv("ETHREX_ICI_GBPS", "1e-3")  # 1 MB/s: huge share
    hlo_introspect.record("ShareAir", "quotient", Fake(), devices=8)
    hlo_introspect.record_collective_share("ShareAir", "quotient", 0.5)
    with METRICS.lock:
        share = METRICS.gauges.get("prover_collective_wall_share")
    assert share == 1.0  # clamped
    # unknown kernel / zero wall are silent no-ops
    hlo_introspect.record_collective_share("NoSuch", "open", 1.0)
    hlo_introspect.record_collective_share("ShareAir", "quotient", 0.0)


# ---------------------------------------------------------------------------
# occupancy math units (satellite)


def test_merge_intervals_collapses_overlap():
    merged = occupancy.merge_intervals(
        [(3.0, 4.0), (0.0, 1.0), (0.5, 2.0), (1.9, 2.5), ("x", 1), (5, 5)])
    assert merged == [(0.0, 2.5), (3.0, 4.0)]
    assert occupancy.busy_seconds([(0, 1), (0.5, 2)]) == pytest.approx(2.0)
    assert occupancy.merge_intervals(None) == []


def test_occupancy_two_lane_prove():
    lanes = {
        "0": {"intervals": [(0.0, 4.0), (5.0, 8.0)], "devices": 2},
        "1": {"intervals": [(0.0, 3.0)], "devices": 2},
    }
    rep = occupancy.compute(lanes, devices=4)
    assert rep["wallSeconds"] == pytest.approx(8.0)
    # busy-device-seconds: lane0 7s*2dev + lane1 3s*2dev = 20
    assert rep["busyDeviceSeconds"] == pytest.approx(20.0)
    assert rep["occupancy"] == pytest.approx(20.0 / 32.0)
    # the (4, 5) bubble is the only span with no lane busy
    assert rep["idleGapSeconds"] == pytest.approx(1.0)
    assert rep["idleGapCount"] == 1
    # per-lane busy+idle sums to the measured wall within 5%
    # (exactly, by construction)
    for lane in rep["lanes"]:
        total = lane["busySeconds"] + lane["idleSeconds"]
        assert abs(total - rep["wallSeconds"]) \
            <= 0.05 * max(rep["wallSeconds"], 1e-9)


def test_occupancy_serial_fallback_is_one_over_ndev():
    # a serial prove on an 8-device mesh: one weight-1 lane busy the
    # whole wall -> occupancy exactly 1/8
    rep = occupancy.compute({"0": [(0.0, 10.0)]}, devices=8)
    assert rep["occupancy"] == pytest.approx(1.0 / 8.0)
    # and a fully-busy single-device prove is 1.0, clamped never above
    rep1 = occupancy.compute(
        {"0": {"intervals": [(0.0, 10.0)], "devices": 1}}, devices=1)
    assert rep1["occupancy"] == pytest.approx(1.0)


def test_occupancy_empty_and_window():
    rep = occupancy.compute({}, devices=4)
    assert rep["occupancy"] == 0.0 and rep["wallSeconds"] == 0.0
    # an explicit window clips intervals outside it
    rep = occupancy.compute({"0": [(0.0, 10.0)]}, devices=1,
                            window=(2.0, 6.0))
    assert rep["wallSeconds"] == pytest.approx(4.0)
    assert rep["occupancy"] == pytest.approx(1.0)


def test_record_prove_feeds_registry_and_gauges():
    occupancy.record_prove({"0": [(0.0, 1.0)], "1": [(0.5, 2.0)]},
                           devices=2)
    rep = occupancy.REGISTRY.report()
    assert rep["provesRecorded"] == 1
    assert rep["lastProve"]["devices"] == 2
    assert rep["worstOccupancy"] == rep["lastProve"]["occupancy"]
    with METRICS.lock:
        assert METRICS.gauges.get("prover_device_occupancy") \
            == pytest.approx(rep["lastProve"]["occupancy"])
    # garbage lanes are swallowed (never-raise hook)
    occupancy.record_prove(object(), devices=None)


def test_run_proof_jobs_serial_path_records_occupancy():
    """The real wiring: mesh-less `_run_proof_jobs` runs jobs serially
    and must still land a single-lane occupancy record whose busy+idle
    matches the wall."""
    from ethrex_tpu.prover.tpu_backend import _run_proof_jobs

    def mk(tag):
        return lambda job_mesh: {"proof": tag}

    out = _run_proof_jobs(
        [("stateAir", "state", mk("s")),
         ("vm0", "vm_circuits", mk("v0")),
         ("vm1", "vm_circuits", mk("v1"))], None)
    assert out == {"stateAir": {"proof": "s"}, "vm0": {"proof": "v0"},
                   "vm1": {"proof": "v1"}}
    rep = occupancy.REGISTRY.report()
    assert rep["provesRecorded"] == 1
    last = rep["lastProve"]
    assert last["devices"] == 1
    (lane,) = last["lanes"]
    assert abs(lane["busySeconds"] + lane["idleSeconds"]
               - last["wallSeconds"]) \
        <= 0.05 * max(last["wallSeconds"], 1e-9)


# ---------------------------------------------------------------------------
# surfaces: RPC stubs, monitor panel, Perfetto lanes, alerts, snapshot


def _l1_node():
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis

    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(0xA11CE))
    return Node(Genesis.from_json({
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }))


def test_perf_rpc_collectives_and_occupancy_stubs_on_l1_node():
    from ethrex_tpu.rpc.server import RpcServer

    server = RpcServer(_l1_node())
    perf = server.handle({"jsonrpc": "2.0", "id": 1,
                          "method": "ethrex_perf", "params": []})["result"]
    # pre-autopsy / L1-only: well-formed empty stubs, never missing keys
    assert perf["collectives"]["kernels"] == []
    assert perf["collectives"]["iciGbpsAssumed"] > 0
    assert perf["occupancy"] == {"provesRecorded": 0, "lastProve": None,
                                 "worstOccupancy": None}
    health = server.handle({"jsonrpc": "2.0", "id": 2,
                            "method": "ethrex_health",
                            "params": []})["result"]
    assert health["perf"]["kernelsIntrospected"] == 0
    assert health["perf"]["collectiveOpsTotal"] == 0
    assert health["perf"]["deviceOccupancy"] is None


def test_perf_rpc_carries_autopsy_rows_once_populated():
    from ethrex_tpu.rpc.server import RpcServer

    class Fake:
        def as_text(self):
            return _HLO

        def memory_analysis(self):
            return _AttrMem()

    hlo_introspect.record("FibonacciAir", "quotient", Fake(), devices=8)
    occupancy.record_prove({"0": [(0.0, 1.0)]}, devices=8)
    server = RpcServer(_l1_node())
    perf = server.handle({"jsonrpc": "2.0", "id": 1,
                          "method": "ethrex_perf", "params": []})["result"]
    (row,) = perf["collectives"]["kernels"]
    assert row["air"] == "FibonacciAir" and row["devices"] == 8
    assert row["collectiveOps"] == 4
    assert perf["occupancy"]["provesRecorded"] == 1
    health = server.handle({"jsonrpc": "2.0", "id": 2,
                            "method": "ethrex_health",
                            "params": []})["result"]
    assert health["perf"]["kernelsIntrospected"] == 1
    assert health["perf"]["deviceOccupancy"] \
        == pytest.approx(1.0 / 8.0)


def test_monitor_panel_renders_autopsy_and_degrades():
    from ethrex_tpu.utils.monitor import _perf_lines

    snap = {"perf": {
        "enabled": True,
        "throughput": {"l1_import_mgas_per_sec": 12.5,
                       "prover_trace_cells_per_sec": 3.1e6,
                       "proofs_per_hour": None},
        "collectives": {"kernels": [
            {"air": "FibonacciAir", "kernel": "quotient", "devices": 8,
             "collectiveOps": 9, "crossDeviceBytes": 1.75e9,
             "copyOps": 3}]},
        "occupancy": {"provesRecorded": 2, "lastProve": {
            "occupancy": 0.41, "devices": 8, "idleGapSeconds": 1.25,
            "lanes": [{"lane": "0", "devices": 4, "busySeconds": 3.0,
                       "idleSeconds": 1.0},
                      {"lane": "1", "devices": 4, "busySeconds": 2.0,
                       "idleSeconds": 2.0}]}},
    }}
    text = "\n".join(_perf_lines(snap, 100))
    assert "collectives" in text
    assert "quotient" in text and "1.75e+09" in text
    assert "occupancy   41% of 8 devices" in text
    assert "lane 0" in text and "busy" in text
    # degraded sections (error stubs / None / wrong types) never raise
    for coll, occ in (({"error": "x"}, {"error": "y"}),
                      (None, None), ([], "junk"),
                      ({"kernels": "?"}, {"lastProve": "?"})):
        lines = _perf_lines({"perf": {"enabled": True,
                                      "collectives": coll,
                                      "occupancy": occ}}, 100)
        assert isinstance(lines, list)


def test_trace_events_render_device_lanes():
    from ethrex_tpu.utils.tracing import to_trace_events

    trace = {"traceId": "ab" * 8, "spans": [
        {"spanId": "s1", "name": "prove", "start": 0.0, "seconds": 4.0},
        {"spanId": "s2", "parentId": "s1", "name": "prove.vm0",
         "start": 0.5, "seconds": 1.5,
         "attrs": {"deviceLane": 0, "laneDevices": 2}},
        {"spanId": "s3", "parentId": "s1", "name": "prove.vm1",
         "start": 0.5, "seconds": 2.0,
         "attrs": {"deviceLane": 1, "laneDevices": 2}},
    ]}
    out = to_trace_events(trace)
    xs = {e["name"]: e for e in out["traceEvents"] if e["ph"] == "X"}
    assert xs["prove"]["tid"] == 1           # un-laned spans stay put
    assert xs["prove.vm0"]["tid"] == 2
    assert xs["prove.vm1"]["tid"] == 3
    lane_names = {e["args"]["name"] for e in out["traceEvents"]
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    assert "device-lane 0 (2 dev)" in lane_names
    assert "device-lane 1 (2 dev)" in lane_names
    # malformed lane attrs degrade to the default track, never raise
    bad = to_trace_events({"traceId": "cd" * 8, "spans": [
        {"spanId": "b", "name": "x", "start": 0.0, "seconds": 1.0,
         "attrs": {"deviceLane": "zero"}}]})
    (ev,) = [e for e in bad["traceEvents"] if e["ph"] == "X"]
    assert ev["tid"] == 1


def test_default_rules_include_autopsy_pair():
    from ethrex_tpu.utils.alerts import default_rules

    by_name = {r.name: r for r in default_rules(None)}
    occ_rule = by_name["prover_occupancy_floor:warn"]
    assert occ_rule.below is True and occ_rule.severity == "warn"
    assert occ_rule.threshold == pytest.approx(0.5)
    share_rule = by_name["prover_collective_share:warn"]
    assert share_rule.below is False and share_rule.severity == "warn"
    assert share_rule.threshold == pytest.approx(0.4)


def test_snapshot_perf_section_carries_autopsy():
    from ethrex_tpu.utils import snapshot

    occupancy.record_prove({"0": [(0.0, 1.0)]}, devices=2)
    bundle = snapshot.collect(None, reason="test")
    perf = bundle["perf"]
    assert "collectives" in perf and "occupancy" in perf
    assert perf["occupancy"]["provesRecorded"] == 1
