"""The seven per-layer metrics that read the leaf spans, on a recorded
span list small enough to check by hand; and every entry of
BENCHMARK.json they add finds its file by name."""

import os

import pytest

import harness
import metrics_lib as ml

NEW = ("prover_idle_s", "trace_gen_s", "ckpt_s", "grind_s", "d2h_mb",
       "unspanned_s", "spans_lost")
METRICS_DIR = os.path.join(harness.BENCH_DIR, "metrics")


def _span(tid, sid, parent, name, start, seconds, **attrs):
    s = {"traceId": tid, "spanId": sid, "parentId": parent, "name": name,
         "start": start, "seconds": seconds, "status": "ok"}
    if attrs:
        s["attrs"] = attrs
    return s


def ctx():
    """Two batches.  t1 spans 100.0-110.0: leaves cover 100-101 (idle),
    101-101.5 (fetch), 102-104 and 103-105 (two leaf spans that overlap:
    102-105 once), 105-106 (grind), 109-110 (complete): 6.5 s of 10.
    t2 spans 110.0-114.0 with leaves over 110-111 and 112-114: 3 of 4."""
    spans = [
        _span("t1", "a0", None, "prover.assign", 101.1, 0.1),
        _span("t1", "i1", "a0", "prover.idle", 100.0, 1.0, polls=0),
        _span("t1", "f1", "a0", "prover.fetch_input", 101.0, 0.5,
              batch=1),
        _span("t1", "p1", "a0", "prover.prove", 101.6, 7.4),
        _span("t1", "g1", "p1", "prove.trace_gen", 102.0, 2.0,
              air="TransferAir", rows=16384, width=278),
        _span("t1", "c1", "p1", "prove.ckpt_copy", 103.0, 2.0,
              phase="commit", d2h_bytes=3_000_000),
        _span("t1", "r1", "p1", "fri.grind", 105.0, 1.0,
              tries=70000),
        _span("t1", "k1", "a0", "prover.ckpt_complete", 109.0, 1.0,
              disk_bytes=5_000_000),
        _span("t2", "a2", None, "prover.assign", 111.0, 0.1),
        _span("t2", "i2", "a2", "prover.idle", 110.0, 1.0, polls=2),
        _span("t2", "p2", "a2", "prover.prove", 111.5, 2.5),
        _span("t2", "s2", "p2", "ckpt.store", 112.0, 2.0, phase="fri",
              job="binding", disk_bytes=1_000_000),
        _span("t2", "l2", "p2", "fri.layer", 112.5, 0.25, log_n=17,
              d2h_bytes=1_000_000, d2h_s=0.2),
        {"name": "bench.batch", "start": 100.0, "seconds": 14.0,
         "traceId": None, "attrs": {}},
    ]
    return {"spans": spans, "batches": 2, "counters0": {}, "counters1": {},
            "table": None, "memory": {}, "peaks": None}


def read(name, c):
    return ml.load_metric(METRICS_DIR, name)[1](c)


def test_span_sums_by_hand():
    c = ctx()
    assert read("prover_idle_s", c) == pytest.approx(1.0)
    assert read("trace_gen_s", c) == pytest.approx(1.0)
    # copy 2.0 + complete 1.0 + store 2.0, over two batches
    assert read("ckpt_s", c) == pytest.approx(2.5)
    assert read("grind_s", c) == pytest.approx(0.5)


def test_d2h_mb_sums_the_attribute_and_ignores_spans_without_it():
    c = ctx()
    assert read("d2h_mb", c) == pytest.approx((3.0 + 1.0) / 2)
    c["spans"].append(_span("t2", "x", "p2", "query.canon", 113.0, 0.1,
                            d2h_bytes="many"))
    assert read("d2h_mb", c) == pytest.approx(2.0)


def test_unspanned_counts_an_overlap_of_leaf_spans_once(capsys):
    c = ctx()
    # t1: 10 - 6.5, t2: 4 - 3 (fri.layer lies inside ckpt.store)
    assert read("unspanned_s", c) == pytest.approx((3.5 + 1.0) / 2)
    out = capsys.readouterr().out
    assert "leaf spans 6.5000s + unspanned 3.5000s = batch extent " \
        "10.0000s" in out
    assert '"prove.ckpt_copy": {"n": 1, "s": 2.0, "d2h_bytes": 3000000}' \
        in out
    # a parent is no leaf: prover.prove covers t1's gaps and is not counted
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "unspanned_s", os.path.join(METRICS_DIR, "unspanned_s.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    t1 = [s for s in c["spans"] if s.get("traceId") == "t1"]
    assert {s["name"] for s in mod.leaves(t1)} == {
        "prover.idle", "prover.fetch_input", "prove.trace_gen",
        "prove.ckpt_copy", "fri.grind", "prover.ckpt_complete"}
    assert mod.union_seconds([(0, 2), (1, 3), (5, 6), (5.5, 5.75)]) == 4.0


def test_spans_lost_reads_the_tracers_counters(monkeypatch):
    from ethrex_tpu.utils.tracing import TRACER

    base = read("spans_lost", ctx())
    assert base == TRACER.trimmed + TRACER.dropped + TRACER.wire_truncated
    monkeypatch.setattr(TRACER, "trimmed", TRACER.trimmed + 3)
    monkeypatch.setattr(TRACER, "wire_truncated", TRACER.wire_truncated + 2)
    assert read("spans_lost", ctx()) == base + 5


@pytest.mark.parametrize("name", [n for n in NEW if n != "spans_lost"])
def test_nothing_to_read_gives_none(name):
    """A program without the spans (the parent of this PR) or a window
    without batches: the metric is left out, never a 0, never an error."""
    c = ctx()
    c["spans"] = [s for s in c["spans"] if s["name"] == "bench.batch"]
    assert read(name, c) is None
    c = ctx()
    c["batches"] = 0
    assert read(name, c) is None


def test_spans_lost_is_none_for_a_tracer_that_does_not_count(monkeypatch):
    from ethrex_tpu.utils import tracing

    class Old:
        dropped = 0

    monkeypatch.setattr(tracing, "TRACER", Old())
    assert read("spans_lost", ctx()) is None


@pytest.mark.parametrize("name", NEW)
def test_every_new_entry_finds_its_file(name):
    bench = harness.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry["moves"] == "batch_prove_s"
    assert entry["workloads"] == ["prove-transfer10"]
    assert entry["better"] == "lower"
    decl, reader = ml.load_metric(METRICS_DIR, name)
    assert callable(reader)
    assert (decl["layer"], decl["unit"]) == (entry["layer"], entry["unit"])
    cells = harness.metrics_of(bench, "per_layer", "prove-transfer10",
                               {"batch_prove_s", "setup_s"})
    assert name in {m["name"] for m in cells}
    # appended, not put in the middle: what was there keeps its place
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
