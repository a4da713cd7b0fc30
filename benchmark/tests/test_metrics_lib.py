"""The declared readers on spans, counters and a table small enough to
check by hand; every metric file of the benchmark loads and names a
reader that exists."""

import json
import os

import pytest

import harness
import metrics_lib as ml

MS = 1e6


def ctx():
    spans = [
        {"name": "prover.assign", "start": 100.00, "seconds": 0.01,
         "traceId": "t1"},
        {"name": "backend.prove", "start": 100.05, "seconds": 8.0,
         "traceId": "t1"},
        {"name": "prove.trace_lde", "start": 100.10, "seconds": 0.5,
         "traceId": "t1", "attrs": {"width": 278, "n": 16384}},
        {"name": "prove.query", "start": 105.0, "seconds": 3.0,
         "traceId": "t1"},
        {"name": "prover.store_proof", "start": 108.10, "seconds": 0.02,
         "traceId": "t1"},
        {"name": "backend.prove", "start": 110.0, "seconds": 9.0,
         "traceId": "t2"},
        {"name": "prove.query", "start": 112.0, "seconds": 2.0,
         "traceId": "t2"},
        {"name": "bench.batch", "start": 100.0, "seconds": 24.0,
         "traceId": None},
    ]
    table = {
        "markers": {"bench.window_start": {"ns": 0.0, "wall": 100.0}},
        "devices": [{"name": "/device:TPU:0", "ops": [
            ["fusion.1", 150 * MS, 100 * MS]], "modules": [
            ["jit_phase_commit(1)", 150 * MS, 100 * MS],
            ["jit_phase_commit(2)", 5000 * MS, 50 * MS]]}]}
    return {"spans": spans, "batches": 2,
            "counters0": {"jax_cache.compiles": 140},
            "counters1": {"jax_cache.compiles": 143},
            "table": table, "offset_ns": 100.0 * 1e9,
            "t0_ns": 0.0, "t1_ns": 20_000 * MS,
            "memory": {"peak_bytes_in_use": 1_500_000_000},
            "peaks": {"hbm_bytes_per_s": 819e9}}


def test_span_readers_by_hand():
    c = ctx()
    assert ml.span_sum({"spans": ["prove.query"], "per": "batch"}, c) == \
        pytest.approx(2.5)
    assert ml.span_sum({"spans": ["bench.batch"],
                        "minus": ["backend.prove"]}, c) == \
        pytest.approx(24.0 - 17.0)
    assert ml.span_sum({"spans": ["nothing.here"]}, c) is None
    # self time: 8 - (0.5 + 3) and 9 - 2, over two batches
    assert ml.span_self({"span": "backend.prove", "per": "batch",
                         "children": ["prove.trace_lde", "prove.query"]},
                        c) == pytest.approx((4.5 + 7.0) / 2)
    # assign start 100.00 -> store_proof end 108.12, less prove 8.0
    assert ml.span_interval({"from": "prover.assign",
                             "to": "prover.store_proof",
                             "minus": ["backend.prove"]}, c) == \
        pytest.approx(0.12)
    assert ml.span_max({"spans": ["backend.prove"]}, c) == 9.0


def test_counter_memory_and_trace_readers_by_hand():
    c = ctx()
    assert ml.counter_delta({"counter": "jax_cache.compiles"}, c) == 3
    assert ml.counter_delta({"counter": "absent"}, c) is None
    assert ml.memory_stat({"stat": "peak_bytes_in_use", "scale": 1e-9},
                          c) == pytest.approx(1.5)
    # busy 0.1 s of a 20 s stretch
    assert ml.trace_busy({}, c) == pytest.approx(99.5)
    # only the execution that starts inside the width-278 span counts
    src = {"module": "phase_commit", "peak": "hbm_bytes_per_s",
           "within_span": {"name": "prove.trace_lde",
                           "attrs": {"width": 278}},
           "bytes_fn": "commit_phase_bytes",
           "bytes_args": {"width": 278, "log_n": 14, "log_blowup": 3}}
    least = 318_111_712 / 819e9
    assert ml.roofline(src, c) == pytest.approx(100 * least / 0.100)
    # no trace, nothing to read: the metric is left out, never a 0
    c["table"] = None
    assert ml.roofline(src, c) is None
    assert ml.trace_busy({}, c) is None


def test_every_metric_of_the_benchmark_has_a_reader():
    bench = harness.load_benchmark()
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        decl, read = ml.load_metric(
            os.path.join(harness.BENCH_DIR, "metrics"), m["name"])
        assert callable(read)
        assert decl["layer"] == m["layer"] and decl["unit"] == m["unit"]
        assert decl["moves"] == m["moves"] and m["moves"] in e2e
        assert set(m["workloads"]) <= cells
        assert decl["workloads"] == m["workloads"]


def test_every_cell_names_files_that_exist():
    bench = harness.load_benchmark()
    for w in bench["workloads"]:
        cell, config, mix, kind = harness.load_cell(bench, w["name"])
        assert kind.Traffic(mix, 1).mix is mix
        assert config["name"] == w["config"]
        assert config["window_metric"] in {
            m["name"] for m in harness.metrics_of(bench, "end_to_end",
                                                  w["name"])}
        harness.load_deployment(config["deployment"])
    for c in bench["configs"]:
        with open(os.path.join(harness.REPO_ROOT, c["file"])) as f:
            assert json.load(f)["source"] == c["source"]
