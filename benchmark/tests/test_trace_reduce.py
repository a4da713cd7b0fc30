"""The reduction from a profiler trace to numbers: busy union, module
time, idle gaps and their attribution, on a table small enough to check
by hand, and on the recorded trace under fixtures/."""

import json
import os

import pytest

import trace_reduce as tr

MS = 1e6   # ns


def table():
    # one device; two programs; ops overlap (a while encloses its body)
    return {
        "markers": {"bench.window_start": {"ns": 0.0, "wall": 100.0},
                    "bench.window_end": {"ns": 100 * MS, "wall": 100.1}},
        "devices": [{
            "name": "/device:TPU:0",
            "modules": [["jit_phase_commit(7)", 10 * MS, 20 * MS],
                        ["jit_fold(9)", 50 * MS, 10 * MS],
                        ["jit_phase_commit(7)", 70 * MS, 20 * MS]],
            "ops": [["while.1", 10 * MS, 20 * MS],
                    ["fusion.2", 12 * MS, 5 * MS],
                    ["fusion.3", 20 * MS, 10 * MS],
                    ["fusion.4", 50 * MS, 10 * MS],
                    ["fusion.2", 70 * MS, 20 * MS]],
        }]}


def test_merge_and_clip():
    assert tr.merge([[5, 7], [1, 3], [2, 4], [7, 8]]) == [[1, 4], [5, 8]]
    assert tr.clip([[1, 4], [5, 8]], 3, 6) == [[3, 4], [5, 6]]


def test_busy_is_the_union_not_the_sum():
    # 10-30, 50-60, 70-90 -> 50 ms of 100; the sum of ops would be 65
    assert tr.busy_seconds(table(), 0, 100 * MS) == pytest.approx(0.050)
    # clipped to 20-80 ms: 10 + 10 + 10
    assert tr.busy_seconds(table(), 20 * MS, 80 * MS) == \
        pytest.approx(0.030)
    assert tr.busy_seconds({"devices": [], "markers": {}}, 0, 1) is None


def test_idle_gaps_are_the_complement():
    gaps = tr.idle_gaps(table(), 0, 100 * MS)
    assert gaps == [[0, 10 * MS], [30 * MS, 50 * MS], [60 * MS, 70 * MS],
                    [90 * MS, 100 * MS]]
    assert sum(b - a for a, b in gaps) / 1e9 == pytest.approx(0.050)


def test_module_seconds_by_name_and_inside_spans():
    assert tr.module_seconds(table(), "phase_commit") == \
        (pytest.approx(0.040), 2)
    assert tr.module_seconds(table(), "fold") == (pytest.approx(0.010), 1)
    # only executions that start inside a span on the trace clock
    spans = [{"name": "prove.trace_lde", "start": 100.065, "seconds": 0.010,
              "attrs": {"width": 278}},
             {"name": "prove.trace_lde", "start": 100.005, "seconds": 0.010,
              "attrs": {"width": 115}}]
    offset = tr.clock_offset_ns(table())
    assert offset == pytest.approx(100.0 * 1e9)
    wins = tr.span_windows_ns(spans, offset, "prove.trace_lde",
                              {"width": 278})
    assert len(wins) == 1 and wins[0][0] == pytest.approx(65 * MS)
    assert tr.module_seconds(table(), "phase_commit", wins) == \
        (pytest.approx(0.020), 1)


def test_top_ops_charge_an_enclosing_op_only_its_own_time():
    top = dict(tr.top_ops(table(), 0, 100 * MS))
    assert top["jit_phase_commit/fusion.2"] == pytest.approx(0.025)
    assert top["jit_phase_commit/fusion.3"] == pytest.approx(0.010)
    assert top["jit_phase_commit/while.1"] == pytest.approx(0.005)
    assert top["jit_fold/fusion.4"] == pytest.approx(0.010)
    assert sum(top.values()) == pytest.approx(0.050)   # == busy


def test_gaps_go_to_the_innermost_span_that_covers_them():
    spans = [{"name": "backend.prove", "start": 100.0, "seconds": 0.092},
             {"name": "prove.query", "start": 100.030, "seconds": 0.020},
             {"name": "other", "start": 100.2, "seconds": 0.5}]
    got = dict(tr.attribute_gaps(tr.idle_gaps(table(), 0, 100 * MS),
                                 spans, 100.0 * 1e9))
    assert got["prove.query"] == pytest.approx(0.020)
    assert got["backend.prove"] == pytest.approx(0.020)   # 0-10, 60-70
    assert got["(no span)"] == pytest.approx(0.010)       # 90-100


FIXTURE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "fixtures", "tpu_v5e_prove_trace.json")


def test_recorded_trace_reduces_to_its_recorded_numbers():
    """A stretch of a real `prove-transfer10` trace (TPU v5e, PR 26),
    cut to the table's form; the numbers beside it were worked out once
    from the same events with a plain loop (fixtures/README.txt)."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    t = fx["table"]
    t0 = t["markers"]["bench.window_start"]["ns"]
    t1 = t["markers"]["bench.window_end"]["ns"]
    busy = tr.busy_seconds(t, t0, t1)
    assert busy == pytest.approx(fx["expected"]["busy_s"], rel=1e-9)
    gaps = tr.idle_gaps(t, t0, t1)
    assert busy + sum(b - a for a, b in gaps) / 1e9 == \
        pytest.approx((t1 - t0) / 1e9, rel=1e-9)
    sec, runs = tr.module_seconds(t, "phase_commit")
    assert runs == fx["expected"]["phase_commit_runs"]
    assert sec == pytest.approx(fx["expected"]["phase_commit_s"], rel=1e-9)
    top = tr.top_ops(t, t0, t1, k=10**6)
    assert sum(s for _, s in top) == pytest.approx(busy, rel=1e-6)
