"""The harness: one cell, once.  Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its
own that this module finds by the name in BENCHMARK.json:

  configs/<config>.json          the deployment as run, its guarantees
  deployments/<deployment>.py    the choreography a configuration names
  traffic/<traffic>.json         the mix, parameters only
  traffic_kinds/<kind>.py        the generator and plain reference of the
                                 mix's `kind` (traffic.py finds it)
  metrics/<metric>.json | .py    a per-layer reader (metrics_lib.py)

so a later PR adds a cell, a configuration or a metric by adding files
and entries, and edits nothing that is here.
"""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import shutil
import time

import check
import metrics_lib
import trace_reduce
from common import BenchFailure, Spans, err, log
from traffic import load_mix

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(BENCH_DIR)


# ---------------------------------------------------------------------------
# finding things by name

def load_benchmark(path: str | None = None) -> dict:
    with open(path or os.path.join(REPO_ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def load_cell(bench: dict, workload: str, bench_dir: str = BENCH_DIR):
    """(cell, config, traffic mix, the mix's kind) of the workload
    called `workload`."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchFailure(f"BENCHMARK.json has no workload {workload!r} "
                           f"(it has {sorted(cells)})")
    cell = cells[workload]
    with open(os.path.join(bench_dir, "configs",
                           cell["config"] + ".json")) as f:
        config = json.load(f)
    mix, kind = load_mix(os.path.join(bench_dir, "traffic",
                                      cell["traffic"] + ".json"), bench_dir)
    return cell, config, mix, kind


def load_deployment(name: str, bench_dir: str = BENCH_DIR):
    path = os.path.join(bench_dir, "deployments", name + ".py")
    if not os.path.exists(path):
        path = os.path.join(BENCH_DIR, "deployments", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_deployment_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.Deployment


def metrics_of(bench: dict, group: str, workload: str,
               reported: set | None = None) -> list[dict]:
    """The metrics of `group` this cell reports: those that list it
    under `workloads`, and those with no list whose `moves` (or, end to
    end, whose own name) the cell reports."""
    out = []
    for m in bench[group]:
        cells = m.get("workloads")
        if cells is not None:
            if workload in cells:
                out.append(m)
        elif reported is None or m.get("moves", m["name"]) in reported:
            out.append(m)
    return out


# ---------------------------------------------------------------------------
# the chip

def find_chip(chips: int) -> dict:
    """The device as JAX reports it; BenchFailure unless it is an
    accelerator with at least `chips` chips.  From here on this process
    holds the chip."""
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu":
        raise BenchFailure(
            f"JAX found no TPU (default platform {device['platform']!r}); "
            "nothing was run, nothing is measured")
    if device["count"] < chips:
        raise BenchFailure(f"the cell needs {chips} chip(s), JAX reports "
                           f"{device['count']}")
    return device


def device_peaks(kind: str) -> dict | None:
    with open(os.path.join(BENCH_DIR, "peaks.json")) as f:
        return json.load(f)["devices"].get(kind)


def memory_stats() -> dict:
    import jax

    fullest: dict = {}
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats.get("peak_bytes_in_use", -1) > \
                fullest.get("peak_bytes_in_use", -1):
            fullest = stats
    return fullest


# ---------------------------------------------------------------------------
# counters and spans of the program

def _numbers(prefix: str, d: dict, out: dict) -> None:
    for k, v in d.items():
        if isinstance(v, bool):
            continue
        if isinstance(v, (int, float)):
            out[f"{prefix}.{k}"] = v
        elif isinstance(v, dict):
            _numbers(f"{prefix}.{k}", v, out)


def snapshot_counters(deployment) -> dict:
    from ethrex_tpu.prover import runtime_errors as rt
    from ethrex_tpu.utils import exec_cache, jax_cache

    out: dict = {}
    _numbers("jax_cache", dict(jax_cache.STATS), out)
    _numbers("exec_cache", exec_cache.runtime_stats(), out)
    _numbers("rt", rt.runtime_stats(), out)
    out.update(deployment.counters())
    try:
        with open("/proc/self/io") as f:
            for line in f:
                if line.startswith("write_bytes:"):
                    out["proc.write_bytes"] = int(line.split()[1])
    except OSError:
        pass
    return out


def program_spans(trace_ids: list) -> list[dict]:
    from ethrex_tpu.utils.tracing import TRACER

    spans = []
    for tid in trace_ids:
        rec = TRACER.get_trace(tid)
        if rec:
            spans.extend(s for s in rec["spans"] if isinstance(s, dict))
    return spans


def clear_stale_checkpoints() -> None:
    """A run starts as a prover that has nothing to resume: the
    program keys its phase checkpoints by batch number, not by the
    batch's content, so what a killed run left behind would be resumed
    by this run's batch of the same number (PERF.md, Open questions)."""
    from ethrex_tpu.prover import checkpoint

    shutil.rmtree(checkpoint.checkpoint_dir(), ignore_errors=True)


def print_setup_split(deployment, t_process: float, t_imports: float,
                      t_setup: float) -> None:
    from ethrex_tpu.utils import exec_cache, jax_cache

    split = {"imports_and_chip_s": round(t_imports - t_process, 3),
             **{k: round(v, 3) for k, v in deployment.setup_split.items()},
             "setup_s": round(t_setup - t_process, 3)}
    log(f"set-up split: {json.dumps(split)}")
    log(f"compile cache: dir {jax_cache.cache_dir()} "
        f"{json.dumps(jax_cache.STATS)}")
    log(f"executable store: {json.dumps(exec_cache.runtime_stats())}")
    try:
        from ethrex_tpu.utils.metrics import METRICS

        hist = (METRICS.snapshot().get("histograms") or {}).get(
            "prover_phase_compile_seconds") or {}
        rows = [{**row.get("labels", {}),
                 "count": row.get("count"), "sum": row.get("sum")}
                for row in hist.get("series", [])]
        log(f"phase programs (compile or deserialise seconds): "
            f"{json.dumps(rows)}")
    except Exception as exc:  # noqa: BLE001 — a log line, not a result
        log(f"phase program seconds not available: {exc}")


# ---------------------------------------------------------------------------
# the profiler

class Profiler:
    """`jax.profiler` around the window, with one marker at each end
    that carries the wall clock."""

    def __init__(self, run_dir: str):
        self.dir = os.path.join(run_dir, "trace")

    def start(self) -> None:
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._mark("bench.window_start")

    @staticmethod
    def _mark(name: str) -> None:
        import jax

        with jax.profiler.TraceAnnotation(name, wall=str(time.time())):
            time.sleep(0.001)

    def stop(self) -> dict:
        import glob

        import jax

        self._mark("bench.window_end")
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        t_stop = time.monotonic() - t_stop
        paths = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        if not paths:
            raise BenchFailure("the profiler wrote no trace")
        size = sum(os.path.getsize(p) for p in paths)
        t0 = time.monotonic()
        table = trace_reduce.load_xplane(paths[0])
        log(f"trace: {size} bytes, written in {t_stop:.1f}s, read in "
            f"{time.monotonic() - t0:.1f}s; "
            f"lines {json.dumps(table['lines'])}")
        shutil.rmtree(self.dir, ignore_errors=True)
        return table


def log_batches(in_window: list, wall0: float) -> None:
    """One earlier line per batch of the window: its span, and the
    stage spans of the same trace that began inside it."""
    for s in sorted(in_window, key=lambda s: s["start"]):
        if s["name"] not in ("backend.prove", "bench.batch"):
            continue
        inside: dict = {}
        for c in in_window:
            if c.get("traceId") == s.get("traceId") and c is not s \
                    and c["name"].startswith("prove.") \
                    and c["start"] >= s["start"] - 1e-3:
                inside[c["name"]] = round(
                    inside.get(c["name"], 0.0) + c["seconds"], 3)
        log(f"  span {s['name']} +{s['start'] - wall0:.3f}s "
            f"{s['seconds']:.3f}s {json.dumps(inside)}")


def add_trace_report(result: dict, ctx: dict) -> None:
    """What the driver reads from a traced run besides the metrics:
    device busy seconds, the traced window's length, and the
    breakdown."""
    table, t0, t1 = ctx["table"], ctx["t0_ns"], ctx["t1_ns"]
    result["device"]["busy_s"] = trace_reduce.busy_seconds(table, t0, t1)
    result["device"]["window_s"] = (t1 - t0) / 1e9
    result["breakdown"] = {
        "device_ops": trace_reduce.top_ops(table, t0, t1),
        "idle_gaps": trace_reduce.attribute_gaps(
            trace_reduce.idle_gaps(table, t0, t1), ctx["spans"],
            ctx["offset_ns"])}


# ---------------------------------------------------------------------------
# one run

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, bench_path: str | None = None,
             bench_dir: str = BENCH_DIR, prover: str | None = None,
             device: dict | None = None, controls: dict | None = None) -> dict:
    """Run one cell once and return the result object.  `prover` and
    `device` are for the tests (the `exec` prover standing in on a CPU),
    `controls` for the control sweep (faults planted in a copy of what
    the timed path produced, each judged beside the sound records); the
    command passes none of them."""
    bench = load_benchmark(bench_path)
    cell, config, mix, kind = load_cell(bench, workload, bench_dir)
    if device is None:
        device = find_chip(int(cell["chips"]))
    t_imports = time.monotonic()
    prover = prover or config["prover"]
    traffic = kind.Traffic(mix, seed)
    spans = Spans()
    run_dir = os.path.join(bench_dir, ".run", workload)
    deployment = load_deployment(config["deployment"], bench_dir)(
        config, traffic, spans, prover, run_dir)
    profiler = Profiler(run_dir) if trace else None
    if trace:
        seconds = min(seconds, float(mix.get("trace_seconds", seconds)))
    try:
        clear_stale_checkpoints()
        deployment.setup()
        t_setup = time.monotonic()
        print_setup_split(deployment, t_process, t_imports, t_setup)
        counters0 = snapshot_counters(deployment)
        if profiler:
            profiler.start()
        deployment.run_window(seconds)
        table = profiler.stop() if profiler else None
        counters1 = snapshot_counters(deployment)
        memory = memory_stats()
        deployment.collect()
        records = sorted(deployment.records.values(),
                         key=lambda r: r.number)
        exec_proofs = 0 if prover == "exec" else sum(
            1 for r in records
            if deployment.rollup.get_proof(r.number, "exec") is not None)
        all_spans = program_spans(deployment.trace_ids()) + spans.spans
    finally:
        deployment.close()
    window_s = deployment.window_t1 - deployment.window_t0
    batches = sum(1 for r in records if r.in_window and r.proof)
    log(f"counters over the window: " + json.dumps(
        {k: counters1[k] - counters0.get(k, 0) for k in counters1
         if counters1[k] != counters0.get(k, 0)}))

    # -- correct: after the window, the peak read, outside every timer
    t_check = time.monotonic()
    from ethrex_tpu.prover.backend import get_backend

    memo = {} if controls else None

    def judge(recs):
        return check.judge(recs, traffic, kind, config,
                           prover, get_backend(prover), counters1,
                           exec_proofs, memo)

    numbers, failed = judge(records)
    log(f"check: {time.monotonic() - t_check:.2f}s over "
        f"{sum(1 for r in records if r.in_window)} batch(es)")
    control_results = {}
    for name, plant in (controls or {}).items():
        broken = copy.deepcopy(records)
        plant(broken, check.replayed(
            [r for r in broken if r.in_window], traffic.seed, config))
        t_control = time.monotonic()
        got, _ = judge(broken)
        control_results[name] = {
            "correct": check.verdict(got),
            "over": {k: v for k, v in got.items() if v[0] > v[1]}}
        log(f"control {name}: {time.monotonic() - t_control:.2f}s "
            f"{json.dumps(control_results[name])}")

    # -- metrics
    e2e = {config["window_metric"]: window_s / batches if batches else None,
           "setup_s": t_setup - t_process}
    result: dict = {
        "correct": check.verdict(numbers),
        "attempted": sum(1 for r in records if r.in_window),
        "failed": failed,
        "metrics": {},
        "device": {**device,
                   "memory_peak_bytes": memory.get("peak_bytes_in_use")},
    }
    wall0 = deployment.window_wall0
    in_window = [s for s in all_spans if s.get("start", 0) >= wall0 - 1e-3]
    log_batches(in_window, wall0)
    warm: dict = {}     # where the warm-up batch's share of set-up went
    for s in all_spans:
        if s.get("start", 0) < wall0 - 1e-3:
            warm[s["name"]] = round(
                warm.get(s["name"], 0.0) + (s.get("seconds") or 0.0), 3)
    log(f"set-up spans (seconds by name): {json.dumps(warm)}")
    if not trace:
        for m in metrics_of(bench, "end_to_end", workload):
            if e2e.get(m["name"]) is not None:
                result["metrics"][m["name"]] = {
                    "value": e2e[m["name"]], "unit": m["unit"]}
    else:
        ctx = {"spans": in_window, "batches": batches,
               "counters0": counters0, "counters1": counters1,
               "table": table, "memory": memory,
               "peaks": device_peaks(device["kind"]), "config": config,
               "offset_ns": trace_reduce.clock_offset_ns(table),
               "t0_ns": table["markers"]["bench.window_start"]["ns"],
               "t1_ns": table["markers"]["bench.window_end"]["ns"]}
        reported = {m["name"] for m in
                    metrics_of(bench, "end_to_end", workload)}
        for m in metrics_of(bench, "per_layer", workload, reported):
            _, read = metrics_lib.load_metric(
                os.path.join(bench_dir, "metrics"), m["name"])
            value = read(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        add_trace_report(result, ctx)
        log(f"end-to-end in this traced run (not reported): "
            f"{json.dumps(e2e)}")
    if control_results:
        result["controls"] = control_results
    result["compared"] = numbers
    for name, (value, limit) in numbers.items():
        err(f"compared {name} {value} limit {limit}")
    return result
