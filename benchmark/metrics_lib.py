"""Per-layer metric readers.  A metric is a file of its own,
`benchmark/metrics/<name>.json`, that states its layer, unit, the
end-to-end metric it should move, the cells it can be read in, and a
`source` declared as data; the kinds of source are the functions below.
A metric that needs code is `benchmark/metrics/<name>.py` with a
`read(ctx)` function, found by the same name.  A reader that finds
nothing to read returns None and the harness leaves the metric out of
the line — it never stands in a 0.

`ctx` (what a traced run hands every reader):
  spans      program and benchmark spans that began inside the window
  batches    batches the window finished
  counters0, counters1   program counters at the window's two ends
  table      the reduced profiler trace (trace_reduce.load_xplane) or None
  offset_ns  wall clock minus trace clock
  t0_ns, t1_ns           the window on the trace clock (a traced run
                         records all of its window)
  memory     jax device.memory_stats() after the window
  peaks      the device's row of peaks.json
"""

from __future__ import annotations

import importlib.util
import json
import os

import kernel_bytes
import trace_reduce


def _sum(spans: list, names) -> float:
    return sum(s["seconds"] for s in spans if s["name"] in names)


def _per(value: float, source: dict, batches: int) -> float | None:
    if source.get("per") == "batch":
        return value / batches if batches else None
    return value


def span_sum(source: dict, ctx: dict):
    """Seconds of the spans named in `spans`, less those in `minus`."""
    if not any(s["name"] in source["spans"] for s in ctx["spans"]):
        return None
    total = _sum(ctx["spans"], source["spans"]) \
        - _sum(ctx["spans"], source.get("minus", ()))
    return _per(total, source, ctx["batches"])


def span_self(source: dict, ctx: dict):
    """Self time of the spans called `span`: their seconds less the
    seconds of the `children` spans of the same trace that lie inside
    them."""
    parents = [s for s in ctx["spans"] if s["name"] == source["span"]]
    if not parents:
        return None
    total = 0.0
    for p in parents:
        p0, p1 = p["start"], p["start"] + p["seconds"]
        inside = sum(
            s["seconds"] for s in ctx["spans"]
            if s["name"] in source["children"]
            and s.get("traceId") == p.get("traceId")
            and s["start"] >= p0 - 1e-3
            and s["start"] + s["seconds"] <= p1 + 1e-3)
        total += p["seconds"] - inside
    return _per(total, source, ctx["batches"])


def span_interval(source: dict, ctx: dict):
    """Per trace, from the first `from` span's start to the last `to`
    span's end, less the `minus` spans; summed over traces."""
    by_trace: dict = {}
    for s in ctx["spans"]:
        by_trace.setdefault(s.get("traceId"), []).append(s)
    total, found = 0.0, False
    for spans in by_trace.values():
        a = [s["start"] for s in spans if s["name"] == source["from"]]
        b = [s["start"] + s["seconds"] for s in spans
             if s["name"] == source["to"]]
        if not a or not b:
            continue
        found = True
        total += max(b) - min(a) - _sum(spans, source.get("minus", ()))
    return _per(total, source, ctx["batches"]) if found else None


def span_max(source: dict, ctx: dict):
    got = [s["seconds"] for s in ctx["spans"]
           if s["name"] in source["spans"]]
    return max(got) if got else None


def counter_delta(source: dict, ctx: dict):
    name = source["counter"]
    if name not in ctx["counters0"] or name not in ctx["counters1"]:
        return None
    return ctx["counters1"][name] - ctx["counters0"][name]


def memory_stat(source: dict, ctx: dict):
    value = (ctx.get("memory") or {}).get(source["stat"])
    if value is None:
        return None
    return value * float(source.get("scale", 1.0))


def trace_busy(source: dict, ctx: dict):
    """The device's idle share of the traced window, in percent:
    100 * (1 - busy / window)."""
    if ctx.get("table") is None:
        return None
    busy = trace_reduce.busy_seconds(ctx["table"], ctx["t0_ns"],
                                     ctx["t1_ns"])
    if busy is None:
        return None
    window = (ctx["t1_ns"] - ctx["t0_ns"]) / 1e9
    return 100.0 * (1.0 - busy / window)


def _module_time(source: dict, ctx: dict):
    if ctx.get("table") is None:
        return None
    windows = None
    within = source.get("within_span")
    if within:
        windows = trace_reduce.span_windows_ns(
            ctx["spans"], ctx["offset_ns"], within["name"],
            within.get("attrs"))
        if not windows:
            return None
    seconds, runs = trace_reduce.module_seconds(
        ctx["table"], source["module"], windows)
    return (seconds, runs) if runs else None


def roofline(source: dict, ctx: dict):
    """The least time the chip could take for the program's executions
    (bytes from kernel_bytes over the peak named in `peak`) as a share of
    the device time the trace gives them."""
    got = _module_time(source, ctx)
    if got is None or not ctx.get("peaks"):
        return None
    seconds, runs = got
    fn = kernel_bytes.FUNCTIONS[source["bytes_fn"]]
    least = runs * fn(**source["bytes_args"]) / ctx["peaks"][source["peak"]]
    return 100.0 * least / seconds if seconds > 0 else None


KINDS = {f.__name__: f for f in (
    span_sum, span_self, span_interval, span_max, counter_delta,
    memory_stat, trace_busy, roofline)}


def load_metric(metrics_dir: str, name: str):
    """The reader of metric `name`: its declaration and a callable
    ctx -> value or None."""
    decl_path = os.path.join(metrics_dir, name + ".json")
    code_path = os.path.join(metrics_dir, name + ".py")
    decl = {}
    if os.path.exists(decl_path):
        with open(decl_path) as f:
            decl = json.load(f)
    if os.path.exists(code_path):
        spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_").replace("-", "_"),
            code_path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return decl, module.read
    if not decl:
        raise FileNotFoundError(
            f"per-layer metric {name!r} has no file under {metrics_dir}")
    source = decl["source"]
    kind = KINDS[source["kind"]]
    return decl, (lambda ctx: kind(source, ctx))
