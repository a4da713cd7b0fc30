"""From a profiler trace to numbers.  `load_xplane` turns the
`.xplane.pb` that `jax.profiler` writes into a small table (plain lists,
JSON-serialisable, which is what `fixtures/` keeps); every reduction
below works on that table alone, so the tests check them on a recorded
table without a chip.

The table:
  {"markers": {name: {"ns": trace clock, "wall": time.time() seconds}},
   "devices": [{"name": "/device:TPU:0",
                "ops":     [[name, start_ns, duration_ns], ...],
                "modules": [[name, start_ns, duration_ns], ...]}]}

`ops` are the device's "XLA Ops" line (one event per HLO op executed,
control-flow ops enclosing their bodies), `modules` its "XLA Modules"
line (one event per program execution, named `<module>(<program id>)`).
Markers are `jax.profiler.TraceAnnotation`s the harness writes with the
wall clock as a stat, so program spans (wall clock) can be laid over
device events (trace clock).
"""

from __future__ import annotations

import bisect
import re

MARKER_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def short_name(name: str) -> str:
    """On the TPU an op event is named by its whole HLO instruction
    ("%fusion.33 = u32[131072,8,4]{...} fusion(...), kind=kCustom, ...");
    the instruction's own name is enough to find it again."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    table: dict = {"markers": {}, "devices": [], "lines": {}}
    for plane in data.planes:
        is_device = plane.name.startswith("/device:")
        dev = {"name": plane.name, "ops": [], "modules": []}
        for line in plane.lines:
            count = 0
            want = None
            if is_device and line.name == OPS_LINE:
                want = dev["ops"]
            elif is_device and line.name == MODULES_LINE:
                want = dev["modules"]
            for ev in line.events:
                count += 1
                if want is not None:
                    want.append([short_name(ev.name), float(ev.start_ns),
                                 float(ev.duration_ns)])
                elif not is_device and ev.name.startswith(MARKER_PREFIX):
                    stats = dict(ev.stats)
                    table["markers"][ev.name] = {
                        "ns": float(ev.start_ns),
                        "wall": float(stats.get("wall", 0.0))}
            table["lines"][f"{plane.name}|{line.name}"] = count
        if is_device and (dev["ops"] or dev["modules"]):
            table["devices"].append(dev)
    return table


# ---------------------------------------------------------------------------
# intervals

def merge(intervals: list) -> list:
    """Union of [start, end) intervals as a sorted list of disjoint
    ones."""
    out: list = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def clip(intervals: list, t0: float, t1: float) -> list:
    return [[max(a, t0), min(b, t1)] for a, b in intervals
            if min(b, t1) > max(a, t0)]


def _device_intervals(dev: dict) -> list:
    events = dev["ops"] or dev["modules"]
    return [[s, s + d] for _, s, d in events]


def busy_seconds(table: dict, t0_ns: float, t1_ns: float) -> float | None:
    """Seconds in [t0, t1) during which an operation ran on the device:
    the union of the device's op intervals, averaged over the devices
    that ran anything.  None where no device event was recorded."""
    per_device = []
    for dev in table["devices"]:
        merged = clip(merge(_device_intervals(dev)), t0_ns, t1_ns)
        per_device.append(sum(b - a for a, b in merged) / 1e9)
    if not per_device:
        return None
    return sum(per_device) / len(per_device)


def idle_gaps(table: dict, t0_ns: float, t1_ns: float) -> list:
    """[start_ns, end_ns] of every stretch of [t0, t1) in which nothing
    ran on the first device."""
    if not table["devices"]:
        return []
    merged = clip(merge(_device_intervals(table["devices"][0])),
                  t0_ns, t1_ns)
    gaps, at = [], t0_ns
    for a, b in merged:
        if a > at:
            gaps.append([at, a])
        at = max(at, b)
    if t1_ns > at:
        gaps.append([at, t1_ns])
    return gaps


# ---------------------------------------------------------------------------
# programs and ops

def module_seconds(table: dict, pattern: str,
                   windows_ns: list | None = None) -> tuple[float, int]:
    """(device seconds, executions) of the programs whose "XLA Modules"
    name matches `pattern`; with `windows_ns`, only executions that
    start inside one of those [start, end) stretches of the trace
    clock."""
    rx = re.compile(pattern)
    wins = merge(windows_ns) if windows_ns is not None else None
    starts = [w[0] for w in wins] if wins is not None else None
    total, count = 0.0, 0
    for dev in table["devices"]:
        for name, s, d in dev["modules"]:
            if not rx.search(name):
                continue
            if wins is not None:
                i = bisect.bisect_right(starts, s) - 1
                if i < 0 or s >= wins[i][1]:
                    continue
            total += d / 1e9
            count += 1
    return total, count


def _module_of(modules: list, starts: list, t: float) -> str:
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < modules[i][1] + modules[i][2]:
        return re.sub(r"\(\d+\)$", "", modules[i][0])
    return "?"


def top_ops(table: dict, t0_ns: float, t1_ns: float, k: int = 10) -> list:
    """[["module/op", seconds], ...]: the device operations that took
    most time in [t0, t1).  An op that encloses others (a `while` and
    its body) is charged only the time its children leave."""
    totals: dict[str, float] = {}
    for dev in table["devices"]:
        modules = sorted(dev["modules"], key=lambda e: e[1])
        mstarts = [m[1] for m in modules]
        events = sorted((e for e in dev["ops"]
                         if e[1] + e[2] > t0_ns and e[1] < t1_ns),
                        key=lambda e: (e[1], -e[2]))
        stack: list = []    # [name, start, end, child_ns]

        def close(upto: float) -> None:
            while stack and stack[-1][2] <= upto:
                name, s, e, child = stack.pop()
                self_ns = max(0.0, (e - s) - child)
                key = f"{_module_of(modules, mstarts, s)}/{name}"
                totals[key] = totals.get(key, 0.0) + self_ns / 1e9
                if stack:
                    stack[-1][3] += e - s

        for name, s, d in events:
            close(s)
            stack.append([name, s, s + d, 0.0])
        close(float("inf"))
        if not dev["ops"]:
            for name, s, d in modules:
                if s + d > t0_ns and s < t1_ns:
                    key = re.sub(r"\(\d+\)$", "", name)
                    totals[key] = totals.get(key, 0.0) + d / 1e9
    return [[name, sec] for name, sec in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


# ---------------------------------------------------------------------------
# spans over the trace

def clock_offset_ns(table: dict, marker: str = "bench.window_start") -> float:
    """wall clock (ns) minus trace clock (ns), from one marker."""
    m = table["markers"][marker]
    return m["wall"] * 1e9 - m["ns"]


def span_windows_ns(spans: list, offset_ns: float, name: str,
                    attrs: dict | None = None) -> list:
    """The [start, end) of each span called `name` (and carrying
    `attrs`), on the trace clock."""
    out = []
    for s in spans:
        if s.get("name") != name:
            continue
        have = s.get("attrs") or {}
        if attrs and any(have.get(k) != v for k, v in attrs.items()):
            continue
        a = s["start"] * 1e9 - offset_ns
        out.append([a, a + s["seconds"] * 1e9])
    return out


def attribute_gaps(gaps: list, spans: list, offset_ns: float,
                   k: int = 10) -> list:
    """[[span name, idle seconds], ...]: each idle gap charged to the
    innermost (shortest) span that covers its midpoint — what the host
    was doing while the device waited; "(no span)" where none does.
    A trace has hundreds of thousands of gaps and a few hundred spans,
    so the innermost span is worked out once per stretch between span
    boundaries and each gap looks its stretch up."""
    timed = [(s["start"] * 1e9 - offset_ns,
              s["start"] * 1e9 - offset_ns + s["seconds"] * 1e9,
              s["name"]) for s in spans]
    edges = sorted({t for s0, s1, _ in timed for t in (s0, s1)})
    owner = []      # owner[i] covers [edges[i], edges[i + 1])
    for lo, hi in zip(edges, edges[1:]):
        mid = (lo + hi) / 2
        covering = [(s1 - s0, name) for s0, s1, name in timed
                    if s0 <= mid <= s1]
        owner.append(min(covering)[1] if covering else "(no span)")
    totals: dict[str, float] = {}
    for a, b in gaps:
        i = bisect.bisect_right(edges, (a + b) / 2) - 1
        name = owner[i] if 0 <= i < len(owner) else "(no span)"
        totals[name] = totals.get(name, 0.0) + (b - a) / 1e9
    return [[name, sec] for name, sec in
            sorted(totals.items(), key=lambda kv: -kv[1])[:k]]
