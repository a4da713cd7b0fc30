"""HLO collective accounting for the compiled STARK phase programs.

The roofline registry (perf/roofline.py) answers "how fast is each
kernel vs the hardware"; this module answers the ROADMAP item-1
question it cannot: *where does the multi-device wall go*.  Each
compiled phase executable is inspected post-AOT (stark/prover.py
`_aot_phases` and the bench's fused core step) on three axes:

- **HLO text** (``as_text()`` / ``hlo_modules()``): count the
  collective/reshard ops GSPMD inserted — all-gather, all-reduce,
  reduce-scatter, collective-permute, all-to-all, plus layout
  ``copy`` ops — and estimate the bytes each moves from its result
  shape.  ``crossDeviceBytes`` sums the true collectives only; copies
  are intra-device resharding traffic and carry their own row.
- **``memory_analysis()``** (shape varies by jaxlib: an object with
  ``*_size_in_bytes`` attributes, a dict, a list of either, or None):
  the per-kernel HBM working set (arg + output + temp + alias bytes).
- **``cost_analysis()``** stays with the roofline; the two registries
  share the (air, kernel) key space so reports join.

Everything here is telemetry behind the never-raise contract: a
jaxlib that renames an API degrades to partial rows (or none), never
a failed prove.  Recorded per (air, kernel, devices), exported as
labelled gauges, reported through ethrex_perf / the monitor / the
flight recorder, and consumed by the bench's scaling autopsy
(docs/PERFORMANCE.md "Reading the scaling autopsy").
"""

from __future__ import annotations

import os
import re
import threading

from ..utils.metrics import METRICS

# classification (docs/PERFORMANCE.md): the cross-device collectives GSPMD
# inserts at sharding boundaries, plus intra-device reshard copies
COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "collective-permute", "all-to-all")
RESHARD_KINDS = ("copy",)

_ALL_KINDS = COLLECTIVE_KINDS + RESHARD_KINDS

_OP_RE = re.compile(
    r"\b(" + "|".join(re.escape(k) for k in _ALL_KINDS) + r")(-start|-done)?\(")
_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# assumed cross-device interconnect bandwidth used to turn collective
# bytes into an *estimated* seconds share of a kernel wall.  Like the
# roofline peak this is a coarse, relative anchor, not a measurement:
# override with ETHREX_ICI_GBPS (GB/s) for a calibrated link.
_DEFAULT_ICI_GBPS = 75.0


def ici_gbps() -> float:
    env = os.environ.get("ETHREX_ICI_GBPS")
    if env:
        try:
            v = float(env)
            if v > 0:
                return v
        except ValueError:
            pass
    return _DEFAULT_ICI_GBPS


def _shape_bytes(dtype: str, dims: str) -> int:
    size = _DTYPE_BYTES.get(dtype)
    if size is None:
        return 0
    total = size
    for d in dims.split(","):
        d = d.strip()
        if d:
            total *= int(d)
    return total


def hlo_text(compiled) -> str | None:
    """Best-effort HLO text of a compiled executable, tolerant of every
    jaxlib surface: ``as_text()`` (jax AOT Compiled), ``hlo_modules()``
    (lower-level executables), or None when neither answers."""
    for attr in ("as_text",):
        fn = getattr(compiled, attr, None)
        if callable(fn):
            try:
                text = fn()
                if isinstance(text, str) and text:
                    return text
            except Exception:
                pass
    fn = getattr(compiled, "hlo_modules", None)
    if callable(fn):
        try:
            parts = []
            for mod in fn() or []:
                to_string = getattr(mod, "to_string", None)
                if callable(to_string):
                    parts.append(to_string())
            if parts:
                return "\n".join(parts)
        except Exception:
            pass
    return None


def count_collectives(text) -> dict:
    """Per-op collective counts and result-shape byte estimates from one
    HLO module's text.  Async pairs (``all-gather-start`` /
    ``all-gather-done``) count once, on the start leg.  Bytes are the
    instruction's result shapes (the data the op materializes), summed;
    an unparseable line still counts the op with zero bytes."""
    out: dict = {k: {"count": 0, "bytes": 0} for k in _ALL_KINDS}
    if not isinstance(text, str):
        return out
    for line in text.splitlines():
        m = _OP_RE.search(line)
        if m is None or m.group(2) == "-done":
            continue
        kind = m.group(1)
        cell = out[kind]
        cell["count"] += 1
        eq = line.find("=")
        lhs_end = m.start()
        region = line[eq + 1:lhs_end] if 0 <= eq < lhs_end else ""
        cell["bytes"] += sum(_shape_bytes(d, dims)
                             for d, dims in _SHAPE_RE.findall(region))
    return out


_MEM_FIELDS = {
    "argument_size_in_bytes": "argBytes",
    "output_size_in_bytes": "outputBytes",
    "temp_size_in_bytes": "tempBytes",
    "alias_size_in_bytes": "aliasBytes",
    "generated_code_size_in_bytes": "codeBytes",
}


def parse_memory_analysis(mem) -> dict:
    """Normalize any ``memory_analysis()`` shape — an object with
    ``*_size_in_bytes`` attributes (jax >= 0.4.30 AOT), a dict keyed the
    same way, a list/tuple of either (one entry per computation), or
    None — to {argBytes, outputBytes, tempBytes, aliasBytes, codeBytes,
    peakBytes} with float-or-None values.  peakBytes (the HBM working
    set estimate) is arg+output+temp+alias over whichever of those
    fields were present; absent fields stay None (partial rows, never
    an error)."""
    out: dict = {v: None for v in _MEM_FIELDS.values()}
    out["peakBytes"] = None
    if mem is None:
        return out
    entries = mem if isinstance(mem, (list, tuple)) else [mem]
    for entry in entries:
        if entry is None:
            continue
        for field, key in _MEM_FIELDS.items():
            if isinstance(entry, dict):
                v = entry.get(field)
            else:
                v = getattr(entry, field, None)
            if isinstance(v, (int, float)) and not isinstance(v, bool) \
                    and v >= 0:
                out[key] = (out[key] or 0.0) + float(v)
    working = [out[k] for k in ("argBytes", "outputBytes", "tempBytes",
                                "aliasBytes") if out[k] is not None]
    if working:
        out["peakBytes"] = float(sum(working))
    return out


def introspect(compiled) -> dict:
    """One executable -> {ops, collectiveOps, crossDeviceBytes, copyOps,
    copyBytes, memory}.  Never raises; an opaque executable yields a
    row of zeros/Nones."""
    try:
        ops = count_collectives(hlo_text(compiled))
    except Exception:
        ops = {k: {"count": 0, "bytes": 0} for k in _ALL_KINDS}
    mem = None
    try:
        fn = getattr(compiled, "memory_analysis", None)
        if callable(fn):
            mem = fn()
    except Exception:
        mem = None
    memory = parse_memory_analysis(mem)
    coll_ops = sum(ops[k]["count"] for k in COLLECTIVE_KINDS)
    coll_bytes = sum(ops[k]["bytes"] for k in COLLECTIVE_KINDS)
    return {
        "ops": ops,
        "collectiveOps": coll_ops,
        "crossDeviceBytes": coll_bytes,
        "copyOps": ops["copy"]["count"],
        "copyBytes": ops["copy"]["bytes"],
        "memory": memory,
    }


class HloIntrospectRegistry:
    """Per (air, kernel) collective/memory accounting, alongside the
    roofline's cost rows (same key space, same MAX_KEYS clamp)."""

    MAX_KEYS = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._kernels: dict[tuple[str, str], dict] = {}

    def record(self, air: str, kernel: str, compiled,
               devices: int = 1) -> None:
        row = introspect(compiled)
        row["devices"] = max(1, int(devices))
        key = (str(air), str(kernel))
        with self._lock:
            if key not in self._kernels \
                    and len(self._kernels) >= self.MAX_KEYS:
                return
            self._kernels[key] = row
        record_kernel_collectives(
            air, kernel, row["collectiveOps"], row["crossDeviceBytes"],
            row["memory"].get("peakBytes"))

    def lookup(self, air: str, kernel: str) -> dict | None:
        with self._lock:
            row = self._kernels.get((str(air), str(kernel)))
        return dict(row) if row else None

    def report(self) -> dict:
        """JSON report for ethrex_perf / the flight recorder.  An
        L1-only node that never compiled a kernel answers the same
        shape with an empty kernel list (degradation stub)."""
        with self._lock:
            cells = {k: dict(v) for k, v in self._kernels.items()}
        kernels = []
        for (air, kernel), row in sorted(cells.items()):
            kernels.append({
                "air": air, "kernel": kernel,
                "devices": row.get("devices", 1),
                "collectiveOps": row.get("collectiveOps", 0),
                "crossDeviceBytes": row.get("crossDeviceBytes", 0),
                "copyOps": row.get("copyOps", 0),
                "copyBytes": row.get("copyBytes", 0),
                "ops": row.get("ops", {}),
                "hbmPeakBytes":
                    (row.get("memory") or {}).get("peakBytes"),
                "memory": row.get("memory", {}),
            })
        return {"kernels": kernels, "iciGbpsAssumed": ici_gbps()}

    def reset(self) -> None:
        with self._lock:
            self._kernels.clear()


REGISTRY = HloIntrospectRegistry()


def record(air: str, kernel: str, compiled, devices: int = 1) -> None:
    """Never-raise hook (called next to roofline.record_cost from
    stark/prover._aot_phases): introspect one compiled phase program's
    HLO + memory analysis into the registry and refresh the gauges."""
    try:
        REGISTRY.record(air, kernel, compiled, devices=devices)
    except Exception:
        pass


def record_kernel_collectives(air: str, kernel: str, ops: float,
                              cross_bytes: float,
                              hbm_bytes: float | None = None) -> None:
    """Labelled gauges for one kernel's collective accounting (never
    raises: rides the AOT-compile path)."""
    try:
        labels = {"air": air, "stage": kernel}
        METRICS.set_labeled(
            "prover_kernel_collective_ops", labels, float(ops),
            help_text="Cross-device collective ops (all-gather, "
                      "all-reduce, reduce-scatter, collective-permute, "
                      "all-to-all) in the compiled STARK phase program's "
                      "HLO, per air+stage")
        METRICS.set_labeled(
            "prover_kernel_collective_bytes", labels, float(cross_bytes),
            help_text="Estimated cross-device bytes moved by the phase "
                      "program's collectives (result-shape bytes summed "
                      "over collective ops)")
        if hbm_bytes is not None:
            METRICS.set_labeled(
                "prover_kernel_hbm_bytes", labels, float(hbm_bytes),
                help_text="Per-kernel HBM working-set estimate from XLA "
                          "memory_analysis (arg+output+temp+alias bytes)")
    except Exception:
        pass


def record_collective_share(air: str, kernel: str,
                            wall_seconds: float) -> None:
    """Estimated share of one measured kernel wall spent moving
    collective bytes (bytes / ETHREX_ICI_GBPS / wall, clamped to 1) —
    the live signal behind the prover_collective_share alert.  Called
    from stark/prover next to the roofline wall hook; never raises."""
    try:
        row = REGISTRY.lookup(air, kernel)
        if row is None or not isinstance(wall_seconds, (int, float)) \
                or wall_seconds <= 0:
            return
        est_s = float(row.get("crossDeviceBytes") or 0) \
            / (ici_gbps() * 1e9)
        share = min(1.0, est_s / float(wall_seconds))
        METRICS.set_labeled(
            "prover_kernel_collective_wall_share",
            {"air": air, "stage": kernel}, share,
            help_text="Estimated fraction of the last measured kernel "
                      "wall spent in cross-device collectives "
                      "(collective bytes over ETHREX_ICI_GBPS; coarse, "
                      "relative — docs/PERFORMANCE.md)")
        METRICS.set(
            "prover_collective_wall_share", share,
            help_text="Estimated collective share of the most recently "
                      "measured kernel wall (max-interesting signal for "
                      "the prover_collective_share alert)")
    except Exception:
        pass
