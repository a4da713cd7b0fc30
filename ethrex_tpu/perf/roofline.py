"""Roofline accounting for the compiled STARK phase programs.

XLA's cost model (``compiled.cost_analysis()``) reports static FLOPs and
bytes-accessed per executable; the prover records each phase's
block_until_ready-bounded wall-clock.  Together they give per-kernel
achieved-FLOP/s, arithmetic intensity (FLOPs/byte) and a
utilization-vs-peak estimate — the same view a training stack's
continuous profiler provides, applied to proving kernels.

Caveats (documented in docs/PERFORMANCE.md and carried in the report):

- XLA counts u32 modular-arithmetic ops as "flops"; the prover does
  integer work, for which no published peak exists, so utilization
  against the bf16 peak is a *relative* signal across runs on one
  device kind, not an MXU occupancy.
- The peak comes from a table keyed by the device's ``device_kind``.
  A kind that is not in the table has no peak and no utilization
  ("not measured") — never a default.

The recording hooks are exception-guarded: a failing cost_analysis can
never fail a prove.
"""

from __future__ import annotations

import threading

from ..utils.metrics import record_kernel_flops

# Published per-chip peaks, keyed by jax.devices()[0].device_kind.
# "TPU v5 lite" is how a v5e reports itself.  Source: Google Cloud
# documentation, "TPU v5e" system-architecture page — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197.0e12, "int8_ops": 393.0e12,
                    "hbm_bytes_per_sec": 819.0e9},
}


def peak_flops_estimate(device_kind: str | None = None) -> float | None:
    """bf16 peak FLOP/s of `device_kind` (default: the first device's
    kind); None for a kind the table does not know."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    return DEVICE_PEAKS.get(device_kind, {}).get("bf16_flops")


def _parse_cost(cost) -> dict:
    """Normalize cost_analysis() to {'flops', 'bytes'} with
    float-or-None values.  jax 0.9 returns one dict for a compiled
    executable (keys "flops", "bytes accessed"), or None where the
    backend has no cost model; either key may be missing."""
    out = {"flops": None, "bytes": None}
    if not isinstance(cost, dict):
        return out
    for key, name in (("flops", "flops"), ("bytes accessed", "bytes")):
        v = cost.get(key)
        if isinstance(v, (int, float)) and not isinstance(v, bool) \
                and v >= 0:
            out[name] = float(v)
    return out


class RooflineRegistry:
    """Per (air, kernel) static cost + measured wall accumulator."""

    MAX_KEYS = 256

    def __init__(self):
        self._lock = threading.Lock()
        self._kernels: dict[tuple[str, str], dict] = {}

    def _cell(self, air: str, kernel: str) -> dict | None:
        key = (str(air), str(kernel))
        cell = self._kernels.get(key)
        if cell is None:
            if len(self._kernels) >= self.MAX_KEYS:
                return None
            cell = self._kernels[key] = {
                "flops": None, "bytes": None, "devices": 1,
                "wallCount": 0, "wallTotal": 0.0, "wallLast": None,
                "wallMin": None,
            }
        return cell

    def record_cost(self, air: str, kernel: str, cost,
                    devices: int = 1) -> None:
        """`devices`: mesh size the executable was compiled for — the
        report carries it so a sharded kernel's static FLOPs are read
        against the right number of chips (utilization stays relative
        to the single-chip peak estimate, documented in
        docs/PERFORMANCE.md)."""
        parsed = _parse_cost(cost)
        with self._lock:
            cell = self._cell(air, kernel)
            if cell is None:
                return
            if parsed["flops"] is not None:
                cell["flops"] = parsed["flops"]
            if parsed["bytes"] is not None:
                cell["bytes"] = parsed["bytes"]
            cell["devices"] = max(1, int(devices))

    def record_wall(self, air: str, kernel: str, seconds: float) -> None:
        sec = float(seconds)
        with self._lock:
            cell = self._cell(air, kernel)
            if cell is None:
                return
            cell["wallCount"] += 1
            cell["wallTotal"] += sec
            cell["wallLast"] = sec
            if cell["wallMin"] is None or sec < cell["wallMin"]:
                cell["wallMin"] = sec
            flops = cell["flops"]
        # export gauges outside the lock; achieved-FLOP/s uses the LAST
        # wall (the gauge is "current", the report also carries min/avg)
        if flops and sec > 0:
            peak = peak_flops_estimate()
            achieved = flops / sec
            util = achieved / peak if peak else None
            record_kernel_flops(air, kernel, flops, achieved, util)

    def report(self) -> dict:
        peak = peak_flops_estimate()
        with self._lock:
            cells = {k: dict(v) for k, v in self._kernels.items()}
        kernels = []
        for (air, kernel), c in sorted(cells.items()):
            flops, nbytes = c["flops"], c["bytes"]
            last = c["wallLast"]
            achieved = flops / last if flops and last else None
            kernels.append({
                "air": air, "kernel": kernel,
                "devices": c.get("devices", 1),
                "flops": flops, "bytes": nbytes,
                "intensityFlopsPerByte":
                    round(flops / nbytes, 3) if flops and nbytes else None,
                "wallCount": c["wallCount"],
                "wallLastSeconds":
                    round(last, 6) if last is not None else None,
                "wallMinSeconds":
                    round(c["wallMin"], 6)
                    if c["wallMin"] is not None else None,
                "wallAvgSeconds":
                    round(c["wallTotal"] / c["wallCount"], 6)
                    if c["wallCount"] else None,
                "achievedFlopsPerSec":
                    round(achieved, 1) if achieved else None,
                "utilizationVsPeak":
                    round(achieved / peak, 6)
                    if achieved and peak else None,
            })
        return {"peakFlopsEstimate": peak,
                "peakSource": "device_kind table" if peak
                else "not measured",
                "kernels": kernels}

    def reset(self) -> None:
        with self._lock:
            self._kernels.clear()


ROOFLINE = RooflineRegistry()


def record_cost(air: str, kernel: str, cost, devices: int = 1) -> None:
    """Never-raise hook: fold one compiled program's cost_analysis()
    output (any shape, including None) into the registry; `devices` is
    the mesh size the executable was compiled for (1 = unsharded)."""
    try:
        ROOFLINE.record_cost(air, kernel, cost, devices=devices)
    except Exception:
        pass


def record_wall(air: str, kernel: str, seconds: float) -> None:
    """Never-raise hook: fold one measured phase wall-clock in and
    refresh the prover_kernel_* gauges."""
    try:
        ROOFLINE.record_wall(air, kernel, seconds)
    except Exception:
        pass
