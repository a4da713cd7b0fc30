"""`unspanned_s`: seconds per batch that no leaf span names.  Per batch
trace: from the first span's start to the last span's end, less the
union of the intervals of its leaf spans (a span that is no other
span's `parentId`).  With `prover.idle` in the trace the extents tile
the window, so leaf spans + `unspanned_s` = `batch_prove_s` to within
the tiling's error: `log_batch` puts that identity and the batch's leaf
spans by name on two lines of the run's log."""

import json


def by_trace(spans: list) -> dict:
    traces: dict = {}
    for s in spans:
        if s.get("traceId"):
            traces.setdefault(s["traceId"], []).append(s)
    return traces


def leaves(spans: list) -> list:
    parents = {s.get("parentId") for s in spans}
    return [s for s in spans if s.get("spanId") not in parents]


def union_seconds(intervals: list) -> float:
    total, end = 0.0, float("-inf")
    for a, b in sorted(intervals):
        if b > end:
            total += b - max(a, end)
            end = b
    return total


def batch_rows(spans: list) -> dict:
    """One batch trace: its extent, the union of its leaf spans, and the
    leaf spans by name (count, seconds and the summed byte attributes)."""
    extent = max(s["start"] + s["seconds"] for s in spans) \
        - min(s["start"] for s in spans)
    table: dict = {}
    leaf = leaves(spans)
    for s in leaf:
        row = table.setdefault(s["name"], {"n": 0, "s": 0.0})
        row["n"] += 1
        row["s"] += s["seconds"]
        for key in ("d2h_bytes", "disk_bytes", "tries"):
            value = (s.get("attrs") or {}).get(key)
            if isinstance(value, (int, float)):
                row[key] = row.get(key, 0) + value
    covered = union_seconds(
        [(s["start"], s["start"] + s["seconds"]) for s in leaf])
    return {"extent": extent, "covered": covered, "spans": len(spans),
            "table": table}


def log_batch(tid: str, rows: dict) -> None:
    table = {name: {k: round(v, 4) if isinstance(v, float) else v
                    for k, v in row.items()}
             for name, row in sorted(rows["table"].items(),
                                     key=lambda kv: -kv[1]["s"])}
    print(f"  leaf spans of trace {tid} ({rows['spans']} spans): "
          f"{json.dumps(table)}", flush=True)
    print(f"  trace {tid}: leaf spans {rows['covered']:.4f}s + "
          f"unspanned {rows['extent'] - rows['covered']:.4f}s = "
          f"batch extent {rows['extent']:.4f}s", flush=True)


def read(ctx):
    traces = by_trace(ctx["spans"])
    if not traces or not ctx["batches"]:
        return None
    total = 0.0
    for tid, spans in sorted(traces.items(),
                             key=lambda kv: min(s["start"] for s in kv[1])):
        rows = batch_rows(spans)
        total += rows["extent"] - rows["covered"]
        log_batch(tid, rows)
    return total / ctx["batches"]
