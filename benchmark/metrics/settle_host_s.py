"""`settle_host_s`: the proof sender's seconds per batch it settled in
the window.  A batch is settled in the window where its `proof.settle`
span is in it; its seconds are its own `proof.verify` and
`proof.settle` spans, and the `l1.verify` spans (one `verifyBatches` a
range, attributes `first` and `last`) whose range holds it, shared out
over the settled batches.  None where no batch was settled in the
window (a program without the spans, or a cell with no proof sender)."""


def _batch(span):
    value = (span.get("attrs") or {}).get("batch")
    return value if isinstance(value, int) else None


def read(ctx):
    spans = ctx["spans"]
    settled = {_batch(s) for s in spans
               if s.get("name") == "proof.settle"} - {None}
    if not settled:
        return None
    total = sum(s.get("seconds") or 0.0 for s in spans
                if s.get("name") in ("proof.verify", "proof.settle")
                and _batch(s) in settled)
    for s in spans:
        attrs = s.get("attrs") or {}
        first, last = attrs.get("first"), attrs.get("last")
        if s.get("name") == "l1.verify" and isinstance(first, int) \
                and isinstance(last, int) \
                and any(first <= n <= last for n in settled):
            total += s.get("seconds") or 0.0
    return total / len(settled)
