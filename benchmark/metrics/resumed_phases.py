"""`resumed_phases`: phases per batch that were loaded from checkpoints
in place of being proved: the `resumed_phases` attribute of the
`backend.prove` spans, summed.  None where the program does not count
them."""


def read(ctx):
    got = [(s.get("attrs") or {}).get("resumed_phases")
           for s in ctx["spans"] if s["name"] == "backend.prove"]
    got = [n for n in got if isinstance(n, (int, float))]
    if not got or not ctx["batches"]:
        return None
    return sum(got) / ctx["batches"]
