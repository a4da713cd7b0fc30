"""`token_trace_gen_s`: seconds per batch of host trace generation for
`TokenAir`: the `prove.trace_gen` spans whose `air` attribute names it.
None where no batch of the window had a token circuit."""


def read(ctx):
    got = [s["seconds"] for s in ctx["spans"]
           if s["name"] == "prove.trace_gen"
           and (s.get("attrs") or {}).get("air") == "TokenAir"]
    if not got or not ctx["batches"]:
        return None
    return sum(got) / ctx["batches"]
