"""`token_calls`: token calls per batch that the token circuit proved,
by the `tok_calls` attribute of `prove.vm_batch`.  None where the
program's span carries no such attribute (a program from before it)."""


def read(ctx):
    got = [s["attrs"]["tok_calls"] for s in ctx["spans"]
           if s["name"] == "prove.vm_batch"
           and isinstance((s.get("attrs") or {}).get("tok_calls"), int)]
    if not got or not ctx["batches"]:
        return None
    return sum(got) / ctx["batches"]
