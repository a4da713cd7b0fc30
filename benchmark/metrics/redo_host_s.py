"""`redo_host_s`: seconds per batch of host work that a resumed attempt
repeats though the dead attempt had done it: the `prove.execute`,
`prove.vm_batch` and `prove.trace_gen` spans that lie inside a
`prover.prove` span of the same trace whose `attempt` attribute is 2
(the attempt a reclaimed lease starts).  None where no attempt of the
window was a second one, or the program does not number its attempts."""

REDONE = ("prove.execute", "prove.vm_batch", "prove.trace_gen")


def read(ctx):
    second = [s for s in ctx["spans"] if s["name"] == "prover.prove"
              and (s.get("attrs") or {}).get("attempt") == 2]
    if not second or not ctx["batches"]:
        return None
    total = 0.0
    for p in second:
        p0, p1 = p["start"], p["start"] + p["seconds"]
        total += sum(s["seconds"] for s in ctx["spans"]
                     if s["name"] in REDONE
                     and s.get("traceId") == p.get("traceId")
                     and p0 - 1e-3 <= s["start"] <= p1)
    return total / ctx["batches"]
