"""`lost_attempt_s`: seconds per batch of attempts that were killed:
the `prover.prove` spans that ended in an error and carry an `attempt`
attribute (a program that numbers its attempts is one that can resume
them).  Their work is thrown away but for the envelopes they landed.
None where no attempt of the window was killed."""


def read(ctx):
    lost = [s["seconds"] for s in ctx["spans"]
            if s["name"] == "prover.prove" and s.get("status") == "error"
            and "attempt" in (s.get("attrs") or {})]
    if not lost or not ctx["batches"]:
        return None
    return sum(lost) / ctx["batches"]
