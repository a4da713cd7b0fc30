"""`commit_s`: seconds the committer takes to seal one batch, the mean
of the window's `seq.commit` spans (its children `seq.witness`,
`seq.blobs`, `seq.l1_commit` and `seq.store` lie inside it).  Each
commit joins the trace of the batch it seals; the commits of the window
are those of batches the prover has not reached yet.  None where the
window holds no such span (a program without the committer's spans, or
a cell with no sequencer)."""


def read(ctx):
    got = [s.get("seconds") or 0.0 for s in ctx["spans"]
           if s.get("name") == "seq.commit"]
    return sum(got) / len(got) if got else None
