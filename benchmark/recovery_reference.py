"""The plain reference of what a preemption may do to a fleet's batches
(configuration `baseline1-prover-preempted`).  Nothing of the program is
imported: the deployment hands over what it saw happen, in order, and
this file says what was wrong with it.

The semantics, in one sentence: the same batches, never interrupted,
give the same proofs; every batch assigned is stored exactly once, next
after its kill and before any later batch; no failure is charged to a
batch for its prover's death.

  violations(events, ledgers, kills_per_batch)
      events    [("kill", batch) | ("store", batch), ...] in the order
                they happened, set-up's warm-up batch included
      ledgers   what the coordinator and the prover's runtime counted
                over the same stretch: `reassignments`, `quarantined`,
                `rejected_submits`, `failures` (all must read 0), and per
                killed batch `resumed_phases` and `disk_loads` (both
                must be above 0: the resumed attempt got its phases from
                the disk, not from the dead attempt's memory)
      -> a list of sentences, empty where the run kept to the semantics

  same_proofs(preempted, uninterrupted)
      {batch: digest} of the preempted run and of a run of the same
      batches that nothing interrupted -> a list of sentences

`check.py` holds every proof of the window, each of them a resumed one,
to the plain references of the transfers and of the STARKs besides.
"""

from __future__ import annotations


def violations(events: list, ledgers: dict, kills_per_batch: int = 1) -> list:
    wrong = []
    killed: dict = {}
    stored: list = []
    in_flight = None            # the batch killed and not yet stored
    for what, batch in events:
        if what == "kill":
            if in_flight is not None and in_flight != batch:
                wrong.append(f"batch {batch} was killed while batch "
                             f"{in_flight}, killed before it, was still "
                             "unstored")
            if batch in stored:
                wrong.append(f"batch {batch} was killed after it had been "
                             "stored")
            killed[batch] = killed.get(batch, 0) + 1
            in_flight = batch
        elif what == "store":
            if batch in stored:
                wrong.append(f"batch {batch} was stored twice")
            if in_flight is not None and batch != in_flight:
                wrong.append(f"batch {batch} was stored next after the "
                             f"kill of batch {in_flight}")
            if stored and batch != stored[-1] + 1:
                wrong.append(f"batch {batch} was stored after batch "
                             f"{stored[-1]}")
            stored.append(batch)
            if batch == in_flight:
                in_flight = None
        else:
            wrong.append(f"unknown event {what!r}")
    if in_flight is not None:
        wrong.append(f"batch {in_flight} was killed and never stored")
    for batch in stored:
        if killed.get(batch, 0) != kills_per_batch:
            wrong.append(f"batch {batch} was killed {killed.get(batch, 0)} "
                         f"time(s), the mix says {kills_per_batch}")
    for name in ("reassignments", "quarantined", "rejected_submits",
                 "failures"):
        if ledgers.get(name, 0):
            wrong.append(f"the coordinator counted {name} "
                         f"{ledgers[name]} for a prover's death")
    for name, why in (("resumed_phases", "resumed no phase"),
                      ("disk_loads", "read no envelope from the disk")):
        for batch in killed:
            if not (ledgers.get(name) or {}).get(batch, 0):
                wrong.append(f"the attempt that finished batch {batch} "
                             f"{why}")
    return wrong


def same_proofs(preempted: dict, uninterrupted: dict) -> list:
    wrong = []
    for batch, digest in sorted(preempted.items()):
        if batch not in uninterrupted:
            wrong.append(f"batch {batch} has no uninterrupted proof to be "
                         "compared with")
        elif uninterrupted[batch] != digest:
            wrong.append(f"batch {batch}: the resumed proof differs from "
                         "the uninterrupted one")
    return wrong
