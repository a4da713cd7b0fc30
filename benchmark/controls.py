"""The controls: one guarantee of the configuration broken in a copy of
what the timed path produced.  Each has to come out as not correct.
Every control breaks ONE proof of the window, and one that the witness
replay does not draw (`check.replayed`): whatever decides `correct` has to
hold every proof, not a sample.  The benchmark's own runs never run
them; `sweep.py --controls` does, on the chip at the cell's own size,
and `tests/test_correct.py` keeps them at a size a test run can hold.
"""

from __future__ import annotations

import ethtx


def _target(records, replayed: set):
    """The last proven batch of the window that the witness replay
    leaves alone (where it replays every proof, the last of all)."""
    window = [r for r in records if r.in_window and r.proof]
    if not window:
        raise ValueError("the window holds no proven batch to break")
    return ([r for r in window if r.number not in replayed] or window)[-1]


def flip_trace_root(records, replayed: set) -> None:
    """One bit of the binding STARK's trace root flipped: a corrupt
    proof an honest verifier rejects."""
    stark = _target(records, replayed).proof["proof"]
    stark["trace_root"] = [stark["trace_root"][0] ^ 1,
                           *stark["trace_root"][1:]]


def garble_fri_layer(records, replayed: set) -> None:
    """One limb of one value that the vm STARK's FRI opens in a middle
    layer, one off: what a fold kernel that rounds or drops a limb would
    hand back.  Only the device layers' checks can see it."""
    fri = _target(records, replayed).proof["vm_proof"]["fri"]
    opening = fri["queries"][len(fri["queries"]) // 2][len(fri["roots"]) // 2]
    lo = list(opening["values"][0])
    lo[1] = (lo[1] + 1) % 2013265921
    opening["values"] = [lo, opening["values"][1]]


def forge_balance(records, replayed: set) -> None:
    """One wei added to the last account the proof's write log writes:
    a state transition nobody executed."""
    rec = _target(records, replayed)
    rows = [row for block in rec.proof["write_log"] for row in block
            if row[0] == "a" and row[3]]
    if not rows:
        raise ValueError("the write log has no account row to forge")
    fields = ethtx.rlp_decode(bytes.fromhex(rows[-1][3]))
    fields[1] = ethtx.int_bytes(int.from_bytes(fields[1], "big") + 1)
    rows[-1][3] = ethtx.rlp_encode(fields).hex()


def replay_previous(records, replayed: set) -> None:
    """The batch answered with the proof of the batch before it: a
    valid proof of the wrong batch."""
    rec = _target(records, replayed)
    proofs = {r.number: r.proof for r in records if r.proof}
    if rec.number - 1 not in proofs:
        raise ValueError("no earlier proof to replay")
    rec.proof = proofs[rec.number - 1]


CONTROLS = {"flip_trace_root": flip_trace_root,
            "garble_fri_layer": garble_fri_layer,
            "forge_balance": forge_balance,
            "replay_previous": replay_previous}
