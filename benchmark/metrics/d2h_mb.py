"""`d2h_mb`: megabytes copied from the device to the host per batch, by
the `d2h_bytes` attribute of the program's spans (checkpoint copies, FRI
layers and final coefficients, the query phase's copy when checkpoints
are off).  A span without the attribute counts nothing; a program with
no such attribute gives None."""


def read(ctx):
    counted = [s["attrs"]["d2h_bytes"] for s in ctx["spans"]
               if isinstance((s.get("attrs") or {}).get("d2h_bytes"),
                             (int, float))]
    if not counted or not ctx["batches"]:
        return None
    return sum(counted) / 1e6 / ctx["batches"]
