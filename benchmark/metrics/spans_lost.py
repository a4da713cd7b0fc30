"""`spans_lost`: spans the program's tracer threw away, at read time:
trimmed from the front of a trace over its cap, evicted with their
trace from the ring, or left out of a wire payload.  It reads 0, or
every span metric of the run is suspect.  None where the tracer does
not count (a program from before the counters)."""


def read(ctx):
    from ethrex_tpu.utils.tracing import TRACER

    counts = [getattr(TRACER, name, None)
              for name in ("trimmed", "dropped", "wire_truncated")]
    if any(c is None for c in counts):
        return None
    return sum(counts)
