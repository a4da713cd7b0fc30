"""Types and small helpers shared by the harness and the deployments."""

from __future__ import annotations

import dataclasses
import sys
import time


class BenchFailure(Exception):
    """The run cannot be measured (no chip, a stack that did not start,
    a backlog that ran dry): no result line is printed."""


@dataclasses.dataclass
class BatchRecord:
    """One batch the timed path handled."""

    number: int                     # the system's batch number (1-based)
    blocks: list                    # list[list[reference.Transfer]]
    in_window: bool = False
    proof: dict | None = None
    program_input: dict | None = None  # the prover input as stored


def log(msg: str) -> None:
    """Everything but the result goes on earlier lines of stdout."""
    print(msg, flush=True)


def err(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def wait_for(predicate, what: str, timeout: float, poll: float = 0.05):
    t0 = time.monotonic()
    while True:
        got = predicate()
        if got:
            return got
        if time.monotonic() - t0 > timeout:
            raise BenchFailure(
                f"timed out after {timeout:.0f}s waiting for {what}")
        time.sleep(poll)


class Spans:
    """The benchmark's own spans, in the program's span schema (name,
    start on the wall clock, seconds), so one set of readers serves
    both."""

    def __init__(self):
        self.spans: list[dict] = []

    def add(self, name: str, start: float, seconds: float, **attrs):
        self.spans.append({"name": name, "start": start,
                           "seconds": seconds, "attrs": attrs,
                           "traceId": None})
