"""Performance telemetry the running program keeps about itself
(docs/PERFORMANCE.md).  Speed is measured elsewhere: by the benchmark
under ``benchmark/`` on the chip, with its findings in ``PERF.md``.

- ``profiler``: a process-wide stage-attribution tree unifying the
  block_until_ready-bounded prover stage spans with the L1 import legs
  (execute / merkleize / store_write), the EVM split (sig_recovery /
  opcode_loop) and the sorted trie commit, plus opt-in ``jax.profiler``
  trace capture around a prove.
- ``roofline``: XLA cost-model FLOPs/bytes per compiled STARK phase
  program combined with measured wall-clock into achieved-FLOP/s and
  utilization-vs-peak estimates.
- ``hlo_introspect`` / ``occupancy``: per-kernel collective/reshard
  accounting from the compiled programs' HLO, and device-occupancy
  timelines for the parallel prover (docs/PERFORMANCE.md "Reading the
  scaling autopsy").
- ``chain_path``: measured queues over the transaction pipeline, a
  sampled per-tx lifecycle and the live ``block_inclusion_tps`` gauge
  (docs/OBSERVABILITY.md "Chain-path telemetry").
- ``loadgen``: the open-loop load harness for the JSON-RPC front door
  (``python -m ethrex_tpu.perf.loadgen``).

Everything here but ``loadgen`` is telemetry and sits behind the
never-raise contract: a failing hook degrades to missing numbers, never
a failed prove or import.
"""

from . import profiler, roofline  # noqa: F401
