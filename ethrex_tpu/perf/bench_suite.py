"""The bench suite: BASELINE measurements, the append-only history, and
the CI regression gate.  The repo-root ``bench.py`` is a thin CLI shim
over this module.

Headline: BASELINE config 1 — prove a 10-transfer block end-to-end on
one TPU chip — plus BASELINE configs 2/4/5 attached to the same JSON
line when the chip budget allows.

The measured quantity is the full `--prover tpu` pipeline on a real
committed batch: stateless re-execution, per-tx fine-log derivation, and
the DEEP-FRI STARKs (state-update circuit, VM circuits, output binding),
exactly what `TpuBackend.prove` ships to the proof coordinator, followed
by an independent `verify`.

Configs (BASELINE.md):
  1 (headline)      10-transfer block, vm mode, 3 STARKs
  2 (--measure-2)   100-tx ERC-20 batch, token mode, 4 STARKs
  3 (BENCH_FULL=1)  1000-tx mixed transfer+token batch (opt-in: hours of
                    compile on a cold cache)
  4 (--measure-4)   Groth16 BN254 wrap (format=groth16 on the config-1
                    batch: aggregation + wrap + full verify)
  5 (--measure-5)   8-proof recursive aggregation (8 sponge STARKs in
                    ONE outer FriVerifyAir proof, verified)

Host-side configs (chip-independent): --measure-mgas (L1 pipelined
import throughput) and --measure-serving (open-loop JSON-RPC serving
sweep via perf/loadgen — client-observed p50/p95/p99 + error rate at
each offered rate over real TCP against a live in-process node, gated
on p99 and sustained rate).

Cold start: --measure-warmup runs the cold-vs-hydrated warmup drill —
two child processes share one emptied executable-cache dir (via
ETHREX_EXEC_CACHE_DIR, under <cache root>/warmup_drill), the first
compiling and serializing the AOT
executable, the second hydrating it — and appends a gateable
`stark_core_warmup_hydrated_s` record (lower is better) carrying both
warmup walls (`warmup_s`).  --measure-warmup-child is the per-process
entry point.

Mesh scaling: --measure-scaling sweeps the prove-core cells/s at
1/2/4/8 simulated host devices (one forced-CPU child per count via
XLA_FLAGS=--xla_force_host_platform_device_count; list overridable
with BENCH_SCALING_DEVICES) and appends ONE history record whose
`devices`/`scaling` fields keep it out of the same-backend regression
gates.  --measure-scaling-one is the per-count child entry point.

vs_baseline is a measured-vs-measured gas rate: the reference's SP1-CUDA
prover does a 7,898,434-gas mainnet block in 143 s on an RTX 4090
(/root/reference/docs/l2/bench/prover_performance.md:7-9) = 55,234 gas/s;
we report (batch_gas / wall_s) / 55,234.

No chip, no record: the default mode refuses to run where JAX finds
only the CPU — a message on stderr, exit code 3, nothing on stdout.
Otherwise the parent (which never imports JAX, so the one chip stays
free for its children) runs the ``--measure`` child and then the
sub-config children one after another.  ``BENCH_ALLOW_CPU=1`` is the
one explicit way tests run a ``--measure-*`` mode on the CPU, and a
record so made says ``platform: cpu``.  Every final record is appended
to ``bench_history.jsonl`` (one JSON object per line, with ts +
backend); the regression gate reads same-backend pairs out of it.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...,
"backend", "stages", "configs": {...}}.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

BASELINE_GAS_PER_SEC = 7_898_434 / 143.0
BASELINE_CELLS_PER_SEC = 1.0e8  # round-1/2 estimated anchor (fallback only)
# this module lives at ethrex_tpu/perf/bench_suite.py; the CLI shim and
# the state files live at the repo root next to it
_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
BENCH_PATH = os.path.join(_REPO_ROOT, "bench.py")
HISTORY_PATH = os.path.join(_REPO_ROOT, "bench_history.jsonl")
ATTEMPT_TIMEOUT = int(os.environ.get("BENCH_TIMEOUT", "3000"))
NUM_TXS = int(os.environ.get("BENCH_TXS", "10"))

def _guard_backend() -> None:
    """Every --measure-* child starts here: refuse to publish from the
    CPU unless BENCH_ALLOW_CPU=1 says the caller (a test) wants that."""
    allow_cpu = os.environ.get("BENCH_ALLOW_CPU") == "1"
    if allow_cpu:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if jax.default_backend() == "cpu" and not allow_cpu:
        print("backend is cpu, refusing to publish", file=sys.stderr)
        sys.exit(3)
    from ethrex_tpu.utils.jax_cache import enable_persistent_cache

    enable_persistent_cache()


def measure() -> None:
    """BASELINE config 1: one block of NUM_TXS plain transfers, proven
    end-to-end and independently verified."""
    _guard_backend()

    import jax

    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.guest.witness import generate_witness
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.prover.tpu_backend import TpuBackend

    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(secp256k1.pubkey_from_secret(secret))
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }
    node = Node(Genesis.from_json(genesis))
    for n in range(NUM_TXS):
        tx = Transaction(
            tx_type=2, chain_id=1337, nonce=n,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=21_000, to=bytes([0x50 + n]) * 20, value=1000 + n,
        ).sign(secret)
        node.submit_transaction(tx)
    block = node.produce_block()
    gas = block.header.gas_used
    witness = generate_witness(node.chain, [block])
    pi = ProgramInput(blocks=[block], witness=witness, config=node.config)

    backend = TpuBackend()
    # one warm-up prove compiles (or hydrates from the on-disk
    # executable cache) every XLA program before the timed section;
    # warmup_s + the cache hit/miss split record which one happened
    t_w0 = time.perf_counter()
    warm = backend.prove(pi, "stark")
    warmup_wall = time.perf_counter() - t_w0
    assert warm.get("vm", {}).get("mode") == "transfer"

    from ethrex_tpu.utils import exec_cache, tracing

    t0 = time.perf_counter()
    with tracing.span("bench.prove") as bench_span:
        proof = backend.prove(pi, "stark")
    wall = time.perf_counter() - t0
    if not backend.verify(proof):
        print("self-verification failed", file=sys.stderr)
        sys.exit(4)

    # per-stage breakdown from the profiling spans of the timed prove
    stages = {}
    critical = {}
    if bench_span is not None:
        stages = {k: round(v, 4) for k, v in sorted(
            tracing.TRACER.stage_breakdown(bench_span.trace_id).items())}
        # critical-path attribution of the same trace: unlike "stages"
        # (which sums possibly-overlapping stage spans), these components
        # partition the wall, so they answer WHICH leg dominated
        cp = tracing.critical_path(
            tracing.TRACER.get_trace(bench_span.trace_id))
        critical = {k: round(v, 4) for k, v in sorted(
            cp.get("components", {}).items())}

    cache_stats = exec_cache.runtime_stats()
    gas_per_sec = gas / wall
    print(json.dumps({
        "metric": "transfer_batch_prove_wall_s",
        "value": round(wall, 3),
        "unit": "s",
        "vs_baseline": round(gas_per_sec / BASELINE_GAS_PER_SEC, 4),
        "batch_gas": gas,
        "num_txs": NUM_TXS,
        "gas_per_sec": round(gas_per_sec, 1),
        "proofs_per_hour_chip": round(3600.0 / wall, 2),
        "warmup_s": round(warmup_wall, 3),
        "executable_cache": {k: cache_stats.get(k) for k in
                             ("hits", "misses", "errors", "stores")},
        "stages": stages,
        "critical_path": critical,
        "config": "BASELINE-1 (10-transfer block, vm mode, 3 STARKs)",
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }))


def _token_genesis(sender):
    from ethrex_tpu.guest import token_template as tt

    token = bytes.fromhex("7070" * 10)
    storage = {hex(tt.balance_slot(sender)): hex(10**15)}
    return token, {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {
            "0x" + sender.hex(): {"balance": hex(10**21)},
            "0x" + token.hex(): {"balance": "0x0",
                                 "code": "0x" + tt.TEMPLATE_CODE.hex(),
                                 "storage": storage},
        },
        "gasLimit": hex(60_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }


def _span_stages(bench_span) -> dict:
    """Stage breakdown of one timed region from its trace's spans."""
    from ethrex_tpu.utils import tracing

    if bench_span is None:
        return {}
    return {k: round(v, 4) for k, v in sorted(
        tracing.TRACER.stage_breakdown(bench_span.trace_id).items())}


def measure_config2() -> None:
    """BASELINE config 2: a 100-tx ERC-20 batch, token mode, proven
    end-to-end (state + transfer + token + binding STARKs), verified."""
    _guard_backend()

    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.guest import token_template as tt
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.guest.witness import generate_witness
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.prover.tpu_backend import TpuBackend
    from ethrex_tpu.utils import tracing

    n_txs = int(os.environ.get("BENCH_ERC20_TXS", "100"))
    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    token, genesis = _token_genesis(sender)
    node = Node(Genesis.from_json(genesis))
    for n in range(n_txs):
        node.submit_transaction(Transaction(
            tx_type=2, chain_id=1337, nonce=n,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=100_000, to=token, value=0,
            data=tt.transfer_calldata(bytes([0x60 + n % 16]) * 20,
                                      100 + n)).sign(secret))
    block = node.produce_block()
    gas = block.header.gas_used
    assert len(block.body.transactions) == n_txs
    witness = generate_witness(node.chain, [block])
    pi = ProgramInput(blocks=[block], witness=witness, config=node.config)
    backend = TpuBackend()
    warm = backend.prove(pi, "stark")
    assert warm.get("vm", {}).get("mode") == "token"
    t0 = time.perf_counter()
    with tracing.span("bench.prove") as bench_span:
        proof = backend.prove(pi, "stark")
    wall = time.perf_counter() - t0
    if not backend.verify(proof):
        print("self-verification failed", file=sys.stderr)
        sys.exit(4)
    print(json.dumps({
        "metric": "erc20_batch_prove_wall_s", "value": round(wall, 3),
        "unit": "s",
        "vs_baseline": round((gas / wall) / BASELINE_GAS_PER_SEC, 4),
        "batch_gas": gas, "num_txs": n_txs,
        "gas_per_sec": round(gas / wall, 1),
        "stages": _span_stages(bench_span),
        "config": "BASELINE-2 (100-tx ERC-20 batch, token mode, 4 STARKs)",
    }))


def _phase_compile_walls() -> dict:
    """Per-phase-program AOT compile seconds ("Air/kernel", suffixed
    "@<mesh>" on mesh builds) from the in-process metrics registry —
    populated by a warmup prove's phase-program builds
    (stark/prover.py _aot_phases), single-device and mesh paths alike.
    Gives the cold-start item-2 work a per-program baseline to beat."""
    from ethrex_tpu.utils.metrics import METRICS

    out: dict = {}
    snap = METRICS.snapshot()
    hist = (snap.get("histograms") or {}).get(
        "prover_phase_compile_seconds") or {}
    for row in hist.get("series", []):
        lab = row.get("labels", {})
        key = "{}/{}".format(lab.get("air", "?"), lab.get("kernel", "?"))
        if lab.get("mesh", "none") != "none":
            key += "@" + lab["mesh"]
        out[key] = round(out.get(key, 0.0) + float(row.get("sum", 0.0)), 4)
    return out


def measure_config4() -> None:
    """BASELINE config 4: Groth16 BN254 wrap — format=groth16 on the
    config-1 batch (aggregation + R1CS wrap + pairing verify).  The
    warmup's compile cost is broken down per phase program in the
    record's `phase_compile` map."""
    _guard_backend()

    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.guest.witness import generate_witness
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.prover.tpu_backend import TpuBackend
    from ethrex_tpu.utils import tracing

    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }
    node = Node(Genesis.from_json(genesis))
    for n in range(NUM_TXS):
        node.submit_transaction(Transaction(
            tx_type=2, chain_id=1337, nonce=n,
            max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
            gas_limit=21_000, to=bytes([0x50 + n]) * 20,
            value=1000 + n).sign(secret))
    block = node.produce_block()
    witness = generate_witness(node.chain, [block])
    pi = ProgramInput(blocks=[block], witness=witness, config=node.config)
    backend = TpuBackend()
    t_w0 = time.perf_counter()
    warm = backend.prove(pi, "groth16")
    warmup_wall = time.perf_counter() - t_w0
    assert "groth16" in warm
    t0 = time.perf_counter()
    with tracing.span("bench.prove") as bench_span:
        proof = backend.prove(pi, "groth16")
    wall = time.perf_counter() - t0
    if not backend.verify(proof):
        print("self-verification failed", file=sys.stderr)
        sys.exit(4)
    print(json.dumps({
        "metric": "groth16_wrap_prove_wall_s", "value": round(wall, 3),
        "unit": "s", "vs_baseline": 0.0,
        "batch_gas": block.header.gas_used,
        "stages": _span_stages(bench_span),
        "warmup_wall_s": round(warmup_wall, 3),
        "phase_compile": _phase_compile_walls(),
        "config": "BASELINE-4 (config-1 batch, compressed + Groth16 wrap)",
    }))


def measure_config5() -> None:
    """BASELINE config 5: 8-proof recursive aggregation — eight sponge
    STARKs proven, then ONE outer FriVerifyAir STARK covering every FRI
    query opening of all eight; verify_aggregated must accept."""
    _guard_backend()

    from ethrex_tpu.models import poseidon2_air as pair
    from ethrex_tpu.stark import aggregate as agg_mod
    from ethrex_tpu.stark import prover as stark_prover
    from ethrex_tpu.stark.prover import StarkParams
    from ethrex_tpu.utils import tracing

    params = StarkParams(log_blowup=3, num_queries=40, log_final_size=4)
    airs, proofs = [], []
    for i in range(8):
        limbs = pair.pad_message_limbs(list(range(16 * (i + 1))))
        air = pair.Poseidon2SpongeAir(num_chunks=len(limbs) // 8)
        trace = pair.generate_sponge_trace(limbs)
        pub = pair.sponge_public_inputs(limbs)
        proofs.append(stark_prover.prove(air, trace, pub, params))
        airs.append(air)
    # warm-up aggregation compiles the outer AIR's phase programs
    agg_mod.aggregate(airs, proofs, params)
    t0 = time.perf_counter()
    with tracing.span("bench.prove") as bench_span:
        agg = agg_mod.aggregate(airs, proofs, params)
    wall = time.perf_counter() - t0
    agg_mod.verify_aggregated(airs, agg, params)
    print(json.dumps({
        "metric": "aggregate8_prove_wall_s", "value": round(wall, 3),
        "unit": "s", "vs_baseline": 0.0,
        "stages": _span_stages(bench_span),
        "config": "BASELINE-5 (8 STARKs -> one outer recursion proof)",
    }))


def measure_config3() -> None:
    """BASELINE config 3 (opt-in, BENCH_FULL=1): 1000-tx mixed batch —
    500 transfers + 500 token calls across blocks."""
    _guard_backend()

    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.guest import token_template as tt
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.guest.witness import generate_witness
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.prover.tpu_backend import TpuBackend
    from ethrex_tpu.utils import tracing

    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    token, genesis = _token_genesis(sender)
    node = Node(Genesis.from_json(genesis))
    nonce = 0
    blocks = []
    for _ in range(4):   # 4 blocks x 250 txs
        for i in range(125):
            node.submit_transaction(Transaction(
                tx_type=2, chain_id=1337, nonce=nonce,
                max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
                gas_limit=21_000, to=bytes([0x50 + i % 32]) * 20,
                value=100 + i).sign(secret))
            nonce += 1
            node.submit_transaction(Transaction(
                tx_type=2, chain_id=1337, nonce=nonce,
                max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
                gas_limit=100_000, to=token, value=0,
                data=tt.transfer_calldata(bytes([0x60 + i % 16]) * 20,
                                          10 + i)).sign(secret))
            nonce += 1
        blocks.append(node.produce_block())
    gas = sum(b.header.gas_used for b in blocks)
    witness = generate_witness(node.chain, blocks)
    pi = ProgramInput(blocks=blocks, witness=witness, config=node.config)
    backend = TpuBackend()
    warm = backend.prove(pi, "stark")
    assert warm.get("vm", {}).get("mode") == "token"
    t0 = time.perf_counter()
    with tracing.span("bench.prove") as bench_span:
        proof = backend.prove(pi, "stark")
    wall = time.perf_counter() - t0
    if not backend.verify(proof):
        sys.exit(4)
    print(json.dumps({
        "metric": "mixed1000_batch_prove_wall_s", "value": round(wall, 3),
        "unit": "s",
        "vs_baseline": round((gas / wall) / BASELINE_GAS_PER_SEC, 4),
        "batch_gas": gas, "num_txs": 1000,
        "stages": _span_stages(bench_span),
        "config": "BASELINE-3 (1000-tx mixed batch)",
    }))


def measure_mgas() -> None:
    """L1 execution-throughput microbench (reference anchor: ~669 Mgas/s
    live import on its bench box, docs/perf/README.md:126-131): build a
    chain of full transfer blocks, then re-import it through the
    PIPELINED path (execute N+1 while N merkleizes in the native C++
    MPT engine) into a fresh store.  Host CPU only — no TPU needed."""
    import os as _os

    _os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ethrex_tpu.blockchain.blockchain import Blockchain
    from ethrex_tpu.blockchain.fork_choice import apply_fork_choice
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.perf.profiler import PROFILER
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.storage.store import Store

    from ethrex_tpu.blockchain.mempool import MAX_SENDER_SLOTS

    num_blocks = int(os.environ.get("BENCH_MGAS_BLOCKS", "20"))
    txs_per_block = int(os.environ.get("BENCH_MGAS_TXS", "400"))
    # enough senders that no one holds more than the mempool's per-sender
    # slot cap while a block's worth of txs queues (the cap is overload
    # protection on the serving path; the untimed chain build here must
    # live within it, not bypass it)
    n_senders = -(-txs_per_block // MAX_SENDER_SLOTS)
    secrets = [0xA11CE + i for i in range(n_senders)]
    senders = [secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(s)) for s in secrets]
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + a.hex(): {"balance": hex(10**24)}
                  for a in senders},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }
    node = Node(Genesis.from_json(genesis))
    nonces = [0] * n_senders
    blocks = []
    for _ in range(num_blocks):
        for i in range(txs_per_block):
            s = i % n_senders
            node.submit_transaction(Transaction(
                tx_type=2, chain_id=1337, nonce=nonces[s],
                max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
                gas_limit=21_000, to=bytes([0x50 + i % 64]) * 20,
                value=1 + i).sign(secrets[s]))
            nonces[s] += 1
        blocks.append(node.produce_block())
    gas = sum(b.header.gas_used for b in blocks)
    # RLP round-trip so the import is COLD, like a real sync: the chain
    # build above cached every tx's sender; re-decoding drops those
    # caches, so the timed region pays (batched, parallel) signature
    # recovery like a node importing a chain file would
    from ethrex_tpu.primitives.block import Block as _Block
    blocks = [_Block.decode(b.encode()) for b in blocks]
    # fresh store, re-import through full validation (pipelined)
    store = Store()
    gh = store.init_genesis(Genesis.from_json(genesis))
    chain = Blockchain(store, node.config)
    # stage attribution: the import path feeds the continuous profiler
    # (execute / merkleize / store_write + the evm sig_recovery /
    # opcode_loop split); deltas around the timed region isolate this
    # import from the chain build above
    before = PROFILER.stage_totals("l1_import")
    before_evm = PROFILER.stage_totals("evm")
    t0 = time.perf_counter()
    chain.add_blocks_pipelined(blocks)
    wall = time.perf_counter() - t0
    after = PROFILER.stage_totals("l1_import")
    after_evm = PROFILER.stage_totals("evm")
    stages = {k: round(after.get(k, 0.0) - before.get(k, 0.0), 4)
              for k in sorted(set(after) | set(before))
              if after.get(k, 0.0) - before.get(k, 0.0) > 0}
    stages.update({
        f"evm/{k}": round(after_evm.get(k, 0.0) - before_evm.get(k, 0.0), 4)
        for k in sorted(set(after_evm) | set(before_evm))
        if after_evm.get(k, 0.0) - before_evm.get(k, 0.0) > 0})
    apply_fork_choice(store, blocks[-1].hash)
    assert store.head_header().hash == blocks[-1].hash
    from ethrex_tpu.crypto import native_secp256k1
    print(json.dumps({
        "metric": "l1_import_mgas_per_sec",
        "value": round(gas / wall / 1e6, 2),
        "unit": "Mgas/s",
        "vs_baseline": round((gas / wall / 1e6) / 669.0, 4),
        "blocks": num_blocks, "txs": num_blocks * txs_per_block,
        "batch_gas": gas, "wall_s": round(wall, 3),
        "native_secp256k1": native_secp256k1.available(),
        "stages": stages or {"import": round(wall, 4)},
        "config": "L1 pipelined import (cold senders), ETH transfers "
                  "(ref anchor 669 Mgas/s, docs/perf/README.md:126-131)",
    }))


def measure_core() -> None:
    """Fallback microbench: fully-jitted prove-core throughput (the round
    1-2 metric, against its documented estimated anchor), now AOT-
    compiled so the record pairs measured cells/s with the kernel's
    static FLOPs and a utilization-vs-peak estimate."""
    _guard_backend()
    import jax

    from ethrex_tpu.parallel.core import compile_prove_step
    from ethrex_tpu.perf import roofline

    t_c0 = time.perf_counter()
    fn, args, cost = compile_prove_step(log_n=15, width=64, log_blowup=2,
                                        log_final_size=5, mesh=None)
    jax.block_until_ready(fn(*args))
    t_compile = time.perf_counter() - t_c0
    runs = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    wall = min(runs)
    value = (1 << 15) * 64 / wall
    parsed = roofline._parse_cost(cost)
    flops = parsed.get("flops")
    peak = roofline.peak_flops_estimate()
    achieved = flops / wall if flops and wall > 0 else None
    out = {
        "metric": "stark_prove_core_trace_cells_per_sec",
        "value": round(value, 1),
        "unit": "cells/s",
        "vs_baseline": round(value / BASELINE_CELLS_PER_SEC, 4),
        "stages": {"compile_and_warmup": round(t_compile, 4),
                   "best_of_5_runs": round(wall, 4)},
        "note": "fallback microbench; baseline anchor is an estimate",
    }
    if flops:
        out["flops"] = flops
        out["achieved_flops_per_sec"] = round(achieved, 1)
        out["utilization_vs_peak"] = round(achieved / peak, 6) \
            if peak else None
    print(json.dumps(out))


def measure_warmup_child() -> None:
    """One warmup sample for the cold-start drill: compile (or hydrate)
    the core microbench config and run it once.  The parent
    --measure-warmup spawns this twice against one executable-cache dir
    — first cold (populating it), then hydrated — and the
    executable_cache hit/miss split proves which path each child took."""
    _guard_backend()
    import jax

    from ethrex_tpu.parallel.core import compile_prove_step
    from ethrex_tpu.utils import exec_cache

    t0 = time.perf_counter()
    fn, args, _cost = compile_prove_step(log_n=15, width=64, log_blowup=2,
                                         log_final_size=5, mesh=None)
    jax.block_until_ready(fn(*args))
    warmup = time.perf_counter() - t0
    stats = exec_cache.runtime_stats()
    print(json.dumps({
        "metric": "stark_core_warmup_s",
        "value": round(warmup, 4),
        "unit": "s",
        "backend": jax.default_backend(),
        "stages": {"compile_and_warmup": round(warmup, 4)},
        "executable_cache": {k: stats.get(k) for k in
                             ("hits", "misses", "errors", "stores")},
    }))


def measure_warmup() -> None:
    """Cold-vs-hydrated warmup drill (ROADMAP item 2's yardstick): two
    child processes share one FRESH executable-cache dir — child A pays
    the full AOT compile and serializes it, child B must hydrate.  Emits
    and appends ONE record whose gateable value is the HYDRATED warmup
    (lower is better; the same-backend history gate keeps the cold-start
    win locked in) with the cold wall and the speedup alongside."""
    import shutil

    from ethrex_tpu.utils.jax_cache import cache_dir as cache_root

    t0 = time.perf_counter()
    # a fixed sub-directory of the cache root, emptied first: the drill
    # needs a FRESH cache, not an unpredictable path.  The XLA
    # persistent cache must be fresh too: an XLA-cache-hit compile
    # serializes without its jit symbols, so the cold child's store
    # would be rejected at validation and the drill would measure
    # hit-vs-hit instead of cold-vs-hydrated
    drill = os.path.join(cache_root(), "warmup_drill")
    shutil.rmtree(drill, ignore_errors=True)
    env = {"ETHREX_EXEC_CACHE_DIR": os.path.join(drill, "exec"),
           "JAX_COMPILATION_CACHE_DIR": os.path.join(drill, "xla")}
    cold = _attempt("--measure-warmup-child",
                    min(EXTRA_TIMEOUT, 1500), env=env) \
        or {"_err": "no output"}
    hydrated = _attempt("--measure-warmup-child",
                        min(EXTRA_TIMEOUT, 1500), env=env) \
        or {"_err": "no output"}
    cold_s = cold.get("value")
    hyd_s = hydrated.get("value")
    ok = (isinstance(cold_s, (int, float)) and cold_s > 0
          and isinstance(hyd_s, (int, float)) and hyd_s > 0)
    record = {
        "metric": "stark_core_warmup_hydrated_s",
        "value": round(float(hyd_s), 4) if ok else 0.0,
        "unit": "s",
        "backend": (hydrated.get("backend") or cold.get("backend")
                    or "unknown"),
        "warmup_s": {"cold": cold_s, "hydrated": hyd_s},
        "stages": {"warmup_cold_s": cold_s, "warmup_hydrated_s": hyd_s,
                   "drill_s": round(time.perf_counter() - t0, 4)},
        "executable_cache": {"cold": cold.get("executable_cache"),
                             "hydrated": hydrated.get("executable_cache")},
        "config": "cold-vs-hydrated warmup drill (core microbench "
                  "config, two children sharing one fresh "
                  "executable-cache dir)",
    }
    if ok:
        record["speedup_x"] = round(float(cold_s) / float(hyd_s), 2)
    else:
        record["error"] = (cold.get("_err") or hydrated.get("_err")
                           or "child produced no warmup value")
    append_history(record)
    print(json.dumps(record))


def _scaling_prove_autopsy(ndev: int, mesh) -> dict:
    """Per-kernel autopsy for one scaling child: a small FibonacciAir
    prove on the child's mesh populates per-kernel AOT compile walls
    (prover_phase_compile_seconds), measured walls (roofline), and HLO
    collective accounting (perf/hlo_introspect.py); a second,
    steady-state prove gives the wall the occupancy estimate is read
    against.  Occupancy here is the single-lane host-idle signal: the
    fraction of the prove wall spent inside the four device kernels
    (the rest is host orchestration — Merkle paths, transcript, FRI
    queries), computed through perf/occupancy.compute so the same
    interval math the parallel prover uses carries the bench number.
    BENCH_SCALING_PROVE_ROWS sizes the trace (default 128 rows)."""
    from ethrex_tpu.models import fibonacci as fib
    from ethrex_tpu.parallel import mesh as mesh_lib
    from ethrex_tpu.perf import hlo_introspect
    from ethrex_tpu.perf import occupancy as occ_mod
    from ethrex_tpu.perf.roofline import ROOFLINE
    from ethrex_tpu.stark import prover as stark_prover
    from ethrex_tpu.stark.prover import StarkParams

    rows = int(os.environ.get("BENCH_SCALING_PROVE_ROWS", "128"))
    air = fib.FibonacciAir()
    trace = fib.generate_trace(rows)
    pub = fib.public_inputs(trace)
    params = StarkParams(log_blowup=2, num_queries=8, log_final_size=4)
    t0 = time.perf_counter()
    stark_prover.prove(air, trace, pub, params, mesh=mesh)
    warm_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    stark_prover.prove(air, trace, pub, params, mesh=mesh)
    prove_wall = time.perf_counter() - t1

    compile_walls = _phase_compile_walls()
    mesh_label = mesh_lib.shape_label(mesh)
    suffix = "" if mesh_label == "none" else "@" + mesh_label
    intro = {(k["air"], k["kernel"]): k
             for k in hlo_introspect.REGISTRY.report()["kernels"]}
    kernels: dict = {}
    intervals = []
    acc = 0.0
    for row in ROOFLINE.report()["kernels"]:
        if row["air"] != "FibonacciAir":
            continue
        k = row["kernel"]
        wall = row.get("wallLastSeconds") or 0.0
        ir = intro.get(("FibonacciAir", k), {})
        kernels[k] = {
            "wall_s": round(wall, 6),
            "compile_s": compile_walls.get(f"FibonacciAir/{k}{suffix}"),
            "collective_ops": ir.get("collectiveOps", 0),
            "collective_bytes": ir.get("crossDeviceBytes", 0),
            "copy_ops": ir.get("copyOps", 0),
            "hbm_bytes": ir.get("hbmPeakBytes"),
        }
        if wall > 0:
            intervals.append((acc, acc + wall))
            acc += wall
    occ = occ_mod.compute(
        {"0": {"intervals": intervals, "devices": ndev}},
        devices=ndev, window=(0.0, max(prove_wall, acc)))
    return {
        "kernels": kernels,
        "occupancy": {
            "fraction": round(occ["occupancy"], 4),
            "idle_gap_s": round(occ["idleGapSeconds"], 4),
            "busy_device_s": round(occ["busyDeviceSeconds"], 4),
            "wall_s": round(occ["wallSeconds"], 4),
            "devices": ndev,
        },
        "prove_wall_s": round(prove_wall, 4),
        "prove_warmup_s": round(warm_s, 4),
        "prove_rows": rows,
    }


def measure_scaling_one() -> None:
    """One scaling sample: prove-core cells/s with the trace sharded
    across EVERY visible device, plus the per-kernel autopsy fields the
    parent's explain_scaling diff consumes ({wall, compile, collective
    ops/bytes, HBM bytes} per kernel and a device-occupancy estimate —
    docs/PERFORMANCE.md "Reading the scaling autopsy").  The parent
    sweep (--measure-scaling) controls the device count by spawning
    this in a child process with
    XLA_FLAGS=--xla_force_host_platform_device_count=N; on one device
    the headline degrades to exactly the --measure-core configuration.
    BENCH_SCALING_LOG_N sizes the fused core step (default 2^15 rows)."""
    _guard_backend()
    import jax

    from ethrex_tpu.parallel import mesh as mesh_lib
    from ethrex_tpu.parallel.core import compile_prove_step

    ndev = len(jax.devices())
    mesh = mesh_lib.make_mesh() if ndev > 1 else None
    log_n = int(os.environ.get("BENCH_SCALING_LOG_N", "15"))
    t_c0 = time.perf_counter()
    fn, args, _cost = compile_prove_step(log_n=log_n, width=64,
                                         log_blowup=2,
                                         log_final_size=5, mesh=mesh)
    jax.block_until_ready(fn(*args))
    t_compile = time.perf_counter() - t_c0
    runs = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        runs.append(time.perf_counter() - t0)
    wall = min(runs)
    value = (1 << log_n) * 64 / wall
    # the autopsy prove is additive telemetry: its failure degrades the
    # child record to the pre-autopsy shape, never kills the sample
    try:
        autopsy = _scaling_prove_autopsy(ndev, mesh)
    except Exception as exc:  # pragma: no cover - degradation path
        autopsy = {"error": f"{type(exc).__name__}: {exc}"}
    print(json.dumps({
        "metric": "stark_prove_core_trace_cells_per_sec",
        "value": round(value, 1),
        "unit": "cells/s",
        "devices": ndev,
        "stages": {"compile_and_warmup": round(t_compile, 4),
                   "best_of_5_runs": round(wall, 4)},
        "kernels": autopsy.get("kernels", {}),
        "occupancy": autopsy.get("occupancy", {}),
        "prove_wall_s": autopsy.get("prove_wall_s"),
        "autopsy_error": autopsy.get("error"),
    }))


def _default_ici_gbps() -> float:
    try:
        from ethrex_tpu.perf import hlo_introspect

        return hlo_introspect.ici_gbps()
    except Exception:
        return 75.0


def explain_scaling(sweep: dict, ici_gbps: "float | None" = None) -> dict:
    """Pure 1-vs-N scaling autopsy over the sweep's child records.

    ``sweep`` maps str(device_count) -> the child JSON from
    --measure-scaling-one.  The baseline is the smallest device count
    carrying kernel data, the target the largest; for each kernel the
    wall delta is attributed across the regressor classes the autopsy
    can see — estimated collective seconds (collective bytes over the
    ETHREX_ICI_GBPS interconnect anchor), compile multiplication, and
    occupancy (host-idle) drop — and the dominant regressor is named
    per kernel and for the whole target wall.  Unit-testable with
    synthetic records; returns {"error": ...} when fewer than two
    samples carry kernels."""
    gbps = float(ici_gbps) if ici_gbps else _default_ici_gbps()

    usable = {}
    for key, rec in (sweep or {}).items():
        try:
            nd = int(key)
        except (TypeError, ValueError):
            continue
        if isinstance(rec, dict) and isinstance(rec.get("kernels"), dict) \
                and rec["kernels"]:
            usable[nd] = rec
    if len(usable) < 2:
        return {"error": "need kernel data at >= 2 device counts",
                "sampled": sorted(usable)}
    base_n, tgt_n = min(usable), max(usable)
    base, tgt = usable[base_n], usable[tgt_n]

    kernels: dict = {}
    total_delta = 0.0
    total_coll_s = 0.0
    for k, trow in tgt["kernels"].items():
        brow = base["kernels"].get(k) or {}
        bw = brow.get("wall_s") or 0.0
        tw = trow.get("wall_s") or 0.0
        delta = tw - bw
        coll_bytes = float(trow.get("collective_bytes") or 0)
        coll_s = coll_bytes / (gbps * 1e9)
        bc, tc = brow.get("compile_s"), trow.get("compile_s")
        compile_ratio = round(tc / bc, 2) if bc and tc else None
        coll_share = min(1.0, coll_s / delta) if delta > 0 else 0.0
        regressor = "collectives" if delta > 0 and coll_share >= 0.5 \
            else ("wall" if delta > 0 else "none")
        pct = round(100.0 * delta / bw, 1) if bw > 0 else None
        bits = []
        if pct is not None:
            bits.append(f"{pct:+.0f}% wall")
        if delta > 0 and coll_bytes:
            bits.append(f"{100.0 * coll_share:.0f}% of delta is "
                        "collective bytes")
        if compile_ratio is not None:
            bits.append(f"compile x{compile_ratio:.1f}")
        kernels[k] = {
            "baselineWallSeconds": bw, "targetWallSeconds": tw,
            "wallDeltaSeconds": round(delta, 6), "wallDeltaPct": pct,
            "collectiveOps": trow.get("collective_ops", 0),
            "collectiveBytes": coll_bytes,
            "estCollectiveSeconds": round(coll_s, 6),
            "collectiveShareOfDelta": round(coll_share, 4),
            "compileRatio": compile_ratio,
            "regressor": regressor,
            "summary": f"{k}: " + "; ".join(bits) if bits else k,
        }
        if delta > 0:
            total_delta += delta
            total_coll_s += min(coll_s, delta)

    base_occ = ((base.get("occupancy") or {}).get("fraction"))
    tgt_occ = ((tgt.get("occupancy") or {}).get("fraction"))
    occ_drop = (base_occ - tgt_occ) \
        if isinstance(base_occ, (int, float)) \
        and isinstance(tgt_occ, (int, float)) else None

    dominant_kernel = max(
        kernels, key=lambda k: kernels[k]["wallDeltaSeconds"],
        default=None)
    if total_delta > 0 and total_coll_s / total_delta >= 0.5:
        dom_class = "collectives"
    elif occ_drop is not None and occ_drop >= 0.3:
        dom_class = "idle"
    elif total_delta > 0:
        dom_class = kernels[dominant_kernel]["regressor"] \
            if dominant_kernel else "wall"
    else:
        dom_class = "none"
    dom_summary = kernels[dominant_kernel]["summary"] \
        if dominant_kernel and total_delta > 0 else \
        f"no kernel wall regressed from {base_n} to {tgt_n} devices"

    bv, tv = base.get("value"), tgt.get("value")
    ratio = round(tv / bv, 3) \
        if isinstance(bv, (int, float)) and bv \
        and isinstance(tv, (int, float)) else None
    return {
        "baselineDevices": base_n, "targetDevices": tgt_n,
        "headline": {"baseline": bv, "target": tv,
                     "targetOverBaseline": ratio},
        "kernels": kernels,
        "occupancy": {"baseline": base_occ, "target": tgt_occ,
                      "drop": round(occ_drop, 4)
                      if occ_drop is not None else None},
        "dominant": {"kernel": dominant_kernel, "regressor": dom_class,
                     "summary": dom_summary},
        "iciGbpsAssumed": gbps,
    }


def measure_scaling() -> None:
    """Multi-device scaling sweep: prove-core cells/s at 1/2/4/8
    simulated host devices (BENCH_SCALING_DEVICES overrides the list),
    one child process per count so each run gets a fresh XLA device
    topology.  Each child also emits the per-kernel autopsy fields and
    the record carries `autopsy` = explain_scaling(sweep) — the named
    dominant regressor for the N-device wall (docs/PERFORMANCE.md
    "Reading the scaling autopsy"); the human-readable summary prints
    to stderr (stdout stays the one-JSON-line contract).  Emits — and
    appends to bench_history.jsonl — ONE record whose top-level
    `devices` / `scaling` fields exclude it from the same-backend
    history gates: different device counts are different hardware, not
    a regression signal."""
    counts = [int(c) for c in os.environ.get(
        "BENCH_SCALING_DEVICES", "1,2,4,8").split(",") if c.strip()]
    sweep = {}
    t0 = time.perf_counter()
    for nd in counts:
        env = {
            "XLA_FLAGS":
                f"--xla_force_host_platform_device_count={nd}",
            "JAX_PLATFORMS": "cpu",
            "BENCH_ALLOW_CPU": "1",
        }
        res = _attempt("--measure-scaling-one",
                       min(EXTRA_TIMEOUT, 1500), env=env)
        sweep[str(nd)] = res if res is not None else {"error": "no output"}
    best = None
    for nd in counts:
        cand = sweep.get(str(nd)) or {}
        val = cand.get("value")
        if isinstance(val, (int, float)) and (best is None
                                              or val > best[1]):
            best = (nd, float(val))
    try:
        autopsy = explain_scaling(sweep)
    except Exception as exc:  # pragma: no cover - degradation path
        autopsy = {"error": f"{type(exc).__name__}: {exc}"}
    record = {
        "metric": "stark_prove_core_trace_cells_per_sec",
        "value": round(best[1], 1) if best else 0.0,
        "unit": "cells/s",
        "devices": best[0] if best else 0,
        "backend": "cpu",
        "scaling": sweep,
        "autopsy": autopsy,
        "stages": {"sweep_s": round(time.perf_counter() - t0, 4)},
        "config": "scaling sweep (simulated host devices: "
                  + ",".join(str(c) for c in counts)
                  + "; core log_n="
                  + os.environ.get("BENCH_SCALING_LOG_N", "15")
                  + ", autopsy prove rows="
                  + os.environ.get("BENCH_SCALING_PROVE_ROWS", "128")
                  + ")",
    }
    append_history(record)
    dom = autopsy.get("dominant") if isinstance(autopsy, dict) else None
    if isinstance(dom, dict):
        print("scaling autopsy [{}->{} devices] dominant regressor: "
              "{} — {}".format(autopsy.get("baselineDevices"),
                               autopsy.get("targetDevices"),
                               dom.get("regressor"), dom.get("summary")),
              file=sys.stderr)
        for k, row in sorted((autopsy.get("kernels") or {}).items()):
            print("  " + str(row.get("summary")), file=sys.stderr)
    print(json.dumps(record))


def build_serving_record(sweep: dict, setup_s: float = 0.0,
                         sweep_s: float = 0.0,
                         batch: dict | None = None,
                         reference_rate: float | None = None) -> dict:
    """Pure record builder for the serving sweep (unit-testable without
    a live node).  Headline value is the client-observed p99 at the
    highest sustainable offered rate (lower is better); the sustained
    rate itself rides along as a sub-config so the history gate can
    also hold the throughput direction.

    When `reference_rate` is set and the sweep sustains beyond it, the
    headline p99 is taken at the gentlest sustained rate >= the
    reference instead: tail latency is only comparable across history
    at equal offered load, so a server that newly sustains 10x the old
    ceiling must not see its p99 gate judged at the new ceiling while
    the baseline was judged at the old one.  The throughput direction
    is held by the serving_sustained_tps sub-config either way.

    `batch`, when provided, is the JSON-RPC batch-array stage summary
    (offered rate, per-array p99 and the server-side
    rpc_batch_requests_total delta) and rides along unchanged."""
    reports = sweep.get("rates") or []
    sustained = sweep.get("maxSustainableRate")
    pick = None
    for rep in reports:
        if sustained is not None and rep.get("offeredRate") == sustained:
            pick = rep
    if (pick is not None and reference_rate is not None
            and sustained is not None and sustained > reference_rate):
        at_ref = [r for r in reports
                  if reference_rate <= r.get("offeredRate", 0) <= sustained]
        if at_ref:
            pick = min(at_ref, key=lambda r: r.get("offeredRate", 0))
    if pick is None and reports:
        pick = reports[0]   # nothing sustained: report the gentlest rate
    lat = (pick or {}).get("latency") or {}
    stages = {"setup_s": round(setup_s, 4), "sweep_s": round(sweep_s, 4)}
    record = {
        "metric": "serving_rpc_p99_seconds",
        # accepted-request p99 only: shed responses live in a separate
        # histogram, so fast rejections cannot flatter this gate
        "value": round(lat.get("p99") or 0.0, 6),
        "unit": "s",
        "sustained_rate": sustained if sustained is not None else 0.0,
        "shed_rate": (pick or {}).get("shedRate", 0.0),
        "arrivals": sweep.get("arrivals"),
        # the simulated-sender population the sweep ran with: tail
        # latency at 16 senders and at 10k senders are different
        # benchmarks, so the history gate can tell them apart
        "senders": sweep.get("senders"),
        "rates": [{
            "offeredRate": r.get("offeredRate"),
            "achievedRate": r.get("achievedRate"),
            "errorRate": r.get("errorRate"),
            "missed": r.get("missed"),
            "shed": r.get("shed"),
            "shedRate": r.get("shedRate"),
            "p50": (r.get("latency") or {}).get("p50"),
            "p95": (r.get("latency") or {}).get("p95"),
            "p99": (r.get("latency") or {}).get("p99"),
        } for r in reports],
        "stages": stages,
        "backend": "cpu",   # serving is host-side, chip-independent
        "configs": {"serving_rate": {
            "metric": "serving_sustained_tps",
            "value": float(sustained) if sustained else 0.0,
            "unit": "req/s",
        }},
        "config": "open-loop JSON-RPC serving sweep (loadgen Harness, "
                  "real TCP, tx mix, producer thread)",
    }
    if batch is not None:
        record["batch"] = batch
    return record


def measure_serving() -> None:
    """Serving-tail bench: an in-process node behind a real TCP
    RpcServer, a block-producer thread, and the open-loop loadgen
    Harness swept over ≥2 offered rates (BENCH_SERVING_RATES).  Appends
    its own history record — serving is host-side like mgas, so a
    standalone run should still leave a gateable line."""
    import threading

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.perf import loadgen
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.rpc.server import RpcServer

    # the asyncio front door sustains hundreds-to-thousands of req/s on
    # one core, so the default sweep probes the new regime (the old
    # thread-per-connection server toppled past ~30)
    rates = [float(r) for r in os.environ.get(
        "BENCH_SERVING_RATES", "30,100,300,1000").split(",") if r.strip()]
    duration = float(os.environ.get("BENCH_SERVING_DURATION", "3.0"))
    arrivals = os.environ.get("BENCH_SERVING_ARRIVALS", "poisson")
    senders = int(os.environ.get("BENCH_SERVING_SENDERS", "16"))
    batch_rate = float(os.environ.get("BENCH_SERVING_BATCH_RATE", "100"))
    batch_size = int(os.environ.get("BENCH_SERVING_BATCH_SIZE", "8"))
    # the p99 history gate holds at this offered rate (the old serving
    # ceiling) so tail latency is compared at equal load across records
    reference = float(os.environ.get("BENCH_SERVING_REFERENCE_RATE", "30"))

    root = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(loadgen.DEFAULT_KEY))
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + root.hex(): {"balance": hex(10**24)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }
    node = Node(Genesis.from_json(genesis))
    server = RpcServer(node, port=0).start()
    stop = threading.Event()

    def producer():
        while not stop.is_set():
            try:
                node.produce_block()
            except Exception:
                pass
            stop.wait(0.3)

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    try:
        harness = loadgen.Harness(
            f"http://127.0.0.1:{server.port}", key=loadgen.DEFAULT_KEY,
            senders=senders, payload="tx")
        t0 = time.perf_counter()
        harness.setup()
        setup_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        sweep = harness.sweep(rates, duration=duration, arrivals=arrivals)
        sweep_s = time.perf_counter() - t1
        # batch-array stage: one scheduled slot = one JSON-RPC array of
        # `batch_size` reads, dispatched concurrently server-side.  The
        # server and bench share a process, so the METRICS counter
        # delta proves the batch path (not per-request fallback) served
        # the arrays.
        from ethrex_tpu.utils.metrics import METRICS
        t2 = time.perf_counter()
        before = METRICS.snapshot()["counters"]
        batch_rep = loadgen.Harness(
            f"http://127.0.0.1:{server.port}", payload="batch",
            batch_size=batch_size).run(batch_rate, duration, arrivals)
        after = METRICS.snapshot()["counters"]
        batch_s = time.perf_counter() - t2
        batch = {
            "offeredRate": batch_rep["offeredRate"],
            "achievedRate": batch_rep["achievedRate"],
            "batchSize": batch_size,
            "errorRate": batch_rep["errorRate"],
            "shedRate": batch_rep.get("shedRate", 0.0),
            "p99": (batch_rep.get("latency") or {}).get("p99"),
            "rpc_batch_requests_total": (
                after.get("rpc_batch_requests_total", 0.0)
                - before.get("rpc_batch_requests_total", 0.0)),
            "rpc_batch_entries_total": (
                after.get("rpc_batch_entries_total", 0.0)
                - before.get("rpc_batch_entries_total", 0.0)),
        }
    finally:
        stop.set()
        thread.join(timeout=5)
        server.stop()
        node.stop()
    record = build_serving_record(sweep, setup_s, sweep_s, batch=batch,
                                  reference_rate=reference)
    # every measure_* names its stage breakdown inline (tooling lint)
    record.update({"stages": {"setup_s": round(setup_s, 4),
                              "sweep_s": round(sweep_s, 4),
                              "batch_s": round(batch_s, 4)}})
    append_history(record)
    print(json.dumps(record))


def build_inclusion_record(runs: list, queues: dict | None = None,
                           explain: dict | None = None,
                           setup_s: float = 0.0,
                           sweep_s: float = 0.0) -> dict:
    """Pure record builder for the inclusion sweep (unit-testable
    without a live node).  Headline value is the best included-tps
    among offered rates whose run stayed healthy (errors under
    MAX_ERROR_RATE — typed sheds/rejections are NOT errors: admission
    control refusing the overflow is exactly how the best rate is
    found); falls back to the best overall when nothing stayed clean.
    Higher is better.  Per-stage chain-path queue stats and the
    explain_chain_path verdict ride along so a regression in the gate
    comes with its own autopsy."""
    from ethrex_tpu.perf.loadgen import MAX_ERROR_RATE

    rows = []
    for run in runs or []:
        rep = run.get("report") or {}
        rows.append({
            "offeredRate": rep.get("offeredRate"),
            "achievedRate": rep.get("achievedRate"),
            "errorRate": rep.get("errorRate"),
            "shed": rep.get("shed"),
            "shedRate": rep.get("shedRate"),
            "rejected": rep.get("rejected"),
            "rejectionRate": rep.get("rejectionRate"),
            "rejections": rep.get("rejections"),
            "missed": rep.get("missed"),
            "blocks": run.get("blocks"),
            "txsIncluded": run.get("txsIncluded"),
            "includedTps": run.get("includedTps"),
        })
    healthy = [r["includedTps"] for r in rows
               if isinstance(r.get("includedTps"), (int, float))
               and (r.get("errorRate") or 0.0) <= MAX_ERROR_RATE]
    any_tps = [r["includedTps"] for r in rows
               if isinstance(r.get("includedTps"), (int, float))]
    best = max(healthy) if healthy else (max(any_tps) if any_tps else 0.0)
    return {
        "metric": "block_inclusion_tps",
        "value": round(best, 3),
        "unit": "tx/s",
        "rates": rows,
        "stages": {"setup_s": round(setup_s, 4),
                   "sweep_s": round(sweep_s, 4)},
        # chain-path stage-queue stats at sweep end: where the backlog
        # sat when the offered load outran inclusion
        "queues": queues,
        "explain": explain,
        "backend": "cpu",   # inclusion is host-side, chip-independent
        "config": "open-loop block-inclusion sweep (loadgen Harness, "
                  "real TCP, dev producer, chain-path stage queues)",
    }


def measure_inclusion() -> None:
    """Block-inclusion throughput bench (docs/PERFORMANCE.md "Reading
    the inclusion bench"): an in-process node behind a real TCP
    RpcServer with the dev producer running, swept with sustained
    offered tx load at several rates (ETHREX_INCLUSION_RATES).  Each
    rate reports included-tps (sealed-block tx count over the rate's
    wall, drain grace included) with shed/rejection accounting; the
    chain-path stage queues and explain_chain_path() verdict ride
    along.  Appends a block_inclusion_tps history record (higher is
    better) for the --check-regression gate."""
    import threading

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.node import Node
    from ethrex_tpu.perf import loadgen
    from ethrex_tpu.perf.chain_path import CHAIN_PATH, explain_chain_path
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.rpc.server import RpcServer

    rates = [float(r) for r in os.environ.get(
        "ETHREX_INCLUSION_RATES", "50,150,400").split(",") if r.strip()]
    duration = float(os.environ.get("ETHREX_INCLUSION_DURATION", "3.0"))
    arrivals = os.environ.get("ETHREX_INCLUSION_ARRIVALS", "poisson")
    senders = int(os.environ.get("ETHREX_INCLUSION_SENDERS", "32"))
    block_time = float(os.environ.get("ETHREX_INCLUSION_BLOCK_TIME",
                                      "0.25"))

    root = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(loadgen.DEFAULT_KEY))
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + root.hex(): {"balance": hex(10**24)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }
    node = Node(Genesis.from_json(genesis))
    server = RpcServer(node, port=0).start()
    stop = threading.Event()

    def producer():
        # the real dev-producer shape: build only when txs wait, at a
        # fixed block time (prewarm off — the bench wants the bare
        # chain-path service rate, not cache-warming variance)
        while not stop.is_set():
            try:
                if len(node.mempool):
                    node.produce_block()
            except Exception:
                pass
            stop.wait(block_time)

    thread = threading.Thread(target=producer, daemon=True)
    thread.start()
    runs = []
    try:
        harness = loadgen.Harness(
            f"http://127.0.0.1:{server.port}", key=loadgen.DEFAULT_KEY,
            senders=senders, payload="tx")
        t0 = time.perf_counter()
        harness.setup()
        CHAIN_PATH.reset()   # measure the sweep, not the funding setup
        setup_s = time.perf_counter() - t0
        t1 = time.perf_counter()
        for rate in sorted(rates):
            blocks0 = node.store.latest_number()
            txs0 = CHAIN_PATH.txs_included
            t_rate = time.perf_counter()
            rep = harness.run(rate, duration, arrivals)
            # drain grace: give the producer a couple of block times to
            # seal what the run admitted, then measure over the full
            # wall so the tps number is conservative and honest
            stop.wait(2.0 * block_time)
            wall = time.perf_counter() - t_rate
            blocks = node.store.latest_number() - blocks0
            included = CHAIN_PATH.txs_included - txs0
            runs.append({
                "report": rep,
                "blocks": blocks,
                "txsIncluded": included,
                "includedTps": round(included / wall, 3) if wall else 0.0,
            })
        sweep_s = time.perf_counter() - t1
        # the sanitized stage view (utilization inf spelled "inf") so the
        # history record stays strict-JSON parseable
        queues = CHAIN_PATH.to_json().get("stages")
        explain = explain_chain_path(CHAIN_PATH)
        # the queue stats above are the canonical view; drop the
        # explainer's embedded copy to keep the record lean
        explain.pop("stages", None)
    finally:
        stop.set()
        thread.join(timeout=5)
        server.stop()
        node.stop()
    record = build_inclusion_record(runs, queues=queues, explain=explain,
                                    setup_s=setup_s, sweep_s=sweep_s)
    # every measure_* names its stage breakdown inline (tooling lint)
    record.update({"stages": {"setup_s": round(setup_s, 4),
                              "sweep_s": round(sweep_s, 4)}})
    append_history(record)
    print(json.dumps(record))


def measure_aggregate() -> None:
    """Aggregation-stage bench (docs/AGGREGATION.md): two small sponge
    STARKs proven as setup, then the ONE outer FriVerifyAir recursion
    proof the l2 aggregator ships to settlement — the headline number is
    the outer prove wall only.  Smaller query count than BASELINE-5 so a
    CPU-fallback run finishes honestly; appends its own history record
    so the lower-is-better gate has a line to hold."""
    _guard_backend()

    import jax

    from ethrex_tpu.models.fibonacci import FibonacciAir, generate_trace
    from ethrex_tpu.stark import aggregate as agg_mod
    from ethrex_tpu.stark import prover as stark_prover
    from ethrex_tpu.stark.prover import StarkParams
    from ethrex_tpu.utils import tracing

    params = StarkParams(log_blowup=2, num_queries=2, log_final_size=4)
    outer = StarkParams(log_blowup=3, num_queries=8, log_final_size=4)
    t0 = time.perf_counter()
    airs, proofs = [], []
    for i in range(2):
        air = FibonacciAir()
        trace = generate_trace(16, a0=1, b0=2 + i)
        pub = [1, 2 + i, int(trace[-1, 1])]
        proofs.append(stark_prover.prove(air, trace, pub, params))
        airs.append(air)
    inner_s = time.perf_counter() - t0
    # warm-up aggregation compiles the outer AIR's phase programs, so
    # the timed prove is steady-state, not XLA compile (same reason
    # BASELINE-5 warms up — run-to-run comparability for the gate)
    t1 = time.perf_counter()
    agg_mod.aggregate(airs, proofs, params, outer)
    warmup_s = time.perf_counter() - t1
    t2 = time.perf_counter()
    with tracing.span("bench.prove") as bench_span:
        agg = agg_mod.aggregate(airs, proofs, params, outer)
    wall = time.perf_counter() - t2
    agg_mod.verify_aggregated(airs, agg, params, outer)
    record = {
        "metric": "aggregate_prove_wall_s", "value": round(wall, 3),
        "unit": "s",
        "inner_proofs": len(proofs),
        "stages": {"inner_prove_s": round(inner_s, 3),
                   "warmup_s": round(warmup_s, 3),
                   **_span_stages(bench_span)},
        "backend": jax.default_backend(),
        "config": "2 Fibonacci STARKs -> one outer recursion proof "
                  "(differential-test outer params, 8 queries)",
    }
    append_history(record)
    print(json.dumps(record))


def measure_settle() -> None:
    """Settlement-amortization bench (docs/AGGREGATION.md): the same
    exec-proven mini L2 run settled two ways — drip per-batch (the live
    proof_send_interval pattern: one L1 verify tx per proven batch) vs
    the aggregation pipeline (ONE L1 tx for the run) — reporting settled
    proofs per L1 verification tx.  Host-side like mgas: the exec prover
    just replays batches, no chip involved."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    from ethrex_tpu.crypto import secp256k1
    from ethrex_tpu.l2.l1_client import InMemoryL1
    from ethrex_tpu.l2.sequencer import Sequencer, SequencerConfig
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis
    from ethrex_tpu.primitives.transaction import Transaction
    from ethrex_tpu.prover import protocol
    from ethrex_tpu.prover.client import ProverClient

    batches = int(os.environ.get("BENCH_SETTLE_BATCHES", "6"))
    exec_t = protocol.PROVER_EXEC
    secret = 0xA11CE
    sender = secp256k1.pubkey_to_address(
        secp256k1.pubkey_from_secret(secret))
    genesis = {
        "config": {"chainId": 1337, "terminalTotalDifficulty": 0,
                   "shanghaiTime": 0, "cancunTime": 0},
        "alloc": {"0x" + sender.hex(): {"balance": hex(10**21)}},
        "gasLimit": hex(30_000_000), "baseFeePerGas": "0x7",
        "timestamp": "0x0",
    }

    def run(aggregation: bool) -> tuple[InMemoryL1, int, dict]:
        """Commit, prove (real TCP), settle; returns the L1, the number
        of settlement L1 txs, and the phase timings."""
        node = Node(Genesis.from_json(genesis))
        l1 = InMemoryL1([exec_t])
        seq = Sequencer(node, l1, SequencerConfig(
            needed_prover_types=(exec_t,),
            aggregation_enabled=aggregation,
            aggregation_min_batches=2,
            aggregation_max_batches=max(2, batches)))
        seq.coordinator.start()
        client = ProverClient(exec_t,
                              [("127.0.0.1", seq.coordinator.port)],
                              heartbeat_interval=0, backoff_base=0.01,
                              rng_seed=0)
        settle_txs = 0
        try:
            t0 = time.perf_counter()
            for n in range(batches):
                tx = Transaction(
                    tx_type=2, chain_id=1337, nonce=n,
                    max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
                    gas_limit=21_000, to=bytes([0x51]) * 20, value=100 + n,
                ).sign(secret)
                node.submit_transaction(tx)
                seq.produce_block()
                assert seq.commit_next_batch() is not None
            commit_s = time.perf_counter() - t0
            t1 = time.perf_counter()
            deadline = time.time() + 60.0
            for n in range(1, batches + 1):
                while seq.rollup.get_proof(n, exec_t) is None:
                    if time.time() > deadline:
                        raise RuntimeError(f"batch {n} never proven")
                    client.poll_once()
                if not aggregation:
                    # the live drip: one send_proofs per proven batch
                    if seq.send_proofs() is not None:
                        settle_txs += 1
            prove_s = time.perf_counter() - t1
            t2 = time.perf_counter()
            if aggregation:
                while seq.aggregate_proofs() is not None:
                    settle_txs += 1
            settle_s = time.perf_counter() - t2
        finally:
            seq.stop()
            node.stop()
        assert l1.last_verified_batch() == batches, \
            f"only {l1.last_verified_batch()}/{batches} settled"
        return l1, settle_txs, {"commit_s": round(commit_s, 4),
                                "prove_s": round(prove_s, 4),
                                "settle_s": round(settle_s, 4)}

    l1_pb, txs_pb, t_pb = run(aggregation=False)
    l1_ag, txs_ag, t_ag = run(aggregation=True)
    per_batch_ratio = batches / max(1, txs_pb)
    agg_ratio = l1_ag.proofs_settled_aggregated / max(
        1, l1_ag.aggregated_settlements)
    record = {
        "metric": "settled_proofs_per_l1_tx",
        "value": round(agg_ratio, 3),
        "unit": "proofs/tx",
        "batches": batches,
        "aggregated_l1_txs": txs_ag,
        "per_batch_l1_txs": txs_pb,
        "per_batch_proofs_per_tx": round(per_batch_ratio, 3),
        "amortization_x": round(agg_ratio / max(per_batch_ratio, 1e-9), 2),
        "stages": {"per_batch": t_pb, "aggregated": t_ag},
        "backend": "cpu",   # exec replay is host-side, chip-independent
        "config": f"{batches}-batch exec pipeline, drip per-batch vs "
                  "aggregated settlement (real TCP provers)",
    }
    append_history(record)
    print(json.dumps(record))


def _attempt(flag: str, timeout: int,
             env: dict | None = None) -> dict | None:
    try:
        proc = subprocess.run(
            [sys.executable, BENCH_PATH, flag],
            capture_output=True, text=True, timeout=timeout,
            cwd=_REPO_ROOT,
            env={**os.environ, **env} if env else None)
    except subprocess.TimeoutExpired:
        return {"_err": f"timeout {timeout}s"}
    line = ""
    for cand in reversed(proc.stdout.strip().splitlines()):
        if cand.startswith("{"):
            line = cand
            break
    if proc.returncode == 0 and line:
        try:
            return json.loads(line)
        except ValueError:
            return {"_err": "unparseable output"}
    tail = (proc.stderr or proc.stdout or "").strip().splitlines()
    return {"_err": f"rc={proc.returncode} " + " | ".join(tail[-3:])[:400]}


EXTRA_TIMEOUT = int(os.environ.get("BENCH_EXTRA_TIMEOUT", "2700"))


def _extra_configs() -> dict:
    """BASELINE configs 2/4/5 (and 3 with BENCH_FULL=1), each in its own
    child attempt; failures are recorded, not fatal."""
    out = {}
    flags = [("2", "--measure-2"), ("4", "--measure-4"),
             ("5", "--measure-5")]
    if os.environ.get("BENCH_FULL") == "1":
        flags.append(("3", "--measure-3"))
    for name, flag in flags:
        res = _attempt(flag, EXTRA_TIMEOUT)
        out[name] = res if res is not None else {"error": "no output"}
    return out


def _mgas_config() -> dict:
    """The L1-side number (host CPU, chip-independent)."""
    res = _attempt("--measure-mgas", min(EXTRA_TIMEOUT, 1200))
    return res if res is not None else {"error": "no output"}


def _core_config() -> dict:
    """The prove-core cells/s microbench as a sub-record, so every suite
    run leaves a gateable kernel-throughput
    number in the history."""
    res = _attempt("--measure-core", min(EXTRA_TIMEOUT, 1500))
    return res if res is not None else {"error": "no output"}


# ---------------------------------------------------------------------------
# append-only history

def append_history(record: dict) -> None:
    """One JSON line per final bench record (ts + backend + the full
    record including sub-configs).  Append-only so the perf trajectory
    is kept; never raises — a read-only checkout must not break the
    bench."""
    try:
        entry = dict(record)
        entry.setdefault("ts", time.time())
        with open(HISTORY_PATH, "a") as f:
            f.write(json.dumps(entry) + "\n")
    except Exception:
        pass


def _read_history() -> list[dict]:
    out: list[dict] = []
    try:
        with open(HISTORY_PATH) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue    # a torn append must not kill the gate
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def _history_series(metric: str) -> list[tuple[str, float]]:
    """Chronological (backend, value) pairs for one metric, pulled from
    top-level records and their sub-configs."""
    series: list[tuple[str, float]] = []
    for rec in _read_history():
        # multi-device scaling sweeps are a different hardware config:
        # gating a 1-device record against an 8-device one (or vice
        # versa) would compare apples to oranges, so any record carrying
        # a scaling sweep or a non-1 devices field stays out of the
        # same-backend series entirely
        if rec.get("scaling") is not None \
                or rec.get("devices") not in (None, 1):
            continue
        backend = rec.get("backend") or "unknown"
        candidates = [rec]
        cfgs = rec.get("configs")
        if isinstance(cfgs, dict):
            candidates += [c for c in cfgs.values() if isinstance(c, dict)]
        for cand in candidates:
            if (cand.get("metric") == metric
                    and isinstance(cand.get("value"), (int, float))
                    and cand["value"] > 0):
                series.append((backend, float(cand["value"])))
    return series


# ---------------------------------------------------------------------------
# CI regression gate

REGRESSION_THRESHOLD = float(
    os.environ.get("BENCH_REGRESSION_THRESHOLD", "0.8"))


def check_regression(current: dict | None = None,
                     baseline: dict | None = None,
                     threshold: float = REGRESSION_THRESHOLD) -> int:
    """CI gate: compare a fresh mgas run against a baseline mgas
    record the caller hands in.  Exit code 2 when current/baseline drops
    below `threshold` (default 0.8, i.e. a >20% regression); 0 when OK
    or when there is no baseline; 1 when the current measurement
    itself failed.  Prints one JSON line either way."""
    if current is None:
        current = _mgas_config()
    if baseline is None:
        baseline = {}
    cur = current.get("value") if isinstance(current, dict) else None
    base = baseline.get("value") if isinstance(baseline, dict) else None
    out = {"metric": "mgas_regression_check", "current": cur,
           "baseline": base, "threshold": threshold}
    if not isinstance(cur, (int, float)) or cur <= 0:
        out["status"] = "error"
        out["detail"] = current.get("error", "no current measurement") \
            if isinstance(current, dict) else "no current measurement"
        print(json.dumps(out))
        return 1
    if not isinstance(base, (int, float)) or base <= 0:
        out["status"] = "no-baseline"
        print(json.dumps(out))
        return 0
    out["ratio"] = cur / base
    out["status"] = "regression" if out["ratio"] < threshold else "ok"
    print(json.dumps(out))
    return 2 if out["status"] == "regression" else 0


def check_history_metric(metric: str,
                         threshold: float = REGRESSION_THRESHOLD,
                         lower_is_better: bool = False) -> int:
    """Gate one metric on its last two SAME-BACKEND history entries (a
    chip number must never be judged against a BENCH_ALLOW_CPU number).
    For lower-is-better metrics (wall-clock) the ratio is inverted so
    `ratio < threshold` always means "got worse".  Exit code 2 on
    regression, else 0 (including no/insufficient history)."""
    series = _history_series(metric)
    out: dict = {"metric": f"{metric}_regression_check",
                 "threshold": threshold}
    if not series:
        out["status"] = "no-baseline"
        print(json.dumps(out))
        return 0
    backend = series[-1][0]
    same = [v for b, v in series if b == backend]
    out["backend"] = backend
    if len(same) < 2:
        out["status"] = "no-baseline"
        out["detail"] = f"fewer than two {backend} records in history"
        print(json.dumps(out))
        return 0
    cur, base = same[-1], same[-2]
    out["current"] = cur
    out["baseline"] = base
    out["ratio"] = (base / cur) if lower_is_better else (cur / base)
    out["status"] = "regression" if out["ratio"] < threshold else "ok"
    print(json.dumps(out))
    return 2 if out["status"] == "regression" else 0


def check_regression_suite(threshold: float = REGRESSION_THRESHOLD) -> int:
    """The full --check-regression gate: a live mgas run (an error
    there is exit code 1), plus same-backend history gates on the prover
    numbers — headline wall (lower is better) and prove-core cells/s —
    and on `l1_import_mgas_per_sec` itself.  One JSON line per check;
    exit code is the worst
    individual code (2 regression > 1 error > 0 ok)."""
    codes = [
        check_regression(threshold=threshold),
        check_history_metric("transfer_batch_prove_wall_s",
                             threshold=threshold, lower_is_better=True),
        check_history_metric("stark_prove_core_trace_cells_per_sec",
                             threshold=threshold),
        check_history_metric("l1_import_mgas_per_sec",
                             threshold=threshold),
        # serving-tail gates (fed by --measure-serving records): client-
        # observed p99 must not balloon, sustained rate must not collapse
        check_history_metric("serving_rpc_p99_seconds",
                             threshold=threshold, lower_is_better=True),
        check_history_metric("serving_sustained_tps",
                             threshold=threshold),
        # aggregation gates (fed by --measure-aggregate / --measure-settle
        # records): the outer recursion prove must not slow down, and the
        # N->1 settlement amortization must not collapse
        check_history_metric("aggregate_prove_wall_s",
                             threshold=threshold, lower_is_better=True),
        check_history_metric("settled_proofs_per_l1_tx",
                             threshold=threshold),
        # cold-start gate (fed by --measure-warmup records): the
        # hydrated second-process warmup must stay collapsed — growth
        # here means the executable cache stopped hydrating
        check_history_metric("stark_core_warmup_hydrated_s",
                             threshold=threshold, lower_is_better=True),
        # chain-path gate (fed by --measure-inclusion records): the
        # end-to-end block-inclusion throughput must not collapse —
        # this holds the whole admit→select→execute→include pipeline,
        # not just the RPC front door the serving gates watch
        check_history_metric("block_inclusion_tps",
                             threshold=threshold),
    ]
    if 2 in codes:
        return 2
    return max(codes)


# ---------------------------------------------------------------------------
# top-level suite

def _publish(result: dict) -> None:
    """Attach the sub-configs, append to the history, and print the one
    final JSON line.  The headline child names its platform; the
    history's same-backend gates key on it."""
    result.setdefault("backend", result.get("platform", "unknown"))
    if os.environ.get("BENCH_SKIP_EXTRAS") != "1":
        result["configs"] = _extra_configs()
        result["configs"]["mgas"] = _mgas_config()
        result["configs"]["core"] = _core_config()
    append_history(result)
    print(json.dumps(result))


def main() -> None:
    """Default mode: the BASELINE-1 headline child, then the
    sub-configs.  This parent never imports JAX (a parent that had
    would hold the chip and starve its children), so it learns that
    there is no chip from the child: _guard_backend exits 3 there.  Any
    failure of the headline child means no record — a message on
    stderr, exit 3, nothing on stdout."""
    result = _attempt("--measure", ATTEMPT_TIMEOUT)
    if "_err" in result:
        print(f"bench: no record published: {result['_err']}",
              file=sys.stderr)
        sys.exit(3)
    _publish(result)


def cli(argv: list[str] | None = None) -> None:
    """Flag dispatch for the bench.py shim (and `python -m`)."""
    argv = sys.argv if argv is None else argv
    if "--measure-core" in argv:
        measure_core()
    elif "--measure-scaling-one" in argv:
        measure_scaling_one()
    elif "--measure-scaling" in argv:
        measure_scaling()
    elif "--measure-serving" in argv:
        measure_serving()
    elif "--measure-inclusion" in argv:
        measure_inclusion()
    elif "--measure-aggregate" in argv:
        measure_aggregate()
    elif "--measure-settle" in argv:
        measure_settle()
    elif "--measure-mgas" in argv:
        measure_mgas()
    elif "--measure-2" in argv:
        measure_config2()
    elif "--measure-3" in argv:
        measure_config3()
    elif "--measure-4" in argv:
        measure_config4()
    elif "--measure-5" in argv:
        measure_config5()
    elif "--measure-warmup-child" in argv:
        measure_warmup_child()
    elif "--measure-warmup" in argv:
        measure_warmup()
    elif "--measure" in argv:
        measure()
    elif "--check-regression" in argv:
        sys.exit(check_regression_suite())
    else:
        main()


if __name__ == "__main__":
    cli()
