"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls:
`ethrex-tpu l2 --dev --run-prover --provers tpu` (cli.start_l2_stack —
Node + dev L1 + Sequencer + JSON-RPC server + an in-process
ProverClient("tpu") reaching TpuBackend through the TCP proof
coordinator), fed signed EIP-1559 transfers over real JSON-RPC.  Each
batch is BASELINE.json configs[0]: one block of 10 ETH transfers, proven
in circuit (vm mode) at full AIR width, PARAMS unchanged, and settled on
the dev L1.  Batch 1 is the cold path (every program compiles), batch 2
the warm one.

    python chip_smoke.py            one chip: two batches end to end
    python chip_smoke.py --chips 4  ONLY the mesh prove and its one-chip
                                    comparison (byte-identical proofs)

The last line of stdout is one JSON object,
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}};
everything else is on earlier lines.  Without a TPU the last line says
"ok": false and the exit code is 1 — nothing here ever runs on the CPU
in its place.  All work happens in this one process: a chip belongs to
one process at a time.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

# the --dev genesis account's well-known test key (cli.DEV_GENESIS)
DEV_SECRET = \
    0x45A915E4D060149EB4365960E6A7A45F334393093061116B197E3240065FF2D8
NUM_TRANSFERS = 10          # BASELINE.json configs[0]
BATCH_TIMEOUT = 1100.0      # seconds one batch may take to settle


class SmokeFailure(AssertionError):
    """One of the smoke's checks missed."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)
    log(f"  ok: {message}")


def _device_json() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _memory(dev) -> dict:
    return dev.memory_stats() or {}


def _wait(predicate, what: str, timeout: float, poll: float = 0.2,
          progress=None):
    """Poll until `predicate()`; `progress()` (a line for the log) is
    printed once a minute, so a run that is cut still says how far it
    got."""
    t0 = last = time.monotonic()
    while time.monotonic() - t0 < timeout:
        got = predicate()
        if got:
            return got
        if progress is not None and time.monotonic() - last >= 60:
            last = time.monotonic()
            log(f"  ... {last - t0:.0f}s waiting for {what}: {progress()}")
        time.sleep(poll)
    raise SmokeFailure(f"timed out after {timeout:.0f}s waiting for {what}")


def _rpc(port: int, method: str, params: list):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}",
        data=json.dumps({"jsonrpc": "2.0", "id": 1, "method": method,
                         "params": params}).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        body = json.loads(resp.read())
    if "error" in body:
        raise SmokeFailure(f"{method} answered {body['error']}")
    return body["result"]


def _transfers(first_nonce: int, count: int) -> list:
    """`count` signed EIP-1559 transfers from the dev account."""
    from ethrex_tpu.primitives.transaction import (TYPE_DYNAMIC_FEE,
                                                   Transaction)

    return [Transaction(
        tx_type=TYPE_DYNAMIC_FEE, chain_id=1337, nonce=first_nonce + i,
        max_priority_fee_per_gas=1, max_fee_per_gas=10**10,
        gas_limit=21_000, to=bytes([0x50 + i]) * 20, value=1000 + i,
    ).sign(DEV_SECRET) for i in range(count)]


def _phase_devices(progs) -> set:
    """Devices the four compiled phase programs of one cache entry are
    bound to."""
    from ethrex_tpu.stark.prover import _KERNELS

    return {d for kernel in _KERNELS for d in
            getattr(progs, kernel).runtime_executable().local_devices()}


def _compile_counts() -> tuple[int, dict]:
    """(number of fresh phase-program compiles, seconds per "Air/kernel")
    from what record_phase_compile already keeps."""
    from ethrex_tpu.utils.metrics import METRICS

    hist = (METRICS.snapshot().get("histograms") or {}).get(
        "prover_phase_compile_seconds") or {}
    fresh = 0
    walls: dict = {}
    for row in hist.get("series", []):
        lab = row.get("labels", {})
        if lab.get("source") == "compiled":
            fresh += int(row.get("count", 0))
        key = "{}/{}".format(lab.get("air", "?"), lab.get("kernel", "?"))
        if lab.get("mesh", "none") != "none":
            key += "@" + lab["mesh"]
        walls[key] = round(walls.get(key, 0.0) + float(row.get("sum", 0.0)), 4)
    return fresh, walls


def _print_versions() -> None:
    import jax
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — a label, not a dependency
        libtpu = "unknown"
    d = jax.devices()[0]
    log(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
        f"libtpu {libtpu}; device_kind {d.device_kind!r}, "
        f"{len(jax.devices())} device(s)")


def _print_caches() -> None:
    import jax

    from ethrex_tpu.utils import exec_cache, jax_cache

    log(f"compile cache: dir {jax.config.jax_compilation_cache_dir or jax_cache.cache_dir()} "
        f"hits {jax_cache.STATS['cache_hits']} "
        f"misses {jax_cache.STATS['cache_misses']} "
        f"backend compiles {jax_cache.STATS['compiles']} "
        f"({jax_cache.STATS['compile_seconds']:.1f}s)")
    ex = exec_cache.runtime_stats()
    log(f"executable store: dir {ex['dir']} hits {ex['hits']} "
        f"misses {ex['misses']} stores {ex['stores']} errors {ex['errors']}")


def _flip_trace_root(proof: dict) -> dict:
    """A copy of `proof` with one limb of the binding STARK's trace root
    flipped."""
    bad = dict(proof)
    bad["proof"] = dict(proof["proof"])
    root = list(bad["proof"]["trace_root"])
    root[0] ^= 1
    bad["proof"]["trace_root"] = root
    return bad


# ---------------------------------------------------------------------------
# one chip: batches through coordinator -> ProverClient -> TpuBackend

def run_batches(num_transfers: int, num_batches: int = 2) -> None:
    """Start the `l2 --dev --run-prover --provers tpu` stack, push
    `num_batches` single-block batches of `num_transfers` transfers
    through it over JSON-RPC, and check every claim the smoke makes.
    Raises SmokeFailure on the first miss.  Knows nothing about which
    device it runs on beyond jax.devices()[0]; main() decides that."""
    import jax

    from ethrex_tpu import cli
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.prover import protocol
    from ethrex_tpu.prover import runtime_errors as rt
    from ethrex_tpu.prover.tpu_backend import TpuBackend
    from ethrex_tpu.stark import prover as stark_prover
    from ethrex_tpu.utils import jax_cache
    from ethrex_tpu.utils.tracing import TRACER

    dev = jax.devices()[0]
    _print_versions()
    args = cli.build_parser().parse_args(
        ["l2", "--dev", "--run-prover", "--provers", "tpu",
         "--http.port", "0", "--block-time", "2", "--commit-interval", "2"])
    stack = cli.start_l2_stack(args)
    check(not isinstance(stack, int), "l2 stack started")
    seq, l1 = stack.seq, stack.l1
    port = stack.server.port
    try:
        # the producer and committer run on their timers, as in
        # production; the smoke only holds them (the sequencer's admin
        # pause) between batches so that each batch is exactly one
        # block of `num_transfers` transfers and no empty batch queues
        # behind a cold compile
        seq.pause_actor("produce_block")
        seq.pause_actor("commit_next_batch")
        (client,) = stack.clients
        check(type(client.backend) is TpuBackend
              and client.backend.mesh is None,
              "the prover client's backend is get_backend('tpu')")
        mem0 = _memory(dev)
        keys_before: set = set()
        fresh_before = 0
        for batch_no in range(1, num_batches + 1):
            label = "cold" if batch_no == 1 else "warm"
            log(f"batch {batch_no} ({label}): {num_transfers} transfers")
            raws = ["0x" + tx.encode_canonical().hex() for tx in
                    _transfers((batch_no - 1) * num_transfers,
                               num_transfers)]
            head0 = int(_rpc(port, "eth_blockNumber", []), 16)
            compiles0 = jax_cache.STATS["compiles"]
            t_send = time.monotonic()
            for raw in raws:
                _rpc(port, "eth_sendRawTransaction", [raw])
            seq.resume_actor("produce_block")
            _wait(lambda: int(_rpc(port, "eth_blockNumber", []), 16)
                  > head0, "the block producer", 30)
            seq.pause_actor("produce_block")
            block = _rpc(port, "eth_getBlockByNumber",
                         [hex(head0 + 1), False])
            check(len(block["transactions"]) == num_transfers,
                  f"block {head0 + 1} holds all {num_transfers} transfers")
            seq.resume_actor("commit_next_batch")
            _wait(lambda: seq.rollup.latest_batch_number() >= batch_no,
                  "the committer", 60)
            seq.pause_actor("commit_next_batch")
            _wait(lambda: l1.last_verified_batch() >= batch_no
                  or seq.fatal is not None
                  or batch_no in seq.coordinator.quarantined,
                  f"batch {batch_no} to be verified on the L1",
                  BATCH_TIMEOUT, poll=0.5,
                  progress=lambda: (
                      f"{jax_cache.STATS['compiles']} backend compiles "
                      f"({jax_cache.STATS['compile_seconds']:.0f}s); "
                      "phase programs built so far "
                      + json.dumps(_compile_counts()[1])))
            wall = time.monotonic() - t_send
            check(seq.fatal is None, "no sequencer actor died")
            check(l1.last_verified_batch() >= batch_no,
                  f"batch {batch_no} settled on the dev L1")

            proof = seq.rollup.get_proof(batch_no, protocol.PROVER_TPU)
            check(proof is not None and proof["backend"] == "tpu",
                  "the stored proof's backend is tpu")
            check(proof["proof"] is not None
                  and proof["state_proof"] is not None,
                  "the proof carries its STARKs")
            check("vm" in proof and proof.get("vm_proof") is not None,
                  "the proof contains the vm component (transfer "
                  f"semantics proven in circuit, mode "
                  f"{proof.get('vm', {}).get('mode')!r})")
            check(seq.rollup.get_proof(batch_no, protocol.PROVER_EXEC)
                  is None, "no exec proof exists for the batch")
            check(not seq.coordinator.quarantined,
                  "the coordinator quarantined nothing")
            spans = TRACER.get_trace(
                seq.coordinator.batch_traces[batch_no])["spans"]
            prove_s = [s["seconds"] for s in spans
                       if s["name"] == "backend.prove"]
            log(f"  [{dev.device_kind}] batch {batch_no} wall, first "
                f"eth_sendRawTransaction -> last_verified_batch: "
                f"{wall:.2f}s; TpuBackend.prove wall: "
                f"{', '.join(f'{s:.2f}s' for s in prove_s)}")

            verifier = TpuBackend()
            pi = ProgramInput.from_json(seq.rollup.get_prover_input(
                batch_no, seq.cfg.commit_hash))
            check(verifier.verify(proof),
                  "an independent TpuBackend().verify accepts the proof")
            check(verifier.verify_with_input(proof, pi),
                  "verify_with_input accepts the proof")
            check(not verifier.verify(_flip_trace_root(proof)),
                  "a proof with one flipped trace-root limb is rejected")

            keys = set(stark_prover._PHASE_CACHE)
            fresh, walls = _compile_counts()
            new_keys = keys - keys_before
            for key in sorted(new_keys, key=repr):
                log(f"  built phase programs: {key[0][0].__name__} "
                    f"width={key[0][1]} log_n={key[1]} mesh={key[4]}")
            if batch_no == 1:
                check(len(keys) >= 3, "batch 1 built the phase programs "
                      "of at least three AIRs")
                for key in keys:
                    devs = _phase_devices(stark_prover._PHASE_CACHE[key])
                    check({d.platform for d in devs} == {dev.platform},
                          f"{key[0][0].__name__} phase programs are bound to "
                          f"{dev.platform} devices")
                log("  compile seconds per AIR/phase "
                    f"[{dev.device_kind}]: {json.dumps(walls)}")
            else:
                # a shape that legitimately moved between the batches is
                # named above; what must not happen is a second build
                # of an (AIR, log_n) batch 1 already had
                seen = {(k[0], k[1]) for k in keys_before}
                rebuilt = [k for k in new_keys if (k[0], k[1]) in seen]
                check(not rebuilt, "batch 2 rebuilt no phase program "
                      "batch 1 had already built")
                check(fresh - fresh_before == 4 * len(new_keys),
                      f"batch 2 compiled {fresh - fresh_before} phase "
                      f"programs, all for {len(new_keys)} new shape(s)")
                log(f"  backend compiles across batch 2 (all jitted "
                    f"programs): "
                    f"{jax_cache.STATS['compiles'] - compiles0}")
            keys_before, fresh_before = keys, fresh

            if batch_no == 1:
                mem1 = _memory(dev)
                est = rt._estimated_bytes("TransferAir")
                log(f"  [{dev.device_kind}] memory_stats: peak "
                    f"{mem1.get('peak_bytes_in_use')} limit "
                    f"{mem1.get('bytes_limit')} in use "
                    f"{mem1.get('bytes_in_use')}; memory gate estimate "
                    f"for TransferAir (largest per-program "
                    f"memory_analysis working set): {est}")
                vp = proof["vm_proof"]
                layout = vp["n"] * vp["width"] * 4 << vp["log_blowup"]
                if mem1 or dev.platform == "tpu":
                    rose = (mem1["peak_bytes_in_use"]
                            - mem0["peak_bytes_in_use"])
                    check(rose >= layout,
                          f"device peak_bytes_in_use rose by {rose} "
                          f">= one LDE layout ({layout})")
                else:
                    log("  memory_stats: not reported by this platform")

        stats = rt.runtime_stats()
        log(f"runtime stats: {json.dumps(stats)}")
        check(stats["degradations"] == 0, "0 degradations")
        check(stats["memoryGateShrinks"] == 0, "0 memory-gate shrinks")
        check(stats["oomRetries"] == 0 and stats["deviceLostRetries"] == 0,
              "0 transient retries")
        check(l1.last_verified_batch() == num_batches,
              f"last_verified_batch() == {num_batches}")
        _print_caches()
    finally:
        # the CLI's own coordinated drain (run_l2 ends the same way)
        from ethrex_tpu.utils.shutdown import build_node_shutdown

        build_node_shutdown(
            node=stack.node, servers=[stack.server], sequencer=seq,
            prover_clients=stack.clients,
            stores=[stack.node.store, stack.rollup], deadline=30).run()


# ---------------------------------------------------------------------------
# --chips N: the mesh prove and what it is compared with, nothing else

def _transfer_input(num_transfers: int):
    """One block of `num_transfers` plain transfers as a ProgramInput
    (vm mode: the batch the circuits cover)."""
    from ethrex_tpu import cli
    from ethrex_tpu.guest.execution import ProgramInput
    from ethrex_tpu.guest.witness import generate_witness
    from ethrex_tpu.node import Node
    from ethrex_tpu.primitives.genesis import Genesis

    node = Node(Genesis.from_json(cli.DEV_GENESIS))
    for tx in _transfers(0, num_transfers):
        node.submit_transaction(tx)
    block = node.produce_block()
    witness = generate_witness(node.chain, [block])
    return ProgramInput(blocks=[block], witness=witness,
                        config=node.config)


def run_mesh(n_devices: int, num_transfers: int) -> None:
    """Prove one batch with TpuBackend(mesh=make_mesh(n_devices)) and
    with TpuBackend(); the proofs must be byte-identical, every device
    must have been used, and at least one job must have run sharded with
    a collective in its compiled phases."""
    import jax

    from ethrex_tpu.parallel import mesh as mesh_lib
    from ethrex_tpu.perf import hlo_introspect
    from ethrex_tpu.prover.tpu_backend import TpuBackend
    from ethrex_tpu.stark import prover as stark_prover
    from ethrex_tpu.utils.jax_cache import enable_persistent_cache

    _print_versions()
    enable_persistent_cache()
    pi = _transfer_input(num_transfers)
    mesh = mesh_lib.make_mesh(n_devices)
    devs = list(mesh.devices.flat)
    peaks0 = [_memory(d).get("peak_bytes_in_use") for d in devs]

    t0 = time.monotonic()
    mesh_proof = TpuBackend(mesh=mesh).prove(pi, "stark")
    log(f"[{devs[0].device_kind} x{n_devices}] mesh prove wall (cold): "
        f"{time.monotonic() - t0:.2f}s")
    check("vm" in mesh_proof, "the mesh proof contains the vm component")
    # state_proof + one job per vm circuit; the binding STARK runs after
    # them over the whole mesh
    n_jobs = 1 + sum(k in mesh_proof for k in ("vm_proof", "tok_proof")) \
        + len(mesh_proof.get("bc_proofs", ()))
    slices = mesh_lib.split_mesh(mesh, n_jobs)
    log(f"_run_proof_jobs layout: {n_jobs} jobs over {n_devices} devices "
        f"-> slices {[[d.id for d in s.devices.flat] for s in slices]}")
    collectives = {
        (row["air"], row["kernel"]): row["collectiveOps"]
        for row in hlo_introspect.REGISTRY.report()["kernels"]
        if row["devices"] >= 2}
    log(f"collective ops per sharded phase program: "
        f"{json.dumps({'/'.join(k): v for k, v in collectives.items()})}")
    sharded = []
    for key, progs in stark_prover._PHASE_CACHE.items():
        if key[4] is None or len(key[4][0]) < 2:
            continue
        lde = progs.commit.output_shardings[0]
        spread = (len(lde.device_set) >= 2
                  and not lde.is_fully_replicated)
        air_name = key[0][0].__name__
        has_coll = any(collectives.get((air_name, k), 0) > 0
                       for k in stark_prover._KERNELS)
        log(f"  {air_name} on devices {list(key[4][0])}: LDE sharding "
            f"{lde.spec} over {len(lde.device_set)} devices, "
            f"collective in its phases: {has_coll}")
        if spread and has_coll:
            sharded.append(air_name)
    check(sharded, "at least one job ran on a slice of >= 2 devices with "
          f"its LDE's shards on distinct devices and a collective in its "
          f"compiled phases ({sharded})")
    peaks1 = [_memory(d).get("peak_bytes_in_use") for d in devs]
    log(f"peak_bytes_in_use per device before {peaks0} after {peaks1}")
    if any(p is not None for p in peaks1) or devs[0].platform == "tpu":
        check(all(b > a for a, b in zip(peaks0, peaks1)),
              f"every one of the {n_devices} devices' peak_bytes_in_use "
              "rose")
    else:
        log("  memory_stats: not reported by this platform")

    t0 = time.monotonic()
    single_proof = TpuBackend().prove(pi, "stark")
    log(f"[{devs[0].device_kind}] one-device prove wall (cold): "
        f"{time.monotonic() - t0:.2f}s")
    check(mesh_proof == single_proof
          and json.dumps(mesh_proof, sort_keys=True)
          == json.dumps(single_proof, sort_keys=True),
          "the mesh proof and the one-device proof are byte-identical")
    verifier = TpuBackend()
    check(verifier.verify(mesh_proof)
          and verifier.verify_with_input(mesh_proof, pi),
          "the proof verifies (verify and verify_with_input)")
    _print_caches()


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the mesh prove and its "
                             "one-chip comparison")
    chips = parser.parse_args(argv).chips
    device = None
    t0 = time.monotonic()
    try:
        import jax  # noqa: F401 — this process holds the chip from here

        device = _device_json()
        if device["platform"] != "tpu":
            raise SmokeFailure(
                f"JAX found no TPU (default platform "
                f"{device['platform']!r}); nothing was run")
        if device["count"] < chips:
            raise SmokeFailure(f"--chips {chips} needs {chips} devices, "
                               f"JAX reports {device['count']}")
        if chips == 1:
            run_batches(NUM_TRANSFERS)
        else:
            run_mesh(chips, NUM_TRANSFERS)
        log(f"total wall: {time.monotonic() - t0:.1f}s")
    except BaseException as exc:  # noqa: BLE001 — the last line must say so
        if isinstance(exc, KeyboardInterrupt):
            raise
        if not isinstance(exc, SmokeFailure):
            import traceback

            traceback.print_exc()
        print(json.dumps({"ok": False, "device": device,
                          "error": f"{type(exc).__name__}: {exc}"}),
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
