"""TPU prover backend: the `--prover tpu` seam (SURVEY.md north star).

Round-2 scope — the proof now covers the STATE TRANSITION, not just the
output bytes.  `prove` emits two DEEP-FRI STARKs over the same TPU prover
(stark/prover.py):

  1. the STATE proof (models/state_update_air.StateUpdateAir): in-circuit
     verification that applying the batch's write log, entry by entry with
     Merkle openings, transforms the touched-state commitment r_pre into
     r_post — public inputs (r_pre, r_post, log_digest);
  2. the BINDING proof (models/poseidon2_air.Poseidon2SpongeAir): the
     claimed ProgramOutput bytes plus (r_pre, r_post, log_digest) hashed
     in-circuit to one digest, chaining the state proof's publics to the
     batch output the L1 consumes.

`verify` checks both STARKs with the independent host verifier, recomputes
log_digest / r_pre / r_post from the proof-carried write log, and — when
given the ProverInput — audits the log against the witness MPT with trie
operations only (guest/access_log.replay_log_against_witness): every old
value, every storage root, and the final keccak state root, with NO EVM
execution on the verifying side.

Round-3: the VM AIR (transfer scope).  When every transaction in the
batch is a plain ETH transfer, `prove` swaps the executor's per-block
write log for a per-tx fine log (guest/transfer_log.py) and emits a THIRD
STARK (models/transfer_air.TransferAir) proving that every account entry
in that log follows EVM transfer semantics — nonce + 1, sender debit of
value + fee, recipient credit, per-tx coinbase tip — over in-circuit
Poseidon2 recomputation of the flat keys and field digests.  `verify`
recomputes the circuit's public digest from the SAME claimed log that
drives the state proof's commitments, so tampering any transfer amount in
the log leaves NO satisfiable proof: the reference's equivalent guarantee
comes from executing the guest inside the zkVM
(crates/prover/src/backend/sp1.rs:145-163).

Round-4: the token/storage AIR (SLOAD/SSTORE/CALL scope).  Batches may
also contain calls to the canonical token template
(guest/token_template.py): each such call enters the transfer stream as
a value-0 fee/nonce tx AND contributes a segment to a FOURTH STARK
(models/token_air.TokenAir) proving the two balance-slot writes follow
the template's transfer semantics (debit with no underflow, credit with
no wrap).  The verifier recomputes the token digest from the claimed
log's slot rows + the claimed calldata (slot keys re-derived by keccak
from the claimed sender/dst), so tampering any storage slot's NEW value
in the write log leaves no satisfiable proof either.

Round-5: the generic bytecode AIR.  Transactions calling ARBITRARY
bytecode are provable when the executed trace stays inside the supported
opcode subset and machine envelope (guest/bytecode_vm.py): each such
call gets its own STARK (models/bytecode_air.py) proving every step's
stack/memory/storage/control-flow semantics, with the step records
absorbed into a public digest the verifier recomputes from the claimed
step list — checking opcodes/immediates against the claimed code
(pinned by keccak to the code_hash inside the contract's account row,
which r_pre commits), calldata/env values against the claimed tx, and
storage records against the SAME write-log rows the state circuit
applies.  Reads enter the fine log as no-op rows so r_pre commits them
and the witness replay audits them.

Residual trust gaps in vm mode, all closed natively by
`verify_with_input` and documented here for the wire verifier:
  * tx-list authenticity (the claimed senders/values/calldata vs the
    signed txs in the committed blocks) — the circuit binds the claimed
    list, the witness check compares it against the batch's blocks;
  * fee/tip vs base fee: for transfers verify checks fee - tip ==
    21000 * base_fee on the claimed per-block base fee; for token and
    generic calls fee = g*price is checked against the CLAIMED per-tx
    gas g (bounded below by 21000), whose truth is witness-checked (a
    wrong g shifts balances and breaks the replayed state root);
  * gas/refund accounting inside generic calls is NOT in-circuit (the
    executed path's semantics are gas-independent once the receipt says
    it succeeded; the receipt itself is bound by the receipts root);
  * the contract account rows may change only their storage_root
    (natively checked); the root's VALUE is MPT work left to the witness
    replay;
  * batches outside the transfer/token/generic-subset class still use
    the claimed-log mode (state proof + binding only).
"""

from __future__ import annotations

from ..guest import access_log
from ..guest.execution import ProgramInput, execution_program
from ..models import poseidon2_air as pair
from ..models import state_update_air as sua
from ..ops import babybear as bb
from ..ops import poseidon2 as p2
from ..stark import prover as stark_prover
from ..stark import verifier as stark_verifier
from ..stark.prover import StarkParams
from ..utils import faults, tracing
from . import checkpoint as ckpt_mod
from . import protocol
from . import runtime_errors as rt
from .backend import ProverBackend

PARAMS = StarkParams(log_blowup=3, num_queries=40, log_final_size=4)


def output_to_limbs(output_bytes: bytes) -> list[int]:
    """ProgramOutput.encode() -> 24-bit BabyBear limbs (raw byte slices —
    the full output is absorbed by the sponge, no pre-compression)."""
    padded = output_bytes + b"\x00" * ((-len(output_bytes)) % 3)
    limbs = [int.from_bytes(padded[i:i + 3], "big")
             for i in range(0, len(padded), 3)]
    limbs.append(len(output_bytes))  # length limb: no padding ambiguity
    return limbs


def binding_limbs(output_bytes: bytes, r_pre: list[int], r_post: list[int],
                  digest: list[int],
                  vmdigest: list[int] | None = None,
                  tokdigest: list[int] | None = None,
                  bcdigests: list | None = None) -> list[int]:
    """Message of the binding sponge: output bytes, the state proof's 24
    public limbs, a mode limb + statement digest for each VM circuit
    (zeroed in claimed-log mode), then the generic-call digests prefixed
    by their count — one padded stream."""
    limbs = output_to_limbs(output_bytes) + list(r_pre) + list(r_post) \
        + list(digest)
    for d in (vmdigest, tokdigest):
        limbs += [0] * 9 if d is None else [1] + list(d)
    bcdigests = bcdigests or []
    limbs += [len(bcdigests)]
    for d in bcdigests:
        limbs += list(d)
    return pair.pad_message_limbs(limbs)


def _schedule_for(depth: int) -> int:
    """seg_periods for a tree depth (smallest power of two fitting the
    3-leaf + depth-fold + tail schedule; >= 8)."""
    need = depth + 5
    return max(8, 1 << (need - 1).bit_length())


def _mode_of(vm_batch) -> str:
    """The single classifier both the prover's metadata and the
    committer's expected_vm_mode derive from — one definition, because
    check_coverage demands strict equality between the two."""
    return "generic" if vm_batch.bc_calls else (
        "token" if vm_batch.tok_segs else "transfer")


def _vm_meta_json(vm_batch) -> dict:
    blocks = []
    codes: dict[str, str] = {}   # contract addr -> bytecode (one per
    for b in vm_batch.blocks:    # contract, however many calls hit it)
        txs = []
        for t in b.txs:
            row = {"sender": t.sender.hex(), "to": t.recipient.hex(),
                   "value": t.value, "fee": t.fee, "tip": t.tip}
            if t.kind == "tok":
                row.update({"kind": "tok", "gas": t.gas,
                            "dst": t.dst.hex(), "amount": t.amount})
            elif t.kind == "gen":
                row.update({"kind": "gen", "gas": t.gas,
                            "data": t.data.hex(),
                            "steps": [s.to_json() for s in t.steps]})
                codes[t.recipient.hex()] = t.code.hex()
            txs.append(row)
        blocks.append({"coinbase": b.coinbase.hex(),
                       "base_fee": b.base_fee, "txs": txs})
    out = {"mode": _mode_of(vm_batch), "blocks": blocks}
    if codes:
        out["codes"] = codes
    return out


def _vm_stream_from_claims(vm_meta: dict, blocks_log: list):
    """Build the VM digest streams a verifier recomputes from the claimed
    tx list + the claimed write log; performs the native structural and
    fee-relation checks of vm mode.  Returns (transfer_items, tok_items,
    bc_pubs) where bc_pubs holds one 8-limb digest per generic call (the
    claimed step lists are pinned to the claimed code/calldata/log by
    guest/bytecode_vm.check_steps — data indexing, no EVM execution).
    Raises ValueError on any mismatch."""
    from ..guest import bytecode_vm as bv
    from ..guest import flat_model
    from ..guest import token_template as tmpl
    from ..models import bytecode_air as bca
    from ..models import transfer_air as ta

    mode = vm_meta.get("mode")
    if mode not in ("transfer", "token", "generic"):
        raise ValueError("unknown vm mode")
    blocks = vm_meta["blocks"]
    if len(blocks) != len(blocks_log):
        raise ValueError("vm block count does not match the log")

    def acct_digests(entry, want_addr: bytes):
        if entry[0] != "acct":
            raise ValueError("vm log entry is not an account write")
        _, addr, _, old_rlp, new_rlp, cleared = entry
        if addr != want_addr or cleared:
            raise ValueError("vm log entry address mismatch")
        old = [0] * 8 if not old_rlp else flat_model.account_value_digest(
            flat_model.AccountState.decode(old_rlp))
        new = [0] * 8 if not new_rlp else flat_model.account_value_digest(
            flat_model.AccountState.decode(new_rlp))
        return flat_model.account_key_digest(addr), old, new

    def slot_row(entry, want_addr: bytes, want_slot: int):
        if entry[0] != "slot":
            raise ValueError("vm log entry is not a storage write")
        _, addr, slot, old_v, new_v = entry
        if addr != want_addr or int(slot) != want_slot:
            raise ValueError("vm slot row does not match the claimed call")
        old_v, new_v = int(old_v), int(new_v)
        if not (0 <= old_v < 1 << 256 and 0 <= new_v < 1 << 256):
            raise ValueError("vm slot value out of range")
        return old_v, new_v

    # untrusted-size guards, mirroring the 1MB write_log cap in _check
    claimed_codes = vm_meta.get("codes", {})
    if len(claimed_codes) > 1024 or any(
            len(c) > 2 * 0x40000 for c in claimed_codes.values()):
        raise ValueError("vm code claims too large")

    items = []
    tok_items = []
    bc_pubs: list = []
    for bmeta, rows in zip(blocks, blocks_log):
        coinbase = bytes.fromhex(bmeta["coinbase"])
        base_fee = int(bmeta["base_fee"])
        cursor = 0
        touched_contracts: list[bytes] = []
        gen_codes: dict[bytes, bytes] = {}
        for txm in bmeta["txs"]:
            value = int(txm["value"])
            fee = int(txm["fee"])
            tip = int(txm["tip"])
            kind = txm.get("kind", "xfer")
            if not (0 <= value < 1 << 256 and 0 <= tip <= fee < 1 << 256):
                raise ValueError("vm tx amounts out of range")
            sender = bytes.fromhex(txm["sender"])
            to = bytes.fromhex(txm["to"])
            if kind == "tok":
                if mode not in ("token", "generic"):
                    raise ValueError("token tx outside token mode")
                if value != 0:
                    raise ValueError("token call with value")
                g = int(txm["gas"])
                # fee = g*price, tip = g*(price - base_fee): g divides
                # both and their difference is g*base_fee; g's own truth
                # is witness-checked via the replayed balances
                if g < 21000 or fee - tip != g * base_fee \
                        or fee % g or tip % g:
                    raise ValueError("vm token fee out of model")
            elif kind == "gen":
                if mode != "generic":
                    raise ValueError("generic tx outside generic mode")
                if value != 0:
                    raise ValueError("generic call with value")
                g = int(txm["gas"])
                if g < 21000 or fee - tip != g * base_fee \
                        or fee % g or tip % g:
                    raise ValueError("vm generic fee out of model")
            elif fee - tip != 21000 * base_fee:
                raise ValueError("vm fee does not match the base fee")
            ks, os_, ns = acct_digests(rows[cursor], sender)
            cursor += 1
            if kind == "gen":
                code_hex = claimed_codes.get(txm["to"])
                if code_hex is None:
                    raise ValueError("vm generic call without code claim")
                if len(txm["steps"]) > bv.MAX_STEPS \
                        or len(txm["data"]) > 2_000_000:
                    raise ValueError("vm generic claims too large")
                code = bytes.fromhex(code_hex)
                data = bytes.fromhex(txm["data"])
                steps = [bv.StepRec.from_json(s) for s in txm["steps"]]
                touched: list[int] = []
                seen: set[int] = set()
                for st in steps:
                    if st.op in (bv.OP_SLOAD, bv.OP_SSTORE) \
                            and st.a not in seen:
                        seen.add(st.a)
                        touched.append(st.a)
                slot_rows = []
                for slot in touched:
                    old_v, new_v = slot_row(rows[cursor], to, slot)
                    cursor += 1
                    slot_rows.append((slot, old_v, new_v))
                try:
                    bv.check_steps(code, data, sender, 0, steps,
                                   slot_rows, address=to)
                except bv.StepCheckError as e:
                    raise ValueError(f"vm generic steps: {e}")
                bc_pubs.append(bca.bc_digest_stream(steps))
                if to not in touched_contracts:
                    touched_contracts.append(to)
                if gen_codes.setdefault(to, code) != code:
                    raise ValueError("vm generic code claim inconsistent")
                kr = flat_model.account_key_digest(to)
                orr = nr = [0] * 8
            elif kind == "tok":
                amount = int(txm["amount"])
                dst = bytes.fromhex(txm["dst"])
                if not (0 <= amount < 1 << 256):
                    raise ValueError("vm token amount out of range")
                if amount == 0:
                    tok_items.append((0, 0, 0, 0, 0, 0, 0, True))
                else:
                    kf = tmpl.balance_slot(sender)
                    kt = tmpl.balance_slot(dst)
                    fold, fnew = slot_row(rows[cursor], to, kf)
                    cursor += 1
                    told, tnew = slot_row(rows[cursor], to, kt)
                    cursor += 1
                    if to not in touched_contracts:
                        touched_contracts.append(to)
                    tok_items.append((amount, kf, fold, fnew,
                                      kt, told, tnew, False))
                kr = flat_model.account_key_digest(to)
                orr = nr = [0] * 8
            elif value == 0:
                # no-op credit: no log row; the circuit's NOP segment
                # absorbs zero digests and pins the amount to zero
                kr = flat_model.account_key_digest(to)
                orr = nr = [0] * 8
            else:
                kr, orr, nr = acct_digests(rows[cursor], to)
                cursor += 1
            if tip == 0:
                kc = flat_model.account_key_digest(coinbase)
                oc = nc = [0] * 8
            else:
                kc, oc, nc = acct_digests(rows[cursor], coinbase)
                cursor += 1
            txf = (ta._limbs11(value), ta._limbs11(fee), ta._limbs11(tip))
            items.append(("tx", txf, (ks, os_, ns, kr, orr, nr)))
            items.append(("cb", None, (kc, oc, nc)))
        # each touched token contract: ONE account row at block end whose
        # fields other than storage_root are unchanged (the storage_root
        # transition itself is MPT work the witness replay audits)
        for caddr in touched_contracts:
            entry = rows[cursor]
            cursor += 1
            if entry[0] != "acct" or entry[1] != caddr or entry[5]:
                raise ValueError("vm contract row mismatch")
            old_rlp, new_rlp = entry[3], entry[4]
            if not old_rlp or not new_rlp:
                raise ValueError("vm contract lifecycle change")
            o = flat_model.AccountState.decode(old_rlp)
            n = flat_model.AccountState.decode(new_rlp)
            if (o.nonce, o.balance, o.code_hash) != \
                    (n.nonce, n.balance, n.code_hash):
                raise ValueError("vm contract fields changed")
            code = gen_codes.get(caddr)
            if code is not None:
                # pin the claimed bytecode to the account row r_pre binds
                from ..crypto.keccak import keccak256
                from ..primitives.account import EMPTY_CODE_HASH

                want = EMPTY_CODE_HASH if not code else keccak256(code)
                if o.code_hash != want:
                    raise ValueError("vm generic code hash mismatch")
        if cursor != len(rows):
            raise ValueError("vm log shape mismatch")
    if mode == "token" and not tok_items:
        raise ValueError("token mode without token txs")
    if mode == "generic" and not bc_pubs:
        raise ValueError("generic mode without generic txs")
    return items, tok_items, bc_pubs


def vm_mode_from_artifacts(blocks, coarse_log, receipts, witness,
                           initial_root: bytes) -> str:
    """The VM-circuit coverage an honest prover reaches on this batch,
    classified from execution artifacts already in hand (the committer
    captures them during witness generation — no extra execution)."""
    from ..guest import transfer_log as tl_mod
    from ..guest.witness_oracles import WitnessOracles

    try:
        oracles = WitnessOracles(witness, initial_root)
        vb = tl_mod.build_vm_batch(blocks, coarse_log, receipts,
                                   oracles=oracles)
    except tl_mod.NotTransferBatch:
        return "claimed"
    return _mode_of(vb)


def expected_vm_mode(program_input: ProgramInput) -> str:
    """The classifier over a bare ProgramInput (stateless re-execution;
    committers with live artifacts use vm_mode_from_artifacts)."""
    blocks_log: list = []
    receipts: list = []
    output = execution_program(program_input, write_log=blocks_log,
                               receipts_out=receipts)
    return vm_mode_from_artifacts(program_input.blocks, blocks_log,
                                  receipts, program_input.witness,
                                  output.initial_state_root)


def _p2_path() -> str:
    """Which host Poseidon2 ran (attribute `p2` of `prove.trace_gen` and
    `prove.vm_batch`): a fallback to Python must not pass for a
    regression of unknown cause."""
    return "native" if p2.available() else "python"


def _traced_gen(air_name: str, generate, *args):
    """A job's trace generation (host numpy) under its leaf span.  No
    `stage=`: it runs inside the job's own stage span."""
    with tracing.span("prove.trace_gen", air=air_name,
                      p2=_p2_path()) as sp:
        trace = generate(*args)
        tracing.set_attrs(sp, rows=int(trace.shape[0]),
                          width=int(trace.shape[1]))
    return trace


def _run_proof_jobs(jobs: list, mesh) -> dict:
    """Run independent STARK proving jobs, concurrently when the mesh
    has devices to split.

    `jobs` is a list of ``(name, group, builder)``; ``builder(job_mesh)``
    generates its trace and returns a proof dict.  With no mesh or a
    1-device mesh jobs run serially on the caller's thread, VM-circuit
    jobs wrapped in the pre-existing ``vm_circuits`` stage span with one
    ``vm_circuits/<air>`` child span each.  Otherwise the mesh is split
    into min(len(jobs), n_devices) disjoint contiguous slices
    (parallel/mesh.py split policy) and one worker thread per slice runs
    its round-robin share of jobs serially, re-entering the caller's
    trace so per-job spans land in the same trace tree; the aggregate
    ``vm_circuits`` wall (first VM start to last VM finish, overlap
    collapsed) is fed to prover_stage_seconds directly.  Proofs are
    bit-identical to the serial path — slicing only changes placement.
    Returns results keyed by job name; a worker exception propagates.
    """
    import time as _time
    from concurrent.futures import ThreadPoolExecutor

    from ..parallel import mesh as mesh_lib
    from ..utils import metrics as metrics_mod

    ndev = 1 if mesh is None else int(mesh.devices.size)
    try:
        metrics_mod.record_mesh_devices(ndev)
    except Exception:
        pass

    ckpt_ctx = ckpt_mod.current_context()

    def _run_one(name, group, build, job_mesh, lane=0, lane_devices=1):
        stage = name if group == "vm_circuits" else group
        # the job name scopes this job's phase checkpoints; activate()
        # also re-binds the batch context on pool worker threads
        # (threading.local does not cross ThreadPoolExecutor).  The
        # deviceLane attr routes the span onto its mesh slice's lane in
        # the Perfetto export (tracing.to_trace_events).
        with ckpt_mod.activate(ckpt_ctx, job=name):
            with tracing.span(f"prove.{name}", stage=stage,
                              deviceLane=lane, laneDevices=lane_devices):
                return build(job_mesh)

    def _record_occupancy(lane_timings, lane_devices):
        # occupancy telemetry (perf/occupancy.py): busy intervals per
        # mesh-slice lane, weighted by slice size, against the full
        # ndev mesh — never-raise
        try:
            from ..perf import occupancy as occ_mod

            lanes = {str(i): {"intervals": ivs,
                              "devices": lane_devices.get(i, 1)}
                     for i, ivs in lane_timings.items() if ivs}
            if lanes:
                occ_mod.record_prove(lanes, devices=ndev)
        except Exception:
            pass

    results: dict = {}
    vm_jobs = [j for j in jobs if j[1] == "vm_circuits"]
    if ndev == 1 or len(jobs) == 1:
        try:
            metrics_mod.record_vm_parallelism(1)
        except Exception:
            pass
        serial_ivs: list = []
        for name, group, build in jobs:
            if group != "vm_circuits":
                t0 = _time.perf_counter()
                results[name] = _run_one(name, group, build, mesh,
                                         lane=0, lane_devices=ndev)
                serial_ivs.append((t0, _time.perf_counter()))
        if vm_jobs:
            with tracing.span("prove.vm_proofs", stage="vm_circuits"):
                for name, group, build in vm_jobs:
                    t0 = _time.perf_counter()
                    results[name] = _run_one(name, group, build, mesh,
                                             lane=0, lane_devices=ndev)
                    serial_ivs.append((t0, _time.perf_counter()))
        # one lane carrying the whole mesh: a single-job prove on an
        # N-device mesh still keeps all N devices (weight = ndev, so
        # occupancy reflects mesh-sharded, not sliced, execution)
        _record_occupancy({0: serial_ivs}, {0: ndev})
        return results

    slices = mesh_lib.split_mesh(mesh, len(jobs))
    assigned: list[list] = [[] for _ in slices]
    vm_slices = set()
    for i, job in enumerate(jobs):
        assigned[i % len(slices)].append(job)
        if job[1] == "vm_circuits":
            vm_slices.add(i % len(slices))
    try:
        metrics_mod.record_vm_parallelism(max(1, len(vm_slices)))
    except Exception:
        pass

    cur = tracing.current()
    tid, pid = cur if cur else (None, None)
    timings: dict = {}
    lane_timings: dict = {i: [] for i in range(len(slices))}
    lane_devices = {}
    for i, s in enumerate(slices):
        try:
            lane_devices[i] = max(1, int(s.devices.size))
        except Exception:
            lane_devices[i] = 1

    def _worker(lane, slice_mesh, slice_jobs):
        # re-enter the prove's trace on this thread so every job span
        # (and its stark child spans) joins the same subtree
        with tracing.trace_context(tid, pid):
            for name, group, build in slice_jobs:
                t0 = _time.perf_counter()
                results[name] = _run_one(
                    name, group, build, slice_mesh, lane=lane,
                    lane_devices=lane_devices.get(lane, 1))
                t1 = _time.perf_counter()
                timings[name] = (t0, t1)
                lane_timings[lane].append((t0, t1))

    with ThreadPoolExecutor(max_workers=len(slices)) as pool:
        futs = [pool.submit(_worker, i, s, a)
                for i, (s, a) in enumerate(zip(slices, assigned)) if a]
        for f in futs:
            f.result()

    vm_times = [timings[name] for name, group, _ in jobs
                if group == "vm_circuits" and name in timings]
    if vm_times:
        wall = max(t1 for _, t1 in vm_times) - min(t0 for t0, _ in vm_times)
        try:
            metrics_mod.observe_prover_stage("vm_circuits", wall)
        except Exception:
            pass
    _record_occupancy(lane_timings, lane_devices)
    return results


class TpuBackend(ProverBackend):
    prover_type = protocol.PROVER_TPU

    def __init__(self, mesh=None):
        # optional jax.sharding.Mesh: every STARK's device phases run
        # sharded across it (stark/prover.py threads the constraints;
        # XLA inserts the collectives).  Proofs are bit-identical to
        # single-chip runs, so verification is unchanged.
        self.mesh = mesh

    def prewarm(self) -> int:
        """Restore the phase programs and the FRI layer programs from
        the on-disk executable cache (utils/exec_cache) so the first
        post-restart proof runs at steady-state wall.  Hydration only —
        this never compiles; shapes not yet on disk stay cold until
        first use, where the per-kernel disk lookup still serves them
        in deserialize time.  Sub-mesh entries (split_mesh slices) are
        not pre-installed here — they hydrate from disk inside
        _aot_phases on first use."""
        from ..stark.prover import hydrate_phase_cache

        count = hydrate_phase_cache(None)
        if self.mesh is not None:
            count += hydrate_phase_cache(self.mesh)
        return count

    def prove(self, program_input: ProgramInput, proof_format: str) -> dict:
        import time as _time

        from ..perf import profiler as perf_profiler

        # one root span per prove so per-stage child spans form a single
        # subtree even when no caller opened a trace (e.g. bench); the
        # profiler.capture is a no-op unless --profile-dir opted in to
        # device tracing
        t0 = _time.perf_counter()
        resumes0 = rt.STATS["phase_resumes"]
        with tracing.span("backend.prove", format=proof_format) as sp:
            try:
                with perf_profiler.capture("prove"):
                    out = self._prove_impl(program_input, proof_format)
            finally:
                # phases loaded from checkpoints in place of proved: 0
                # in a first attempt, and what a killed one had landed
                # in the attempt that resumes it
                tracing.set_attrs(sp, resumed_phases=rt.STATS[
                    "phase_resumes"] - resumes0)
        try:
            from ..utils.metrics import record_proof_wall

            record_proof_wall(_time.perf_counter() - t0)
        except Exception:
            pass
        # refresh device-memory / live-array gauges while the runtime
        # still holds this proof's peak allocations (never raises)
        from ..utils.jax_cache import update_metrics_gauges

        update_metrics_gauges()
        return out

    def _prove_impl(self, program_input: ProgramInput,
                    proof_format: str) -> dict:
        import time as _time

        from ..guest import transfer_log as tl_mod
        from ..guest.witness_oracles import WitnessOracles
        from ..models import token_air as tka
        from ..models import transfer_air as ta

        # -- execute phase, checkpointed.  The envelope stores the
        # execution artifacts (output bytes, coarse write log, receipts)
        # so a restarted prover skips the EVM re-execution; the VM-batch
        # classification below is cheap host work recomputed either way.
        ckpt_ctx = ckpt_mod.current_context()
        exe_parts = {"kind": "proof_ckpt", "job": "backend",
                     "phase": "execute", "format": proof_format}
        blocks_log: list = []
        receipts: list = []
        exe_pay = (ckpt_mod.load(ckpt_ctx.batch_id, exe_parts)
                   if ckpt_ctx is not None else None)
        if exe_pay is not None:
            rt.note_resume("execute")
            with tracing.span("prove.execute", stage="execute",
                              resumed=True):
                encoded = exe_pay["encoded"]
                blocks_log = exe_pay["blocks_log"]
                receipts = exe_pay["receipts"]
                initial_root = exe_pay["initial_root"]
        else:
            with tracing.span("prove.execute", stage="execute"):
                output = rt.guard_phase(
                    "execute", "-",
                    lambda: execution_program(program_input,
                                              write_log=blocks_log,
                                              receipts_out=receipts))
                encoded = output.encode()
                initial_root = output.initial_state_root
            if ckpt_ctx is not None:
                ckpt_mod.store(ckpt_ctx.batch_id, exe_parts,
                               {"encoded": encoded,
                                "blocks_log": blocks_log,
                                "receipts": receipts,
                                "initial_root": initial_root},
                               meta={"lease_token": ckpt_ctx.lease_token})
            faults.inject("backend.phase", None, kinds=("drop",))

        # `prove.vm_batch`: everything between the execution and the first
        # trace: the VM batch, the access records, every job's public
        # inputs and the binding statement (host Python; the job closures
        # below only close over what is made here and run later).  One
        # finished interval, recorded where it ends.
        vmb_wall0 = _time.time()
        vm_batch = None
        try:
            oracles = WitnessOracles(program_input.witness, initial_root)
            vm_batch = tl_mod.build_vm_batch(program_input.blocks,
                                             blocks_log, receipts,
                                             oracles=oracles)
            blocks_log = vm_batch.blocks_log
        except tl_mod.NotTransferBatch:
            pass

        # -- independent STARK jobs: state_proof + the VM-mode circuits.
        # Each job is (name, stage, builder) where builder(mesh) generates
        # its trace and proves on the mesh slice it is handed.  With a
        # multi-device mesh the jobs run CONCURRENTLY on disjoint
        # sub-meshes (parallel/mesh.py split_mesh policy: min(jobs,
        # devices) contiguous slices, every device used, extra jobs
        # round-robined and proven serially within their slice); with no
        # mesh or 1 device they run serially on the main thread.  Proofs
        # are bit-identical either way — sharding and slicing only move
        # layout, never values.
        entries = access_log.flatten_entries(blocks_log)
        records, r_pre, r_post, depth = \
            access_log.build_access_records(entries)
        S = _schedule_for(depth)
        air = sua.StateUpdateAir(depth, seg_periods=S)
        pub = sua.state_update_public_inputs(records, r_pre, r_post, S)

        def _state_job(job_mesh):
            trace = _traced_gen("StateUpdateAir",
                                sua.generate_state_update_trace,
                                records, r_pre, depth, S)
            return stark_prover.prove(air, trace, pub, PARAMS,
                                      mesh=job_mesh)

        jobs = [("state_proof", "state_proof", _state_job)]

        vm_pub = None
        vm_proof = None
        vm_air = None
        tok_pub = None
        tok_proof = None
        tok_air = None
        bc_pubs: list = []
        bc_proofs: list = []
        bc_airs: list = []
        if vm_batch is not None:
            vm_air = ta.TransferAir()
            vm_pub = ta.transfer_public_inputs(vm_batch.segs)

            def _transfer_job(job_mesh):
                trace = _traced_gen("TransferAir",
                                    ta.generate_transfer_trace,
                                    vm_batch.segs)
                return stark_prover.prove(vm_air, trace, vm_pub,
                                          PARAMS, mesh=job_mesh)

            jobs.append(("vm_circuits/TransferAir", "vm_circuits",
                         _transfer_job))
            if vm_batch.tok_segs:
                tok_air = tka.TokenAir()
                tok_pub = tka.token_public_inputs(vm_batch.tok_segs)

                def _token_job(job_mesh):
                    trace = _traced_gen("TokenAir",
                                        tka.generate_token_trace,
                                        vm_batch.tok_segs)
                    return stark_prover.prove(tok_air, trace, tok_pub,
                                              PARAMS, mesh=job_mesh)

                jobs.append(("vm_circuits/TokenAir", "vm_circuits",
                             _token_job))
            if vm_batch.bc_calls:
                from ..models import bytecode_air as bca

                for idx, call in enumerate(vm_batch.bc_calls):
                    air_bc = bca.BytecodeAir()
                    pub_bc = bca.bytecode_public_inputs(call.steps)
                    bc_airs.append(air_bc)
                    bc_pubs.append(pub_bc)

                    def _bc_job(job_mesh, _air=air_bc, _call=call,
                                _pub=pub_bc):
                        trace = _traced_gen(
                            "BytecodeAir", bca.generate_bytecode_trace,
                            _call.steps, _call.snaps)
                        return stark_prover.prove(_air, trace, _pub,
                                                  PARAMS, mesh=job_mesh)

                    jobs.append((f"vm_circuits/BytecodeAir{idx}",
                                 "vm_circuits", _bc_job))

        # the binding statement needs only public inputs, all known by
        # now: build it first so that its phase programs — and, on the
        # one-device path where the jobs run one after the other, the
        # transfer circuit's — compile while the first job proves (a
        # cold prover spends most of its first proof compiling)
        digest = pub[16:24]
        limbs = binding_limbs(encoded, r_pre, r_post, digest, vm_pub,
                              tok_pub, bc_pubs)
        bind_air = pair.Poseidon2SpongeAir(num_chunks=len(limbs) // 8)
        bind_pub = pair.sponge_public_inputs(limbs)
        vmb_seconds = _time.time() - vmb_wall0
        row_kinds = [e[0] for b in blocks_log for e in b]
        tracing.record_span(
            "prove.vm_batch", vmb_wall0, vmb_seconds,
            mode="claimed" if vm_batch is None else _mode_of(vm_batch),
            p2=_p2_path(),
            txs=sum(len(b.body.transactions)
                    for b in program_input.blocks),
            tok_calls=0 if vm_batch is None else len(vm_batch.tok_segs),
            acct_rows=row_kinds.count("acct"),
            slot_rows=row_kinds.count("slot"))
        bind_trace = _traced_gen("Poseidon2SpongeAir",
                                 pair.generate_sponge_trace, limbs)
        # look-ups when the programs are warm; cold, the builds run in
        # the background and a job pays for one it has to wait for under
        # its `prove.phase_build`.  Every AIR of the batch is asked for,
        # in the order its job runs (state, transfer, token, binding),
        # and the builds queue in that order: a job then proves while
        # the later AIRs still build (queued last, the state circuit's
        # programs held every job up for the whole of a cold token
        # batch's builds: 1087 s, then 52 s of proving; PR 29, chip call
        # 4).  The FRI layer programs the process lacks (none, once
        # `prewarm` has restored them) build from the batch's largest
        # codeword down (the state circuit's, where the batch has slot
        # rows): a layer size left out builds inside that STARK's own
        # FRI loop
        with tracing.span("prove.compile_ahead"):
            ahead = []
            if vm_batch is not None and self.mesh is None:
                state_rows = sua.segment_count(len(records)) * S \
                    * pair.PERIOD
                vm_rows = ta.segment_count(len(vm_batch.segs)) * ta.SEG_LEN
                ahead += [(air, state_rows), (vm_air, vm_rows)]
                if tok_air is not None:     # one segment a call: < vm_rows
                    ahead.append((tok_air, tka.segment_count(
                        len(vm_batch.tok_segs)) * tka.SEG_LEN))
                stark_prover.warm_fri_programs(max(vm_rows, state_rows),
                                               PARAMS)
            ahead.append((bind_air, bind_trace.shape[0]))
            stark_prover.compile_ahead(ahead, PARAMS, self.mesh)

        results = _run_proof_jobs(jobs, self.mesh)
        state_proof = results["state_proof"]
        if vm_batch is not None:
            vm_proof = results["vm_circuits/TransferAir"]
            if vm_batch.tok_segs:
                tok_proof = results["vm_circuits/TokenAir"]
            bc_proofs = [results[f"vm_circuits/BytecodeAir{i}"]
                         for i in range(len(bc_airs))]

        with tracing.span("prove.binding", stage="binding"), \
                ckpt_mod.job_scope("binding"):
            bind_proof = stark_prover.prove(bind_air, bind_trace,
                                            bind_pub, PARAMS,
                                            mesh=self.mesh)
        proof = {
            "backend": self.prover_type,
            "format": proof_format,
            "output": "0x" + encoded.hex(),
            "write_log": access_log.raw_log_to_json(blocks_log),
            "depth": depth,
            "seg_periods": S,
            "state_proof": state_proof,
            "proof": bind_proof,
        }
        if vm_batch is not None:
            proof["vm"] = _vm_meta_json(vm_batch)
            proof["vm_proof"] = vm_proof
            if tok_proof is not None:
                proof["tok_proof"] = tok_proof
            if bc_proofs:
                proof["bc_proofs"] = bc_proofs
        if proof_format in (protocol.FORMAT_COMPRESSED,
                            protocol.FORMAT_GROTH16):
            # recursion: one outer STARK proves every inner proof's FRI
            # query openings; their Merkle path data leaves the wire
            from ..stark import aggregate as agg_mod

            airs = [air, bind_air]
            proofs = [state_proof, bind_proof]
            if vm_batch is not None:
                airs.append(vm_air)
                proofs.append(vm_proof)
            if tok_proof is not None:
                airs.append(tok_air)
                proofs.append(tok_proof)
            airs.extend(bc_airs)
            proofs.extend(bc_proofs)
            with tracing.span("prove.aggregate", stage="aggregate"), \
                    ckpt_mod.job_scope("aggregate"):
                agg = agg_mod.aggregate(airs, proofs, PARAMS,
                                        mesh=self.mesh)
            proof["state_proof"], proof["proof"] = agg.inners[:2]
            cursor = 2
            if vm_batch is not None:
                proof["vm_proof"] = agg.inners[cursor]
                cursor += 1
            if tok_proof is not None:
                proof["tok_proof"] = agg.inners[cursor]
                cursor += 1
            if bc_proofs:
                proof["bc_proofs"] = agg.inners[cursor:cursor
                                                + len(bc_proofs)]
            proof["aggregate"] = {
                "outer": agg.outer, "max_depth": agg.max_depth,
                "seg_periods": agg.seg_periods,
            }
            if proof_format == protocol.FORMAT_GROTH16:
                from . import groth16_wrap

                # proof_to_json stays inside the span: it is what forces
                # any still-in-flight device work to the host
                with tracing.span("prove.groth16_wrap",
                                  stage="groth16_wrap"):
                    wrapped = groth16_wrap.wrap_prove(
                        [int(v) for v in agg.outer["pub_inputs"]],
                        rnd=encoded[:32])
                    proof["groth16"] = groth16_wrap.proof_to_json(wrapped)
        return proof

    # -- verification -------------------------------------------------------

    def _reconstruct(self, proof: dict):
        """Rebuild the AIRs and collect the inner STARKs of one batch
        proof, enforcing every public-input binding against the claimed
        log along the way (no STARK verification happens here).  Returns
        (airs, proofs, blocks_log, encoded); shared by `_check` and by
        `stark_components` (the cross-batch aggregation path)."""
        if proof.get("backend") != self.prover_type:
            raise ValueError("wrong backend tag")
        encoded = bytes.fromhex(proof["output"][2:])
        if sum(len(b) for b in proof["write_log"]) > 1_000_000:
            raise ValueError("write log too large")
        blocks_log = access_log.raw_log_from_json(proof["write_log"])

        # recompute the flat commitments from the claimed log; the tree
        # shape is fully determined by the log, so the proof's claimed
        # depth/seg_periods get no attacker freedom (a huge claimed depth
        # would otherwise allocate 2^depth leaves before any AIR check)
        entries = access_log.flatten_entries(blocks_log)
        records, r_pre, r_post, depth = \
            access_log.build_access_records(entries)
        S = _schedule_for(depth)
        if int(proof["depth"]) != depth or int(proof["seg_periods"]) != S:
            raise ValueError("claimed tree shape does not match the log")
        segments = sua.segment_count(len(records))
        digest = sua.log_digest(records, S, segments)

        state = proof["state_proof"]
        claimed_pub = [int(v) % bb.P for v in state["pub_inputs"]]
        if claimed_pub != r_pre + r_post + digest:
            raise ValueError("state proof publics do not match the log")
        air = sua.StateUpdateAir(depth, seg_periods=S)

        # vm mode: the circuits' public digests are recomputed from the
        # SAME claimed log (plus the claimed tx list), so the write log's
        # account values are constrained by EVM transfer semantics and
        # its storage slots by the token-template semantics
        vm_meta = proof.get("vm")
        vm_air = None
        vm_proof = None
        vm_pub = None
        tok_air = None
        tok_proof = None
        tok_pub = None
        bc_pubs: list = []
        bc_proofs: list = []
        bc_airs: list = []
        if vm_meta is not None:
            from ..models import token_air as tka
            from ..models import transfer_air as ta

            items, tok_items, bc_pubs = _vm_stream_from_claims(vm_meta,
                                                               blocks_log)
            vm_pub = ta.vm_digest_stream(items)
            vm_proof = proof["vm_proof"]
            if [int(v) % bb.P for v in vm_proof["pub_inputs"]] != vm_pub:
                raise ValueError("vm proof does not bind this log")
            vm_air = ta.TransferAir()
            if tok_items:
                tok_pub = tka.tok_digest_stream(tok_items)
                tok_proof = proof["tok_proof"]
                if [int(v) % bb.P for v in tok_proof["pub_inputs"]] != \
                        tok_pub:
                    raise ValueError("token proof does not bind this log")
                tok_air = tka.TokenAir()
            if bc_pubs:
                from ..models import bytecode_air as bca

                bc_proofs = proof.get("bc_proofs") or []
                if len(bc_proofs) != len(bc_pubs):
                    raise ValueError("generic proof count mismatch")
                for p, pub in zip(bc_proofs, bc_pubs):
                    if [int(v) % bb.P for v in p["pub_inputs"]] != pub:
                        raise ValueError(
                            "generic proof does not bind its steps")
                    bc_airs.append(bca.BytecodeAir())

        limbs = binding_limbs(encoded, r_pre, r_post, digest, vm_pub,
                              tok_pub, bc_pubs)
        bind = proof["proof"]
        if [int(v) for v in bind["pub_inputs"][:len(limbs)]] != limbs:
            raise ValueError("binding proof does not bind this statement")
        bind_air = pair.Poseidon2SpongeAir(num_chunks=len(limbs) // 8)

        airs = [air, bind_air]
        proofs = [state, bind]
        if vm_air is not None:
            airs.append(vm_air)
            proofs.append(vm_proof)
        if tok_air is not None:
            airs.append(tok_air)
            proofs.append(tok_proof)
        airs.extend(bc_airs)
        proofs.extend(bc_proofs)
        return airs, proofs, blocks_log, encoded

    def stark_components(self, proof: dict):
        """The (airs, inner STARK proofs) of a FORMAT_STARK batch proof,
        FRI paths intact, publics validated against the claimed log —
        the raw material l2/aggregator.py feeds into
        stark.aggregate.aggregate_groups for cross-batch recursion."""
        if proof.get("aggregate") is not None:
            raise ValueError("proof is already aggregated: its inner FRI "
                             "paths are gone and cannot be re-aggregated")
        airs, proofs, _, _ = self._reconstruct(proof)
        return airs, proofs

    def _check(self, proof: dict):
        """Shared verification core; returns the parsed raw log + claimed
        output bytes, or raises."""
        airs, proofs, blocks_log, encoded = self._reconstruct(proof)

        agg_info = proof.get("aggregate")
        if agg_info is not None:
            # compressed/groth16: every proof verified through the outer
            # recursion STARK (their FRI paths are gone from the wire)
            from ..stark import aggregate as agg_mod

            agg = agg_mod.AggregateProof(
                inners=proofs, outer=agg_info["outer"],
                max_depth=int(agg_info["max_depth"]),
                seg_periods=int(agg_info["seg_periods"]))
            agg_mod.verify_aggregated(airs, agg, PARAMS)
            wrapped = proof.get("groth16")
            if wrapped is not None:
                from . import groth16_wrap

                if not groth16_wrap.wrap_verify(
                        groth16_wrap.proof_from_json(wrapped),
                        [int(v) for v in agg.outer["pub_inputs"]]):
                    raise ValueError("groth16 wrap rejected")
        else:
            for a, p in zip(airs, proofs):
                if not stark_verifier.verify(a, p, PARAMS):
                    raise ValueError("proof rejected")
        return blocks_log, encoded

    def verify(self, proof: dict) -> bool:
        try:
            self._check(proof)
            return True
        except (KeyError, ValueError, TypeError, IndexError,
                access_log.LogAuditError,
                stark_verifier.VerificationError):
            return False

    def verify_submission(self, proof: dict) -> bool:
        """Structural gate only: the full STARK audit is expensive and
        stays in send_proofs (verify_with_input); at submit time the
        coordinator just needs enough shape to reject wire corruption and
        free the assignment slot for honest provers."""
        try:
            bytes.fromhex(proof["output"][2:])
            return (proof.get("backend") == self.prover_type
                    and isinstance(proof.get("proof"), dict)
                    and isinstance(proof.get("state_proof"), dict)
                    and isinstance(proof.get("write_log"), list))
        except (KeyError, TypeError, ValueError):
            return False

    def check_coverage(self, proof: dict, expected_mode: str) -> bool:
        """Reject mode downgrades WITHOUT the witness: the committer
        derived `expected_mode` by running the same deterministic
        classifier the honest prover runs, so any other mode on the wire
        is a forgery attempt (most importantly claimed-log for a batch
        the circuits cover)."""
        if not expected_mode:
            return True    # pre-metadata batches: no constraint
        vm = proof.get("vm")
        actual = vm.get("mode") if isinstance(vm, dict) else "claimed"
        return actual == expected_mode

    def verify_with_input(self, proof: dict,
                          program_input: ProgramInput) -> bool:
        """Full audit: every STARK + the witness MPT replay (trie ops
        only, no EVM) against the claimed initial/final state roots; in
        vm mode, the claimed tx metadata is REBUILT from the batch's
        signed txs + a re-execution (closing the wire-verifier's
        authenticity gaps: tx list, per-tx gas, and the token template's
        code hash, which build_vm_batch pins against the real pre-state);
        plus a downgrade check: a batch the circuits cover must carry
        the vm proofs."""
        from ..guest.execution import ProgramOutput
        from ..guest.transfer_log import (NotTransferBatch, build_vm_batch,
                                          is_generic_call_shape,
                                          is_plain_transfer,
                                          is_token_call_shape)
        from ..guest.witness_oracles import WitnessOracles

        try:
            blocks_log, encoded = self._check(proof)
            output = ProgramOutput.decode(encoded)
            access_log.replay_log_against_witness(
                blocks_log, program_input.witness.nodes,
                output.initial_state_root, output.final_state_root)
            oracles = WitnessOracles(program_input.witness,
                                     output.initial_state_root)
            vm_meta = proof.get("vm")
            if vm_meta is None:
                # downgrade check: a batch the circuits cover must carry
                # the vm proofs.  The static predicate over-approximates
                # the circuits' scope (a generic-shape call may still
                # leave the executed subset), so on ambiguity re-derive
                # applicability exactly as the prover would.
                if not all(is_plain_transfer(tx) or is_token_call_shape(tx)
                           or is_generic_call_shape(tx)
                           for blk in program_input.blocks
                           for tx in blk.body.transactions):
                    return True
                try:
                    coarse: list = []
                    receipts: list = []
                    execution_program(program_input, write_log=coarse,
                                      receipts_out=receipts)
                    build_vm_batch(program_input.blocks, coarse, receipts,
                                   oracles=oracles)
                except NotTransferBatch:
                    return True
                return False
            # rebuild the vm metadata from the real signed txs and a
            # re-execution; claimed metadata must match it exactly
            try:
                coarse = []
                receipts = []
                execution_program(program_input, write_log=coarse,
                                  receipts_out=receipts)
                rebuilt = build_vm_batch(program_input.blocks, coarse,
                                         receipts, oracles=oracles)
            except NotTransferBatch:
                return False
            return _vm_meta_json(rebuilt) == vm_meta
        except (KeyError, ValueError, TypeError, IndexError,
                access_log.LogAuditError,
                stark_verifier.VerificationError):
            return False
