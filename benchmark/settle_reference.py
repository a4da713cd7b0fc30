"""The plain reference of settlement: what a rollup guarantees from the
moment a transaction is acknowledged to the moment its batch is
verified on the L1, checked on what one run recorded and what the
stack's JSON-RPC and the dev L1 show afterwards.  It imports nothing of
the program and takes nothing on the program's word but those records:
the deployment hands it plain JSON values and bytes.

`violations(record)` is the list of what broke, empty when nothing did.
`record` holds
  acks       [[tx hash hex, nonce], ...]: every transaction
             eth_sendRawTransaction acknowledged, in the order sent
  blocks     {number: eth_getBlockByNumber(number, false)} for every
             block from 1 to the head
  receipts   {tx hash hex: eth_getTransactionReceipt(hash)} for every ack
  batches    {number: ethrex_getBatchByNumber(number)} for every batch
             the stack committed
  l1_roots   {number: state root hex} the dev L1 holds for each batch
             committed to it
  verified   the dev L1's last_verified_batch, each value it was seen to
             take, in order (the first is what it read before the run)
  settled    [{"first", "last", "proofs": [bytes, ...]}, ...]: each
             verifyBatches the proof sender made, with the proof bytes it
             handed the L1 for each batch of the range, in order
  judged     {number: proof}: the stored proofs the check judged
  deleted    [[number, prover type], ...]: every proof the stack deleted

Guarantees, one check each:
  * every acknowledged transaction is in exactly one block, the
    acknowledged transactions lie in the chain in nonce order, and each
    one's receipt has status 1;
  * every block up to the last committed batch's last is in exactly one
    committed batch, and batch numbers run from 1 without a gap;
  * last_verified_batch never falls and never passes a batch that no
    verifyBatches carried a proof of;
  * each verified batch's state root on the L1 is its last block's state
    root as the RPC reads it;
  * each settled proof is the stored proof the check judged;
  * no proof was deleted.
"""

from __future__ import annotations

import json


def _int(value) -> int:
    return int(value, 16) if isinstance(value, str) else int(value)


def _canon(obj) -> str:
    return json.dumps(obj, sort_keys=True, separators=(",", ":"))


def _transactions(record: dict) -> list:
    wrong = []
    where: dict = {}
    for number in sorted(record["blocks"], key=_int):
        for h in record["blocks"][number]["transactions"]:
            where.setdefault(h, []).append(_int(number))
    chain_order = []
    for h, nonce in record["acks"]:
        found = where.get(h, [])
        if len(found) != 1:
            wrong.append(f"acknowledged transaction {h} (nonce {nonce}) is "
                         f"in {len(found)} blocks {found[:4]}")
            continue
        chain_order.append((found[0], record["blocks_index"][h], nonce, h))
        receipt = record["receipts"].get(h)
        if not receipt or _int(receipt.get("status", 0)) != 1:
            wrong.append(f"transaction {h} has no receipt of status 1: "
                         f"{receipt!r}")
    chain_order.sort()
    nonces = [nonce for _, _, nonce, _ in chain_order]
    if any(b <= a for a, b in zip(nonces, nonces[1:])):
        wrong.append("the acknowledged transactions are not in the chain "
                     "in nonce order")
    return wrong


def _batches(record: dict) -> list:
    wrong = []
    numbers = sorted(_int(n) for n in record["batches"])
    if numbers != list(range(1, len(numbers) + 1)):
        return [f"committed batch numbers {numbers[:8]}... do not run "
                "from 1 without a gap"]
    by_number = {_int(n): b for n, b in record["batches"].items()}
    expect = 1
    for n in numbers:
        first = _int(by_number[n]["firstBlock"])
        last = _int(by_number[n]["lastBlock"])
        if first != expect or last < first:
            wrong.append(f"batch {n} holds blocks {first}..{last}, the "
                         f"next block to batch was {expect}")
        expect = last + 1
    return wrong


def _settlement(record: dict) -> list:
    wrong = []
    seen = record["verified"]
    if any(b < a for a, b in zip(seen, seen[1:])):
        wrong.append(f"last_verified_batch fell: {seen}")
    carried: dict = {}
    for call in record["settled"]:
        first, last = int(call["first"]), int(call["last"])
        proofs = call["proofs"]
        if len(proofs) != last - first + 1:
            wrong.append(f"verifyBatches {first}..{last} carried "
                         f"{len(proofs)} proofs")
            continue
        for n, raw in zip(range(first, last + 1), proofs):
            if raw:
                carried[n] = raw
    top = max(seen) if seen else 0
    for n in range(1, top + 1):
        if n not in carried:
            wrong.append(f"batch {n} is verified on the L1 but no "
                         "verifyBatches carried a proof of it")
    blocks = record["blocks"]
    batches = {_int(n): b for n, b in record["batches"].items()}
    roots = {_int(n): r for n, r in record["l1_roots"].items()}
    for n in range(1, top + 1):
        batch = batches.get(n)
        block = blocks.get(_int(batch["lastBlock"])) if batch else None
        if block is None or roots.get(n) != block["stateRoot"]:
            wrong.append(f"verified batch {n}: the L1's state root "
                         f"{roots.get(n)} is not its last block's "
                         f"{block and block['stateRoot']}")
    for n, proof in sorted(record["judged"].items()):
        raw = carried.get(int(n))
        if raw is not None and _canon(json.loads(raw)) != _canon(proof):
            wrong.append(f"batch {n}: the proof settled on the L1 is not "
                         "the stored proof the check judged")
    for n, kind in record["deleted"]:
        wrong.append(f"the proof sender deleted the {kind} proof of "
                     f"batch {n}")
    return wrong


def violations(record: dict) -> list[str]:
    """What of the guarantees above the record breaks."""
    record = dict(record)
    record["blocks"] = {_int(n): b for n, b in record["blocks"].items()}
    record["blocks_index"] = {
        h: i for b in record["blocks"].values()
        for i, h in enumerate(b["transactions"])}
    return _transactions(record) + _batches(record) + _settlement(record)
