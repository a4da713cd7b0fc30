"""ethrex-tpu CLI (parity target: cmd/ethrex/cli.rs — ~90 clap flags with
ETHREX_* env-var mirrors, plus the removedb / import / export /
compute-state-root subcommands, cli.rs:562-676).

Every flag reads its default from the matching ETHREX_* environment
variable (the reference's clap `env` mirrors); explicit CLI arguments win.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time

from .node import Node
from .primitives.genesis import Genesis
from .rpc.server import RpcServer

DEV_GENESIS = {
    "config": {
        "chainId": 1337,
        "homesteadBlock": 0, "eip150Block": 0, "eip155Block": 0,
        "byzantiumBlock": 0, "constantinopleBlock": 0, "petersburgBlock": 0,
        "istanbulBlock": 0, "berlinBlock": 0, "londonBlock": 0,
        "mergeNetsplitBlock": 0, "terminalTotalDifficulty": 0,
        "shanghaiTime": 0, "cancunTime": 0, "pragueTime": 0,
    },
    "alloc": {
        # dev account (well-known test key
        # 0x45a915e4d060149eb4365960e6a7a45f334393093061116b197e3240065ff2d8)
        "0xa94f5374fce5edbc8e2a8697c15331677e6ebf0b": {
            "balance": "0xd3c21bcecceda1000000"},
    },
    "gasLimit": "0x1c9c380",
    "baseFeePerGas": "0x7",
    "timestamp": "0x0",
}


def _env(name: str, default=None):
    return os.environ.get(f"ETHREX_{name}", default)


def _env_int(name: str, default: int) -> int:
    v = _env(name)
    return int(v) if v is not None else default


def _env_float(name: str, default: float) -> float:
    v = _env(name)
    return float(v) if v is not None else default


def _add_node_flags(parser: argparse.ArgumentParser):
    parser.add_argument("--dev", action="store_true",
                        default=_env("DEV") == "1",
                        help="dev mode: auto-produce blocks from the mempool")
    parser.add_argument("--datadir", default=_env("DATADIR"),
                        help="persist the chain in <datadir>/chain.db "
                             "(native C++ KV store); default: in-memory")
    parser.add_argument("--network", "--genesis", dest="genesis",
                        default=_env("NETWORK"),
                        help="network preset (mainnet|sepolia|hoodi, with "
                             "embedded genesis + bootnodes) or a genesis "
                             "JSON path")
    parser.add_argument("--http.addr", dest="http_addr",
                        default=_env("HTTP_ADDR", "127.0.0.1"))
    parser.add_argument("--http.port", dest="http_port", type=int,
                        default=_env_int("HTTP_PORT", 8545))
    parser.add_argument("--ws.port", dest="ws_port", type=int,
                        default=_env_int("WS_PORT", 0),
                        help="WebSocket JSON-RPC + subscriptions (0 = off)")
    parser.add_argument("--rpc-backlog", dest="rpc_backlog", type=int,
                        default=_env_int("RPC_BACKLOG", 128),
                        help="TCP listen backlog for the RPC listeners "
                             "(HTTP, Engine API, WebSocket); saturation "
                             "shows up as rpc_connections_reset_total")
    parser.add_argument("--rpc-executor-workers",
                        dest="rpc_executor_workers", type=int,
                        default=_env_int("RPC_EXECUTOR_WORKERS", 0),
                        help="handler threads behind the asyncio RPC "
                             "front door (0 = ETHREX_RPC_EXECUTOR_WORKERS "
                             "env or built-in default); the event loop "
                             "never blocks, handlers run here")
    parser.add_argument("--rpc-max-batch", dest="rpc_max_batch", type=int,
                        default=_env_int("RPC_MAX_BATCH", 0),
                        help="largest JSON-RPC batch array accepted "
                             "(0 = ETHREX_RPC_MAX_BATCH env or built-in "
                             "default); larger arrays get a typed -32600 "
                             "error, never a dropped connection")
    parser.add_argument("--block-time", dest="block_time", type=float,
                        default=_env_float("BLOCK_TIME", 1.0),
                        help="dev block production interval (s)")
    parser.add_argument("--coinbase",
                        default=_env("COINBASE", "0x" + "00" * 20))
    parser.add_argument("--metrics.port", dest="metrics_port", type=int,
                        default=_env_int("METRICS_PORT", 0),
                        help="Prometheus /metrics port (0 = off)")
    parser.add_argument("--log-level", dest="log_level",
                        choices=("debug", "info", "warning", "error"),
                        default=_env("LOG_LEVEL", "info"),
                        help="structured logger threshold")
    parser.add_argument("--log-json", dest="log_json",
                        action="store_true",
                        default=_env("LOG_JSON") == "1",
                        help="emit logs as one JSON object per line "
                             "(with trace/span IDs when in context)")
    parser.add_argument("--authrpc.addr", dest="authrpc_addr",
                        default=_env("AUTHRPC_ADDR", "127.0.0.1"))
    parser.add_argument("--authrpc.port", dest="authrpc_port", type=int,
                        default=_env_int("AUTHRPC_PORT", 0),
                        help="Engine API port (0 = off)")
    parser.add_argument("--authrpc.jwtsecret", dest="jwt_path",
                        default=_env("AUTHRPC_JWTSECRET"),
                        help="path to a hex-encoded 32-byte JWT secret")
    parser.add_argument("--p2p.enabled", dest="p2p_enabled",
                        action="store_true",
                        default=_env("P2P_ENABLED") == "1")
    parser.add_argument("--p2p.addr", dest="p2p_addr",
                        default=_env("P2P_ADDR", "0.0.0.0"))
    parser.add_argument("--p2p.port", dest="p2p_port", type=int,
                        default=_env_int("P2P_PORT", 30303))
    parser.add_argument("--discovery.port", dest="discovery_port", type=int,
                        default=_env_int("DISCOVERY_PORT", 30303),
                        help="discv4 UDP port")
    parser.add_argument("--p2p-timeout", dest="p2p_timeout", type=float,
                        default=_env_float("P2P_TIMEOUT", 10.0),
                        help="per-request p2p timeout CEILING (s): the "
                        "adaptive phi-accrual estimator tightens below "
                        "this per peer, never above it; also bounds the "
                        "dial/handshake (docs/P2P_RESILIENCE.md)")
    parser.add_argument("--p2p-retries", dest="p2p_retries", type=int,
                        default=_env_int("P2P_RETRIES", 2),
                        help="retries per p2p request after the first "
                        "attempt, with jittered exponential backoff; "
                        "0 disables retry (docs/P2P_RESILIENCE.md)")
    parser.add_argument("--bootnodes", default=_env("BOOTNODES", ""),
                        help="comma-separated enode URLs")
    parser.add_argument("--syncmode", choices=("full", "snap"),
                        default=_env("SYNCMODE", "full"))
    parser.add_argument("--kzg-setup", dest="kzg_setup",
                        default=_env("KZG_SETUP"),
                        help="path to the ceremony trusted_setup.json for "
                        "the 0x0a precompile; CONSENSUS-CRITICAL: every "
                        "node of a chain must use the same setup (default: "
                        "the deterministic dev setup, crypto/kzg.py)")
    parser.add_argument("--node-config", dest="node_config",
                        default=_env("NODE_CONFIG"),
                        help="JSON file persisting known peers across "
                        "restarts (reference: node_config.json)")
    parser.add_argument("--shutdown-deadline", dest="shutdown_deadline",
                        type=float,
                        default=_env_float("SHUTDOWN_DEADLINE", 30.0),
                        help="bounded SIGTERM/SIGINT drain deadline (s): "
                        "RPC stops, writers join, in-flight proof submits "
                        "land, every backend flushes and closes")
    parser.add_argument("--debug-snapshot-dir", dest="debug_snapshot_dir",
                        default=_env("DEBUG_SNAPSHOT_DIR"),
                        help="flight-recorder destination: debug snapshot "
                        "bundles (metrics, windows, alerts, traces, TPU "
                        "telemetry) written here on fatal actor errors, "
                        "shutdown, and ethrex_debug_snapshot calls")
    parser.add_argument("--profile-dir", dest="profile_dir",
                        default=_env("PROFILE_DIR"),
                        help="opt-in continuous profiler destination: "
                        "jax.profiler device traces (TensorBoard/XProf "
                        "format) captured around each prove land here; "
                        "unset keeps device tracing off (zero overhead)")
    parser.add_argument("--sender-workers", dest="sender_workers", type=int,
                        default=_env_int("SENDER_WORKERS", 0),
                        help="thread-pool size for batched sender "
                        "recovery (native secp256k1 engine); 0 = "
                        "min(8, cpu_count)")
    parser.add_argument("--executable-cache-dir",
                        dest="executable_cache_dir",
                        default=_env("EXEC_CACHE_DIR"),
                        help="on-disk serialized-executable cache for AOT "
                        "prover kernels (utils/exec_cache): a restarted "
                        "prover hydrates compiled programs from here in "
                        "deserialize time instead of recompiling — ship "
                        "it in a deploy image to kill cold-start "
                        "(docs/PERFORMANCE.md); default: exec/ "
                        "under the compile-cache root "
                        "(JAX_COMPILATION_CACHE_DIR, else "
                        "<checkout>/.jax_cache)")


def _enable_compile_caches(args):
    """Production startup wiring for the two compile caches: the XLA
    persistent compilation cache (utils/jax_cache, HLO-level) and the
    serialized-executable store (utils/exec_cache, whole-program level —
    the prover cold-start killer).  Never fatal: a node that cannot set
    up caching still serves."""
    try:
        from .utils import exec_cache, jax_cache

        if getattr(args, "executable_cache_dir", None):
            exec_cache.set_cache_dir(args.executable_cache_dir)
        jax_cache.enable_persistent_cache()
    except Exception as e:  # noqa: BLE001 — caching is an optimization
        print(f"compile-cache setup skipped: {e}", file=sys.stderr)


def _load_genesis(args) -> Genesis | None:
    if args.genesis:
        from .config import is_preset, load_network

        if is_preset(args.genesis):
            genesis, bootnodes = load_network(args.genesis)
            # preset bootnodes seed the dial list unless overridden
            if hasattr(args, "bootnodes") and not args.bootnodes:
                args.bootnodes = ",".join(bootnodes)
            return genesis
        with open(args.genesis) as f:
            return Genesis.from_json(json.load(f))
    if args.dev:
        return Genesis.from_json(DEV_GENESIS)
    return None


def _open_store(datadir: str | None):
    if not datadir:
        return None
    from .storage.persistent import PersistentBackend
    from .storage.store import Store

    os.makedirs(datadir, exist_ok=True)
    store = Store(PersistentBackend(os.path.join(datadir, "chain.db")))
    # diff layering: trie nodes reach the durable log only once finalized
    # (stale branches stay RAM-only; storage/layering.py)
    store.enable_layering()
    return store


def _decode_chain_file(path: str):
    from .primitives import rlp
    from .primitives.block import Block, BlockBody, BlockHeader

    with open(path, "rb") as f:
        rest = f.read()
    blocks = []
    while rest:
        item, rest = rlp.decode_prefix(rest)
        blocks.append(Block(BlockHeader.decode_fields(item[0]),
                            BlockBody.from_fields(item[1:])))
    return blocks


def cmd_import(args) -> int:
    """`ethrex import <chain.rlp>` — bulk-import an RLP chain file and
    report throughput (cli.rs `import` + tooling/import_benchmark)."""
    import time

    genesis = _load_genesis(args)
    if genesis is None:
        print("import requires --network <genesis.json> (or --dev)",
              file=sys.stderr)
        return 1
    node = Node(genesis, store=_open_store(args.datadir))
    blocks = _decode_chain_file(args.file)
    t0 = time.perf_counter()
    node.chain.add_blocks_in_batch(blocks)
    # make the imported tip canonical (the reference's import subcommand
    # ends with a fork-choice update to the last imported block)
    from .blockchain.fork_choice import apply_fork_choice

    tip = blocks[-1].hash
    apply_fork_choice(node.store, tip, tip, tip)
    dt = time.perf_counter() - t0
    gas = sum(b.header.gas_used for b in blocks)
    print(f"imported {len(blocks)} blocks, {gas / 1e6:.1f} Mgas "
          f"in {dt:.2f}s = {gas / dt / 1e6:.1f} Mgas/s")
    node.store.flush()
    return 0


def cmd_export(args) -> int:
    """`ethrex export <out.rlp>` — canonical chain to an RLP file."""
    from .primitives import rlp

    genesis = _load_genesis(args)
    if genesis is None:
        print("export requires --network/--dev", file=sys.stderr)
        return 1
    node = Node(genesis, store=_open_store(args.datadir))
    last = args.last if args.last is not None else \
        node.store.latest_number()
    with open(args.file, "wb") as f:
        for n in range(args.first, last + 1):
            block = node.store.get_canonical_block(n)
            if block is None:
                print(f"missing canonical block {n}", file=sys.stderr)
                return 1
            f.write(block.encode())
    print(f"exported blocks {args.first}..{last} to {args.file}")
    return 0


def cmd_removedb(args) -> int:
    """`ethrex removedb` — delete the datadir (cli.rs removedb)."""
    import shutil

    if not args.datadir:
        print("removedb requires --datadir", file=sys.stderr)
        return 1
    if not os.path.isdir(args.datadir):
        print(f"no database at {args.datadir}")
        return 0
    if not args.force:
        resp = input(f"delete {args.datadir}? [y/N] ")
        if resp.strip().lower() not in ("y", "yes"):
            print("aborted")
            return 1
    shutil.rmtree(args.datadir)
    print(f"removed {args.datadir}")
    return 0


def cmd_compute_state_root(args) -> int:
    """`ethrex compute-state-root --network genesis.json`."""
    genesis = _load_genesis(args)
    if genesis is None:
        print("compute-state-root requires --network", file=sys.stderr)
        return 1
    from .storage.store import Store

    header = Store().init_genesis(genesis)
    print(f"state root: 0x{header.state_root.hex()}")
    print(f"genesis hash: 0x{header.hash.hex()}")
    return 0


def _parse_enode(url: str):
    # enode://<128-hex pubkey>@host:port
    if not url.startswith("enode://"):
        raise ValueError(f"not an enode URL: {url}")
    rest = url[len("enode://"):]
    pub_hex, _, addr = rest.partition("@")
    host, _, port = addr.partition(":")
    from .p2p.rlpx import _pub_from_bytes

    return _pub_from_bytes(bytes.fromhex(pub_hex)), host, int(port or 30303)


def run_node(args) -> int:
    _enable_compile_caches(args)
    if args.kzg_setup:
        from .crypto import kzg

        kzg.set_setup(kzg.TrustedSetup.from_ceremony_json(args.kzg_setup))

    genesis = _load_genesis(args)
    if genesis is None:
        print("either --dev or --network <genesis.json> is required",
              file=sys.stderr)
        return 1

    coinbase = bytes.fromhex(args.coinbase.removeprefix("0x"))
    store = _open_store(args.datadir)
    node = Node(genesis, coinbase=coinbase, store=store)
    rpc_tuning = {
        "executor_workers": args.rpc_executor_workers or None,
        "max_batch": args.rpc_max_batch or None,
    }
    server = RpcServer(node, args.http_addr, args.http_port,
                       backlog=args.rpc_backlog, **rpc_tuning).start()
    print(f"genesis hash: 0x{node.genesis_header.hash.hex()}")
    print(f"JSON-RPC listening on http://{args.http_addr}:{server.port}")
    authrpc = None
    if args.authrpc_port:
        if args.jwt_path:
            with open(args.jwt_path) as f:
                jwt_secret = bytes.fromhex(
                    f.read().strip().removeprefix("0x"))
        else:
            # never expose an unauthenticated consensus-control endpoint:
            # generate a secret like the reference does and tell the user
            import secrets as _secrets

            jwt_secret = _secrets.token_bytes(32)
            print(f"generated JWT secret (pass to your CL): "
                  f"{jwt_secret.hex()}")
        authrpc = RpcServer(node, args.authrpc_addr, args.authrpc_port,
                            jwt_secret=jwt_secret, engine=True,
                            backlog=args.rpc_backlog, **rpc_tuning).start()
        print(f"Engine API listening on http://{args.authrpc_addr}:"
              f"{authrpc.port}")
    ws = None
    if args.ws_port:
        from .rpc.websocket import WsServer

        ws = WsServer(server, args.http_addr, args.ws_port,
                      backlog=args.rpc_backlog).start()
        print(f"WebSocket JSON-RPC on ws://{args.http_addr}:{ws.port}")
    metrics = None
    if args.metrics_port:
        from .utils.metrics import MetricsServer

        metrics = MetricsServer(args.http_addr, args.metrics_port).start()
        print(f"metrics on http://{args.http_addr}:{metrics.port}/metrics")

    p2p = None
    if args.p2p_enabled:
        from .p2p.connection import P2PServer

        p2p = P2PServer(node, host=args.p2p_addr, port=args.p2p_port,
                        timeout=args.p2p_timeout,
                        retries=args.p2p_retries)
        p2p.start()
        from .p2p.rlpx import _pub_bytes

        print(f"p2p listening on {p2p.host}:{p2p.port} "
              f"(enode pubkey {_pub_bytes(p2p.pub).hex()})")
        peers = []
        if args.node_config and os.path.exists(args.node_config):
            with open(args.node_config) as f:
                peers = json.load(f).get("known_peers", [])
        for url in filter(None, args.bootnodes.split(",")):
            peers.append(url.strip())
        for url in peers:
            try:
                pub, host, port = _parse_enode(url)
                p2p.dial(host, port, pub)
            except (ValueError, OSError) as e:
                print(f"bootnode {url}: {e}", file=sys.stderr)

    if args.dev:
        node.start_dev_producer(args.block_time)
        print(f"dev producer running (block time {args.block_time}s)")

    # observability: sampler + SLO alerts + optional flight recorder
    from .utils import snapshot
    from .utils.alerts import build_default_engine

    if args.debug_snapshot_dir:
        snapshot.configure(args.debug_snapshot_dir)
    if getattr(args, "profile_dir", None):
        from .perf import profiler as perf_profiler

        perf_profiler.configure(args.profile_dir)
    if getattr(args, "sender_workers", 0):
        from .blockchain import sender_recovery

        sender_recovery.configure(args.sender_workers)
    node.start_telemetry(alerts=build_default_engine(node))

    # coordinated drain (utils/shutdown.py): rpc -> producer -> flush+close
    from .utils.shutdown import build_node_shutdown

    manager = build_node_shutdown(
        node=node, servers=[server, authrpc, ws, metrics],
        stores=[node.store],
        deadline=args.shutdown_deadline)
    stop_event = _install_signal_handlers(stop_event=threading.Event())
    try:
        while not stop_event.wait(0.5):
            pass
    except KeyboardInterrupt:
        pass
    finally:
        # persist known peers (reference: node_config.json on shutdown)
        if p2p is not None and args.node_config:
            known = []
            for peer in p2p.peers:
                try:
                    host, port = peer.sock.getpeername()[:2]
                    known.append(
                        f"enode://{bytes(peer.remote_pub).hex()}"
                        f"@{host}:{port}")
                except (OSError, AttributeError, TypeError):
                    continue
            with open(args.node_config, "w") as f:
                json.dump({"known_peers": known}, f)
        report = manager.run()
        print(f"shutdown complete in {report['durationSeconds']:.2f}s "
              f"({len(report['steps'])} steps)")
    return 0


def _install_signal_handlers(stop_event: threading.Event):
    """SIGTERM/SIGINT set the stop event; the main loop then runs the
    coordinated drain.  Falls back silently off the main thread (tests
    drive the manager directly)."""
    def _on_signal(signum, frame):
        print(f"received {signal.Signals(signum).name}; draining...")
        stop_event.set()

    try:
        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)
    except ValueError:
        pass
    return stop_event


def start_l2_stack(args):
    """Wire and start the sequencer stack — L2 node + block producer +
    committer + proof coordinator + proof sender + JSON-RPC server (+
    optional in-process prover clients) — from parsed `l2` arguments.
    Returns a namespace (node, l1, seq, rollup, server, clients), or an
    int exit code when the arguments cannot be satisfied.  `run_l2` and
    chip_smoke.py both start the stack here, so the smoke drives exactly
    what the CLI runs."""
    from .l2.l1_client import InMemoryL1
    from .l2.rollup_store import PersistentRollupStore, RollupStore
    from .l2.sequencer import Sequencer, SequencerConfig

    _enable_compile_caches(args)
    genesis = _load_genesis(args)
    if genesis is None:
        print("either --dev or --network <genesis.json> is required",
              file=sys.stderr)
        return 1
    coinbase = bytes.fromhex(args.coinbase.removeprefix("0x"))
    store = _open_store(args.datadir)
    node = Node(genesis, coinbase=coinbase, store=store)

    if args.datadir:
        rollup = PersistentRollupStore(
            os.path.join(args.datadir, "rollup.db"))
    else:
        rollup = RollupStore()

    prover_types = tuple(t for t in args.l2_provers.split(",") if t)
    if args.l1_url:
        from .l2.eth_client import EthClient
        from .l2.l1_contract import RpcL1Client

        if not (args.l1_contract and args.l1_secret):
            print("--l1.contract and --l1.secret are required with "
                  "--l1.url", file=sys.stderr)
            return 1
        l1 = RpcL1Client(
            EthClient(args.l1_url),
            bytes.fromhex(args.l1_contract.removeprefix("0x")),
            int(args.l1_secret.removeprefix("0x"), 16),
            needed_prover_types=list(prover_types))
    elif args.datadir:
        from .l2.l1_client import PersistentInMemoryL1

        l1 = PersistentInMemoryL1(
            os.path.join(args.datadir, "l1_dev.json"),
            needed_prover_types=list(prover_types))
        print("l2: using datadir-persisted dev L1 "
              "(pass --l1.url for a real one)")
    else:
        l1 = InMemoryL1(needed_prover_types=list(prover_types))
        print("l2: using in-process dev L1 (pass --l1.url for a real one)")

    ha_role = getattr(args, "ha_role", None)
    if ha_role and not l1.supports_leases():
        # refusing beats running unfenced: without the lease cell a
        # second sequencer could double-commit (docs/SEQUENCER_HA.md)
        print("--ha-role requires an L1 client with leader-lease support "
              "(the RPC L1 client has no lease cell yet)", file=sys.stderr)
        return 1
    cfg = SequencerConfig(
        block_time=args.block_time or 1.0,
        commit_interval=args.commit_interval,
        batch_gas_limit=getattr(args, "batch_gas_limit", None),
        needed_prover_types=prover_types,
        ha_role=ha_role,
        leader_lease=getattr(args, "leader_lease", 3.0),
        ha_node_id=getattr(args, "ha_node_id", None))
    seq = Sequencer(node, l1, cfg, rollup=rollup)
    node.sequencer = seq

    server = RpcServer(
        node, args.http_addr, args.http_port,
        backlog=getattr(args, "rpc_backlog", None),
        executor_workers=getattr(args, "rpc_executor_workers", 0) or None,
        max_batch=getattr(args, "rpc_max_batch", 0) or None).start()
    print(f"genesis hash: 0x{node.genesis_header.hash.hex()}")
    print(f"L2 JSON-RPC listening on http://{args.http_addr}:{server.port}")
    latest = rollup.latest_batch_number()
    if latest:
        print(f"resuming from checkpoint: batch {latest} "
              f"(blocks up to {seq.last_batched_block})")
    seq.start()
    if seq.leadership is not None:
        print(f"sequencer in HA mode as {cfg.ha_role} "
              f"(lease ttl {cfg.leader_lease}s, node id "
              f"{seq.leadership.node_id}); actors parked until the "
              f"leader lease is won — watch ethrex_ready")
    else:
        print(f"sequencer running (block time {cfg.block_time}s, commit "
              f"interval {cfg.commit_interval}s, proof coordinator on port "
              f"{seq.coordinator.port})")

    clients = []
    if args.l2_run_prover:
        from .prover.client import ProverClient

        for ptype in prover_types:
            client = ProverClient(
                ptype, [("127.0.0.1", seq.coordinator.port)])
            client.start()
            clients.append(client)
            print(f"in-process {ptype} prover polling the coordinator")
    import types

    return types.SimpleNamespace(node=node, l1=l1, seq=seq, rollup=rollup,
                                 server=server, clients=clients)


def run_l2(args) -> int:
    """`ethrex-tpu l2`: launch the sequencer stack (start_l2_stack)
    against a datadir with durable checkpoints and serve until a signal
    (reference: cmd/ethrex/cli.rs:562-676 `l2` subcommand tree +
    crates/l2/sequencer/mod.rs start_l2)."""
    stack = start_l2_stack(args)
    if isinstance(stack, int):
        return stack
    node, seq, rollup = stack.node, stack.seq, stack.rollup
    server, clients = stack.server, stack.clients

    # observability: sampler + SLO alerts + optional flight recorder
    # (fatal actor errors auto-snapshot via Sequencer's on_fatal hook)
    from .utils import snapshot
    from .utils.alerts import build_default_engine

    if args.debug_snapshot_dir:
        snapshot.configure(args.debug_snapshot_dir)
    if getattr(args, "profile_dir", None):
        from .perf import profiler as perf_profiler

        perf_profiler.configure(args.profile_dir)
    if getattr(args, "sender_workers", 0):
        from .blockchain import sender_recovery

        sender_recovery.configure(args.sender_workers)
    node.start_telemetry(alerts=build_default_engine(node))

    # coordinated drain: rpc -> prover clients -> sequencer (in-flight
    # proof submits land) -> producer -> flush+close both stores
    from .utils.shutdown import build_node_shutdown

    manager = build_node_shutdown(
        node=node, servers=[server], sequencer=seq,
        prover_clients=clients, stores=[node.store, rollup],
        deadline=args.shutdown_deadline)
    stop_event = _install_signal_handlers(stop_event=threading.Event())

    code = 0
    try:
        while seq.fatal is None and not stop_event.wait(0.5):
            pass
        if seq.fatal is not None:
            actor, err = seq.fatal
            print(f"fatal sequencer actor {actor}: {err}", file=sys.stderr)
            code = 1
    except KeyboardInterrupt:
        pass
    finally:
        report = manager.run()
        print(f"shutdown complete in {report['durationSeconds']:.2f}s "
              f"({len(report['steps'])} steps)")
    return code


def build_parser() -> argparse.ArgumentParser:
    flags = argparse.ArgumentParser(add_help=False)
    _add_node_flags(flags)
    parser = argparse.ArgumentParser(
        prog="ethrex-tpu", description="TPU-native Ethereum L1/L2 node",
        parents=[flags])
    # shared flags are accepted before OR after the subcommand (clap-style)
    sub = parser.add_subparsers(dest="command")

    p_import = sub.add_parser("import", parents=[flags],
                              help="import an RLP chain file")
    p_import.add_argument("file")
    p_export = sub.add_parser("export", parents=[flags],
                              help="export the canonical chain")
    p_export.add_argument("file")
    p_export.add_argument("--first", type=int, default=1)
    p_export.add_argument("--last", type=int, default=None)
    p_rm = sub.add_parser("removedb", parents=[flags],
                          help="delete the database directory")
    p_rm.add_argument("--force", action="store_true")
    sub.add_parser("compute-state-root", parents=[flags],
                   help="print the genesis state root")
    p_l2 = sub.add_parser("l2", parents=[flags],
                          help="run the L2 sequencer stack")
    p_l2.add_argument("--commit-interval", type=float,
                      default=float(_env("COMMIT_INTERVAL", "2.0")),
                      help="seconds between batch commits")
    batch_gas = _env("COMMITTER_BATCH_GAS_LIMIT")
    p_l2.add_argument("--committer.batch-gas-limit", dest="batch_gas_limit",
                      type=int,
                      default=int(batch_gas) if batch_gas else None,
                      help="most gas one batch may hold: the committer "
                           "seals a batch at the last whole block within "
                           "it (a lone block over it is a batch of its "
                           "own) and leaves the rest for its next tick; "
                           "default: every block up to the head")
    p_l2.add_argument("--l1.url", dest="l1_url",
                      default=_env("L1_URL"),
                      help="L1 JSON-RPC endpoint (omit for dev L1)")
    p_l2.add_argument("--l1.contract", dest="l1_contract",
                      default=_env("L1_CONTRACT"),
                      help="OnChainProposer contract address on L1")
    p_l2.add_argument("--l1.secret", dest="l1_secret",
                      default=_env("L1_SECRET"),
                      help="hex secret key for L1 commitment txs")
    p_l2.add_argument("--provers", dest="l2_provers",
                      default=_env("L2_PROVERS", "tpu"),
                      help="comma-separated required prover types")
    p_l2.add_argument("--run-prover", dest="l2_run_prover",
                      action="store_true",
                      help="also run in-process prover client(s)")
    p_l2.add_argument("--ha-role", dest="ha_role",
                      choices=("leader", "follower"),
                      default=_env("HA_ROLE"),
                      help="run HA leader election against the L1 lease "
                           "cell: 'leader' bids immediately, 'follower' "
                           "starts as a hot standby (docs/SEQUENCER_HA.md)")
    p_l2.add_argument("--leader-lease", dest="leader_lease", type=float,
                      default=float(_env("HA_LEASE", "3.0")),
                      help="leader lease TTL in seconds (renewal runs at "
                           "ttl/3; failover completes within one TTL)")
    p_l2.add_argument("--ha-node-id", dest="ha_node_id",
                      default=_env("HA_NODE_ID"),
                      help="stable node identity for the lease cell "
                           "(default: derived from role + process)")
    p_repl = sub.add_parser(
        "repl", help="interactive JSON-RPC shell against a running node")
    p_repl.add_argument("--url", default=_env("RPC_URL",
                                              "http://127.0.0.1:8545"))
    p_mon = sub.add_parser(
        "monitor", help="terminal dashboard for a running node")
    p_mon.add_argument("--url", default=_env("RPC_URL",
                                             "http://127.0.0.1:8545"))
    p_mon.add_argument("--interval", type=float, default=2.0)
    return parser


def main(argv=None):
    args = build_parser().parse_args(argv)

    # repl/monitor subcommands don't take the shared node flags
    from .utils.tracing import setup_logging

    setup_logging(getattr(args, "log_level", "info") or "info",
                  json_mode=bool(getattr(args, "log_json", False)))

    def cmd_repl(a):
        from .utils.repl import run as repl_run

        return repl_run(a.url)

    def cmd_monitor(a):
        from .utils.monitor import run as monitor_run

        return monitor_run(a.url, a.interval)

    handlers = {
        "import": cmd_import,
        "export": cmd_export,
        "removedb": cmd_removedb,
        "compute-state-root": cmd_compute_state_root,
        "l2": run_l2,
        "repl": cmd_repl,
        "monitor": cmd_monitor,
        None: run_node,
    }
    return handlers[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
