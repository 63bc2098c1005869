"""CLI entry — twin of lighthouse/src/main.rs (clap tree, :50) and the
environment builder (lighthouse/environment): `python -m lighthouse_tpu
<subcommand>` with bn / vc / account / db subcommands, spec-preset
selection (--spec minimal|mainnet), and the runtime wiring (slot clock +
API server + chain) for an interop development node.
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    from .serve.stack import BLS_BACKENDS

    p = argparse.ArgumentParser(
        prog="lighthouse-tpu",
        description="TPU-native Ethereum consensus framework",
    )
    p.add_argument("--spec", choices=["minimal", "mainnet"], default="mainnet")
    sub = p.add_subparsers(dest="command", required=True)

    bn = sub.add_parser("bn", help="run a beacon node (interop genesis)")
    bn.add_argument("--validators", type=int, default=64)
    bn.add_argument("--http-port", type=int, default=5052)
    bn.add_argument("--datadir", default=None, help="slabdb path (memory if unset)")
    bn.add_argument("--bls-backend", choices=BLS_BACKENDS, default="python",
                    help="BLS engine that verifies: jax runs the batch "
                         "kernel on JAX's default device (the TPU where "
                         "one is attached)")
    bn.add_argument("--slots", type=int, default=0,
                    help="exit after N slots (0 = run until interrupted)")
    bn.add_argument("--auto-propose", action="store_true",
                    help="produce blocks with interop keys each slot")
    bn.add_argument("--discovery-port", type=int, default=None,
                    help="enable discv5 on this UDP port (0 = ephemeral)")
    bn.add_argument("--boot-nodes", default=None,
                    help="comma-separated enr: records to bootstrap from")
    bn.add_argument("--network", default=None,
                    choices=["mainnet", "sepolia", "holesky"],
                    help="use a built-in network config (boot ENRs + spec)")
    bn.add_argument("--testnet-dir", default=None,
                    help="load config.yaml/boot_enr.yaml from a directory")
    bn.add_argument("--chaos", action="append", default=[],
                    metavar="SITE=KIND[:ARG][xN]",
                    help="arm a fault before startup (repeatable), e.g. "
                         "bls.device_verify=errorx3 or "
                         "bls.device_verify=slow:0.5; network byzantine "
                         "kinds drop/stall/corrupt-chunk/wrong-blocks/"
                         "extra-blocks arm the req/resp sites, e.g. "
                         "rpc.respond=corrupt-chunk or "
                         "sync.request=stall:3.0x2; pod-mesh kinds arm "
                         "the per-shard sites, e.g. "
                         "pod.dispatch=shard-dropx1 or "
                         "pod.dispatch=device-hang:2.0 or "
                         "pod.gather=corrupt-shard-result — see "
                         "utils/faults.py")
    bn.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve /metrics (Prometheus text), /health, and "
                         "/trace (Chrome trace-event JSON of the flight "
                         "recorder, loadable in Perfetto) on this port "
                         "(0 = ephemeral); separate from the beacon API "
                         "server, like the reference's http_metrics")
    bn.add_argument("--serve-port", type=int, default=None,
                    metavar="PORT",
                    help="open the multi-tenant batch-verification "
                         "service (Beacon-API-shaped JSON submit/poll, "
                         "serve/http.py) on this port (0 = ephemeral); "
                         "shares the node's verifier ladder, e.g. "
                         "--serve-port 5053 next to the beacon API or "
                         "--serve-port 0 in tests; standalone twin: "
                         "tools/serve.py")
    bn.add_argument("--scenario", default=None,
                    metavar="NAME[:seed=N]",
                    help="run a named adversarial scenario (SLO-gated, "
                         "seed-deterministic; see scenario/spec.py and "
                         "tools/scenario_run.py --list) instead of "
                         "serving, e.g. --scenario smoke, "
                         "--scenario mainnet-shape:seed=99, or the "
                         "hostile regimes --scenario long-non-finality, "
                         "--scenario slashing-flood, "
                         "--scenario hostile-checkpoint-sync:epochs=4, "
                         "--scenario registry-pressure; exits 0/1 "
                         "on SLO pass/fail")
    bn.add_argument("--prewarm", action="store_true",
                    help="warm-boot phase: deserialize every current "
                         "entry of the AOT executable store "
                         "(<datadir>/aot_cache/, populated by earlier "
                         "runs) into the kernel cache and trace-compile "
                         "any misses BEFORE the beacon API, metrics, "
                         "serve front door or discovery open — a node "
                         "restarted over a populated store performs "
                         "zero tracing-compiles of staged programs on "
                         "its serving path (requires --datadir)")
    bn.add_argument("--tune", action="store_true",
                    help="first-contact kernel autotune: run timed "
                         "trials of every range-proven kernel arm "
                         "across the batch-shape ladder on THIS "
                         "device kind, persist the winning plan into "
                         "the AOT store's signed manifest, and install "
                         "it before any listener opens; subsequent "
                         "boots reinstall the plan via --prewarm with "
                         "zero trials (requires --datadir; "
                         "LIGHTHOUSE_TPU_MXU overrides the plan when "
                         "set)")
    bn.add_argument("--selfcheck", action="store_true",
                    help="boot-time known-answer suite: run the "
                         "verdict-integrity canary corpus through the "
                         "scalar path AND every installed kernel batch "
                         "shape of the active BLS backend (pairs with "
                         "--prewarm, which installs the store's working "
                         "set first), refusing to boot on any verdict "
                         "mismatch — a silently-corrupting device fails "
                         "the boot, never the chain")
    bn.add_argument("--upnp", action="store_true",
                    help="attempt UPnP port mapping for p2p/discovery "
                         "(best-effort; nat.rs analog)")

    vc = sub.add_parser("vc", help="run a validator client against a BN")
    vc.add_argument("--beacon-node", default="http://127.0.0.1:5052")
    vc.add_argument("--keys", type=int, default=8, help="interop key count")
    vc.add_argument("--slots", type=int, default=None,
                    help="exit after attesting through slot N (tests)")
    vc.add_argument("--fork", default="altair",
                    help="state fork variant the BN serves (SSZ decode)")

    acct = sub.add_parser("account", help="keystore/wallet operations")
    acct_sub = acct.add_subparsers(dest="account_cmd", required=True)
    new = acct_sub.add_parser("new", help="create an EIP-2335 keystore")
    new.add_argument("--password", required=True)
    new.add_argument("--index", type=int, default=0, help="EIP-2334 index")
    new.add_argument("--seed-hex", default=None)
    wallet = acct_sub.add_parser("wallet", help="create an EIP-2386 HD wallet")
    wallet.add_argument("--name", required=True)
    wallet.add_argument("--password", required=True)
    wallet.add_argument("--seed-hex", default=None)

    vm = sub.add_parser(
        "validator-manager", help="bulk validator operations"
    )
    vm_sub = vm.add_subparsers(dest="vm_cmd", required=True)
    create = vm_sub.add_parser("create", help="derive N validator keystores")
    create.add_argument(
        "--output-dir", default=None,
        help="install keystores into <dir>/validators/ with a manifest "
             "(validator_dir discipline; omit to print JSON)",
    )
    create.add_argument("--count", type=int, required=True)
    create.add_argument("--wallet-password", required=True)
    create.add_argument("--keystore-password", required=True)
    create.add_argument("--seed-hex", default=None)
    create.add_argument("--deposit-gwei", type=int, default=32_000_000_000)

    lcli = sub.add_parser("lcli", help="dev/ops utilities (lcli analog)")
    lcli_sub = lcli.add_subparsers(dest="lcli_cmd", required=True)
    skip = lcli_sub.add_parser("skip-slots", help="advance a state N slots")
    skip.add_argument("--slots", type=int, required=True)
    skip.add_argument("--validators", type=int, default=16)
    parse = lcli_sub.add_parser("parse-ssz", help="decode an SSZ file")
    parse.add_argument("--type", dest="ssz_type", required=True,
                       choices=["BeaconState", "SignedBeaconBlock"])
    parse.add_argument("--fork", default="base")
    parse.add_argument("path")

    db = sub.add_parser("db", help="database tools (database_manager analog)")
    db_sub = db.add_subparsers(dest="db_cmd", required=True)
    for name, help_ in (
        ("inspect", "entry/dead-byte counts via the engine"),
        ("compact", "rewrite the live set (atomic, fsync'd)"),
        ("verify", "offline integrity scan: per-column record counts, "
                   "CRC32-C failures, and the recovery report (exit 1 on "
                   "damage) — never opens the engine"),
    ):
        d = db_sub.add_parser(name, help=help_)
        d.add_argument("path")

    boot = sub.add_parser(
        "boot-node", help="run a standalone discv5 boot node (boot_node analog)"
    )
    boot.add_argument("--ip", default="127.0.0.1")
    boot.add_argument("--port", type=int, default=9000)
    boot.add_argument(
        "--run-secs", type=float, default=None, help="exit after N seconds (tests)"
    )

    watch = sub.add_parser(
        "watch", help="run the standalone watch analytics service"
    )
    watch.add_argument("--beacon-url", required=True)
    watch.add_argument("--db", default=":memory:")
    watch.add_argument("--port", type=int, default=0)
    watch.add_argument("--interval", type=float, default=1.0)
    watch.add_argument(
        "--run-secs", type=float, default=None, help="exit after N seconds (tests)"
    )

    sub.add_parser("version")
    return p


def _spec_for(name: str, n_validators: int):
    from .consensus import spec as S
    from .consensus.testing import phase0_spec

    preset = S.PRESETS[name]
    return phase0_spec(preset)


def run_bn(args) -> int:
    from .beacon.harness import BeaconChainHarness
    from .network.api import BeaconApiServer
    from .utils import get_logger, log_with
    import logging

    log = get_logger("bn")
    from .serve.stack import select_bls_backend

    device = select_bls_backend(args.bls_backend)
    if args.bls_backend == "jax":
        from .crypto.bls.jax_backend.backend import enable_compile_cache

        device["compile_cache"] = enable_compile_cache()
    log_with(log, logging.INFO, "BLS backend selected", **device)
    if getattr(args, "scenario", None):
        from .scenario import parse_scenario_arg
        from .scenario.engine import ScenarioEngine

        scn = parse_scenario_arg(args.scenario)
        log_with(log, logging.INFO, "Running scenario",
                 scenario=scn.name, seed=scn.seed)
        report = ScenarioEngine(scn).run()
        for s in report["slo"]:
            if s["ok"]:
                lvl, verdict = logging.INFO, "ok"
            elif s.get("level") == "warn":
                lvl, verdict = logging.WARNING, "WARN"
            else:
                lvl, verdict = logging.ERROR, "FAIL"
            log_with(log, lvl, f"SLO {verdict}",
                     gate=s["name"], observed=s["observed"],
                     threshold=s["threshold"])
        if report.get("trace_dump"):
            log_with(log, logging.WARNING, "Flight-recorder dump written",
                     path=report["trace_dump"])
        log_with(log, logging.INFO, "Scenario finished",
                 scenario=scn.name,
                 verdict="PASS" if report["pass"] else "FAIL",
                 fingerprint=report["fingerprint"])
        return 0 if report["pass"] else 1
    for spec_str in getattr(args, "chaos", []):
        from .utils import faults

        faults.arm_from_spec(spec_str)
        log_with(log, logging.WARNING, "Chaos fault armed", spec=spec_str)
    spec = _spec_for(args.spec, args.validators)
    boot_enrs = []
    if args.testnet_dir:
        from .consensus.network_config import Eth2NetworkConfig

        net = Eth2NetworkConfig.from_dir(args.testnet_dir)
        spec, boot_enrs = net.chain_spec, net.boot_enrs()
        log_with(log, logging.INFO, "Loaded testnet dir", name=net.name)
    elif args.network:
        from .consensus.network_config import HARDCODED_NETWORKS

        net = HARDCODED_NETWORKS[args.network]()
        spec, boot_enrs = net.chain_spec, net.boot_enrs()
        log_with(log, logging.INFO, "Using built-in network", name=net.name)
    if args.boot_nodes:
        from .network.enr import Enr

        boot_enrs += [Enr.from_text(t) for t in args.boot_nodes.split(",")]
    store = None
    if args.datadir:
        import os

        from .consensus.containers import types_for
        from .store import HotColdDB, SlabStore

        os.makedirs(args.datadir, exist_ok=True)
        store = HotColdDB(
            store=SlabStore(os.path.join(args.datadir, "beacon.slab")),
            types_family=types_for(spec.preset),
        )
    # AOT executable store under the datadir: attach it whenever a
    # datadir exists (normal operation then captures each compiled
    # program), and with --prewarm install every current entry NOW —
    # before the harness compiles anything and before any listener
    # (API / metrics / serve / discovery) opens.  ROADMAP item 4.
    aot_store = None
    if args.datadir:
        import os

        from .crypto.bls import api as _bls_api
        from .crypto.bls.jax_backend import aot as _aot

        backend = _bls_api.get_backend()
        if hasattr(backend, "attach_aot_store"):
            aot_store = _aot.AotStore(
                os.path.join(args.datadir, "aot_cache")
            )
            backend.attach_aot_store(aot_store)
            if args.tune:
                # First-contact tuning: measure every legal arm on this
                # silicon and persist the plan BEFORE prewarm, so the
                # prewarm pass below (and every later boot's) installs
                # and loads against the tuned routing.  Best-effort: a
                # failed tune costs this boot the plan, nothing else.
                try:
                    from .crypto.bls.jax_backend import autotune as _autotune

                    t_tune = time.perf_counter()
                    plan = _autotune.tune_and_store(aot_store)
                    log_with(log, logging.INFO, "Kernel autotune done",
                             device_kind=plan.get("device_kind"),
                             shapes=len(plan.get("shapes", {})),
                             wall_s=round(time.perf_counter() - t_tune, 3))
                except Exception as exc:  # noqa: BLE001 — tune is optional
                    log_with(log, logging.WARNING, "Kernel autotune failed",
                             error=str(exc))
            if args.prewarm:
                t_warm = time.perf_counter()
                report = _aot.prewarm(
                    backend, aot_store, compile_misses=True
                )
                log_with(log, logging.INFO, "Prewarm boot phase done",
                         **report.to_row())
                _aot.record_boot_row(dict(
                    report.to_row(), phase="prewarm",
                    wall_s=round(time.perf_counter() - t_warm, 3),
                ))
        elif args.prewarm or args.tune:
            log_with(log, logging.WARNING,
                     "--prewarm/--tune: active BLS backend has no AOT seam",
                     backend=getattr(backend, "name", "?"))
    elif args.prewarm or args.tune:
        log_with(log, logging.WARNING,
                 "--prewarm/--tune needs --datadir (the store lives under "
                 "it); skipping")
    if args.selfcheck:
        # after prewarm so the kernel sweep covers the installed working
        # set, before any listener so a lying device can never serve
        from .integrity import run_selfcheck

        t_chk = time.perf_counter()
        chk = run_selfcheck()
        log_with(log, logging.INFO, "Integrity selfcheck done",
                 ok=chk.ok, checked=chk.checked,
                 kernel_batches=",".join(map(str, chk.batch_sizes)) or "-",
                 wall_s=round(time.perf_counter() - t_chk, 3))
        if not chk.ok:
            for line in chk.mismatches:
                log_with(log, logging.ERROR, "Selfcheck mismatch",
                         detail=line)
            log_with(log, logging.ERROR,
                     "Integrity selfcheck FAILED; refusing to boot")
            return 1
    h = BeaconChainHarness(n_validators=args.validators, spec=spec, store=store)
    server = BeaconApiServer(h.chain, port=args.http_port)
    server.start()
    metrics_server = None
    if args.metrics_port is not None:
        from .obs import MetricsServer

        metrics_server = MetricsServer(port=args.metrics_port).start()
        log_with(log, logging.INFO, "Metrics endpoint up",
                 url=f"http://127.0.0.1:{metrics_server.port}/metrics",
                 endpoints="/metrics,/health,/trace")
    serve_service = serve_server = None
    if args.serve_port is not None:
        from .serve import ServeApiServer, VerifyService

        # the shared construction path (serve/stack.py) builds the same
        # ingest/resilient/pod ladder the node wires, over this chain's
        # pubkey cache — node-embedded serving, identical verdicts
        serve_service = VerifyService.standalone(
            pubkey_cache=getattr(h.chain, "pubkey_cache", None),
        ).start()
        serve_server = ServeApiServer(
            serve_service, port=args.serve_port
        ).start()
        log_with(log, logging.INFO, "Verification service up",
                 url=f"http://127.0.0.1:{serve_server.port}"
                     "/eth/v1/verify/batch")
    discovery = None
    if args.discovery_port is not None:
        from .network.discv5 import Discv5Service

        discovery = Discv5Service(port=args.discovery_port)
        discovery.start()
        if boot_enrs:
            discovery.bootstrap(boot_enrs)
            discovery.lookup()
        log_with(
            log, logging.INFO, "Discovery started",
            enr=discovery.enr.to_text()[:40] + "...",
            udp_port=discovery.port, table=len(discovery.table),
        )
    upnp = None
    if args.upnp:
        # best-effort (nat.rs posture): a missing/refusing gateway logs
        # and the node continues unreachable-from-outside.  Maps the
        # DISCOVERY UDP port (the only p2p socket this mode owns) to the
        # host's real LAN address — never the unauthenticated HTTP API.
        from .network.nat import PortMappingService, lan_address

        if discovery is None:
            log_with(log, logging.WARNING,
                     "--upnp needs --discovery-port; nothing to map")
        else:
            try:
                upnp = PortMappingService(
                    lan_address(), tcp_port=None, udp_port=discovery.port
                )
                upnp.start()
                log_with(log, logging.INFO, "UPnP discovery mapping installed",
                         udp=discovery.port)
            except Exception as exc:  # noqa: BLE001
                upnp = None
                log_with(log, logging.WARNING, "UPnP unavailable",
                         error=str(exc))
    log_with(
        log, logging.INFO, "Beacon node started",
        spec=args.spec, validators=args.validators,
        http=f"http://127.0.0.1:{server.port}",
    )
    slot = 0
    try:
        while args.slots == 0 or slot < args.slots:
            time.sleep(spec.seconds_per_slot if args.slots == 0 else 0.01)
            slot += 1
            h.set_slot(slot)
            if args.auto_propose:
                h.add_block_at_slot(slot)
                h.attest_to_head(slot)
                st = h.head_state()
                log_with(
                    log, logging.INFO, "Slot processed", slot=slot,
                    head=h.chain.head_root.hex()[:8],
                    justified=int(st.current_justified_checkpoint.epoch),
                    finalized=int(st.finalized_checkpoint.epoch),
                )
    except KeyboardInterrupt:
        pass
    finally:
        if upnp is not None:
            upnp.stop()  # delete the WAN mapping; stop the renewals
        if discovery is not None:
            discovery.stop()
        if serve_server is not None:
            serve_server.stop()
        if serve_service is not None:
            serve_service.stop()
        if metrics_server is not None:
            metrics_server.stop()
        server.stop()
    return 0


def run_vc(args) -> int:
    """The validator-client process: duties + sign + publish over the
    Beacon API (validator_client/src/lib.rs posture)."""
    from .network.api import BeaconApiClient
    from .validator.remote import run_validator_client

    client = BeaconApiClient(args.beacon_node)
    print(json.dumps({"version": client.node_version(),
                      "syncing": client.node_syncing()}), flush=True)
    spec = _spec_for(args.spec, args.keys)
    published = 0
    try:
        published = run_validator_client(
            args.beacon_node, args.keys, slots=args.slots, spec=spec,
            fork=args.fork,
        )
    except KeyboardInterrupt:
        pass
    print(json.dumps({"published_attestations": published}))
    return 0


def run_account(args) -> int:
    if args.account_cmd == "wallet":
        from .crypto import wallet as wlt

        seed = bytes.fromhex(args.seed_hex) if args.seed_hex else None
        print(json.dumps(wlt.create_wallet(args.name, args.password, seed=seed),
                         indent=2))
        return 0
    from .crypto import keys as kd
    from .crypto import keystore as ks
    from .crypto.bls.api import SecretKey

    seed = (
        bytes.fromhex(args.seed_hex)
        if args.seed_hex
        else __import__("os").urandom(32)
    )
    path = kd.validator_signing_path(args.index)
    sk_int = kd.derive_path(seed, path)
    sk = SecretKey(sk_int)
    store = ks.encrypt(
        sk.to_bytes(), args.password, path=path,
        pubkey=sk.public_key().to_bytes(),
    )
    print(json.dumps(store, indent=2))
    return 0


def run_validator_manager(args) -> int:
    from .crypto import wallet as wlt

    seed = bytes.fromhex(args.seed_hex) if args.seed_hex else None
    w = wlt.create_wallet("vm", args.wallet_password, seed=seed)
    mgr = None
    if getattr(args, "output_dir", None):
        from .validator.validator_dir import ValidatorDirManager

        mgr = ValidatorDirManager(args.output_dir)
    out = []
    for _ in range(args.count):
        signing, withdrawal = wlt.next_validator(
            w, args.wallet_password, args.keystore_password
        )
        if mgr is not None:
            mgr.create(signing)
        out.append(
            {
                "voting_pubkey": "0x" + signing["pubkey"],
                "withdrawal_pubkey": "0x" + withdrawal["pubkey"],
                "deposit_gwei": args.deposit_gwei,
                "keystore": signing,
            }
        )
    print(json.dumps(out, indent=1))
    return 0


def run_lcli(args) -> int:
    if args.lcli_cmd == "skip-slots":
        import time as _t

        from .consensus import spec as S
        from .consensus.state_processing.per_slot import process_slots
        from .consensus.testing import interop_state, phase0_spec

        spec = phase0_spec(S.PRESETS[args.spec])
        state, _ = interop_state(args.validators, spec, fork="altair")
        t0 = _t.perf_counter()
        state = process_slots(state, args.slots, spec)
        dt = _t.perf_counter() - t0
        print(json.dumps({
            "slots": args.slots,
            "validators": args.validators,
            "seconds": round(dt, 3),
            "slots_per_sec": round(args.slots / dt, 1),
            "state_root": "0x" + state.root().hex(),
        }))
        return 0
    if args.lcli_cmd == "parse-ssz":
        from .consensus import spec as S
        from .consensus.containers import types_for
        from .network.api import to_json

        T = types_for(S.PRESETS[args.spec])
        cls = {
            "BeaconState": T.BeaconState_BY_FORK,
            "SignedBeaconBlock": T.SignedBeaconBlock_BY_FORK,
        }[args.ssz_type][args.fork]
        with open(args.path, "rb") as f:
            obj = cls.deserialize_value(f.read())
        print(json.dumps(to_json(cls, obj))[:100000])
        return 0
    return 2


def run_db(args) -> int:
    from .store import SlabStore, DBColumn

    if args.db_cmd == "verify":
        # independent Python-side scan (store/wal.py): reads the log
        # directly, verifying every record CRC — usable on a damaged file
        # the engine would truncate on open
        from .store.wal import verify_file

        report = verify_file(args.path)
        print(json.dumps(report, indent=2))
        return 0 if report["ok"] else 1

    s = SlabStore(args.path)
    if args.db_cmd == "inspect":
        info = {"entries": len(s), "dead_bytes": s.dead_bytes()}
        info["per_column"] = {
            c.name: len(s.keys(c)) for c in DBColumn if s.keys(c)
        }
        print(json.dumps(info, indent=2))
    elif args.db_cmd == "compact":
        before = s.dead_bytes()
        s.compact()
        print(json.dumps({"reclaimed_bytes": before}))
    s.close()
    return 0


def run_boot_node(args) -> int:
    """Standalone discovery bootstrap server (boot_node/src/server.rs:
    serve FINDNODE from a table fed only by inbound traffic)."""
    import time as _time

    from .network.discv5 import BootNode

    node = BootNode(ip=args.ip, port=args.port)
    node.start()
    print(node.enr.to_text(), flush=True)
    try:
        if args.run_secs is not None:
            _time.sleep(args.run_secs)
        else:
            while True:
                _time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        node.stop()
    return 0


def run_watch(args) -> int:
    """`lighthouse_tpu watch`: the standalone analytics service following
    a BN over the Beacon API (the reference's `watch/` process)."""
    import time

    from .watch import WatchDaemon

    daemon = WatchDaemon(args.beacon_url, db_path=args.db,
                         http_port=args.port)
    daemon.start(interval=args.interval)
    print(f"watch up: http=127.0.0.1:{daemon.port} -> {args.beacon_url}",
          flush=True)
    try:
        if args.run_secs is not None:
            time.sleep(args.run_secs)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        daemon.stop()
    return 0


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    if args.command == "version":
        from .network.api import VERSION

        print(VERSION)
        return 0
    return {
        "bn": run_bn,
        "vc": run_vc,
        "account": run_account,
        "validator-manager": run_validator_manager,
        "lcli": run_lcli,
        "db": run_db,
        "boot-node": run_boot_node,
        "watch": run_watch,
    }[args.command](args)


if __name__ == "__main__":
    sys.exit(main())
