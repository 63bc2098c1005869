#!/usr/bin/env python3
"""Smoke run of the served BLS verify path on a TPU.

``python chip_smoke.py`` (one v5e chip) boots the verification service
through ``tools/serve.py``'s own entry with ``--bls-backend jax`` — the
daemon's wiring, the default compiled sizes (64, 256, 1024) warmed before
traffic, the integrity guard on — and sends mainnet-shaped traffic over
real HTTP:

* gossip: 1,024 single-key attestation sets from 4 tenants, 64 per
  submission (16 attestation messages, 64 signers each);
* committee aggregates: 64 sets, each a 440-member committee drawn from a
  4,096-key registry;
* sync committee: 4 sets of 512 keys;
* 16 of those sets poisoned, spread across submissions: half sign the
  wrong message, half carry a signer outside the set.

Every verdict must equal the one known by construction, 8 sets are
re-checked on the pure-Python backend, no fallback, retry or integrity
counter may move, the breaker must end closed with no CPU rung in the
ladder's journal, no program may compile after warm-up, and the largest
dispatched program must hold Mosaic kernels (``tpu_custom_call``), not
interpreted ones.

``python chip_smoke.py --four-chips`` (a v5e 2x2 host) runs only the same
sets through the stack as ``build_verify_stack`` wires it over 4 devices
(PodVerifier -> ShardedVerifyProgram, registry-sharded pubkey gather for
single-key sets), one compiled size (64), and the same sets on
``devices[0]`` alone; verdicts must match set for set.

One process, no platform override.  Earlier lines report each phase
(sets, wall seconds, compile seconds, peak device bytes; informational,
not benchmark metrics); the last line is ``{"ok": true, "device": ...}``.
With no TPU, or on any failed check, it exits non-zero with
``"ok": false``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import random
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

TENANTS = ("vc-0", "vc-1", "vc-2", "vc-3")
REGISTRY = 4096
GOSSIP_MESSAGES, GOSSIP_SIGNERS = 16, 64     # 1,024 single-key sets
GOSSIP_PER_SUBMISSION = 64
COMMITTEES, COMMITTEE_SIZE = 64, 440         # PERF.md epoch shape
COMMITTEES_PER_SUBMISSION = 16
SYNC_SETS, SYNC_SIZE = 4, 512
POISON = {"gossip": 10, "committee": 4, "sync": 2}   # 16 in all
DEADLINE_MS = 12_000                         # one slot: compiles are done
FOUR_CHIP_SIZES = (64,)
WATCHDOG_S = 1150                            # the driver allows 1,200 s

#: ladder rungs that would answer on the host (or not at all) instead of
#: the device; a smoke run that touched one proves nothing about the chip
RUNG_COUNTERS = (
    "VERIFY_DEGRADED_BATCHES", "VERIFY_DEVICE_RETRIES", "PIPELINE_FALLBACKS",
    "INGEST_FALLBACKS", "INTEGRITY_DISTRUSTED", "INTEGRITY_RELADDERED",
    "INTEGRITY_QUARANTINES", "INTEGRITY_GUARD_BACKSTOPS",
)


class CheckFailed(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def emit(doc: dict) -> None:
    print(json.dumps(doc), flush=True)


# ---------------------------------------------------------------------------
# Traffic: built from --seed, untimed test data
# ---------------------------------------------------------------------------


class Registry:
    """4,096 validator keys with consecutive secret keys, so each public
    key is one point addition from the last.  Doubles as the beacon
    ``ValidatorPubkeyCache`` surface (``len`` / ``get``) the ingest
    engine's registry tier syncs from."""

    def __init__(self, base: int, n: int):
        from lighthouse_tpu.crypto.bls.api import PublicKey, SecretKey
        from lighthouse_tpu.crypto.bls.curve import G1_GENERATOR, affine_add
        from lighthouse_tpu.crypto.bls.fields import Fp

        self.base = base
        pt = SecretKey(base).public_key().point
        self.keys = []
        for _ in range(n):
            self.keys.append(PublicKey(pt))
            pt = affine_add(pt, G1_GENERATOR, Fp)

    def sk(self, i: int) -> int:
        return self.base + i

    def __len__(self) -> int:
        return len(self.keys)

    def get(self, i: int):
        return self.keys[i] if 0 <= i < len(self.keys) else None


def _root(*parts) -> bytes:
    return hashlib.sha256("|".join(map(str, parts)).encode()).digest()


def build_traffic(seed: int):
    """(registry, phases): phases maps a phase name to its submissions,
    each ``(tenant, [(SignatureSet, expected_verdict), ...])``."""
    from lighthouse_tpu.crypto.bls import params
    from lighthouse_tpu.crypto.bls.api import (
        SecretKey, Signature, SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.curve import affine_add
    from lighthouse_tpu.crypto.bls.fields import Fp2
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2

    rng = random.Random(seed)
    reg = Registry(rng.randrange(1, params.R - 2 * REGISTRY), REGISTRY)
    outside = rng.randrange(1, params.R)        # a key in no set

    def poisoned(n_sets, n, per):
        """Which sets to poison: ``n`` of them, one per ``per``-sized
        submission where there are enough; even ones sign the wrong
        message, odd ones carry a signer outside the set."""
        kinds = {}
        for k in range(n):
            i = (k * per + rng.randrange(per)) % n_sets
            while i in kinds:
                i = (i + 1) % n_sets
            kinds[i] = "wrong-message" if k % 2 == 0 else "outside-signer"
        return kinds

    def signed(members, msg, kind):
        sk = sum(reg.sk(i) for i in members)
        if kind == "outside-signer":
            sk += outside
        if kind == "wrong-message":
            msg = msg + b"/wrong"
        return SecretKey(sk % params.R).sign(msg)

    # gossip: 64 consecutive validators sign each attestation message, so
    # each valid signature is one G2 addition of H(m) from its neighbour's
    bad = poisoned(GOSSIP_MESSAGES * GOSSIP_SIGNERS, POISON["gossip"],
                   GOSSIP_PER_SUBMISSION)
    gossip = []
    for c in range(GOSSIP_MESSAGES):
        msg = _root(seed, "gossip", c)
        h = hash_to_g2(msg)
        first = c * GOSSIP_SIGNERS
        sig = SecretKey(reg.sk(first)).sign(msg).point
        for v in range(first, first + GOSSIP_SIGNERS):
            kind = bad.get(len(gossip))
            this = Signature(sig) if kind is None else signed([v], msg, kind)
            gossip.append((SignatureSet(this, [reg.keys[v]], msg),
                           kind is None))
            sig = affine_add(sig, h, Fp2)

    def aggregates(name, n_sets, size, n_bad, per):
        bad = poisoned(n_sets, n_bad, per)
        out = []
        for c in range(n_sets):
            members = rng.sample(range(REGISTRY), size)
            msg = _root(seed, name, c)
            out.append((SignatureSet(signed(members, msg, bad.get(c)),
                                     [reg.keys[i] for i in members], msg),
                        c not in bad))
        return out

    committee = aggregates("committee", COMMITTEES, COMMITTEE_SIZE,
                           POISON["committee"], COMMITTEES_PER_SUBMISSION)
    sync = aggregates("sync", SYNC_SETS, SYNC_SIZE, POISON["sync"], 1)

    def submissions(sets, per):
        return [(TENANTS[k % len(TENANTS)], sets[i:i + per])
                for k, i in enumerate(range(0, len(sets), per))]

    return reg, {
        "gossip": submissions(gossip, GOSSIP_PER_SUBMISSION),
        "committee": submissions(committee, COMMITTEES_PER_SUBMISSION),
        "sync": submissions(sync, SYNC_SETS),
    }


def oracle_sample(phases, n_valid: int = 4, n_bad: int = 4):
    """The sets re-checked on the pure-Python backend: valid and
    poisoned ones from every phase."""
    pool = [(p, s, ok) for p, subs in phases.items()
            for _t, sets in subs for s, ok in sets]
    good = [x for x in pool if x[2]]
    bad = [x for x in pool if not x[2]]
    pick = []
    for want, src in ((n_valid, good), (n_bad, bad)):
        by_phase = {}
        for x in src:
            by_phase.setdefault(x[0], []).append(x)
        names = sorted(by_phase)
        for k in range(want):
            lst = by_phase[names[k % len(names)]]
            pick.append(lst[(k // len(names)) % len(lst)])
    return pick


def check_oracle(sample) -> None:
    from lighthouse_tpu.crypto.bls import api

    cpu = api.cpu_backend()
    for phase, s, expected in sample:
        got = bool(cpu.verify_signature_sets([s]))
        check(got == expected,
              f"python backend says {got} for a {phase} set built "
              f"{'valid' if expected else 'poisoned'}")


# ---------------------------------------------------------------------------
# Device observations
# ---------------------------------------------------------------------------


def counters() -> dict:
    from lighthouse_tpu.utils import metrics as M

    out = {name: getattr(M, name).value() for name in RUNG_COUNTERS}
    out["compiles"] = M.JIT_COMPILE_SECONDS.count()
    out["compile_s"] = M.JIT_COMPILE_SECONDS.sum()
    out["pod_sharded_fallbacks"] = M.POD_SHARDED_FALLBACKS.value()
    return out


def peak_bytes(device) -> int | None:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def phase_line(name, n_sets, wall, before, after, device, **extra) -> None:
    emit({"phase": name, "sets": n_sets, "wall_s": wall,
          "compile_s": after["compile_s"] - before["compile_s"],
          "peak_bytes_in_use": peak_bytes(device), **extra})


def check_ladder(stack, start: dict) -> None:
    end = counters()
    for name in RUNG_COUNTERS + ("pod_sharded_fallbacks",):
        check(end[name] == start[name],
              f"{name} moved {start[name]} -> {end[name]}: a rung other "
              f"than the device answered")
    check(end["compiles"] == start["compiles"],
          f"{end['compiles'] - start['compiles']} program(s) compiled "
          f"after warm-up: a batch ran at a size nobody warmed")
    check(stack.breaker.is_closed, "circuit breaker is not closed")
    journal = list(stack.resilient.journal)
    check(not [e for e in journal if e[0] == "cpu"],
          f"the ladder's journal holds CPU entries: {journal}")


def check_mosaic(backend) -> dict:
    """The largest program the backend dispatched must hold Mosaic
    kernels: ``tpu_custom_call`` in its compiled text.  Interpreted
    Pallas lowers to plain HLO loops instead."""
    import jax

    key = max(k for k in backend._kernels if isinstance(k[0], int))
    B = key[0]
    args = jax.device_put(backend.warm_batch(B).args)
    compiled = backend._kernels[key].jitted.lower(*args).compile()
    n = compiled.as_text().count("tpu_custom_call")
    check(n > 0, f"B={B} program holds no tpu_custom_call: Pallas ran "
                 f"interpreted")
    return {"batch": B, "tpu_custom_calls": n}


# ---------------------------------------------------------------------------
# One chip: the daemon over HTTP
# ---------------------------------------------------------------------------


def _wire(sets) -> list:
    return [{"signature": "0x" + s.signature.to_bytes().hex(),
             "pubkeys": ["0x" + pk.to_bytes().hex() for pk in s.signing_keys],
             "message": "0x" + s.message.hex()} for s in sets]


def _http(method: str, url: str, doc=None) -> dict:
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(url, data=data, method=method)
    with urllib.request.urlopen(req, timeout=120) as resp:
        return json.loads(resp.read())["data"]


def run_phase(base: str, submissions, timeout_s: float = 600.0) -> list:
    """POST every submission, then poll each until done; returns the
    verdict lists in submission order."""
    rids = []
    for tenant, sets in submissions:
        doc = {"tenant": tenant, "deadline_ms": DEADLINE_MS,
               "sets": _wire([s for s, _ in sets])}
        out = _http("POST", f"{base}/eth/v1/verify/batch", doc)
        rids.append(out["request_id"])
    verdicts = []
    end = time.monotonic() + timeout_s
    for rid in rids:
        while True:
            out = _http("GET", f"{base}/eth/v1/verify/batch/{rid}")
            if out["status"] == "done":
                verdicts.append(out["verdicts"])
                break
            check(time.monotonic() < end, f"request {rid} never finished")
            time.sleep(0.05)
    return verdicts


def one_chip(seed: int, device) -> None:
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import serve as serve_tool

    t0 = time.perf_counter()
    _reg, phases = build_traffic(seed)
    check_oracle(oracle_sample(phases))
    emit({"phase": "traffic", "sets": sum(len(s) for subs in phases.values()
                                          for _t, s in subs),
          "build_s": time.perf_counter() - t0})

    before = counters()
    t0 = time.perf_counter()
    service, server, boot = serve_tool.start([
        "--port", "0", "--bls-backend", "jax",
        "--rate", "1000000", "--burst", "1000000", "--max-queue", "100000",
    ])
    try:
        after = counters()
        phase_line("warm", 0, time.perf_counter() - t0, before, after, device,
                   compile_s_per_size=boot["warm_seconds"],
                   compile_cache=boot.get("compile_cache"))
        stack = service.stack
        start = counters()
        base = f"http://127.0.0.1:{server.port}"
        for name, subs in phases.items():
            before = counters()
            t0 = time.perf_counter()
            got = run_phase(base, subs)
            wall = time.perf_counter() - t0
            for (tenant, sets), verdicts in zip(subs, got):
                want = [ok for _s, ok in sets]
                check(verdicts == want,
                      f"{name} submission from {tenant}: verdicts {verdicts} "
                      f"!= expected {want}")
            phase_line(name, sum(len(s) for _t, s in subs), wall, before,
                       counters(), device,
                       poisoned=sum(not ok for _t, s in subs for _s, ok in s))
        check_ladder(stack, start)
        emit({"phase": "mosaic", **check_mosaic(stack.backend)})
    finally:
        server.stop()
        service.stop()


# ---------------------------------------------------------------------------
# Four chips: the pod's sharded program against devices[0] alone
# ---------------------------------------------------------------------------


def four_chips(seed: int, device) -> None:
    from lighthouse_tpu.beacon.processor import verify_with_bisection
    from lighthouse_tpu.crypto.bls.jax_backend.backend import (
        enable_compile_cache,
    )
    from lighthouse_tpu.serve.stack import build_verify_stack, select_bls_backend

    t0 = time.perf_counter()
    reg, phases = build_traffic(seed)
    check_oracle(oracle_sample(phases))
    emit({"phase": "traffic", "build_s": time.perf_counter() - t0})

    select_bls_backend("jax")
    cache = enable_compile_cache()
    stack = build_verify_stack(pubkey_cache=reg, batch_sizes=FOUR_CHIP_SIZES)
    check(stack.pod is not None, "no PodVerifier was wired over 4 devices")
    before = counters()
    t0 = time.perf_counter()
    warm = stack.warm(FOUR_CHIP_SIZES)
    phase_line("warm", 0, time.perf_counter() - t0, before, counters(),
               device, compile_s_per_size=warm, compile_cache=cache)

    start = counters()
    backend = stack.backend
    for name, subs in phases.items():
        n = sum(len(s) for _t, s in subs)
        for label, verify in (
                ("pod", lambda sets: stack.verifier.verify_batch(sets)),
                ("device0", lambda sets: verify_with_bisection(
                    backend.verify_signature_sets, sets))):
            before = counters()
            t0 = time.perf_counter()
            got = [list(verify([s for s, _ in sets]).verdicts)
                   for _t, sets in subs]
            phase_line(f"{name}/{label}", n, time.perf_counter() - t0,
                       before, counters(), device)
            for (tenant, sets), verdicts in zip(subs, got):
                want = [ok for _s, ok in sets]
                check(verdicts == want,
                      f"{name}/{label} batch from {tenant}: verdicts "
                      f"{verdicts} != expected {want}")
    check_ladder(stack, start)
    modes = {deferred for prog in stack.pod._sharded_programs.values()
             for deferred, _names in prog._programs}
    check(modes == {False, True},
          f"sharded program modes run: {sorted(modes)}; both the plain and "
          f"the registry-gather program must have run")
    check(any(e[0] == "pod" for e in stack.resilient.journal),
          "no batch completed on the sharded program")


def _expire() -> None:
    emit({"ok": False, "error": f"watchdog: still running after "
                                f"{WATCHDOG_S} s"})
    os._exit(3)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-device sharded path and its "
                         "devices[0] comparison (a v5e 2x2 host)")
    args = ap.parse_args(argv)
    want = 4 if args.four_chips else 1
    watchdog = threading.Timer(WATCHDOG_S, _expire)
    watchdog.daemon = True
    watchdog.start()
    try:
        import jax

        devices = jax.devices()
        info = {"platform": devices[0].platform,
                "kind": devices[0].device_kind, "count": len(devices)}
        check(info["platform"] == "tpu",
              f"JAX finds no TPU (platform {info['platform']!r})")
        check(len(devices) >= want,
              f"need {want} TPU devices, JAX shows {len(devices)}")
        sys.path.insert(0, ROOT)
        if args.four_chips:
            four_chips(args.seed, devices[0])
        else:
            one_chip(args.seed, devices[0])
    except Exception as exc:  # noqa: BLE001 — report, then fail the run
        import traceback

        traceback.print_exc()
        emit({"ok": False, "error": f"{type(exc).__name__}: {exc}"[:2000]})
        return 1
    emit({"ok": True, "device": info})
    return 0


if __name__ == "__main__":
    sys.exit(main())
