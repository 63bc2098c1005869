#!/usr/bin/env python3
"""North-star #2 harness: a mainnet-epoch attestation batch on the device.

BASELINE.json config 4 / BASELINE.md: "mainnet epoch verification load =
32 slots x 64 committees x up to 2,048 validators/committee (~900k active
validators)"; target >= 10x blst-on-32-core.  This measures exactly that
shape end-to-end on the device verify path:

* 2,048 aggregate signature sets (one per committee of the epoch), each
  carrying ~active/2048 member pubkeys,
* device-side committee aggregation (segment tree-reduce — the marshal
  step that costs ~900k G1 adds on a CPU) feeding the standard
  multi-aggregate pairing pipeline (backend._epoch_verify_kernel),
* one JSON line per run: sets/s, validators/s, and the blst-32-core
  comparison derived from the calibration constants below.

blst calibration (documented external figures, see BASELINE.md): a
server-class x86 core does a single pairing-verify in 0.5-1.4 ms and
batch verification amortizes ~2-3x; G1 point adds cost ~0.4-0.6 us.  An
epoch batch on blst-32-core therefore costs roughly
    (n_sets+1 Miller loops / amortization + n_validators G1 adds) / 32
with the OPTIMISTIC end of every range taken, so the reported ratio is a
floor, not a flattering estimate.

Usage:
    python tools/epoch_attestation_bench.py [--sets 2048] [--committee 440]
        [--pool 256] [--iters 2] [--cpu]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# blst calibration constants (optimistic/cheap end of the published ranges)
BLST_VERIFY_SEC = 0.5e-3  # single verify per core (fast end)
BLST_BATCH_AMORTIZATION = 3.0  # batch verify speedup (optimistic)
BLST_G1_ADD_SEC = 0.4e-6  # per point add (fast end)
BLST_CORES = 32


def blst_32core_epoch_seconds(n_sets: int, n_validators: int) -> float:
    pairing = (n_sets + 1) * BLST_VERIFY_SEC / BLST_BATCH_AMORTIZATION
    aggregation = n_validators * BLST_G1_ADD_SEC
    return (pairing + aggregation) / BLST_CORES


def build_epoch_batch(n_sets: int, committee: int, pool: int):
    """One epoch's aggregates with POOLED keys: committees sample a pool
    of ``pool`` distinct validators, and each set's aggregate signature is
    produced with the SUM of the member secret keys (identical group
    element to aggregating per-member signatures — BLS linearity), so
    building 900k memberships costs n_sets signs, not n_validators."""
    from lighthouse_tpu.crypto.bls import params
    from lighthouse_tpu.crypto.bls.api import SecretKey

    sks = [SecretKey(1000 + i) for i in range(pool)]
    pks = [sk.public_key().point for sk in sks]
    committees = []
    sigs = []
    msgs = []
    for s in range(n_sets):
        members = [(s * 7 + j * 3) % pool for j in range(committee)]
        committees.append([pks[m] for m in members])
        sk_agg = sum((1000 + m) for m in members) % params.R
        msg = s.to_bytes(8, "big") * 4
        sigs.append(SecretKey(sk_agg).sign(msg).point)
        msgs.append(msg)
    weights = [
        0x9E3779B97F4A7C15 ^ (i * 0x2545F4914F6CDD1D) or 1
        for i in range(n_sets)
    ]
    return committees, sigs, msgs, weights


def run(n_sets: int, committee: int, pool: int, iters: int) -> dict:
    import jax

    from lighthouse_tpu.crypto.bls.jax_backend.backend import (
        enable_compile_cache,
    )

    enable_compile_cache()
    from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
    from lighthouse_tpu.crypto.bls.jax_backend import points as P
    from lighthouse_tpu.crypto.bls.jax_backend.backend import (
        _epoch_verify_kernel,
        _pack_wbits,
        encode_committee_pubkeys,
    )

    dev = jax.devices()[0]
    positions = 1 << (committee - 1).bit_length()
    print(
        f"device={dev} sets={n_sets} committee={committee} "
        f"positions={positions} validators={n_sets * committee}",
        file=sys.stderr,
    )
    t0 = time.time()
    committees, sigs, msgs, weights = build_epoch_batch(
        n_sets, committee, pool
    )
    print(f"test-data build: {time.time() - t0:.1f}s", file=sys.stderr)

    t0 = time.time()
    pk_enc, pad_mask = encode_committee_pubkeys(committees, positions)
    sig_enc = P.g2_encode(sigs)
    h_enc = P.g2_encode([hash_to_g2(m) for m in msgs])
    wbits = _pack_wbits(weights)
    t_marshal = time.time() - t0
    print(
        f"host marshal (encode committees + hash): {t_marshal:.1f}s",
        file=sys.stderr,
    )

    args = jax.device_put((pk_enc, pad_mask, sig_enc, h_enc, wbits), dev)
    fn = jax.jit(_epoch_verify_kernel, static_argnums=5)
    t0 = time.time()
    ok = fn(*args, positions)
    ok = bool(ok)
    t_compile = time.time() - t0
    print(f"compile+first run: {t_compile:.1f}s ok={ok}", file=sys.stderr)
    assert ok, "epoch batch must verify"

    times = []
    for _ in range(iters):
        t0 = time.time()
        bool(fn(*args, positions))
        times.append(time.time() - t0)
    best = min(times)
    n_validators = n_sets * committee
    sets_per_s = n_sets / best
    validators_per_s = n_validators / best
    blst_sec = blst_32core_epoch_seconds(n_sets, n_validators)
    result = {
        "metric": "epoch_attestation_batch",
        "value": round(sets_per_s, 1),
        "unit": "sets/s",
        "vs_baseline": round(blst_sec / best / 10.0, 4),  # 1.0 == 10x blst-32c
        "device": str(dev),
        "sets": n_sets,
        "committee": committee,
        "validators_per_s": round(validators_per_s, 1),
        "batch_seconds": round(best, 3),
        "blst_32core_estimate_seconds": round(blst_sec, 4),
        "speedup_vs_blst_32core": round(blst_sec / best, 2),
        "host_marshal_seconds": round(t_marshal, 1),
        "compile_seconds": round(t_compile, 1),
    }
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--sets", type=int, default=2048)
    ap.add_argument("--committee", type=int, default=440)
    ap.add_argument("--pool", type=int, default=256)
    ap.add_argument("--iters", type=int, default=2)
    ap.add_argument("--cpu", action="store_true")
    args = ap.parse_args()
    if args.cpu:
        import jax

        jax.config.update("jax_platforms", "cpu")
    print(json.dumps(run(args.sets, args.committee, args.pool, args.iters)))


if __name__ == "__main__":
    main()
