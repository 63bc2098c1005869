#!/usr/bin/env python3
"""The device public-key check against the plain reference, key by key.

    python tools/keycheck_compare.py --keys 4096 --non-members 512 \\
        --seed 7 --sizes 64,256,1024

Builds ``--keys`` compressed G1 keys from ``--seed``: members (consecutive
multiples of the generator from a seeded start, as the benchmark's
validators are) and ``--non-members`` on-curve points outside G1 (seeded
random x, plus the two order-3 points at x = 0).  Each key's reference
verdict is whether ``benchmark/bls_ref.py``'s ``g1_decompress`` accepts
it; the program decodes each without the subgroup check
(``PublicKey.from_bytes_unchecked``) and asks ``JaxBackend.g1_keycheck``
at every lane count in ``--sizes``.  Prints one JSON line per size (first
call seconds, calls, milliseconds per call, disagreements) and a last
line ``{"ok": ...}``; exits 1 on any disagreement.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
from concurrent.futures import ProcessPoolExecutor

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import bls_ref as B  # noqa: E402


def make_keys(n: int, non_members: int, seed: int) -> list:
    """``(compressed key, planted as member)`` pairs, shuffled by seed."""
    rng = random.Random(seed)
    out = []
    pt = B.g1_mul(B.G1_GEN, rng.randrange(1 << 40, 1 << 48))
    for _ in range(n - non_members):
        pt = B.g1_add(pt, B.G1_GEN)
        out.append((B.g1_compress(B.g1_affine(pt)), True))
    odd = [(0, 2), (0, B.P - 2)]
    while len(odd) < non_members:
        x = rng.randrange(B.P)
        y = B.fp_sqrt((x * x * x + B.B1) % B.P)
        if y is not None:
            odd.append((x, y))
    out += [(B.g1_compress(p), False) for p in odd[:non_members]]
    rng.shuffle(out)
    return out


def reference(raw: bytes) -> bool:
    try:
        return B.g1_decompress(raw) is not None
    except B.DecodeError:
        return False


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--keys", type=int, default=4096)
    ap.add_argument("--non-members", type=int, default=512)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--sizes", default="64")
    ap.add_argument("--workers", type=int, default=8)
    args = ap.parse_args(argv)

    keys = make_keys(args.keys, args.non_members, args.seed)
    with ProcessPoolExecutor(args.workers) as pool:
        ref = list(pool.map(reference, [k for k, _ in keys], chunksize=64))
    planted_wrong = sum(r != m for r, (_k, m) in zip(ref, keys))

    import jax

    from lighthouse_tpu.crypto.bls.api import PublicKey
    from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

    points = [PublicKey.from_bytes_unchecked(k).point for k, _ in keys]
    ok = planted_wrong == 0
    for size in [int(s) for s in args.sizes.split(",")]:
        backend = JaxBackend(batch_sizes=(size,))
        t0 = time.perf_counter()
        backend.warm_keycheck(size)
        first_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        got, lanes = backend.g1_keycheck(points)
        wall = time.perf_counter() - t0
        calls = lanes // size
        t0 = time.perf_counter()
        backend._keycheck_read(backend._keycheck_dispatch([], size), 0)
        one_ms = (time.perf_counter() - t0) * 1e3
        wrong = sum(g != r for g, r in zip(got, ref))
        ok = ok and wrong == 0 and len(got) == len(ref)
        print(json.dumps({
            "size": size, "device": jax.devices()[0].device_kind,
            "first_call_s": round(first_s, 3), "keys": len(points),
            "non_members": sum(not r for r in ref), "calls": calls,
            "all_calls_ms": round(wall * 1e3, 3),
            "ms_per_call": round(wall * 1e3 / calls, 3),
            "one_call_ms": round(one_ms, 3), "disagreements": wrong,
        }), flush=True)
    print(json.dumps({"ok": ok, "planted_wrong": planted_wrong}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
