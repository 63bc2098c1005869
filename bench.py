#!/usr/bin/env python3
"""Benchmark: batched BLS12-381 signature-set verification on the device.

Measures the verification kernel (the north-star workload, BASELINE.md:
>= 50,000 signature-sets/s on one TPU v5e chip) and prints ONE JSON line:

    {"metric": "tpu_batch_verify", "value": <sets/s>, "unit": "sets/s",
     "vs_baseline": <value / 50000>, "device": "...", ...}

The timed section is the jitted device kernel — subgroup checks, weight
scalar muls, Miller loops, GT reduction, final exponentiation — on a
pre-marshaled batch, matching what blst's verify_multiple_aggregate_
signatures timing covers (hashing excluded there too; it happens at gossip
decode).  Host marshal cost is reported on stderr.

The measurement runs in a killable child process.  It needs a TPU: with
no chip, or when the child fails or hangs, bench.py prints no
``tpu_batch_verify`` line and exits non-zero.

Env knobs: BENCH_BATCH (default 8192 — the measured best, PERF.md),
BENCH_ITERS (default 3), BENCH_TPU_TIMEOUT.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

NORTH_STAR = 50_000.0


def _arm_watchdog(seconds: float, stage: str):
    """Hang insurance inside the child: a stalled stage ends the child
    with a non-zero exit instead of an unkillable stall."""
    import threading

    def fire():
        print(f"bench watchdog: {stage} exceeded {seconds}s", file=sys.stderr,
              flush=True)
        os._exit(3)

    t = threading.Timer(seconds, fire)
    t.daemon = True
    t.start()
    return t.cancel


def run_measurement() -> None:
    """Child mode: measure on the TPU, print one JSON line."""
    B = int(os.environ.get("BENCH_BATCH", "8192"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    init_timeout = float(os.environ.get("BENCH_INIT_TIMEOUT", "240"))
    compile_timeout = float(os.environ.get("BENCH_COMPILE_TIMEOUT", "2800"))

    # backend modules materialize jnp constants at import: watchdog first
    disarm = _arm_watchdog(init_timeout, "device init")
    import jax

    if jax.devices()[0].platform != "tpu":
        print(f"bench: no TPU (JAX sees {jax.devices()[0].platform}); "
              "tpu_batch_verify not measured", file=sys.stderr)
        sys.exit(2)
    from lighthouse_tpu.crypto.bls.jax_backend.backend import (
        enable_compile_cache,
    )

    enable_compile_cache()
    device_h2c = os.environ.get("BENCH_DEVICE_H2C", "") == "1"
    from __graft_entry__ import _build_sets, _marshal

    if device_h2c:
        from lighthouse_tpu.crypto.bls.jax_backend.backend import (
            _verify_kernel_h2c as _verify_kernel,
        )
    else:
        from lighthouse_tpu.crypto.bls.jax_backend.backend import _verify_kernel

    dev = jax.devices()[0]
    disarm()
    print(f"device: {dev} (device_h2c={device_h2c})", file=sys.stderr)

    sets = _build_sets(B)  # test-data construction: NOT timed (includes
    # signing, which a real node receives from the wire)
    t0 = time.time()
    args = _marshal(sets, device_h2c=device_h2c)
    t_marshal = time.time() - t0
    print(
        f"host marshal (hash+encode+weights) for B={B}: {t_marshal:.1f}s "
        f"({B / t_marshal:.0f} sets/s host-side)",
        file=sys.stderr,
    )

    args = jax.device_put(args, dev)
    # traced_jit: the compile lands in the flight recorder as a
    # jit.compile span with the program fingerprint, feeding the
    # compile-time BENCH_HISTORY row below
    from lighthouse_tpu.crypto.bls.jax_backend.backend import (
        program_fingerprint,
        traced_jit,
    )

    fn = traced_jit(
        _verify_kernel,
        program_fingerprint(
            _verify_kernel.__name__, B=B, device_h2c=device_h2c
        ),
    )
    t0 = time.time()
    disarm = _arm_watchdog(compile_timeout, f"compile B={B}")
    ok = fn(*args)
    ok.block_until_ready()
    disarm()
    t_compile = time.time() - t0
    print(f"compile+first run: {t_compile:.1f}s, result={bool(ok)}", file=sys.stderr)
    assert bool(ok) is True, "benchmark batch must verify"

    times = []
    for _ in range(iters):
        t0 = time.time()
        fn(*args).block_until_ready()
        times.append(time.time() - t0)
    t_best = min(times)
    sets_per_s = B / t_best
    print(
        f"kernel: best {t_best * 1000:.1f}ms over {iters} iters -> "
        f"{sets_per_s:.1f} sets/s",
        file=sys.stderr,
    )
    from lighthouse_tpu.crypto.bls.jax_backend import fp as _fp

    result = {
        "metric": "tpu_batch_verify",
        "value": round(sets_per_s, 1),
        "unit": "sets/s",
        "vs_baseline": round(sets_per_s / NORTH_STAR, 6),
        "device": str(dev),
        # the silicon identity every BENCH_HISTORY row kind carries, so
        # bench rows join autotuned plans on the same key
        "device_kind": _device_kind(),
        "batch": B,
        "compile_sec": round(t_compile, 1),
        "host_marshal_sets_per_s": round(B / t_marshal, 1),
        "device_h2c": device_h2c,
        "kernel": "pallas" if _fp.pallas_enabled() else "scan",
        "chains": _fp.chains_active(),
        "miller_fused": _fp.miller_fused_active(),
        "wsm": _fp.wsm_fused_active(),
    }
    result["mxu_routed"] = _fp.mxu_active()
    if os.environ.get("BENCH_MARSHAL", "1") != "0":
        result["marshal"] = _measure_marshal(device_h2c)
    if os.environ.get("BENCH_MXU", "") == "1":
        result["mxu"] = _measure_mxu()
        _record_mxu_history(result)
    if os.environ.get("BENCH_PIPELINE", "") == "1":
        result["pipeline"] = _measure_pipeline(B, device_h2c)
    if os.environ.get("BENCH_SERVE", "") == "1":
        result["serve"] = _measure_serve(device_h2c)
        _record_serve_history(result)
    if os.environ.get("BENCH_EPOCH", "") == "1":
        result["epoch_system"] = _measure_epoch_system(device_h2c)
    if os.environ.get("BENCH_BOOT", "") == "1":
        result["boot"] = _measure_boot()
        _record_boot_history(result)
    if os.environ.get("BENCH_AUTOTUNE", "") == "1":
        result["autotune"] = _measure_autotune()
        _record_autotune_history(result)
    if os.environ.get("BENCH_INTEGRITY", "") == "1":
        result["integrity"] = _measure_integrity()
        _record_integrity_history(result)
    # every jit.compile span recorded this run, with per-program
    # fingerprints — the compile-time attribution ROADMAP item 4 asks for
    from lighthouse_tpu.obs import TRACER
    from lighthouse_tpu.obs import report as trace_report

    compiles = trace_report.compile_events(
        TRACER.chrome_trace()["traceEvents"]
    )
    if compiles:
        result["compile_events"] = compiles
        for c in compiles:
            print(
                f"jit.compile {c.get('fingerprint', '?')} "
                f"{c['seconds']:.1f}s {c.get('kernel', '')}",
                file=sys.stderr,
            )
        # compile-time regression gate (ROADMAP item 4): any program >3x
        # slower to compile than its last kind="compile" history row is a
        # loud failure — fingerprints carry jax version + backend, so CPU
        # children never compare against TPU rows
        regressions = _compile_regressions(compiles, _load_history())
        if regressions:
            result["compile_regression"] = regressions
            print("=" * 64, file=sys.stderr)
            print("COMPILE-TIME REGRESSION (>3x last BENCH_HISTORY entry):",
                  file=sys.stderr)
            for r in regressions:
                print(
                    f"  {r['fingerprint']} {r.get('kernel') or '?'}: "
                    f"{r['seconds']:.1f}s vs {r['previous_seconds']:.1f}s "
                    f"({r['ratio']:.1f}x)",
                    file=sys.stderr,
                )
            print("=" * 64, file=sys.stderr)
    if os.environ.get("BENCH_MULTICHIP", "") == "1":
        result["multichip"] = _measure_multichip()
    if "TPU" in str(dev):
        _record_tpu_history(result)
        _record_compile_history(result)
        _record_marshal_history(result)
        _record_multichip_history(result)
    print(json.dumps(result), flush=True)


def _measure_marshal(device_h2c: bool) -> dict:
    """Marshal microbench: the per-set scalar loop vs the vectorized
    ingest engine (lighthouse_tpu/ingest) on the two production shapes —
    gossip (single-signer sets over a warm registry) and committee
    fan-out (K signers per set, repeat committees, warm aggregate cache).
    Host-only: no kernel dispatch, so it runs identically on any child.
    Feeds the kind="marshal" BENCH_HISTORY row."""
    from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
    from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend
    from lighthouse_tpu.ingest import IngestEngine
    from lighthouse_tpu.utils import metrics as M

    backend = JaxBackend(min_batch=8, device_h2c=device_h2c)
    engine = IngestEngine(backend, device_gather=False)
    n_pks = 256
    sks = [SecretKey(300 + i) for i in range(n_pks)]
    pks = [sk.public_key() for sk in sks]
    # marshal never touches signature validity: one signed point serves
    # every set (signing 1k+ sets would dominate the bench's own wall)
    sig = sks[0].sign(b"bench")
    out = {"device_h2c": device_h2c}

    # gossip shape: single-signer sets, every signer in the warm cache
    n_g = int(os.environ.get("BENCH_MARSHAL_GOSSIP", "2048"))
    gossip = [
        # 32-byte messages: gossip verification signs fixed-size roots
        SignatureSet(sig, [pks[i % n_pks]], i.to_bytes(32, "little"))
        for i in range(n_g)
    ]
    engine.marshal_sets(gossip)  # warm the cache, untimed
    t0 = time.time()
    mb = engine.marshal_sets(gossip)
    t_vec = time.time() - t0
    assert not mb.invalid
    t0 = time.time()
    backend.marshal_sets(gossip)
    t_scalar = time.time() - t0
    out["gossip"] = {
        "sets": n_g,
        "scalar_sets_per_s": round(n_g / t_scalar, 1),
        "vectorized_sets_per_s": round(n_g / t_vec, 1),
        "speedup": round(t_scalar / t_vec, 2),
    }

    # committee fan-out shape (north-star #2): K signers per set, a
    # rotation of repeat committees — the epoch-processing regime where
    # the aggregate cache skips K Jacobian adds per set
    K = int(os.environ.get("BENCH_MARSHAL_K", "128"))
    n_c = int(os.environ.get("BENCH_MARSHAL_COMMITTEES", "32"))
    n_b = int(os.environ.get("BENCH_MARSHAL_B", "1024"))
    pool_k = min(64, n_pks)
    committees = [
        [pks[(c * 7 + j) % pool_k] for j in range(K)] for c in range(n_c)
    ]
    sets = [
        SignatureSet(sig, committees[i % n_c],
                     (i % n_c).to_bytes(32, "big"))
        for i in range(n_b)
    ]
    engine.marshal_sets(sets)  # warm, untimed
    hits0 = M.INGEST_CACHE_HITS.value()
    t0 = time.time()
    mb = engine.marshal_sets(sets)
    t_vec = time.time() - t0
    assert not mb.invalid
    cache_hits = M.INGEST_CACHE_HITS.value() - hits0
    t0 = time.time()
    backend.marshal_sets(sets)
    t_scalar = time.time() - t0
    out["committee"] = {
        "sets": n_b,
        "signers_per_set": K,
        "committees": n_c,
        "scalar_sets_per_s": round(n_b / t_scalar, 1),
        "vectorized_sets_per_s": round(n_b / t_vec, 1),
        "speedup": round(t_scalar / t_vec, 2),
        "cache_hits": cache_hits,
    }
    print(f"marshal microbench: {out}", file=sys.stderr)
    return out


def _measure_mxu() -> dict:
    """BENCH_MXU=1: the MXU-vs-VPU Montgomery core A/B (ROADMAP item 1,
    r6).

    Two scopes: (a) the mont_mul kernel microbench — one dispatch per
    call, identical padding/tiling both arms (ONE _mont_call family
    keyed on mxu), so the delta is purely VPU schoolbook columns vs the
    13-bit re-limbed banded matmul; (b) the end-to-end verify kernel
    with fp.set_mxu toggled across separate jit compiles, at the batch
    sizes BENCH_MXU_VERIFY_BATCHES (default 512,4096,8192 on TPU — the
    sweep PERF.md's batch table uses).  On CPU both arms run the exact
    kernel program in interpret mode: throughput numbers are
    meaningless there (and labeled), but the rows prove the A/B
    harness end to end, and the verify sweep defaults to empty to skip
    the minutes-scale interpret compiles (opt in with the env knob).
    Feeds the kind="mxu" BENCH_HISTORY rows."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from lighthouse_tpu.crypto.bls.jax_backend import fp as F
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

    on_tpu = jax.default_backend() == "tpu"
    interpret = not on_tpu
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    out = {"backend": jax.default_backend(), "interpret": interpret}

    T = int(os.environ.get("BENCH_MXU_T", "8192" if on_tpu else "128"))
    rng = np.random.default_rng(0xA8)
    a = jnp.asarray(rng.integers(0, 1 << 15, size=(26, T), dtype=np.uint32))
    b = jnp.asarray(rng.integers(0, 1 << 15, size=(26, T), dtype=np.uint32))
    mm = {"batch": T}
    for arm, mxu in (("vpu", False), ("mxu", True)):
        fn = jax.jit(functools.partial(
            PF.mont_mul_limbs, interpret=interpret, mxu=mxu))
        fn(a, b).block_until_ready()  # compile, untimed
        times = []
        for _ in range(iters):
            t0 = time.time()
            fn(a, b).block_until_ready()
            times.append(time.time() - t0)
        best = min(times)
        mm[arm] = {
            "best_ms": round(best * 1000, 3),
            "mont_muls_per_s": round(T / best, 1),
        }
        print(f"mont_mul microbench [{arm}]: {mm[arm]}", file=sys.stderr)
    mm["mxu_speedup"] = round(
        mm["vpu"]["best_ms"] / mm["mxu"]["best_ms"], 3)
    out["mont_mul"] = mm

    batches = os.environ.get(
        "BENCH_MXU_VERIFY_BATCHES", "512,4096,8192" if on_tpu else "")
    verify_rows = []
    if batches.strip():
        from __graft_entry__ import _example_batch
        from lighthouse_tpu.crypto.bls.jax_backend.backend import (
            _verify_kernel,
        )

        prev = F.mxu_enabled()
        try:
            for Bv in [int(x) for x in batches.split(",") if x.strip()]:
                args = _example_batch(Bv)
                row = {"batch": Bv}
                for arm, mxu in (("vpu", False), ("mxu", True)):
                    F.set_mxu(mxu)
                    fn = jax.jit(_verify_kernel)
                    ok = fn(*args)
                    assert bool(jax.block_until_ready(ok)) is True
                    times = []
                    for _ in range(iters):
                        t0 = time.time()
                        jax.block_until_ready(fn(*args))
                        times.append(time.time() - t0)
                    best = min(times)
                    row[arm] = {
                        "best_ms": round(best * 1000, 2),
                        "sets_per_s": round(Bv / best, 1),
                    }
                row["mxu_speedup"] = round(
                    row["vpu"]["best_ms"] / row["mxu"]["best_ms"], 3)
                verify_rows.append(row)
                print(f"verify A/B: {row}", file=sys.stderr)
        finally:
            F.set_mxu(prev)
    out["verify"] = verify_rows
    return out


def _measure_epoch_system(device_h2c: bool) -> dict:
    """BENCH_EPOCH=1: the epoch-batch *system* number (north-star #2
    shape) — committee-aggregate sets streamed through PipelinedVerifier
    with the ingest engine as the marshal stage, reported as end-to-end
    sets/s alongside the kernel headline.  Sized by env knobs so the TPU
    run can scale it up without touching code."""
    from lighthouse_tpu.beacon.processor import (
        PipelinedVerifier,
        ResilientVerifier,
    )
    from lighthouse_tpu.crypto.bls.api import (
        PythonBackend,
        SecretKey,
        SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend
    from lighthouse_tpu.ingest import IngestEngine

    K = int(os.environ.get("BENCH_EPOCH_COMMITTEE_SIZE", "128"))
    n_c = int(os.environ.get("BENCH_EPOCH_COMMITTEES", "16"))
    per = int(os.environ.get("BENCH_EPOCH_BATCH", "64"))
    n_batches = int(os.environ.get("BENCH_EPOCH_BATCHES", "4"))

    from lighthouse_tpu.crypto.bls.api import AggregateSignature

    sks = [SecretKey(900 + i) for i in range(K)]
    pks = [sk.public_key() for sk in sks]
    committees = []
    for c in range(n_c):
        msg = b"epoch-duty-%d" % c
        agg = AggregateSignature.aggregate([sk.sign(msg) for sk in sks])
        committees.append(SignatureSet(agg.signature, list(pks), msg))
    batches = [
        [committees[j % n_c] for j in range(per)] for _ in range(n_batches)
    ]

    backend = JaxBackend(min_batch=8, device_h2c=device_h2c)
    engine = IngestEngine(backend)
    rv = ResilientVerifier(
        device_verify=backend.verify_signature_sets,
        cpu_verify=PythonBackend().verify_signature_sets,
    )
    pv = PipelinedVerifier.for_backend(rv, backend, ingest=engine)

    pv.verify_stream(batches[:1])  # compile + cache warm, untimed
    t0 = time.time()
    outs = pv.verify_stream(batches)
    wall = time.time() - t0
    assert all(all(o.verdicts) for o in outs)
    total = per * n_batches
    out = {
        "committee_size": K,
        "committees": n_c,
        "sets": total,
        "wall_sec": round(wall, 3),
        "sets_per_s": round(total / wall, 1),
        "aggregate_signatures_per_s": round(total * K / wall, 1),
    }
    print(f"epoch system (north-star #2 shape): {out}", file=sys.stderr)
    return out


def _measure_pipeline(B: int, device_h2c: bool) -> dict:
    """BENCH_PIPELINE=1: serial verify_signature_sets vs the pipelined
    marshal/dispatch/resolve stream (PipelinedVerifier) over the same
    batches — the A/B for PERF.md's "wall approaches max(marshal,
    device)" claim.  Uses real SignatureSets (the backend path includes
    host marshal, which is the whole point)."""
    from lighthouse_tpu.beacon.processor import (
        PipelinedVerifier,
        ResilientVerifier,
    )
    from lighthouse_tpu.crypto.bls.api import (
        PythonBackend,
        SecretKey,
        SignatureSet,
    )
    from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend
    from lighthouse_tpu.utils import metrics as M

    n_batches = int(os.environ.get("BENCH_PIPELINE_BATCHES", "4"))
    per = max(8, B // n_batches)
    distinct = min(per, 256)
    pool = []
    for i in range(distinct):
        sk = SecretKey(300 + i)
        msg = bytes([i % 256, 7]) * 16
        pool.append(SignatureSet(sk.sign(msg), [sk.public_key()], msg))
    batches = [
        [pool[j % distinct] for j in range(per)] for _ in range(n_batches)
    ]

    backend = JaxBackend(min_batch=8, device_h2c=device_h2c)
    rv = ResilientVerifier(
        device_verify=backend.verify_signature_sets,
        cpu_verify=PythonBackend().verify_signature_sets,
    )
    # marshal stage = the vectorized ingest engine (cache-backed); the
    # serial arm below keeps the scalar loop, so the A/B also shows the
    # marshal stage leaving the critical path
    from lighthouse_tpu.ingest import IngestEngine

    engine = IngestEngine(backend)
    pv = PipelinedVerifier.for_backend(rv, backend, ingest=engine)

    backend.verify_signature_sets(batches[0])  # compile, untimed
    engine.marshal_sets(batches[0])  # warm the pubkey cache, untimed
    t0 = time.time()
    for b in batches:
        assert backend.verify_signature_sets(b)
    serial = time.time() - t0
    from lighthouse_tpu.obs import TRACER
    from lighthouse_tpu.obs import report as trace_report

    mark = TRACER.mark()
    t0 = time.time()
    outs = pv.verify_stream(batches)
    piped = time.time() - t0
    assert all(all(o.verdicts) for o in outs)
    out = {
        "batches": n_batches,
        "sets_per_batch": per,
        "serial_wall_sec": round(serial, 3),
        "pipelined_wall_sec": round(piped, 3),
        "speedup": round(serial / piped, 3) if piped > 0 else None,
        "device_occupancy_pct": round(M.PIPELINE_OCCUPANCY.value(), 1),
    }
    # per-stage attribution from the flight recorder: marshal/dispatch/
    # resolve p50/p99 plus overlap efficiency (wall / max(marshal, device),
    # 1.0 = perfect overlap) over the spans of the pipelined run only
    events = TRACER.chrome_trace(since_sid=mark)["traceEvents"]
    attr = trace_report.attribution(events)
    out["stages"] = {
        name: {
            "count": st["count"],
            "p50_ms": round(st["p50_s"] * 1000, 3),
            "p99_ms": round(st["p99_s"] * 1000, 3),
            "total_s": st["total_s"],
        }
        for name, st in attr["stages"].items()
        if name.startswith("pipeline.") or name.startswith("verify.")
    }
    out["overlap_efficiency"] = attr["overlap"]
    out["host_share"] = attr["share"]["host_share"]
    print(f"pipeline A/B: {out}", file=sys.stderr)
    print("pipeline stage attribution (tracer):", file=sys.stderr)
    for name, st in sorted(out["stages"].items()):
        print(
            f"  {name:20s} n={st['count']:<4d} p50={st['p50_ms']:.3f}ms "
            f"p99={st['p99_ms']:.3f}ms total={st['total_s']:.3f}s",
            file=sys.stderr,
        )
    ov = out["overlap_efficiency"]
    if ov.get("ratio") is not None:
        print(
            f"  overlap efficiency {ov['ratio']:.3f} (mode={ov['mode']}, "
            "1.0 = perfect overlap)",
            file=sys.stderr,
        )
    return out


def _measure_boot() -> dict:
    """BENCH_BOOT=1: cold-vs-prewarmed boot wall clock over the AOT
    executable store (ROADMAP item 4's operational half).

    Phase "cold" stages BENCH_BOOT_PROGRAMS synthetic programs through
    ``traced_jit``'s capture hook — trace-compile plus export+serialize
    into a throwaway store, exactly what a first boot pays.  Phase
    "prewarm" boots a fresh backend from that store (``aot.prewarm`` +
    first real call per program) — what every subsequent boot pays.
    Synthetic programs keep the A/B about the *store machinery*
    (serialize, verify, deserialize, install); the real kernels' compile
    cost is already tracked by the kind="compile" rows, so the speedup
    composes from history.  Feeds the kind="boot" BENCH_HISTORY rows."""
    import shutil
    import tempfile

    import jax.numpy as jnp

    from lighthouse_tpu.crypto.bls.jax_backend import aot
    from lighthouse_tpu.crypto.bls.jax_backend.backend import (
        JaxBackend,
        program_fingerprint,
        traced_jit,
    )

    n = int(os.environ.get("BENCH_BOOT_PROGRAMS", "4"))
    root = tempfile.mkdtemp(prefix="bench-boot-")
    store = aot.AotStore(os.path.join(root, "aot_cache"))
    x = jnp.arange(64, dtype=jnp.float32)
    t0 = time.perf_counter()
    for i in range(n):
        def prog(v, _i=i):
            return ((v * jnp.float32(_i + 1)) + 0.5).sum()

        key = ("bench-boot", i)

        def hook(call, args, _key=key):
            store.capture(call, _key, args, kernel="bench_boot_prog")

        call = traced_jit(
            prog, program_fingerprint("bench_boot_prog", i=i), capture=hook
        )
        float(call(x))
    cold_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    backend = JaxBackend(min_batch=8, device_h2c=False)
    report = aot.prewarm(backend, store)
    for i in range(n):
        float(backend._kernels[("bench-boot", i)](x))
    prewarm_s = time.perf_counter() - t0
    shutil.rmtree(root, ignore_errors=True)
    return {
        "programs": n,
        "cold_s": round(cold_s, 4),
        "prewarm_s": round(prewarm_s, 4),
        "speedup": round(cold_s / prewarm_s, 2) if prewarm_s else None,
        "loaded": len(report.loaded),
        "rejected": len(report.rejected),
    }


def _measure_autotune() -> dict:
    """BENCH_AUTOTUNE=1: run the per-device-kind kernel autotuner
    (crypto/bls/jax_backend/autotune.py) — timed trials of every
    range-proven arm across the batch-shape ladder — and persist the
    winning plan into an AOT store so the chip run leaves tuned plans
    behind for the next boot's ``bn --prewarm``.

    Knobs: BENCH_AUTOTUNE_SHAPES (ladder override), BENCH_AUTOTUNE_STORE
    (plan destination; default ``aot_tuned/`` beside this script so the
    artifact survives the session), BENCH_ITERS.  Feeds the
    kind="autotune" BENCH_HISTORY rows."""
    from lighthouse_tpu.crypto.bls.jax_backend import aot, autotune

    shapes_env = os.environ.get("BENCH_AUTOTUNE_SHAPES", "")
    shapes = (
        tuple(int(s) for s in shapes_env.split(",") if s.strip())
        if shapes_env
        else autotune.default_shapes()
    )
    root = os.environ.get("BENCH_AUTOTUNE_STORE") or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "aot_tuned"
    )
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    store = aot.AotStore(root)
    t0 = time.perf_counter()
    plan = autotune.tune_and_store(store, shapes=shapes, iters=iters)
    return {
        "device_kind": plan["device_kind"],
        "jax": plan["jax"],
        "store": root,
        "arms": [a.arm for a in autotune.proven_arms()],
        "shapes": plan["shapes"],
        "tune_s": round(time.perf_counter() - t0, 3),
    }


def _measure_serve(device_h2c: bool) -> dict:
    """BENCH_SERVE=1: the verification front door's fill-or-flush knob.

    A closed-loop multi-tenant load generator (three tenants, paced
    submissions, admission opened wide so batching economics are what is
    measured) drives a real :class:`VerifyService` at two or more
    ``flush_margin`` operating points and reports per-point p50/p99
    end-to-end latency against device efficiency.  The expected shape —
    a *later* effective flush deadline (small margin) fills compiled
    batches and buys device throughput; an *earlier* one (large margin)
    flushes partial batches and buys p99 — lands as ``kind="serve"``
    BENCH_HISTORY rows.

    The device rung defaults to a calibrated cost model
    (``BENCH_SERVE_CALL_MS`` fixed per-call overhead +
    ``BENCH_SERVE_SET_US`` per set) so the sweep isolates front-door
    batching from kernel throughput, which the kind="tpu" rows already
    track; ``BENCH_SERVE_REAL=1`` swaps in the real
    JaxBackend/ResilientVerifier ladder over real signature sets."""
    from lighthouse_tpu.beacon.processor import BatchOutcome
    from lighthouse_tpu.serve.admission import TenantPolicy
    from lighthouse_tpu.serve.service import VerifyService

    n_requests = int(os.environ.get("BENCH_SERVE_REQUESTS", "200"))
    sets_per = int(os.environ.get("BENCH_SERVE_SETS", "4"))
    gap = float(os.environ.get("BENCH_SERVE_GAP_MS", "2.0")) / 1000.0
    call_ms = float(os.environ.get("BENCH_SERVE_CALL_MS", "3.0"))
    set_us = float(os.environ.get("BENCH_SERVE_SET_US", "100.0"))
    deadline_s = float(os.environ.get("BENCH_SERVE_DEADLINE_MS", "250")) / 1e3
    margins = [
        float(m) / 1000.0
        for m in os.environ.get("BENCH_SERVE_MARGINS_MS", "5,230").split(",")
    ]
    real = os.environ.get("BENCH_SERVE_REAL", "") == "1"

    if real:
        from lighthouse_tpu.beacon.processor import ResilientVerifier
        from lighthouse_tpu.crypto.bls.api import (
            PythonBackend,
            SecretKey,
            SignatureSet,
        )
        from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

        pool = []
        for i in range(32):
            sk = SecretKey(700 + i)
            msg = bytes([i % 256, 11]) * 16
            pool.append(SignatureSet(sk.sign(msg), [sk.public_key()], msg))
        payload = [pool[j % len(pool)] for j in range(sets_per)]
        backend = JaxBackend(min_batch=8, device_h2c=device_h2c)
        backend.verify_signature_sets(payload)  # compile, untimed

        def make_verifier():
            return ResilientVerifier(
                device_verify=backend.verify_signature_sets,
                cpu_verify=PythonBackend().verify_signature_sets,
            )
    else:
        payload = [("bench-set", j) for j in range(sets_per)]

        class _ModelVerifier:
            """Calibrated device cost model: a fixed per-call overhead
            (dispatch + pad + transfer) plus a per-set marginal cost —
            the economics the batcher amortizes."""

            def __init__(self):
                self.calls = 0
                self.busy_s = 0.0

            def verify_batch(self, sets):
                d = call_ms / 1e3 + set_us / 1e6 * len(sets)
                time.sleep(d)
                self.calls += 1
                self.busy_s += d
                return BatchOutcome(
                    verdicts=[True] * len(sets), device_calls=1
                )

        def make_verifier():
            return _ModelVerifier()

    points = []
    for margin in margins:
        verifier = make_verifier()
        svc = VerifyService(
            verifier,
            default_policy=TenantPolicy(
                rate=1e9, burst=1e9, max_queue=10**9,
            ),
            compiled_sizes=(8, 32, 128),
            flush_margin=margin,
            default_deadline_s=deadline_s,
        )
        ids = []
        t0 = time.monotonic()
        for r in range(n_requests):
            res = svc.submit(f"vc-{r % 3}", payload, deadline_s=deadline_s)
            if res.accepted:
                ids.append(res.request_id)
            svc.tick()
            if gap:
                time.sleep(gap)
        svc.flush()
        wall = time.monotonic() - t0
        lats, misses, done_sets = [], 0, 0
        for rid in ids:
            req = svc._requests.get(rid)
            if req is None or req.done_at is None:
                continue
            lats.append(req.done_at - req.submitted_at)
            done_sets += len(req.sets)
            misses += bool(req.deadline_missed)
        lats.sort()
        flushes = svc.batcher.flushes_full + svc.batcher.flushes_deadline
        point = {
            "flush_margin_ms": round(margin * 1e3, 3),
            "deadline_ms": round(deadline_s * 1e3, 3),
            "requests_done": len(lats),
            "p50_ms": round(lats[len(lats) // 2] * 1e3, 3) if lats else None,
            "p99_ms": round(
                lats[min(len(lats) - 1, int(len(lats) * 0.99))] * 1e3, 3
            ) if lats else None,
            "sets_per_s": round(done_sets / wall, 1) if wall > 0 else None,
            "flushes_full": svc.batcher.flushes_full,
            "flushes_deadline": svc.batcher.flushes_deadline,
            "mean_batch": round(done_sets / flushes, 1) if flushes else None,
            "deadline_miss_rate": round(misses / len(lats), 4) if lats else None,
        }
        if not real:
            point["device_busy_share"] = round(verifier.busy_s / wall, 3)
            point["sets_per_device_s"] = (
                round(done_sets / verifier.busy_s, 1)
                if verifier.busy_s > 0 else None
            )
        points.append(point)
        print(f"serve point: {point}", file=sys.stderr)
    return {
        "mode": "real" if real else "model",
        "call_ms": call_ms,
        "set_us": set_us,
        "gap_ms": gap * 1e3,
        "requests": n_requests,
        "sets_per_request": sets_per,
        "points": points,
    }


def _measure_integrity() -> dict:
    """BENCH_INTEGRITY=1: verdict-integrity canary overhead A/B.

    Drives an :class:`IntegrityGuard` over a calibrated cost-model
    verifier (``BENCH_INTEGRITY_CALL_MS`` fixed per-dispatch overhead +
    ``BENCH_INTEGRITY_SET_US`` per set — the serve bench's idiom, so the
    guard's *structural* cost is isolated from kernel throughput) at the
    committee shape (``BENCH_INTEGRITY_SETS``, default 2048 = 16
    committees x 128 signers) across a canary-count sweep
    (``BENCH_INTEGRITY_K``, default ``0,1,2,4``; 0 is the unguarded
    baseline).  Each canary is a single-set known-answer batch on a
    prewarmed program, so its per-dispatch floor (default 1ms) is the
    cached single-set call cost, not a full coalesced dispatch.  The
    acceptance bar: overhead at the default K stays <=2% of the
    committee-shape dispatch.  Feeds the kind="integrity" BENCH_HISTORY
    rows."""
    from lighthouse_tpu.beacon.processor import BatchOutcome
    from lighthouse_tpu.integrity.corpus import DEFAULT_K, CanaryCorpus
    from lighthouse_tpu.integrity.guard import IntegrityGuard

    n_sets = int(os.environ.get("BENCH_INTEGRITY_SETS", "2048"))
    iters = int(os.environ.get("BENCH_INTEGRITY_ITERS", "10"))
    call_ms = float(os.environ.get("BENCH_INTEGRITY_CALL_MS", "1.0"))
    set_us = float(os.environ.get("BENCH_INTEGRITY_SET_US", "100.0"))
    ks = sorted({
        int(k) for k in os.environ.get(
            "BENCH_INTEGRITY_K", f"0,1,{DEFAULT_K},4"
        ).split(",")
    } | {0, DEFAULT_K})

    cc = CanaryCorpus()
    truth = {}
    for e in cc.entries():
        for s in e.sets:
            truth[id(s)] = e.expected

    class CostModelVerifier:
        """Calibrated inner rung: answers the canaries honestly (their
        known verdicts), everything else True, and charges the modelled
        dispatch cost."""

        def verify_batch(self, sets):
            time.sleep(call_ms / 1e3 + set_us * len(sets) / 1e6)
            return BatchOutcome(
                [truth.get(id(s), True) for s in sets], 1
            )

    payload = [object() for _ in range(n_sets)]
    points = []
    for k in ks:
        guard = IntegrityGuard(
            CostModelVerifier(), None, corpus=cc, k=k, enabled=k > 0,
        )
        guard.verify_batch(payload)  # warm the corpus memo, untimed
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = guard.verify_batch(payload)
            times.append(time.perf_counter() - t0)
            assert len(out.verdicts) == n_sets
        assert guard.distrusted == 0, "cost model failed its own canaries"
        times.sort()
        points.append({
            "k": k,
            "seconds_per_batch": times[len(times) // 2],
            "canary_checks": guard.canary_checks,
        })
    base = points[0]["seconds_per_batch"]
    for p in points:
        p["overhead_pct"] = round(
            (p["seconds_per_batch"] / base - 1.0) * 100.0, 3
        )
    at_default = next(p for p in points if p["k"] == DEFAULT_K)
    out = {
        "n_sets": n_sets,
        "iters": iters,
        "call_ms": call_ms,
        "set_us": set_us,
        "default_k": DEFAULT_K,
        "points": points,
        "overhead_at_default_pct": at_default["overhead_pct"],
    }
    print(
        f"integrity: K={DEFAULT_K} overhead "
        f"{at_default['overhead_pct']:.2f}% on {n_sets}-set committee "
        f"shape (bar: <=2%)",
        file=sys.stderr,
    )
    return out


def _record_integrity_history(result: dict) -> None:
    """Append a kind="integrity" row per canary-count operating point so
    the guard's overhead curve is tracked in BENCH_HISTORY alongside the
    serve rows.  Recorded for CPU children too (the cost-model sweep is
    host-independent structural overhead); the device and shape fields
    keep rows comparable only with their own kind."""
    try:
        g = result.get("integrity")
        if not g:
            return
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(_history_path(), "a") as f:
            for p in g.get("points", ()):
                row = {
                    "kind": "integrity",
                    "device": result.get("device"),
                    "device_kind": result.get("device_kind") or _device_kind(),
                    "n_sets": g.get("n_sets"),
                    "call_ms": g.get("call_ms"),
                    "set_us": g.get("set_us"),
                    "default_k": g.get("default_k"),
                    "measured_at": stamp,
                }
                row.update(p)
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _record_serve_history(result: dict) -> None:
    """Append a kind="serve" row per operating point so the front-door
    latency/throughput trade-off is tracked in BENCH_HISTORY alongside
    the pipeline and marshal rows.  Recorded for CPU children too (the
    cost-model sweep is host-independent batching economics); the device
    and mode fields keep rows comparable only with their own kind."""
    try:
        s = result.get("serve")
        if not s:
            return
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(_history_path(), "a") as f:
            for p in s.get("points", ()):
                row = {
                    "kind": "serve",
                    "device": result.get("device"),
                    "device_kind": result.get("device_kind") or _device_kind(),
                    "mode": s.get("mode"),
                    "gap_ms": s.get("gap_ms"),
                    "sets_per_request": s.get("sets_per_request"),
                    "measured_at": stamp,
                }
                row.update(p)
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _record_boot_history(result: dict) -> None:
    """Append kind="boot" rows (one per boot phase) so the cold-vs-
    prewarmed boot trajectory lands in BENCH_HISTORY alongside the
    compile rows — the same ledger ``cli.run_bn --prewarm`` appends its
    own boot row to.  Recorded for CPU children too (store machinery is
    host-side work); the device field keeps rows comparable only with
    their own kind."""
    try:
        b = result.get("boot")
        if not b:
            return
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(_history_path(), "a") as f:
            for phase in ("cold", "prewarm"):
                row = {
                    "kind": "boot",
                    "device": result.get("device"),
                    "device_kind": result.get("device_kind") or _device_kind(),
                    "phase": phase,
                    "seconds": b.get(f"{phase}_s"),
                    "programs": b.get("programs"),
                    "loaded": b.get("loaded"),
                    "measured_at": stamp,
                }
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _history_path() -> str:
    return os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_HISTORY.jsonl"
    )


def _device_kind() -> str:
    """Silicon identity stamped on every BENCH_HISTORY row kind — the
    same key (device kind × jax version) autotuned plans persist under,
    so history rows and plans join without guessing from device strings."""
    from lighthouse_tpu.utils import device_kind

    return device_kind()


def _record_autotune_history(result: dict) -> None:
    """Append kind="autotune" rows — one per tuned batch shape, carrying
    the per-arm trial timings and the chosen arm — so plan decisions are
    auditable in BENCH_HISTORY next to the mxu A/B rows they generalize.
    Recorded for CPU children too (stub/interpret tuning proof runs);
    device_kind keeps them from ever being read as chip plans."""
    try:
        a = result.get("autotune")
        if not a:
            return
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(_history_path(), "a") as f:
            for shape, entry in (a.get("shapes") or {}).items():
                row = {
                    "kind": "autotune",
                    "device": result.get("device"),
                    "device_kind": a.get("device_kind"),
                    "jax": a.get("jax"),
                    "batch": int(shape),
                    "store": a.get("store"),
                    "measured_at": stamp,
                }
                row.update(entry)
                f.write(json.dumps(row) + "\n")
    except (OSError, ValueError):
        pass


def _record_tpu_history(result: dict) -> None:
    """Append a successful TPU measurement to BENCH_HISTORY.jsonl."""
    try:
        entry = dict(result)
        entry["measured_at"] = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(_history_path(), "a") as f:
            f.write(json.dumps(entry) + "\n")
    except OSError:
        pass


def _record_compile_history(result: dict) -> None:
    """Append a kind="compile" row per program so compile-time
    regressions show in BENCH_HISTORY the same way throughput does."""
    try:
        with open(_history_path(), "a") as f:
            for c in result.get("compile_events", []):
                row = {
                    "kind": "compile",
                    "fingerprint": c.get("fingerprint"),
                    "kernel": c.get("kernel"),
                    "seconds": c["seconds"],
                    "device": result.get("device"),
                    "device_kind": result.get("device_kind") or _device_kind(),
                    "batch": result.get("batch"),
                    "measured_at": time.strftime(
                        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                    ),
                }
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _record_marshal_history(result: dict) -> None:
    """Append a kind="marshal" row per shape so the host-side marshal
    trajectory is tracked in BENCH_HISTORY the way compile times are."""
    try:
        m = result.get("marshal")
        if not m:
            return
        with open(_history_path(), "a") as f:
            for shape in ("gossip", "committee"):
                if shape not in m:
                    continue
                row = {
                    "kind": "marshal",
                    "shape": shape,
                    "device": result.get("device"),
                    "device_kind": result.get("device_kind") or _device_kind(),
                    "device_h2c": m.get("device_h2c"),
                    "measured_at": time.strftime(
                        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                    ),
                }
                row.update(m[shape])
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _record_mxu_history(result: dict) -> None:
    """Append kind="mxu" rows — one per A/B scope — so the MXU-vs-VPU
    trajectory lands in BENCH_HISTORY alongside compile/marshal rows.
    Recorded for CPU children too (interpret-mode harness proof runs):
    the device field keeps them from ever being read as chip numbers."""
    try:
        m = result.get("mxu")
        if not m:
            return
        stamp = time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())
        with open(_history_path(), "a") as f:
            base = {
                "kind": "mxu",
                "device": result.get("device"),
                "device_kind": result.get("device_kind") or _device_kind(),
                "interpret": m.get("interpret"),
                "measured_at": stamp,
            }
            row = dict(base, scope="mont_mul")
            row.update(m.get("mont_mul") or {})
            f.write(json.dumps(row) + "\n")
            for v in m.get("verify") or ():
                row = dict(base, scope="verify")
                row.update(v)
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _record_multichip_history(result: dict) -> None:
    """Append a kind="multichip" row per mesh width so sets/s-vs-device
    scaling is tracked in BENCH_HISTORY alongside throughput rows."""
    try:
        rows = result.get("multichip")
        if not rows:
            return
        with open(_history_path(), "a") as f:
            for r in rows:
                row = {
                    "kind": "multichip",
                    "device": result.get("device"),
                    "device_kind": result.get("device_kind") or _device_kind(),
                    "measured_at": time.strftime(
                        "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
                    ),
                }
                row.update(r)
                f.write(json.dumps(row) + "\n")
    except OSError:
        pass


def _load_history() -> list[dict]:
    """All parsed BENCH_HISTORY rows, oldest first (bad lines skipped)."""
    rows = []
    try:
        with open(_history_path()) as f:
            for ln in f:
                ln = ln.strip()
                if not ln:
                    continue
                try:
                    rows.append(json.loads(ln))
                except json.JSONDecodeError:
                    continue
    except OSError:
        pass
    return rows


def _compile_regressions(
    compiles: list[dict], history: list[dict], factor: float = 3.0
) -> list[dict]:
    """Programs whose compile time exceeds their last kind="compile"
    BENCH_HISTORY row by more than ``factor``.  Pure: compares by
    fingerprint (which bakes in jax version + backend platform, so a CPU
    child never judges itself against a TPU row)."""
    last: dict[str, dict] = {}
    for row in history:
        if row.get("kind") == "compile" and row.get("fingerprint"):
            last[row["fingerprint"]] = row
    out = []
    for c in compiles:
        prev = last.get(c.get("fingerprint"))
        if not prev:
            continue
        prev_s = float(prev.get("seconds") or 0.0)
        if prev_s > 0 and c["seconds"] > factor * prev_s:
            out.append(
                {
                    "fingerprint": c.get("fingerprint"),
                    "kernel": c.get("kernel"),
                    "seconds": round(float(c["seconds"]), 1),
                    "previous_seconds": round(prev_s, 1),
                    "ratio": round(float(c["seconds"]) / prev_s, 2),
                }
            )
    return out


def _measure_multichip() -> list[dict]:
    """BENCH_MULTICHIP=1: WEAK-scaling sweep of the rule-driven sharded
    program (parallel/partition.py) — per-device batch held constant
    (BENCH_MULTICHIP_BATCH, default 64) while the global batch grows
    with the mesh, which is the serving shape: more chips admit more
    traffic.  Per width the row records the end-to-end rate, the
    per-stage H2D / compute / gather attribution (stages run blocking
    for attribution; the e2e number lets them overlap), and
    ``scaling_efficiency`` = sets_per_s(n) / (n * sets_per_s(1)) — the
    ROADMAP item-2 gate is >=0.85 at width 8 ON REAL HARDWARE (the r7
    agenda asserts it there; CPU children record but do not gate).
    Mesh widths 1/2/4/8 capped by visible devices; on CPU the
    conftest-style XLA_FLAGS=--xla_force_host_platform_device_count=8
    recipe makes all four widths measurable."""
    import jax

    from __graft_entry__ import _example_batch
    from lighthouse_tpu.crypto.bls.jax_backend.backend import _verify_kernel
    from lighthouse_tpu.parallel.mesh import make_mesh
    from lighthouse_tpu.parallel.partition import ShardedVerifyProgram

    per_dev = int(os.environ.get("BENCH_MULTICHIP_BATCH", "64"))
    iters = int(os.environ.get("BENCH_ITERS", "3"))
    rows = []
    n_dev = len(jax.devices())
    base_rate = None
    for n in (1, 2, 4, 8):
        if n > n_dev:
            break
        B = per_dev * n
        args = _example_batch(B)
        program = ShardedVerifyProgram(make_mesh(n), _verify_kernel)
        padded = program.pad_operands(args)
        # compile + first run, untimed; the batch is all-valid
        first = program.verdict_vector(padded)
        assert bool(first.all()) is True
        best = stages_best = None
        stages = {}
        for _ in range(iters):
            # end-to-end (stages free to overlap: H2D is async)
            t0 = time.time()
            program.resolve(
                program.execute(program.shard_operands(padded)))
            e2e = time.time() - t0
            best = e2e if best is None else min(best, e2e)
            # staged, blocking between stages, for attribution
            t0 = time.time()
            sharded = program.shard_operands(padded)
            jax.block_until_ready(jax.tree.leaves(sharded))
            t1 = time.time()
            handle = program.execute(sharded)
            jax.block_until_ready(handle)
            t2 = time.time()
            program.resolve(handle)
            t3 = time.time()
            total = t3 - t0
            if stages_best is None or total < stages_best:
                stages_best = total
                stages = {
                    "h2d_ms": round((t1 - t0) * 1000, 2),
                    "compute_ms": round((t2 - t1) * 1000, 2),
                    "gather_ms": round((t3 - t2) * 1000, 2),
                }
        rate = B / best
        if base_rate is None:
            base_rate = rate
        row = {
            "devices": n,
            "batch": B,
            "per_device_batch": per_dev,
            "best_ms": round(best * 1000, 2),
            "sets_per_s": round(rate, 1),
            "scaling_efficiency": round(rate / (n * base_rate), 4),
        }
        row.update(stages)
        rows.append(row)
        print(f"multichip scaling: {row}", file=sys.stderr)
    return rows


def _run_child(timeout: float) -> dict | None:
    env = dict(os.environ)
    env["BENCH_CHILD"] = "tpu"
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env,
            capture_output=True,
            text=True,
            timeout=timeout,
        )
    except subprocess.TimeoutExpired:
        return None
    sys.stderr.write(proc.stderr[-4000:])
    for line in reversed(proc.stdout.strip().splitlines()):
        try:
            return json.loads(line)
        except json.JSONDecodeError:
            continue
    return None


def orchestrate() -> None:
    tpu_timeout = float(os.environ.get("BENCH_TPU_TIMEOUT", "3000"))
    result = _run_child(timeout=tpu_timeout)
    if result and result.get("value", 0) > 0:
        # Opportunistic chains A/B — now DEFAULT OFF: the r5 sessions
        # measured chains standalone (WIN at B=512: 2,759 vs 2,607,
        # r5 chip sessions) but the chains+miller COMPOSED
        # program is a pathological Mosaic compile (>6,700 s without
        # finishing, session2 06:52Z) — with miller default-on, an
        # automatic chains arm would re-enter that compile.  Re-enable
        # explicitly with BENCH_AB_CHAINS=1 after the composition is
        # tamed (e.g. segment-count reduction in the chain kernels).
        if (
            "LIGHTHOUSE_TPU_CHAINS" not in os.environ
            and os.environ.get("BENCH_AB_CHAINS", "0") == "1"
            and "TPU" in str(result.get("device", ""))
        ):
            os.environ["LIGHTHOUSE_TPU_CHAINS"] = "1"
            alt = _run_child(timeout=tpu_timeout)
            del os.environ["LIGHTHOUSE_TPU_CHAINS"]
            if alt and alt.get("value", 0) > 0:
                print(
                    f"chains A/B: off={result['value']} on={alt['value']}",
                    file=sys.stderr,
                )
                if alt["value"] > result["value"]:
                    result = alt
        print(json.dumps(result))
        if result.get("compile_regression"):
            print(
                "bench: FAILING on compile-time regression (see child "
                "stderr banner above)",
                file=sys.stderr,
            )
            sys.exit(1)
        return
    print("bench: the TPU measurement failed, timed out or found no chip "
          "(see the child's stderr above); tpu_batch_verify not measured",
          file=sys.stderr)
    sys.exit(1)


if __name__ == "__main__":
    if os.environ.get("BENCH_CHILD"):
        run_measurement()
    else:
        orchestrate()
