"""The "jax" BLS backend: batched signature-set verification on TPU.

Device twin of blst's `verify_multiple_aggregate_signatures` as wrapped by
the reference's verify_signature_sets (crypto/bls/src/impls/blst.rs:35-117):

  host:   per-set validation (empty sets, infinity signatures/pubkeys),
          pubkey aggregation, hash-to-curve H(m), nonzero 64-bit random
          weights (RAND_BITS=64, blst.rs:14), marshaling to Montgomery limbs
  device: G2 subgroup checks (Scott's psi test), weight scalar muls
          ([r_i]PK_i in G1, [r_i]sig_i in G2), signature accumulation,
          batched Miller loops, GT product tree, one final exponentiation

The device kernel is jitted once per padded batch size (powers of two), so a
long-running node reuses a handful of compiled programs — the XLA analog of
the reference's "compile the backend once, stream batches through it".
"""

from __future__ import annotations

import hashlib
import json
import os
import secrets
import threading
import time
from dataclasses import dataclass, field
from typing import Any

import numpy as np

from .. import params
from ..curve import Fp, G1_GENERATOR, affine_neg, from_jacobian, jac_add, to_jacobian
from ..fields import Fp2
from ..hash_to_curve import hash_to_g2
from ....obs.tracer import TRACER
from ....utils.metrics import COMPILE_CACHE_ERRORS, JIT_COMPILE_SECONDS
from . import fp as F
from . import pairing as PR
from . import points as P
from . import tower as T


#: the checkout this package runs from: the compile cache's fixed home
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))))))


def _cpu_machine_tag() -> str:
    """Short fingerprint of this host's CPU feature set.  XLA:CPU
    executables bake in the compile machine's vector flags, and loading
    one on a host with other flags can crash mid-run, so CPU cache
    entries are kept per feature set."""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 "flags", aarch64 "Features"
                if line.startswith(("flags", "Features")):
                    return hashlib.sha256(line.encode()).hexdigest()[:12]
    except OSError:
        pass
    import platform

    return hashlib.sha256(
        (platform.machine() + platform.processor() + platform.node()).encode()
    ).hexdigest()[:12]


def enable_compile_cache() -> str | None:
    """Turn on JAX's persistent compilation cache and return its
    directory, so a restart reuses compiled BLS programs instead of
    paying minutes of compile again.

    ``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads
    it itself and no directory is set here.  Otherwise the cache is the
    fixed ``<checkout>/.jax_cache`` (a path that moves never hits), with
    a subdirectory per CPU feature set when the backend is the CPU.
    Never raises: a dead cache only costs compile time, so the failure
    is counted on /metrics and logged, and None is returned."""
    try:
        import jax

        cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache_dir:
            cache_dir = os.path.join(CHECKOUT, ".jax_cache")
            if jax.default_backend() == "cpu":
                cache_dir = os.path.join(cache_dir,
                                         f"cpu-{_cpu_machine_tag()}")
            os.makedirs(cache_dir, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # the BLS programs are exactly the long-compile case the cache
        # exists for; cache even small/fast entries so tests exercise it
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        return cache_dir
    except Exception as exc:  # noqa: BLE001 — see docstring
        COMPILE_CACHE_ERRORS.inc()
        from ....utils import get_logger, log_with

        log_with(get_logger("bls.jax"), 30,
                 "persistent compile cache unavailable", error=str(exc))
        return None


def program_fingerprint(kernel: str, **attrs) -> str:
    """Stable per-program fingerprint for compile-time attribution: the
    kernel entry point + its static shape/config attrs + the jax version
    and backend (the same identity the AOT cache of ROADMAP item 4 will
    key on).  12 hex chars, sha256-derived."""
    import jax

    blob = json.dumps(
        {"kernel": kernel, "jax": jax.__version__,
         "backend": jax.default_backend(), **attrs},
        sort_keys=True,
    )
    return hashlib.sha256(blob.encode()).hexdigest()[:12]


def traced_jit(fn, fingerprint: str, *, capture=None, **jit_kw):
    """``jax.jit`` wrapped so the FIRST call per cache entry — the one
    that traces + compiles the program — is timed into the flight
    recorder as a ``jit.compile`` span (per-program fingerprint in its
    fields) and into ``jit_compile_seconds``.  Subsequent calls go
    straight to the compiled callable.

    ``capture``, when given, is invoked as ``capture(call, args)`` right
    after the first call completes — the AOT store's export hook
    (jax_backend/aot.py), which is never-raise by contract and works
    from arg avals only (safe under donation)."""
    import jax

    jitted = jax.jit(fn, **jit_kw)
    state = {"first": True}

    def call(*args):
        if state["first"]:
            state["first"] = False
            t0 = time.perf_counter()
            with TRACER.span("jit.compile", fingerprint=fingerprint,
                             kernel=getattr(fn, "__name__", str(fn))):
                out = jitted(*args)
            JIT_COMPILE_SECONDS.observe(time.perf_counter() - t0)
            if capture is not None:
                capture(call, args)
            return out
        return jitted(*args)

    call.jitted = jitted
    call.fingerprint = fingerprint
    return call


def _tree_reduce_g2(pt):
    """Reduce the trailing batch axis of a Jacobian G2 pytree (X, Y, Z, inf)
    by point addition (log-depth tree).  Uses the COMPLETE jac_add: the
    summands are adversarial signature points, so coincidences must be
    handled, not assumed away."""
    import jax.numpy as jnp

    B = pt[3].shape[-1]
    target = 1 << max(0, (B - 1).bit_length())
    if target != B:
        reps = target - B
        one = F.LFp(F.bcast(F.ONE_MONT, (reps,)), 1.0)
        zero = F.LFp(jnp.zeros_like(one.limbs), 0.0)

        def cat_fp2(c, pad):
            return (
                F.LFp(
                    jnp.concatenate([c[0].limbs, pad[0].limbs], axis=-1),
                    max(c[0].bound, pad[0].bound),
                ),
                F.LFp(
                    jnp.concatenate([c[1].limbs, pad[1].limbs], axis=-1),
                    max(c[1].bound, pad[1].bound),
                ),
            )

        X, Y, Z, inf = pt
        pt = (
            cat_fp2(X, (one, zero)),
            cat_fp2(Y, (one, zero)),
            cat_fp2(Z, (zero, zero)),
            jnp.concatenate([inf, jnp.ones((reps,), dtype=bool)], axis=-1),
        )
    n = target
    while n > 1:
        half = n // 2
        lo = _slice_pt(pt, 0, half)
        hi = _slice_pt(pt, half, 2 * half)
        pt = P.jac_add(P.FP2_OPS, lo, hi)
        n = half
    return pt


def _slice_lfp_tree(x, a, b):
    if isinstance(x, F.LFp):
        return F.LFp(x.limbs[..., a:b], x.bound)
    import jax.numpy as jnp

    if isinstance(x, jnp.ndarray) or hasattr(x, "shape"):
        return x[..., a:b]
    return tuple(_slice_lfp_tree(c, a, b) for c in x)


def _slice_pt(pt, a, b):
    return tuple(_slice_lfp_tree(c, a, b) for c in pt)


def _concat_lfp_tree(x, y):
    import jax.numpy as jnp

    if isinstance(x, F.LFp):
        return F.LFp(
            jnp.concatenate([x.limbs, y.limbs], axis=-1), max(x.bound, y.bound)
        )
    return tuple(_concat_lfp_tree(a, b) for a, b in zip(x, y))


def _verify_kernel(pk_aff, sig_aff, h_aff, wbits):
    """The jitted device program.  All inputs have trailing batch axis B.

    pk_aff:  G1 affine (x, y) Montgomery limbs — per-set aggregated pubkey
    sig_aff: G2 affine pytree — per-set signature
    h_aff:   G2 affine pytree — per-set message point H(m)
    wbits:   (64, B) uint32 — bits of the nonzero random weights, MSB first
    Returns a scalar bool.
    """
    import jax.numpy as jnp

    # 1. signature subgroup checks (blst.rs:71-81)
    ok_sub = jnp.all(P.g2_subgroup_check(sig_aff))
    # 2. weight scalar muls (the dispatch leader after the fused Miller
    # loop: LIGHTHOUSE_TPU_WSM runs each double-and-add bit as one
    # Mosaic program per curve — pallas_wsm.py)
    if F.wsm_fused_active():
        from . import pallas_wsm

        no_inf = jnp.zeros(wbits.shape[1:], dtype=bool)
        wpk = pallas_wsm.scalar_mul_bits_fused(
            P.FP_OPS, pk_aff, no_inf, wbits)
        wsig = pallas_wsm.scalar_mul_bits_fused(
            P.FP2_OPS, sig_aff, no_inf, wbits)
    else:
        wpk = P.scalar_mul_bits(
            P.FP_OPS, P.from_affine(P.FP_OPS, pk_aff), wbits)
        wsig = P.scalar_mul_bits(
            P.FP2_OPS, P.from_affine(P.FP2_OPS, sig_aff), wbits)
    # 3. signature accumulation: S = sum_i [r_i] sig_i
    S = _tree_reduce_g2(wsig)
    s_inf = P.pt_is_infinity(P.FP2_OPS, S)
    # 4. affinize
    wpk_aff = P.to_affine(P.FP_OPS, wpk, F.fp_inv)
    S_aff = P.to_affine(P.FP2_OPS, S, T.fp2_inv)
    # 5. assemble pairs: (wpk_i, H_i) for each set plus (-G1, S)
    neg_gen = _neg_gen_const()
    p_side = (
        _concat_lfp_tree(wpk_aff[0], neg_gen[0]),
        _concat_lfp_tree(wpk_aff[1], neg_gen[1]),
    )
    q_side = (
        _concat_lfp_tree(h_aff[0], S_aff[0]),
        _concat_lfp_tree(h_aff[1], S_aff[1]),
    )
    # 6. Miller loops + GT product + final exponentiation
    f = PR.miller_loop(p_side, q_side)
    # If S is infinity, its pair contributes 1 (e(P, O) = 1): mask the last
    # batch element rather than trusting the (0,0) affinization.
    B = wbits.shape[-1]
    mask = jnp.concatenate(
        [jnp.zeros((B,), dtype=bool), jnp.broadcast_to(s_inf, (1,))]
    )
    one = PR._fp12_one_like_from_fp2(q_side[0])
    f = T.fp12_select(mask, one, f)
    ok_pair = PR.final_exp_is_one(PR.gt_product(f))
    return ok_pair & ok_sub


def _segment_aggregate_g1(pk_aff, pad_inf, positions: int):
    """Aggregate ``positions`` committee pubkeys per set ON DEVICE.

    Layout is position-major: trailing axis = positions*B with element
    ``pos*B + set`` — every tree-reduction step then slices a CONTIGUOUS
    range of the last axis (pallas-friendly 2D limb shapes throughout),
    halving the position count per step: log2(positions) complete
    jac_adds over (B,)-wide lanes.  ``pad_inf`` marks absent members
    (committees are shorter than the padded width); their lanes are the
    infinity point, the identity of the reduction.

    This is SURVEY §7's hard part (d): per-set aggregation of up to 2048
    keys is the marshal bottleneck at epoch scale (~900k host G1 adds per
    epoch); as a device segment-sum it rides the same limb kernels as the
    pairing."""
    p = P.from_affine(P.FP_OPS, pk_aff)
    p = (p[0], p[1], p[2], p[3] | pad_inf)
    total = pad_inf.shape[-1]
    B = total // positions
    n = positions
    while n > 1:
        half = n // 2
        lo = _slice_pt(p, 0, half * B)
        hi = _slice_pt(p, half * B, 2 * half * B)
        p = P.jac_add(P.FP_OPS, lo, hi)
        n = half
    return p


def _epoch_verify_kernel(pk_aff, pad_inf, sig_aff, h_aff, wbits,
                         positions: int):
    """Epoch-scale batch verify: device committee aggregation feeding the
    standard multi-aggregate pipeline (blst.rs:35-117 semantics at the
    BASELINE.json config-4 shape: one mainnet epoch's aggregates)."""
    agg = _segment_aggregate_g1(pk_aff, pad_inf, positions)
    agg_aff = P.to_affine(P.FP_OPS, agg, F.fp_inv)
    return _verify_kernel(agg_aff, sig_aff, h_aff, wbits)


def encode_committee_pubkeys(committees: list, positions: int):
    """Host marshal for the segmented kernel: committees (lists of oracle
    affine G1 points, ragged) -> position-major encoded pytree + padding
    mask.  Padding lanes carry the generator (any valid point) under an
    infinity flag."""
    import numpy as np

    from ..curve import G1_GENERATOR

    B = len(committees)
    flat = []
    mask = np.zeros(positions * B, dtype=bool)
    for pos in range(positions):
        for b, committee in enumerate(committees):
            if pos < len(committee):
                flat.append(committee[pos])
            else:
                flat.append(G1_GENERATOR)
                mask[pos * B + b] = True
    import jax.numpy as jnp

    return P.g1_encode(flat), jnp.asarray(mask)


def _aggregate_verify_kernel(pk_aff, h_aff, sig_aff):
    """Distinct-message aggregate verification (blst.rs:244-255 semantics):
    check prod_i e(pk_i, H(m_i)) * e(-G1, sig) == 1 with ONE final exp.

    pk_aff: G1 affine batch (one per message); h_aff: G2 affine batch of
    message points; sig_aff: batch-1 G2 affine aggregate signature.
    Unlike the signature-set kernel there are no random weights (single
    statement, not a batch of independent claims) and just one subgroup
    check.
    """
    import jax.numpy as jnp

    ok_sub = jnp.all(P.g2_subgroup_check(sig_aff))
    neg_gen = _neg_gen_const()
    p_side = (
        _concat_lfp_tree(pk_aff[0], neg_gen[0]),
        _concat_lfp_tree(pk_aff[1], neg_gen[1]),
    )
    q_side = (
        _concat_lfp_tree(h_aff[0], sig_aff[0]),
        _concat_lfp_tree(h_aff[1], sig_aff[1]),
    )
    f = PR.miller_loop(p_side, q_side)
    ok_pair = PR.final_exp_is_one(PR.gt_product(f))
    return ok_pair & ok_sub


def _verify_kernel_h2c(pk_aff, sig_aff, u0, u1, wbits):
    """_verify_kernel with DEVICE-SIDE map-to-curve: takes the hash-to-field
    outputs (u0, u1 Fp2 batches) instead of precomputed H(m) points, so the
    host's per-set cost drops to SHA-256 expansion (~10 us vs ~30 ms of
    bigint SSWU).  See jax_backend/h2c.py."""
    from . import h2c

    h_aff = h2c.map_to_g2(u0, u1)
    return _verify_kernel(pk_aff, sig_aff, h_aff, wbits)


def _pack_wbits(weights: list[int]) -> np.ndarray:
    """(64, B) MSB-first weight bits, vectorized (was a 64xB Python loop).
    Ingested as two uint32 halves: numpy rejects Python ints >= 2^63 when
    building a uint64 array directly."""
    w_hi = np.array([(w >> 32) & 0xFFFFFFFF for w in weights], dtype=np.uint32)
    w_lo = np.array([w & 0xFFFFFFFF for w in weights], dtype=np.uint32)
    shifts = np.arange(31, -1, -1, dtype=np.uint32)[:, None]
    hi_bits = (w_hi[None, :] >> shifts) & np.uint32(1)
    lo_bits = (w_lo[None, :] >> shifts) & np.uint32(1)
    return np.concatenate([hi_bits, lo_bits], axis=0)


def _neg_gen_const():
    """-G1 generator as a batch-1 device constant."""
    ng = affine_neg(G1_GENERATOR)
    return P.g1_encode([ng])


_PIN_LOCK = threading.Lock()


def _pin_mxu(fn, mxu: bool):
    """Trace ``fn`` under a pinned kernel arm.  The routed plane is read
    by the program body at TRACE time (``fp.mxu_active`` inside the
    Montgomery products), so a per-shape plan that differs from the
    process-wide gate must hold the toggle around the traced call; the
    override is restored exactly, and compiled executions skip the
    Python body entirely — the pin costs nothing after the first call.
    The toggle is process-wide, so concurrent traces (``VerifyStack.warm``)
    take turns; their compiles still overlap."""
    def armed(*args):
        with _PIN_LOCK:
            prev = F.set_mxu(mxu)
            try:
                return fn(*args)
            finally:
                F.set_mxu(prev)

    armed.__name__ = fn.__name__
    return armed


class JaxBackend:
    """Device batch verification backend, registered as "jax"."""

    name = "jax"

    def __init__(self, min_batch: int = 8, device_h2c: bool | None = None,
                 batch_sizes=None):
        self._kernels = {}
        self._aot_store = None
        # The padded batch sizes, one compiled program each: a batch of
        # n sets runs at the smallest size >= n, past the largest at its
        # doublings.  A serving process pins these to the sizes it warms
        # (serve/stack.py), so bisection halves and canary batches reuse
        # warm programs instead of compiling new shapes mid-traffic; the
        # default is every power of two from ``min_batch``.
        self.batch_sizes = tuple(sorted(batch_sizes or (min_batch,)))
        # device_h2c: map messages to G2 ON DEVICE (host only hashes).
        # Measured on the v5e at B=512 (PERF.md): host marshal 120 -> 5,008
        # sets/s/core while the kernel pays +70% (2,655 -> 1,565 sets/s) for
        # the two sqrt chains — system throughput is host-bound without it,
        # balanced with it.  Default: on for TPU, off on CPU (where the
        # bigger graph just slows the test oracle).
        if device_h2c is None:
            import jax

            device_h2c = jax.default_backend() == "tpu"
        self.device_h2c = device_h2c

    def _kernel(self, B: int):
        # The arm (mxu) joins the cache key AND the compile fingerprint:
        # a different arm means a different Mosaic program for every
        # Montgomery product in the trace, so a stale cached executable
        # would silently A/A.  The arm itself is resolved per padded
        # batch shape through the installed autotuned plan
        # (fp.mxu_for_batch); set_mxu / LIGHTHOUSE_TPU_MXU remain
        # explicit overrides and force one arm for every shape.  Plan
        # resolution happens HERE, at lookup/compile time — a cache hit
        # never consults it again, so tuned routing costs nothing per
        # dispatched batch.
        mxu = F.mxu_for_batch(B)
        key = (B, self.device_h2c, mxu)
        if key not in self._kernels:
            import jax

            fn = _verify_kernel_h2c if self.device_h2c else _verify_kernel
            fp_hex = program_fingerprint(
                fn.__name__, B=B, device_h2c=self.device_h2c,
                mxu=mxu,
            )
            # Store-first: a cache miss consults the attached AOT store
            # before paying a tracing-compile — a populated store makes
            # the second boot's working set compile-free.
            if self._install_from_store(key, fp_hex):
                return self._kernels[key]
            # Donate the marshalled operands on TPU: they are fresh
            # per-batch buffers, and donation lets XLA alias them for
            # temporaries — required for double-buffered dispatch to
            # keep two batches resident without growing HBM. CPU/test
            # backends ignore donation (XLA warns), so gate it.  The
            # gate itself is load-bearing and lint-enforced: the spmd
            # audit family's donation lint (spmd-donate) fails on any
            # non-empty donate_argnums outside a TPU-backend guard,
            # and on reads of a donated buffer after the donating call.
            donate = ()
            if jax.default_backend() == "tpu":
                donate = tuple(range(5 if self.device_h2c else 4))
            self._kernels[key] = traced_jit(
                _pin_mxu(fn, mxu), fp_hex,
                capture=self._aot_capture(key, fn.__name__),
                donate_argnums=donate,
            )
        return self._kernels[key]

    # -- AOT executable store seams (jax_backend/aot.py) -------------------

    def attach_aot_store(self, store) -> None:
        """Attach an :class:`~.aot.AotStore`: cache misses consult it
        before compiling, and fresh compiles are exported into it (the
        ``traced_jit`` capture hook), so normal operation populates the
        store the next boot prewarms from."""
        self._aot_store = store

    def install_kernel(self, cache_key, fingerprint: str, call) -> None:
        """Install a deserialized AOT executable under a kernel-cache
        key, wearing the ``traced_jit`` surface (``.jitted`` /
        ``.fingerprint``) so dispatch and the dispatch audit cannot tell
        it from an organically compiled program."""
        def installed(*args):
            return call(*args)

        installed.jitted = call
        installed.fingerprint = fingerprint
        installed.aot = True
        self._kernels[tuple(cache_key)] = installed

    def _install_from_store(self, key, fp_hex: str) -> bool:
        if self._aot_store is None:
            return False
        call = self._aot_store.load(fp_hex)
        if call is None:
            return False
        self.install_kernel(key, fp_hex, call)
        return True

    def _aot_capture(self, key, kernel: str):
        """The traced_jit first-call hook bound to this cache key, or
        None when no store is attached (the common test path)."""
        if self._aot_store is None:
            return None
        store = self._aot_store

        def hook(call, args):
            store.capture(call, key, args, kernel=kernel)

        return hook

    def warm_compile(self, B: int) -> bool:
        """Trace+compile the batch-verify kernel for padded size ``B``
        ahead of traffic: one synthetic valid set, marshalled once and
        tiled along the batch axis (every kernel operand is batch-last).
        Goes through the normal ``_kernel`` path, so spans, metrics and
        AOT capture fire exactly as for organic traffic."""
        import jax

        if self._padded_size(B) != B:
            return False
        self._kernel(B)(*jax.device_put(self.warm_batch(B).args))
        return True

    def warm_batch(self, B: int) -> MarshalledBatch:
        """A valid marshalled batch of exactly ``B`` columns: one
        synthetic set, marshalled once and tiled along the batch axis
        (every kernel operand is batch-last)."""
        import jax

        from ..api import SecretKey, SignatureSet

        sk = SecretKey(2)
        msg = b"lighthouse-tpu warm-compile probe"
        s = SignatureSet(sk.sign(msg), [sk.public_key()], msg)
        mb = self.marshal_sets([s], weights=[1])
        args = jax.tree_util.tree_map(
            lambda a: np.tile(
                np.asarray(a), (1,) * (np.asarray(a).ndim - 1) + (B // mb.B,)
            ),
            mb.args,
        )
        return MarshalledBatch(B, B, self.device_h2c, args)

    # -- single/aggregate verification reuses the set machinery ------------

    def verify(self, pubkey, msg: bytes, sig) -> bool:
        from ..api import SignatureSet

        return self.verify_signature_sets([SignatureSet(sig, [pubkey], msg)])

    def aggregate_verify(self, pubkeys, msgs, sig) -> bool:
        """Distinct-message aggregate verification (blst.rs:244-255) on the
        device: one multi-pairing over the (pk_i, H(m_i)) pairs plus the
        aggregate signature, one final exp."""
        if not pubkeys or len(pubkeys) != len(msgs):
            return False
        if sig.point is None:
            return False
        import jax

        h_pts = [hash_to_g2(m) for m in msgs]
        pk_pts = [pk.point for pk in pubkeys]
        if any(p is None for p in pk_pts) or any(h is None for h in h_pts):
            return False
        # compiled per distinct n: this path is rare and sizes are small
        B = len(pk_pts)
        key = ("agg", B)
        if key not in self._kernels:
            fp_hex = program_fingerprint("_aggregate_verify_kernel", n=B)
            if not self._install_from_store(key, fp_hex):
                self._kernels[key] = traced_jit(
                    _aggregate_verify_kernel, fp_hex,
                    capture=self._aot_capture(
                        key, "_aggregate_verify_kernel"
                    ),
                )
        fn = self._kernels[key]
        ok = fn(
            P.g1_encode(pk_pts),
            P.g2_encode(h_pts),
            P.g2_encode([sig.point]),
        )
        return bool(ok)

    def fast_aggregate_verify(self, pubkeys, msg: bytes, sig) -> bool:
        from ..api import SignatureSet

        if not pubkeys:
            return False
        return self.verify_signature_sets([SignatureSet(sig, list(pubkeys), msg)])

    # -- the batch hot path ------------------------------------------------

    def verify_signature_sets(self, sets) -> bool:
        # Chaos hook: the armed site for device errors / hung compiles.
        # Unarmed cost is one dict lookup (faults.py).
        from lighthouse_tpu.utils import faults as _faults

        _faults.fire("bls.device_verify")
        mb = self.marshal_sets(sets)
        if mb.invalid:
            return False
        return self.resolve(self.dispatch(mb))

    # -- pipelined three-stage path (marshal | dispatch | resolve) ---------
    #
    # verify_signature_sets == resolve(dispatch(marshal_sets(sets))), but
    # exposing the stages lets the PipelinedVerifier (beacon/processor.py)
    # marshal batch N+1 on host workers while batch N's kernel runs: the
    # host marshal (5,008 sets/s/core with device h2c) and the fused-Miller
    # device rate (6,221 sets/s at B=8192) are near co-bound, so overlap
    # approaches wall = max(marshal, device) instead of their sum.

    def marshal_sets(self, sets, weights=None) -> MarshalledBatch:
        """Pure host stage: validation, pubkey aggregation, hashing, limb
        encode, weight packing.  Thread-safe (no backend state touched
        besides reads), so a marshal pool may run several concurrently.

        ``weights`` pins the per-set random weight draw (one int per
        set).  This is the determinism seam the ingest engine's
        differential suite uses to assert byte-identity between this
        scalar oracle and the vectorized path; production callers leave
        it None and get the secrets-drawn weights.
        """
        if not sets:
            return MarshalledBatch(0, 0, self.device_h2c, invalid=True)
        n = len(sets)
        given = weights
        pk_pts, sig_pts, h_pts, weights = [], [], [], []
        for idx, s in enumerate(sets):
            if s.signature.point is None:
                return MarshalledBatch(n, 0, self.device_h2c, invalid=True)
            if not s.signing_keys:
                return MarshalledBatch(n, 0, self.device_h2c, invalid=True)
            if len(s.signing_keys) == 1:
                # the dominant gossip case: nothing to aggregate
                agg = s.signing_keys[0].point
            else:
                # Aggregate the set's pubkeys host-side (cheap affine adds
                # over cached decompressed keys — the ValidatorPubkeyCache
                # analog).
                acc = to_jacobian(None, Fp)
                for pk in s.signing_keys:
                    acc = jac_add(acc, to_jacobian(pk.point, Fp), Fp)
                agg = from_jacobian(acc, Fp)
            if agg is None:
                return MarshalledBatch(n, 0, self.device_h2c, invalid=True)
            if not self.device_h2c:
                h = hash_to_g2(s.message)
                if h is None:  # probability-zero, but keep the host total
                    return MarshalledBatch(n, 0, self.device_h2c,
                                           invalid=True)
                h_pts.append(h)
            if given is None:
                r = 0
                while r == 0:
                    r = secrets.randbits(params.RAND_BITS)
            else:
                r = int(given[idx])
            pk_pts.append(agg)
            sig_pts.append(s.signature.point)
            weights.append(r)

        # Pad to the kernel batch size by replicating entry 0: a valid
        # duplicate cannot flip the conjunction, an invalid one already
        # fails it.
        B = self._padded_size(n)
        reps = B - n
        pk_pts += [pk_pts[0]] * reps
        sig_pts += [sig_pts[0]] * reps
        weights += [weights[0]] * reps

        pk_aff = P.g1_encode(pk_pts)
        sig_aff = P.g2_encode(sig_pts)
        wbits = _pack_wbits(weights)
        if self.device_h2c:
            from ..hash_to_curve import hash_to_field_fp2

            from . import h2c as _h2c  # noqa: F401 (kernel-side import)

            us = [hash_to_field_fp2(s.message, 2) for s in sets]
            us += [us[0]] * reps  # replicate computed u-values, not hashes
            u0 = T.fp2_encode([u[0] for u in us])
            u1 = T.fp2_encode([u[1] for u in us])
            args = (pk_aff, sig_aff, u0, u1, wbits)
        else:
            h_pts += [h_pts[0]] * reps
            h_aff = P.g2_encode(h_pts)
            args = (pk_aff, sig_aff, h_aff, wbits)
        return MarshalledBatch(n, B, self.device_h2c, args)

    def local_verify_fn(self):
        """The raw (unjitted) batch kernel for SPMD wrapping: the
        rule-driven sharded program (parallel/partition.py) runs this
        per device on its batch shard under shard_map, instead of
        slicing arrays around the jitted single-device program."""
        return _verify_kernel_h2c if self.device_h2c else _verify_kernel

    @staticmethod
    def registry_pk_wrap(x, y):
        """Wrap psum-gathered canonical Montgomery limb planes as the
        kernel's pubkey operand — the partition layer's seam so it
        never imports the field stack (bound 1.0 = encode_mont's)."""
        return (F.LFp(x, 1.0), F.LFp(y, 1.0))

    def dispatch(self, mb: MarshalledBatch):
        """Device stage, NON-BLOCKING: enqueue transfers and the kernel,
        return the in-flight result.  jax dispatch is async — device_put
        starts the host->device copies immediately and the jitted call
        returns before the kernel finishes, so the caller can marshal the
        next batch while this one runs.  ``resolve`` blocks on the value."""
        if mb.invalid:
            return False
        import jax

        args = jax.device_put(mb.args)
        return self._kernel(mb.B)(*args)

    def resolve(self, handle) -> bool:
        """Block on an in-flight dispatch and return the verdict."""
        return bool(handle)

    def verify_marshalled(self, mb: MarshalledBatch) -> bool:
        return False if mb.invalid else self.resolve(self.dispatch(mb))

    @property
    def min_batch(self) -> int:
        return self.batch_sizes[0]

    def _padded_size(self, n: int) -> int:
        """The compiled batch size a batch of ``n`` sets runs at: the
        smallest of ``batch_sizes`` >= n, else the largest doubled until
        it holds n (bounded recompiles per size)."""
        for B in self.batch_sizes:
            if B >= n:
                return B
        B = self.batch_sizes[-1]
        while B < n:
            B *= 2
        return B


@dataclass
class MarshalledBatch:
    """Host-marshalled kernel operands for one padded batch.

    The marshal stage (validation, pubkey aggregation, SHA-256 expansion
    or full hash-to-curve, weight packing, limb encode) is pure host
    work; ``args`` are exactly the positional operands of the jitted
    verify kernel.  ``invalid`` short-circuits dispatch: host validation
    already rejected the batch (empty set, infinity key/signature), the
    verdict is False without touching the device."""

    n: int                      # real (unpadded) set count
    B: int                      # padded kernel batch size
    device_h2c: bool
    args: tuple = field(default=())
    invalid: bool = False
    # registry mode (ingest marshal_for_mesh): the (B,) validator-slot
    # vector when the pubkey operand is DEFERRED to the sharded
    # program's partitioned-registry gather — args then exclude pk, and
    # only the mesh path (parallel/partition.py) may consume the batch.
    slots: Any = None


def register() -> "JaxBackend":
    """Create and register the backend in the api registry."""
    from .. import api

    backend = JaxBackend()
    api.register_backend(backend)
    return backend
