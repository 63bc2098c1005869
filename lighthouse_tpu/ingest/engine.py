"""Batch ingest engine: the array-at-a-time replacement for scalar marshal.

``JaxBackend.marshal_sets`` walks the batch one set at a time — per-set
hashing, per-set pubkey aggregation, per-set limb encode — so the host
feed path pins one core at ~5k sets/s while the device verifies 6.2k.
``IngestEngine.marshal_sets`` produces a **byte-identical**
``MarshalledBatch`` from three stages:

1. *expand* — each *unique* message's hash-to-field draw runs once,
   through the scalar hashlib ``hash_to_field_fp2``, and fans out to
   every set that signs it;
2. *cache*  — aggregated-pubkey limb columns come from the
   :class:`~lighthouse_tpu.ingest.cache.PubkeyLimbCache`; repeat signers
   (registry validators, warm committees) skip host aggregation and limb
   encode entirely, and an all-registry batch can gather its pubkey
   operand directly on-device;
3. *encode* — the remaining operands (signatures, u-draws, weights) are
   built by the same batched codecs the scalar path uses, padded and
   packed with identical rules.

The scalar path is retained verbatim as the differential oracle and the
degraded mode: ``marshal_sets`` never raises — any failure in the
vectorized path falls back to ``backend.marshal_sets``, and a failure
there yields an invalid batch, which the ``PipelinedVerifier`` routes
into the ResilientVerifier ladder.  A batch is degraded, never dropped.

Determinism seam: both marshals accept an optional ``weights`` list so
the differential suite can pin the random weight draw and assert
byte-for-byte equality of every array in ``MarshalledBatch.args``.
"""

from __future__ import annotations

import secrets
import time

import numpy as np

from ..crypto.bls import params
from ..crypto.bls.hash_to_curve import hash_to_field_fp2
from ..crypto.bls.jax_backend import fp as F
from ..crypto.bls.jax_backend import points as P
from ..crypto.bls.jax_backend import tower as T
from ..crypto.bls.jax_backend.backend import MarshalledBatch, _pack_wbits
from ..obs.tracer import TRACER
from ..utils import faults as _faults
from ..utils import metrics as M
from ..utils.logging import get_logger
from .cache import PubkeyLimbCache
from .pool import MarshalPool

log = get_logger("ingest.engine")


def _lfp_cols(arr) -> F.LFp:
    """(N, B) canonical Montgomery limb columns -> LFp, exactly what
    ``fp.lfp_encode`` wraps its ``encode_mont`` output in."""
    import jax.numpy as jnp

    return F.LFp(jnp.asarray(arr), 1.0)


class IngestEngine:
    """Vectorized marshal front-end over a ``JaxBackend``.

    Parameters
    ----------
    backend:
        The ``JaxBackend`` whose scalar ``marshal_sets`` is both the
        fallback and the byte-identity oracle.
    pubkey_cache:
        Optional beacon ``ValidatorPubkeyCache``; when given, the limb
        cache's registry tier is lazily synced from it before each
        marshal (an O(1) length check when nothing is new).
    device_gather:
        Gather the pubkey operand on-device for all-registry batches.
        ``None`` (default) auto-enables off-CPU, where skipping the
        host->device pubkey transfer is the point; on CPU the host
        assembly path is faster.
    """

    def __init__(self, backend, pubkey_cache=None, cache=None, pool=None,
                 device_gather: bool | None = None,
                 lru_capacity: int | None = None):
        self._backend = backend
        self._pubkey_cache = pubkey_cache
        kw = {} if lru_capacity is None else {"lru_capacity": lru_capacity}
        self.cache = cache if cache is not None else PubkeyLimbCache(**kw)
        self.pool = pool if pool is not None else MarshalPool()
        self._device_gather = device_gather

    # -- lifecycle ---------------------------------------------------------

    def begin_epoch(self, epoch: int) -> None:
        """Epoch-boundary hook: invalidate the aggregate cache tier."""
        self.cache.begin_epoch(epoch)

    def close(self) -> None:
        self.pool.close()

    def sync_registry(self) -> int:
        """Pull new validators from the beacon pubkey cache into the
        limb cache's registry tier (no-op without one); returns how
        many were added."""
        if self._pubkey_cache is None:
            return 0
        return self.cache.sync_registry(self._pubkey_cache)

    def _use_device_gather(self) -> bool:
        if self._device_gather is None:
            import jax

            self._device_gather = jax.default_backend() != "cpu"
        return self._device_gather

    # -- the never-raise marshal entry point -------------------------------
    #
    # Registered in analysis DEFAULT_NEVER_RAISE: the prover checks that
    # every path either returns a MarshalledBatch or lands in a broad
    # handler whose body only touches metrics/logging.  Shape mirrors
    # ResilientVerifier.verify_batch's degradation ladder:
    #   vectorized -> scalar oracle -> invalid batch (resilient ladder).

    def marshal_sets(self, sets, weights=None) -> MarshalledBatch:
        """Marshal ``sets`` vectorized; byte-identical to the scalar
        ``backend.marshal_sets`` on every input.  Never raises."""
        try:
            _faults.fire("ingest.marshal")
            return self._marshal_vectorized(sets, weights)
        except Exception:
            M.INGEST_FALLBACKS.inc()
            log.warning("ingest: vectorized marshal failed; "
                        "degrading to scalar path", exc_info=True)
        try:
            return self._backend.marshal_sets(sets, weights)
        except Exception:
            M.INGEST_FALLBACKS.inc()
            log.error("ingest: scalar fallback failed; "
                      "marking batch invalid", exc_info=True)
        return MarshalledBatch(len(sets), 0, self._backend.device_h2c,
                               invalid=True)

    def marshal_for_mesh(self, sets, weights=None) -> MarshalledBatch:
        """Marshal for the rule-driven sharded program: when every set
        resolves to a single-signer registry slot, the pubkey operand is
        DEFERRED — the batch carries the (B,) slot vector
        (``mb.slots``) and the sharded program gathers the columns from
        the mesh-partitioned registry mirror on device, so the pubkey
        operand never exists on host and never rides H2D.  Any other
        shape (LRU hits, cold sets, registry misses) degrades to the
        ordinary ``marshal_sets``.  Never raises (same ladder)."""
        try:
            _faults.fire("ingest.marshal")
            return self._marshal_vectorized(sets, weights,
                                            defer_registry=True)
        except Exception:
            M.INGEST_FALLBACKS.inc()
            log.warning("ingest: mesh marshal failed; degrading to the "
                        "standard path", exc_info=True)
        return self.marshal_sets(sets, weights)

    # -- vectorized pipeline ----------------------------------------------

    def _marshal_vectorized(self, sets, weights=None,
                            defer_registry: bool = False) -> MarshalledBatch:
        backend = self._backend
        if not sets:
            return MarshalledBatch(0, 0, backend.device_h2c, invalid=True)
        n = len(sets)
        self.sync_registry()
        t0 = time.perf_counter()
        with TRACER.span("ingest.marshal", sets=n):
            # Validation mirrors the scalar loop's early-outs: any
            # malformed set invalidates the whole batch (the resilient
            # ladder re-verifies set-by-set to isolate it).
            for s in sets:
                if (s.signature.point is None or not s.signing_keys
                        or not s.keys_valid()):
                    return MarshalledBatch(n, 0, backend.device_h2c,
                                           invalid=True)

            B = backend._padded_size(n)
            reps = B - n

            with TRACER.span("ingest.encode", sets=n):
                slots_arr = None
                pk_operand = None
                resolved = None
                if defer_registry:
                    slots_arr, resolved = self._registry_slots(sets, reps)
                if slots_arr is None:
                    pk_operand = self._pk_operand(sets, n, B, reps,
                                                  resolved=resolved)
                    if pk_operand is None:  # an aggregate was infinity
                        return MarshalledBatch(n, 0, backend.device_h2c,
                                               invalid=True)
                sig_pts = [s.signature.point for s in sets]
                sig_pts += [sig_pts[0]] * reps
                sig_aff = P.g2_encode(sig_pts)
                wbits = _pack_wbits(self._weights(weights, n, reps))

            msgs = [s.message for s in sets]
            if backend.device_h2c:
                from ..crypto.bls.jax_backend import h2c as _h2c  # noqa: F401

                with TRACER.span("ingest.expand", sets=n):
                    us = self._expand_dedup(msgs)
                us += [us[0]] * reps
                u0 = T.fp2_encode([u[0] for u in us])
                u1 = T.fp2_encode([u[1] for u in us])
                args = (sig_aff, u0, u1, wbits)
                if slots_arr is None:
                    args = (pk_operand,) + args
            else:
                # Host hash-to-curve: the field draws are deduplicated as
                # above; the curve steps (SSWU, isogeny, cofactor) reuse
                # the scalar building blocks hash_to_g2 composes, so
                # outputs stay identical.
                from ..crypto.bls.curve import affine_add
                from ..crypto.bls.endo import clear_cofactor_fast
                from ..crypto.bls.fields import Fp2
                from ..crypto.bls.hash_to_curve import iso_map, sswu

                with TRACER.span("ingest.expand", sets=n):
                    us = self._expand_dedup(msgs)
                h_pts = []
                for u0_, u1_ in us:
                    h = clear_cofactor_fast(
                        affine_add(iso_map(sswu(u0_)), iso_map(sswu(u1_)),
                                   Fp2))
                    if h is None:  # probability-zero, mirrors scalar
                        return MarshalledBatch(n, 0, backend.device_h2c,
                                               invalid=True)
                    h_pts.append(h)
                h_pts += [h_pts[0]] * reps
                h_aff = P.g2_encode(h_pts)
                args = (sig_aff, h_aff, wbits)
                if slots_arr is None:
                    args = (pk_operand,) + args
        elapsed = time.perf_counter() - t0
        if elapsed > 0:
            M.INGEST_MARSHAL_RATE.set(n / elapsed)
        return MarshalledBatch(n, B, backend.device_h2c, args,
                               slots=slots_arr)

    # -- stage helpers -----------------------------------------------------

    def _expand_dedup(self, msgs: list[bytes]) -> list[list]:
        """Hash-to-field draws for ``msgs``, hashing each *unique*
        message once.  Committee fan-out re-signs one signing root per
        committee across many sets; the scalar oracle re-hashes it per
        set, but hashing is a pure function of the message, so fan-out
        after deduplication yields the identical values.  Inline, not
        sharded: hashlib holds the GIL for inputs under 2 KiB, so
        threads cannot help."""
        by_msg = {m: hash_to_field_fp2(m, 2) for m in set(msgs)}
        return [by_msg[m] for m in msgs]

    def _registry_slots(self, sets, reps: int):
        """The deferred-pk fast path's precondition check: a padded
        (B,) int32 slot vector when EVERY set is a single-signer
        registry hit, else (None, resolved) so the operand path reuses
        the one cache resolve (mixed batches keep that path — a
        half-deferred batch would still marshal pk columns on host,
        paying both costs)."""
        resolved = self.cache.resolve_batch(sets)
        slots, cols, missing = resolved
        if cols or missing or (slots < 0).any():
            return None, resolved
        if reps:
            slots = np.concatenate(
                [slots, np.full(reps, slots[0], dtype=slots.dtype)])
        return slots.astype(np.int32), resolved

    def _pk_operand(self, sets, n: int, B: int, reps: int, resolved=None):
        """Aggregated-pubkey LFp pair for the padded batch, cache-first.

        Returns ``None`` if any signer set aggregates to infinity (the
        scalar path's invalid-batch condition).
        """
        from ..crypto.bls.curve import from_jacobian, jac_add, to_jacobian
        from ..crypto.bls.fields import Fp

        slots, cols, missing = (resolved if resolved is not None
                                else self.cache.resolve_batch(sets))
        if missing:
            agg_pts = []
            for i in missing:
                keys = sets[i].signing_keys
                if len(keys) == 1:
                    agg = keys[0].point
                else:
                    acc = to_jacobian(None, Fp)
                    for pk in keys:
                        acc = jac_add(acc, to_jacobian(pk.point, Fp), Fp)
                    agg = from_jacobian(acc, Fp)
                if agg is None:
                    return None
                agg_pts.append(agg)
            xs = F.encode_mont([p[0].v for p in agg_pts])
            ys = F.encode_mont([p[1].v for p in agg_pts])
            entries = []
            for j, i in enumerate(missing):
                xc = np.ascontiguousarray(xs[:, j])
                yc = np.ascontiguousarray(ys[:, j])
                cols[i] = (xc, yc)
                entries.append((sets[i].signing_keys, xc, yc))
            self.cache.insert_aggregates(entries)

        if not cols and self._use_device_gather():
            # every set resolved to a registry slot: one on-device gather,
            # no host limb assembly, no H2D pubkey transfer at dispatch
            pad = np.concatenate([slots, np.full(reps, slots[0],
                                                 dtype=slots.dtype)])
            gx, gy = self.cache.gather_device(pad)
            return (F.LFp(gx, 1.0), F.LFp(gy, 1.0))

        pk_x = np.empty((F.N, B), dtype=np.uint32)
        pk_y = np.empty((F.N, B), dtype=np.uint32)
        reg_idx = np.nonzero(slots >= 0)[0]
        if reg_idx.size:
            rx, ry = self.cache.registry_columns(slots[reg_idx])
            pk_x[:, reg_idx] = rx
            pk_y[:, reg_idx] = ry
        for i, (xc, yc) in cols.items():
            pk_x[:, i] = xc
            pk_y[:, i] = yc
        if reps:
            pk_x[:, n:] = pk_x[:, :1]
            pk_y[:, n:] = pk_y[:, :1]
        return (_lfp_cols(pk_x), _lfp_cols(pk_y))

    @staticmethod
    def _weights(weights, n: int, reps: int) -> list[int]:
        """Per-set weights, padded: injected (tests) or drawn in one
        ``token_bytes`` call instead of n ``randbits`` calls."""
        if weights is not None:
            ws = [int(w) for w in weights]
            if len(ws) != n:
                raise ValueError(f"{len(ws)} weights for {n} sets")
        else:
            mask = (1 << params.RAND_BITS) - 1
            nbytes = (params.RAND_BITS + 7) // 8
            buf = secrets.token_bytes(nbytes * n)
            ws = [
                int.from_bytes(buf[i * nbytes:(i + 1) * nbytes], "little")
                & mask
                for i in range(n)
            ]
            for i, w in enumerate(ws):
                while w == 0:  # zero weight would void the check
                    w = secrets.randbits(params.RAND_BITS)
                ws[i] = w
        return ws + [ws[0]] * reps
