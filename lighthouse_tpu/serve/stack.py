"""The one verifier-stack construction path.

``BeaconNode`` and the standalone :class:`~.service.VerifyService` used
to wire the ``IngestEngine`` -> ``ResilientVerifier`` -> ``PodVerifier``
ladder independently; this module is the single factory both consume, so
a signature batch takes byte-identical decisions whichever front end
submitted it.  The ladder, bottom up:

* the active BLS backend (``crypto/bls/api.get_backend()``) — the device
  rung; when it exposes the marshal/dispatch/resolve split, the
  vectorized :class:`~lighthouse_tpu.ingest.IngestEngine` marshals for it
  (byte-identical to the scalar marshal, degrading to it internally);
* :class:`~lighthouse_tpu.beacon.processor.ResilientVerifier` — the
  breaker-guarded device/CPU degradation ladder;
* :class:`~lighthouse_tpu.parallel.pod.PodVerifier` — per-shard fault
  domains across the device mesh when more than one device is visible
  (``maybe_build`` returns None on single-device hosts).

The returned :class:`VerifyStack` exposes the outermost ``verifier``
(the object whose ``verify_batch`` callers use) plus every rung, so a
caller that needs the breaker or the ingest engine directly (the node's
sync manager, the service's epoch hook) reaches the same instances.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass

#: the ``--bls-backend`` choices of ``bn`` and ``tools/serve.py``
BLS_BACKENDS = ("python", "jax", "fake")


def select_bls_backend(name: str) -> dict:
    """Make ``name`` the process's active BLS backend and say where it
    verifies: ``{"backend", "platform", "kind", "count"}``, the device
    fields naming JAX's devices for ``jax`` and the host for the others
    (the boot log line of ``bn`` and ``tools/serve.py``)."""
    from ..crypto.bls import api as _bls_api

    _bls_api.set_backend(name)
    if name != "jax":
        return {"backend": name, "platform": "host", "kind": name,
                "count": 1}
    import jax

    devices = jax.devices()
    return {"backend": name, "platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


@dataclass
class VerifyStack:
    """The assembled ladder: ``verifier`` is the outermost verify_batch
    surface (the pod when one was built, else the resilient rung)."""

    breaker: object
    verifier: object
    resilient: object
    ingest: object | None
    pod: object | None
    injector: object
    # the warm-boot report when the stack was built with prewarm=True
    # (crypto/bls/jax_backend/aot.PrewarmReport), else None
    prewarm_report: object | None = None
    # the IntegrityGuard wrapping ``verifier`` when the verdict-integrity
    # layer is on (integrity/guard.py), else None
    integrity: object | None = None
    # the device backend the ingest rung marshals for, else None
    backend: object | None = None

    def warm(self, sizes) -> dict:
        """Compile every program this stack dispatches at each padded
        batch size in ``sizes`` before traffic arrives: the backend's
        single-device kernel (the ladder's rung) and, behind a pod, the
        sharded programs.  The programs compile concurrently — tracing
        holds the interpreter lock, the compiler does not — and the
        result maps each size to the seconds until its last program was
        ready.  A no-op ({}) without a device backend."""
        if self.backend is None:
            return {}
        # the registry decides whether the pod's deferred-pubkey program
        # is in play, so it must be current before the pod warms
        self.ingest.sync_registry()
        jobs = []
        # largest first: it compiles longest, so its compile overlaps
        # the smaller sizes' traces
        for B in sorted(sizes, reverse=True):
            jobs.append((int(B), self._warm_device))
            if self.pod is not None:
                jobs += [(int(B), self.pod.warm_compile),
                         (int(B), self.pod.warm_registry)]
        secs: dict = {}
        errors: list = []
        lock = threading.Lock()
        t0 = time.perf_counter()

        def run(B: int, job) -> None:
            try:
                job(B)
            except Exception as exc:  # noqa: BLE001 — re-raised below
                errors.append(exc)
            with lock:
                secs[B] = max(secs.get(B, 0.0), time.perf_counter() - t0)

        threads = [threading.Thread(target=run, args=job, name=f"warm-{job[0]}")
                   for job in jobs]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        return dict(sorted(secs.items()))

    def _warm_device(self, B: int) -> None:
        if not self.backend.warm_compile(B):
            raise ValueError(f"{B} is not a padded batch size of "
                             f"{self.backend.batch_sizes}")


def _make_ingest_device_verify(ingest):
    """Device rung of the resilience ladder, marshalled by the ingest
    engine.  Fires the same ``bls.device_verify`` chaos site
    ``verify_signature_sets`` does, so armed device faults still trip
    the breaker and fall down the ladder."""
    def device_verify(sets) -> bool:
        from ..crypto.bls import api as _bls_api
        from ..utils import faults as _faults

        be = _bls_api.get_backend()
        if be is not ingest._backend:
            # backend swapped since wiring: use it directly
            return be.verify_signature_sets(sets)
        _faults.fire("bls.device_verify")
        mb = ingest.marshal_sets(sets)
        if mb.invalid:
            return False
        return be.resolve(be.dispatch(mb))

    return device_verify


def build_verify_stack(pubkey_cache=None, injector=None,
                       breaker=None, aot_store=None,
                       prewarm=False, integrity="auto",
                       canary_k=None, audit_fraction=0.0,
                       batch_sizes=None) -> VerifyStack:
    """Assemble the full verification ladder against the active backend.

    Parameters
    ----------
    pubkey_cache:
        Optional beacon ``ValidatorPubkeyCache`` handed to the ingest
        engine's limb cache (the node passes its chain's; a standalone
        service usually has none).
    injector:
        Fault injector for the pod's per-shard sites; defaults to the
        process-global one, exactly as the node wired it.
    breaker:
        Pre-built ``CircuitBreaker`` (scenario engines pin its clock);
        defaults to a fresh real-time one.
    aot_store:
        Optional :class:`~..crypto.bls.jax_backend.aot.AotStore`
        attached to the active backend (when it has the seam): cache
        misses deserialize from the store, fresh compiles are captured
        into it.
    prewarm:
        Install every current store entry into the backend's kernel
        cache NOW — before this function returns, so before any caller
        can open a listener over the stack.  When the store's manifest
        carries an autotuned kernel plan for this (device kind × jax
        version), the plan installs first (``PrewarmReport.plan_shapes``
        counts the shapes), so the loaded programs are exactly the arms
        the tuned dispatcher will ask for.  The report lands on the
        returned stack's ``prewarm_report``.
    integrity:
        ``"auto"`` (default) turns the verdict-integrity guard on when a
        device backend is active — canary known-answer batches around
        every dispatch, fail-closed re-ladder on mismatch
        (integrity/guard.py).  The scalar python backend *is* the oracle,
        so auto leaves it unguarded.  Pass True/False to force.
    canary_k:
        Canary batches per dispatch (default
        ``integrity.corpus.DEFAULT_K``).
    audit_fraction:
        Fraction of accepted batches re-verified by the cross-arm audit
        sampler (0.0 disables sampling; the canary layer is unaffected).
    batch_sizes:
        The padded batch sizes the device backend compiles (a serving
        front end passes its batcher's compiled sizes, so every batch it
        dispatches — bisection halves and canaries included — runs on a
        program :meth:`VerifyStack.warm` compiled).  None keeps the
        backend's own sizes.
    """
    from ..beacon.processor import CircuitBreaker, ResilientVerifier
    from ..crypto.bls import api as _bls_api
    from ..utils import faults as faults_mod

    if breaker is None:
        breaker = CircuitBreaker()
    if injector is None:
        injector = faults_mod.INJECTOR
    ingest = None
    _active = _bls_api.get_backend()
    prewarm_report = None
    if aot_store is not None and hasattr(_active, "attach_aot_store"):
        _active.attach_aot_store(aot_store)
        if prewarm:
            from ..crypto.bls.jax_backend import aot as _aot

            prewarm_report = _aot.prewarm(_active, aot_store)
    if batch_sizes is not None and hasattr(_active, "batch_sizes"):
        _active.batch_sizes = tuple(sorted(batch_sizes))
    if hasattr(_active, "marshal_sets") and hasattr(_active, "dispatch"):
        from ..ingest import IngestEngine

        ingest = IngestEngine(_active, pubkey_cache=pubkey_cache)
        device_verify = _make_ingest_device_verify(ingest)
    else:
        # the pure-Python backend has no stage split: direct call
        device_verify = (
            lambda s: _bls_api.get_backend().verify_signature_sets(s)
        )
    resilient = ResilientVerifier(
        device_verify=device_verify,
        cpu_verify=lambda s: _bls_api.cpu_backend().verify_signature_sets(s),
        breaker=breaker,
    )
    verifier = resilient
    pod = None
    if ingest is not None:
        from ..parallel.pod import PodVerifier

        # the pod fronts the service whenever a mesh is visible; the
        # sharded-program path gets the mesh-aware marshal (defers the
        # pubkey operand for all-registry batches) and the partitioned
        # registry mirror provider so slot-mode batches gather on-device
        pod = PodVerifier.maybe_build(
            resilient, backend=_active,
            marshal=ingest.marshal_sets,
            sharded_marshal=ingest.marshal_for_mesh,
            registry_provider=ingest.cache.registry_device_sharded,
            injector=injector,
        )
        if pod is not None:
            verifier = pod
    guard = None
    want_integrity = (ingest is not None) if integrity == "auto" else bool(integrity)
    if want_integrity:
        from ..integrity.corpus import DEFAULT_K
        from ..integrity.guard import IntegrityGuard

        guard = IntegrityGuard(
            verifier, resilient,
            k=DEFAULT_K if canary_k is None else int(canary_k),
            audit_fraction=audit_fraction,
        )
        if pod is not None:
            guard.attach_pod(pod)
        verifier = guard
    return VerifyStack(
        breaker=breaker, verifier=verifier, resilient=resilient,
        ingest=ingest, pod=pod, injector=injector,
        prewarm_report=prewarm_report, integrity=guard,
        backend=_active if ingest is not None else None,
    )
