"""AOT executable store: serialize the staged BLS programs, warm-boot nodes.

ROADMAP item 4's operational half.  A long-running node compiles a
handful of programs once and streams batches through them — but every
boot and every upgrade re-pays that compile (170 s for the pallas
chains, worse for pathological compositions).  This module makes the
compiled artifacts themselves durable:

* :class:`AotStore` — an on-disk store under ``<datadir>/aot_cache/``:
  one ``jax.export`` StableHLO blob per staged program, keyed by the
  same ``program_fingerprint`` the ``jit.compile`` spans carry (kernel
  entry point x static config x jax version x device kind), indexed by
  a signed JSON ``manifest.json``.  Capture is a side effect of normal
  operation: the backend's ``traced_jit`` first-call hook exports each
  program right after its compile, so a node that has served traffic
  has, by construction, a store describing its working set.
* :func:`prewarm` — the ``bn --prewarm`` boot phase: deserialize and
  install every current manifest entry into the backend's kernel cache
  (``prewarm.load`` spans, ``aot_cache_hits_total``), and optionally
  trace-compile the misses, BEFORE the node joins the network or the
  serve front door opens.  A prewarmed process performs zero tracing
  compiles of staged programs on its serving path.

Integrity posture (never-raise): a corrupt, truncated, tampered or
version-mismatched entry can only cost the time to detect it — ``load``
falls back to returning None (the caller trace-compiles as if the store
were cold) and counts the event in ``aot_cache_rejects_total``.  The
manifest is signed (sha256 over a domain-separated canonical encoding)
so partial writes and hand-edits are detected as a unit; each blob is
content-addressed by its own sha256 recorded in the manifest.
"""

from __future__ import annotations

import hashlib
import json
import os
import time
from dataclasses import dataclass, field

from ....obs.tracer import TRACER
from ....utils import device_kind, get_logger, log_with
from ....utils.metrics import (
    AOT_CACHE_HITS,
    AOT_CACHE_MISSES,
    AOT_CACHE_REJECTS,
)

log = get_logger("aot")

MANIFEST_SCHEMA = 1

# Domain separator for the manifest signature: sha256 over this prefix +
# the canonical (sorted-keys, compact) JSON of the entries table.  Not a
# MAC — there is no secret; the signature detects truncation, partial
# writes and accidental edits as a unit, the same trust model as the
# per-blob content hashes.
MANIFEST_DOMAIN = "lighthouse-tpu/aot-manifest/v1:"

# The registered program set eligible for AOT capture from the serving
# path: the batch-verify kernels (both h2c modes), the rare-path
# aggregate kernel and the public-key check that runs on every flush
# carrying fresh keys.  Keep this a literal tuple — the ``aot-manifest``
# registry-lint family AST-parses it and cross-references (a) every name
# here against the kernel definitions in backend.py and (b) every
# manifest entry's ``kernel`` field against this set (orphans are
# findings in both directions).
AOT_KERNELS = (
    "_verify_kernel",
    "_verify_kernel_h2c",
    "_aggregate_verify_kernel",
    "_g1_keycheck_kernel",
)


def manifest_signature(entries: dict) -> str:
    """Deterministic signature over a manifest table (see
    MANIFEST_DOMAIN) — used for both the ``entries`` index and the
    autotuned ``plan`` (each carries its own signature, so tampering
    with either is detected independently).  Shared with the
    ``aot-manifest`` / ``tune-plan`` lint families so an audited
    manifest is checked with the byte-identical algorithm."""
    blob = json.dumps(entries, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256((MANIFEST_DOMAIN + blob).encode()).hexdigest()


# Sentinel for _write_manifest: "keep whatever plan the manifest already
# holds" (capture must not drop a tuned plan when it re-signs entries).
_KEEP_PLAN = object()


_EXPORT_TYPES_REGISTERED = [False]


def register_export_types() -> None:
    """Register the backend's custom pytree containers with
    ``jax.export``'s serialization registry (idempotent).  The staged
    programs close over :class:`~.fp.LFp` operands; without this,
    ``Exported.serialize`` refuses the pytree."""
    if _EXPORT_TYPES_REGISTERED[0]:
        return
    from jax import export

    from . import fp as F

    try:
        export.register_pytree_node_serialization(
            F.LFp,
            serialized_name="lighthouse_tpu.LFp",
            serialize_auxdata=lambda bound: json.dumps(bound).encode(),
            deserialize_auxdata=lambda b: json.loads(bytes(b).decode()),
        )
    except ValueError:
        pass  # a previous registration (e.g. module reload) already holds
    _EXPORT_TYPES_REGISTERED[0] = True


def _abstractify(args):
    """Shape/dtype skeleton of the call args: export re-traces from
    avals only, so this never touches buffer contents — safe even when
    the originals were donated to the compiled call."""
    import jax

    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args
    )


class AotStore:
    """Signed on-disk store of exported (AOT-serialized) staged programs.

    Layout under ``root``::

        manifest.json        signed index: fingerprint -> entry meta
        <fingerprint>.bin    jax.export StableHLO blob, content-hashed

    Every read path is never-raise: a broken store behaves exactly like
    a cold one (plus a rejects counter and a structured log line)."""

    def __init__(self, root: str):
        self.root = root
        self.manifest_path = os.path.join(root, "manifest.json")

    # -- manifest ----------------------------------------------------------

    def entries(self) -> dict:
        """The signature-verified entries table; ``{}`` (plus one reject
        count) when the manifest is absent-after-claiming, corrupt,
        truncated, or its signature does not match."""
        if not os.path.exists(self.manifest_path):
            return {}
        try:
            with open(self.manifest_path, encoding="utf-8") as f:
                doc = json.load(f)
            entries = doc["entries"]
            if doc.get("schema") != MANIFEST_SCHEMA:
                raise ValueError(f"manifest schema {doc.get('schema')!r}")
            if doc.get("signature") != manifest_signature(entries):
                raise ValueError("manifest signature mismatch")
            return entries
        except Exception as exc:  # noqa: BLE001 — never-raise read path
            AOT_CACHE_REJECTS.inc()
            log_with(log, 30, "AOT manifest rejected",
                     path=self.manifest_path, error=str(exc))
            return {}

    def _read_doc(self) -> dict:
        """Raw manifest document, ``{}`` on any problem (never-raise;
        signature checks happen in :meth:`entries` / :meth:`plan`)."""
        if not os.path.exists(self.manifest_path):
            return {}
        try:
            with open(self.manifest_path, encoding="utf-8") as f:
                return json.load(f)
        except Exception:  # noqa: BLE001 — never-raise read path
            return {}

    def plan(self) -> dict:
        """The signature-verified autotuned kernel plan
        (autotune.tune's output: device kind × jax version × per-shape
        winning arms), or ``{}`` when absent, corrupt, or tampered —
        a bad plan behaves exactly like a cold boot."""
        doc = self._read_doc()
        plan = doc.get("plan")
        if not isinstance(plan, dict) or not plan:
            return {}
        if doc.get("plan_signature") != manifest_signature(plan):
            AOT_CACHE_REJECTS.inc()
            log_with(log, 30, "AOT plan rejected",
                     path=self.manifest_path, error="plan signature mismatch")
            return {}
        return plan

    def write_plan(self, plan: dict | None) -> None:
        """Persist (or clear, with ``None``) the autotuned plan alongside
        the entries table.  The entries index is re-read and re-signed
        through the verified path, so a tuner can never launder a
        tampered entries table by writing a plan."""
        os.makedirs(self.root, exist_ok=True)
        self._write_manifest(self.entries(), plan=plan or None)

    def _write_manifest(self, entries: dict, plan=_KEEP_PLAN) -> None:
        if plan is _KEEP_PLAN:
            # capture() rewrites entries; keep the tuned plan it rides
            # with — but only if the plan still verifies (a tampered
            # plan must not get re-signed into legitimacy).
            held = self.plan()
            plan = held or None
        doc = {
            "schema": MANIFEST_SCHEMA,
            "entries": entries,
            "signature": manifest_signature(entries),
        }
        if plan:
            doc["plan"] = plan
            doc["plan_signature"] = manifest_signature(plan)
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f, sort_keys=True, indent=1)
        os.replace(tmp, self.manifest_path)

    # -- capture (export + serialize) --------------------------------------

    def capture(self, call, cache_key, args, kernel: str = "") -> bool:
        """Export + serialize a just-compiled ``traced_jit`` program and
        record it under its fingerprint.  Runs on the serving path right
        after a first-call compile, so it must never raise: a failed
        capture costs the next boot a compile, nothing else."""
        try:
            import jax
            from jax import export

            register_export_types()
            fp_hex = call.fingerprint
            with TRACER.span("aot.capture", fingerprint=fp_hex,
                             kernel=kernel or "?"):
                exported = export.export(call.jitted)(*_abstractify(args))
                data = bytes(exported.serialize())
            os.makedirs(self.root, exist_ok=True)
            blob_name = fp_hex + ".bin"
            with open(os.path.join(self.root, blob_name), "wb") as f:
                f.write(data)
            entries = self.entries()
            entries[fp_hex] = {
                "kernel": kernel or getattr(call, "kernel", ""),
                "cache_key": list(cache_key),
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "blob": blob_name,
                "sha256": hashlib.sha256(data).hexdigest(),
                "size": len(data),
            }
            self._write_manifest(entries)
            log_with(log, 20, "AOT program captured", fingerprint=fp_hex,
                     kernel=kernel, bytes=len(data))
            return True
        except Exception as exc:  # noqa: BLE001 — capture is best-effort
            log_with(log, 30, "AOT capture failed",
                     kernel=kernel, error=str(exc))
            return False

    # -- load (deserialize) ------------------------------------------------

    def load(self, fingerprint: str, meta: dict | None = None):
        """Deserialize one entry into a callable, or None (never raises).
        Counts ``aot_cache_hits_total`` on success, ``_misses_total``
        when the store simply has no such program, ``_rejects_total``
        when an entry exists but fails integrity or deserialization."""
        if meta is None:
            meta = self.entries().get(fingerprint)
        if meta is None:
            AOT_CACHE_MISSES.inc()
            return None
        try:
            from jax import export

            with open(os.path.join(self.root, meta["blob"]), "rb") as f:
                data = f.read()
            if hashlib.sha256(data).hexdigest() != meta["sha256"]:
                raise ValueError("blob sha256 mismatch")
            register_export_types()
            exported = export.deserialize(bytearray(data))
            AOT_CACHE_HITS.inc()
            return exported.call
        except Exception as exc:  # noqa: BLE001 — fall back to compiling
            AOT_CACHE_REJECTS.inc()
            log_with(log, 30, "AOT entry rejected", fingerprint=fingerprint,
                     error=str(exc))
            return None


# ---------------------------------------------------------------------------
# The --prewarm boot phase
# ---------------------------------------------------------------------------


@dataclass
class PrewarmReport:
    """What one prewarm pass did, for the boot log, the ``kind="boot"``
    bench row and the handoff scenario's SLO facts."""

    loaded: list = field(default_factory=list)     # installed fingerprints
    rejected: list = field(default_factory=list)   # failed integrity/deser
    stale: list = field(default_factory=list)      # other jax/backend/config
    compiled: list = field(default_factory=list)   # misses trace-compiled
    plan_shapes: int = 0                           # tuned shapes installed
    seconds: float = 0.0

    def to_row(self) -> dict:
        return {
            "loaded": len(self.loaded), "rejected": len(self.rejected),
            "stale": len(self.stale), "compiled": len(self.compiled),
            "plan_shapes": self.plan_shapes,
            "seconds": round(self.seconds, 3),
        }


def _entry_current(meta: dict, backend) -> bool:
    """Entry matches this process: same jax version + device kind, and —
    for verify-kernel entries whose cache key pins them — the backend's
    current h2c/mxu config (an entry for the other config would install
    into a cache slot the dispatcher never consults)."""
    import jax

    from . import fp as F

    if meta.get("jax") != jax.__version__:
        return False
    if meta.get("backend") != jax.default_backend():
        return False
    key = meta.get("cache_key") or ()
    if len(key) == 3 and key[0] != "agg":
        if bool(key[1]) != bool(getattr(backend, "device_h2c", key[1])):
            return False
        try:
            batch = int(key[0])
        except (TypeError, ValueError):
            return False
        # plan-aware: the arm the dispatcher will ask for at this batch
        # shape (installed tuned plan, unless an override forces one arm
        # for every shape — see fp.mxu_for_batch).
        if bool(key[2]) != F.mxu_for_batch(batch):
            return False
    return True


def prewarm(backend, store: AotStore, *, compile_misses: bool = False,
            ) -> PrewarmReport:
    """Deserialize and install every current manifest entry into
    ``backend``'s kernel cache, one ``prewarm.load`` span per entry.

    Runs BEFORE the node joins the network or the serve front door
    opens (cli.run_bn orders it ahead of every listener).  Entries for
    another jax version / device kind / backend config are skipped as
    stale (the fingerprint the backend would ask for differs anyway);
    corrupt entries are rejected by :meth:`AotStore.load` and — when
    ``compile_misses`` — re-compiled through the normal traced path so
    the store heals itself on the next capture.

    The autotuned kernel plan installs FIRST: entry currency is judged
    against the plan-resolved arm per batch shape, so the loaded set is
    exactly the programs the tuned dispatcher will ask for.  A stale or
    tampered plan installs nothing and the pass proceeds on the
    env/default arm (cold-plan behavior)."""
    report = PrewarmReport()
    t0 = time.perf_counter()
    plan = store.plan()
    if plan:
        from . import autotune

        report.plan_shapes = autotune.install_plan(plan)
    entries = store.entries()
    for fp_hex, meta in sorted(entries.items()):
        if not _entry_current(meta, backend):
            report.stale.append(fp_hex)
            AOT_CACHE_MISSES.inc()
            continue
        with TRACER.span("prewarm.load", fingerprint=fp_hex,
                         kernel=meta.get("kernel", "?")):
            call = store.load(fp_hex, meta)
        if call is None:
            report.rejected.append(fp_hex)
            if compile_misses and _recompile_entry(backend, meta):
                report.compiled.append(fp_hex)
            continue
        backend.install_kernel(tuple(meta.get("cache_key", ())),
                               fp_hex, call)
        report.loaded.append(fp_hex)
    report.seconds = time.perf_counter() - t0
    log_with(log, 20, "Prewarm finished", **report.to_row())
    return report


def _recompile_entry(backend, meta: dict) -> bool:
    """Trace-compile the program a rejected entry described, through the
    backend's normal (capturing) kernel path, so the store heals.  Only
    the batch-verify keys are recompilable from metadata alone."""
    key = meta.get("cache_key") or ()
    if len(key) != 3 or key[0] == "agg":
        return False
    try:
        warm = getattr(backend, "warm_compile", None)
        return bool(warm and warm(int(key[0])))
    except Exception as exc:  # noqa: BLE001 — prewarm must not kill boot
        log_with(log, 30, "Prewarm recompile failed",
                 cache_key=list(key), error=str(exc))
        return False


def record_boot_row(row: dict, path: str | None = None) -> None:
    """Append a ``kind="boot"`` row to BENCH_HISTORY.jsonl (the same
    ledger bench.py writes), never raising: boot accounting must not be
    able to fail a boot."""
    try:
        if path is None:
            path = os.path.join(
                os.path.dirname(os.path.abspath(__file__)),
                "..", "..", "..", "..", "BENCH_HISTORY.jsonl",
            )
        out = {
            "kind": "boot",
            "device_kind": device_kind(),
            "measured_at": time.strftime(
                "%Y-%m-%dT%H:%M:%SZ", time.gmtime()
            ),
        }
        out.update(row)
        with open(path, "a", encoding="utf-8") as f:
            f.write(json.dumps(out) + "\n")
    except Exception as exc:  # noqa: BLE001 — accounting only
        log_with(log, 30, "boot history write failed", error=str(exc))
