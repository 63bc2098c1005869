"""Span tracer + ring-buffer flight recorder.

The tracer is the repo's low-overhead timing substrate: every hot-path
stage (pipeline marshal/dispatch/resolve, the resilience ladder rungs,
breaker transitions, block import, sync batches, JIT compiles) wraps
itself in a named span, and the most recent ``capacity`` spans live in a
process-global ring buffer — always on, cheap enough to leave enabled,
and dumpable as Chrome trace-event JSON (loadable in Perfetto /
``chrome://tracing``) the moment something goes wrong.  Dumps fire
automatically on breaker-open and scenario SLO failure via
:meth:`Tracer.maybe_dump`, so a failed run always leaves an artifact.

Span names are a closed registry (``SPANS`` below): the static audit
cross-references every literal ``.span("...")`` / ``.instant("...")``
call site against it, both directions, exactly the way fault sites and
metric names are checked — keep the keys literal (AST-parsed, never
imported, by ``analysis/registry_lint.py``).

Clocks are ``time.perf_counter()`` (monotonic): span timestamps are
relative to an arbitrary process epoch and only deltas are meaningful.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import NamedTuple

from ..utils.logging import get_logger
from ..utils.metrics import TRACE_DUMPS, TRACE_SPANS_DROPPED

log = get_logger("obs.tracer")


# ---------------------------------------------------------------------------
# The canonical span-name registry.  Keys are the only names
# instrumentation sites may pass to span()/instant(); the registry lint
# AST-parses this dict and flags unknown names and orphaned entries.
# ---------------------------------------------------------------------------

SPANS: dict[str, str] = {
    # PipelinedVerifier stages (beacon/processor.py)
    "pipeline.marshal": "host marshal of one batch (pool worker wall)",
    "pipeline.dispatch": "non-blocking device enqueue of a marshalled batch",
    "pipeline.resolve": "verdict resolution (blocks on the device)",
    # ResilientVerifier ladder (beacon/processor.py)
    "verify.batch": "resilience ladder around one signature batch",
    "verify.device": "device-engine attempt inside the ladder",
    "verify.cpu": "pure-Python CPU fallback rung",
    "breaker.transition": "circuit-breaker state change (instant event)",
    # chain / sync lifecycle (beacon/chain.py, beacon/sync.py)
    "block.import": "BeaconChain.process_block end-to-end",
    "sync.batch": "sync batch lifecycle: request through import",
    # JIT compiles (crypto/bls/jax_backend/backend.py)
    "jit.compile": "XLA/Mosaic program compile, per-program fingerprint",
    # AOT executable store (crypto/bls/jax_backend/aot.py)
    "aot.capture": "export+serialize of a just-compiled staged program",
    "prewarm.load": "AOT store load+install of one program at warm boot",
    # kernel autotuner (crypto/bls/jax_backend/autotune.py)
    "autotune.trial": "timed arm x batch-shape microbench (best-of-iters)",
    # scenario engine virtual slots (scenario/engine.py)
    "scenario.slot": "one virtual slot of a scenario run",
    # vectorized ingest engine (ingest/engine.py)
    "ingest.marshal": "IngestEngine vectorized marshal of one batch",
    "ingest.expand": "batched SHA-256 hash-to-field draws for the batch",
    "ingest.encode": "pubkey cache resolve + operand limb assembly",
    # pod-scale verification service (parallel/pod.py)
    "pod.dispatch": "one pod round: per-shard device dispatch + gather",
    "pod.reshard": "mesh shrink onto surviving devices (instant event)",
    # multi-tenant verification front door (serve/service.py)
    "serve.submit": "one tenant submission: admission through enqueue",
    "serve.dispatch": "one coalesced device batch: flush through verdicts",
    "serve.decode": "public-key decode of one POST at the HTTP edge",
    "serve.keycheck": "device G1 check of one flush's fresh public keys",
    # verdict-integrity layer (integrity/guard.py, integrity/selfcheck.py)
    "integrity.canary": "canary known-answer sweep around one dispatch",
    "integrity.audit": "cross-arm audit re-verify of a sampled batch",
    "integrity.quarantine": "device trust quarantine (instant event)",
    "integrity.selfcheck": "boot-time known-answer sweep over installed kernels",
}


class SpanRecord(NamedTuple):
    """One committed span: ``(name, start, duration, parent, fields)``."""

    sid: int          # unique, monotonically increasing span id
    parent: int       # sid of the enclosing span on this thread, or 0
    name: str         # key into SPANS
    t0: float         # perf_counter() at entry
    dur: float        # seconds
    tid: int          # OS thread id
    fields: tuple     # sorted (key, value) pairs, JSON-safe values


class _NopSpan:
    """Singleton no-op context manager returned by a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def add(self, **fields):
        return self


_NOP = _NopSpan()


class _LiveSpan:
    """An open span; commits itself to the tracer ring on ``__exit__``."""

    __slots__ = ("_tracer", "name", "fields", "sid", "parent", "t0")

    def __init__(self, tracer, name, fields):
        self._tracer = tracer
        self.name = name
        self.fields = fields
        self.sid = 0
        self.parent = 0
        self.t0 = 0.0

    def add(self, **fields):
        """Attach extra fields to the span before it closes."""
        self.fields.update(fields)
        return self

    def __enter__(self):
        tracer = self._tracer
        self.sid = next(tracer._ids)
        stack = tracer._stack()
        if stack:
            self.parent = stack[-1]
        stack.append(self.sid)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb):
        dur = time.perf_counter() - self.t0
        stack = self._tracer._stack()
        if stack and stack[-1] == self.sid:
            stack.pop()
        if exc_type is not None:
            self.fields["error"] = exc_type.__name__
        self._tracer._commit(self, dur)
        return False


class Tracer:
    """Thread-safe ring-buffer flight recorder of timing spans.

    ``capacity`` bounds memory: beyond it the oldest spans are dropped
    (and counted in ``trace_spans_dropped_total``).  A disabled tracer's
    ``span()`` call is a single attribute test returning a shared no-op
    context manager — cheap enough to leave instrumentation in place
    unconditionally.
    """

    def __init__(self, capacity: int = 8192, enabled: bool = True):
        if capacity < 1:
            raise ValueError("tracer capacity must be >= 1")
        self.capacity = capacity
        self.enabled = enabled
        self._ids = itertools.count(1)
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)
        self._dropped = 0
        self._dump_dir: str | None = None
        self._dump_seq: dict = {}
        self._dump_limit = 8

    # -- emission ---------------------------------------------------------

    def span(self, name: str, **fields):
        """Open a span; use as ``with TRACER.span("pipeline.marshal"):``."""
        if not self.enabled:
            return _NOP
        return _LiveSpan(self, name, fields)

    def instant(self, name: str, **fields) -> None:
        """Record a zero-duration point event (e.g. a state transition)."""
        if not self.enabled:
            return
        sp = _LiveSpan(self, name, fields)
        sp.sid = next(self._ids)
        stack = self._stack()
        if stack:
            sp.parent = stack[-1]
        sp.t0 = time.perf_counter()
        self._commit(sp, 0.0)

    def _stack(self) -> list:
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = []
            self._tls.stack = stack
        return stack

    def _commit(self, sp: _LiveSpan, dur: float) -> None:
        rec = SpanRecord(
            sid=sp.sid,
            parent=sp.parent,
            name=sp.name,
            t0=sp.t0,
            dur=dur,
            tid=threading.get_ident(),
            fields=tuple(sorted(sp.fields.items())),
        )
        with self._lock:
            if len(self._buf) == self.capacity:
                self._dropped += 1
                TRACE_SPANS_DROPPED.inc()
            self._buf.append(rec)

    # -- inspection -------------------------------------------------------

    def snapshot(self, since_sid: int = 0) -> list:
        """Spans currently in the ring with ``sid > since_sid``, oldest first."""
        with self._lock:
            recs = list(self._buf)
        if since_sid:
            recs = [r for r in recs if r.sid > since_sid]
        return recs

    def mark(self) -> int:
        """Current high-water span id; pass to snapshot()/dump() as ``since``."""
        with self._lock:
            return self._buf[-1].sid if self._buf else 0

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._dropped = 0

    # -- export -----------------------------------------------------------

    def chrome_trace(self, since_sid: int = 0) -> dict:
        """The ring as a Chrome trace-event JSON object (Perfetto-loadable)."""
        events = []
        for r in self.snapshot(since_sid):
            args = dict(r.fields)
            args["sid"] = r.sid
            if r.parent:
                args["parent"] = r.parent
            events.append({
                "name": r.name,
                "cat": "lighthouse_tpu",
                "ph": "X",
                "ts": round(r.t0 * 1e6, 3),
                "dur": round(r.dur * 1e6, 3),
                "pid": os.getpid(),
                "tid": r.tid,
                "args": args,
            })
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, path: str, since_sid: int = 0) -> str:
        """Write the ring as Chrome trace JSON to ``path``; returns ``path``."""
        doc = self.chrome_trace(since_sid)
        tmp = f"{path}.tmp"
        with open(tmp, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, indent=None, separators=(",", ":"))
        os.replace(tmp, path)
        TRACE_DUMPS.inc()
        return path

    def configure_dump_dir(self, path: str | None) -> None:
        """Directory for automatic ``maybe_dump`` artifacts (None disables)."""
        with self._lock:
            self._dump_dir = path
            self._dump_seq = {}

    def maybe_dump(self, reason: str, since_sid: int = 0) -> str | None:
        """Best-effort automatic dump (breaker-open, SLO failure, ...).

        Writes ``trace-<reason>-<NNN>.json`` into the configured dump dir
        (or ``$LIGHTHOUSE_TPU_TRACE_DIR``), at most ``_dump_limit`` files
        per reason per process.  Never raises — this is called from
        never-raise paths like the breaker transition.
        """
        try:
            with self._lock:
                dump_dir = self._dump_dir
            dump_dir = dump_dir or os.environ.get("LIGHTHOUSE_TPU_TRACE_DIR")
            if not dump_dir or not self.enabled:
                return None
            with self._lock:
                seq = self._dump_seq.get(reason, 0) + 1
                if seq > self._dump_limit:
                    return None
                self._dump_seq[reason] = seq
            os.makedirs(dump_dir, exist_ok=True)
            path = os.path.join(dump_dir, f"trace-{reason}-{seq:03d}.json")
            self.dump(path, since_sid)
            log.info("flight-recorder dump (%s) -> %s", reason, path)
            return path
        except Exception as exc:  # never-raise: diagnostics must not kill the node
            log.warning("flight-recorder dump failed (%s): %s", reason, exc)
            return None


def _env_capacity() -> int:
    try:
        return max(1, int(os.environ.get("LIGHTHOUSE_TPU_TRACE_RING", "8192")))
    except ValueError:
        return 8192


#: The process-global flight recorder every instrumentation site uses.
#: ``LIGHTHOUSE_TPU_TRACE=0`` disables it; ``LIGHTHOUSE_TPU_TRACE_RING``
#: resizes the ring.
TRACER = Tracer(
    capacity=_env_capacity(),
    enabled=os.environ.get("LIGHTHOUSE_TPU_TRACE", "1") != "0",
)
