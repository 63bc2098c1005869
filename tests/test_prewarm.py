"""AOT executable store + warm boot lifecycle (ROADMAP item 4's
operational half).

The real verify kernels cost minutes to trace-compile on CPU, so the
fast tier exercises the full lifecycle — capture on first call, signed
manifest, cold-restart prewarm with zero tracing-compiles, integrity
rejection, jax-version invalidation, concurrent prewarm-under-load, and
the SLO-gated warm-standby handoff scenario — over small synthetic
programs staged through the same ``traced_jit`` capture hook the
backend uses.  What the suite pins is the machinery, not the kernels:
the serialize/deserialize path, key discipline and never-raise posture
are identical either way.
"""

import json
import threading

import jax
import jax.numpy as jnp
import pytest

from lighthouse_tpu.crypto.bls.jax_backend import aot
from lighthouse_tpu.crypto.bls.jax_backend.backend import (
    JaxBackend,
    program_fingerprint,
    traced_jit,
)
from lighthouse_tpu.utils.metrics import (
    AOT_CACHE_HITS,
    AOT_CACHE_MISSES,
    AOT_CACHE_REJECTS,
    JIT_COMPILE_SECONDS,
)

X = jnp.arange(8, dtype=jnp.float32)


def _stage(store: aot.AotStore, n: int = 2) -> dict:
    """Compile ``n`` synthetic programs through the instrumented path;
    the capture hook writes each into ``store`` exactly as a serving
    node would.  Returns index -> expected output."""
    expected = {}
    for i in range(n):
        def prog(x, _i=i):
            return ((x + jnp.float32(_i)) * 3.0).sum()

        key = ("toy", i)

        def hook(call, args, _key=key):
            store.capture(call, _key, args, kernel="toy_prog")

        call = traced_jit(
            prog, program_fingerprint("toy_prog", i=i), capture=hook
        )
        expected[i] = float(call(X))
    return expected


def _rewrite_entries(store: aot.AotStore, mutate) -> None:
    """Apply ``mutate(entries)`` and re-sign — simulates a legitimate
    writer (e.g. an older process) rather than tampering."""
    with open(store.manifest_path, encoding="utf-8") as f:
        doc = json.load(f)
    mutate(doc["entries"])
    doc["signature"] = aot.manifest_signature(doc["entries"])
    with open(store.manifest_path, "w", encoding="utf-8") as f:
        json.dump(doc, f)


# ---------------------------------------------------------------------------
# Populate -> cold restart -> zero-compile prewarm
# ---------------------------------------------------------------------------


def test_populate_then_cold_restart_prewarm_zero_compiles(tmp_path):
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    expected = _stage(store, n=2)
    entries = store.entries()
    assert len(entries) == 2
    for meta in entries.values():
        assert meta["kernel"] == "toy_prog"
        assert meta["jax"] == jax.__version__
        assert meta["size"] > 0

    # "cold restart": a fresh backend process prewarms from the store
    hits0 = AOT_CACHE_HITS.value()
    compiles0 = JIT_COMPILE_SECONDS.count()
    backend = JaxBackend(min_batch=8, device_h2c=False)
    report = aot.prewarm(backend, store)
    assert sorted(report.loaded) == sorted(entries)
    assert not report.rejected and not report.stale
    # the acceptance criterion: zero tracing-compiles of staged
    # programs on the prewarmed path, including the first real call
    for i, want in expected.items():
        call = backend._kernels[("toy", i)]
        assert getattr(call, "aot", False)
        assert float(call(X)) == want
    assert JIT_COMPILE_SECONDS.count() == compiles0
    assert AOT_CACHE_HITS.value() == hits0 + 2


def test_capture_is_never_raise(tmp_path):
    # a call object without .jitted/.fingerprint cannot be exported;
    # capture must swallow it (a failed capture costs a compile, not a
    # serving-path exception)
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    assert store.capture(object(), ("toy", 0), (X,)) is False
    assert store.entries() == {}


# ---------------------------------------------------------------------------
# Integrity: byte-flip, truncation, tamper -> reject + fall back
# ---------------------------------------------------------------------------


def test_byte_flipped_blob_rejected_not_raised(tmp_path):
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    _stage(store, n=1)
    (fp_hex, meta), = store.entries().items()
    blob = tmp_path / "aot_cache" / meta["blob"]
    data = bytearray(blob.read_bytes())
    data[len(data) // 2] ^= 0xFF
    blob.write_bytes(bytes(data))

    rejects0 = AOT_CACHE_REJECTS.value()
    backend = JaxBackend(min_batch=8, device_h2c=False)
    report = aot.prewarm(backend, store, compile_misses=False)
    assert report.loaded == []
    assert report.rejected == [fp_hex]
    assert AOT_CACHE_REJECTS.value() > rejects0
    assert ("toy", 0) not in backend._kernels


def test_truncated_manifest_reads_as_cold_store(tmp_path):
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    _stage(store, n=1)
    with open(store.manifest_path, "w", encoding="utf-8") as f:
        f.write('{"schema": 1, "entries": {"aa')
    rejects0 = AOT_CACHE_REJECTS.value()
    assert store.entries() == {}
    assert AOT_CACHE_REJECTS.value() == rejects0 + 1


def test_tampered_entries_fail_signature_as_a_unit(tmp_path):
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    _stage(store, n=2)
    # hand-edit WITHOUT re-signing: the whole table is rejected
    with open(store.manifest_path, encoding="utf-8") as f:
        doc = json.load(f)
    next(iter(doc["entries"].values()))["size"] += 1
    with open(store.manifest_path, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    assert store.entries() == {}


def test_missing_entry_counts_a_miss(tmp_path):
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    misses0 = AOT_CACHE_MISSES.value()
    assert store.load("no-such-fingerprint") is None
    assert AOT_CACHE_MISSES.value() == misses0 + 1


# ---------------------------------------------------------------------------
# jax-version bump -> stale skip (the upgrade story)
# ---------------------------------------------------------------------------


def test_jax_version_bump_invalidates_entries(tmp_path):
    store = aot.AotStore(str(tmp_path / "aot_cache"))
    _stage(store, n=2)

    def bump(entries):
        for meta in entries.values():
            meta["jax"] = "0.0.0"

    _rewrite_entries(store, bump)
    misses0 = AOT_CACHE_MISSES.value()
    backend = JaxBackend(min_batch=8, device_h2c=False)
    report = aot.prewarm(backend, store)
    assert report.loaded == [] and report.rejected == []
    assert len(report.stale) == 2
    assert AOT_CACHE_MISSES.value() == misses0 + 2
    assert not any(k[0] == "toy" for k in backend._kernels)


# ---------------------------------------------------------------------------
# Concurrent prewarm + serve: the front door never closes
# ---------------------------------------------------------------------------


def test_prewarm_concurrent_with_serving_sheds_nothing(tmp_path):
    """The standby process prewarms while the old node keeps serving:
    admission on the serving thread must not shed a single request
    while the prewarm thread deserializes and installs."""
    from lighthouse_tpu.beacon.processor import (
        CircuitBreaker,
        ResilientVerifier,
    )
    from lighthouse_tpu.serve.admission import TenantPolicy
    from lighthouse_tpu.serve.service import VerifyService

    store = aot.AotStore(str(tmp_path / "aot_cache"))
    _stage(store, n=3)

    resilient = ResilientVerifier(
        device_verify=lambda sets: True,
        cpu_verify=lambda sets: True,
        breaker=CircuitBreaker(),
    )
    # the burst covers the loop's liveness backstop: a rate-limit shed
    # says how fast this loop spins, not whether prewarm degrades serving
    svc = VerifyService(
        resilient,
        policies={"client": TenantPolicy(rate=1000.0, burst=4096.0,
                                         priority="p0")},
        compiled_sizes=(8, 32),
        default_deadline_s=30.0,
    )

    standby = JaxBackend(min_batch=8, device_h2c=False)
    reports = []

    def boot_standby():
        reports.append(aot.prewarm(standby, store))

    t = threading.Thread(target=boot_standby)
    t.start()
    served = 0
    while t.is_alive() or served < 32:
        res = svc.submit("client", [("client", served)], deadline_s=30.0)
        assert res.accepted, res.reason
        served += 1
        svc.tick()
        if served >= 4096:  # liveness backstop, never expected
            break
    t.join(timeout=60)
    assert not t.is_alive()
    svc.flush()
    assert sum(svc.admission.shed.get("client", {}).values()) == 0
    assert svc.completed.get("client", 0) == served
    (report,) = reports
    assert len(report.loaded) == 3 and not report.rejected


# ---------------------------------------------------------------------------
# The SLO-gated handoff scenario (spec registry + determinism pin)
# ---------------------------------------------------------------------------

# Pinned run fingerprint for the warm-handoff scenario (same contract
# as MAINNET_SHAPE_FINGERPRINT in test_scenario.py): an intentional
# engine change may move it — re-pin deliberately.
WARM_HANDOFF_FINGERPRINT = "93ad89596842ffca"


@pytest.mark.scenario
def test_warm_handoff_scenario_passes_slos_deterministically():
    from lighthouse_tpu.scenario import run_scenario

    r1 = run_scenario("warm-handoff")
    r2 = run_scenario("warm-handoff")
    assert r1["pass"], [s for s in r1["slo"] if not s["ok"]]
    assert r2["pass"]
    assert r1["fingerprint"] == r2["fingerprint"]
    assert r1["fingerprint"] == WARM_HANDOFF_FINGERPRINT
    by_name = {s["name"]: s for s in r1["slo"]}
    assert by_name["handoff_shed"]["observed"] == 0
    assert by_name["handoff_cutover"]["ok"]
    assert by_name["standby_compiles"]["observed"] == 0
    assert by_name["prewarm_loaded"]["observed"] >= 4
    assert r1["facts"]["handoff_completed"] > 0
