"""Vectorized ingest engine suite (lighthouse_tpu/ingest).

Four families:

* differential — the engine's ``MarshalledBatch`` must be **byte
  identical** to the scalar ``JaxBackend.marshal_sets`` oracle on every
  corpus shape (randomized message lengths including empty, duplicate
  signers, multi-signer committees, off-registry keys, padding, invalid
  sets, both h2c modes), with the weight draw pinned through the
  ``weights`` determinism seam;
* cache — hit/miss/eviction counters prove repeat signers skip
  aggregation + limb-encode, epoch boundaries invalidate the aggregate
  tier, the LRU bound holds, and the device-gather path matches host
  assembly;
* chaos — an armed ``ingest.marshal`` fault degrades to the scalar
  oracle (byte-equal output, fallback counter), and a double failure
  yields an invalid batch for the resilient ladder, never an exception;
* expand — the deduplicated hash-to-field draw equals the scalar
  ``hash_to_field_fp2`` per message, in input order, at every SHA-256
  padding edge, and 8 unique roots expand in under 10 ms;
* budget — the CI gate: on the committee fan-out shape the vectorized
  marshal must beat the scalar loop by >= 10x, so a regression to
  per-set Python fails loudly.
"""

import time

import numpy as np
import pytest

from lighthouse_tpu.crypto.bls.api import (
    PublicKey,
    SecretKey,
    Signature,
    SignatureSet,
)
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_field_fp2
from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend
from lighthouse_tpu.ingest import IngestEngine, MarshalPool, PubkeyLimbCache
from lighthouse_tpu.utils import faults
from lighthouse_tpu.utils import metrics as M

# Module-level test material: marshal never checks signature validity, so
# ONE signed point serves every set (signing is ~ms/set; re-signing per
# set would dominate the suite's wall time).
SKS = [SecretKey(1000 + i) for i in range(24)]
PKS = [sk.public_key() for sk in SKS]
SIG = SKS[0].sign(b"ingest-shared")

RNG = np.random.default_rng(0xA11CE)


@pytest.fixture(autouse=True)
def _clean_global_injector():
    faults.INJECTOR.disarm()
    yield
    faults.INJECTOR.disarm()


def _rand_msg(maxlen: int = 96) -> bytes:
    m = int(RNG.integers(0, maxlen + 1))
    return RNG.integers(0, 256, m, dtype=np.uint8).tobytes()


def _rand_sets(n: int, multi: bool = False) -> list:
    sets = []
    for i in range(n):
        if multi and i % 3 == 0:
            k = int(RNG.integers(2, 7))
            keys = [PKS[int(j)] for j in RNG.integers(0, len(PKS), k)]
        else:
            keys = [PKS[int(RNG.integers(0, len(PKS)))]]
        sets.append(SignatureSet(SIG, keys, _rand_msg()))
    return sets


def _weights(n: int) -> list[int]:
    return [int(x) for x in RNG.integers(1, 2**63, n)]


def _flat_arrays(x) -> list[np.ndarray]:
    out = []
    if isinstance(x, tuple):
        for y in x:
            out.extend(_flat_arrays(y))
    elif hasattr(x, "limbs"):
        assert x.bound == 1.0
        out.append(np.asarray(x.limbs))
    else:
        out.append(np.asarray(x))
    return out


def assert_mb_equal(a, b, tag=""):
    """Byte-for-byte equality of two MarshalledBatches."""
    assert (a.n, a.B, a.invalid, a.device_h2c) == \
        (b.n, b.B, b.invalid, b.device_h2c), tag
    if a.invalid:
        return
    assert len(a.args) == len(b.args), tag
    for i, (x, y) in enumerate(zip(a.args, b.args)):
        fx, fy = _flat_arrays(x), _flat_arrays(y)
        assert len(fx) == len(fy), (tag, i)
        for j, (ax, bx) in enumerate(zip(fx, fy)):
            assert ax.dtype == bx.dtype and ax.shape == bx.shape, (tag, i, j)
            assert ax.tobytes() == bx.tobytes(), (tag, i, j)


class FakeRegistry:
    """Minimal ValidatorPubkeyCache stand-in: index -> PublicKey."""

    def __init__(self, keys):
        self._keys = list(keys)

    def __len__(self):
        return len(self._keys)

    def get(self, i):
        return self._keys[i]

    def append(self, pk):
        self._keys.append(pk)


# ---------------------------------------------------------------------------
# differential: engine output == scalar oracle output, byte for byte
# ---------------------------------------------------------------------------


class TestDifferential:
    @pytest.mark.parametrize("device_h2c", [True, False])
    def test_randomized_corpus(self, device_h2c):
        be = JaxBackend(min_batch=8, device_h2c=device_h2c)
        eng = IngestEngine(be, device_gather=False)
        # n=3 exercises pad-to-8 replication; n=13 pad-to-16; n=8 exact
        for n, multi in [(1, False), (3, False), (8, True), (13, True)]:
            sets = _rand_sets(n, multi)
            ws = _weights(n)
            oracle = be.marshal_sets(sets, ws)
            cold = eng.marshal_sets(sets, ws)
            warm = eng.marshal_sets(sets, ws)  # cache-hit path
            assert_mb_equal(oracle, cold, f"cold n={n} h2c={device_h2c}")
            assert_mb_equal(oracle, warm, f"warm n={n} h2c={device_h2c}")

    def test_empty_and_repeated_messages(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        # empty messages, shared messages (dedup fan-out), varied lengths
        msgs = [b"", b"", b"x" * 200, b"shared-root", b"shared-root", b"y"]
        sets = [SignatureSet(SIG, [PKS[i % 4]], m)
                for i, m in enumerate(msgs)]
        ws = _weights(len(sets))
        assert_mb_equal(be.marshal_sets(sets, ws),
                        eng.marshal_sets(sets, ws), "msgs")

    def test_duplicate_signers_in_one_set(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        # same key repeated: aggregation hits the doubling path
        sets = [SignatureSet(SIG, [PKS[0], PKS[0], PKS[1]], b"dup"),
                SignatureSet(SIG, [PKS[2]] * 4, b"dup2")]
        ws = _weights(2)
        assert_mb_equal(be.marshal_sets(sets, ws),
                        eng.marshal_sets(sets, ws), "dups")

    def test_off_registry_keys(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        reg = FakeRegistry(PKS[:8])  # PKS[8:] are off-registry
        eng = IngestEngine(be, pubkey_cache=reg, device_gather=False)
        sets = [SignatureSet(SIG, [PKS[i]], b"m%d" % i) for i in range(16)]
        ws = _weights(16)
        assert_mb_equal(be.marshal_sets(sets, ws),
                        eng.marshal_sets(sets, ws), "off-registry")
        # off-registry singles live in the LRU tier, not the registry
        assert eng.cache.registry_size() == 8
        assert eng.cache.lru_size() == 8

    def test_invalid_sets_match_oracle(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        none_sig = [SignatureSet(Signature(None), [PKS[0]], b"x")]
        no_keys = [SignatureSet(SIG, [], b"x")]
        # aggregate-to-infinity: a key plus its negation
        neg = PublicKey((PKS[0].point[0], -PKS[0].point[1]))
        to_inf = [SignatureSet(SIG, [PKS[0], neg], b"x")]
        for bad in (none_sig, no_keys, to_inf, []):
            ws = _weights(len(bad))
            o = be.marshal_sets(bad, ws)
            v = eng.marshal_sets(bad, ws)
            assert o.invalid and v.invalid
        # an invalid aggregate must not poison the cache
        assert eng.cache.lru_size() == 0

    def test_device_gather_matches_host_assembly(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        reg = FakeRegistry(PKS)
        dg = IngestEngine(be, pubkey_cache=reg, device_gather=True)
        hg = IngestEngine(be, pubkey_cache=reg, device_gather=False)
        sets = [SignatureSet(SIG, [PKS[i % len(PKS)]], b"g%d" % i)
                for i in range(12)]
        ws = _weights(12)
        oracle = be.marshal_sets(sets, ws)
        assert_mb_equal(oracle, dg.marshal_sets(sets, ws), "device-gather")
        assert_mb_equal(oracle, hg.marshal_sets(sets, ws), "host-gather")


# ---------------------------------------------------------------------------
# expand: deduplicated hash-to-field == the scalar draw, per message
# ---------------------------------------------------------------------------


class TestExpand:
    @pytest.mark.parametrize(
        "length", [0, 1, 31, 32, 55, 56, 63, 64, 119, 120, 200])
    def test_dedup_matches_scalar_in_input_order(self, length):
        """Lengths straddle SHA-256's padding edges (55/56 bytes fit one
        block or spill into two; b_0 carries a 64-byte z_pad prefix);
        repeats cover the fan-out after deduplication."""
        rng = np.random.default_rng(length)
        a, b, c = (rng.integers(0, 256, length, dtype=np.uint8).tobytes()
                   for _ in range(3))
        msgs = [a, b, a, c, b, a]
        eng = IngestEngine(JaxBackend(min_batch=8, device_h2c=True))
        assert eng._expand_dedup(msgs) == \
            [hash_to_field_fp2(m, 2) for m in msgs]

    def test_expand_cost_tripwire(self):
        """8 unique 32-byte roots, best of 5, under 10 ms.  hashlib takes
        under 1 ms on an 8-core CPU host; numpy SHA-256 lanes took 60-90
        ms there whatever the batch, so a return to fixed per-call
        interpreter work fails loudly."""
        msgs = [bytes([i]) * 32 for i in range(8)]
        eng = IngestEngine(JaxBackend(min_batch=8, device_h2c=True))
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            eng._expand_dedup(msgs)
            best = min(best, time.perf_counter() - t0)
        assert best < 0.010, f"expand of 8 roots took {best * 1e3:.1f} ms"


# ---------------------------------------------------------------------------
# cache behavior
# ---------------------------------------------------------------------------


class TestPubkeyLimbCache:
    def test_hit_counters_prove_encode_skipped(self):
        """The acceptance proof: on a warm cache the whole batch resolves
        as hits — zero misses means zero aggregation/limb-encode calls
        (a miss is the only path into encode_mont for pubkeys)."""
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        sets = _rand_sets(16, multi=True)
        h0, m0 = M.INGEST_CACHE_HITS.value(), M.INGEST_CACHE_MISSES.value()
        eng.marshal_sets(sets, _weights(16))
        cold_misses = M.INGEST_CACHE_MISSES.value() - m0
        assert cold_misses > 0
        h1, m1 = M.INGEST_CACHE_HITS.value(), M.INGEST_CACHE_MISSES.value()
        eng.marshal_sets(sets, _weights(16))
        assert M.INGEST_CACHE_MISSES.value() == m1  # no new encodes
        assert M.INGEST_CACHE_HITS.value() - h1 == 16  # every set hit

    def test_epoch_boundary_invalidates_aggregates(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        reg = FakeRegistry(PKS[:8])
        eng = IngestEngine(be, pubkey_cache=reg, device_gather=False)
        committee = [PKS[1], PKS[2], PKS[3]]
        sets = [SignatureSet(SIG, committee, b"c")]
        eng.marshal_sets(sets, [7])
        assert eng.cache.lru_size() == 1
        ev0 = M.INGEST_CACHE_EVICTIONS.value()
        eng.begin_epoch(5)
        assert eng.cache.lru_size() == 0  # aggregate tier cleared
        assert eng.cache.registry_size() == 8  # registry tier survives
        assert M.INGEST_CACHE_EVICTIONS.value() - ev0 == 1
        eng.begin_epoch(5)  # same epoch: no-op
        assert M.INGEST_CACHE_EVICTIONS.value() - ev0 == 1
        # next marshal repopulates and stays byte-identical
        ws = [9]
        assert_mb_equal(be.marshal_sets(sets, ws),
                        eng.marshal_sets(sets, ws), "post-epoch")
        assert eng.cache.lru_size() == 1

    def test_lru_bound_evicts_oldest(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False, lru_capacity=4)
        ev0 = M.INGEST_CACHE_EVICTIONS.value()
        for i in range(6):  # 6 distinct committees through a 4-entry LRU
            sets = [SignatureSet(SIG, [PKS[i], PKS[i + 1]], b"c%d" % i)]
            eng.marshal_sets(sets, [3])
        assert eng.cache.lru_size() <= 4
        assert M.INGEST_CACHE_EVICTIONS.value() - ev0 >= 2

    def test_sync_registry_is_incremental(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        reg = FakeRegistry(PKS[:4])
        eng = IngestEngine(be, pubkey_cache=reg, device_gather=False)
        assert eng.cache.sync_registry(reg) == 4
        assert eng.cache.sync_registry(reg) == 0  # no-op when unchanged
        reg.append(PKS[4])
        assert eng.cache.sync_registry(reg) == 1
        assert eng.cache.registry_size() == 5
        # device mirror gathers the same columns the host path serves
        slots = np.array([0, 3, 4, 0])
        hx, hy = eng.cache.registry_columns(slots)
        dx, dy = eng.cache.gather_device(slots)
        assert np.array_equal(hx, np.asarray(dx))
        assert np.array_equal(hy, np.asarray(dy))


class TestMarshalPool:
    def test_shards_preserve_order(self):
        pool = MarshalPool(workers=4, min_shard=1)
        try:
            items = list(range(23))
            out = pool.map_shards(lambda xs: [x * 2 for x in xs], items)
            assert out == [x * 2 for x in items]
        finally:
            pool.close()

    def test_non_elementwise_fn_rejected(self):
        pool = MarshalPool(workers=1)
        with pytest.raises(ValueError):
            pool.map_shards(lambda xs: xs[:-1], [1, 2, 3])

    def test_small_batches_run_inline(self):
        pool = MarshalPool(workers=8, min_shard=256)
        assert pool.shard_count(100) == 1
        assert pool._pool is None  # never spun up


# ---------------------------------------------------------------------------
# chaos: the ingest.marshal fault site and the degradation ladder
# ---------------------------------------------------------------------------


@pytest.mark.chaos
class TestIngestChaos:
    def test_armed_fault_degrades_to_scalar_byte_equal(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        sets = _rand_sets(5)
        ws = _weights(5)
        f0 = M.INGEST_FALLBACKS.value()
        faults.INJECTOR.arm("ingest.marshal", "error", times=1)
        mb = eng.marshal_sets(sets, ws)  # must not raise
        assert M.INGEST_FALLBACKS.value() - f0 == 1
        assert not mb.invalid
        assert_mb_equal(be.marshal_sets(sets, ws), mb, "chaos-fallback")

    def test_double_failure_yields_invalid_batch_not_exception(self):
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)

        def broken(sets, weights=None):
            raise RuntimeError("scalar path down")

        eng._backend = type(
            "B", (), {"marshal_sets": staticmethod(broken),
                      "device_h2c": True, "_padded_size": be._padded_size},
        )()
        f0 = M.INGEST_FALLBACKS.value()
        faults.INJECTOR.arm("ingest.marshal", "error", times=1)
        mb = eng.marshal_sets(_rand_sets(3), _weights(3))
        assert mb.invalid  # the resilient ladder's signal, not a raise
        assert M.INGEST_FALLBACKS.value() - f0 == 2

    def test_pipelined_verifier_uses_engine_marshal(self):
        """for_backend(ingest=...) wires the engine as the marshal stage;
        an armed slow fault at ingest.marshal proves the call routes
        through the engine (and still yields a valid batch)."""
        from lighthouse_tpu.beacon.processor import PipelinedVerifier

        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        seen = []
        orig = eng.marshal_sets

        def spying(sets, weights=None):
            seen.append(len(sets))
            return orig(sets, weights)

        eng.marshal_sets = spying
        pv = PipelinedVerifier.for_backend(None, be, ingest=eng)
        mb = pv._marshal(_rand_sets(4))
        assert seen == [4] and not mb.invalid


# ---------------------------------------------------------------------------
# the CI budget gate: >= 10x on the committee fan-out shape
# ---------------------------------------------------------------------------


class TestMarshalBudget:
    def test_vectorized_beats_scalar_10x_on_committee_shape(
        self, record_property
    ):
        """Fast-tier regression tripwire (ISSUE 9 acceptance): on the
        epoch-processing shape — K=128 signers/set, repeat committees,
        warm cache — the vectorized marshal must hold >= 10x over the
        per-set scalar loop.  The margin comes from the limb cache (no
        128-key aggregation or limb encode on a warm set) and from
        hashing each of the 16 committee roots once, not per set; a
        slip below 10x means per-set Python crept back into the hot
        loop."""
        K, n_c, B = 128, 16, 256
        pool_k = 16
        committees = [
            [PKS[(c * 5 + j) % pool_k] for j in range(K)] for c in range(n_c)
        ]
        sets = [
            SignatureSet(SIG, committees[i % n_c],
                         (i % n_c).to_bytes(32, "big"))
            for i in range(B)
        ]
        be = JaxBackend(min_batch=8, device_h2c=True)
        eng = IngestEngine(be, device_gather=False)
        ws = _weights(B)
        warm = eng.marshal_sets(sets, ws)  # populate cache, untimed
        assert not warm.invalid

        # both arms timed alike and interleaved, best of several rounds:
        # load from other processes then slows both, not one
        t_vec = t_scalar = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            mb = eng.marshal_sets(sets, ws)
            t_vec = min(t_vec, time.perf_counter() - t0)
            t0 = time.perf_counter()
            oracle = be.marshal_sets(sets, ws)
            t_scalar = min(t_scalar, time.perf_counter() - t0)
        assert not mb.invalid

        assert_mb_equal(oracle, mb, "budget-shape")
        speedup = t_scalar / t_vec
        record_property("speedup", round(speedup, 2))  # junit XML
        assert speedup >= 10.0, (
            f"vectorized marshal only {speedup:.1f}x scalar "
            f"(scalar {B / t_scalar:.0f} sets/s, "
            f"vectorized {B / t_vec:.0f} sets/s); the >=10x budget means "
            "per-set Python returned to the marshal hot loop"
        )
