"""Rule-driven sharded verification program suite (parallel/partition).

Runs on the conftest's virtual 8-device CPU mesh.  Families:

* rule table — operand naming binds the live marshal output to the
  literal ``OPERAND_LEAVES`` inventory, every leaf resolves through
  ``PARTITION_RULES``, and an unmatched leaf is a hard error;
* program — stub-kernel SPMD dispatch: all-true verdicts, a poisoned
  column condemns exactly its shard, non-divisible batches pad with
  AND-safe duplicates, and the partitioned-registry gather reconstructs
  byte-exact pubkey columns from the mesh-sharded mirror;
* pod — the sharded fast path through ``PodVerifier``: clean batches
  take one SPMD dispatch, a failing shard re-verifies only its column
  range, device loss re-shards 8 -> 4 and width 1 falls back to the
  per-device coordinator;
* epoch stream — double buffering bounds in-flight chunks, so peak host
  memory stays O(chunk) over an epoch-sized stream (tracemalloc-pinned);
* registry mirror — ``registry_device_sharded`` shrinks per-device
  bytes by the mesh width;
* the shard_map seam — ``shard_map`` + ``jax.jit(in_shardings=)`` drive a
  mesh program end-to-end on this jax version.

The real-kernel mesh byte-identity runs (random / all-invalid /
aggregate-to-infinity corpora against the single-device oracle) are
marked slow: they compile the production kernel for the 8-way mesh.
"""

import threading
import tracemalloc

import numpy as np
import pytest

from lighthouse_tpu.beacon.processor import CircuitBreaker, ResilientVerifier
from lighthouse_tpu.parallel import partition as P
from lighthouse_tpu.parallel.mesh import (
    BATCH_AXIS,
    shard_map,
    make_mesh,
)
from lighthouse_tpu.parallel.pod import PodVerifier
from lighthouse_tpu.utils import faults
from lighthouse_tpu.utils.faults import FaultInjector

pytestmark = pytest.mark.compile

N_LIMBS = 26


@pytest.fixture(autouse=True)
def _clean_global_injector():
    faults.INJECTOR.disarm()
    yield
    faults.INJECTOR.disarm()


# ---------------------------------------------------------------------------
# Stub operands: real pytree shapes (F.LFp nodes), no field math — the
# kernel is a conjunction over the wbits plane, so a set's verdict is
# encoded by zeroing its wbits column.
# ---------------------------------------------------------------------------


def _lfp(B, val=1):
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.bls.jax_backend import fp as F

    return F.LFp(jnp.full((N_LIMBS, B), val, dtype=jnp.uint32), 1.0)


def _point2(B):
    return ((_lfp(B), _lfp(B)), (_lfp(B), _lfp(B)))


def _stub_args(verdicts):
    """Non-h2c operand tuple (pk, sig, h, wbits) for a bool batch."""
    import jax.numpy as jnp

    B = len(verdicts)
    wb = np.ones((4, B), dtype=np.uint32)
    for i, v in enumerate(verdicts):
        if not v:
            wb[:, i] = 0
    return ((_lfp(B), _lfp(B)), _point2(B), _point2(B), jnp.asarray(wb))


def _stub_kernel(pk, sig, h, wbits):
    import jax.numpy as jnp

    return jnp.all(wbits > 0)


@pytest.fixture(scope="module")
def mesh8():
    return make_mesh(8)


@pytest.fixture(scope="module")
def program8(mesh8):
    return P.ShardedVerifyProgram(mesh8, _stub_kernel)


# ---------------------------------------------------------------------------
# Rule table / operand naming
# ---------------------------------------------------------------------------


class TestRuleTable:
    def test_stub_operands_name_into_the_inventory(self):
        names = [n for n, _ in P.named_operand_leaves(_stub_args([True] * 4))]
        assert set(names) <= set(P.OPERAND_LEAVES)
        assert "pk/x/limbs" in names and "wbits" in names

    def test_live_marshal_leaves_bind_to_inventory_and_rules(self):
        """The engine's marshalled operand tree names into
        OPERAND_LEAVES and every leaf is rule-claimed — host-only, no
        kernel compile."""
        from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet
        from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend
        from lighthouse_tpu.ingest import IngestEngine

        backend = JaxBackend()
        engine = IngestEngine(backend)
        sks = [SecretKey(7000 + i) for i in range(4)]
        pks = [sk.public_key() for sk in sks]
        sig = sks[0].sign(b"partition-binding")
        sets = [SignatureSet(sig, [pks[i]], b"m%d" % i) for i in range(4)]

        mb = engine.marshal_sets(sets)
        named = P.named_operand_leaves(mb.args)
        assert {n for n, _ in named} <= set(P.OPERAND_LEAVES)
        specs = P.match_partition_rules(P.PARTITION_RULES, named)
        assert len(specs) == len(named)

        class _PkCache:
            def __init__(self, keys):
                self._keys = keys

            def __len__(self):
                return len(self._keys)

            def get(self, i):
                return self._keys[i]

        engine.cache.sync_registry(_PkCache(pks))
        mb = engine.marshal_for_mesh(sets)
        assert mb.slots is not None  # all-registry batch defers the pk
        named = P.named_operand_leaves(mb.args, deferred_pk=True)
        reg_leaves = {"registry/x", "registry/y", "slots"}
        assert ({n for n, _ in named} | reg_leaves) <= set(P.OPERAND_LEAVES)

    def test_unmatched_leaf_is_a_hard_error(self):
        with pytest.raises(ValueError, match="partition rule not found"):
            P.match_partition_rules((), [("pk/x/limbs", np.ones((2, 4)))])

    def test_unrecognized_operand_arity_is_a_hard_error(self):
        with pytest.raises(ValueError, match="unrecognized operand"):
            P.named_operand_leaves((np.ones((2, 4)),))

    def test_specs_split_only_the_trailing_batch_axis(self):
        args = _stub_args([True] * 8)
        specs = P.operand_partition_specs(args)
        flat = []

        def collect(t):
            if isinstance(t, tuple) and t and not hasattr(t, "_fields"):
                from jax.sharding import PartitionSpec as PS

                if isinstance(t, PS):
                    flat.append(t)
                else:
                    for e in t:
                        collect(e)
            else:
                flat.append(t)

        collect(specs)
        for spec in flat:
            assert spec[-1] == BATCH_AXIS
            assert all(p is None for p in spec[:-1])


# ---------------------------------------------------------------------------
# The sharded program (stub kernel, 8-way mesh)
# ---------------------------------------------------------------------------


class TestShardedProgram:
    def test_all_true_batch_verdicts_true_everywhere(self, program8):
        v = program8.verdict_vector(_stub_args([True] * 16))
        assert v.shape == (8,) and v.all()

    def test_poisoned_column_condemns_exactly_its_shard(self, program8):
        verdicts = [True] * 16
        verdicts[5] = False  # shard 2 owns columns [4, 6)
        v = program8.verdict_vector(_stub_args(verdicts))
        assert list(v) == [i != 2 for i in range(8)]
        assert program8.shard_bounds(16)[2] == (4, 6)

    def test_non_divisible_batch_pads_and_stays_true(self, program8):
        v = program8.verdict_vector(_stub_args([True] * 12))
        assert v.all()
        bounds = program8.shard_bounds(12)
        assert bounds[5] == (10, 12)
        assert bounds[6] == (12, 12) and bounds[7] == (12, 12)

    def test_padding_is_and_safe_for_a_failing_tail(self, program8):
        verdicts = [True] * 12
        verdicts[11] = False  # last real column; pad dups column 0 (True)
        v = program8.verdict_vector(_stub_args(verdicts))
        # only shard 5 ([10, 12)) fails; padding-only shards stay true
        assert list(v) == [i != 5 for i in range(8)]

    def test_program_cache_reuses_compiles_per_structure(self, program8):
        before = len(program8._programs)
        program8.verdict_vector(_stub_args([True] * 16))
        program8.verdict_vector(_stub_args([True] * 24))
        assert len(program8._programs) == max(before, 1)


class TestPartitionedRegistry:
    N_REG = 24  # divisible by 8: no mirror padding

    def _registry(self, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PS

        rx = np.zeros((N_LIMBS, self.N_REG), dtype=np.uint32)
        rx[0, :] = np.arange(self.N_REG)
        ry = np.zeros((N_LIMBS, self.N_REG), dtype=np.uint32)
        ry[0, :] = 1000 + np.arange(self.N_REG)
        sharding = NamedSharding(mesh, PS(None, BATCH_AXIS))
        return (jax.device_put(rx, sharding), jax.device_put(ry, sharding))

    @staticmethod
    def _reg_kernel(pk, sig, h, wbits):
        """The gathered pubkey columns must match the slot vector the
        marshal carried in the wbits plane — a byte-identity probe for
        the masked-take + psum gather."""
        import jax.numpy as jnp

        x_ok = jnp.all(pk[0].limbs[0, :] == wbits[0, :])
        y_ok = jnp.all(pk[1].limbs[0, :] == 1000 + wbits[0, :])
        return x_ok & y_ok & jnp.all(wbits[1, :] > 0)

    def _rest_args(self, slots, valid):
        import jax.numpy as jnp

        B = len(slots)
        wb = np.ones((4, B), dtype=np.uint32)
        wb[0, :] = slots
        for i, v in enumerate(valid):
            if not v:
                wb[1, i] = 0
        return (_point2(B), _point2(B), jnp.asarray(wb))

    @staticmethod
    def _pk_wrap(x, y):
        from lighthouse_tpu.crypto.bls.jax_backend import fp as F

        return (F.LFp(x, 1.0), F.LFp(y, 1.0))

    def test_gather_is_byte_identical_to_host_take(self, mesh8):
        prog = P.ShardedVerifyProgram(
            mesh8, self._reg_kernel, pk_wrap=self._pk_wrap
        )
        rng = np.random.default_rng(14)
        slots = rng.integers(0, self.N_REG, 16).astype(np.int32)
        v = prog.verdict_vector_registry(
            self._registry(mesh8), slots, self._rest_args(slots, [True] * 16)
        )
        assert v.shape == (8,) and v.all()

    def test_registry_failure_localizes_to_the_shard(self, mesh8):
        prog = P.ShardedVerifyProgram(
            mesh8, self._reg_kernel, pk_wrap=self._pk_wrap
        )
        slots = np.arange(16, dtype=np.int32) % self.N_REG
        valid = [True] * 16
        valid[9] = False  # shard 4 owns columns [8, 10)
        v = prog.verdict_vector_registry(
            self._registry(mesh8), slots, self._rest_args(slots, valid)
        )
        assert list(v) == [i != 4 for i in range(8)]

    def test_non_divisible_slots_pad_like_the_operands(self, mesh8):
        prog = P.ShardedVerifyProgram(
            mesh8, self._reg_kernel, pk_wrap=self._pk_wrap
        )
        slots = np.arange(13, dtype=np.int32) % self.N_REG
        v = prog.verdict_vector_registry(
            self._registry(mesh8), slots, self._rest_args(slots, [True] * 13)
        )
        assert v.all()

    def test_registry_mode_without_pk_wrap_raises(self, mesh8):
        prog = P.ShardedVerifyProgram(mesh8, self._reg_kernel)
        with pytest.raises(ValueError, match="pk_wrap"):
            prog.execute_registry(self._registry(mesh8), np.zeros(8), ())


# ---------------------------------------------------------------------------
# Pod integration: the sharded fast path
# ---------------------------------------------------------------------------


class _ShardedStubMB:
    def __init__(self, args, B):
        self.args = args
        self.B = B
        self.invalid = []
        self.slots = None


class ShardedStubBackend:
    """Backend-mode surface with the raw-kernel seam the sharded path
    needs (``local_verify_fn``) plus the width-keyed kernel the
    per-device coordinator uses, so both roads are drivable."""

    def __init__(self):
        self.local_fn_grabs = 0
        self.kernel_widths = []
        self._lock = threading.Lock()

    def marshal_sets(self, sets):
        args = _stub_args([bool(s) for s in sets])
        return _ShardedStubMB(args, len(sets))

    def local_verify_fn(self):
        with self._lock:
            self.local_fn_grabs += 1
        return _stub_kernel

    def _kernel(self, width):
        import jax

        with self._lock:
            self.kernel_widths.append(width)
        return jax.jit(_stub_kernel)

    def resolve(self, handle):
        return bool(handle)


def make_sharded_pod(**kw):
    clock = [0.0]
    breaker = CircuitBreaker(failure_threshold=3, now=lambda: clock[0])

    def _all(sets):
        return all(bool(s) for s in sets)

    resilient = ResilientVerifier(
        device_verify=_all, cpu_verify=_all, breaker=breaker,
        now=lambda: clock[0], injector=FaultInjector(),
    )
    backend = kw.pop("backend", None) or ShardedStubBackend()
    pod = PodVerifier(
        resilient, backend=backend, injector=FaultInjector(),
        backoff_base=0.0, **kw,
    )
    return pod, backend


class TestPodShardedPath:
    def test_clean_batch_takes_one_spmd_dispatch(self):
        pod, backend = make_sharded_pod()
        out = pod.verify_batch([True] * 10)
        assert out.verdicts == [True] * 10
        assert out.device_calls == 8          # one program, whole mesh
        assert backend.local_fn_grabs == 1    # sharded road, not threaded
        assert backend.kernel_widths == []

    def test_failing_shard_reverifies_only_its_columns(self):
        pod, _ = make_sharded_pod()
        sets = [True] * 10
        sets[7] = False
        out = pod.verify_batch(sets)
        assert out.verdicts == sets
        # partial fallback: the mesh dispatch is still billed in full
        assert out.device_calls >= 8

    def test_device_loss_reshards_the_sharded_program(self):
        pod, _ = make_sharded_pod()
        health = pod._ensure_health()
        for dev in (4, 5, 6, 7):
            health.exclude(dev)
        out = pod.verify_batch([True] * 8)
        assert out.verdicts == [True] * 8
        assert out.device_calls == 4          # width followed the mesh

    def test_width_one_falls_back_to_the_coordinator(self):
        pod, backend = make_sharded_pod()
        health = pod._ensure_health()
        for dev in range(1, 8):
            health.exclude(dev)
        out = pod.verify_batch([True] * 6)
        assert out.verdicts == [True] * 6
        assert out.device_calls == 1
        assert backend.kernel_widths != []    # the threaded road ran

    def test_sharded_disabled_flag_uses_the_coordinator(self):
        pod, backend = make_sharded_pod(sharded=False)
        out = pod.verify_batch([True] * 8)
        assert out.verdicts == [True] * 8
        assert backend.local_fn_grabs == 0
        assert backend.kernel_widths != []

    def test_slot_mode_without_registry_provider_remarshal_falls_back(self):
        """A slot-mode batch whose sharded dispatch cannot run (no
        registry provider) re-marshals through the standard path for
        the per-device coordinator — never an exception, never a wrong
        verdict."""
        backend = ShardedStubBackend()

        def slot_marshal(sets):
            mb = backend.marshal_sets(sets)
            mb.slots = np.arange(len(sets), dtype=np.int32)
            mb.args = mb.args[1:]  # deferred pk: (sig, h, wbits)
            return mb

        pod, _ = make_sharded_pod(
            backend=backend, sharded_marshal=slot_marshal
        )
        out = pod.verify_batch([True] * 8)
        assert out.verdicts == [True] * 8
        assert backend.kernel_widths != []    # coordinator finished it


# ---------------------------------------------------------------------------
# Epoch streaming: double buffering + peak host memory
# ---------------------------------------------------------------------------


class _StreamStubProgram:
    """Host-only program stand-in: handles are the operand tuples, so
    whatever the stream keeps alive is visible to tracemalloc."""

    width = 4

    def __init__(self):
        self.live = 0
        self.peak_live = 0
        self.registry_calls = 0

    def pad_operands(self, args):
        return args

    def shard_operands(self, args, deferred_pk=False):
        return args

    def dispatch(self, args):
        self.live += 1
        self.peak_live = max(self.peak_live, self.live)
        return args

    def dispatch_registry(self, registry, slots, rest_args):
        self.registry_calls += 1
        return self.dispatch(tuple(rest_args))

    def resolve(self, handle):
        self.live -= 1
        ok = bool(np.all(handle[0]))
        return np.full(self.width, ok, dtype=bool)


class _StreamStubMB:
    def __init__(self, arr, slots=None, invalid=()):
        self.args = (arr,)
        self.invalid = list(invalid)
        self.slots = slots


class TestEpochStream:
    def test_results_arrive_in_order_with_bounded_inflight(self):
        prog = _StreamStubProgram()
        chunks = [[bool((i + j) % 3) for j in range(4)] for i in range(9)]

        def marshal(chunk):
            return _StreamStubMB(np.array(chunk, dtype=np.int8))

        results = list(P.stream_epoch(chunks, marshal, prog, inflight=2))
        assert [r.index for r in results] == list(range(9))
        assert prog.peak_live <= 2
        for r, chunk in zip(results, chunks):
            assert r.ok == all(chunk)
            assert r.n == len(chunk)

    def test_invalid_chunk_yields_false_without_dispatch(self):
        prog = _StreamStubProgram()

        def marshal(chunk):
            if len(chunk) == 2:
                return _StreamStubMB(np.ones(1), invalid=[0])
            return _StreamStubMB(np.ones(len(chunk), dtype=np.int8))

        chunks = [[True] * 4, [True] * 2, [True] * 4]
        results = list(P.stream_epoch(chunks, marshal, prog))
        assert [r.ok for r in results] == [True, False, True]
        assert results[1].verdicts is None

    def test_registry_chunks_ride_the_partitioned_gather(self):
        prog = _StreamStubProgram()

        def marshal(chunk):
            return _StreamStubMB(
                np.ones(len(chunk), dtype=np.int8),
                slots=np.zeros(len(chunk), dtype=np.int32),
            )

        results = list(P.stream_epoch(
            [[True] * 4] * 3, marshal, prog, registry=("rx", "ry")
        ))
        assert prog.registry_calls == 3
        assert all(r.ok for r in results)

    def test_peak_host_memory_is_chunk_scale_not_epoch_scale(self):
        """An epoch-sized stream of 4 MB chunks must never hold more
        than inflight + 1 chunks' operands on host: the double buffer
        frees each marshalled chunk as its verdict resolves."""
        chunk_bytes = 4 * 1024 * 1024
        n_chunks = 16
        prog = _StreamStubProgram()

        def marshal(chunk):
            return _StreamStubMB(
                np.ones(chunk_bytes, dtype=np.uint8) * len(chunk)
            )

        tracemalloc.start()
        try:
            base = tracemalloc.get_traced_memory()[0]
            ok = all(
                r.ok for r in P.stream_epoch(
                    [[True]] * n_chunks, marshal, prog, inflight=2
                )
            )
            peak = tracemalloc.get_traced_memory()[1]
        finally:
            tracemalloc.stop()
        assert ok
        # whole epoch = 64 MB of operands; the stream may hold ~3
        assert peak - base < 4 * chunk_bytes


# ---------------------------------------------------------------------------
# Registry mirror partitioning (ingest cache)
# ---------------------------------------------------------------------------


class _FakeFp:
    def __init__(self, v):
        self.v = v


class _FakeKey:
    def __init__(self, i):
        self.point = (_FakeFp(2 * i + 1), _FakeFp(2 * i + 2))


class _FakeValidatorCache:
    def __init__(self, n):
        self._keys = [_FakeKey(i) for i in range(n)]

    def __len__(self):
        return len(self._keys)

    def get(self, i):
        return self._keys[i]


class TestShardedRegistryMirror:
    def test_per_device_bytes_shrink_with_mesh_width(self):
        from lighthouse_tpu.ingest import PubkeyLimbCache

        cache = PubkeyLimbCache()
        assert cache.sync_registry(_FakeValidatorCache(37)) == 37
        full_cols = cache.registry_device()[0].shape[1]
        assert full_cols == 37
        per_dev = {}
        for width in (1, 2, 4, 8):
            rx, _ry = cache.registry_device_sharded(make_mesh(width))
            shard_cols = rx.sharding.shard_shape(rx.shape)[1]
            assert rx.shape[1] == 37 + ((-37) % width)  # padded, not grown
            assert shard_cols * width == rx.shape[1]
            per_dev[width] = shard_cols
        assert per_dev[1] == 37
        assert per_dev[8] == 5  # ceil(37 / 8)
        assert per_dev[1] > per_dev[2] > per_dev[4] > per_dev[8]

    def test_registry_growth_invalidates_the_sharded_mirror(self):
        from lighthouse_tpu.ingest import PubkeyLimbCache

        cache = PubkeyLimbCache()
        cache.sync_registry(_FakeValidatorCache(8))
        mesh = make_mesh(8)
        first = cache.registry_device_sharded(mesh)
        assert cache.registry_device_sharded(mesh) is first  # cached
        cache.sync_registry(_FakeValidatorCache(16))
        second = cache.registry_device_sharded(mesh)
        assert second is not first
        assert second[0].shape[1] == 16


# ---------------------------------------------------------------------------
# The shard_map seam (parallel/mesh)
# ---------------------------------------------------------------------------


class TestShardMapSeam:
    def test_shard_map_and_jit_sharded_run_a_mesh_program(self, mesh8):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as PS

        def local(x):
            return jax.lax.psum(jnp.sum(x), BATCH_AXIS)

        fn = shard_map(
            local, mesh8, in_specs=PS(BATCH_AXIS), out_specs=PS()
        )
        jfn = jax.jit(
            fn, in_shardings=NamedSharding(mesh8, PS(BATCH_AXIS))
        )
        x = jnp.arange(16.0)
        assert float(jfn(x)) == float(x.sum())


# ---------------------------------------------------------------------------
# Real-kernel mesh byte-identity (slow: production kernel, 8-way compile)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestRealKernelByteIdentity:
    @pytest.fixture(scope="class")
    def material(self):
        from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet

        sks = [SecretKey(9000 + i) for i in range(8)]
        pks = [sk.public_key() for sk in sks]
        msgs = [b"epoch-%d" % i for i in range(8)]
        sets = [
            SignatureSet(sk.sign(m), [pk], m)
            for sk, pk, m in zip(sks, pks, msgs)
        ]
        return sks, pks, sets

    def _program(self, backend):
        return P.ShardedVerifyProgram(
            make_mesh(8), backend.local_verify_fn(),
            pk_wrap=getattr(backend, "registry_pk_wrap", None),
        )

    @pytest.fixture()
    def jax_active(self):
        # build_verify_stack wires the pod off the *active* registry
        # backend; the default pure-python one has no shard surface.
        from lighthouse_tpu.crypto.bls import api

        prev = api.get_backend()
        api.set_backend("jax")
        try:
            yield
        finally:
            api._ACTIVE[0] = prev

    def test_valid_corpus_matches_single_device(self, material):
        from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

        _sks, _pks, sets = material
        backend = JaxBackend()
        mb = backend.marshal_sets(sets)
        assert not mb.invalid
        single = bool(backend.resolve(backend.dispatch(mb)))
        v = self._program(backend).verdict_vector(tuple(mb.args))
        assert bool(v.all()) == single is True

    def test_invalid_corpus_localizes_and_matches(self, material):
        from lighthouse_tpu.crypto.bls.api import SignatureSet
        from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

        sks, pks, sets = material
        bad = list(sets)
        bad[5] = SignatureSet(sks[5].sign(b"other"), [pks[5]], b"epoch-5")
        backend = JaxBackend()
        mb = backend.marshal_sets(bad)
        single = bool(backend.resolve(backend.dispatch(mb)))
        assert single is False
        prog = self._program(backend)
        v = prog.verdict_vector(tuple(mb.args))
        assert not v.all()
        # the failing shard is exactly the one owning column 5
        owner = next(
            i for i, (a, b) in enumerate(prog.shard_bounds(len(bad)))
            if a <= 5 < b
        )
        assert not v[owner]
        assert all(v[i] for i in range(8) if i != owner)

    def test_aggregate_to_infinity_takes_the_ladder_byte_identical(
        self, material, jax_active
    ):
        """The pk + (-pk) set marshals invalid, so the sharded program
        never sees it — the pod front door must still produce the
        oracle's per-set verdicts via the ladder."""
        from lighthouse_tpu.crypto.bls.api import PublicKey, SignatureSet
        from lighthouse_tpu.serve.stack import build_verify_stack

        sks, pks, sets = material
        neg = PublicKey((pks[0].point[0], -pks[0].point[1]))
        stack = build_verify_stack()
        to_inf = SignatureSet(sks[0].sign(b"inf"), [pks[0], neg], b"inf")
        corpus = list(sets[:4]) + [to_inf]
        verdicts = stack.verifier.verify_batch(corpus).verdicts
        assert list(verdicts) == [True] * 4 + [False]

    def test_serve_stack_routes_the_sharded_path(self, material, jax_active):
        from lighthouse_tpu.serve.stack import build_verify_stack

        _sks, _pks, sets = material
        stack = build_verify_stack()
        assert stack.pod is not None, "8-device mesh must wire the pod"
        assert stack.pod._sharded_enabled()
        out = stack.verifier.verify_batch(list(sets))
        assert out.verdicts == [True] * len(sets)
