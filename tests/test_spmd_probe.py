"""Differential probes for the spmd static-audit family.

The ``spmd`` lint family (analysis/spmd_lint) *statically* proves four
theorem classes over the staged sharded programs by abstract
interpretation of their jaxprs.  This suite executes the real programs
on the conftest's 8-way virtual CPU mesh and checks that the runtime
behaviour lands inside the statically proven envelopes:

* shard-verdict localization — a single invalid set condemns exactly
  the shard whose ``shard_bounds`` range contains it, for every column
  position, including non-divisible remainders (bounds theorem);
* pad absorption — mirror-of-column-0 pad lanes never flip a shard's
  verdict, true or false (pad theorem);
* replication — the (width,) verdict output is bit-identical on every
  device of the mesh (replication theorem, the check that
  ``out_specs=P()`` is honoured in value, not just in type);
* registry gather — the masked take + psum reconstruction is
  byte-identical to a host-side ``take`` oracle, and the gather index
  envelope proven statically ([0, n_local-1] after masking) holds for
  boundary slots (collective/bounds theorems).

The analyzer itself is covered in test_static_analysis; this file is
the "differential" half the ISSUE demands: same programs, real
``shard_map`` execution, runtime facts vs proved envelopes.  The
real-production-kernel run is marked slow (8-way kernel compile).
"""

import numpy as np
import pytest

from lighthouse_tpu.parallel import partition as P
from lighthouse_tpu.parallel.mesh import BATCH_AXIS, make_mesh

pytestmark = pytest.mark.compile

N_LIMBS = 26


def _lfp(B, val=1):
    import jax.numpy as jnp

    from lighthouse_tpu.crypto.bls.jax_backend import fp as F

    return F.LFp(jnp.full((N_LIMBS, B), val, dtype=jnp.uint32), 1.0)


def _point2(B):
    return ((_lfp(B), _lfp(B)), (_lfp(B), _lfp(B)))


def _stub_args(verdicts):
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
# Shard-verdict localization vs the static shard_bounds envelope
# ---------------------------------------------------------------------------


class TestVerdictEnvelope:
    def test_valid_corpus_every_shard_true(self, program8):
        v = program8.verdict_vector(_stub_args([True] * 16))
        assert v.shape == (8,) and v.all()

    @pytest.mark.parametrize("bad", [0, 7, 9, 15])
    def test_single_invalid_condemns_exactly_its_proven_shard(
            self, program8, bad):
        verdicts = [True] * 16
        verdicts[bad] = False
        v = program8.verdict_vector(_stub_args(verdicts))
        bounds = program8.shard_bounds(16)
        expect = [not (lo <= bad < hi) for lo, hi in bounds]
        assert list(v) == expect

    @pytest.mark.parametrize("total,bad", [(13, 12), (13, 0), (9, 8)])
    def test_non_divisible_remainder_localizes(self, program8, total, bad):
        verdicts = [True] * total
        verdicts[bad] = False
        v = program8.verdict_vector(_stub_args(verdicts))
        # the full padded contract: shard i condemns iff its padded
        # column range holds the bad set, or holds a pad lane while
        # column 0 (the pad mirror source) is itself the bad set
        width = program8.width
        padded = total + (-total) % width
        size = padded // width
        expect = []
        for i in range(width):
            cols = range(i * size, (i + 1) * size)
            hit = any(c == bad or (c >= total and bad == 0) for c in cols)
            expect.append(not hit)
        assert list(v) == expect


# ---------------------------------------------------------------------------
# Pad absorption: mirror-of-column-0 lanes never flip a verdict
# ---------------------------------------------------------------------------


class TestPadAbsorption:
    def test_all_pad_shards_mirror_a_true_column(self, program8):
        v = program8.verdict_vector(_stub_args([True]))
        assert v.shape == (8,) and v.all()

    def test_all_pad_shards_mirror_a_false_column(self, program8):
        # a failing column 0 duplicates into every pad lane: all shards
        # must go false together — pads absorb, they don't invent truth
        v = program8.verdict_vector(_stub_args([False]))
        assert not v.any()

    def test_failing_tail_does_not_leak_into_pads(self, program8):
        verdicts = [True] * 12
        verdicts[11] = False
        v = program8.verdict_vector(_stub_args(verdicts))
        bounds = program8.shard_bounds(12)
        assert list(v) == [not (lo <= 11 < hi) for lo, hi in bounds]

    def test_padded_stage_is_width_multiple_and_mirrors_col0(
            self, program8):
        args = program8.pad_operands(_stub_args([True] * 13))
        wb = np.asarray(args[3])
        assert wb.shape[1] % program8.width == 0
        for j in range(13, wb.shape[1]):
            assert (wb[:, j] == wb[:, 0]).all()


# ---------------------------------------------------------------------------
# Replication: the verdict vector is bit-identical on every device
# ---------------------------------------------------------------------------


class TestReplication:
    @pytest.mark.parametrize("verdicts", [
        [True] * 16,
        [True] * 7 + [False] + [True] * 8,
        [False] * 16,
        [True] * 13,
    ])
    def test_verdict_bit_identical_across_all_shards(
            self, program8, verdicts):
        handle = program8.dispatch(_stub_args(verdicts))
        handle.block_until_ready()
        shards = handle.addressable_shards
        assert len(shards) == 8
        ref = np.asarray(shards[0].data)
        assert ref.shape == (8,)
        for s in shards[1:]:
            got = np.asarray(s.data)
            assert got.tobytes() == ref.tobytes()


# ---------------------------------------------------------------------------
# Registry gather: runtime values vs the host oracle and the proven
# index envelope
# ---------------------------------------------------------------------------


class TestRegistryGatherProbe:
    N_REG = 24

    def _registry_arrays(self):
        rx = np.zeros((N_LIMBS, self.N_REG), dtype=np.uint32)
        rx[0, :] = np.arange(self.N_REG)
        ry = np.zeros((N_LIMBS, self.N_REG), dtype=np.uint32)
        ry[0, :] = 1000 + np.arange(self.N_REG)
        return rx, ry

    def _registry(self, mesh):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as PS

        rx, ry = self._registry_arrays()
        sharding = NamedSharding(mesh, PS(None, BATCH_AXIS))
        return (jax.device_put(rx, sharding), jax.device_put(ry, sharding))

    def _gather_program(self, mesh):
        """The production gather body, staged alone so the probe can
        compare its full (26, B) reconstruction to a host take."""
        import jax
        from jax.sharding import PartitionSpec as PS

        from lighthouse_tpu.parallel.mesh import shard_map

        def local(reg_x, reg_y, slots_local):
            x, y = P._registry_gather_local(
                reg_x, reg_y, slots_local, BATCH_AXIS
            )
            # re-gather the per-shard slices so the host sees the full
            # planes in batch order
            x = jax.lax.all_gather(x, BATCH_AXIS, axis=1, tiled=True)
            y = jax.lax.all_gather(y, BATCH_AXIS, axis=1, tiled=True)
            return x, y

        return shard_map(
            local, mesh,
            in_specs=(PS(None, BATCH_AXIS), PS(None, BATCH_AXIS),
                      PS(BATCH_AXIS)),
            out_specs=(PS(), PS()),
        )

    @pytest.mark.parametrize("seed", [3, 14])
    def test_gather_matches_host_take_oracle(self, mesh8, seed):
        import jax.numpy as jnp

        rng = np.random.default_rng(seed)
        slots = rng.integers(0, self.N_REG, 16).astype(np.int32)
        reg = self._registry(mesh8)
        fn = self._gather_program(mesh8)
        x, y = fn(reg[0], reg[1], jnp.asarray(slots))
        rx, ry = self._registry_arrays()
        np.testing.assert_array_equal(np.asarray(x), rx[:, slots])
        np.testing.assert_array_equal(np.asarray(y), ry[:, slots])

    def test_boundary_slots_stay_in_the_proven_envelope(self, mesh8):
        """Slots pinned to 0 and n-1 — the ends of the statically
        proven [0, n_total-1] domain — still reconstruct exactly,
        which means every shard's masked take stayed inside its local
        [0, n_local-1] bound (out-of-bound indices would wrap or clamp
        to the wrong column and break the byte identity)."""
        import jax.numpy as jnp

        slots = np.array(
            [0, self.N_REG - 1] * 8, dtype=np.int32
        )
        reg = self._registry(mesh8)
        x, y = self._gather_program(mesh8)(
            reg[0], reg[1], jnp.asarray(slots)
        )
        rx, ry = self._registry_arrays()
        np.testing.assert_array_equal(np.asarray(x), rx[:, slots])
        np.testing.assert_array_equal(np.asarray(y), ry[:, slots])

    def test_registry_verdicts_localize_like_the_flat_path(self, mesh8):
        from lighthouse_tpu.crypto.bls.jax_backend import fp as F

        def reg_kernel(pk, sig, h, wbits):
            import jax.numpy as jnp

            x_ok = jnp.all(pk[0].limbs[0, :] == wbits[0, :])
            y_ok = jnp.all(pk[1].limbs[0, :] == 1000 + wbits[0, :])
            return x_ok & y_ok & jnp.all(wbits[1, :] > 0)

        def pk_wrap(x, y):
            return (F.LFp(x, 1.0), F.LFp(y, 1.0))

        prog = P.ShardedVerifyProgram(mesh8, reg_kernel, pk_wrap=pk_wrap)
        slots = np.arange(16, dtype=np.int32) % self.N_REG
        wb = np.ones((4, 16), dtype=np.uint32)
        wb[0, :] = slots
        wb[1, 9] = 0  # invalidate set 9
        import jax.numpy as jnp

        rest = (_point2(16), _point2(16), jnp.asarray(wb))
        v = prog.verdict_vector_registry(self._registry(mesh8), slots, rest)
        bounds = prog.shard_bounds(16)
        assert list(v) == [not (lo <= 9 < hi) for lo, hi in bounds]


# ---------------------------------------------------------------------------
# Real production kernel (slow: 8-way compile)
# ---------------------------------------------------------------------------


@pytest.mark.slow
class TestRealKernelTheorems:
    @pytest.fixture(scope="class")
    def material(self):
        from lighthouse_tpu.crypto.bls.api import SecretKey, SignatureSet

        sks = [SecretKey(7100 + i) for i in range(8)]
        pks = [sk.public_key() for sk in sks]
        msgs = [b"probe-%d" % i for i in range(8)]
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

    def test_replication_and_pads_on_the_real_kernel(self, material):
        from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

        _sks, _pks, sets = material
        backend = JaxBackend()
        # 5 of 8: three pad lanes mirror column 0 through the real
        # pairing kernel
        mb = backend.marshal_sets(sets[:5])
        assert not mb.invalid
        prog = self._program(backend)
        handle = prog.dispatch(tuple(mb.args))
        handle.block_until_ready()
        shards = handle.addressable_shards
        ref = np.asarray(shards[0].data)
        for s in shards[1:]:
            assert np.asarray(s.data).tobytes() == ref.tobytes()
        v = prog.resolve(handle)
        assert v.shape == (8,) and v.all()

    def test_real_invalid_localizes_inside_the_envelope(self, material):
        from lighthouse_tpu.crypto.bls.api import SignatureSet
        from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

        sks, pks, sets = material
        bad = list(sets)
        bad[5] = SignatureSet(sks[5].sign(b"other"), [pks[5]], b"probe-5")
        backend = JaxBackend()
        mb = backend.marshal_sets(bad)
        prog = self._program(backend)
        v = prog.verdict_vector(tuple(mb.args))
        bounds = prog.shard_bounds(8)
        assert list(v) == [not (lo <= 5 < hi) for lo, hi in bounds]
