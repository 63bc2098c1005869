"""The main path's Pallas kernels compile for a v5e chip.

The chip is described, not attached (``jax.experimental.topologies``):
the TPU compiler installed here compiles for it and refuses what the chip
would refuse — unaligned slices, over-budget VMEM, zero-sized vectors —
which interpret mode lets through (the r5 ``_wide_square`` i=25 bug).
Each compiled program must hold a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and the test workers each import
every test file.  The persistent compile cache is off around these
tests, since a program compiled for a described chip cannot be read back
without one.  The fused Miller step kernels and the whole verify kernel
take minutes to compile here, so they are marked slow.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lighthouse_tpu.crypto.bls.jax_backend import fp as F
from lighthouse_tpu.crypto.bls.jax_backend import pallas_fp as PF

pytestmark = pytest.mark.compile


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _limbs(one_chip, B):
    return jax.ShapeDtypeStruct((F.N, B), jnp.uint32, sharding=one_chip)


def _compiled_text(fn, *shapes) -> str:
    t0 = time.perf_counter()
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    print(f"compiled in {time.perf_counter() - t0:.1f} s")
    return text


@pytest.mark.parametrize("B", [512, 1024])
def test_mont_mul_compiles_for_v5e(one_chip, B):
    x = _limbs(one_chip, B)
    text = _compiled_text(
        lambda a, b: PF.mont_mul_limbs(a, b, interpret=False, mxu=False),
        x, x)
    assert "tpu_custom_call" in text


def test_fermat_megachain_compiles_for_v5e(one_chip):
    text = _compiled_text(
        lambda a: PF.pow_chain_limbs(a, F.P_INT - 2, interpret=False,
                                     mxu=False),
        _limbs(one_chip, 512))
    assert "tpu_custom_call" in text


def test_fp2_sqrt_chain_compiles_for_v5e(one_chip):
    from lighthouse_tpu.crypto.bls.jax_backend import h2c

    x = _limbs(one_chip, 512)
    text = _compiled_text(
        lambda a0, a1: PF.fp2_pow_chain(a0, a1, tuple(h2c._SQRT_EXP_BITS),
                                        interpret=False, mxu=False),
        x, x)
    assert "tpu_custom_call" in text


@pytest.mark.slow
@pytest.mark.parametrize("step", ["dbl", "add"])
def test_fused_miller_step_compiles_for_v5e(one_chip, step):
    from lighthouse_tpu.crypto.bls.jax_backend import pallas_miller as PM

    call = (PM._dbl_call if step == "dbl" else PM._add_call)(1024, 512,
                                                            False)
    n_lanes = PM._F12 + PM._TPT + (2 if step == "dbl" else 4 + 2)
    shapes = [_limbs(one_chip, 1024)] * n_lanes
    if step == "add":
        shapes.append(jax.ShapeDtypeStruct((1, 1024), jnp.uint32,
                                           sharding=one_chip))
    shapes += [_limbs(one_chip, 512)] * PM.N_CONSTS
    assert "tpu_custom_call" in _compiled_text(call, *shapes)


@pytest.mark.slow
def test_verify_kernel_compiles_for_v5e(one_chip, monkeypatch):
    """The whole device-h2c batch kernel at B=64, as a TPU process
    stages it: the routing gates and interpret choices read
    ``jax.default_backend()``, so the test makes it answer "tpu"."""
    from lighthouse_tpu.crypto.bls import api
    from lighthouse_tpu.crypto.bls.jax_backend import backend as BK

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(F, "_PALLAS_MODE", None)
    sk = api.SecretKey(7)
    s = api.SignatureSet(sk.sign(b"m" * 32), [sk.public_key()], b"m" * 32)
    mb = BK.JaxBackend(batch_sizes=(64,), device_h2c=True).marshal_sets([s])
    shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        mb.args)
    assert "tpu_custom_call" in _compiled_text(BK._verify_kernel_h2c,
                                               *shapes)
