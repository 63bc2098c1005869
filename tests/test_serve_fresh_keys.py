"""Fresh public keys on the served path: the edge decompresses without
the subgroup check, the flush validates the keys on the device, and a
key outside G1 fails its own set only.

The key outside G1 is an honest key plus a point of order 3: with the
honest signature it passes the pairing check, so only key validation
tells it apart.  The device rung is stubbed (every set it is handed
verifies), so a ``false`` can only come from the key check; the key
check itself is the real device program, compiled at 8 lanes.
"""

import json
import types
import urllib.error
import urllib.request

import pytest

pytest.importorskip("jax")

from lighthouse_tpu.crypto.bls import api
from lighthouse_tpu.crypto.bls.api import PublicKey, SecretKey, SignatureSet
from lighthouse_tpu.crypto.bls.curve import (
    G1_GENERATOR, Fp, affine_add, affine_neg, g1_to_bytes,
)
from lighthouse_tpu.crypto.bls.hash_to_curve import hash_to_g2
from lighthouse_tpu.crypto.bls.jax_backend.backend import JaxBackend
from lighthouse_tpu.crypto.bls.pairing import pairing_check
from lighthouse_tpu.serve import ServeApiServer, VerifyService
from lighthouse_tpu.utils import metrics as M

pytestmark = pytest.mark.compile

MSG = b"fresh-key-attestation-data-root!"


def _outside_g1(sk: SecretKey) -> bytes:
    """``sk``'s key plus the order-3 point (0, 2): on the curve, not in G1."""
    return g1_to_bytes(affine_add(sk.public_key().point, (Fp(0), Fp(2)), Fp))


def _wire(sk: SecretKey, key: bytes | None = None) -> dict:
    return {"signature": "0x" + sk.sign(MSG).to_bytes().hex(),
            "pubkeys": ["0x" + (key or sk.public_key().to_bytes()).hex()],
            "message": "0x" + MSG.hex()}


@pytest.fixture(scope="module")
def backend():
    be = JaxBackend(batch_sizes=(8,))
    be.warm_keycheck(8)
    return be


@pytest.fixture
def served(backend):
    handed = []

    def verify_batch(sets):
        handed.extend(sets)
        return types.SimpleNamespace(verdicts=[True] * len(sets))

    svc = VerifyService(types.SimpleNamespace(verify_batch=verify_batch),
                        compiled_sizes=(8,))
    svc.stack = types.SimpleNamespace(backend=backend)
    server = ServeApiServer(svc, port=0).start()
    yield svc, server, handed
    server.stop()
    svc.stop()


def _post(port, doc):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/eth/v1/verify/batch",
        data=json.dumps(doc).encode(), method="POST",
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def _verdicts(svc, server, sets):
    status, doc = _post(server.port, {"tenant": "t", "sets": sets})
    assert status == 202, doc
    svc.flush()
    return svc.result(doc["data"]["request_id"])["verdicts"]


def test_a_key_outside_g1_fails_its_set_only(served):
    svc, server, handed = served
    sks = [SecretKey(500 + i) for i in range(3)]
    bad = _outside_g1(sks[1])
    before = M.SERVE_KEYS_REJECTED.value()
    got = _verdicts(svc, server, [_wire(sks[0]), _wire(sks[1], bad),
                                  _wire(sks[2])])
    assert got == [True, False, True]
    assert len(handed) == 2            # the bad set never entered the batch
    assert M.SERVE_KEYS_REJECTED.value() == before + 1


def test_bytes_that_name_no_point_still_get_400(served):
    _svc, server, _handed = served
    sk = SecretKey(600)
    x = 1
    while Fp(x * x * x + 4).sqrt() is not None:   # an x off the curve
        x += 1
    off_curve = bytes([0x80]) + x.to_bytes(47, "big")
    infinity = bytes([0xC0]) + bytes(47)
    for key in (off_curve, infinity):
        status, doc = _post(server.port, {"tenant": "t",
                                          "sets": [_wire(sk, key)]})
        assert status == 400, doc


def test_a_repeated_key_is_checked_once(served):
    svc, server, _handed = served
    sk = SecretKey(700)
    before = M.SERVE_KEYS_CHECKED_DEVICE.value()
    misses = M.SERVE_DECODE_MISSES.value()
    assert _verdicts(svc, server, [_wire(sk)]) == [True]
    assert _verdicts(svc, server, [_wire(sk), _wire(sk)]) == [True, True]
    assert M.SERVE_KEYS_CHECKED_DEVICE.value() == before + 1
    assert M.SERVE_DECODE_MISSES.value() == misses + 1


def test_the_negative_cache_holds_a_rejected_key(served):
    svc, server, handed = served
    sk = SecretKey(800)
    bad = _outside_g1(sk)
    assert _verdicts(svc, server, [_wire(sk, bad)]) == [False]
    checked = M.SERVE_KEYS_CHECKED_DEVICE.value()
    assert _verdicts(svc, server, [_wire(sk, bad)]) == [False]
    assert M.SERVE_KEYS_CHECKED_DEVICE.value() == checked
    assert bad in server.pubkeys._rejected and bad not in server.pubkeys._keys
    assert server.pubkeys.decode(bad) is server.pubkeys._rejected[bad]
    assert not handed


def test_the_cpu_rung_validates_unchecked_keys():
    """The CPU rung (``cpu_backend().verify_signature_sets``, the
    ladder's ``cpu_verify``) subgroup-checks a key decoded unchecked:
    without that, the pairing alone accepts the set."""
    sk = SecretKey(900)
    bad = PublicKey.from_bytes_unchecked(_outside_g1(sk))
    s = SignatureSet(sk.sign(MSG), [bad], MSG)
    assert s.keys_unvalidated
    assert pairing_check([(affine_neg(G1_GENERATOR), s.signature.point),
                          (bad.point, hash_to_g2(MSG))])
    cpu = api.cpu_backend()
    assert cpu.verify_signature_sets([s]) is False
    assert bad.subgroup_checked and not bad.subgroup_check()
    good = PublicKey.from_bytes_unchecked(sk.public_key().to_bytes())
    assert cpu.verify_signature_sets([SignatureSet(sk.sign(MSG), [good], MSG)])
