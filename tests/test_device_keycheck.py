"""The device public-key check (``_g1_keycheck_kernel``) against the plain
reference, and what the service does when the check cannot be trusted.

Keys are built and judged by ``benchmark/bls_ref.py``, which imports
nothing of the program: its ``g1_decompress`` accepts exactly the G1
members.  The program decodes each key without the subgroup check and
asks the device.  One compile of the smallest size serves the module.
"""

import random
import types

import numpy as np
import pytest

pytest.importorskip("jax")

from benchmark import bls_ref as R
from lighthouse_tpu.crypto.bls import params
from lighthouse_tpu.crypto.bls.api import PublicKey, SecretKey, SignatureSet
from lighthouse_tpu.crypto.bls.curve import g1_subgroup_check_slow
from lighthouse_tpu.crypto.bls.jax_backend import backend as BK
from lighthouse_tpu.serve import VerifyService
from lighthouse_tpu.utils import metrics as M

pytestmark = pytest.mark.compile

SIZE = 8  # lanes per key-check call: 6 keys and the 2 known answers


def _keys(seed: int = 0x6E1):
    """(compressed, member) for 16 members and 8 on-curve non-members:
    5 of full order, both points of order 3 (x = 0) and one of order 11
    (both small orders make the ladder's fast add meet +-acc)."""
    rng = random.Random(seed)
    out = [(R.g1_compress(R.g1_affine(R.g1_mul(R.G1_GEN,
                                                rng.randrange(1, R.R)))),
            True) for _ in range(16)]
    odd = []
    while len(odd) < 5:
        x = rng.randrange(R.P)
        y = R.fp_sqrt((x * x * x + R.B1) % R.P)
        if y is not None:
            odd.append((x, y))
    n_e = params.R * params.H1            # 11^2 divides the curve's order
    o11 = R.g1_mul(R.g1_jac(odd[0]), n_e // 121)
    while R.g1_mul(o11, 11)[2] != 0:
        o11 = R.g1_mul(o11, 11)
    odd += [(0, 2), (0, R.P - 2), R.g1_affine(o11)]
    out += [(R.g1_compress(p), False) for p in odd]
    rng.shuffle(out)
    return out


def _reference(raw: bytes) -> bool:
    try:
        return R.g1_decompress(raw) is not None
    except R.DecodeError:
        return False


@pytest.fixture(scope="module")
def backend():
    be = BK.JaxBackend(batch_sizes=(SIZE,))
    be.warm_keycheck(SIZE)
    return be


def test_device_keycheck_agrees_with_the_reference(backend):
    keys = _keys()
    want = [_reference(raw) for raw, _planted in keys]
    assert want == [planted for _raw, planted in keys]
    pks = [PublicKey.from_bytes_unchecked(raw) for raw, _ in keys]
    assert not any(pk.subgroup_checked for pk in pks)
    assert [g1_subgroup_check_slow(pk.point) for pk in pks] == want
    got, lanes = backend.g1_keycheck([pk.point for pk in pks])
    assert got == want
    assert lanes == SIZE * -(-len(keys) // (SIZE - 2))
    assert backend.keycheck_size == SIZE


def _mixed(seed, members, non_members):
    keys = _keys(seed)
    return ([k for k in keys if k[1]][:members]
            + [k for k in keys if not k[1]][:non_members])


def _service(backend):
    seen = []

    def verify_batch(sets):
        seen.append(list(sets))
        return types.SimpleNamespace(verdicts=[True] * len(sets))

    svc = VerifyService(types.SimpleNamespace(verify_batch=verify_batch),
                        compiled_sizes=(SIZE,))
    svc.stack = types.SimpleNamespace(backend=backend)
    return svc, seen


def _sets(raws):
    sk = SecretKey(41)
    sig = sk.sign(b"k" * 32)
    return [SignatureSet(sig, [PublicKey.from_bytes_unchecked(r)], b"k" * 32)
            for r in raws]


def test_a_flush_checks_its_fresh_keys_once_on_the_device(backend):
    keys = _mixed(3, 3, 2)
    svc, _seen = _service(backend)
    sets = _sets([raw for raw, _ in keys])
    before = M.SERVE_KEYS_CHECKED_DEVICE.value()
    rejected = svc._validate_keys(sets)
    assert rejected == {i for i, (_r, ok) in enumerate(keys) if not ok}
    assert M.SERVE_KEYS_CHECKED_DEVICE.value() - before == 5
    assert svc._validate_keys(sets) == rejected      # kept on the keys
    assert M.SERVE_KEYS_CHECKED_DEVICE.value() - before == 5


def _lying(lanes):
    return np.ones(SIZE, dtype=bool)


def _failing(lanes):
    raise RuntimeError("device lost")


@pytest.mark.parametrize("kernel", [_lying, _failing])
def test_a_lying_or_failing_key_check_falls_back_to_the_host(
        backend, monkeypatch, kernel):
    """A known-answer lane that reads wrong (here the non-member lane
    reads True) or a call that fails: the flush's keys are checked on
    the host, and the batch counts as degraded."""
    monkeypatch.setitem(backend._kernels, ("g1check", SIZE), kernel)
    keys = _mixed(5, 4, 2)
    svc, seen = _service(backend)
    for i, s in enumerate(_sets([raw for raw, _ in keys])):
        assert svc.submit(f"t{i}", [s]).accepted
    degraded = M.VERIFY_DEGRADED_BATCHES.value()
    on_device = M.SERVE_KEYS_CHECKED_DEVICE.value()
    svc.flush()
    assert M.VERIFY_DEGRADED_BATCHES.value() == degraded + 1
    assert M.SERVE_KEYS_CHECKED_DEVICE.value() == on_device
    got = [svc.result(f"r{i + 1:08d}")["verdicts"] for i in range(6)]
    assert got == [[ok] for _raw, ok in keys]
    assert sum(len(b) for b in seen) == sum(ok for _raw, ok in keys)
