"""The generator: verdicts known by construction hold, and every seed gets
the same work in another order."""

import pytest

from benchmark import bls_ref as B, traffic as T

CELLS = [("gossip-attestation", "hot-steady"),
         ("aggregate-attestation", "saturate")]


@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_verdicts_by_construction_match_the_reference(tiny, config_name, mix_name):
    config, mix = tiny(config_name, mix_name)
    tr = T.build(config, mix, 2**31 + 7, 4.0)
    sets = [s for sub in tr.submissions for s in sub.sets]
    sets += [s for sub in tr.warmup for s in sub.sets]
    poisoned = [s for s in sets if s.poison]
    assert sorted(s.poison for s in poisoned) == [
        "cancelling_pair", "cancelling_pair", "outside_signer", "wrong_message"]
    check = poisoned + [s for s in sets if not s.poison][:6]
    cache = {}
    for s in check:
        keys = [tr.pubkeys[i] for i in s.keys]
        assert B.verify_set(s.sig, keys, s.msg, cache) == s.expected, s.poison


@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_the_program_agrees_with_the_construction(tiny, config_name, mix_name):
    """A second witness: the system's own pure-Python backend."""
    api = pytest.importorskip("lighthouse_tpu.crypto.bls.api")
    config, mix = tiny(config_name, mix_name)
    tr = T.build(config, mix, 99, 4.0)
    sets = [s for sub in tr.submissions for s in sub.sets]
    check = [s for s in sets if s.poison] + [s for s in sets if not s.poison][:4]
    cpu = api.cpu_backend()
    for s in check:
        ss = api.SignatureSet(api.Signature.from_bytes(s.sig),
                              [api.PublicKey.from_bytes(tr.pubkeys[i]) for i in s.keys],
                              s.msg)
        assert bool(cpu.verify_signature_sets([ss])) == s.expected, s.poison


@pytest.mark.parametrize("config_name,mix_name", CELLS)
def test_seeds_share_the_shape_of_the_work(tiny, config_name, mix_name):
    config, mix = tiny(config_name, mix_name)
    a = T.plan(config, mix, 1, 6.0)
    b = T.plan(config, mix, 2**31 + 5, 6.0)
    sizes = [sorted(len(sids) for _t, _d, sids in p[1]) for p in (a, b)]
    assert sizes[0] == sizes[1]
    if mix["loop"] == "open":
        assert a[1][-1][1] < 6.0 and b[1][-1][1] < 6.0
        assert a[1] != b[1]
    assert a[4] != b[4]                        # different keys


def test_same_seed_same_bytes(tiny):
    config, mix = tiny("gossip-attestation", "hot-steady")
    one = T.build(config, mix, 5, 3.0)
    two = T.build(config, mix, 5, 3.0)
    assert one.pubkeys == two.pubkeys
    assert [[(s.sig, s.keys, s.msg) for s in sub.sets] for sub in one.submissions] == \
           [[(s.sig, s.keys, s.msg) for s in sub.sets] for sub in two.submissions]
