"""The fresh-key gossip cell's traffic, at its committed size: keys drawn
from the whole 1,048,576-validator registry reach the service unwarmed,
and verdicts known by construction still hold."""

from benchmark import bls_ref as B, traffic as T


def test_fresh_steady_sends_every_key_fresh():
    config = T.load("configs", "gossip-attestation-mainnet")
    mix = T.load("traffic", "fresh-steady")
    assert config["validators"] == config["published"]["validators"] == 1 << 20
    assert config["reduced"] == []
    tr = T.build(config, mix, 2**31 + 11, 4.0)
    assert tr.warmup == []
    sets = [s for sub in tr.submissions for s in sub.sets]
    keys = [k for s in sets for k in s.keys]
    # committees drawn from 2^20 validators: keys almost never repeat
    assert len(set(keys)) >= 0.95 * len(keys)
    assert len(tr.pubkeys) == len(set(keys))
    check = [s for s in sets if s.poison] + [s for s in sets if not s.poison][:3]
    assert any(s.poison for s in check)
    cache = {}
    for s in check:
        got = B.verify_set(s.sig, [tr.pubkeys[i] for i in s.keys], s.msg, cache)
        assert got == s.expected, s.poison


def test_fresh_and_hot_steady_differ_only_in_where_keys_come_from():
    hot = T.load("traffic", "hot-steady")
    fresh = T.load("traffic", "fresh-steady")
    assert fresh.pop("warm_keys") == "none" and "warm_keys" not in hot
    assert fresh == hot
