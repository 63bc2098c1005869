"""A configuration, a mix and a metric are files found by name: adding one
needs no edit to any file that is there."""

import json
import shutil

import pytest

from benchmark import bls_ref as B, run as R, traffic as T
from benchmark.tests.conftest import shrink

HERE = R.HERE


def test_new_files_are_found_by_name(tmp_path):
    bench = json.loads(open(f"{HERE}/../BENCHMARK.json").read())
    root = tmp_path / "benchmark"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(".cache", "tests"))
    config = json.loads((root / "configs" / "gossip-attestation.json").read_text())
    config["name"] = "gossip-new"
    (root / "configs" / "gossip-new.json").write_text(json.dumps(config))
    mix = json.loads((root / "traffic" / "hot-steady.json").read_text())
    mix["rate_sets_per_s"] = 123
    (root / "traffic" / "new-mix.json").write_text(json.dumps(mix))
    (root / "metrics" / "requests_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx.records))\n")
    bench["configs"].append({"name": "gossip-new", "source": "test",
                             "file": "benchmark/configs/gossip-new.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new-cell", "config": "gossip-new",
                               "traffic": "new-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "requests_seen", "unit": "requests",
                               "better": "higher", "source": "host_clock",
                               "layer": "HTTP edge", "moves": "sets_per_s",
                               "workloads": ["new-cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = R.load_cell("new-cell", str(root))
    assert cell.config["name"] == "gossip-new"
    assert cell.mix["rate_sets_per_s"] == 123
    assert [m["name"] for m in cell.per_layer] == ["requests_seen"]
    assert "latency_p50_ms" not in [m["name"] for m in cell.end_to_end]
    ctx = R.Context(cell, 1.0, 0.0, 0.0, 1.0, [{}, {}], [], (), [], None, 0, 1.0)
    assert R.read_metrics(cell.per_layer, ctx) == {
        "requests_seen": {"value": 2.0, "unit": "requests"}}


def test_a_reader_with_nothing_to_read_is_left_out():
    cell = R.load_cell("gossip-hot-steady")
    ctx = R.Context(cell, 1.0, 0.0, 0.0, 1.0, [], [], (0.1,), [0, 0], None, 0, 1.0)
    out = R.read_metrics(cell.per_layer, ctx)
    assert "device_idle_share.steady" not in out
    assert "device_calls_per_flush" not in out


#: cells a later change could bring as data alone: (name, changes to the
#: gossip configuration, changes to the open-loop mix), each applied to
#: the committed files at a size a CPU test holds
SHAPES = [
    ("slot-bursts", {}, {"arrivals": {"kind": "bursts", "period_s": 2.0, "bursts": [
        {"at_s": 0.5, "width_s": 0.2, "share": 0.8}]}}),
    ("fresh-registry", {"validators": 1 << 20}, {"warm_keys": "none"}),
    ("sync-committee", {"sync_committee_size": 16, "unit": {
        "per_committee": 1, "participation": [0.5, 1.0], "sets": [
            {"role": "sync_aggregate", "signers": "sync_committee", "message": "slot"}]}},
     {"arrivals": {"kind": "uniform"}}),
    ("block-import", {"sync_committee_size": 16, "unit": {
        "per_slot": 1, "participation": [0.75, 1.0], "sets": [
            {"role": "proposal", "signers": "unit_member", "message": "unit"},
            {"role": "randao", "signers": "unit_member", "message": "slot"},
            {"role": "attestation", "signers": "participants",
             "message": "committee", "count": 3},
            {"role": "sync_aggregate", "signers": "sync_committee", "message": "slot"}]}},
     {"submission_units": 1}),
]


@pytest.mark.parametrize("name,config_change,mix_change", SHAPES,
                         ids=[s[0] for s in SHAPES])
def test_new_traffic_shapes_are_data(tmp_path, name, config_change, mix_change):
    """A new kind of traffic is a configuration and a mix file: arrivals
    in slot bursts, keys fresh from a mainnet-size registry, a fixed sync
    committee, a block's mixed set roles.  Found by name, built by the one
    generator, with verdicts the reference confirms."""
    bench = json.loads(open(f"{HERE}/../BENCHMARK.json").read())
    root = tmp_path / "benchmark"
    shutil.copytree(HERE, root, ignore=shutil.ignore_patterns(".cache", "tests"))
    config, mix = shrink(T.load("configs", "gossip-attestation"),
                         T.load("traffic", "hot-steady"))
    config.update(config_change, name=name)
    mix.update(mix_change)
    (root / "configs" / f"{name}.json").write_text(json.dumps(config))
    (root / "traffic" / f"{name}.json").write_text(json.dumps(mix))
    bench["configs"].append({"name": name, "source": "test", "reduced": [],
                             "file": f"benchmark/configs/{name}.json", "why": "test"})
    bench["workloads"].append({"name": name, "config": name, "traffic": name,
                               "chips": 1, "why": "test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = R.load_cell(name, str(root))
    tr = T.build(cell.config, cell.mix, 2**31 + 11, 6.0)
    sets = [s for sub in tr.submissions for s in sub.sets]
    dues = [sub.due for sub in tr.submissions]
    assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 6.0
    assert sum(len(sub.sets) for sub in tr.submissions) >= 12 * 6.0
    if name == "slot-bursts":
        inside = [d for d in dues if 0.5 <= d % 2.0 < 0.7]
        assert len(inside) >= 0.6 * len(dues)
    if name == "fresh-registry":
        assert tr.warmup == []
        used = {i for s in sets for i in s.keys}
        assert used == set(range(len(tr.pubkeys)))
        assert len(tr.pubkeys) < 1 << 10
    if name == "sync-committee":
        assert len({i for s in sets for i in s.keys}) <= 16
    if name == "block-import":
        assert {len(sub.sets) for sub in tr.submissions} == {6}
        assert len({i for sub in tr.submissions for i in sub.sets[5].keys}) <= 16
    check = [s for s in sets if s.poison] + [s for s in sets if not s.poison][-2:]
    assert len([s for s in check if s.poison]) == 4
    cache = {}
    for s in check:
        keys = [tr.pubkeys[i] for i in s.keys]
        assert B.verify_set(s.sig, keys, s.msg, cache) == s.expected, s.poison
