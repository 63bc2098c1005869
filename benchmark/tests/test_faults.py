"""Whole runs on the CPU at a tiny size, past the harness's look for a chip,
with the path under test broken on purpose: ``correct`` must come out
false.  The system verifies on its pure-Python backend here; on the chip
the same faults sit in the same rungs.

* an answer altered where it is produced (the device rung),
* half of a batch's answers left out,
* the control: batch verification with every weight 1, the step that
  would save the weights' scalar multiplications, which only the
  cancelling pair of poisoned sets can tell from the real thing.
"""

import pytest

from benchmark import run as R

pytest.importorskip("lighthouse_tpu")


def tiny_cell(tiny):
    config, mix = tiny("gossip-attestation", "hot-steady")
    config["service"] = dict(config["service"], compiled_sizes=[8],
                             bls_backend="python")
    mix["rate_sets_per_s"] = 10
    names = ("sets_per_s", "latency_p50_ms", "setup_s")
    return R.Cell("tiny", 1, config, mix,
                  [{"name": n, "unit": "u"} for n in names], [])


@pytest.fixture(autouse=True)
def no_chip(monkeypatch):
    """Past the look for a chip: the run goes on as if it had found one."""
    monkeypatch.setattr(R, "find_devices", lambda chips: (
        {"platform": "cpu", "kind": "cpu", "count": 1}, None))


def run(tiny, seed=4):
    return R.run(tiny_cell(tiny), seed, 4.0, False)


def test_sound_run_is_correct(tiny):
    doc = run(tiny)
    assert doc["correct"], doc["checks"]
    assert doc["attempted"] > 0 and doc["failed"] == 0
    assert list(doc)[-1] == "checks"


def test_answer_altered_where_produced(tiny, monkeypatch):
    from lighthouse_tpu.beacon import processor

    real = processor.ResilientVerifier._device_or_cpu

    def flipped(self, items, budget):
        out = real(self, items, budget)
        return [not out[0]] + out[1:]

    monkeypatch.setattr(processor.ResilientVerifier, "_device_or_cpu", flipped)
    doc = run(tiny)
    assert not doc["correct"]
    assert doc["checks"]["wrong_verdicts"]["value"] > 0


def test_half_of_the_batch_left_out(tiny, monkeypatch):
    from lighthouse_tpu.beacon import processor

    real = processor.ResilientVerifier.verify_batch

    def half(self, sets):
        out = real(self, sets)
        keep = max(1, len(out.verdicts) // 2)
        return processor.BatchOutcome(out.verdicts[:keep], out.device_calls)

    monkeypatch.setattr(processor.ResilientVerifier, "verify_batch", half)
    doc = run(tiny)
    assert not doc["correct"]


def test_control_unweighted_batch_is_caught(tiny, monkeypatch):
    from lighthouse_tpu.crypto.bls import api

    class OneBits:
        @staticmethod
        def randbits(_k):
            return 1

    monkeypatch.setattr(api, "secrets", OneBits)
    doc = run(tiny)
    assert not doc["correct"]
    assert doc["checks"]["wrong_verdicts"]["value"] >= 2
