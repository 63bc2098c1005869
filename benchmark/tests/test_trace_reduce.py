"""Trace reduction: busy union, executions, idle gaps by host span."""

from types import SimpleNamespace as NS

import pytest

from benchmark import trace_reduce as TR


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def profile(ops, modules, marker_at=1_000):
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=modules), NS(name="XLA Ops", events=ops)])
    host = NS(name="/host:CPU", lines=[
        NS(name="python", events=[ev(TR.MARKER, marker_at, 10)])])
    return NS(planes=[host, dev])


def test_busy_is_the_union_clipped_to_the_window():
    # window [1000, 11000) ns; ops overlap and straddle the edges
    ops = [ev("a", 500, 1_000), ev("b", 1_200, 1_000), ev("c", 2_000, 500),
           ev("a", 6_000, 1_000), ev("d", 10_500, 2_000)]
    mods = [ev("jit__verify_kernel_h2c(7)", 900, 1_700),
            ev("jit_call_exported(8)", 6_000, 1_000),
            ev("jit__verify_kernel_h2c(7)", 10_500, 2_000),
            ev("jit_convert_element_type(9)", 7_000, 10)]
    out = TR.reduce_profile(profile(ops, mods), 0.0, 0.0, 10e-6)
    # [1000,2500) + [6000,7000) + [10500,11000) = 3000 ns
    assert out["busy_s"] == pytest.approx(3e-6)
    assert out["window_s"] == pytest.approx(10e-6)
    assert out["executions"] == 2          # verify programs starting inside
    assert sum(n for _m, n in out["modules"]) == 3
    assert dict(out["device_ops"])["a"] == pytest.approx(1.5e-6)
    assert TR.idle_share(out) == pytest.approx(70.0)


def test_idle_gaps_are_labelled_by_the_open_host_span():
    ops = [ev("k", 1_000, 1_000), ev("k", 5_000, 1_000)]
    mods = [ev("m", 1_000, 1_000), ev("m", 5_000, 1_000)]
    # marker at trace 1000 ns = monotonic 0 s; span covers [3000, 4500) ns
    spans = [{"name": "ingest.marshal", "start": 2e-6, "end": 3.5e-6}]
    out = TR.reduce_profile(profile(ops, mods), 0.0, 0.0, 7e-6, spans)
    gaps = dict(out["idle_gaps"])
    assert gaps["ingest.marshal"] == pytest.approx(3e-6)   # gap [2000,5000)
    assert gaps["no span"] == pytest.approx(2e-6)          # gap [6000,8000)


def test_no_marker_is_an_error():
    p = profile([], [])
    p.planes[0].lines[0].events = []
    with pytest.raises(ValueError):
        TR.reduce_profile(p, 0.0, 0.0, 1.0)


def test_recorded_chip_trace():
    """A trace recorded on a v5e: a marker, then three executions of one
    small jitted program (a copy and a fusion each)."""
    import os

    path = os.path.join(os.path.dirname(__file__), "fixtures", "small.xplane.pb")
    # the marker at monotonic 0; the window runs 10 ms from it
    out = TR.reduce(path, 0.0, 0.0, 0.010)
    assert out["devices"] == 1
    # the first execution lies ~1 ms before the marker (device and host
    # clocks agree to about a millisecond), the other two inside; the
    # program is not a verify program
    assert [n for _m, n in out["modules"]] == [2]
    assert out["executions"] == 0
    ops = dict(out["device_ops"])
    assert set(ops) == {"%fusion", "%copy-start", "%copy-done"}
    assert out["busy_s"] == pytest.approx(sum(ops.values()))
    assert 3.5e-6 < out["busy_s"] < 4e-6
    assert out["window_s"] == pytest.approx(0.010)
    assert dict(out["idle_gaps"])["no span"] > 0.0099


def test_flushes_are_weighed_by_their_share_inside_the_window(monkeypatch):
    # window [1000, 21000) ns; dispatch spans in monotonic s from the marker
    ops = [ev("k", 2_000, 1_000), ev("k", 4_000, 1_000), ev("x", 5_500, 100),
           ev("k", 12_000, 2_000), ev("k", 19_000, 1_000), ev("k", 21_500, 1_000)]
    mods = [ev("jit_call_exported(1)", 2_000, 1_000),
            ev("jit_call_exported(1)", 4_000, 1_000),
            ev("jit_convert(2)", 5_500, 100),
            ev("jit__verify_kernel(3)", 12_000, 2_000),
            ev("jit_call_exported(1)", 19_000, 1_000),
            ev("jit_call_exported(1)", 21_500, 1_000)]
    spans = [{"name": "serve.dispatch", "start": 0.5e-6, "end": 5e-6},
             {"name": "serve.dispatch", "start": 10e-6, "end": 14e-6},
             {"name": "serve.dispatch", "start": 17e-6, "end": 23e-6},
             {"name": "ingest.marshal", "start": 9e-6, "end": 10e-6}]
    monkeypatch.setattr(TR, "CLOCK_SLACK_NS", 0)
    out = TR.reduce_profile(profile(ops, mods), 0.0, 0.0, 20e-6, spans)
    # the third dispatch [18000, 24000) has half of itself inside the
    # window, one of its two calls and 1000 ns of its busy time
    assert out["flushes"] == [[2, pytest.approx(2.1e-6), 1.0],
                              [1, pytest.approx(2e-6), 1.0],
                              [1, pytest.approx(1e-6), 0.5]]
    assert TR.per_flush(out, 0) == pytest.approx(4 / 2.5)
    assert TR.per_flush(out, 1) == pytest.approx(5.1e-6 / 2.5)
    assert TR.per_flush({"flushes": [[0, 1e-3, 1.0]]}, 1) is None
    assert TR.per_flush(None, 0) is None
