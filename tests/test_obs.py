"""Observability suite: tracer, flight recorder, scrape endpoint, report.

Locks down four surfaces: (1) the ring-buffer tracer (capacity /
oldest-drop accounting, thread safety under concurrent emitters, the
disabled-tracer fast path, parent nesting, error tagging); (2) the
Chrome trace export and the dump / automatic-dump (``maybe_dump``)
artifact mechanics; (3) the stage-attribution math in ``obs.report``
(quantiles, host-vs-device split, overlap efficiency in pipeline /
serial / empty modes) plus ``tools/trace_report.py --check`` over the
recorded fixture; (4) the ``MetricsServer`` endpoints.
"""

import json
import os
import sys
import threading
import time
import urllib.request

import pytest

from lighthouse_tpu.obs import MetricsServer, SPANS
from lighthouse_tpu.obs import report as R
from lighthouse_tpu.obs.tracer import _NOP, Tracer

pytestmark = pytest.mark.obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACE_FIXTURE = os.path.join(REPO, "tests", "fixtures", "trace",
                             "pipeline_trace.json")


# ---------------------------------------------------------------------------
# Tracer ring mechanics
# ---------------------------------------------------------------------------


class TestTracerRing:
    def test_spans_commit_in_order_with_fields(self):
        t = Tracer(capacity=16)
        with t.span("verify.batch", sets=3):
            pass
        t.instant("breaker.transition", state="OPEN")
        recs = t.snapshot()
        assert [r.name for r in recs] == ["verify.batch", "breaker.transition"]
        assert recs[0].fields == (("sets", 3),)
        assert recs[1].fields == (("state", "OPEN"),)
        assert recs[1].dur == 0.0
        assert recs[0].sid < recs[1].sid

    def test_capacity_drops_oldest_and_counts(self):
        t = Tracer(capacity=4)
        for i in range(7):
            t.instant("scenario.slot", slot=i)
        recs = t.snapshot()
        assert len(recs) == 4
        # the *newest* four survive; the oldest three are dropped
        assert [dict(r.fields)["slot"] for r in recs] == [3, 4, 5, 6]
        assert t.dropped == 3

    def test_mark_and_since_sid_isolate_a_window(self):
        t = Tracer(capacity=64)
        t.instant("scenario.slot", slot=0)
        mark = t.mark()
        t.instant("scenario.slot", slot=1)
        t.instant("scenario.slot", slot=2)
        window = t.snapshot(since_sid=mark)
        assert [dict(r.fields)["slot"] for r in window] == [1, 2]
        assert t.mark() > mark

    def test_parent_nesting_and_error_tagging(self):
        t = Tracer(capacity=16)
        with pytest.raises(ValueError):
            with t.span("verify.batch") as outer:
                with t.span("verify.device") as inner:
                    assert inner.parent == outer.sid
                    raise ValueError("boom")
        recs = {r.name: r for r in t.snapshot()}
        assert recs["verify.device"].parent == recs["verify.batch"].sid
        assert recs["verify.batch"].parent == 0
        # the exception is tagged on both spans it unwound through
        assert dict(recs["verify.device"].fields)["error"] == "ValueError"
        assert dict(recs["verify.batch"].fields)["error"] == "ValueError"

    def test_clear_resets_ring_and_dropped(self):
        t = Tracer(capacity=2)
        for _ in range(5):
            t.instant("scenario.slot")
        t.clear()
        assert t.snapshot() == [] and t.dropped == 0 and t.mark() == 0

    def test_add_attaches_fields_before_close(self):
        t = Tracer(capacity=8)
        with t.span("sync.batch", start_slot=1) as sp:
            sp.add(blocks=7)
        (rec,) = t.snapshot()
        assert dict(rec.fields) == {"start_slot": 1, "blocks": 7}


class TestTracerConcurrency:
    def test_no_spans_lost_under_contention(self):
        n_threads, per_thread = 8, 200
        t = Tracer(capacity=n_threads * per_thread)

        def emit(k):
            for i in range(per_thread):
                with t.span("verify.batch", worker=k, i=i):
                    pass

        threads = [
            threading.Thread(target=emit, args=(k,)) for k in range(n_threads)
        ]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=30)
            assert not th.is_alive()
        recs = t.snapshot()
        assert len(recs) == n_threads * per_thread
        assert t.dropped == 0
        sids = [r.sid for r in recs]
        assert len(set(sids)) == len(sids), "span ids must be unique"
        # per-thread parent stacks stay isolated: top-level spans have no parent
        assert all(r.parent == 0 for r in recs)

    def test_disabled_tracer_is_nop_and_cheap(self):
        t = Tracer(capacity=8, enabled=False)
        assert t.span("verify.batch", sets=1) is _NOP
        assert t.instant("breaker.transition") is None
        assert t.snapshot() == []
        # overhead bound (best-of-5 to shrug off CI noise): the disabled
        # path is one attribute test + returning a shared no-op object
        n = 10_000
        best = float("inf")
        for _ in range(5):
            t0 = time.perf_counter()
            for _ in range(n):
                t.span("verify.batch")
            best = min(best, (time.perf_counter() - t0) / n)
        assert best < 1e-6, f"disabled span() cost {best * 1e9:.0f}ns"

    def test_capacity_must_be_positive(self):
        with pytest.raises(ValueError):
            Tracer(capacity=0)


# ---------------------------------------------------------------------------
# Chrome export + dump artifacts
# ---------------------------------------------------------------------------


class TestExport:
    def test_chrome_trace_shape(self):
        t = Tracer(capacity=8)
        with t.span("block.import", slot=9):
            t.instant("breaker.transition", state="OPEN")
        doc = t.chrome_trace()
        assert doc["displayTimeUnit"] == "ms"
        evs = doc["traceEvents"]
        assert [ev["name"] for ev in evs] == [
            "breaker.transition", "block.import",
        ]  # inner instant commits before the enclosing span closes
        for ev in evs:
            assert ev["ph"] == "X" and ev["cat"] == "lighthouse_tpu"
            assert ev["pid"] == os.getpid()
            assert "sid" in ev["args"]
        outer = evs[1]
        assert outer["args"]["slot"] == 9
        assert evs[0]["args"]["parent"] == outer["args"]["sid"]
        assert outer["dur"] >= 0.0

    def test_dump_roundtrips_and_counts(self, tmp_path):
        from lighthouse_tpu.utils.metrics import TRACE_DUMPS

        t = Tracer(capacity=8)
        t.instant("scenario.slot", slot=1)
        before = TRACE_DUMPS.value()
        path = t.dump(str(tmp_path / "trace.json"))
        assert TRACE_DUMPS.value() == before + 1
        doc = json.loads(open(path).read())
        assert [ev["name"] for ev in doc["traceEvents"]] == ["scenario.slot"]
        assert not os.path.exists(path + ".tmp")

    def test_maybe_dump_disabled_without_dir(self):
        t = Tracer(capacity=8)
        t.instant("scenario.slot")
        assert t.maybe_dump("unit") is None

    def test_maybe_dump_writes_deterministic_names_and_rate_limits(
        self, tmp_path
    ):
        t = Tracer(capacity=8)
        t.configure_dump_dir(str(tmp_path))
        t.instant("scenario.slot")
        paths = [t.maybe_dump("breaker-open") for _ in range(12)]
        written = [p for p in paths if p]
        assert len(written) == t._dump_limit == 8
        assert [os.path.basename(p) for p in written[:2]] == [
            "trace-breaker-open-001.json", "trace-breaker-open-002.json",
        ]
        # a different reason has its own counter
        assert os.path.basename(t.maybe_dump("slo-smoke")) == (
            "trace-slo-smoke-001.json"
        )

    def test_maybe_dump_never_raises(self, tmp_path):
        t = Tracer(capacity=8)
        t.instant("scenario.slot")
        # unwritable target: a *file* where the dump dir should be
        blocker = tmp_path / "blocked"
        blocker.write_text("x")
        t.configure_dump_dir(str(blocker))
        assert t.maybe_dump("unit") is None  # swallowed, logged

    def test_env_dir_fallback(self, tmp_path, monkeypatch):
        monkeypatch.setenv("LIGHTHOUSE_TPU_TRACE_DIR", str(tmp_path))
        t = Tracer(capacity=8)
        t.instant("scenario.slot")
        p = t.maybe_dump("env")
        assert p and os.path.dirname(p) == str(tmp_path)


# ---------------------------------------------------------------------------
# Attribution math (obs.report)
# ---------------------------------------------------------------------------


def _ev(name, ts_us, dur_us, **args):
    return {"name": name, "ts": ts_us, "dur": dur_us, "args": args}


class TestReportMath:
    def test_stage_stats_quantiles(self):
        evs = [_ev("verify.batch", i * 100, d)
               for i, d in enumerate([1e3, 2e3, 3e3, 4e3])]
        st = R.stage_stats(evs)["verify.batch"]
        assert st["count"] == 4
        assert st["total_s"] == pytest.approx(0.01)
        assert st["p50_s"] == pytest.approx(0.003)  # nearest-rank on 4 vals
        assert st["p99_s"] == pytest.approx(0.004)

    def test_host_device_share(self):
        evs = [
            _ev("pipeline.marshal", 0, 3e6),
            _ev("pipeline.resolve", 0, 1e6),
            _ev("scenario.slot", 0, 10e6),  # structural: neither bucket
        ]
        share = R.host_device_share(evs)
        assert share["host_s"] == pytest.approx(3.0)
        assert share["device_s"] == pytest.approx(1.0)
        assert share["other_s"] == pytest.approx(10.0)
        assert share["host_share"] == pytest.approx(0.75)

    def test_overlap_pipeline_mode(self):
        # marshal busy 2.0s, device busy 2.0s, wall 2.2s -> ratio 1.1
        evs = [
            _ev("pipeline.marshal", 0, 1e6),
            _ev("pipeline.marshal", 1.0e6, 1e6),
            _ev("pipeline.dispatch", 0.2e6, 0.5e6),
            _ev("pipeline.resolve", 0.7e6, 1.5e6),
        ]
        ov = R.overlap_efficiency(evs)
        assert ov["mode"] == "pipeline"
        assert ov["wall_s"] == pytest.approx(2.2)
        assert ov["ratio"] == pytest.approx(1.1)

    def test_overlap_serial_fallback_and_empty(self):
        evs = [
            _ev("verify.batch", 0, 2e6),
            _ev("verify.device", 0.1e6, 1.5e6),
        ]
        ov = R.overlap_efficiency(evs)
        assert ov["mode"] == "serial"
        assert ov["ratio"] == pytest.approx(2.0 / 1.5)
        assert R.overlap_efficiency([])["mode"] == "empty"
        assert R.overlap_efficiency([])["ratio"] is None

    def test_compile_events_strip_ids(self):
        evs = [_ev("jit.compile", 0, 2.5e6,
                   fingerprint="abc123", kernel="_verify_kernel",
                   sid=4, parent=2)]
        (c,) = R.compile_events(evs)
        assert c == {"seconds": 2.5, "fingerprint": "abc123",
                     "kernel": "_verify_kernel"}

    def test_unknown_names_against_registry(self):
        evs = [_ev("verify.batch", 0, 1), _ev("bogus.stage", 0, 1)]
        assert R.unknown_names(evs, SPANS) == ["bogus.stage"]

    def test_attribution_bundles_everything(self):
        rep = R.attribution([_ev("verify.batch", 0, 1e6)])
        assert set(rep) == {"stages", "share", "overlap", "compiles", "events"}
        assert rep["events"] == 1


# ---------------------------------------------------------------------------
# tools/trace_report.py over the recorded fixture
# ---------------------------------------------------------------------------


def _trace_report_main(argv):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import trace_report
    finally:
        sys.path.pop(0)
    return trace_report.main(argv)


class TestTraceReportTool:
    def test_check_passes_on_recorded_fixture(self, capsys):
        assert _trace_report_main(["--check", TRACE_FIXTURE]) == 0
        assert "CHECK OK" in capsys.readouterr().out

    def test_fixture_attributes_real_pipeline_stages(self):
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import trace_report
        finally:
            sys.path.pop(0)
        events = trace_report.load_events(TRACE_FIXTURE)
        rep = R.attribution(events)
        assert rep["overlap"]["mode"] == "pipeline"
        for stage in ("pipeline.marshal", "pipeline.dispatch",
                      "pipeline.resolve", "verify.batch"):
            assert stage in rep["stages"], stage
        assert not R.unknown_names(events, SPANS)

    def test_check_fails_on_unknown_stage(self, tmp_path, capsys):
        bad = tmp_path / "bad.json"
        bad.write_text(json.dumps({"traceEvents": [
            {"name": "rogue.stage", "ts": 0, "dur": 1, "ph": "X"},
        ]}))
        assert _trace_report_main(["--check", str(bad)]) == 1
        assert "rogue.stage" in capsys.readouterr().err

    def test_check_fails_on_empty_and_corrupt(self, tmp_path, capsys):
        empty = tmp_path / "empty.json"
        empty.write_text(json.dumps({"traceEvents": []}))
        assert _trace_report_main(["--check", str(empty)]) == 1
        corrupt = tmp_path / "corrupt.json"
        corrupt.write_text("{not json")
        assert _trace_report_main(["--check", str(corrupt)]) == 1
        malformed = tmp_path / "malformed.json"
        malformed.write_text(json.dumps({"traceEvents": [{"ts": 0}]}))
        assert _trace_report_main(["--check", str(malformed)]) == 1

    def test_human_and_json_modes(self, capsys):
        assert _trace_report_main([TRACE_FIXTURE]) == 0
        human = capsys.readouterr().out
        assert "overlap efficiency" in human
        assert _trace_report_main(["--json", TRACE_FIXTURE]) == 0
        doc = json.loads(capsys.readouterr().out)
        assert "stages" in doc and "overlap" in doc


# ---------------------------------------------------------------------------
# Instrumentation integration: real code paths emit registered spans
# ---------------------------------------------------------------------------


class TestInstrumentation:
    def test_resilient_verifier_emits_ladder_spans(self):
        from lighthouse_tpu.beacon.processor import ResilientVerifier
        from lighthouse_tpu.obs.tracer import TRACER

        rv = ResilientVerifier(
            device_verify=lambda sets: True,
            cpu_verify=lambda sets: True,
        )
        mark = TRACER.mark()
        assert all(rv.verify_batch([object(), object()]).verdicts)
        names = [r.name for r in TRACER.snapshot(since_sid=mark)]
        assert "verify.batch" in names and "verify.device" in names
        rec = next(r for r in TRACER.snapshot(since_sid=mark)
                   if r.name == "verify.batch")
        assert dict(rec.fields)["sets"] == 2

    def test_all_emitted_span_names_are_registered(self):
        from lighthouse_tpu.obs.tracer import TRACER

        evs = TRACER.chrome_trace()["traceEvents"]
        assert not R.unknown_names(evs, SPANS)


# ---------------------------------------------------------------------------
# MetricsServer endpoints
# ---------------------------------------------------------------------------


@pytest.fixture()
def served():
    srv = MetricsServer(port=0).start()
    try:
        yield srv
    finally:
        srv.stop()


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=5
    ) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read()


class TestMetricsServer:
    def test_metrics_endpoint_serves_prometheus_text(self, served):
        status, ctype, body = _get(served.port, "/metrics")
        assert status == 200 and ctype.startswith("text/plain")
        text = body.decode()
        for family in ("trace_spans_dropped_total", "trace_dumps_written_total",
                       "jit_compile_seconds"):
            assert f"# TYPE {family}" in text, family

    def test_health_endpoint(self, served):
        status, ctype, body = _get(served.port, "/health")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert doc["status"] == "ok" and doc["pid"] == os.getpid()

    def test_trace_endpoint_serves_chrome_json(self, served):
        from lighthouse_tpu.obs.tracer import TRACER

        TRACER.instant("breaker.transition", state="CLOSED")
        status, ctype, body = _get(served.port, "/trace")
        assert status == 200 and ctype == "application/json"
        doc = json.loads(body)
        assert "traceEvents" in doc
        assert any(
            ev["name"] == "breaker.transition" for ev in doc["traceEvents"]
        )

    def test_unknown_path_404s(self, served):
        with pytest.raises(urllib.error.HTTPError) as exc:
            _get(served.port, "/nope")
        assert exc.value.code == 404

    def test_last_server_tracks_most_recent(self, served):
        from lighthouse_tpu.obs import last_server

        assert last_server() is served
