"""The load generator against a stand-in server that answers after a fixed
delay: latency runs from the due time, lateness is small, the closed loop
keeps its clients busy and says when it ran dry."""

import json
import os
import subprocess
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from benchmark import stats

HERE = os.path.dirname(os.path.abspath(__file__))
LOADGEN = os.path.join(os.path.dirname(HERE), "loadgen.py")
DELAY = 0.2


class StandIn:
    def __init__(self):
        self.posted = {}
        self.lock = threading.Lock()
        outer = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_POST(self):
                doc = json.loads(self.rfile.read(int(self.headers["Content-Length"])))
                with outer.lock:
                    rid = f"r{len(outer.posted)}"
                    outer.posted[rid] = (time.monotonic(), len(doc["sets"]))
                self._send(202, {"data": {"request_id": rid, "status": "queued"}})

            def do_GET(self):
                rid = self.path.rsplit("/", 1)[1]
                t, n = outer.posted[rid]
                if time.monotonic() - t >= DELAY:
                    self._send(200, {"data": {"status": "done", "verdicts": [True] * n}})
                else:
                    self._send(200, {"data": {"status": "queued"}})

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        self.thread = threading.Thread(target=self.httpd.serve_forever, daemon=True)
        self.thread.start()

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


@pytest.fixture
def server():
    s = StandIn()
    yield s
    s.stop()


def drive(tmp_path, server, doc, seconds):
    inp, out = tmp_path / "in.json", tmp_path / "out.json"
    inp.write_text(json.dumps(doc))
    start = time.monotonic() + 0.5
    subprocess.run([sys.executable, LOADGEN, "--input", str(inp), "--output", str(out),
                    "--port", str(server.httpd.server_address[1]),
                    "--start", repr(start), "--seconds", str(seconds)],
                   check=True, timeout=120)
    return start, json.loads(out.read_text())


def sub(due, n=2):
    return {"tenant": "t", "due": due, "sets": [["aa", [0], "bb"]] * n}


def test_open_loop_latency_runs_from_the_due_time(tmp_path, server):
    dues = [0.05 * k for k in range(20)] + [5.0]    # the last is past the window
    doc = {"loop": "open", "mix": {"poll_ms": 25}, "pubkeys": ["0x00"],
           "submissions": [sub(d) for d in dues]}
    start, out = drive(tmp_path, server, doc, 1.5)
    recs = out["records"]
    assert len(recs) == 20
    late = [r["post_start"] - r["due"] for r in recs]
    assert max(late) < 0.05
    assert all(abs(r["due"] - (start + d)) < 1e-6 for r, d in zip(recs, dues))
    lat = [r["done"] - r["due"] for r in recs]
    assert all(r["verdicts"] == [True, True] for r in recs)
    # served after DELAY, seen at the next poll: DELAY <= latency < DELAY + poll + slack
    assert min(lat) >= DELAY
    assert stats.median(lat) < DELAY + 0.025 + 0.05
    assert out["gets_per_s"] > 0


def test_closed_loop_keeps_clients_busy_and_reports_running_dry(tmp_path, server):
    doc = {"loop": "closed", "mix": {"poll_ms": 25, "tenants": 2,
                                     "in_flight_per_tenant": 2},
           "pubkeys": ["0x00"], "submissions": [sub(None) for _ in range(400)]}
    _start, out = drive(tmp_path, server, doc, 1.0)
    recs = out["records"]
    assert not out["exhausted"]
    # 4 clients, each request ~DELAY plus a poll: about 4 / 0.23 per second
    assert 12 <= len(recs) <= 24
    assert {r["tenant"] for r in recs} == {"tenant-0", "tenant-1"}
    doc["submissions"] = doc["submissions"][:5]
    _start, out = drive(tmp_path, server, doc, 1.0)
    assert out["exhausted"]
