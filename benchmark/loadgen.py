#!/usr/bin/env python3
"""The load generator: a client process that never imports JAX.

    python3 benchmark/loadgen.py --input traffic.json --output records.json \\
        --port 5053 --start <monotonic s> --seconds 30

It reads the submissions that ``traffic.to_file`` wrote, renders each as
the JSON body of ``POST /eth/v1/verify/batch``, and sends them:

* open loop: each submission at its due time, ``start + due``, from a
  pool of sender threads, whatever the server's state;
* closed loop: ``tenants x in_flight_per_tenant`` clients, each sending
  the next submission as soon as its previous one is done, until the
  window closes.  Running out of submissions is an error.

Every accepted request is polled with ``GET .../<request_id>`` every
``poll_ms`` until it reports ``done``.  Requests still open a minute
after the window are given up.  The output holds one record per
submission sent (times on the system-wide monotonic clock) and the
sender's lateness and poll rate.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

GRACE_S = 60.0
SENDERS = 16
POLLERS = 8


def render(sub: dict, pubkeys: list) -> bytes:
    doc = {"tenant": sub["tenant"], "sets": [
        {"signature": "0x" + sig, "pubkeys": [pubkeys[i] for i in keys],
         "message": "0x" + msg} for sig, keys, msg in sub["sets"]]}
    return json.dumps(doc).encode()


class Client:
    def __init__(self, port: int):
        self.port = port
        self.gets = 0
        self.lock = threading.Lock()

    def post(self, body: bytes):
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            conn.request("POST", "/eth/v1/verify/batch", body=body,
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            doc = json.loads(resp.read() or b"{}")
            return resp.status, doc
        finally:
            conn.close()

    def get(self, rid: str):
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=120)
        try:
            conn.request("GET", f"/eth/v1/verify/batch/{rid}")
            resp = conn.getresponse()
            doc = json.loads(resp.read() or b"{}")
        finally:
            conn.close()
        with self.lock:
            self.gets += 1
        return resp.status, doc


def _send(client: Client, idx: int, sub: dict, pubkeys: list, due):
    body = render(sub, pubkeys)
    rec = {"idx": idx, "tenant": sub["tenant"], "n_sets": len(sub["sets"]),
           "due": due, "post_start": time.monotonic()}
    try:
        status, doc = client.post(body)
    except OSError as exc:
        status, doc = 0, {"message": str(exc)}
    rec["post_end"] = time.monotonic()
    rec["status"] = status
    rec["rid"] = (doc.get("data") or {}).get("request_id") if status == 202 else None
    if status != 202:
        rec["error"] = doc.get("message")
    return rec


def _poll_until_done(client: Client, rec: dict, poll_s: float, give_up: float):
    while True:
        time.sleep(poll_s)
        try:
            status, doc = client.get(rec["rid"])
        except OSError:
            status, doc = 0, {}
        data = doc.get("data") or {}
        if status == 200 and data.get("status") == "done":
            rec["done"] = time.monotonic()
            rec["verdicts"] = data.get("verdicts")
            return
        if time.monotonic() > give_up:
            return


def open_loop(client, doc, start, seconds, poll_s):
    pubkeys = doc["pubkeys"]
    subs = doc["submissions"]
    give_up = start + seconds + GRACE_S
    outstanding: dict = {}
    records: list = []
    lock = threading.Lock()
    sent_all = threading.Event()

    def send(idx, sub, due):
        rec = _send(client, idx, sub, pubkeys, due)
        with lock:
            records.append(rec)
            if rec["rid"] is not None:
                outstanding[rec["rid"]] = rec

    def poll_one(rec):
        try:
            status, d = client.get(rec["rid"])
        except OSError:
            return
        data = d.get("data") or {}
        if status == 200 and data.get("status") == "done":
            rec["done"] = time.monotonic()
            rec["verdicts"] = data.get("verdicts")
            with lock:
                outstanding.pop(rec["rid"], None)

    def poller():
        with ThreadPoolExecutor(POLLERS) as pool:
            while True:
                t0 = time.monotonic()
                with lock:
                    batch = list(outstanding.values())
                if not batch and sent_all.is_set():
                    return
                if t0 > give_up:
                    return
                list(pool.map(poll_one, batch))
                time.sleep(max(0.0, poll_s - (time.monotonic() - t0)))

    poll_thread = threading.Thread(target=poller, name="poller")
    poll_thread.start()
    with ThreadPoolExecutor(SENDERS) as senders:
        futures = []
        for idx, sub in enumerate(subs):
            due = start + sub["due"]
            if due >= start + seconds:
                break
            delay = due - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            futures.append(senders.submit(send, idx, sub, due))
        for f in futures:
            f.result()
    sent_all.set()
    poll_thread.join()
    return records, False


def closed_loop(client, doc, start, seconds, poll_s):
    pubkeys = doc["pubkeys"]
    subs = doc["submissions"]
    mix = doc["mix"]
    give_up = start + seconds + GRACE_S
    end = start + seconds
    records: list = []
    lock = threading.Lock()
    nxt = [0]
    exhausted = [False]

    def client_loop(tenant: str):
        while time.monotonic() < end:
            with lock:
                idx = nxt[0]
                if idx >= len(subs):
                    exhausted[0] = True
                    return
                nxt[0] += 1
            sub = dict(subs[idx], tenant=tenant)
            rec = _send(client, idx, sub, pubkeys, None)
            with lock:
                records.append(rec)
            if rec["rid"] is None:
                continue
            _poll_until_done(client, rec, poll_s, give_up)

    delay = start - time.monotonic()
    if delay > 0:
        time.sleep(delay)
    threads = [threading.Thread(target=client_loop, args=(f"tenant-{t}",),
                                name=f"client-{t}-{j}")
               for t in range(mix["tenants"])
               for j in range(mix["in_flight_per_tenant"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return records, exhausted[0]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--input", required=True)
    ap.add_argument("--output", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--start", type=float, default=None,
                    help="time.monotonic() at which the window opens; "
                         "without it, print 'ready' once the input is "
                         "loaded and read the start from stdin")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(args.input, encoding="utf-8") as f:
        doc = json.load(f)
    client = Client(args.port)
    poll_s = doc["mix"]["poll_ms"] / 1000.0
    run = open_loop if doc["loop"] == "open" else closed_loop
    start = args.start
    if start is None:
        print("ready", flush=True)
        start = float(sys.stdin.readline())
    records, exhausted = run(client, doc, start, args.seconds, poll_s)
    elapsed = time.monotonic() - start
    out = {"records": sorted(records, key=lambda r: r["idx"]),
           "exhausted": exhausted, "gets": client.gets,
           "gets_per_s": client.gets / elapsed if elapsed > 0 else 0.0}
    tmp = args.output + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(out, f)
    os.replace(tmp, args.output)
    return 0


if __name__ == "__main__":
    sys.exit(main())
