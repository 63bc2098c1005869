"""CLI entry: version, account keystore creation, db tools, and a short
auto-proposing bn run (the L0 smoke)."""

import json

import pytest

from lighthouse_tpu.cli import main


def test_version(capsys):
    assert main(["version"]) == 0
    assert "lighthouse-tpu" in capsys.readouterr().out


def test_account_new(capsys):
    rc = main([
        "account", "new", "--password", "hunter22", "--index", "3",
        "--seed-hex", "11" * 32,
    ])
    assert rc == 0
    store = json.loads(capsys.readouterr().out)
    assert store["version"] == 4 and store["path"] == "m/12381/3600/3/0/0"
    from lighthouse_tpu.crypto import keystore as ks

    assert len(ks.decrypt(store, "hunter22")) == 32


def test_db_tools(tmp_path, capsys):
    from lighthouse_tpu.store import DBColumn, SlabStore

    path = str(tmp_path / "x.slab")
    s = SlabStore(path)
    s.put(DBColumn.BEACON_BLOCK, b"k", b"v" * 100)
    s.put(DBColumn.BEACON_BLOCK, b"k", b"v" * 100)
    s.close()
    assert main(["db", "inspect", path]) == 0
    info = json.loads(capsys.readouterr().out)
    assert info["entries"] == 1 and info["dead_bytes"] > 0
    assert main(["db", "compact", path]) == 0


def test_db_verify(tmp_path, capsys):
    from lighthouse_tpu.store import DBColumn, SlabStore

    path = str(tmp_path / "v.slab")
    s = SlabStore(path)
    s.put(DBColumn.BEACON_BLOCK, b"k", b"v" * 100)
    s.flush()
    s.close()
    assert main(["db", "verify", path]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["ok"] and rep["per_column"]["BEACON_BLOCK"]["live"] == 1

    with open(path, "ab") as f:  # torn tail → exit 1 with a recovery report
        f.write(b"\x01\xff\xff")
    assert main(["db", "verify", path]) == 1
    rep = json.loads(capsys.readouterr().out)
    assert not rep["ok"] and rep["recovery"]["tail_torn"]


def test_bn_short_run(capsys):
    rc = main([
        "--spec", "minimal", "bn", "--validators", "16", "--http-port", "0",
        "--slots", "3", "--auto-propose",
    ])
    assert rc == 0


def test_bn_chaos_network_fault_kinds(capsys):
    """`bn --chaos` accepts the byzantine network kinds and arms them on
    the global injector (the req/resp sites fire them in a full node)."""
    from lighthouse_tpu.utils import faults

    try:
        rc = main([
            "--spec", "minimal", "bn", "--validators", "16",
            "--http-port", "0", "--slots", "2", "--auto-propose",
            "--chaos", "rpc.respond=extra-blocks",
            "--chaos", "sync.request=stall:0.1x2",
        ])
        assert rc == 0
        assert faults.INJECTOR.armed("rpc.respond")
        assert faults.INJECTOR.armed("sync.request")
        f = faults.INJECTOR._armed["sync.request"]
        assert f.kind == "stall" and f.delay == 0.1 and f.remaining == 2
        assert faults.INJECTOR._armed["rpc.respond"].kind == "extra-blocks"
    finally:
        faults.INJECTOR.disarm()


def test_bn_selfcheck_passes_and_boots(capsys):
    """`bn --selfcheck` runs the known-answer suite against the honest
    backend and the node boots normally (exit 0)."""
    rc = main([
        "--spec", "minimal", "bn", "--validators", "16", "--http-port", "0",
        "--slots", "1", "--auto-propose", "--selfcheck",
    ])
    assert rc == 0


def test_bn_selfcheck_mismatch_refuses_boot(monkeypatch, capsys):
    """A backend that lies about the invalid canaries fails the boot
    with a non-zero exit before any listener opens."""
    from lighthouse_tpu.crypto.bls import api as bls_api

    class StuckTrueBackend:
        name = "stuck-true-stub"

        def verify_signature_sets(self, sets):
            return True

    # the selfcheck resolves the active backend at call time; the canary
    # generator's oracle uses cpu_backend() and stays honest
    monkeypatch.setattr(bls_api, "get_backend", lambda: StuckTrueBackend())
    rc = main([
        "--spec", "minimal", "bn", "--validators", "16", "--http-port", "0",
        "--slots", "1", "--auto-propose", "--selfcheck",
    ])
    assert rc == 1


def test_wallet_and_validator_manager(capsys):
    import json as _json

    assert main([
        "account", "wallet", "--name", "w1", "--password", "pw",
        "--seed-hex", "22" * 32,
    ]) == 0
    w = _json.loads(capsys.readouterr().out)
    assert w["type"] == "hierarchical deterministic" and w["nextaccount"] == 0
    from lighthouse_tpu.crypto.wallet import decrypt_seed, next_validator

    assert decrypt_seed(w, "pw") == bytes.fromhex("22" * 32)
    s1, _ = next_validator(w, "pw", "kpw")
    s2, _ = next_validator(w, "pw", "kpw")
    assert w["nextaccount"] == 2 and s1["pubkey"] != s2["pubkey"]

    assert main([
        "validator-manager", "create", "--count", "2",
        "--wallet-password", "pw", "--keystore-password", "kpw",
        "--seed-hex", "33" * 32,
    ]) == 0
    out = _json.loads(capsys.readouterr().out)
    assert len(out) == 2
    assert out[0]["voting_pubkey"] != out[1]["voting_pubkey"]


def test_lcli_skip_slots_and_parse_ssz(tmp_path, capsys):
    import json as _json

    assert main(["--spec", "minimal", "lcli", "skip-slots", "--slots", "9",
                 "--validators", "16"]) == 0
    out = _json.loads(capsys.readouterr().out)
    assert out["slots"] == 9 and out["state_root"].startswith("0x")

    # round-trip a block file through parse-ssz
    from lighthouse_tpu.consensus.containers import types_for
    from lighthouse_tpu.consensus.spec import MINIMAL

    T = types_for(MINIMAL)
    blk = T.SignedBeaconBlock_BY_FORK["altair"]()
    blk.message.slot = 77
    p = tmp_path / "block.ssz"
    p.write_bytes(blk.encode())
    assert main(["--spec", "minimal", "lcli", "parse-ssz", "--type",
                 "SignedBeaconBlock", "--fork", "altair", str(p)]) == 0
    parsed = _json.loads(capsys.readouterr().out)
    assert parsed["message"]["slot"] == "77"


def test_bn_metrics_port_serves_scrape_endpoints(capsys):
    """`bn --metrics-port 0` boots the scrape endpoint on an ephemeral
    port; /metrics serves known metric families in Prometheus text
    format, /health answers ok, /trace serves Chrome trace JSON."""
    import threading
    import time
    import urllib.request

    from lighthouse_tpu.obs import last_server

    before = last_server()
    rc = {}

    def run():
        rc["bn"] = main([
            "--spec", "minimal", "bn", "--validators", "16",
            "--http-port", "0", "--metrics-port", "0", "--slots", "200",
        ])

    t = threading.Thread(target=run, daemon=True)
    t.start()
    srv = None
    deadline = time.time() + 30
    while time.time() < deadline:
        srv = last_server()
        if srv is not None and srv is not before and srv.port:
            break
        time.sleep(0.02)
    assert srv is not None and srv is not before, "metrics server never came up"

    def get(path):
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}{path}", timeout=5
        ) as resp:
            return resp.headers.get("Content-Type"), resp.read().decode()

    ctype, text = get("/metrics")
    assert ctype.startswith("text/plain")
    for family in ("trace_spans_dropped_total", "jit_compile_seconds",
                   "block_import_latency_seconds"):
        assert f"# TYPE {family}" in text, family

    _, health = get("/health")
    assert json.loads(health)["status"] == "ok"

    _, trace = get("/trace")
    assert "traceEvents" in json.loads(trace)

    t.join(timeout=60)
    assert not t.is_alive() and rc["bn"] == 0


@pytest.mark.parametrize("name", ["fake", "python"])
def test_bn_bls_backend_selects_the_named_engine(name, capsys):
    from lighthouse_tpu.crypto.bls import api

    prev = api.get_backend()
    try:
        rc = main([
            "--spec", "minimal", "bn", "--validators", "8",
            "--http-port", "0", "--slots", "1", "--bls-backend", name,
        ])
        assert rc == 0
        assert api.get_backend().name == name
    finally:
        api._ACTIVE[0] = prev
