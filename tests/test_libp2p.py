"""libp2p wire stack: noise XX, yamux, multistream, gossipsub/req-resp.

Twin of the reference transport tests (lighthouse_network tcp tests,
service/utils.rs build_transport stack): real TCP sockets on localhost,
encrypted channels, muxed streams, and the eth2 wire protocols on top.
"""

import socket
import threading
import time

import pytest
from cryptography.hazmat.primitives.asymmetric import ec

from lighthouse_tpu.network import rpc as rpc_mod
from lighthouse_tpu.network.libp2p import (
    Libp2pHost,
    decode_gossip_rpc,
    encode_gossip_rpc,
)
from lighthouse_tpu.network.noise import (
    NoiseError,
    initiator_handshake,
    marshal_identity_pubkey,
    peer_id_from_pubkey,
    responder_handshake,
    unmarshal_identity_pubkey,
)
from lighthouse_tpu.network.yamux import Session, YamuxError


def _sock_reader(sock):
    def read_exact(n):
        buf = b""
        while len(buf) < n:
            chunk = sock.recv(n - len(buf))
            if not chunk:
                raise NoiseError("closed")
            buf += chunk
        return buf

    return read_exact


def _noise_pair():
    """Run the XX handshake over a socketpair; returns both sessions."""
    sa, sb = socket.socketpair()
    sa.settimeout(30)
    sb.settimeout(30)
    ka = ec.generate_private_key(ec.SECP256K1())
    kb = ec.generate_private_key(ec.SECP256K1())
    result = {}

    def responder():
        result["b"] = responder_handshake(kb, sb.sendall, _sock_reader(sb))

    t = threading.Thread(target=responder)
    t.start()
    result["a"] = initiator_handshake(ka, sa.sendall, _sock_reader(sa))
    t.join(timeout=5)
    return (sa, sb), (ka, kb), result["a"], result["b"]


class TestNoise:
    def test_handshake_and_transport(self):
        (sa, sb), (ka, kb), na, nb = _noise_pair()
        try:
            # identities exchanged and verified
            from cryptography.hazmat.primitives import serialization

            kb_pub = kb.public_key().public_bytes(
                serialization.Encoding.X962,
                serialization.PublicFormat.CompressedPoint,
            )
            assert na.remote_identity == kb_pub
            assert na.remote_peer_id == peer_id_from_pubkey(kb_pub)
            # transport secrecy both directions, multiple frames
            for i in range(4):
                na.write(sa.sendall, b"ping%d" % i)
                assert nb.read(_sock_reader(sb)) == b"ping%d" % i
                nb.write(sb.sendall, b"pong%d" % i)
                assert na.read(_sock_reader(sa)) == b"pong%d" % i
        finally:
            sa.close(); sb.close()

    def test_tampered_frame_rejected(self):
        (sa, sb), _keys, na, nb = _noise_pair()
        try:
            na.write(sa.sendall, b"secret")
            raw = _sock_reader(sb)(2)
            n = int.from_bytes(raw, "big")
            body = bytearray(_sock_reader(sb)(n))
            body[0] ^= 0xFF
            buf = [bytes(raw) + bytes(body)]

            def feeder(k):
                out, buf[0] = buf[0][:k], buf[0][k:]
                return out

            with pytest.raises(NoiseError):
                nb.read(feeder)
        finally:
            sa.close(); sb.close()

    def test_pubkey_protobuf_roundtrip(self):
        key = ec.generate_private_key(ec.SECP256K1())
        from cryptography.hazmat.primitives import serialization

        pub = key.public_key().public_bytes(
            serialization.Encoding.X962, serialization.PublicFormat.CompressedPoint
        )
        assert unmarshal_identity_pubkey(marshal_identity_pubkey(pub)) == pub
        pid = peer_id_from_pubkey(pub)
        assert pid[0] == 0x00  # identity multihash (37-byte marshaled key)


class TestYamux:
    def _pair(self):
        sa, sb = socket.socketpair()

        def recv_a():
            try:
                return sa.recv(65536)
            except OSError:
                return b""

        def recv_b():
            try:
                return sb.recv(65536)
            except OSError:
                return b""

        d = Session(sa.sendall, recv_a, is_dialer=True)
        l = Session(sb.sendall, recv_b, is_dialer=False)
        d.start(); l.start()
        return (sa, sb), d, l

    def test_streams_interleave(self):
        (sa, sb), d, l = self._pair()
        try:
            s1 = d.open_stream()
            s2 = d.open_stream()
            assert (s1.id, s2.id) == (1, 3)  # dialer ids are odd
            s2.write(b"BBBB")
            s1.write(b"AAAA")
            r2 = l.accept_stream()
            r1 = l.accept_stream()
            # frames interleaved across streams arrive per-stream in order
            assert {r1.id, r2.id} == {1, 3}
            by_id = {r.id: r for r in (r1, r2)}
            assert by_id[1].read(4) == b"AAAA"
            assert by_id[3].read(4) == b"BBBB"
            # server replies on the same stream
            by_id[1].write(b"ack")
            assert s1.read(3) == b"ack"
        finally:
            sa.close(); sb.close()

    def test_fin_gives_eof(self):
        (sa, sb), d, l = self._pair()
        try:
            s = d.open_stream()
            s.write(b"last words")
            s.close()
            r = l.accept_stream()
            assert r.read_until_eof() == b"last words"
        finally:
            sa.close(); sb.close()

    def test_large_transfer_crosses_window(self):
        """> 256 KiB forces window-update credit flow."""
        (sa, sb), d, l = self._pair()
        try:
            blob = bytes(range(256)) * 2048  # 512 KiB
            s = d.open_stream()
            t = threading.Thread(target=lambda: (s.write(blob), s.close()))
            t.start()
            r = l.accept_stream()
            got = r.read(len(blob), timeout=10.0)
            t.join(timeout=10)
            assert got == blob
        finally:
            sa.close(); sb.close()


class TestGossipRpcCodec:
    def test_roundtrip(self):
        raw = encode_gossip_rpc(
            subscriptions=[(True, "/eth2/x/beacon_block/ssz_snappy"),
                           (False, "/eth2/x/voluntary_exit/ssz_snappy")],
            publish=[("/eth2/x/beacon_block/ssz_snappy", b"\x01\x02")],
        )
        subs, msgs, control = decode_gossip_rpc(raw)
        assert subs == [(True, "/eth2/x/beacon_block/ssz_snappy"),
                        (False, "/eth2/x/voluntary_exit/ssz_snappy")]
        assert msgs == [("/eth2/x/beacon_block/ssz_snappy", b"\x01\x02")]
        assert control is None

    def test_control_roundtrip(self):
        from lighthouse_tpu.network.libp2p import GossipControl

        ctl = GossipControl(
            ihave=[("/t1", [b"\xaa" * 20, b"\xbb" * 20])],
            iwant=[b"\xcc" * 20],
            graft=["/t2"],
            prune=["/t3", "/t4"],
        )
        raw = encode_gossip_rpc(control=ctl)
        _subs, _msgs, back = decode_gossip_rpc(raw)
        assert back.ihave == [("/t1", [b"\xaa" * 20, b"\xbb" * 20])]
        assert back.iwant == [b"\xcc" * 20]
        assert back.graft == ["/t2"]
        assert back.prune == ["/t3", "/t4"]

    def test_mcache_windows(self):
        from lighthouse_tpu.network.libp2p import MessageCache

        mc = MessageCache(gossip_windows=2, total_windows=3)
        mc.put(b"m1", "/t", b"d1")
        mc.shift()
        mc.put(b"m2", "/t", b"d2")
        assert set(mc.recent_ids("/t")) == {b"m1", b"m2"}
        mc.shift()  # m1 now outside the gossip window
        assert set(mc.recent_ids("/t")) == {b"m2"}
        mc.shift()  # m1 expires entirely
        assert mc.get(b"m1") is None and mc.get(b"m2") == ("/t", b"d2")


@pytest.fixture
def hosts():
    hs = [Libp2pHost() for _ in range(3)]
    for h in hs:
        h.start()
    yield hs
    for h in hs:
        h.stop()


TOPIC = "/eth2/00000000/beacon_block/ssz_snappy"


class TestHost:
    def test_reqresp_and_gossip_relay(self, hosts):
        a, b, c = hosts
        b.rpc_handlers["status"] = lambda req, pid: (rpc_mod.SUCCESS, b"ok:" + req)
        got = []
        for h, nm in zip(hosts, "abc"):
            h.subscribe(TOPIC, lambda p, pid, nm=nm: (got.append(nm), "accept")[1])
        conn_ab = a.dial("127.0.0.1", b.port)
        b.dial("127.0.0.1", c.port)
        time.sleep(0.5)
        assert conn_ab.peer_id == b.peer_id
        code, resp = conn_ab.request("status", b"\x09")
        assert (code, resp) == (rpc_mod.SUCCESS, b"ok:\x09")
        a.publish(TOPIC, b"payload" * 20)
        deadline = time.time() + 5
        while time.time() < deadline and "c" not in got:
            time.sleep(0.05)
        assert "b" in got and "c" in got, got  # relay a->b->c
        assert b.received[0][1] == b"payload" * 20

    def test_reject_penalizes_sender(self, hosts):
        a, b, _c = hosts
        b.subscribe(TOPIC, lambda p, pid: "reject")
        a.subscribe(TOPIC, lambda p, pid: "accept")
        a.dial("127.0.0.1", b.port)
        time.sleep(0.3)
        a.publish(TOPIC, b"bad payload")
        deadline = time.time() + 5
        while time.time() < deadline:
            scores = [i.score() for i in b.peer_manager.peers.values()]
            if any(s < 0 for s in scores):
                break
            time.sleep(0.05)
        assert any(s < 0 for s in scores), scores

    def test_unknown_rpc_protocol_refused(self, hosts):
        a, b, _c = hosts
        conn = a.dial("127.0.0.1", b.port)
        with pytest.raises(Exception):
            conn.request("status", b"\x00", timeout=2.0)  # b has no handler

    def test_mesh_graft_and_iwant_recovery(self):
        """Heartbeats form a mesh; a message published while a peer was
        outside the mesh is recovered via IHAVE -> IWANT.  Manual
        heartbeats so the background loop cannot race the scenario."""
        a = Libp2pHost(heartbeat=False)
        b = Libp2pHost(heartbeat=False)
        a.start(); b.start()
        try:
            self._run_graft_iwant_scenario(a, b)
        finally:
            a.stop(); b.stop()

    def _run_graft_iwant_scenario(self, a, b):
        got_b = []
        a.subscribe(TOPIC, lambda p, pid: "accept")
        b.subscribe(TOPIC, lambda p, pid: (got_b.append(p), "accept")[1])
        a.dial("127.0.0.1", b.port)
        time.sleep(0.3)
        a.heartbeat()  # grafts b into a's mesh
        assert any(TOPIC in c.topics for c in a.connections.values())
        deadline = time.time() + 3
        while time.time() < deadline and not a.mesh.get(TOPIC):
            time.sleep(0.05)
        assert a.mesh.get(TOPIC), "graft must land b in a's mesh"
        # publish lands directly (mesh route)
        a.publish(TOPIC, b"direct")
        deadline = time.time() + 3
        while time.time() < deadline and not got_b:
            time.sleep(0.05)
        assert got_b == [b"direct"]
        # now simulate a missed message: present only in a's mcache; an
        # IHAVE advertisement must trigger b's IWANT and deliver it
        from lighthouse_tpu.network.gossip import message_id
        from lighthouse_tpu.network.libp2p import GossipControl
        from lighthouse_tpu.network.snappy import compress_block

        payload = b"recovered-via-iwant"
        compressed = compress_block(payload)
        mid = message_id(TOPIC, compressed)
        a.mcache.put(mid, TOPIC, compressed)
        a._send_control(b.peer_id, GossipControl(ihave=[(TOPIC, [mid])]))
        deadline = time.time() + 3
        while time.time() < deadline and payload not in got_b:
            time.sleep(0.05)
        assert payload in got_b, "IHAVE/IWANT recovery failed"

    def test_graft_unsubscribed_topic_pruned_back(self, hosts):
        a, b, _c = hosts
        a.subscribe(TOPIC, lambda p, pid: "accept")
        conn = a.dial("127.0.0.1", b.port)
        time.sleep(0.2)
        from lighthouse_tpu.network.libp2p import GossipControl, encode_gossip_rpc

        conn.send_gossip_rpc(
            encode_gossip_rpc(control=GossipControl(graft=[TOPIC]))
        )
        time.sleep(0.5)
        # b is not subscribed: must NOT keep a in any mesh
        assert not b.mesh.get(TOPIC)

    def test_rate_limit_returns_resource_unavailable(self, hosts):
        a, b, _c = hosts
        b.rpc_handlers["goodbye"] = lambda req, pid: (rpc_mod.SUCCESS, b"")
        conn = a.dial("127.0.0.1", b.port)
        # goodbye bucket: capacity 1 -> second immediate call must be limited
        code1, _ = conn.request("goodbye", b"\x00" * 8)
        code2, _ = conn.request("goodbye", b"\x00" * 8)
        assert code1 == rpc_mod.SUCCESS
        assert code2 == rpc_mod.RESOURCE_UNAVAILABLE


class TestIDontWant:
    """gossipsub v1.2 IDONTWANT (the extension the reference vendors its
    gossipsub fork for): honored on forward, emitted on large receive."""

    def test_idontwant_suppresses_forward(self, hosts):
        from lighthouse_tpu.network.libp2p import (
            GossipControl,
            message_id,
            snappy,
        )

        a, b, _c = hosts
        got_b = []
        a.subscribe(TOPIC, lambda p, pid: "accept")
        b.subscribe(TOPIC, lambda p, pid: (got_b.append(p), "accept")[1])
        a.dial("127.0.0.1", b.port)
        time.sleep(0.3)
        payload = b"\x42" * 100
        compressed = snappy.compress_block(payload)
        mid = message_id(TOPIC, compressed)
        # B declares it already has the message
        b_conn_to_a = next(iter(b.connections.values()))
        b_conn_to_a.send_gossip_rpc(
            __import__(
                "lighthouse_tpu.network.libp2p", fromlist=["encode_gossip_rpc"]
            ).encode_gossip_rpc(control=GossipControl(idontwant=[mid]))
        )
        deadline = time.time() + 5
        a_conn = next(iter(a.connections.values()))
        while time.time() < deadline and mid not in a_conn.dont_want:
            time.sleep(0.05)
        assert mid in a_conn.dont_want
        a.publish(TOPIC, payload)
        time.sleep(1.0)
        assert got_b == [], "suppressed: B never received the publish"
        # a DIFFERENT message still flows
        a.publish(TOPIC, b"\x43" * 100)
        deadline = time.time() + 5
        while time.time() < deadline and not got_b:
            time.sleep(0.05)
        assert got_b == [b"\x43" * 100]

    def test_large_message_emits_idontwant(self, hosts):
        from lighthouse_tpu.network.libp2p import (
            IDONTWANT_THRESHOLD,
            message_id,
            snappy,
        )

        a, b, c = hosts
        for h in hosts:
            h.subscribe(TOPIC, lambda p, pid: "accept")
        a.dial("127.0.0.1", b.port)
        conn_bc = b.dial("127.0.0.1", c.port)
        time.sleep(0.5)  # let subscription RPCs propagate first
        for h in hosts:
            h.heartbeat()  # then form meshes deterministically
        time.sleep(0.3)
        import os as _os

        payload = _os.urandom(IDONTWANT_THRESHOLD + 512)  # incompressible:
        # the threshold applies to the WIRE (compressed) size
        compressed = snappy.compress_block(payload)
        mid = message_id(TOPIC, compressed)
        a.publish(TOPIC, payload)
        # B (the relayer) receives the big message from A and announces
        # IDONTWANT to its OTHER mesh peers — C records it on its
        # connection (the sender itself is never told: it obviously has
        # the message, which is also why C, whose only mesh peer IS the
        # sender, emits nothing)
        deadline = time.time() + 8
        seen = False
        while time.time() < deadline and not seen:
            seen = any(
                mid in conn.dont_want for conn in c.connections.values()
            )
            time.sleep(0.05)
        assert seen, "C recorded B's IDONTWANT for the large message"
