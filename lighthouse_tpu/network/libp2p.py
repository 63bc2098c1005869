"""libp2p transport: TCP + noise + yamux + gossipsub/req-resp wire protocols.

The socket-level counterpart of the reference's lighthouse_network
service (`src/service/utils.rs:39-48` build_transport: TCP, noise
encryption, yamux muxing; behaviour composition `src/service/behaviour.rs`):

* multistream-select 1.0 protocol negotiation (uvarint-framed lines),
* Noise XX channel (noise.py) bound to the node's secp256k1 identity,
* yamux sessions (yamux.py), one per connection,
* gossipsub v1.1 wire RPCs (`/meshsub/1.1.0`, protobuf, StrictNoSign as
  eth2 requires) carrying snappy-compressed payloads with the spec
  message-id (gossip.py), flood-published to subscribed peers,
* req/resp: one stream per request negotiated to
  `/eth2/beacon_chain/req/<name>/<v>/ssz_snappy` (rpc.py chunk codec).

Synchronous, thread-per-connection — the IO layer of a node whose hot
path is device batches, not packet shuffling.
"""

from __future__ import annotations

from collections import OrderedDict
import socket
import threading
from typing import Callable

from ..utils.logging import get_logger
from ..utils.metrics import Counter, Gauge
from . import rpc as rpc_mod
from . import snappy
from .gossip import PeerManager, SeenCache, message_id
from .noise import (
    NoiseError,
    NoiseSession,
    _pb_field_bytes,
    _pb_parse,
    _pb_varint,
    initiator_handshake,
    peer_id_from_pubkey,
    responder_handshake,
)
from .quic import QuicEndpoint, QuicError
from .yamux import Session, Stream, YamuxError

log = get_logger("libp2p")

MULTISTREAM = "/multistream/1.0.0"
NOISE_PROTO = "/noise"
YAMUX_PROTO = "/yamux/1.0.0"

# transport observability (the reference's libp2p metrics: peers by
# transport, dial outcomes — lighthouse_network metrics.rs)
PEERS_GAUGE = Gauge("libp2p_peers_connected", "Connected peers",
                    ("transport",))
DIALS = Counter("libp2p_dials_total", "Outbound dial outcomes",
                ("transport", "outcome"))

# errors any transport's streams can surface (yamux-over-noise-over-TCP
# or native QUIC streams — the two stacks share the Stream contract)
TRANSPORT_ERRORS = (YamuxError, QuicError, OSError)
GOSSIP_PROTO = "/meshsub/1.1.0"
# eth2 GOSSIP_MAX_SIZE is 10 MiB; one RPC may carry a few messages
MAX_GOSSIP_RPC_SIZE = 11 * 1024 * 1024
# v1.2 IDONTWANT: only messages at least this large are worth the
# control-message round trip (blocks/blobs; never tiny attestations)
IDONTWANT_THRESHOLD = 16 * 1024


class Libp2pError(Exception):
    pass


# ---------------------------------------------------------------------------
# multistream-select over a byte-stream interface
# ---------------------------------------------------------------------------


def _ms_frame(line: str) -> bytes:
    raw = line.encode() + b"\n"
    return _pb_varint(len(raw)) + raw


MAX_MS_MESSAGE = 64 * 1024  # multistream-select message cap


class _MsgReader:
    """Adapts exact-read byte sources to uvarint-framed reads.  The ONE
    uvarint decoder for the wire layer — bounds enforced here apply to
    multistream lines and gossip RPC frames alike."""

    def __init__(self, read_exact: Callable[[int], bytes]):
        self.read_exact = read_exact

    def read_uvarint(self, max_value: int) -> int:
        n, shift = 0, 0
        while True:
            b = self.read_exact(1)[0]
            n |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
            if shift > 63:
                raise Libp2pError("uvarint over 9 bytes")
        if n > max_value:
            raise Libp2pError(f"frame length {n} over cap {max_value}")
        return n

    def read_line(self) -> str:
        n = self.read_uvarint(MAX_MS_MESSAGE)
        raw = self.read_exact(n)
        return raw.rstrip(b"\n").decode()


def ms_negotiate_out(write, reader: _MsgReader, protocol: str) -> None:
    """Dialer side: propose ``protocol``; raise if the peer says na."""
    write(_ms_frame(MULTISTREAM) + _ms_frame(protocol))
    if reader.read_line() != MULTISTREAM:
        raise Libp2pError("peer is not multistream")
    got = reader.read_line()
    if got != protocol:
        raise Libp2pError(f"peer refused {protocol}: {got}")


def ms_negotiate_in(write, reader: _MsgReader, supported) -> str:
    """Listener side: accept the first supported proposal."""
    if reader.read_line() != MULTISTREAM:
        raise Libp2pError("peer is not multistream")
    write(_ms_frame(MULTISTREAM))
    while True:
        proposal = reader.read_line()
        if proposal in supported:
            write(_ms_frame(proposal))
            return proposal
        write(_ms_frame("na"))


# ---------------------------------------------------------------------------
# gossipsub wire RPCs (protobuf, StrictNoSign)
# ---------------------------------------------------------------------------


def encode_gossip_rpc(
    subscriptions: list[tuple[bool, str]] | None = None,
    publish: list[tuple[str, bytes]] | None = None,
    control: "GossipControl | None" = None,
) -> bytes:
    out = b""
    for sub, topic in subscriptions or []:
        opts = _pb_varint(1 << 3 | 0) + _pb_varint(1 if sub else 0)
        opts += _pb_field_bytes(2, topic.encode())
        out += _pb_field_bytes(1, opts)
    for topic, data in publish or []:
        msg = _pb_field_bytes(2, data) + _pb_field_bytes(4, topic.encode())
        out += _pb_field_bytes(2, msg)
    if control is not None and not control.empty():
        out += _pb_field_bytes(3, control.encode())
    return out


class GossipControl:
    """gossipsub ControlMessage: v1.1 ihave/iwant/graft/prune + the v1.2
    idontwant extension (field 5 — the episub/IDONTWANT work the
    reference vendors its gossipsub fork for)."""

    def __init__(self, ihave=None, iwant=None, graft=None, prune=None,
                 idontwant=None):
        self.ihave: list[tuple[str, list[bytes]]] = ihave or []
        self.iwant: list[bytes] = iwant or []
        self.graft: list[str] = graft or []
        self.prune: list[str] = prune or []
        self.idontwant: list[bytes] = idontwant or []

    def empty(self) -> bool:
        return not (self.ihave or self.iwant or self.graft or self.prune
                    or self.idontwant)

    def encode(self) -> bytes:
        out = b""
        for topic, mids in self.ihave:
            body = _pb_field_bytes(1, topic.encode())
            for mid in mids:
                body += _pb_field_bytes(2, mid)
            out += _pb_field_bytes(1, body)
        if self.iwant:
            body = b""
            for mid in self.iwant:
                body += _pb_field_bytes(1, mid)
            out += _pb_field_bytes(2, body)
        for topic in self.graft:
            out += _pb_field_bytes(3, _pb_field_bytes(1, topic.encode()))
        for topic in self.prune:
            out += _pb_field_bytes(4, _pb_field_bytes(1, topic.encode()))
        if self.idontwant:
            body = b""
            for mid in self.idontwant:
                body += _pb_field_bytes(1, mid)
            out += _pb_field_bytes(5, body)
        return out

    @classmethod
    def decode(cls, raw: bytes) -> "GossipControl":
        f = _pb_parse(raw)
        ctl = cls()
        for ih in f.get(1, []):
            g = _pb_parse(ih)
            ctl.ihave.append(
                (g.get(1, [b""])[0].decode(), list(g.get(2, [])))
            )
        for iw in f.get(2, []):
            g = _pb_parse(iw)
            ctl.iwant.extend(g.get(1, []))
        for gr in f.get(3, []):
            g = _pb_parse(gr)
            ctl.graft.append(g.get(1, [b""])[0].decode())
        for pr in f.get(4, []):
            g = _pb_parse(pr)
            ctl.prune.append(g.get(1, [b""])[0].decode())
        for dw in f.get(5, []):
            g = _pb_parse(dw)
            ctl.idontwant.extend(g.get(1, []))
        return ctl


def decode_gossip_rpc(raw: bytes):
    fields = _pb_parse(raw)
    subs: list[tuple[bool, str]] = []
    msgs: list[tuple[str, bytes]] = []
    for sub_raw in fields.get(1, []):
        f = _pb_parse(sub_raw)
        subs.append(
            (bool(f.get(1, [0])[0]), f.get(2, [b""])[0].decode())
        )
    for msg_raw in fields.get(2, []):
        f = _pb_parse(msg_raw)
        topic = f.get(4, [b""])[0].decode()
        data = f.get(2, [b""])[0]
        msgs.append((topic, data))
    control = None
    if fields.get(3):
        control = GossipControl.decode(fields[3][0])
    return subs, msgs, control


class MessageCache:
    """gossipsub mcache: full messages for IWANT service, sliding window
    of heartbeats for IHAVE advertisement."""

    def __init__(self, gossip_windows: int = 3, total_windows: int = 5):
        self.gossip_windows = gossip_windows
        self.windows: list[list[bytes]] = [[] for _ in range(total_windows)]
        self.msgs: dict[bytes, tuple[str, bytes]] = {}

    def put(self, mid: bytes, topic: str, data: bytes) -> None:
        if mid in self.msgs:
            return  # re-publish: the earlier window entry must stay unique
        self.windows[0].append(mid)
        self.msgs[mid] = (topic, data)

    def get(self, mid: bytes):
        return self.msgs.get(mid)

    def recent_ids(self, topic: str) -> list[bytes]:
        out = []
        for w in self.windows[: self.gossip_windows]:
            for mid in w:
                entry = self.msgs.get(mid)
                if entry is not None and entry[0] == topic:
                    out.append(mid)
        return out

    def shift(self) -> None:
        expired = self.windows.pop()
        for mid in expired:
            self.msgs.pop(mid, None)
        self.windows.insert(0, [])


# ---------------------------------------------------------------------------
# connections
# ---------------------------------------------------------------------------


class _QuicIdentity:
    """Stand-in for a NoiseSession on QUIC connections: the TLS
    handshake already authenticated the libp2p identity."""

    def __init__(self, remote_peer_id: bytes):
        self.remote_peer_id = remote_peer_id


class Connection:
    """One peer connection: secure channel + stream muxer + gossip state
    (noise+yamux over TCP, or a native QUIC connection)."""

    def __init__(self, host: "Libp2pHost", sock: socket.socket,
                 noise: NoiseSession, muxer: Session):
        self.host = host
        self.sock = sock
        self.noise = noise
        self.muxer = muxer
        self.peer_id = noise.remote_peer_id
        self.topics: set[str] = set()  # peer's subscriptions
        # mids this peer told us NOT to forward to it (v1.2 IDONTWANT);
        # bounded FIFO — stale entries age out with the seen-cache window
        self.dont_want: "OrderedDict[bytes, bool]" = OrderedDict()
        self._gossip_out: Stream | None = None
        self.transport = "tcp" if sock is not None else "quic"
        self._lock = threading.Lock()
        self._gossip_write_lock = threading.Lock()
        self.alive = True

    # -- gossip ------------------------------------------------------------

    def _ensure_gossip_stream(self) -> Stream:
        with self._lock:
            if self._gossip_out is None:
                st = self.muxer.open_stream()
                reader = _MsgReader(lambda n: st.read(n, timeout=5.0))
                ms_negotiate_out(st.write, reader, GOSSIP_PROTO)
                self._gossip_out = st
            return self._gossip_out

    def send_gossip_rpc(self, rpc: bytes) -> None:
        try:
            st = self._ensure_gossip_stream()
            # one writer at a time: a large RPC can split across yamux
            # frames while blocked on window credit, and interleaved
            # writers would corrupt the shared stream's varint framing
            with self._gossip_write_lock:
                st.write(_pb_varint(len(rpc)) + rpc)
        except (*TRANSPORT_ERRORS, Libp2pError) as exc:
            log.debug("gossip send to %s failed: %s", self.peer_id.hex()[:8], exc)
            self.alive = False

    # -- req/resp ----------------------------------------------------------

    def _request_raw(self, name: str, payload_ssz: bytes,
                     timeout: float) -> bytes:
        """Stream choreography shared by single- and multi-chunk requests:
        open, negotiate, write, FIN, read to EOF."""
        st = self.muxer.open_stream()
        reader = _MsgReader(lambda n: st.read(n, timeout=timeout))
        ms_negotiate_out(st.write, reader, rpc_mod.protocol_id(name))
        st.write(rpc_mod.encode_request(payload_ssz))
        st.close()  # FIN: request fully written
        return st.read_until_eof(timeout=timeout)

    def request(self, name: str, payload_ssz: bytes,
                timeout: float = 5.0) -> tuple[int, bytes]:
        """One shot request: returns (result_code, response_ssz)."""
        body = self._request_raw(name, payload_ssz, timeout)
        if not body:
            raise Libp2pError(f"empty response to {name}")
        return rpc_mod.decode_response_chunk(body)

    def request_multi(self, name: str, payload_ssz: bytes,
                      timeout: float = 10.0) -> list[tuple[int, bytes]]:
        """Streamed request (BlocksByRange shape): every coded chunk on
        the stream, in order.  An EMPTY stream is a valid response (all
        requested slots skipped / unknown) -> []."""
        return rpc_mod.decode_response_chunks(
            self._request_raw(name, payload_ssz, timeout)
        )

    def close(self) -> None:
        self.alive = False
        self.muxer.stop()
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass


class Libp2pHost:
    """A libp2p node: listener, dialer, gossip pub/sub, req/resp handlers.

    ``rpc_handlers[name] -> (request_ssz, peer_id) -> (code, response_ssz)``
    ``subscribe(topic, handler)`` with handler(payload, peer_id) -> accept/
    ignore/reject (MessageAcceptance semantics, gossip.py scoring).
    """

    # gossipsub v1.1 mesh parameters (the reference's defaults)
    D = 6
    D_LO = 4
    D_HI = 12
    D_LAZY = 6
    HEARTBEAT_SECS = 1.0

    def __init__(self, key=None, ip: str = "127.0.0.1", port: int = 0,
                 heartbeat: bool = True, quic_port: int | None = None):
        from cryptography.hazmat.primitives.asymmetric import ec

        self.key = key or ec.generate_private_key(ec.SECP256K1())
        from cryptography.hazmat.primitives import serialization

        pub = self.key.public_key().public_bytes(
            serialization.Encoding.X962, serialization.PublicFormat.CompressedPoint
        )
        self.peer_id = peer_id_from_pubkey(pub)
        self.listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self.listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.listener.bind((ip, port))
        self.listener.listen(16)
        self.ip, self.port = self.listener.getsockname()
        self.connections: dict[bytes, Connection] = {}
        self.subscriptions: dict[str, Callable] = {}
        self.rpc_handlers: dict[str, Callable] = {}
        self.seen = SeenCache()
        self.peer_manager = PeerManager()
        self.received: list[tuple[str, bytes]] = []
        self.rate_limiter = rpc_mod.RateLimiter()
        self.mesh: dict[str, set[bytes]] = {}  # topic -> mesh peer ids
        self._mesh_lock = threading.Lock()  # heartbeat/reader/publisher
        self.mcache = MessageCache()
        self._heartbeat_enabled = heartbeat
        self._running = False
        self._threads: list[threading.Thread] = []
        # optional QUIC listener (the reference runs TCP+QUIC side by
        # side, `service/utils.rs:39-48`); None disables it.  Bound here
        # (like the TCP listener) so the port is advertisable before
        # start() — the ENR is built between __init__ and start
        self.quic: QuicEndpoint | None = None
        self.quic_port: int | None = None
        if quic_port is not None:
            self.quic = QuicEndpoint(self.key, ip, quic_port)
            self.quic_port = self.quic.port

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        self._running = True
        t = threading.Thread(target=self._accept_loop,
                             name=f"libp2p-{self.port}", daemon=True)
        t.start()
        self._threads.append(t)
        if self.quic is not None:
            qt = threading.Thread(target=self._quic_accept_loop,
                                  name=f"libp2p-quic-{self.quic_port}",
                                  daemon=True)
            qt.start()
            self._threads.append(qt)
        if self._heartbeat_enabled:
            hb = threading.Thread(target=self._heartbeat_loop,
                                  name=f"gossip-hb-{self.port}", daemon=True)
            hb.start()
            self._threads.append(hb)

    def _heartbeat_loop(self) -> None:
        import time as _time

        while self._running:
            _time.sleep(self.HEARTBEAT_SECS)
            try:
                self.heartbeat()
            except Exception as exc:  # noqa: BLE001
                log.debug("heartbeat: %s", exc)

    def heartbeat(self) -> None:
        """gossipsub heartbeat: score decay + score-driven mesh maintenance
        + IHAVE gossip + mcache window shift (the vendored gossipsub's
        heartbeat(), with the v1.1 score gates)."""
        import random as _random

        self.peer_manager.maybe_decay()
        self._enforce_bans()
        for topic in list(self.subscriptions):
            grafts, prunes = [], []
            with self._mesh_lock:
                mesh = self.mesh.setdefault(topic, set())
                subscribed = [
                    pid for pid, c in self.connections.items()
                    if topic in c.topics and c.alive
                ]
                mesh.intersection_update(subscribed)
                # negative-score members are pruned FIRST (score gate)
                for pid_hex in self.peer_manager.mesh_prunable(
                    [p.hex() for p in mesh]
                ):
                    pid = bytes.fromhex(pid_hex)
                    mesh.discard(pid)
                    prunes.append(pid)
                # grow toward D when below D_LO — best score first, and
                # never below-zero peers (accept_graft gate)
                if len(mesh) < self.D_LO:
                    ranked = self.peer_manager.graft_candidates(
                        [p.hex() for p in subscribed if p not in mesh]
                    )
                    for pid_hex in ranked[: self.D - len(mesh)]:
                        pid = bytes.fromhex(pid_hex)
                        mesh.add(pid)
                        grafts.append(pid)
                # shrink toward D when above D_HI (drop worst scores)
                elif len(mesh) > self.D_HI:
                    worst = sorted(
                        mesh, key=lambda p: self.peer_manager.score(p.hex())
                    )
                    for pid in worst[: len(mesh) - self.D]:
                        mesh.discard(pid)
                        prunes.append(pid)
                lazy = [p for p in subscribed if p not in mesh]
            for pid in grafts:  # sends outside the lock
                self._send_control(pid, GossipControl(graft=[topic]))
            for pid in prunes:
                self._send_control(pid, GossipControl(prune=[topic]))
            # IHAVE gossip to a sample of non-mesh subscribers
            mids = self.mcache.recent_ids(topic)
            if mids:
                _random.shuffle(lazy)
                for pid in lazy[: self.D_LAZY]:
                    self._send_control(
                        pid, GossipControl(ihave=[(topic, mids[:64])])
                    )
        self.mcache.shift()

    def _send_control(self, peer_id: bytes, ctl: GossipControl) -> None:
        conn = self.connections.get(peer_id)
        if conn is not None:
            conn.send_gossip_rpc(encode_gossip_rpc(control=ctl))

    def _enforce_bans(self) -> None:
        """Disconnect any live connection whose peer crossed the ban
        threshold (peer_manager ban policy: ban implies disconnect)."""
        for pid, conn in list(self.connections.items()):
            if conn.alive and self.peer_manager.is_banned(pid.hex()):
                log.debug("disconnecting banned peer %s", pid.hex()[:8])
                self._drop_connection(conn)
                conn.close()

    def stop(self) -> None:
        self._running = False
        for conn in list(self.connections.values()):
            conn.close()
        try:
            self.listener.close()
        except OSError:
            pass
        if self.quic is not None:
            self.quic.stop()

    # -- socket plumbing ---------------------------------------------------

    @staticmethod
    def _sock_reader(sock: socket.socket):
        def read_exact(n: int) -> bytes:
            buf = b""
            while len(buf) < n:
                chunk = sock.recv(n - len(buf))
                if not chunk:
                    raise Libp2pError("connection closed")
                buf += chunk
            return buf

        return read_exact

    def _upgrade(self, sock: socket.socket, dialer: bool,
                 expected_peer_id: bytes | None = None) -> Connection:
        sock.settimeout(10.0)
        read_exact = self._sock_reader(sock)
        reader = _MsgReader(read_exact)
        if dialer:
            ms_negotiate_out(sock.sendall, reader, NOISE_PROTO)
            noise = initiator_handshake(self.key, sock.sendall, read_exact)
        else:
            got = ms_negotiate_in(sock.sendall, reader, {NOISE_PROTO})
            assert got == NOISE_PROTO
            noise = responder_handshake(self.key, sock.sendall, read_exact)

        # negotiate the muxer INSIDE the noise channel
        nbuf = [b""]

        def n_read_exact(n: int) -> bytes:
            while len(nbuf[0]) < n:
                nbuf[0] += noise.read(read_exact)
            out, nbuf[0] = nbuf[0][:n], nbuf[0][n:]
            return out

        def n_write(data: bytes) -> None:
            noise.write(sock.sendall, data)

        n_reader = _MsgReader(n_read_exact)
        if dialer:
            ms_negotiate_out(n_write, n_reader, YAMUX_PROTO)
        else:
            ms_negotiate_in(n_write, n_reader, {YAMUX_PROTO})

        def mux_recv() -> bytes:
            if nbuf[0]:
                out, nbuf[0] = nbuf[0], b""
                return out
            try:
                return noise.read(read_exact)
            except (Libp2pError, NoiseError, OSError):
                return b""

        # the handshake is over: drop its timeout BEFORE the muxer's
        # reader starts, or the reader's recv keeps it and closes any
        # connection idle for 10 s (under load, even before this thread
        # got back here to clear it)
        sock.settimeout(None)
        muxer = Session(n_write, mux_recv, is_dialer=dialer,
                        on_stream=None)
        conn = Connection(self, sock, noise, muxer)
        return self._adopt_connection(conn, expected_peer_id)

    def _adopt_connection(self, conn: Connection,
                          expected_peer_id: bytes | None) -> Connection:
        """Transport-agnostic admission: identity pinning, ban check,
        stream dispatch, duplicate replacement, subscription announce —
        shared by the TCP (noise+yamux) and QUIC upgrade paths."""
        # identity pinning (ADVICE r3): a dialer that knows who it meant to
        # reach (from the ENR) must reject an endpoint proving a different
        # identity — rust-libp2p rejects mismatched /p2p/<peer-id> the same
        # way.
        if expected_peer_id is not None and conn.peer_id != expected_peer_id:
            conn.close()
            raise Libp2pError(
                f"remote proved identity {conn.peer_id.hex()[:8]}, "
                f"expected {expected_peer_id.hex()[:8]}"
            )
        if self.peer_manager.is_banned(conn.peer_id.hex()):
            conn.close()
            raise Libp2pError(f"peer {conn.peer_id.hex()[:8]} is banned")
        muxer = conn.muxer
        muxer._on_stream = lambda st: self._spawn_stream_handler(conn, st)
        muxer._on_close = lambda: self._drop_connection(conn)
        muxer.start()
        old = self.connections.get(conn.peer_id)
        if old is not None and old is not conn:
            # replacing a live duplicate would leak its socket + pump
            # threads for the connection's remaining lifetime (ADVICE r3)
            self._drop_connection(old)
            old.close()
        self.connections[conn.peer_id] = conn
        PEERS_GAUGE.inc(labels=(conn.transport,))
        self.peer_manager.connect(conn.peer_id.hex())
        # announce our subscriptions
        if self.subscriptions:
            conn.send_gossip_rpc(encode_gossip_rpc(
                subscriptions=[(True, t) for t in self.subscriptions]
            ))
        return conn

    def _accept_loop(self) -> None:
        while self._running:
            try:
                sock, _addr = self.listener.accept()
            except OSError:
                break
            threading.Thread(
                target=self._inbound, args=(sock,), daemon=True
            ).start()

    def _inbound(self, sock: socket.socket) -> None:
        try:
            self._upgrade(sock, dialer=False)
        except (Libp2pError, NoiseError, OSError, PermissionError) as exc:
            log.debug("inbound upgrade failed: %s", exc)
            try:
                sock.close()
            except OSError:
                pass

    def dial(self, ip: str, port: int,
             expected_peer_id: bytes | None = None) -> Connection:
        """``expected_peer_id``: pin the identity the noise handshake must
        prove (derived from the discovered ENR's secp256k1 key) — a
        hijacked endpoint cannot impersonate the discovered peer."""
        sock = None
        try:
            sock = socket.create_connection((ip, port), timeout=10.0)
            conn = self._upgrade(sock, dialer=True,
                                 expected_peer_id=expected_peer_id)
        except Exception:
            DIALS.inc(labels=("tcp", "failed"))
            if sock is not None:
                try:
                    sock.close()
                except OSError:
                    pass
            raise
        DIALS.inc(labels=("tcp", "ok"))
        return conn

    # -- QUIC transport ----------------------------------------------------

    def _quic_accept_loop(self) -> None:
        while self._running:
            try:
                qconn = self.quic.accept(timeout=1.0)
            except QuicError:
                continue
            try:
                self._adopt_quic(qconn, None)
            except Libp2pError as exc:
                log.debug("inbound QUIC rejected: %s", exc)

    def _adopt_quic(self, qconn, expected_peer_id) -> Connection:
        """A handshake-complete QUIC connection IS secure channel + muxer:
        TLS proved the libp2p identity, QUIC streams replace yamux."""
        conn = Connection(self, None, _QuicIdentity(qconn.remote_peer_id),
                          qconn)
        return self._adopt_connection(conn, expected_peer_id)

    def dial_quic(self, ip: str, port: int,
                  expected_peer_id: bytes | None = None) -> Connection:
        if self.quic is None:
            raise Libp2pError("QUIC transport not enabled on this host")
        try:
            qconn = self.quic.dial(ip, port,
                                   expected_peer_id=expected_peer_id)
            conn = self._adopt_quic(qconn, expected_peer_id)
        except Exception:
            DIALS.inc(labels=("quic", "failed"))
            raise
        DIALS.inc(labels=("quic", "ok"))
        return conn

    def _drop_connection(self, conn: Connection) -> None:
        """Muxer died (peer hung up or send failed): forget the connection
        and record the disconnect, keeping `connections` bounded."""
        conn.alive = False
        if self.connections.get(conn.peer_id) is conn:
            del self.connections[conn.peer_id]
            PEERS_GAUGE.dec(labels=(conn.transport,))
        with self._mesh_lock:
            for mesh in self.mesh.values():
                mesh.discard(conn.peer_id)  # stale entries eat publishes
        info = self.peer_manager.peers.get(conn.peer_id.hex())
        if info is not None:
            info.connected = False
        # stop the muxer itself, not just the raw socket: a QUIC
        # connection has no conn.sock and would otherwise live on as a
        # zombie (threads, endpoint registry, inbound stream dispatch)
        try:
            conn.muxer.stop()
        except Exception:  # noqa: BLE001 — teardown must not throw
            pass
        if conn.sock is not None:
            try:
                conn.sock.close()
            except OSError:
                pass

    # -- per-stream server side -------------------------------------------

    def _spawn_stream_handler(self, conn: Connection, st: Stream) -> None:
        threading.Thread(
            target=self._serve_stream, args=(conn, st), daemon=True
        ).start()

    def _serve_stream(self, conn: Connection, st: Stream) -> None:
        try:
            reader = _MsgReader(lambda n: st.read(n, timeout=10.0))
            supported = {GOSSIP_PROTO} | {
                rpc_mod.protocol_id(n) for n in self.rpc_handlers
            }
            proto = ms_negotiate_in(st.write, reader, supported)
            if proto == GOSSIP_PROTO:
                self._serve_gossip(conn, st, reader)
            else:
                name = proto.split("/")[-3]
                self._serve_rpc(conn, st, name)
        except (*TRANSPORT_ERRORS, Libp2pError, NoiseError, ValueError) as exc:
            log.debug("stream from %s: %s", conn.peer_id.hex()[:8], exc)

    def _serve_gossip(self, conn: Connection, st: Stream,
                      reader: _MsgReader) -> None:
        idle_reader = _MsgReader(lambda n: st.read(n, timeout=3600.0))
        while self._running and conn.alive:
            try:
                n = idle_reader.read_uvarint(MAX_GOSSIP_RPC_SIZE)
            except Libp2pError:
                # oversized/malformed: drop + penalize, never buffer
                self.peer_manager.on_behaviour_penalty(
                    conn.peer_id.hex(), 3.0, "oversized gossip rpc"
                )
                st.reset()
                return
            raw = st.read(n, timeout=10.0)
            subs, msgs, control = decode_gossip_rpc(raw)
            for subscribed, topic in subs:
                (conn.topics.add if subscribed else conn.topics.discard)(topic)
            for topic, data in msgs:
                self._on_gossip_message(conn, topic, data)
            if control is not None:
                self._on_gossip_control(conn, control)

    def _on_gossip_message(self, conn: Connection, topic: str,
                           data: bytes) -> None:
        mid = message_id(topic, data)
        if not self.seen.observe(mid):
            return
        handler = self.subscriptions.get(topic)
        if handler is None:
            return
        if len(data) >= IDONTWANT_THRESHOLD:
            # v1.2: tell mesh peers we have this LARGE message before we
            # even validate it — duplicates of blocks/blobs are the
            # bandwidth the extension exists to save.  Pre-mesh
            # (bootstrap flood mode) every subscriber is a forwarder, so
            # they are the audience.
            with self._mesh_lock:
                mesh = set(self.mesh.get(topic) or ())
            targets = mesh or {
                pid for pid, c in self.connections.items()
                if c.alive and topic in c.topics
            }
            for pid in targets:
                if pid == conn.peer_id:
                    continue
                self._send_control(pid, GossipControl(idontwant=[mid]))
        try:
            payload = snappy.decompress_block(data)
        except snappy.SnappyError:
            self.peer_manager.on_invalid_message(conn.peer_id.hex(), topic)
            return
        outcome = handler(payload, conn.peer_id)
        if outcome == "accept":
            self.received.append((topic, payload))
            self.mcache.put(mid, topic, data)
            self.peer_manager.on_first_delivery(conn.peer_id.hex(), topic)
            self._forward(topic, data, skip=conn.peer_id, mid=mid)
        elif outcome == "reject":
            # per-topic invalid delivery: the squared penalty is what makes
            # repeat offenders fall past the ban threshold
            self.peer_manager.on_invalid_message(conn.peer_id.hex(), topic)
            if self.peer_manager.is_banned(conn.peer_id.hex()):
                self._drop_connection(conn)
                conn.close()

    def _on_gossip_control(self, conn: Connection, ctl: GossipControl) -> None:
        """GRAFT/PRUNE mesh membership; IHAVE -> IWANT for unseen ids;
        IWANT served from the mcache."""
        for topic in ctl.graft:
            if topic in self.subscriptions and self.peer_manager.accept_graft(
                conn.peer_id.hex()
            ):
                with self._mesh_lock:
                    self.mesh.setdefault(topic, set()).add(conn.peer_id)
            else:
                # not subscribed, or the peer's score fails the graft
                # gate: refuse (spec: prune back)
                self._send_control(conn.peer_id, GossipControl(prune=[topic]))
        for topic in ctl.prune:
            with self._mesh_lock:
                self.mesh.get(topic, set()).discard(conn.peer_id)
        for mid in ctl.idontwant[:256]:
            conn.dont_want[mid] = True
            while len(conn.dont_want) > 1024:
                conn.dont_want.popitem(last=False)
        wanted = []
        for topic, mids in ctl.ihave:
            if topic not in self.subscriptions:
                continue
            wanted.extend(m for m in mids if not self.seen.contains(m))
        if wanted:
            self._send_control(conn.peer_id, GossipControl(iwant=wanted[:64]))
        if ctl.iwant:
            # retransmission bound (gossip_retransmission analog): IWANT
            # floods re-serve full messages — rate limit per peer
            if not self.rate_limiter.allow(
                conn.peer_id.hex(), "gossip_iwant",
                cost=float(min(len(ctl.iwant), 64)),  # the actual serve cost
            ):
                self.peer_manager.on_behaviour_penalty(
                    conn.peer_id.hex(), 1.0, "iwant flood"
                )
                return
            sends = []
            for mid in ctl.iwant[:64]:
                entry = self.mcache.get(mid)
                if entry is not None:
                    sends.append(entry)
            if sends:
                conn.send_gossip_rpc(encode_gossip_rpc(publish=sends))

    def _serve_rpc(self, conn: Connection, st: Stream, name: str) -> None:
        body = st.read_until_eof(timeout=10.0)
        if not self.rate_limiter.allow(conn.peer_id.hex(), name):
            st.write(rpc_mod.encode_response_chunk(
                rpc_mod.RESOURCE_UNAVAILABLE, b""))
            st.close()
            return
        request = rpc_mod.decode_request(body) if body else b""
        code, resp = self.rpc_handlers[name](request, conn.peer_id)
        if code == rpc_mod.RAW_CHUNKS:
            # handler returned pre-encoded coded chunks (multi-chunk
            # responses: one chunk per block on the same stream)
            st.write(resp)
        else:
            st.write(rpc_mod.encode_response_chunk(code, resp))
        st.close()

    # -- public API --------------------------------------------------------

    def subscribe(self, topic: str, handler: Callable) -> None:
        self.subscriptions[topic] = handler
        rpc = encode_gossip_rpc(subscriptions=[(True, topic)])
        for conn in list(self.connections.values()):
            conn.send_gossip_rpc(rpc)

    def publish(self, topic: str, payload: bytes) -> bytes:
        compressed = snappy.compress_block(payload)
        mid = message_id(topic, compressed)
        self.seen.observe(mid)
        self.mcache.put(mid, topic, compressed)
        self._forward(topic, compressed, skip=None, mid=mid)
        return mid

    def _forward(self, topic: str, compressed: bytes, skip: bytes | None,
                 mid: bytes) -> None:
        """Route to the topic mesh (gossipsub); peers outside the mesh
        learn of the message via heartbeat IHAVE + IWANT.  With no mesh
        formed yet (pre-heartbeat bootstrap), flood all subscribers.
        Peers that sent IDONTWANT for ``mid`` are skipped (v1.2; callers
        always hold the id — rehashing MBs here would double the relay
        path's hashing cost)."""
        rpc = encode_gossip_rpc(publish=[(topic, compressed)])
        live = {
            pid for pid, c in self.connections.items() if c.alive
        }
        with self._mesh_lock:
            mesh = set(self.mesh.get(topic) or ()) & live
        for conn in list(self.connections.values()):
            if not conn.alive:
                self._drop_connection(conn)
                continue
            if conn.peer_id == skip or topic not in conn.topics:
                continue
            if mesh and conn.peer_id not in mesh:
                continue
            if mid in conn.dont_want:
                continue  # the peer already has it: save the bandwidth
            conn.send_gossip_rpc(rpc)
