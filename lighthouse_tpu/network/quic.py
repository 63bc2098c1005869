"""QUIC v1 transport (RFC 9000/9001/9002) for libp2p.

The reference's network service builds a TCP+QUIC transport pair
(`lighthouse_network/src/service/utils.rs:39-48`, quinn under the
libp2p-quic crate); this is the QUIC half, from the wire up, sharing
nothing with the TCP path but the stream API: QUIC natively provides
the secure channel (TLS 1.3, `tls13.py`) and stream multiplexing, so a
`QuicConnection` replaces noise+yamux wholesale — `libp2p.py` consumes
it through the same `open_stream`/`accept_stream` muxer surface and the
same `Stream.read/write/close/reset` contract as a yamux `Session`.

Layout of this module:
  - varint codec (RFC 9000 §16)
  - packet protection (RFC 9001 §5): HKDF-Expand-Label, Initial
    secrets from the client DCID, AES-128-GCM payload AEAD, AES-ECB
    header-protection masks — pinned to RFC 9001 Appendix A vectors
    in `tests/test_quic.py`
  - long/short header build+parse, packet-number encode/decode
    (RFC 9000 §17, A.2/A.3 sample algorithms re-derived)
  - frames (PADDING/PING/ACK/CRYPTO/STREAM/MAX_*/CLOSE/…, §19)
  - `QuicConnection`: the three packet-number spaces, CRYPTO flow
    into the TLS engine, ACK tracking, PTO retransmit (RFC 9002 §6),
    bidirectional streams with connection+stream flow control
  - `QuicEndpoint`: one UDP socket, DCID demux, dial/accept

Deliberate scope cuts (documented, not hidden): no connection
migration / NEW_CONNECTION_ID rotation, no 0-RTT, no Retry tokens,
no key update, v1 only.  None of these gate interop for a
lighthouse-style node mesh; all are additive later.
"""

from __future__ import annotations

import hashlib
import hmac as hmac_mod
import logging
import os
import secrets
import socket
import struct
import threading
import time
from collections import OrderedDict, deque

from cryptography.hazmat.primitives.ciphers import Cipher, algorithms, modes
from cryptography.hazmat.primitives.ciphers.aead import AESGCM

log = logging.getLogger("quic")

QUIC_V1 = 0x00000001
# RFC 9001 §5.2: the v1 Initial salt (a protocol constant, like a DST).
INITIAL_SALT = bytes.fromhex("38762cf7f55934b34d179ae6a4c80cadccbb7f0a")

MAX_UDP_PAYLOAD = 1452  # conservative for loopback/ethernet
MIN_CLIENT_INITIAL = 1200  # RFC 9000 §8.1 anti-amplification pad


class QuicError(Exception):
    pass


# ---------------------------------------------------------------------------
# varints (RFC 9000 §16): 2-bit length prefix, big-endian
# ---------------------------------------------------------------------------

def enc_varint(v: int) -> bytes:
    if v < 0x40:
        return bytes([v])
    if v < 0x4000:
        return struct.pack(">H", v | 0x4000)
    if v < 0x4000_0000:
        return struct.pack(">I", v | 0x8000_0000)
    if v < 0x4000_0000_0000_0000:
        return struct.pack(">Q", v | 0xC000_0000_0000_0000)
    raise QuicError(f"varint too large: {v}")


def dec_varint(buf: bytes, pos: int) -> tuple[int, int]:
    if pos >= len(buf):
        raise QuicError("varint: truncated")
    first = buf[pos]
    ln = 1 << (first >> 6)
    if pos + ln > len(buf):
        raise QuicError("varint: truncated body")
    v = first & 0x3F
    for i in range(1, ln):
        v = (v << 8) | buf[pos + i]
    return v, pos + ln


# ---------------------------------------------------------------------------
# HKDF + packet protection (RFC 9001 §5)
# ---------------------------------------------------------------------------

def hkdf_extract(salt: bytes, ikm: bytes) -> bytes:
    return hmac_mod.new(salt, ikm, hashlib.sha256).digest()


def hkdf_expand(prk: bytes, info: bytes, length: int) -> bytes:
    out = b""
    block = b""
    counter = 1
    while len(out) < length:
        block = hmac_mod.new(
            prk, block + info + bytes([counter]), hashlib.sha256
        ).digest()
        out += block
        counter += 1
    return out[:length]


def hkdf_expand_label(secret: bytes, label: str, context: bytes,
                      length: int) -> bytes:
    """RFC 8446 §7.1 HkdfLabel: uint16 length || "tls13 "+label || context."""
    full = b"tls13 " + label.encode()
    info = (struct.pack(">H", length) + bytes([len(full)]) + full
            + bytes([len(context)]) + context)
    return hkdf_expand(secret, info, length)


class DirectionKeys:
    """AEAD + header-protection keys for one direction at one level."""

    def __init__(self, secret: bytes):
        self.secret = secret
        self.key = hkdf_expand_label(secret, "quic key", b"", 16)
        self.iv = hkdf_expand_label(secret, "quic iv", b"", 12)
        self.hp = hkdf_expand_label(secret, "quic hp", b"", 16)
        self._aead = AESGCM(self.key)

    def _nonce(self, pn: int) -> bytes:
        return bytes(a ^ b for a, b in zip(self.iv, pn.to_bytes(12, "big")))

    def seal(self, pn: int, header: bytes, payload: bytes) -> bytes:
        return self._aead.encrypt(self._nonce(pn), payload, header)

    def open(self, pn: int, header: bytes, ciphertext: bytes) -> bytes:
        return self._aead.decrypt(self._nonce(pn), ciphertext, header)

    def hp_mask(self, sample: bytes) -> bytes:
        enc = Cipher(algorithms.AES(self.hp), modes.ECB()).encryptor()
        return enc.update(sample)[:5]


def initial_keys(client_dcid: bytes) -> tuple[DirectionKeys, DirectionKeys]:
    """(client_keys, server_keys) for the Initial space (RFC 9001 §5.2)."""
    initial_secret = hkdf_extract(INITIAL_SALT, client_dcid)
    client = hkdf_expand_label(initial_secret, "client in", b"", 32)
    server = hkdf_expand_label(initial_secret, "server in", b"", 32)
    return DirectionKeys(client), DirectionKeys(server)


# ---------------------------------------------------------------------------
# packet numbers (RFC 9000 §17.1, A.2/A.3)
# ---------------------------------------------------------------------------

def encode_pn(pn: int, largest_acked: int) -> bytes:
    """Smallest encoding whose window covers twice the unacked range."""
    num_unacked = pn + 1 if largest_acked < 0 else pn - largest_acked
    min_bits = num_unacked.bit_length() + 1
    nbytes = min(4, max(1, (min_bits + 7) // 8))
    return (pn & ((1 << (8 * nbytes)) - 1)).to_bytes(nbytes, "big")


def decode_pn(truncated: int, pn_nbits: int, largest_pn: int) -> int:
    expected = largest_pn + 1
    win = 1 << pn_nbits
    hwin = win // 2
    mask = win - 1
    candidate = (expected & ~mask) | truncated
    if candidate <= expected - hwin and candidate < (1 << 62) - win:
        return candidate + win
    if candidate > expected + hwin and candidate >= win:
        return candidate - win
    return candidate


# ---------------------------------------------------------------------------
# headers (RFC 9000 §17.2/17.3)
# ---------------------------------------------------------------------------

PKT_INITIAL = 0
PKT_0RTT = 1
PKT_HANDSHAKE = 2
PKT_RETRY = 3
PKT_1RTT = 4  # internal tag (short header)

LEVEL_INITIAL = 0
LEVEL_HANDSHAKE = 1
LEVEL_APP = 2

_LEVEL_FOR_TYPE = {PKT_INITIAL: LEVEL_INITIAL, PKT_HANDSHAKE: LEVEL_HANDSHAKE,
                   PKT_1RTT: LEVEL_APP}


class Packet:
    """One parsed (still protected) QUIC packet from a datagram."""

    __slots__ = ("ptype", "version", "dcid", "scid", "token",
                 "header_len", "pn_offset", "payload_end", "raw")

    def __init__(self):
        self.token = b""


def build_long_header(ptype: int, dcid: bytes, scid: bytes, pn_bytes: bytes,
                      payload_len: int, token: bytes = b"") -> bytes:
    first = 0xC0 | (ptype << 4) | (len(pn_bytes) - 1)
    hdr = bytearray([first])
    hdr += struct.pack(">I", QUIC_V1)
    hdr += bytes([len(dcid)]) + dcid
    hdr += bytes([len(scid)]) + scid
    if ptype == PKT_INITIAL:
        hdr += enc_varint(len(token)) + token
    hdr += enc_varint(payload_len + len(pn_bytes) + 16)  # +16 AEAD tag
    hdr += pn_bytes
    return bytes(hdr)


def build_short_header(dcid: bytes, pn_bytes: bytes,
                       key_phase: int = 0) -> bytes:
    first = 0x40 | (key_phase << 2) | (len(pn_bytes) - 1)
    return bytes([first]) + dcid + pn_bytes


def parse_packet(datagram: bytes, pos: int, local_cid_len: int) -> Packet:
    """Parse one (coalesced) packet's envelope; protection not yet removed.

    For short-header packets the DCID length is not self-describing —
    the endpoint supplies its own connection-id length.
    """
    pkt = Packet()
    pkt.raw = datagram
    if pos >= len(datagram):
        raise QuicError("empty packet")
    first = datagram[pos]
    if first & 0x80:  # long header
        if pos + 6 > len(datagram):
            raise QuicError("truncated long header")
        pkt.version = struct.unpack(">I", datagram[pos + 1:pos + 5])[0]
        p = pos + 5
        dlen = datagram[p]; p += 1
        pkt.dcid = datagram[p:p + dlen]; p += dlen
        slen = datagram[p]; p += 1
        pkt.scid = datagram[p:p + slen]; p += slen
        pkt.ptype = (first >> 4) & 0x03
        if pkt.version != QUIC_V1:
            raise QuicError(f"unsupported version {pkt.version:#x}")
        if pkt.ptype == PKT_INITIAL:
            tlen, p = dec_varint(datagram, p)
            pkt.token = datagram[p:p + tlen]; p += tlen
        elif pkt.ptype == PKT_RETRY:
            raise QuicError("retry not supported")
        length, p = dec_varint(datagram, p)
        pkt.pn_offset = p
        pkt.payload_end = p + length
        if pkt.payload_end > len(datagram):
            raise QuicError("packet length exceeds datagram")
    else:
        if not first & 0x40:
            raise QuicError("fixed bit clear")
        pkt.ptype = PKT_1RTT
        pkt.version = QUIC_V1
        p = pos + 1
        pkt.dcid = datagram[p:p + local_cid_len]
        p += local_cid_len
        pkt.scid = b""
        pkt.pn_offset = p
        pkt.payload_end = len(datagram)
    pkt.header_len = pos
    return pkt


def protect(keys: DirectionKeys, header: bytes, pn: int, pn_len: int,
            payload: bytes) -> bytes:
    """AEAD-seal then header-protect one packet (RFC 9001 §5.3-5.4)."""
    sealed = keys.seal(pn, header, payload)
    out = bytearray(header + sealed)
    pn_offset = len(header) - pn_len
    sample = bytes(out[pn_offset + 4:pn_offset + 20])
    mask = keys.hp_mask(sample)
    out[0] ^= mask[0] & (0x0F if out[0] & 0x80 else 0x1F)
    for i in range(pn_len):
        out[pn_offset + i] ^= mask[1 + i]
    return bytes(out)


def unprotect(keys: DirectionKeys, datagram: bytes, pkt: Packet,
              largest_pn: int) -> tuple[int, bytes]:
    """Remove header+packet protection; returns (pn, plaintext payload)."""
    buf = bytearray(datagram)
    po = pkt.pn_offset
    # minimum protected region: 4 pn-candidate bytes + 16-byte sample
    # (equivalently pn+payload+tag >= 20); shorter is garbage, not a crash
    if pkt.payload_end - po < 20:
        raise QuicError("packet too short for header-protection sample")
    sample = bytes(buf[po + 4:po + 20])
    mask = keys.hp_mask(sample)
    first = buf[pkt.header_len] ^ (mask[0] & (0x0F if buf[pkt.header_len] & 0x80
                                              else 0x1F))
    buf[pkt.header_len] = first
    pn_len = (first & 0x03) + 1
    for i in range(pn_len):
        buf[po + i] ^= mask[1 + i]
    truncated = int.from_bytes(bytes(buf[po:po + pn_len]), "big")
    pn = decode_pn(truncated, pn_len * 8, largest_pn)
    header = bytes(buf[pkt.header_len:po + pn_len])
    ciphertext = bytes(buf[po + pn_len:pkt.payload_end])
    try:
        plain = keys.open(pn, header, ciphertext)
    except Exception as exc:  # InvalidTag
        raise QuicError(f"AEAD open failed: {exc}") from exc
    return pn, plain


# ---------------------------------------------------------------------------
# transport parameters (RFC 9000 §18)
# ---------------------------------------------------------------------------

TP_ORIGINAL_DCID = 0x00
TP_MAX_IDLE_TIMEOUT = 0x01
TP_MAX_UDP_PAYLOAD = 0x03
TP_INITIAL_MAX_DATA = 0x04
TP_INITIAL_MAX_STREAM_DATA_BIDI_LOCAL = 0x05
TP_INITIAL_MAX_STREAM_DATA_BIDI_REMOTE = 0x06
TP_INITIAL_MAX_STREAM_DATA_UNI = 0x07
TP_INITIAL_MAX_STREAMS_BIDI = 0x08
TP_INITIAL_MAX_STREAMS_UNI = 0x09
TP_INITIAL_SCID = 0x0F

STREAM_WINDOW = 1 << 20  # per-stream flow-control window
CONN_WINDOW = 4 << 20    # connection-level window
MAX_INBOUND_STREAMS = 4096  # active-stream cap: remote-controlled memory


def encode_transport_params(params: dict[int, object]) -> bytes:
    out = b""
    for key, val in params.items():
        body = val if isinstance(val, bytes) else enc_varint(val)
        out += enc_varint(key) + enc_varint(len(body)) + body
    return out


def decode_transport_params(raw: bytes) -> dict[int, bytes]:
    out: dict[int, bytes] = {}
    pos = 0
    while pos < len(raw):
        key, pos = dec_varint(raw, pos)
        ln, pos = dec_varint(raw, pos)
        out[key] = raw[pos:pos + ln]
        pos += ln
    return out


def tp_int(params: dict[int, bytes], key: int, default: int) -> int:
    raw = params.get(key)
    if raw is None:
        return default
    return dec_varint(raw, 0)[0]


# ---------------------------------------------------------------------------
# frames (RFC 9000 §19)
# ---------------------------------------------------------------------------

F_PADDING = 0x00
F_PING = 0x01
F_ACK = 0x02
F_ACK_ECN = 0x03
F_RESET_STREAM = 0x04
F_STOP_SENDING = 0x05
F_CRYPTO = 0x06
F_NEW_TOKEN = 0x07
F_STREAM_BASE = 0x08  # ..0x0f: OFF=0x04 LEN=0x02 FIN=0x01
F_MAX_DATA = 0x10
F_MAX_STREAM_DATA = 0x11
F_MAX_STREAMS_BIDI = 0x12
F_MAX_STREAMS_UNI = 0x13
F_DATA_BLOCKED = 0x14
F_STREAM_DATA_BLOCKED = 0x15
F_STREAMS_BLOCKED_BIDI = 0x16
F_STREAMS_BLOCKED_UNI = 0x17
F_NEW_CONNECTION_ID = 0x18
F_RETIRE_CONNECTION_ID = 0x19
F_PATH_CHALLENGE = 0x1A
F_PATH_RESPONSE = 0x1B
F_CONNECTION_CLOSE = 0x1C
F_CONNECTION_CLOSE_APP = 0x1D
F_HANDSHAKE_DONE = 0x1E

# RFC 9000 §12.4 (table 3): frames only valid in 1-RTT packets.  STREAM
# (0x08..0x0f) is checked by range alongside this set.  An Initial/Handshake
# packet carrying one of these is a protocol violation — enforcing it keeps
# pre-handshake-authentication packets from touching stream/flow-control
# state (or faking handshake confirmation).
_APP_ONLY_FRAMES = frozenset({
    F_RESET_STREAM, F_STOP_SENDING, F_NEW_TOKEN,
    F_MAX_DATA, F_MAX_STREAM_DATA, F_MAX_STREAMS_BIDI, F_MAX_STREAMS_UNI,
    F_DATA_BLOCKED, F_STREAM_DATA_BLOCKED,
    F_STREAMS_BLOCKED_BIDI, F_STREAMS_BLOCKED_UNI,
    F_NEW_CONNECTION_ID, F_RETIRE_CONNECTION_ID,
    F_PATH_CHALLENGE, F_PATH_RESPONSE,
    F_CONNECTION_CLOSE_APP, F_HANDSHAKE_DONE,
})


def _enc_ack_frame(ranges: list[list[int]], ack_delay_us: int = 0) -> bytes:
    """ranges: sorted descending, non-overlapping [lo, hi] pairs."""
    largest = ranges[0][1]
    out = bytearray(enc_varint(F_ACK))
    out += enc_varint(largest)
    out += enc_varint(ack_delay_us >> 3)  # default ack_delay_exponent
    out += enc_varint(len(ranges) - 1)
    out += enc_varint(ranges[0][1] - ranges[0][0])
    prev_lo = ranges[0][0]
    for lo, hi in ranges[1:]:
        out += enc_varint(prev_lo - hi - 2)  # gap
        out += enc_varint(hi - lo)
        prev_lo = lo
    return bytes(out)


class _RecvState:
    """Packet-number tracking for one space's receive side."""

    def __init__(self):
        self.ranges: list[list[int]] = []  # [lo, hi] descending
        self.largest = -1
        self.ack_pending = False
        self.unacked_eliciting = 0     # ack-eliciting packets since last ACK
        self.oldest_unacked: float | None = None

    def register(self, pn: int) -> bool:
        """Record pn; returns False when it is a duplicate."""
        self.largest = max(self.largest, pn)
        for rng in self.ranges:
            if rng[0] - 1 <= pn <= rng[1] + 1:
                if rng[0] <= pn <= rng[1]:
                    return False
                if pn == rng[1] + 1:
                    rng[1] = pn
                else:
                    rng[0] = pn
                self._merge()
                return True
        self.ranges.append([pn, pn])
        self.ranges.sort(key=lambda r: -r[1])
        del self.ranges[32:]  # bound state
        return True

    def _merge(self) -> None:
        self.ranges.sort(key=lambda r: -r[1])
        merged: list[list[int]] = []
        for rng in self.ranges:
            if merged and rng[1] >= merged[-1][0] - 1:
                merged[-1][0] = min(merged[-1][0], rng[0])
            else:
                merged.append(rng)
        self.ranges = merged


class _SentPacket:
    __slots__ = ("pn", "time", "ack_eliciting", "frames", "size")

    def __init__(self, pn, now, ack_eliciting, frames, size):
        self.pn = pn
        self.time = now
        self.ack_eliciting = ack_eliciting
        self.frames = frames  # retransmittable descriptors
        self.size = size


class _Space:
    """One packet-number space (Initial / Handshake / 1-RTT)."""

    def __init__(self):
        self.next_pn = 0
        self.largest_acked = -1
        self.recv = _RecvState()
        self.sent: dict[int, _SentPacket] = {}
        # CRYPTO send: queued (offset, bytes); offset counter
        self.crypto_offset = 0
        self.crypto_pending: deque[tuple[int, bytes]] = deque()
        # CRYPTO recv reassembly
        self.crypto_frags: dict[int, bytes] = {}
        self.crypto_delivered = 0
        self.inflight = 0  # bytes of unacked ack-eliciting packets


class QuicStreamError(QuicError):
    pass


class QuicStream:
    """One bidirectional QUIC stream with the yamux `Stream` contract:
    exact-n blocking reads, EOF-terminated bodies, write-side FIN via
    ``close()``, abortive ``reset()`` — so `libp2p.py` treats a QUIC
    connection exactly like a yamux session (`yamux.py:45`)."""

    def __init__(self, conn: "QuicConnection", stream_id: int):
        self.conn = conn
        self.id = stream_id
        self._rx: deque[bytes] = deque()
        self._rx_frags: dict[int, bytes] = {}
        self._rx_delivered = 0   # contiguous bytes handed to _rx
        self._rx_consumed = 0    # bytes the application has read
        self._rx_limit = STREAM_WINDOW  # what we advertised
        self._rx_fin: int | None = None  # final size once FIN seen
        self._rx_highest = 0     # highest received offset (flow control)
        self._reset_err: int | None = None
        self._buf = b""
        self._send_offset = 0
        self._send_limit = STREAM_WINDOW  # peer's advertised limit
        self._closed_local = False
        self._closed_remote = False

    # -- write side --------------------------------------------------------

    def write(self, data: bytes, flags: int = 0, timeout: float = 30.0) -> None:
        if self._closed_local:
            raise QuicStreamError(f"stream {self.id} closed")
        conn = self.conn
        view = memoryview(data)
        deadline = time.monotonic() + timeout
        while len(view):
            with conn._cv:
                if conn._closed:
                    raise QuicStreamError("connection closed")
                allowed = min(
                    self._send_limit - self._send_offset,
                    conn._send_max_data - conn._send_data_total,
                )
                if allowed <= 0:
                    if not conn._cv.wait(deadline - time.monotonic()):
                        raise QuicStreamError(
                            f"stream {self.id}: window starved for {timeout}s")
                    continue
                chunk = bytes(view[:allowed])
                conn._queue_stream(self.id, self._send_offset, chunk, False)
                self._send_offset += len(chunk)
                conn._send_data_total += len(chunk)
            conn._flush()
            view = view[len(chunk):]

    def close(self) -> None:
        if self._closed_local:
            return
        self._closed_local = True
        conn = self.conn
        with conn._cv:
            conn._queue_stream(self.id, self._send_offset, b"", True)
            conn._maybe_gc_stream(self)
        conn._flush()

    def reset(self) -> None:
        self._closed_local = True
        conn = self.conn
        with conn._cv:
            conn._queue_frame(
                LEVEL_APP,
                ("raw", enc_varint(F_RESET_STREAM) + enc_varint(self.id)
                 + enc_varint(0) + enc_varint(self._send_offset)))
            conn._maybe_gc_stream(self)
        conn._flush()

    # -- read side ---------------------------------------------------------

    def _pump(self, timeout: float):
        conn = self.conn
        deadline = time.monotonic() + timeout
        chunk = None
        with conn._cv:
            while True:
                if self._rx:
                    chunk = self._rx.popleft()
                    self._rx_consumed += len(chunk)
                    conn._credit_consumed(self, len(chunk))
                    break
                if self._reset_err is not None:
                    raise QuicStreamError(
                        f"stream {self.id} reset by peer ({self._reset_err})")
                if self._closed_remote:
                    return None
                if conn._closed:
                    raise QuicStreamError("connection closed")
                if not conn._cv.wait(deadline - time.monotonic()):
                    raise QuicStreamError(f"stream {self.id}: read timeout")
        # outside the lock: push any MAX_DATA/MAX_STREAM_DATA updates the
        # consumption queued — a blocked peer only unblocks when they SEND
        conn._flush()
        return chunk

    def read(self, n: int, timeout: float = 5.0) -> bytes:
        while len(self._buf) < n:
            chunk = self._pump(timeout)
            if chunk is None:
                raise QuicStreamError(
                    f"stream {self.id}: EOF at {len(self._buf)}/{n}")
            self._buf += chunk
        out, self._buf = self._buf[:n], self._buf[n:]
        return out

    def read_until_eof(self, timeout: float = 5.0,
                       limit: int = 1 << 24) -> bytes:
        while True:
            chunk = self._pump(timeout)
            if chunk is None:
                break
            self._buf += chunk
            if len(self._buf) > limit:
                raise QuicStreamError("stream body over limit")
        out, self._buf = self._buf, b""
        return out

    def read_available(self, timeout: float = 5.0) -> bytes:
        if not self._buf:
            chunk = self._pump(timeout)
            if chunk is not None:
                self._buf += chunk
        out, self._buf = self._buf, b""
        return out

    # -- connection-side delivery (conn lock held) -------------------------

    def _on_stream_frame(self, offset: int, data: bytes, fin: bool) -> None:
        if fin:
            self._rx_fin = offset + len(data)
        if offset + len(data) > self._rx_limit:
            raise QuicError(f"stream {self.id}: flow-control overrun")
        if data and offset + len(data) > self._rx_delivered:
            self._rx_frags[offset] = data
            # drain contiguous prefix
            while True:
                for off, frag in list(self._rx_frags.items()):
                    if off <= self._rx_delivered < off + len(frag):
                        self._rx.append(frag[self._rx_delivered - off:])
                        self._rx_delivered = off + len(frag)
                        del self._rx_frags[off]
                        break
                    if off + len(frag) <= self._rx_delivered:
                        del self._rx_frags[off]
                        break
                else:
                    break
        if self._rx_fin is not None and self._rx_delivered == self._rx_fin:
            self._closed_remote = True

    def _on_reset(self, err: int) -> None:
        self._reset_err = err
        self._closed_remote = True


# ---------------------------------------------------------------------------
# connection
# ---------------------------------------------------------------------------

CID_LEN = 8
PTO_INITIAL = 0.4  # seconds; doubles per retry
PTO_MAX_RETRIES = 8
IDLE_TIMEOUT = 30.0
# Fixed congestion window, in bytes: bounds the burst a bulk write can
# blast into a UDP socket (loopback loss at unbounded bursts is near
# total); ACK arrival re-opens the window via the post-datagram flush.
CWND_BYTES = 1 << 21
# Post-handshake datagram ceiling when the peer's max_udp_payload_size
# allows it: QUIC's own PMTU signal.  16K datagrams cut the per-packet
# Python+AEAD overhead 12x on loopback/jumbo paths; 1452 remains the
# conservative floor for handshake flights and modest peers.
BIG_UDP_PAYLOAD = 1 << 14


class QuicConnection:
    """One QUIC connection: handshake, spaces, streams, recovery.

    Muxer surface (`open_stream`/`accept_stream`/`stop`) matches
    `yamux.Session` so `libp2p.Connection` drives either transparently.
    """

    def __init__(self, endpoint: "QuicEndpoint", peer_addr, is_client: bool,
                 original_dcid: bytes | None = None):
        from . import tls13 as _tls  # late import: tls13 imports our hkdf

        self.endpoint = endpoint
        self.peer_addr = peer_addr
        self.is_client = is_client
        self._cv = threading.Condition()
        self._closed = False
        self.close_reason: str | None = None

        self.local_cid = secrets.token_bytes(CID_LEN)
        if is_client:
            self.original_dcid = secrets.token_bytes(CID_LEN)
            self.peer_cid = self.original_dcid  # until ServerHello arrives
        else:
            self.original_dcid = original_dcid
            self.peer_cid = None  # learned from the client's SCID

        self.spaces = {lvl: _Space() for lvl in
                       (LEVEL_INITIAL, LEVEL_HANDSHAKE, LEVEL_APP)}
        ckeys, skeys = initial_keys(self.original_dcid)
        if is_client:
            self.send_keys = {LEVEL_INITIAL: ckeys}
            self.recv_keys = {LEVEL_INITIAL: skeys}
        else:
            self.send_keys = {LEVEL_INITIAL: skeys}
            self.recv_keys = {LEVEL_INITIAL: ckeys}

        tp = {
            TP_MAX_IDLE_TIMEOUT: int(IDLE_TIMEOUT * 1000),
            TP_MAX_UDP_PAYLOAD: 65527,
            TP_INITIAL_MAX_DATA: CONN_WINDOW,
            TP_INITIAL_MAX_STREAM_DATA_BIDI_LOCAL: STREAM_WINDOW,
            TP_INITIAL_MAX_STREAM_DATA_BIDI_REMOTE: STREAM_WINDOW,
            TP_INITIAL_MAX_STREAM_DATA_UNI: STREAM_WINDOW,
            TP_INITIAL_MAX_STREAMS_BIDI: 1 << 40,
            TP_INITIAL_MAX_STREAMS_UNI: 0,
            TP_INITIAL_SCID: self.local_cid,
        }
        if not is_client:
            tp[TP_ORIGINAL_DCID] = self.original_dcid
        self.tls = _tls.TlsEngine(
            "client" if is_client else "server",
            endpoint.identity_key, encode_transport_params(tp),
            cert=getattr(endpoint, "cert", None))

        self.handshake_complete = threading.Event()
        self.handshake_confirmed = False
        self._handshake_done_queued = False
        self.remote_peer_id: bytes | None = None

        # streams; _dead holds tombstoned ids so late retransmits for a
        # collected stream don't resurrect it as a fresh inbound stream
        self.streams: dict[int, QuicStream] = {}
        self._dead_streams: "OrderedDict[int, bool]" = OrderedDict()
        self._next_stream = 0 if is_client else 1
        self._accept_q: deque[QuicStream] = deque()
        self._peer_tp: dict[int, bytes] | None = None

        # flow control: what the peer lets us send / what we let them.
        # per-stream initial limits come from the peer's transport params
        # at handshake completion (RFC 9000 section 18.2): _ours applies
        # to streams WE initiate (their ..._bidi_remote), _theirs to
        # streams THEY initiate (their ..._bidi_local)
        self._peer_sd_ours = STREAM_WINDOW
        self._peer_sd_theirs = STREAM_WINDOW
        self._send_max_data = CONN_WINDOW
        self._send_data_total = 0
        self._recv_max_data = CONN_WINDOW
        self._recv_data_total = 0
        self._recv_consumed_total = 0

        # frame queues: level -> deque of descriptors
        #   ("raw", bytes)                      control, retransmit verbatim
        #   ("stream", sid, offset, data, fin)
        self._pending: dict[int, deque] = {
            LEVEL_INITIAL: deque(), LEVEL_HANDSHAKE: deque(),
            LEVEL_APP: deque()}
        self._undecryptable: list[tuple[Packet, bytes]] = []
        # levels whose keys were discarded (RFC 9001 §4.9): packets there
        # are DROPPED, not parked — the keys are never coming back
        self._discarded_levels: set[int] = set()
        self._pto_count = 0
        self._max_payload = MAX_UDP_PAYLOAD
        self._last_rx = time.monotonic()
        self._last_tx = time.monotonic()
        self._amp_budget = 0  # server: 3x bytes received pre-validation
        self._addr_validated = is_client

        if is_client:
            # queue the first flight; dial() flushes AFTER registering the
            # connection for demux, else a same-host server can reply
            # before we are routable and the whole flight rides one PTO
            self.tls.start()
            with self._cv:
                self._drive_tls_locked()

    # -- muxer surface (yamux.Session contract) ---------------------------

    # callback-driven inbound streams, as yamux.Session exposes them:
    # libp2p sets these then calls start()
    _on_stream = None
    _on_close = None

    def start(self) -> None:
        threading.Thread(target=self._stream_accept_loop,
                         name=f"quic-streams-{self.local_cid.hex()[:6]}",
                         daemon=True).start()

    def _stream_accept_loop(self) -> None:
        while True:
            try:
                st = self.accept_stream(timeout=30.0)
            except QuicError:
                if self._closed:
                    cb, self._on_close = self._on_close, None
                    if cb:
                        cb()
                    return
                continue  # idle window with no inbound streams
            if self._on_stream is not None:
                self._on_stream(st)

    def open_stream(self) -> QuicStream:
        with self._cv:
            if self._closed:
                raise QuicError("connection closed")
            sid = self._next_stream
            self._next_stream += 4
            st = QuicStream(self, sid)
            st._send_limit = self._peer_sd_ours
            self.streams[sid] = st
            return st

    def accept_stream(self, timeout: float = 5.0) -> QuicStream:
        deadline = time.monotonic() + timeout
        with self._cv:
            while not self._accept_q:
                if self._closed:
                    raise QuicError("connection closed")
                if not self._cv.wait(deadline - time.monotonic()):
                    raise QuicError("accept_stream timeout")
            return self._accept_q.popleft()

    def stop(self) -> None:
        self.close("closed by application")

    def close(self, reason: str = "", error_code: int = 0) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            self.close_reason = reason
            # highest level with live send keys (Initial/Handshake may be
            # discarded per RFC 9001 §4.9 — a CONNECTION_CLOSE can only
            # ride a level both sides still hold keys for)
            level = next((lv for lv in (LEVEL_APP, LEVEL_HANDSHAKE,
                                        LEVEL_INITIAL)
                          if lv in self.send_keys), None)
            frame = (enc_varint(F_CONNECTION_CLOSE) + enc_varint(error_code)
                     + enc_varint(0) + enc_varint(len(reason))
                     + reason.encode())
            if level is not None:
                try:
                    self._send_one(level, [frame], ack_eliciting=False)
                except OSError:
                    pass
            self._cv.notify_all()
        self.endpoint._forget(self)

    # -- TLS plumbing ------------------------------------------------------

    def _drive_tls_locked(self) -> None:
        for level, msg in self.tls.take_output():
            space = self.spaces[level]
            self._pending[level].append(
                ("crypto", space.crypto_offset, msg))
            space.crypto_offset += len(msg)
        for level, (c_secret, s_secret) in self.tls.secrets.items():
            if level in self._discarded_levels:
                continue  # retired for good (RFC 9001 §4.9)
            mine, theirs = ((c_secret, s_secret) if self.is_client
                            else (s_secret, c_secret))
            if level not in self.send_keys:
                self.send_keys[level] = DirectionKeys(mine)
            # RFC 9001 section 5.7: the server must not process 1-RTT
            # data before the client proves its identity — installing
            # the receive keys only at handshake completion parks early
            # stream data in the (bounded) undecryptable buffer instead
            # of committing flow-control memory to unauthenticated peers
            if level not in self.recv_keys:
                if (level == LEVEL_APP and not self.is_client
                        and not self.tls.complete):
                    continue
                self.recv_keys[level] = DirectionKeys(theirs)
        if self.tls.complete and not self.handshake_complete.is_set():
            self.remote_peer_id = self.tls.peer_id
            self._peer_tp = decode_transport_params(
                self.tls.peer_transport_params)
            self._validate_peer_tp()
            self._send_max_data = tp_int(
                self._peer_tp, TP_INITIAL_MAX_DATA, 0)
            self._peer_sd_ours = tp_int(
                self._peer_tp,
                TP_INITIAL_MAX_STREAM_DATA_BIDI_REMOTE, 0)
            self._peer_sd_theirs = tp_int(
                self._peer_tp,
                TP_INITIAL_MAX_STREAM_DATA_BIDI_LOCAL, 0)
            # never exceed what the peer advertised (RFC 9000 section
            # 18.2 MUST NOT); 1200 is the protocol floor, BIG the cap
            self._max_payload = min(
                max(1200,
                    tp_int(self._peer_tp, TP_MAX_UDP_PAYLOAD, MAX_UDP_PAYLOAD)),
                BIG_UDP_PAYLOAD)
            if not self.is_client and not self._handshake_done_queued:
                self._handshake_done_queued = True
                self._pending[LEVEL_APP].append(
                    ("raw", enc_varint(F_HANDSHAKE_DONE)))
                self.handshake_confirmed = True
                # RFC 9001 §4.9.2: the server confirms at handshake
                # completion and retires its Handshake keys (the final
                # ACK for the client's Finished goes out first)
                self._discard_keys(LEVEL_HANDSHAKE)
            self.handshake_complete.set()
            self._cv.notify_all()

    def _discard_keys(self, level: int) -> None:
        """Retire an encryption level (RFC 9001 §4.9): keys, loss-recovery
        state and queued frames all go — nothing at this level will ever
        be sent or processed again.  Lock held.

        Before dropping the send keys, flush one final ACK for anything
        received at this level: the peer may not have confirmed yet (e.g.
        its Finished is un-ACKed) and without it would burn a PTO
        retransmitting into our discarded keys.
        """
        if level in self._discarded_levels:
            return
        self._discarded_levels.add(level)
        space = self.spaces[level]
        if level in self.send_keys and space.recv.ranges:
            try:
                self._send_one(level, [_enc_ack_frame(space.recv.ranges)],
                               ack_eliciting=False)
            except OSError:
                pass
        self.send_keys.pop(level, None)
        self.recv_keys.pop(level, None)
        self._pending[level].clear()
        space.sent.clear()
        space.inflight = 0
        space.recv.ack_pending = False
        space.recv.unacked_eliciting = 0
        space.recv.oldest_unacked = None
        # parked packets at this level are undecryptable forever: free
        # their slots for levels that can still progress
        self._undecryptable = [
            p for p in self._undecryptable
            if _LEVEL_FOR_TYPE.get(p.ptype) != level
        ]

    def _validate_peer_tp(self) -> None:
        peer_scid = self._peer_tp.get(TP_INITIAL_SCID)
        if peer_scid != self.peer_cid:
            raise QuicError("transport params: initial_scid mismatch")
        if self.is_client:
            odcid = self._peer_tp.get(TP_ORIGINAL_DCID)
            if odcid != self.original_dcid:
                raise QuicError(
                    "transport params: original_destination_cid mismatch")

    # -- inbound -----------------------------------------------------------

    def handle_datagram(self, datagram: bytes) -> None:
        with self._cv:
            if self._closed:
                return
            self._last_rx = time.monotonic()
            if not self._addr_validated:
                self._amp_budget += 3 * len(datagram)
            pos = 0
            while pos < len(datagram):
                if datagram[pos] == 0:  # trailing padding at datagram level
                    pos += 1
                    continue
                try:
                    pkt = parse_packet(datagram, pos, CID_LEN)
                except QuicError as exc:
                    log.debug("drop undecodable packet: %s", exc)
                    return
                try:
                    self._handle_packet(pkt, datagram)
                except QuicError as exc:
                    # a protocol violation inside a decrypted packet is
                    # fatal to the connection, not just this datagram
                    log.warning("protocol violation: %s", exc)
                    self._cv.release()
                    try:
                        self.close(f"protocol violation: {exc}",
                                   error_code=0x03)
                    finally:
                        self._cv.acquire()
                    return
                except Exception as exc:  # noqa: BLE001
                    # malformed input escaping as ValueError/IndexError
                    # (cert parsing, varint truncation, ...) must close
                    # the connection, not zombie its handshake slot with
                    # the rx thread's exception swallowed
                    log.warning("internal error on packet: %r", exc)
                    self._cv.release()
                    try:
                        self.close(f"internal error: {exc!r}",
                                   error_code=0x01)
                    finally:
                        self._cv.acquire()
                    return
                pos = pkt.payload_end
            try:
                self._drive_tls_locked()
            except Exception as exc:
                log.warning("TLS failure: %s", exc)
                self._cv.release()
                try:
                    self.close(f"tls: {exc}", error_code=0x0128)
                finally:
                    self._cv.acquire()
                return
        self._flush()

    def _handle_packet(self, pkt: Packet, datagram: bytes) -> None:
        level = _LEVEL_FOR_TYPE.get(pkt.ptype)
        if level is None:
            return  # 0-RTT / Retry: not used by this stack
        if level in self._discarded_levels:
            return  # keys retired (RFC 9001 §4.9): drop, don't park
        keys = self.recv_keys.get(level)
        if keys is None:
            if len(self._undecryptable) < 8:
                self._undecryptable.append(pkt)
            return
        space = self.spaces[level]
        try:
            pn, plain = unprotect(keys, datagram, pkt, space.recv.largest)
        except QuicError as exc:
            log.debug("drop packet (level %d): %s", level, exc)
            return
        if not space.recv.register(pn):
            return  # duplicate
        if self.peer_cid is None or (pkt.ptype != PKT_1RTT
                                     and pkt.scid != self.peer_cid):
            # server learns the client SCID; client re-targets to the
            # server's chosen SCID on first response
            self.peer_cid = pkt.scid
        if level == LEVEL_HANDSHAKE and not self._addr_validated:
            self._addr_validated = True  # RFC 9001 §4.9: address proven
        self._process_frames(level, plain)
        if level == LEVEL_HANDSHAKE:
            # RFC 9001 §4.9.1: a successfully processed Handshake packet
            # proves the peer is past the Initial exchange on both ends —
            # Initial keys (and any Initial retransmission state) retire
            self._discard_keys(LEVEL_INITIAL)

    def _process_frames(self, level: int, plain: bytes) -> None:
        space = self.spaces[level]
        pos = 0
        ack_eliciting = False
        while pos < len(plain):
            ftype, pos = dec_varint(plain, pos)
            if level != LEVEL_APP and (
                    F_STREAM_BASE <= ftype <= 0x0F
                    or ftype in _APP_ONLY_FRAMES):
                raise QuicError(
                    f"frame type {ftype:#x} forbidden at encryption "
                    f"level {level} (RFC 9000 §12.4)")
            if ftype == F_PADDING:
                continue
            if ftype == F_PING:
                ack_eliciting = True
                continue
            if ftype in (F_ACK, F_ACK_ECN):
                pos = self._on_ack(space, plain, pos, ftype == F_ACK_ECN)
                continue
            ack_eliciting = True
            if ftype == F_CRYPTO:
                off, pos = dec_varint(plain, pos)
                ln, pos = dec_varint(plain, pos)
                self._on_crypto(space, level, off, plain[pos:pos + ln])
                pos += ln
            elif F_STREAM_BASE <= ftype <= 0x0F:
                sid, pos = dec_varint(plain, pos)
                off = 0
                if ftype & 0x04:
                    off, pos = dec_varint(plain, pos)
                if ftype & 0x02:
                    ln, pos = dec_varint(plain, pos)
                else:
                    ln = len(plain) - pos
                self._handle_stream_frame(sid, off, plain[pos:pos + ln],
                                           bool(ftype & 0x01))
                pos += ln
            elif ftype == F_MAX_DATA:
                v, pos = dec_varint(plain, pos)
                if v > self._send_max_data:
                    self._send_max_data = v
                    self._cv.notify_all()
            elif ftype == F_MAX_STREAM_DATA:
                sid, pos = dec_varint(plain, pos)
                v, pos = dec_varint(plain, pos)
                st = self.streams.get(sid)
                if st and v > st._send_limit:
                    st._send_limit = v
                    self._cv.notify_all()
            elif ftype in (F_MAX_STREAMS_BIDI, F_MAX_STREAMS_UNI):
                _, pos = dec_varint(plain, pos)
            elif ftype == F_RESET_STREAM:
                sid, pos = dec_varint(plain, pos)
                err, pos = dec_varint(plain, pos)
                _final, pos = dec_varint(plain, pos)
                st = self.streams.get(sid)
                if st:
                    st._on_reset(err)
                    self._maybe_gc_stream(st)
                    self._cv.notify_all()
            elif ftype == F_STOP_SENDING:
                sid, pos = dec_varint(plain, pos)
                err, pos = dec_varint(plain, pos)
                st = self.streams.get(sid)
                if st and not st._closed_local:
                    st._closed_local = True
                    self._queue_frame(LEVEL_APP, ("raw",
                        enc_varint(F_RESET_STREAM) + enc_varint(sid)
                        + enc_varint(err) + enc_varint(st._send_offset)))
            elif ftype in (F_DATA_BLOCKED, F_STREAMS_BLOCKED_BIDI,
                           F_STREAMS_BLOCKED_UNI, F_RETIRE_CONNECTION_ID):
                _, pos = dec_varint(plain, pos)
            elif ftype == F_STREAM_DATA_BLOCKED:
                _, pos = dec_varint(plain, pos)
                _, pos = dec_varint(plain, pos)
            elif ftype == F_NEW_CONNECTION_ID:
                _, pos = dec_varint(plain, pos)   # sequence
                _, pos = dec_varint(plain, pos)   # retire prior to
                ln = plain[pos]; pos += 1 + ln + 16  # cid + reset token
            elif ftype == F_NEW_TOKEN:
                ln, pos = dec_varint(plain, pos)
                pos += ln
            elif ftype == F_PATH_CHALLENGE:
                data = plain[pos:pos + 8]; pos += 8
                self._queue_frame(level, ("raw",
                    enc_varint(F_PATH_RESPONSE) + data))
            elif ftype == F_PATH_RESPONSE:
                pos += 8
            elif ftype in (F_CONNECTION_CLOSE, F_CONNECTION_CLOSE_APP):
                err, pos = dec_varint(plain, pos)
                if ftype == F_CONNECTION_CLOSE:
                    _, pos = dec_varint(plain, pos)
                rlen, pos = dec_varint(plain, pos)
                reason = plain[pos:pos + rlen].decode("utf-8", "replace")
                pos += rlen
                self._closed = True
                self.close_reason = f"peer closed ({err:#x}): {reason}"
                self._cv.notify_all()
                self.endpoint._forget(self)
                return
            elif ftype == F_HANDSHAKE_DONE:
                if not self.is_client:
                    # only the server sends HANDSHAKE_DONE (RFC 9000 §19.20)
                    raise QuicError("client sent HANDSHAKE_DONE")
                self.handshake_confirmed = True
                # RFC 9001 §4.9.2: handshake confirmation retires the
                # Handshake keys on the client
                self._discard_keys(LEVEL_HANDSHAKE)
            else:
                raise QuicError(f"unknown frame type {ftype:#x}")
        if ack_eliciting:
            rs = space.recv
            rs.unacked_eliciting += 1
            if rs.oldest_unacked is None:
                rs.oldest_unacked = time.monotonic()
            # RFC 9000 section 13.2.2: ack every 2nd ack-eliciting packet;
            # handshake levels ack immediately (latency over overhead)
            if level != LEVEL_APP or rs.unacked_eliciting >= 2:
                rs.ack_pending = True

    def _on_ack(self, space: _Space, plain: bytes, pos: int,
                ecn: bool) -> int:
        largest, pos = dec_varint(plain, pos)
        if largest >= space.next_pn:
            # RFC 9000 §13.1: acknowledging a packet number we never sent
            # is a protocol violation, not a no-op — a forged/corrupt ACK
            # must not poison largest_acked (it would mark every genuine
            # in-flight packet "lost" via the packet-threshold rule)
            raise QuicError(
                f"ACK for unsent packet number {largest} "
                f"(next_pn {space.next_pn})")
        _delay, pos = dec_varint(plain, pos)
        nranges, pos = dec_varint(plain, pos)
        first, pos = dec_varint(plain, pos)
        acked = [(largest - first, largest)]
        lo = largest - first
        for _ in range(nranges):
            gap, pos = dec_varint(plain, pos)
            rlen, pos = dec_varint(plain, pos)
            hi = lo - gap - 2
            acked.append((hi - rlen, hi))
            lo = hi - rlen
        if ecn:
            for _ in range(3):
                _, pos = dec_varint(plain, pos)
        newly = False
        for alo, ahi in acked:
            for pn in [p for p in space.sent if alo <= p <= ahi]:
                space.inflight -= space.sent[pn].size
                del space.sent[pn]
                newly = True
        space.largest_acked = max(space.largest_acked, largest)
        if newly:
            self._pto_count = 0
        # packet-threshold loss: 3 packets past a later-sent acked one
        lost = [p for p in space.sent if p <= space.largest_acked - 3]
        for pn in lost:
            self._requeue(space, pn)
        return pos

    def _on_crypto(self, space: _Space, level: int, off: int,
                   data: bytes) -> None:
        if off + len(data) <= space.crypto_delivered:
            return
        space.crypto_frags[off] = data
        # legitimate TLS flights are a few KB; an attacker spraying
        # widely-spaced CRYPTO offsets must not grow this without bound
        if (len(space.crypto_frags) > 64
                or sum(len(v) for v in space.crypto_frags.values()) > (1 << 18)):
            raise QuicError("CRYPTO reassembly buffer overflow")
        progressed = True
        while progressed:
            progressed = False
            for frag_off, frag in list(space.crypto_frags.items()):
                if frag_off <= space.crypto_delivered < frag_off + len(frag):
                    self.tls.on_data(
                        level, frag[space.crypto_delivered - frag_off:])
                    space.crypto_delivered = frag_off + len(frag)
                    del space.crypto_frags[frag_off]
                    progressed = True
                elif frag_off + len(frag) <= space.crypto_delivered:
                    del space.crypto_frags[frag_off]

    def _handle_stream_frame(self, sid: int, off: int, data: bytes,
                             fin: bool) -> None:
        st = self.streams.get(sid)
        if st is None:
            if sid in self._dead_streams:
                return  # late retransmit for a collected stream
            locally_initiated = (sid % 4 == 0) == self.is_client
            if locally_initiated:
                return  # data for a stream we never opened / already gc'd
            if len(self.streams) >= MAX_INBOUND_STREAMS:
                raise QuicError("inbound stream cap exceeded")
            st = QuicStream(self, sid)
            st._send_limit = self._peer_sd_theirs
            self.streams[sid] = st
            self._accept_q.append(st)
        # connection-level flow control counts the HIGHEST received
        # offset per stream (RFC 9000 section 4.1), so retransmits and
        # reordering don't inflate the total
        new_high = off + len(data)
        if new_high > st._rx_highest:
            self._recv_data_total += new_high - st._rx_highest
            st._rx_highest = new_high
            if self._recv_data_total > self._recv_max_data:
                raise QuicError("connection flow-control overrun")
        st._on_stream_frame(off, data, fin)
        if st._closed_remote:
            self._maybe_gc_stream(st)
        self._cv.notify_all()

    def _maybe_gc_stream(self, st: QuicStream) -> None:
        """Lock held.  Long-lived connections open one stream per
        req/resp; fully-closed streams leave the table (the stream object
        itself stays readable — buffered data lives on it, not here)."""
        if not (st._closed_local and st._closed_remote):
            return
        if self.streams.pop(st.id, None) is not None:
            self._dead_streams[st.id] = True
            while len(self._dead_streams) > 8192:
                self._dead_streams.popitem(last=False)

    def _credit_consumed(self, st: QuicStream, n: int) -> None:
        """Called under lock as the app consumes bytes: slide windows."""
        self._recv_consumed_total += n
        if st._rx_limit - st._rx_consumed < STREAM_WINDOW // 2:
            st._rx_limit = st._rx_consumed + STREAM_WINDOW
            self._pending[LEVEL_APP].append(("raw",
                enc_varint(F_MAX_STREAM_DATA) + enc_varint(st.id)
                + enc_varint(st._rx_limit)))
        if self._recv_max_data - self._recv_consumed_total < CONN_WINDOW // 2:
            self._recv_max_data = self._recv_consumed_total + CONN_WINDOW
            self._pending[LEVEL_APP].append(("raw",
                enc_varint(F_MAX_DATA) + enc_varint(self._recv_max_data)))
        self.endpoint._wake()

    # -- outbound ----------------------------------------------------------

    def _queue_stream(self, sid: int, offset: int, data: bytes,
                      fin: bool) -> None:
        self._pending[LEVEL_APP].append(("stream", sid, offset, data, fin))

    def _queue_frame(self, level: int, desc) -> None:
        self._pending[level].append(desc)

    def _flush(self) -> None:
        with self._cv:
            self._flush_locked()

    def _flush_locked(self) -> None:
        if self._closed:
            return
        budget = None if self._addr_validated else self._amp_budget
        for level in (LEVEL_INITIAL, LEVEL_HANDSHAKE, LEVEL_APP):
            if level not in self.send_keys:
                continue
            space = self.spaces[level]
            while (self._pending[level] or space.recv.ack_pending):
                if (level == LEVEL_APP
                        and space.inflight >= CWND_BYTES
                        and not space.recv.ack_pending):
                    break  # congestion window full; ACKs re-open it
                frames: list[bytes] = []
                descs: list = []
                size = 0
                if space.recv.ack_pending and space.recv.ranges:
                    frames.append(_enc_ack_frame(space.recv.ranges))
                    size += len(frames[-1])
                    space.recv.ack_pending = False
                    space.recv.unacked_eliciting = 0
                    space.recv.oldest_unacked = None
                max_payload = (self._max_payload if level == LEVEL_APP
                               else MAX_UDP_PAYLOAD) - 64
                while self._pending[level] and size < max_payload:
                    desc = self._pending[level].popleft()
                    if desc[0] == "raw":
                        frames.append(desc[1])
                        descs.append(desc)
                        size += len(desc[1])
                    elif desc[0] == "crypto":
                        _, off, data = desc
                        room = max_payload - size - 16
                        if room < 32:
                            self._pending[level].appendleft(desc)
                            break
                        # memoryview keeps the unsent remainder O(1): a
                        # queued 1 MB chunk must not be re-copied per packet
                        view = memoryview(data)
                        take = bytes(view[:room])
                        rest = view[room:]
                        if len(rest):
                            self._pending[level].appendleft(
                                ("crypto", off + len(take), rest))
                        frame = (enc_varint(F_CRYPTO) + enc_varint(off)
                                 + enc_varint(len(take)) + take)
                        frames.append(frame)
                        descs.append(("crypto", off, take))
                        size += len(frame)
                    elif desc[0] == "stream":
                        _, sid, off, data, fin = desc
                        room = max_payload - size - 20
                        if room < 64 and len(data):
                            self._pending[level].appendleft(desc)
                            break
                        view = memoryview(data)
                        take = bytes(view[:room])
                        rest = view[room:]
                        if len(rest):
                            self._pending[level].appendleft(
                                ("stream", sid, off + len(take), rest, fin))
                            fin_now = False
                        else:
                            fin_now = fin
                        frame = (enc_varint(F_STREAM_BASE | 0x04 | 0x02
                                            | (0x01 if fin_now else 0))
                                 + enc_varint(sid) + enc_varint(off)
                                 + enc_varint(len(take)) + take)
                        frames.append(frame)
                        descs.append(("stream", sid, off, take, fin_now))
                        size += len(frame)
                if not frames:
                    break
                sent = self._send_one(level, frames, ack_eliciting=bool(descs)
                                      or any(f[0] == F_PING for f in frames),
                                      descs=descs)
                if budget is not None:
                    budget -= sent
                    self._amp_budget = max(0, budget)
                    if budget <= 0:
                        return  # anti-amplification: wait for more rx

    def _send_one(self, level: int, frames: list[bytes],
                  ack_eliciting: bool, descs: list | None = None) -> int:
        """Assemble, protect and transmit ONE packet; returns bytes sent."""
        space = self.spaces[level]
        pn = space.next_pn
        space.next_pn += 1
        pn_bytes = encode_pn(pn, space.largest_acked)
        payload = b"".join(frames)
        # sample for header protection needs >= 4 bytes of pn+payload
        while len(pn_bytes) + len(payload) < 4:
            payload += b"\x00"
        dcid = self.peer_cid if self.peer_cid is not None else b""
        if level == LEVEL_APP:
            header = build_short_header(dcid, pn_bytes)
        else:
            ptype = PKT_INITIAL if level == LEVEL_INITIAL else PKT_HANDSHAKE
            # a client Initial datagram must be >= 1200 bytes (RFC 9000
            # §14.1): pad the packet payload itself
            if ptype == PKT_INITIAL and self.is_client:
                # datagram = header(<=30 for 8-byte cids) + payload + tag;
                # pad so the total clears 1200 for any pn length
                target = MIN_CLIENT_INITIAL - 26 - len(pn_bytes) - 16
                if len(payload) < target:
                    payload += b"\x00" * (target - len(payload))
            header = build_long_header(ptype, dcid, self.local_cid,
                                       pn_bytes, len(payload))
        datagram = protect(self.send_keys[level], header, pn,
                           len(pn_bytes), payload)
        self._last_tx = time.monotonic()
        if ack_eliciting:
            space.sent[pn] = _SentPacket(pn, time.monotonic(), True,
                                         descs or [], len(datagram))
            space.inflight += len(datagram)
        self.endpoint._transmit(datagram, self.peer_addr)
        return len(datagram)

    def _requeue(self, space: _Space, pn: int) -> None:
        """Move a lost packet's retransmittable content back to pending."""
        rec = space.sent.pop(pn, None)
        if rec is None:
            return
        space.inflight -= rec.size
        level = next(l for l, s in self.spaces.items() if s is space)
        for desc in rec.frames:
            self._pending[level].append(desc)

    # -- timers ------------------------------------------------------------

    def on_tick(self, now: float) -> None:
        flush = False
        with self._cv:
            if self._closed:
                return
            if now - self._last_rx > IDLE_TIMEOUT:
                self._cv.release()
                try:
                    self.close("idle timeout")
                finally:
                    self._cv.acquire()
                return
            # keepalive: a quiet-but-healthy connection (stable gossip
            # mesh, no RPC) must not idle out — PING well inside the
            # timeout; the peer's ACK refreshes both sides' last_rx
            if (self.handshake_complete.is_set()
                    and LEVEL_APP in self.send_keys
                    and now - max(self._last_rx, self._last_tx)
                        > IDLE_TIMEOUT / 3):
                self._pending[LEVEL_APP].append(
                    ("raw", enc_varint(F_PING)))
                flush = True
            for space in self.spaces.values():
                rs = space.recv
                if (rs.unacked_eliciting > 0 and rs.oldest_unacked is not None
                        and now - rs.oldest_unacked > 0.025):
                    rs.ack_pending = True
                    flush = True
            pto = PTO_INITIAL * (2 ** min(self._pto_count, 6))
            for level, space in self.spaces.items():
                if not space.sent:
                    continue
                # time-threshold loss (RFC 9002 section 6.1): a packet
                # sent well before one the peer has acked is lost
                if space.largest_acked >= 0:
                    lost = [pn for pn, rec in space.sent.items()
                            if pn < space.largest_acked
                            and now - rec.time > 0.12]
                    for pn in lost:
                        self._requeue(space, pn)
                    if lost:
                        flush = True
                if not space.sent:
                    continue
                oldest = min(rec.time for rec in space.sent.values())
                if now - oldest > pto:
                    self._pto_count += 1
                    if self._pto_count > PTO_MAX_RETRIES:
                        self._cv.release()
                        try:
                            self.close("handshake/transfer timed out (PTO)")
                        finally:
                            self._cv.acquire()
                        return
                    for pn in list(space.sent):
                        self._requeue(space, pn)
                    flush = True
            # retry packets parked for missing keys
            if self._undecryptable and any(
                    _LEVEL_FOR_TYPE.get(p.ptype) in self.recv_keys
                    for p in self._undecryptable):
                parked, self._undecryptable = self._undecryptable, []
                try:
                    for pkt in parked:
                        self._handle_packet(pkt, pkt.raw)
                    self._drive_tls_locked()
                except Exception as exc:  # noqa: BLE001
                    violation = isinstance(exc, QuicError)
                    log.warning("parked replay failed (%s): %r",
                                "violation" if violation else "internal",
                                exc)
                    self._cv.release()
                    try:
                        self.close(
                            f"protocol violation: {exc}" if violation
                            else f"internal error: {exc!r}",
                            error_code=0x03 if violation else 0x01)
                    finally:
                        self._cv.acquire()
                    return
                flush = True
        if flush:
            self._flush()


# ---------------------------------------------------------------------------
# endpoint
# ---------------------------------------------------------------------------

class QuicEndpoint:
    """One UDP socket carrying many QUIC connections (client and server).

    The reference's QUIC listener is one quinn endpoint per node
    (`lighthouse_network/src/service/utils.rs:39-48`); same shape here:
    ``dial()`` and ``accept()`` both hand back handshake-complete
    `QuicConnection`s whose `remote_peer_id` is the TLS-authenticated
    libp2p identity.
    """

    MAX_PENDING_HANDSHAKES = 64
    MAX_CONNECTIONS = 1024

    def __init__(self, identity_key, ip: str = "127.0.0.1", port: int = 0):
        from . import tls13 as _tls

        self.identity_key = identity_key
        # one certificate per endpoint (it binds only the static identity
        # key) — per-handshake keygen+signing would hand an unauthenticated
        # Initial flood ~1ms of our CPU per 1200-byte datagram
        self.cert = _tls.make_libp2p_cert(identity_key)
        self.sock = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        for opt in (socket.SO_RCVBUF, socket.SO_SNDBUF):
            try:
                self.sock.setsockopt(socket.SOL_SOCKET, opt, 1 << 22)
            except OSError:
                pass
        self.sock.bind((ip, port))
        self.sock.settimeout(0.05)
        self.ip, self.port = self.sock.getsockname()
        self._conns: dict[bytes, QuicConnection] = {}
        self._lock = threading.Lock()
        self._accept_q: deque[QuicConnection] = deque()
        self._accept_cv = threading.Condition(self._lock)
        self._stopped = False
        self._rx_thread = threading.Thread(
            target=self._rx_loop, name=f"quic-rx-{self.port}", daemon=True)
        self._tick_thread = threading.Thread(
            target=self._tick_loop, name=f"quic-tick-{self.port}", daemon=True)
        self._rx_thread.start()
        self._tick_thread.start()

    # -- wiring ------------------------------------------------------------

    def _transmit(self, datagram: bytes, addr) -> None:
        try:
            self.sock.sendto(datagram, addr)
        except OSError as exc:
            log.debug("sendto %s failed: %s", addr, exc)

    def _wake(self) -> None:
        pass  # sends are synchronous; nothing to wake

    def _forget(self, conn: QuicConnection) -> None:
        with self._lock:
            for cid in [c for c, v in self._conns.items() if v is conn]:
                del self._conns[cid]

    def _rx_loop(self) -> None:
        while not self._stopped:
            try:
                datagram, addr = self.sock.recvfrom(65535)
            except socket.timeout:
                continue
            except OSError:
                return
            try:
                self._dispatch(datagram, addr)
            except Exception as exc:
                log.warning("datagram dispatch failed: %s", exc)

    def _dispatch(self, datagram: bytes, addr) -> None:
        if not datagram:
            return
        first = datagram[0]
        if first & 0x80:
            if len(datagram) < 7:
                return
            dlen = datagram[5]
            dcid = datagram[6:6 + dlen]
        else:
            dcid = datagram[1:1 + CID_LEN]
        with self._lock:
            conn = self._conns.get(dcid)
            if conn is None and first & 0x80 and ((first >> 4) & 3) == PKT_INITIAL:
                if len(datagram) < MIN_CLIENT_INITIAL:
                    return  # RFC 9000 §14.1: drop small client Initials
                live = set(self._conns.values())
                pending = sum(1 for c in live
                              if not c.handshake_complete.is_set())
                if (pending >= self.MAX_PENDING_HANDSHAKES
                        or len(live) >= self.MAX_CONNECTIONS):
                    return  # unauthenticated flood: shed load, no state
                conn = QuicConnection(self, addr, is_client=False,
                                      original_dcid=dcid)
                self._conns[dcid] = conn
                self._conns[conn.local_cid] = conn
                threading.Thread(target=self._await_accept, args=(conn,),
                                 daemon=True).start()
        if conn is not None:
            conn.handle_datagram(datagram)

    def _await_accept(self, conn: QuicConnection) -> None:
        if conn.handshake_complete.wait(timeout=15.0):
            with self._lock:
                self._accept_q.append(conn)
                self._accept_cv.notify_all()
        else:
            conn.close("handshake timeout")

    def _tick_loop(self) -> None:
        while not self._stopped:
            time.sleep(0.05)
            now = time.monotonic()
            with self._lock:
                conns = list(set(self._conns.values()))
            for conn in conns:
                try:
                    conn.on_tick(now)
                except Exception as exc:
                    log.warning("tick failed: %s", exc)

    # -- public ------------------------------------------------------------

    def dial(self, ip: str, port: int, timeout: float = 10.0,
             expected_peer_id: bytes | None = None) -> QuicConnection:
        conn = QuicConnection(self, (ip, port), is_client=True)
        with self._lock:
            self._conns[conn.local_cid] = conn
        conn._flush()
        if not conn.handshake_complete.wait(timeout):
            conn.close("dial handshake timeout")
            raise QuicError(f"QUIC dial {ip}:{port}: handshake timeout "
                            f"({conn.close_reason})")
        if (expected_peer_id is not None
                and conn.remote_peer_id != expected_peer_id):
            conn.close("peer identity mismatch")
            raise QuicError(
                f"remote proved identity {conn.remote_peer_id.hex()[:8]}, "
                f"expected {expected_peer_id.hex()[:8]}")
        return conn

    def accept(self, timeout: float = 10.0) -> QuicConnection:
        deadline = time.monotonic() + timeout
        with self._accept_cv:
            while not self._accept_q:
                if self._stopped:
                    raise QuicError("endpoint stopped")
                if not self._accept_cv.wait(deadline - time.monotonic()):
                    raise QuicError("accept timeout")
            return self._accept_q.popleft()

    def stop(self) -> None:
        self._stopped = True
        with self._lock:
            conns = list(set(self._conns.values()))
        for conn in conns:
            conn.close("endpoint shutdown")
        try:
            self.sock.close()
        except OSError:
            pass
