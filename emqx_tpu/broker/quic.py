"""QUIC v1 transport (RFC 9000) carrying MQTT on stream 0.

The reference's MQTT-over-QUIC rides the quicer NIF around MsQuic
(apps/emqx/src/emqx_quic_connection.erl:1-346, emqx_listeners.erl:
193-210, single-stream mode: one client-initiated bidirectional
stream carries the MQTT byte stream). No QUIC library ships in this
image, so the transport is implemented from the RFCs on the
`cryptography` primitives: packet protection and the TLS 1.3
handshake live in quic_crypto.py / quic_tls.py; this module is the
connection machinery — long/short header packets with coalescing,
CRYPTO / STREAM / ACK / HANDSHAKE_DONE / CONNECTION_CLOSE frames,
per-space packet numbers, and ordered stream reassembly.

Scope: the profile our endpoints need, including the RFC 9002
recovery machinery — per-space sent-packet tracking, packet-threshold
loss declaration off ACK ranges, smoothed-RTT PTO timers that send
PROBES (not full-flight retransmits) with exponential backoff, and
retransmission of lost CRYPTO/STREAM ranges — plus NewReno
congestion control (RFC 9002 §7: slow start / congestion avoidance /
halving once per recovery period), so a lossy-but-fat link
retransmits under a cwnd, not at line rate. Flow control is real
both ways: finite windows are advertised and ENFORCED on receive
(FLOW_CONTROL_ERROR on overrun), replenished with
MAX_DATA/MAX_STREAM_DATA per stream as the app consumes, and the
peer's advertised windows gate our sends. TLS-PSK (psk_dhe_ke)
authenticates clients against a PskStore when the listener carries
one. Stream 0 is the MQTT control stream (the reference's
single-stream mode); additional client-initiated bidirectional
streams are served as DATA streams with per-stream MQTT parsing and
same-stream replies (multi-stream mode, emqx_quic_data_stream.erl)."""

from __future__ import annotations

import asyncio
import logging
import os
import struct
from typing import Callable, Dict, List, Optional, Tuple

from .quic_crypto import (
    DirectionKeys, dec_varint, enc_varint, encode_pn, initial_keys,
    protect, unprotect,
)
from .quic_tls import TlsClient, TlsServer, TlsError

log = logging.getLogger("emqx_tpu.broker.quic")

VERSION_V1 = 0x00000001
LEVELS = ("initial", "handshake", "app")

FT_PADDING = 0x00
FT_PING = 0x01
FT_ACK = 0x02
FT_CRYPTO = 0x06
FT_STREAM_BASE = 0x08  # 0x08..0x0f
FT_MAX_DATA = 0x10
FT_MAX_STREAM_DATA = 0x11

# RFC 9002 minimum-viable recovery knobs
K_PACKET_THRESHOLD = 3  # reordering threshold (§6.1.1)
PTO_INITIAL = 0.3  # s; doubles per consecutive timeout (§6.2)
PTO_MAX = 8.0
# flow-control windows we ADVERTISE (and therefore enforce on RX);
# MAX_DATA / MAX_STREAM_DATA replenish as the app consumes (§4)
FC_CONN_WINDOW = 1 << 20
FC_STREAM_WINDOW = 1 << 19
# per-packet STREAM chunk bound: a frame larger than one UDP datagram
# can never be sent (EMSGSIZE) and would retransmit forever
MAX_STREAM_CHUNK = 1200
FT_CONN_CLOSE = 0x1C
FT_CONN_CLOSE_APP = 0x1D
FT_HANDSHAKE_DONE = 0x1E

_LONG_TYPE = {"initial": 0x00, "handshake": 0x02}


def encode_transport_params(scid: bytes,
                            odcid: Optional[bytes] = None) -> bytes:
    def tp(tid: int, val: bytes) -> bytes:
        return enc_varint(tid) + enc_varint(len(val)) + val

    out = b""
    if odcid is not None:
        out += tp(0x00, odcid)  # original_destination_connection_id
    out += tp(0x01, enc_varint(30_000))  # max_idle_timeout ms
    out += tp(0x03, enc_varint(65527))  # max_udp_payload_size
    # finite windows, replenished with MAX_DATA / MAX_STREAM_DATA as
    # the app consumes (RFC 9000 §4) — and ENFORCED on receive
    out += tp(0x04, enc_varint(FC_CONN_WINDOW))  # initial_max_data
    out += tp(0x05, enc_varint(FC_STREAM_WINDOW))  # max_stream_data bidi local
    out += tp(0x06, enc_varint(FC_STREAM_WINDOW))  # bidi remote
    out += tp(0x07, enc_varint(FC_STREAM_WINDOW))  # uni
    out += tp(0x08, enc_varint(16))  # initial_max_streams_bidi
    out += tp(0x09, enc_varint(16))  # uni
    out += tp(0x0F, scid)  # initial_source_connection_id
    return out


class _SentPacket:
    """Bookkeeping for one ack-eliciting packet in flight."""

    __slots__ = ("time", "crypto", "stream", "hs_done", "ping", "fc",
                 "size")

    def __init__(self, time, crypto=None, stream=None, hs_done=False,
                 ping=False, fc=False):
        self.time = time
        self.crypto = crypto  # (offset, length) into crypto_out
        self.stream = stream  # (stream id, abs offset, length)
        self.hs_done = hs_done
        self.ping = ping
        self.fc = fc  # carried a MAX_DATA/MAX_STREAM_DATA update
        self.size = 0  # wire bytes (congestion accounting)


class _StreamState:
    """Per-stream send/receive state (RFC 9000 §2). Stream 0 is the
    MQTT control stream (the reference's single-stream mode); further
    client-initiated bidirectional streams (4, 8, ...) are the
    multi-stream mode's data streams (emqx_quic_data_stream.erl)."""

    __slots__ = ("rx", "rx_off", "out", "sent", "unacked", "rtx",
                 "fin_rcvd", "tx_max", "rx_max", "consumed", "rx_hwm")

    def __init__(self, tx_max: int, rx_max: int) -> None:
        self.rx: Dict[int, bytes] = {}
        self.rx_off = 0
        self.out = b""  # unsent suffix
        self.sent = 0  # absolute stream offset already sent
        self.unacked: Dict[int, bytes] = {}
        self.rtx: List[Tuple[int, bytes]] = []
        self.fin_rcvd = False
        self.tx_max = tx_max  # peer's allowance for OUR sends
        self.rx_max = rx_max  # our advertised window
        self.consumed = 0
        self.rx_hwm = 0  # highest received offset (FC accounting)


class _Space:
    """One packet-number space (initial / handshake / app)."""

    def __init__(self) -> None:
        self.rx: Optional[DirectionKeys] = None
        self.tx: Optional[DirectionKeys] = None
        self.next_pn = 0
        self.largest_rx = -1
        self.received: set = set()
        self.ack_due = False
        self.crypto_out = b""
        self.crypto_sent = 0
        self.crypto_in: Dict[int, bytes] = {}
        self.crypto_in_off = 0
        # --- loss recovery (RFC 9002) ---
        self.sent: Dict[int, _SentPacket] = {}
        self.largest_acked = -1
        self.crypto_rtx: List[Tuple[int, int]] = []  # lost (off, len)
        self.ping_due = False
        self.last_eliciting_sent = 0.0
        self.pto_count = 0


class QuicConnection:
    """Role-shared connection core. The owner pumps:
    datagram_received(data) -> None and flush() -> [datagrams]."""

    def __init__(self, is_server: bool, scid: bytes, dcid: bytes):
        self.is_server = is_server
        self.scid = scid  # our CID (peer addresses us with this)
        self.dcid = dcid  # peer's CID
        self.spaces = {lvl: _Space() for lvl in LEVELS}
        self.tls = None  # set by subclass
        # per-stream state; stream 0 always exists (control stream)
        self._init_tx_max_stream = 1 << 14
        self.streams: Dict[int, _StreamState] = {}
        self._stream(0)
        # streams whose MAX_STREAM_DATA replenish is due
        self._fc_stream_due: set = set()
        # --- flow control (RFC 9000 §4) ---
        # peer's allowance for OUR sends (from its transport params /
        # MAX_DATA); conservative until params parse
        self.tx_max_data = 1 << 14
        self._peer_params_seen = False
        # OUR advertised connection window (enforced on receive,
        # replenished as the app consumes)
        self.rx_max_data = FC_CONN_WINDOW
        self._rx_consumed = 0
        self._rx_hwm_total = 0  # sum of per-stream receive high-water marks
        self._fc_update_due = False
        self._clock = __import__("time").monotonic
        self.on_stream_data: Optional[Callable[[bytes], None]] = None
        # multi-stream seam: inbound bytes for sid != 0 (data streams)
        self.on_data_stream: Optional[Callable[[int, bytes], None]] = None
        self.on_close: Optional[Callable[[], None]] = None
        self.handshake_done = False
        self.closed = False
        self.close_pending: Optional[Tuple[int, str]] = None
        # --- congestion control (RFC 9002 §7, NewReno) ---
        self.max_datagram_size = 1200
        self.cwnd = 10 * self.max_datagram_size
        self.ssthresh = float("inf")
        self.bytes_in_flight = 0
        self._recovery_start = 0.0  # packets sent before this don't
        # trigger a NEW congestion event (once per RTT, §7.3.1)
        # PTO probes may exceed cwnd (§7.5) — but ONLY probes, one
        # credit per fired PTO; threshold-loss retransmissions wait
        # for window room like everything else
        self._probe_credit = 0
        # total stream bytes sent (connection-level MAX_DATA is a sum
        # across streams, not per stream)
        self.tx_sent_total = 0
        # --- RTT estimate (RFC 9002 §5) ---
        self.srtt: Optional[float] = None
        self.rttvar = 0.0

    MAX_STREAMS = 32  # accepted concurrent streams per connection
    # (DoS bound: each stream can buffer up to FC_STREAM_WINDOW of
    # reassembly; the reference's quicer listener caps streams too)

    def _stream(self, sid: int) -> _StreamState:
        st = self.streams.get(sid)
        if st is None:
            st = self.streams[sid] = _StreamState(
                self._init_tx_max_stream, FC_STREAM_WINDOW
            )
        return st

    # --- stream-0 back-compat surface (single-stream callers/tests) ---
    @property
    def stream_out(self) -> bytes:
        return self.streams[0].out

    @property
    def stream_sent(self) -> int:
        return self.streams[0].sent

    @property
    def stream_fin_rcvd(self) -> bool:
        return self.streams[0].fin_rcvd

    @property
    def rx_max_stream(self) -> int:
        return self.streams[0].rx_max

    @rx_max_stream.setter
    def rx_max_stream(self, v: int) -> None:
        self.streams[0].rx_max = v

    @property
    def tx_max_stream(self) -> int:
        return self.streams[0].tx_max

    @tx_max_stream.setter
    def tx_max_stream(self, v: int) -> None:
        self.streams[0].tx_max = v

    def _maybe_parse_peer_params(self) -> None:
        if self._peer_params_seen or self.tls is None:
            return
        raw = getattr(self.tls, "peer_transport_params", None)
        if not raw:
            return
        off = 0
        params = {}
        try:
            while off < len(raw):
                tid, off = dec_varint(raw, off)
                ln, off = dec_varint(raw, off)
                params[tid] = raw[off : off + ln]
                off += ln
        except Exception:
            return
        def vint(tid, default):
            v = params.get(tid)
            if not v:
                return default
            try:
                return dec_varint(v, 0)[0]
            except Exception:
                return default
        self.tx_max_data = vint(0x04, self.tx_max_data)
        # streams here are client-initiated bidi: the sender honors the
        # receiver's bidi_remote (server side) / bidi_local (client)
        tid = 0x06 if not self.is_server else 0x05
        init_max = vint(tid, self._init_tx_max_stream)
        self._init_tx_max_stream = init_max
        for st in self.streams.values():
            st.tx_max = max(st.tx_max, init_max)
        self._peer_params_seen = True

    # --- frame/packet building -----------------------------------------

    def _build_packet(self, level: str, frames: bytes) -> bytes:
        # header protection samples 16 bytes starting 4 bytes past the
        # pn offset: with a 2-byte pn the ciphertext (payload + 16-byte
        # tag) must be >= 18, so tiny frames pad up (RFC 9001 §5.4.2)
        if len(frames) < 3:
            frames += b"\x00" * (3 - len(frames))
        sp = self.spaces[level]
        pn = sp.next_pn
        sp.next_pn += 1
        if level == "app":
            header = bytes([0x41]) + self.dcid + encode_pn(pn)
            pn_off = 1 + len(self.dcid)
        else:
            flags = 0xC1 | (_LONG_TYPE[level] << 4)
            header = bytes([flags]) + struct.pack(">I", VERSION_V1)
            header += bytes([len(self.dcid)]) + self.dcid
            header += bytes([len(self.scid)]) + self.scid
            if level == "initial":
                header += enc_varint(0)  # token length
            header += enc_varint(len(frames) + 2 + 16)  # pn + payload + tag
            pn_off = len(header)
            header += encode_pn(pn)
        return protect(sp.tx, header, pn, frames, pn_off), pn

    def _ack_frame(self, sp: _Space) -> bytes:
        largest = sp.largest_rx
        first = 0
        while (largest - first - 1) in sp.received:
            first += 1
        return (
            bytes([FT_ACK]) + enc_varint(largest) + enc_varint(0)
            + enc_varint(0) + enc_varint(first)
        )

    def _pending_frames(self, level: str):
        """-> (frames bytes, _SentPacket meta | None). Meta is non-None
        when the packet is ack-eliciting (needs loss tracking)."""
        sp = self.spaces[level]
        out = b""
        meta = None

        def mark(**kw):
            nonlocal meta
            if meta is None:
                meta = _SentPacket(self._clock())
            for k, v in kw.items():
                setattr(meta, k, v)

        if sp.ack_due and sp.largest_rx >= 0:
            out += self._ack_frame(sp)
            sp.ack_due = False
        if sp.ping_due:
            out += bytes([FT_PING])
            sp.ping_due = False
            mark(ping=True)
        # retransmit declared-lost CRYPTO ranges first (RFC 9002 §6.3)
        if sp.crypto_rtx:
            coff, clen = sp.crypto_rtx.pop(0)
            chunk = sp.crypto_out[coff : coff + clen]
            out += (
                bytes([FT_CRYPTO]) + enc_varint(coff)
                + enc_varint(len(chunk)) + chunk
            )
            mark(crypto=(coff, clen))
        elif sp.crypto_sent < len(sp.crypto_out):
            coff = sp.crypto_sent
            chunk = sp.crypto_out[coff:]
            out += (
                bytes([FT_CRYPTO]) + enc_varint(coff)
                + enc_varint(len(chunk)) + chunk
            )
            sp.crypto_sent = len(sp.crypto_out)
            mark(crypto=(coff, len(chunk)))
        if self.close_pending is not None and level != "app" and (
            self.spaces["app"].tx is None
        ):
            # a handshake-time failure must still tell the peer (RFC
            # 9000 §10.2.3): transport-level close at this level
            code, reason = self.close_pending
            r = reason.encode()[:64]
            out += (
                bytes([FT_CONN_CLOSE]) + enc_varint(code) + enc_varint(0)
                + enc_varint(len(r)) + r
            )
            self.close_pending = None
            self.closed = True
        if level == "app":
            if self.handshake_done and self.is_server and not getattr(
                self, "_hs_done_sent", False
            ):
                out += bytes([FT_HANDSHAKE_DONE])
                self._hs_done_sent = True
                mark(hs_done=True)
            if self._fc_update_due or self._fc_stream_due:
                # replenish the peer's send windows as the app consumed
                self.rx_max_data = self._rx_consumed + FC_CONN_WINDOW
                out += bytes([FT_MAX_DATA]) + enc_varint(self.rx_max_data)
                fc_sids = sorted(self._fc_stream_due or {0})
                for sid in fc_sids:
                    st = self._stream(sid)
                    st.rx_max = st.consumed + FC_STREAM_WINDOW
                    out += (
                        bytes([FT_MAX_STREAM_DATA]) + enc_varint(sid)
                        + enc_varint(st.rx_max)
                    )
                self._fc_update_due = False
                self._fc_stream_due.clear()
                # fc records WHICH stream windows rode this packet so
                # a loss re-advertises exactly those (a lost data-
                # stream MAX_STREAM_DATA would otherwise deadlock it)
                mark(fc=tuple(fc_sids))
            self._maybe_parse_peer_params()
            # congestion window (RFC 9002 §7): new data AND threshold-
            # loss retransmissions are gated by cwnd (a halved window
            # must not re-burst the lost flight at line rate); only
            # PTO PROBES may exceed it (§7.5), one per fired PTO via
            # _probe_credit — without that exemption a fully
            # blackholed window deadlocks recovery.
            cc_room = self.cwnd - self.bytes_in_flight
            can_send = cc_room > 0 or self.bytes_in_flight == 0
            use_probe = False
            if not can_send and self._probe_credit > 0:
                can_send = use_probe = True
            stream_frame = None  # (sid, off, chunk)
            if can_send:
                for sid in sorted(self.streams):
                    st = self.streams[sid]
                    # retransmit lost chunks before new data
                    if st.rtx:
                        s_off, chunk = st.rtx.pop(0)
                        if len(chunk) > MAX_STREAM_CHUNK:  # legacy oversize
                            st.rtx.insert(
                                0,
                                (
                                    s_off + MAX_STREAM_CHUNK,
                                    chunk[MAX_STREAM_CHUNK:],
                                ),
                            )
                            chunk = chunk[:MAX_STREAM_CHUNK]
                        stream_frame = (sid, s_off, chunk)
                        st.unacked[s_off] = chunk
                        break
                    if st.out:
                        # peer flow control: the stream window bounds
                        # this stream's offset, the CONNECTION window
                        # bounds the SUM across streams (§4.1)
                        allowance = max(
                            0,
                            min(
                                st.tx_max - st.sent,
                                self.tx_max_data - self.tx_sent_total,
                            ),
                        )
                        chunk = st.out[:min(allowance, MAX_STREAM_CHUNK)]
                        if chunk:
                            stream_frame = (sid, st.sent, chunk)
                            st.unacked[st.sent] = chunk
                            st.sent += len(chunk)
                            self.tx_sent_total += len(chunk)
                            st.out = st.out[len(chunk):]
                            break
            if stream_frame is not None and use_probe:
                self._probe_credit -= 1
            if stream_frame is not None:
                sid, s_off, chunk = stream_frame
                out += (
                    bytes([FT_STREAM_BASE | 0x04 | 0x02])  # off+len
                    + enc_varint(sid)
                    + enc_varint(s_off)
                    + enc_varint(len(chunk)) + chunk
                )
                mark(stream=(sid, s_off, len(chunk)))
            if self.close_pending is not None:
                code, reason = self.close_pending
                r = reason.encode()[:64]
                out += (
                    bytes([FT_CONN_CLOSE_APP]) + enc_varint(code)
                    + enc_varint(len(r)) + r
                )
                self.close_pending = None
                self.closed = True
        return out, meta

    def flush(self) -> List[bytes]:
        """Datagrams ready to send (levels coalesced). Loops per level
        until drained (retransmissions emit one range per packet)."""
        dgrams: List[bytes] = []
        while True:
            dgram = b""
            for level in LEVELS:
                sp = self.spaces[level]
                if sp.tx is None:
                    continue
                frames, meta = self._pending_frames(level)
                if not frames:
                    continue
                if level == "initial" and not self.is_server:
                    # client Initials pad the DATAGRAM to >=1200 (RFC
                    # 9000 §14.1); header+tag overhead ~44B
                    need = 1200 - len(frames) - 28
                    if need > 0:
                        frames += b"\x00" * need
                pkt, pn = self._build_packet(level, frames)
                dgram += pkt
                if meta is not None:
                    meta.size = len(pkt)
                    self.bytes_in_flight += meta.size
                    sp.sent[pn] = meta
                    sp.last_eliciting_sent = meta.time
            if not dgram:
                return dgrams
            dgrams.append(dgram)

    # --- receive --------------------------------------------------------

    def datagram_received(self, data: bytes) -> None:
        off = 0
        while off < len(data) and not self.closed:
            consumed = self._packet_received(data[off:])
            if consumed <= 0:
                break
            off += consumed

    def _packet_received(self, data: bytes) -> int:
        first = data[0]
        if first & 0x80:  # long header
            version = struct.unpack_from(">I", data, 1)[0]
            if version != VERSION_V1:
                return -1
            ptype = (first & 0x30) >> 4
            off = 5
            dcid_len = data[off]
            off += 1 + dcid_len
            scid_len = data[off]
            peer_scid = data[off + 1 : off + 1 + scid_len]
            off += 1 + scid_len
            if ptype == 0:  # initial
                tok_len, off = dec_varint(data, off)
                off += tok_len
                level = "initial"
            elif ptype == 2:
                level = "handshake"
            else:
                return -1  # 0-RTT/Retry unsupported
            length, off = dec_varint(data, off)
            total = off + length
            if self.dcid == b"" or level == "initial":
                self.dcid = peer_scid  # latch the peer's CID
            sp = self.spaces[level]
            if sp.rx is None:
                return total
            try:
                pn, payload = unprotect(
                    sp.rx, data[:total], off, sp.largest_rx
                )
            except Exception:
                return total  # undecryptable: drop silently (RFC 9001)
            self._accept(level, sp, pn, payload)
            return total
        # short header: consumes the remainder of the datagram
        sp = self.spaces["app"]
        if sp.rx is None:
            return -1
        pn_off = 1 + len(self.scid)
        try:
            pn, payload = unprotect(sp.rx, data, pn_off, sp.largest_rx)
        except Exception:
            return -1
        self._accept("app", sp, pn, payload)
        return len(data)

    def _accept(self, level: str, sp: _Space, pn: int, payload: bytes) -> None:
        if pn in sp.received:
            return
        sp.received.add(pn)
        sp.largest_rx = max(sp.largest_rx, pn)
        if len(sp.received) > 256:
            # acks only describe the contiguous run below largest_rx;
            # anything 256 behind can never matter again
            floor = sp.largest_rx - 256
            sp.received = {p for p in sp.received if p >= floor}
        if self._handle_frames(level, payload):
            sp.ack_due = True

    # --- frames ---------------------------------------------------------

    def _handle_frames(self, level: str, payload: bytes) -> bool:
        """Returns True if any frame was ack-eliciting."""
        off = 0
        eliciting = False
        n = len(payload)
        while off < n:
            ft = payload[off]
            off += 1
            if ft == FT_PADDING:
                continue
            if ft == FT_PING:
                eliciting = True
                continue
            if ft == FT_ACK:
                largest, off = dec_varint(payload, off)
                _delay, off = dec_varint(payload, off)
                rc, off = dec_varint(payload, off)
                first, off = dec_varint(payload, off)
                # ranges stay as (lo, hi) BOUNDS — the varints are
                # peer-controlled up to 2^62; materializing them as a
                # set would be a one-frame memory-exhaustion DoS
                ranges = [(largest - first, largest)]
                lo = largest - first
                for i in range(rc):
                    gap, off = dec_varint(payload, off)
                    rng, off = dec_varint(payload, off)
                    if i < 1024:  # DoS cap on TRACKED ranges; the rest
                        hi = lo - gap - 2  # still parse (frame sync).
                        ranges.append((hi - rng, hi))
                        lo = hi - rng
                    # beyond the cap (a pathologically lossy link),
                    # unmatched acked packets are later threshold-lost
                    # and retransmit — duplicates the receiver already
                    # tolerates (ADVICE r4: bandwidth, not corruption)
                self._on_ack(level, ranges)
                continue
            if ft == FT_CRYPTO:
                coff, off = dec_varint(payload, off)
                clen, off = dec_varint(payload, off)
                self._crypto_in(level, coff, payload[off : off + clen])
                off += clen
                eliciting = True
                continue
            if FT_STREAM_BASE <= ft <= 0x0F:
                sid, off = dec_varint(payload, off)
                s_off = 0
                if ft & 0x04:
                    s_off, off = dec_varint(payload, off)
                if ft & 0x02:
                    slen, off = dec_varint(payload, off)
                else:
                    slen = n - off
                data = payload[off : off + slen]
                off += slen
                self._stream_in(sid, s_off, data, bool(ft & 0x01))
                eliciting = True
                continue
            if ft in (FT_CONN_CLOSE, FT_CONN_CLOSE_APP):
                code, off = dec_varint(payload, off)
                if ft == FT_CONN_CLOSE:
                    _ft2, off = dec_varint(payload, off)
                rlen, off = dec_varint(payload, off)
                off += rlen
                self._closed_by_peer()
                continue
            if ft == FT_HANDSHAKE_DONE:
                self.handshake_done = True
                eliciting = True
                continue
            if ft == FT_MAX_DATA:
                v, off = dec_varint(payload, off)
                self.tx_max_data = max(self.tx_max_data, v)
                eliciting = True
                continue
            if ft == FT_MAX_STREAM_DATA:
                sid, off = dec_varint(payload, off)
                v, off = dec_varint(payload, off)
                # only update KNOWN streams — a flood of window frames
                # for arbitrary ids must not allocate state
                st = self.streams.get(sid)
                if st is not None:
                    st.tx_max = max(st.tx_max, v)
                eliciting = True
                continue
            if ft in (0x12, 0x13):  # MAX_STREAMS
                _v, off = dec_varint(payload, off)
                eliciting = True
                continue
            if ft in (0x18,):  # NEW_CONNECTION_ID: skip fields
                _seq, off = dec_varint(payload, off)
                _rpt, off = dec_varint(payload, off)
                cl = payload[off]
                off += 1 + cl + 16
                eliciting = True
                continue
            # RFC 9000 §12.4: an unknown frame type is a
            # FRAME_ENCODING_ERROR — fail LOUDLY; silently skipping
            # would drop coalesced STREAM/CRYPTO data with no
            # retransmit to recover it
            log.warning("quic: unknown frame 0x%02x — closing", ft)
            self.close(0x07, f"unknown frame 0x{ft:02x}")
            return True
        return eliciting

    def _crypto_in(self, level: str, coff: int, data: bytes) -> None:
        sp = self.spaces[level]
        sp.crypto_in[coff] = data
        out = b""
        while sp.crypto_in_off in sp.crypto_in:
            chunk = sp.crypto_in.pop(sp.crypto_in_off)
            out += chunk
            sp.crypto_in_off += len(chunk)
        if out:
            try:
                self._tls_input(level, out)
            except TlsError as e:
                log.warning("quic tls failure: %s", e)
                self.close(0x0128, str(e))

    def _stream_in(self, sid: int, s_off: int, data: bytes, fin: bool) -> None:
        if sid % 4 != 0:
            # only client-initiated bidirectional streams are served
            # (the reference's quicer listener accepts the same set)
            self.close(0x05, f"unsupported stream id {sid}")
            return
        st = self.streams.get(sid)
        if st is None:
            if len(self.streams) >= self.MAX_STREAMS:
                self.close(0x04, "stream limit exceeded")
                return
            st = self._stream(sid)
        end = s_off + len(data)
        # FC accounting is OFFSET-based (RFC 9000 §4.1): duplicates /
        # retransmissions never advance the high-water marks, so a
        # PTO-probed copy of delivered data cannot trip a violation
        hwm_delta = max(0, end - st.rx_hwm)
        if end > st.rx_max or (
            self._rx_hwm_total + hwm_delta > self.rx_max_data
        ):
            # the peer overran a window we advertised (RFC 9000
            # §4.1): FLOW_CONTROL_ERROR, not silent acceptance
            self.close(0x03, "flow control violated")
            return
        st.rx_hwm = end if end > st.rx_hwm else st.rx_hwm
        self._rx_hwm_total += hwm_delta
        if s_off + len(data) <= st.rx_off:
            return  # spurious retransmission of delivered data
        if s_off < st.rx_off:
            # trim the already-delivered prefix so the chunk keys at
            # the reassembly cursor (a stale key would leak forever)
            data = data[st.rx_off - s_off:]
            s_off = st.rx_off
        st.rx[s_off] = data
        out = b""
        while st.rx_off in st.rx:
            chunk = st.rx.pop(st.rx_off)
            out += chunk
            st.rx_off += len(chunk)
        if out:
            self._rx_consumed += len(out)
            st.consumed += len(out)
            # replenish once half of EITHER window is consumed — the
            # (smaller) stream window exhausts first; keying only off
            # the connection window would deadlock a conformant peer
            if self.rx_max_data - self._rx_consumed < FC_CONN_WINDOW // 2:
                self._fc_update_due = True
            if st.rx_max - st.consumed < FC_STREAM_WINDOW // 2:
                self._fc_stream_due.add(sid)
            if sid == 0:
                if self.on_stream_data is not None:
                    self.on_stream_data(out)
            elif self.on_data_stream is not None:
                self.on_data_stream(sid, out)
        if fin:
            st.fin_rcvd = True
            if sid == 0:
                # the control stream closing ends the connection (the
                # reference tears the channel down with it); a data
                # stream's FIN just finishes that stream
                self._closed_by_peer()

    def _on_ack(self, level: str, ranges: list) -> None:
        sp = self.spaces[level]
        # clamp acknowledgment claims to what we actually sent
        sent_max = sp.next_pn - 1
        newly = [
            pn for pn in sp.sent
            if any(lo <= pn <= hi for lo, hi in ranges)
        ]
        if not newly:
            return
        sp.pto_count = 0  # forward progress resets the backoff
        now = self._clock()
        # RTT sample off the largest newly-acked packet (RFC 9002 §5)
        largest_newly = max(newly)
        sample = now - sp.sent[largest_newly].time
        if sample >= 0:
            if self.srtt is None:
                self.srtt = sample
                self.rttvar = sample / 2
            else:
                self.rttvar = 0.75 * self.rttvar + 0.25 * abs(
                    self.srtt - sample
                )
                self.srtt = 0.875 * self.srtt + 0.125 * sample
        for pn in newly:
            meta = sp.sent.pop(pn)
            self.bytes_in_flight = max(0, self.bytes_in_flight - meta.size)
            self._cc_on_ack(meta)
            if meta.stream is not None:
                sid, s_off, _ln = meta.stream
                st = self.streams.get(sid)
                if st is not None:
                    st.unacked.pop(s_off, None)
        claimed = max(hi for _lo, hi in ranges)
        sp.largest_acked = max(sp.largest_acked, min(claimed, sent_max))
        self._detect_losses(sp)

    # --- congestion control (RFC 9002 §7: NewReno) ----------------------

    def _cc_on_ack(self, meta: "_SentPacket") -> None:
        if meta.size <= 0 or meta.time <= self._recovery_start:
            return  # acks for pre-recovery packets don't grow cwnd
        if self.cwnd < self.ssthresh:
            self.cwnd += meta.size  # slow start (§7.3.1)
        else:
            # congestion avoidance: ~one MTU per cwnd of acked bytes
            self.cwnd += (
                self.max_datagram_size * meta.size // max(self.cwnd, 1)
            )

    def _cc_on_loss(self, meta: "_SentPacket") -> None:
        self.bytes_in_flight = max(0, self.bytes_in_flight - meta.size)
        if meta.time <= self._recovery_start:
            return  # one congestion event per recovery period (§7.3.1)
        self._recovery_start = self._clock()
        self.ssthresh = max(self.cwnd // 2, 2 * self.max_datagram_size)
        self.cwnd = self.ssthresh

    def _pto_interval(self, sp: _Space) -> float:
        """PTO = srtt + 4*rttvar + max_ack_delay, backed off (§6.2.1);
        the static initial value only seeds the first flight."""
        if self.srtt is None:
            base = PTO_INITIAL
        else:
            base = self.srtt + max(4 * self.rttvar, 0.001) + 0.025
        return min(max(base, 0.05) * (2 ** sp.pto_count), PTO_MAX)

    def _detect_losses(self, sp: _Space) -> None:
        """Packet-threshold loss (RFC 9002 §6.1.1): anything
        K_PACKET_THRESHOLD below the largest acked is lost."""
        lost = [
            pn for pn in sp.sent
            if pn <= sp.largest_acked - K_PACKET_THRESHOLD
        ]
        for pn in sorted(lost):
            meta = sp.sent.pop(pn)
            self._cc_on_loss(meta)
            self._declare_lost(sp, meta)

    def _declare_lost(self, sp: _Space, meta: "_SentPacket") -> None:
        if meta.crypto is not None:
            sp.crypto_rtx.append(meta.crypto)
        if meta.stream is not None:
            sid, s_off, _ln = meta.stream
            st = self.streams.get(sid)
            chunk = st.unacked.pop(s_off, None) if st is not None else None
            if chunk is not None:
                st.rtx.append((s_off, chunk))
        if meta.hs_done:
            self._hs_done_sent = False
        if meta.fc:
            # the peer may be BLOCKED on these updates; resend the
            # SAME stream windows (a lost data-stream MAX_STREAM_DATA
            # would otherwise deadlock that stream: its local rx_max
            # already advanced, so the consume trigger can't re-fire)
            self._fc_update_due = True
            if isinstance(meta.fc, tuple):
                self._fc_stream_due.update(meta.fc)

    def next_timeout(self) -> Optional[float]:
        """Earliest PTO deadline across spaces (absolute monotonic
        time), None when nothing is in flight."""
        deadline = None
        for sp in self.spaces.values():
            if sp.tx is None or not sp.sent:
                continue
            d = sp.last_eliciting_sent + self._pto_interval(sp)
            deadline = d if deadline is None else min(deadline, d)
        return deadline

    def on_timeout(self, now: Optional[float] = None) -> bool:
        """PTO expiry (RFC 9002 §6.2.4): send PROBE data — a duplicate
        of the oldest unacked crypto/stream range — without declaring
        the whole in-flight set lost (ADVICE r4: on paths with RTT
        near the timer a merely delayed ACK previously triggered a
        full spurious retransmit burst). In-flight packets stay
        tracked; real losses surface via the packet threshold when the
        probe's ack arrives. Returns True when anything became
        sendable (owner must flush)."""
        now = self._clock() if now is None else now
        fired = False
        for sp in self.spaces.values():
            if sp.tx is None or not sp.sent or self.closed:
                continue
            if now - sp.last_eliciting_sent < self._pto_interval(sp):
                continue
            sp.pto_count += 1
            # §7.5: probe packets may exceed the congestion window
            self._probe_credit = min(self._probe_credit + 1, 2)
            probed = False
            oldest = min(sp.sent, key=lambda pn: sp.sent[pn].time)
            meta = sp.sent[oldest]
            if meta.crypto is not None and meta.crypto not in sp.crypto_rtx:
                sp.crypto_rtx.append(meta.crypto)
                probed = True
            if meta.stream is not None:
                sid, s_off, _ln = meta.stream
                st = self.streams.get(sid)
                chunk = st.unacked.get(s_off) if st is not None else None
                if chunk is not None and all(o != s_off for o, _c in st.rtx):
                    st.rtx.append((s_off, chunk))
                    probed = True
            if not probed:
                sp.ping_due = True  # nothing rebuildable: bare probe
            fired = True
        return fired

    def _closed_by_peer(self) -> None:
        if not self.closed:
            self.closed = True
            if self.on_close is not None:
                self.on_close()

    # --- app API ---------------------------------------------------------

    def send_stream(self, data: bytes, sid: int = 0) -> None:
        st = self._stream(sid)
        st.out += data

    def next_client_stream(self) -> int:
        """Allocate the next client-initiated bidirectional stream id
        (0, 4, 8, ... — RFC 9000 §2.1). Client side only."""
        used = [s for s in self.streams if s % 4 == 0]
        return (max(used) + 4) if used else 0

    def close(self, code: int = 0, reason: str = "") -> None:
        if not self.closed:
            self.close_pending = (code, reason)

    def _tls_input(self, level: str, data: bytes) -> None:
        raise NotImplementedError


class ServerConnection(QuicConnection):
    def __init__(self, odcid: bytes, cert=None, psk_lookup=None):
        super().__init__(True, scid=os.urandom(8), dcid=b"")
        sp = self.spaces["initial"]
        sp.rx, sp.tx = initial_keys(odcid, is_server=True)
        self.tls = TlsServer(
            encode_transport_params(self.scid, odcid=odcid), cert=cert,
            psk_lookup=psk_lookup,
        )

    def _tls_input(self, level: str, data: bytes) -> None:
        if level == "initial":
            for lvl, out in self.tls.feed_initial(data):
                self.spaces[lvl].crypto_out += out
            if self.tls.server_hs_secret is not None:
                hs = self.spaces["handshake"]
                hs.rx = DirectionKeys(self.tls.client_hs_secret)
                hs.tx = DirectionKeys(self.tls.server_hs_secret)
                app = self.spaces["app"]
                app.rx = DirectionKeys(self.tls.client_app_secret)
                app.tx = DirectionKeys(self.tls.server_app_secret)
        elif level == "handshake":
            self.tls.feed_handshake(data)
            if self.tls.handshake_complete:
                self.handshake_done = True


class ClientConnection(QuicConnection):
    def __init__(self, psk_identity=None, psk=None):
        odcid = os.urandom(8)
        super().__init__(False, scid=os.urandom(8), dcid=odcid)
        sp = self.spaces["initial"]
        sp.rx, sp.tx = initial_keys(odcid, is_server=False)
        self.tls = TlsClient(
            encode_transport_params(self.scid),
            psk_identity=psk_identity, psk=psk,
        )
        sp.crypto_out += self.tls.client_hello()

    def _tls_input(self, level: str, data: bytes) -> None:
        if level == "initial":
            self.tls.feed_initial(data)
            if self.tls.client_hs_secret is not None:
                hs = self.spaces["handshake"]
                hs.rx = DirectionKeys(self.tls.server_hs_secret)
                hs.tx = DirectionKeys(self.tls.client_hs_secret)
        elif level == "handshake":
            fin = self.tls.feed_handshake(data)
            if fin is not None:
                self.spaces["handshake"].crypto_out += fin
                app = self.spaces["app"]
                app.rx = DirectionKeys(self.tls.server_app_secret)
                app.tx = DirectionKeys(self.tls.client_app_secret)


# --- UDP endpoints ---------------------------------------------------------


def _dgram_dcid(data: bytes) -> Optional[bytes]:
    """Destination CID of a datagram's first packet (routing key)."""
    try:
        if data[0] & 0x80:
            ln = data[5]
            return bytes(data[6 : 6 + ln])
        return bytes(data[1:9])  # our CIDs are always 8 bytes
    except IndexError:
        return None


class QuicStreamTransport:
    """Adapts stream 0 of a QUIC connection to the byte-stream
    transport contract the MQTT Connection runtime uses (read/write/
    drain/close/peername) — the quicer single-stream mode.

    MULTI-STREAM mode (emqx_quic_data_stream.erl): further client-
    initiated bidirectional streams are DATA streams. Each gets its
    own MQTT parser; its packets feed the SAME channel (so session,
    auth, aliases and quotas are shared) and the replies they elicit
    (PUBACK/PUBREC/...) return on the SAME stream, per the reference's
    per-stream ordering contract. Connection-level packets (CONNECT /
    DISCONNECT / AUTH) are only legal on the control stream — a data
    stream carrying one is a protocol error. Broker-initiated
    deliveries ride the control stream."""

    quic = True

    def __init__(self, conn: "ServerConnection", endpoint, addr):
        self.conn = conn
        self.endpoint = endpoint
        self.addr = addr
        self._q: asyncio.Queue = asyncio.Queue()
        self.mqtt_conn = None  # set by the endpoint after Connection()
        self._ds_q: Dict[int, asyncio.Queue] = {}
        self._ds_tasks: Dict[int, object] = {}
        conn.on_stream_data = self._q.put_nowait
        conn.on_data_stream = self._data_stream_in
        conn.on_close = self._on_conn_close

    def _on_conn_close(self) -> None:
        self._q.put_nowait(b"")
        for t in self._ds_tasks.values():
            t.cancel()
        self._ds_tasks.clear()

    def _data_stream_in(self, sid: int, data: bytes) -> None:
        q = self._ds_q.get(sid)
        if q is None:
            q = self._ds_q[sid] = asyncio.Queue()
            self._ds_tasks[sid] = asyncio.ensure_future(
                self._ds_run(sid, q)
            )
        q.put_nowait(data)

    def _ds_abort(self, reason: str) -> None:
        self.conn.close(0x0A, reason)
        self.endpoint.kick(self.conn)

    async def _ds_run(self, sid: int, q: asyncio.Queue) -> None:
        """One data stream's packet loop — the emqx_quic_data_stream
        process analog. Mirrors the control-stream run loop's gates:
        the SAME publish/byte limiters (a client must not evade quotas
        by spreading publishes over streams), the listener's packet-
        size cap, and connection-level-packet rejection. Replies
        return on this stream; keepalive is touched by the channel's
        own handle_packet."""
        from . import frame
        from .packet import Auth, Connect, Disconnect, Publish

        parser = None
        try:
            while True:
                data = await q.get()
                mc = self.mqtt_conn
                ch = getattr(mc, "channel", None)
                if ch is None or not ch.connected:
                    # data streams are valid only on a CONNECTed
                    # session (emqx_quic_data_stream waits for the
                    # control stream's CONNECT)
                    self._ds_abort("data stream before CONNECT")
                    return
                if parser is None:
                    parser = frame.Parser(
                        max_packet_size=mc.parser.max_packet_size,
                        proto_ver=ch.proto_ver,
                    )
                out = b""
                for pkt in parser.feed(data):
                    if isinstance(pkt, (Connect, Disconnect, Auth)):
                        self._ds_abort(
                            "connection-level packet on data stream"
                        )
                        return
                    if isinstance(pkt, Publish):
                        ok = await mc.pub_limiter.acquire(1.0)
                        ok = ok and await mc.byte_limiter.acquire(
                            float(len(pkt.payload))
                        )
                        if not ok:
                            self.endpoint.mqtt.broker.metrics.inc(
                                "messages.dropped.quota_exceeded"
                            )
                            self._ds_abort("publish quota exceeded")
                            return
                    replies = ch.handle_packet(pkt)
                    if ch.pending_publish is not None:
                        # per-stream order: this stream's next packet
                        # waits for its publish, as on the control stream
                        replies = replies + await ch.finish_publish()
                    for reply in replies:
                        out += frame.serialize(reply, ch.proto_ver)
                if out:
                    self.conn.send_stream(out, sid=sid)
                    self.endpoint.kick(self.conn)
        except asyncio.CancelledError:
            return
        except Exception as e:
            log.warning("quic data stream %d failed: %s", sid, e)
            self._ds_abort(f"data stream error: {e}")

    def peername(self):
        return self.addr

    async def read(self) -> bytes:
        if self.conn.closed and self._q.empty():
            return b""
        return await self._q.get()

    def write(self, data: bytes) -> None:
        self.conn.send_stream(data)
        self.endpoint.kick(self.conn)

    async def drain(self) -> None:
        self.endpoint.kick(self.conn)

    def close(self) -> None:
        if not self.conn.closed:
            self.conn.close(0, "server closed")
            self.endpoint.kick(self.conn)
            self.conn.closed = True
        self._q.put_nowait(b"")


class _QuicServerProtocol(asyncio.DatagramProtocol):
    def __init__(self, server: "QuicServer"):
        self.server = server

    def connection_made(self, transport):
        self.server._udp = transport

    def datagram_received(self, data, addr):
        try:
            self.server._on_datagram(data, addr)
        except Exception:
            log.exception("quic datagram crashed")


class QuicServer:
    """MQTT-over-QUIC listener: owns the UDP socket, routes datagrams
    to connections by CID, and hands handshaken connections to the
    MQTT Connection runtime of an ordinary `Server` (emqx_listeners
    quic listener analog)."""

    HANDSHAKE_TIMEOUT = 10.0  # reap pre-handshake conns (spoofed
    # Initials are cheap to send; state for them must not be)

    def __init__(self, mqtt_server, host: str = "0.0.0.0", port: int = 14567,
                 cert=None, psk_store=None):
        import time as _time

        self.mqtt = mqtt_server  # a broker Server (never TCP-started)
        self.host, self.port = host, port
        self._udp = None
        self.listen_addr = None
        self.conns: Dict[bytes, ServerConnection] = {}
        self._addr: Dict[bytes, tuple] = {}  # scid -> last peer addr
        self._started: set = set()
        self._conn_tasks: set = set()  # retained connection-run handles
        self._born: Dict[bytes, float] = {}  # scid -> accept time
        self._now = _time.monotonic
        # ONE certificate per listener (configurable PEMs or generated
        # once) — not per connection
        from .quic_tls import make_server_cert

        # TLS-PSK identity store (emqx_psk analog); enables psk_dhe_ke
        # on this listener when set
        self.psk_store = psk_store

        self.cert = cert or make_server_cert()
        self._gc_task = None
        self._pto_task = None

    async def start(self) -> None:
        loop = asyncio.get_running_loop()
        await loop.create_datagram_endpoint(
            lambda: _QuicServerProtocol(self),
            local_addr=(self.host, self.port),
        )
        self.listen_addr = self._udp.get_extra_info("sockname")[:2]
        self._gc_task = asyncio.ensure_future(self._gc_loop())
        self._pto_task = asyncio.ensure_future(self._pto_loop())
        log.info("quic listening on %s", self.listen_addr)

    async def _pto_loop(self) -> None:
        """Recovery pump: fire overdue PTOs and ship retransmissions
        (RFC 9002 §6.2). 100ms granularity bounds timer error well
        under one PTO backoff step."""
        while True:
            try:
                await asyncio.sleep(0.1)
                for scid, conn in list(self.conns.items()):
                    if conn.closed:
                        continue
                    if conn.on_timeout():
                        addr = self._addr.get(conn.scid)
                        if addr is not None and self._udp is not None:
                            for dgram in conn.flush():
                                self._udp.sendto(dgram, addr)
            except asyncio.CancelledError:
                return
            except Exception:
                log.exception("quic pto loop crashed")

    async def _gc_loop(self) -> None:
        while True:
            try:
                await asyncio.sleep(min(2.0, self.HANDSHAKE_TIMEOUT / 2))
                now = self._now()
                for scid, born in list(self._born.items()):
                    conn = self.conns.get(scid)
                    if conn is None:
                        self._born.pop(scid, None)
                        continue
                    if scid in self._started:
                        continue
                    if now - born > self.HANDSHAKE_TIMEOUT:
                        self._forget(conn)
            except asyncio.CancelledError:
                return
            except Exception:
                log.exception("quic gc crashed")

    def _forget(self, conn: "ServerConnection") -> None:
        for k in [k for k, v in self.conns.items() if v is conn]:
            self.conns.pop(k, None)
        self._addr.pop(conn.scid, None)
        self._born.pop(conn.scid, None)
        self._started.discard(conn.scid)

    async def stop(self) -> None:
        for conn in set(self.conns.values()):
            conn.close(0, "listener stopped")
            self.kick(conn)
        if self._gc_task is not None:
            self._gc_task.cancel()
            self._gc_task = None
        if getattr(self, "_pto_task", None) is not None:
            self._pto_task.cancel()
            self._pto_task = None
        if self._udp is not None:
            self._udp.close()
            self._udp = None

    def kick(self, conn: "ServerConnection") -> None:
        addr = self._addr.get(conn.scid)
        if addr is None or self._udp is None:
            return
        for dgram in conn.flush():
            self._udp.sendto(dgram, addr)

    def _on_datagram(self, data: bytes, addr) -> None:
        cid = _dgram_dcid(data)
        if cid is None:
            return
        conn = self.conns.get(cid)
        if conn is None:
            if not data[0] & 0x80 or len(data) < 1200:
                return  # only full-size Initials create state
            # accept gates: eviction + the listener's conn-rate bucket,
            # exactly like the TCP accept path
            if self.mqtt.evicting or not self.mqtt.limits.accept_allowed():
                self.mqtt.broker.metrics.inc("listener.conn_rate_limited")
                return
            conn = ServerConnection(
                odcid=cid, cert=self.cert,
                psk_lookup=(
                    self.psk_store.lookup if self.psk_store is not None
                    else None
                ),
            )
            self.conns[cid] = conn
            self.conns[conn.scid] = conn
            self._born[conn.scid] = self._now()
        self._addr[conn.scid] = addr
        conn.datagram_received(data)
        self.kick(conn)
        if conn.tls.handshake_complete and conn.scid not in self._started:
            self._started.add(conn.scid)
            transport = QuicStreamTransport(conn, self, addr)
            from .server import Connection

            mqtt_conn = Connection(self.mqtt, transport)
            transport.mqtt_conn = mqtt_conn  # data-stream channel seam
            self.mqtt._conns.add(mqtt_conn)

            async def run():
                try:
                    await mqtt_conn.run()
                finally:
                    self.mqtt._conns.discard(mqtt_conn)
                    self._forget(conn)

            task = asyncio.ensure_future(run())
            self._conn_tasks.add(task)
            task.add_done_callback(self._conn_tasks.discard)


class QuicClientEndpoint:
    """Client seam: UDP socket + ClientConnection + handshake pump.
    recv() yields ordered stream-0 bytes (the MQTT byte stream)."""

    def __init__(self, psk_identity=None, psk=None):
        self.conn = ClientConnection(psk_identity=psk_identity, psk=psk)
        self._udp = None
        self.addr = None
        self._q: asyncio.Queue = asyncio.Queue()
        self._ds_q: Dict[int, asyncio.Queue] = {}  # data-stream inboxes
        self.conn.on_stream_data = self._q.put_nowait
        self.conn.on_data_stream = self._on_ds
        self.conn.on_close = lambda: self._q.put_nowait(b"")

    def _on_ds(self, sid: int, data: bytes) -> None:
        self._ds_q.setdefault(sid, asyncio.Queue()).put_nowait(data)

    async def connect(self, host: str, port: int, timeout: float = 5.0):
        loop = asyncio.get_running_loop()
        outer = self

        class P(asyncio.DatagramProtocol):
            def connection_made(self, tr):
                outer._udp = tr

            def datagram_received(self, data, _addr):
                outer.conn.datagram_received(data)
                outer._flush()

        await loop.create_datagram_endpoint(P, remote_addr=(host, port))
        self.addr = (host, port)
        self._flush()  # ships the Initial (client hello)
        deadline = loop.time() + timeout
        while not self.conn.handshake_done:
            if loop.time() > deadline:
                raise TimeoutError("quic handshake timed out")
            await asyncio.sleep(0.005)
            # drive client-side loss recovery during the handshake too:
            # a dropped Initial/Handshake datagram must retransmit
            self.conn.on_timeout()
            self._flush()
        self._pump_task = asyncio.ensure_future(self._pump())
        return self

    async def _pump(self) -> None:
        """Post-handshake recovery pump (PTO + retransmissions)."""
        while not self.conn.closed:
            await asyncio.sleep(0.1)
            try:
                if self.conn.on_timeout():
                    self._flush()
            except Exception:
                log.exception("quic client pump crashed")
                return

    def _flush(self) -> None:
        if self._udp is None:
            return
        for dgram in self.conn.flush():
            self._udp.sendto(dgram)

    def send(self, data: bytes) -> None:
        self.conn.send_stream(data)
        self._flush()

    async def recv(self, timeout: float = 5.0) -> bytes:
        return await asyncio.wait_for(self._q.get(), timeout)

    # --- multi-stream mode (data streams) ----------------------------
    def open_stream(self) -> int:
        """Open a new client-initiated bidi DATA stream; returns its
        id (the reference's multi-stream mode publishes on these)."""
        sid = self.conn.next_client_stream()
        self.conn._stream(sid)
        self._ds_q.setdefault(sid, asyncio.Queue())
        return sid

    def send_on(self, sid: int, data: bytes) -> None:
        self.conn.send_stream(data, sid=sid)
        self._flush()

    async def recv_on(self, sid: int, timeout: float = 5.0) -> bytes:
        q = self._ds_q.setdefault(sid, asyncio.Queue())
        return await asyncio.wait_for(q.get(), timeout)

    def close(self) -> None:
        t = getattr(self, "_pump_task", None)
        if t is not None:
            t.cancel()
        self.conn.close(0, "client done")
        self._flush()
        if self._udp is not None:
            self._udp.close()
            self._udp = None
