"""A minimal MQTT 5 client codec for the load generator.

Written from the MQTT 5.0 specification (OASIS, 2019), independent of
the broker's own codec, so that a fault in the broker's framing cannot
cancel out on the client side. It covers only what the generator sends
and reads: CONNECT/CONNACK, PUBLISH (QoS 0 and 1, no properties sent),
PUBACK and SUBSCRIBE/SUBACK.
"""

from __future__ import annotations

import struct
from typing import Iterator, List, Tuple

CONNECT, CONNACK, PUBLISH, PUBACK, SUBSCRIBE, SUBACK = 1, 2, 3, 4, 8, 9


def varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | 0x80 if n else b)
        if not n:
            return bytes(out)


def _packet(first: int, body: bytes) -> bytes:
    return bytes((first,)) + varint(len(body)) + body


def _str(s: str) -> bytes:
    b = s.encode()
    return struct.pack(">H", len(b)) + b


def connect(client_id: str, keepalive: int = 0) -> bytes:
    # protocol name, level 5, flags: clean start; no properties
    body = _str("MQTT") + bytes((5, 0x02)) + struct.pack(">H", keepalive)
    return _packet(CONNECT << 4, body + b"\x00" + _str(client_id))


def subscribe(packet_id: int, flt: str, qos: int = 0) -> bytes:
    body = struct.pack(">H", packet_id) + b"\x00" + _str(flt) + bytes((qos,))
    return _packet((SUBSCRIBE << 4) | 0x02, body)


def publish(topic: bytes, payload: bytes, qos: int, packet_id: int) -> bytes:
    """`topic` is already UTF-8 encoded and length-prefixed (`topic_field`)."""
    if qos:
        body = topic + struct.pack(">H", packet_id) + b"\x00" + payload
    else:
        body = topic + b"\x00" + payload
    return _packet((PUBLISH << 4) | (qos << 1), body)


def topic_field(topic: str) -> bytes:
    return _str(topic)


class Reader:
    """Splits a byte stream into (type, flags, body) packets."""

    __slots__ = ("buf",)

    def __init__(self):
        self.buf = b""

    def feed(self, data: bytes) -> Iterator[Tuple[int, int, memoryview]]:
        buf = self.buf + data if self.buf else data
        mv = memoryview(buf)
        pos, end = 0, len(buf)
        while end - pos >= 2:
            first = buf[pos]
            n, mul, i = 0, 1, pos + 1
            while True:
                if i >= end:
                    self.buf = buf[pos:]
                    return
                b = buf[i]
                n += (b & 0x7F) * mul
                mul <<= 7
                i += 1
                if not b & 0x80:
                    break
            if end - i < n:
                break
            yield first >> 4, first & 0x0F, mv[i:i + n]
            pos = i + n
        self.buf = buf[pos:]


def parse_varint(mv: memoryview, pos: int) -> Tuple[int, int]:
    n, mul = 0, 1
    while True:
        b = mv[pos]
        pos += 1
        n += (b & 0x7F) * mul
        mul <<= 7
        if not b & 0x80:
            return n, pos


def publish_payload(flags: int, body: memoryview) -> memoryview:
    """The application payload of a received PUBLISH body."""
    (tlen,) = struct.unpack_from(">H", body, 0)
    pos = 2 + tlen + (2 if (flags >> 1) & 3 else 0)
    plen, pos = parse_varint(body, pos)
    return body[pos + plen:]


def puback(body: memoryview) -> Tuple[int, int]:
    """(packet id, reason code) of a PUBACK body."""
    (pid,) = struct.unpack_from(">H", body, 0)
    return pid, (body[2] if len(body) > 2 else 0)


def suback_codes(body: memoryview) -> Tuple[int, List[int]]:
    (pid,) = struct.unpack_from(">H", body, 0)
    plen, pos = parse_varint(body, 2)
    return pid, list(body[pos + plen:])


def connack_code(body: memoryview) -> int:
    return body[1]
