#!/usr/bin/env python3
"""Load generator process: MQTT clients over loopback, no JAX.

The harness starts one process per role as a new interpreter (with
`JAX_PLATFORMS=cpu` in its environment; nothing here imports JAX) and
talks to it over stdin/stdout, one command per line:

  role "pub": N publisher connections. On `warm` it starts warm-up
    traffic; on `window <T_ns> <rate>` it switches to the measured
    schedule at monotonic time T and stops at T + seconds, then waits
    for every QoS 1 PUBACK of the window (at most DRAIN_S), writes its
    records and prints `done`.
  role "sub": socket subscribers. It records every delivery until
    `stop`, then writes its records and prints `done`.

Both print `ready <seconds>` once every client is connected (and
subscribed). Open loop: publishes follow a Poisson schedule from the
seed, timed on CLOCK_MONOTONIC, which every process on the host shares;
each payload carries the message id and its due time. Closed loop:
every connection keeps `inflight` QoS 1 publishes unacknowledged.

One thread, one epoll set: a publish is written when due (the loop
sleeps in `time.sleep` for the last millisecond, so sends are not held
to the poll's millisecond ticks).
"""

from __future__ import annotations

import json
import os
import resource
import select
import socket
import struct
import sys
import time
from array import array

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import mqtt  # noqa: E402
import table as tbl  # noqa: E402

CONNECT_CHUNK = 64  # connects in flight at once (the listen backlog is 100)
DRAIN_S = 60.0  # wait for a window's answers this long after it closes
WINDOW_BITS = 40  # message id = window number << 40 | index in the window
WARM_BLOCK_S = 2.0  # warm-up schedule is generated this many seconds at a time
mono_ns = time.monotonic_ns


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def say(msg: str) -> None:
    print(msg, flush=True)


def _recv_packet(sock: socket.socket, reader: mqtt.Reader):
    while True:
        data = sock.recv(65536)
        if not data:
            raise ConnectionError("broker closed the connection")
        for pkt in reader.feed(data):
            return pkt[0], pkt[1], bytes(pkt[2])


def open_clients(port: int, ids: list, filters: list = ()) -> list:
    """Connect (and subscribe) clients in chunks; blocking, then switched
    to non-blocking for the serving loop."""
    socks = []
    for c0 in range(0, len(ids), CONNECT_CHUNK):
        chunk = []
        for cid in ids[c0:c0 + CONNECT_CHUNK]:
            s = socket.create_connection(("127.0.0.1", port), timeout=120)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            s.sendall(mqtt.connect(cid))
            chunk.append(s)
        for s in chunk:
            r = mqtt.Reader()
            typ, _f, body = _recv_packet(s, r)
            code = mqtt.connack_code(memoryview(body))
            if typ != mqtt.CONNACK or code:
                raise ConnectionError(f"CONNACK {typ} code {code:#x}")
        socks += chunk
    for k, flt in enumerate(filters):
        s = socks[k]
        s.sendall(mqtt.subscribe(1, flt, 0))
        typ, _f, body = _recv_packet(s, mqtt.Reader())
        _pid, codes = mqtt.suback_codes(memoryview(body))
        if typ != mqtt.SUBACK or codes != [0]:
            raise ConnectionError(f"SUBACK {codes} for {flt}")
    for s in socks:
        s.setblocking(False)
    return socks


class Stdin:
    """Line commands from the harness, read without blocking."""

    def __init__(self, ep):
        self.fd = sys.stdin.fileno()
        os.set_blocking(self.fd, False)
        ep.register(self.fd, select.EPOLLIN)
        self.buf = b""

    def lines(self) -> list:
        try:
            data = os.read(self.fd, 4096)
        except BlockingIOError:
            return []
        if not data:
            return ["quit"]
        self.buf += data
        *done, self.buf = self.buf.split(b"\n")
        return [x.decode().strip() for x in done if x.strip()]


# --- publishers -------------------------------------------------------------


class Publishers:
    def __init__(self, spec: dict):
        self.spec = spec
        traffic = spec["traffic"]
        self.loop_kind = traffic["loop"]
        self.table = tbl.Table(spec["conf"], spec["seed"])
        self.seed = spec["seed"]
        self.seconds = float(spec["seconds"])
        self.n_conns = int(traffic["connections"])
        self.qos1_share = float(traffic["qos1_share"])
        self.draw = traffic["topic_draw"]
        self.pad = b"\x00" * (tbl.payload_size(traffic) - 16)
        self.topics: dict = {}
        self.ep = select.epoll()
        t0 = time.monotonic()
        self.socks = open_clients(
            spec["port"], [f"pub{k}" for k in range(self.n_conns)]
        )
        self.connect_s = time.monotonic() - t0
        self.fd_conn = {}
        for k, s in enumerate(self.socks):
            self.fd_conn[s.fileno()] = k
            self.ep.register(s.fileno(), select.EPOLLIN)
        self.readers = [mqtt.Reader() for _ in self.socks]
        self.next_pid = [0] * self.n_conns
        self.pid_msg = [dict() for _ in range(self.n_conns)]
        self.inflight = [0] * self.n_conns
        self.stdin = Stdin(self.ep)
        self.phase = "idle"
        self.window_t = None  # (T_ns, end_ns, rate)
        self.window_no = 0
        self.warm_stream = 0
        self.rate = 0.0
        self.warm_sent = 0
        self.warm_acked = 0
        self.outstanding = 0  # window QoS 1 publishes not yet acked
        # window records
        self.rec = {
            k: array("q") for k in
            ("msg", "conn", "device", "qos", "due", "sent", "ack", "code")
        }
        self.ack_of: dict = {}  # window msg id -> record index
        self.closed_pool = None
        self.closed_cursor = 0

    # schedule pieces --------------------------------------------------------

    def topic_of(self, dev: int) -> bytes:
        t = self.topics.get(dev)
        if t is None:
            t = self.topics[dev] = mqtt.topic_field(self.table.topic(dev))
        return t

    def send(self, conn: int, dev: int, qos: int, msg: int, due: int) -> int:
        pid = 0
        if qos:
            pid = self.next_pid[conn] % 65535 + 1
            self.next_pid[conn] = pid
            self.pid_msg[conn][pid] = msg
            self.inflight[conn] += 1
        payload = struct.pack("<QQ", msg, due) + self.pad
        data = mqtt.publish(self.topic_of(dev), payload, qos, pid)
        s = self.socks[conn]
        n = s.send(data)
        if n != len(data):
            s.setblocking(True)
            s.sendall(data[n:])
            s.setblocking(False)
        return mono_ns()

    def warm_block(self, start_ns: int, rate: float):
        n = max(1, int(round(rate * WARM_BLOCK_S)))
        st = 1000 + self.warm_stream
        self.warm_stream += 1
        off = tbl.poisson_offsets(n, WARM_BLOCK_S, self.seed, st)
        dev = tbl.device_draw(n, self.table.n, self.seed, st + 50000, self.draw)
        qos = tbl.qos_draw(n, self.qos1_share, self.seed, st + 90000)
        due = start_ns + (off * 1e9).astype(np.int64)
        return due, dev, qos

    def window_block(self, t_ns: int, rate: float):
        n = max(1, int(round(rate * self.seconds)))
        st = 10 * (self.window_no + 1)
        off = tbl.poisson_offsets(n, self.seconds, self.seed, st)
        dev = tbl.device_draw(n, self.table.n, self.seed, st + 1, self.draw)
        qos = tbl.qos_draw(n, self.qos1_share, self.seed, st + 2)
        due = t_ns + (off * 1e9).astype(np.int64)
        return due, dev, qos

    def record(self, msg, conn, dev, qos, due, sent) -> None:
        r = self.rec
        if qos:
            self.ack_of[msg] = len(r["msg"])
            self.outstanding += 1
        r["msg"].append(msg)
        r["conn"].append(conn)
        r["device"].append(dev)
        r["qos"].append(qos)
        r["due"].append(due)
        r["sent"].append(sent)
        r["ack"].append(0)
        r["code"].append(-1)

    # reads ----------------------------------------------------------------

    def on_readable(self, fd: int, now: int) -> None:
        conn = self.fd_conn[fd]
        try:
            data = self.socks[conn].recv(65536)
        except BlockingIOError:
            return
        if not data:
            raise ConnectionError(f"broker closed publisher pub{conn}")
        for typ, _flags, body in self.readers[conn].feed(data):
            if typ != mqtt.PUBACK:
                continue
            pid, code = mqtt.puback(body)
            msg = self.pid_msg[conn].pop(pid, None)
            if msg is None:
                continue
            self.inflight[conn] -= 1
            if msg & tbl.WARM_BIT:
                self.warm_acked += 1
            else:
                i = self.ack_of.pop(msg, None)
                if i is not None:
                    self.rec["ack"][i] = now
                    self.rec["code"][i] = code
                    self.outstanding -= 1
            if self.loop_kind == "closed":
                self.closed_next(conn, now)

    # closed loop ------------------------------------------------------------

    def closed_next(self, conn: int, now: int) -> None:
        """Top connection `conn` up to `inflight` unacknowledged
        publishes; those sent from T on are the window's, and from
        T + seconds on nothing more is sent."""
        if self.phase not in ("warm", "window"):
            return
        wt = self.window_t
        if wt is not None and now >= wt[1]:
            return
        in_window = wt is not None and now >= wt[0]
        pool = self.closed_pool
        while self.inflight[conn] < self.inflight_max:
            dev = int(pool[self.closed_cursor % len(pool)])
            self.closed_cursor += 1
            if not in_window:
                msg = tbl.WARM_BIT | self.warm_sent
                self.warm_sent += 1
                self.send(conn, dev, 1, msg, mono_ns())
            else:
                msg = (self.window_no << WINDOW_BITS) | len(self.rec["msg"])
                t = mono_ns()
                sent = self.send(conn, dev, 1, msg, t)
                self.record(msg, conn, dev, 1, t, sent)

    # main loop --------------------------------------------------------------

    def commands(self) -> bool:
        for line in self.stdin.lines():
            words = line.split()
            if words[0] == "quit":
                return False
            if words[0] == "warm":
                self.phase = "warm"
                self.rate = float(words[1])
                self.start_warm(mono_ns())
            elif words[0] == "window":
                t_ns, rate = int(words[1]), float(words[2])
                self.window_t = (t_ns, t_ns + int(self.seconds * 1e9), rate)
                self.window_no += 1
                for v in self.rec.values():
                    del v[:]
                self.ack_of.clear()
                self.outstanding = 0
        return True

    def start_warm(self, now: int) -> None:
        if self.loop_kind == "open":
            self.sched = self.warm_block(now, self.rate)
            self.j = 0
        else:
            self.inflight_max = int(self.spec["traffic"]["inflight"])
            self.closed_pool = tbl.device_draw(
                self.table.n, self.table.n, self.seed, 77, self.draw
            )
            for conn in range(self.n_conns):
                self.closed_next(conn, now)

    def run(self) -> None:
        say(f"ready {self.connect_s:.6f}")
        while True:
            now = mono_ns()
            if self.loop_kind == "open":
                self.open_step(now)
            elif self.phase in ("warm", "window") and self.window_t is not None:
                if self.phase == "warm" and now >= self.window_t[0]:
                    self.phase = "window"
                if self.phase == "window" and now >= self.window_t[1]:
                    self.phase = "drain"
                    self.drain_until = now + int(DRAIN_S * 1e9)
            if self.phase == "drain":
                if self.outstanding <= 0 or now >= self.drain_until:
                    self.finish()
            timeout = self.wait_s(now)
            for fd, _ev in self.ep.poll(timeout):
                if fd == self.stdin.fd:
                    if not self.commands():
                        return
                else:
                    self.on_readable(fd, mono_ns())
            self.sleep_to_due()

    def open_step(self, now: int) -> None:
        if self.phase not in ("warm", "window"):
            return
        due, dev, qos = self.sched
        if self.phase == "warm" and self.window_t is not None:
            t_ns = self.window_t[0]
            if self.j >= len(due) or due[self.j] >= t_ns:
                self.phase = "window"
                self.sched = self.window_block(t_ns, self.window_t[2])
                self.j = 0
                due, dev, qos = self.sched
        limit = now + 50_000
        j = self.j
        warm = self.phase == "warm"
        while j < len(due) and due[j] <= limit:
            d, q, dv = int(due[j]), int(qos[j]), int(dev[j])
            conn = dv % self.n_conns
            if warm:
                msg = tbl.WARM_BIT | self.warm_sent
                self.warm_sent += 1
                self.send(conn, dv, q, msg, d)
            else:
                msg = (self.window_no << WINDOW_BITS) | j
                sent = self.send(conn, dv, q, msg, d)
                self.record(msg, conn, dv, q, d, sent)
            j += 1
        self.j = j
        if j >= len(due):
            if warm:
                start = int(due[-1]) + int(1e9 / self.rate)
                self.sched = self.warm_block(start, self.rate)
                self.j = 0
            else:
                self.phase = "drain"
                self.drain_until = mono_ns() + int(DRAIN_S * 1e9)

    def next_due(self):
        if self.loop_kind == "open" and self.phase in ("warm", "window"):
            due = self.sched[0]
            if self.j < len(due):
                return int(due[self.j])
        if self.window_t is not None and self.phase in ("warm", "window"):
            return self.window_t[0] if self.phase == "warm" else self.window_t[1]
        return None

    def wait_s(self, now: int) -> float:
        nd = self.next_due()
        if nd is None:
            return 0.05
        wait = nd - now
        if wait > 1_500_000:
            return (wait - 1_000_000) / 1e9
        return 0

    def sleep_to_due(self) -> None:
        nd = self.next_due()
        if nd is None:
            return
        wait = nd - mono_ns()
        if 0 < wait <= 1_500_000:
            time.sleep(max(0, wait - 20_000) / 1e9)

    def finish(self) -> None:
        out = {k: np.frombuffer(v, np.int64) if len(v) else np.zeros(0, np.int64)
               for k, v in self.rec.items()}
        out["meta"] = np.array(
            [self.warm_sent, self.warm_acked, self.outstanding], np.int64
        )
        np.savez(self.spec["out"], **out)
        self.phase = "idle"
        self.window_t = None
        say(f"done {len(self.rec['msg'])} {self.outstanding}")


# --- subscribers ------------------------------------------------------------


class Subscribers:
    def __init__(self, spec: dict):
        self.spec = spec
        filters = spec["filters"]
        self.ep = select.epoll()
        t0 = time.monotonic()
        self.socks = open_clients(
            spec["port"], [f"sub{k}" for k in range(len(filters))], filters
        )
        self.connect_s = time.monotonic() - t0
        self.fd_sub = {}
        for k, s in enumerate(self.socks):
            self.fd_sub[s.fileno()] = k
            self.ep.register(s.fileno(), select.EPOLLIN)
        self.readers = [mqtt.Reader() for _ in self.socks]
        self.stdin = Stdin(self.ep)
        self.sub = array("q")
        self.msg = array("q")
        self.at = array("q")

    def run(self) -> None:
        say(f"ready {self.connect_s:.6f}")
        unpack = struct.unpack_from
        while True:
            for fd, _ev in self.ep.poll(0.5):
                if fd == self.stdin.fd:
                    for line in self.stdin.lines():
                        if line.split()[0] in ("stop", "quit"):
                            self.finish()
                            return
                    continue
                k = self.fd_sub[fd]
                try:
                    data = self.socks[k].recv(1 << 18)
                except BlockingIOError:
                    continue
                now = mono_ns()
                if not data:
                    raise ConnectionError(f"broker closed subscriber sub{k}")
                for typ, flags, body in self.readers[k].feed(data):
                    if typ != mqtt.PUBLISH:
                        continue
                    payload = mqtt.publish_payload(flags, body)
                    (mid,) = unpack("<Q", payload, 0)
                    self.sub.append(k)
                    self.msg.append(mid)
                    self.at.append(now)

    def finish(self) -> None:
        np.savez(
            self.spec["out"],
            sub=np.frombuffer(self.sub, np.int64) if len(self.sub) else np.zeros(0, np.int64),
            msg=np.frombuffer(self.msg, np.int64) if len(self.msg) else np.zeros(0, np.int64),
            at=np.frombuffer(self.at, np.int64) if len(self.at) else np.zeros(0, np.int64),
        )
        say(f"done {len(self.msg)}")


def main() -> int:
    with open(sys.argv[1]) as f:
        spec = json.load(f)
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    try:
        role = Publishers(spec) if spec["role"] == "pub" else Subscribers(spec)
        role.run()
    except Exception as e:  # the harness reads this line and fails the run
        log(f"loadgen {spec['role']} failed: {type(e).__name__}: {e}")
        return 1
    finally:
        for s in getattr(locals().get("role"), "socks", ()):
            s.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
