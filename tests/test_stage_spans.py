"""The stage register (obs/profiler.STAGE_MARK) on the trace's clock.

While a `jax.profiler` trace runs, every stage transition on the
broker's event loop ends the open `emqx.<stage>` host span and starts
the next, so a trace of socket publishes shows the loop's stages, flat
and in order, beside the device's operations. With no trace running a
transition creates no TraceMe and reads no clock.
"""

import asyncio
import gc
import glob
import json
import os
import threading
import time

import jax
import pytest
from jax.profiler import ProfileData

from emqx_tpu.boot import Node
from emqx_tpu.broker import frame as F
from emqx_tpu.broker.packet import (
    MQTT_V5, Connack, Connect, Puback, Publish, Suback, SubOpts, Subscribe,
)
from emqx_tpu.obs import profiler
from emqx_tpu.obs.profiler import SPAN_PREFIX, STAGE_MARK, STAGES


class Client:
    def __init__(self, port):
        self.port = port
        self.parser = F.Parser(proto_ver=MQTT_V5)
        self.inbox = asyncio.Queue()

    async def connect(self, cid):
        self.r, self.w = await asyncio.open_connection("127.0.0.1", self.port)
        self.task = asyncio.ensure_future(self._read())
        self.send(Connect(client_id=cid, proto_ver=MQTT_V5))
        assert (await self.expect(Connack)).code == 0
        return self

    async def _read(self):
        while True:
            data = await self.r.read(65536)
            if not data:
                return
            for pkt in self.parser.feed(data):
                await self.inbox.put(pkt)

    def send(self, *pkts):
        self.w.write(b"".join(F.serialize(p, MQTT_V5) for p in pkts))

    async def expect(self, typ, timeout=10.0):
        pkt = await asyncio.wait_for(self.inbox.get(), timeout)
        assert isinstance(pkt, typ), pkt
        return pkt

    def close(self):
        self.task.cancel()
        self.w.close()


def _host_spans(trace_dir):
    """{thread line name: [(name, start_ns, end_ns, stats)]} of the
    trace's `emqx.*` host events, each line sorted by start."""
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
                for e in line.events
                if e.name.startswith(SPAN_PREFIX)
            ]
            if evs:
                out.setdefault(line.name, []).extend(
                    sorted(evs, key=lambda e: e[1])
                )
    return out


async def test_socket_publishes_leave_flat_stage_spans(tmp_path):
    node = Node(config_text=json.dumps({
        "node": {"data_dir": str(tmp_path / "d")},
        "listeners": {"tcp": {"default": {"bind": "127.0.0.1:0"}}},
        "api": {"enable": False},
    }))
    await node.start()
    trace_dir = str(tmp_path / "trace")
    try:
        port = node.listeners.get("tcp", "default").listen_addr[1]
        sub = await Client(port).connect("sub")
        sub.send(Subscribe(1, [("st/+", SubOpts(qos=1))]))
        await sub.expect(Suback)
        pub = await Client(port).connect("pub")
        jax.profiler.start_trace(trace_dir)
        try:
            pub.send(Publish(topic="st/0", payload=b"q0", qos=0))
            for i in range(1, 5):
                pub.send(Publish(topic=f"st/{i}", payload=b"q1", qos=1,
                                 packet_id=i))
            acks = [await pub.expect(Puback) for _ in range(4)]
            got = [await sub.expect(Publish) for _ in range(5)]
            gc.collect()  # a collection is the `gc` stage
        finally:
            jax.profiler.stop_trace()
        assert [a.packet_id for a in acks] == [1, 2, 3, 4]
        assert sorted(p.topic for p in got) == [f"st/{i}" for i in range(5)]
        pub.close()
        sub.close()
    finally:
        await node.stop()
    lines = _host_spans(trace_dir)
    # only the loop's thread carries stage spans
    assert len(lines) == 1, sorted(lines)
    (spans,) = lines.values()
    names = {n[len(SPAN_PREFIX):] for n, _s, _e, _st in spans}
    assert names <= set(STAGES), names - set(STAGES)
    assert {
        "decode", "channel", "coalesce", "launch", "match_fetch",
        "dispatch_loop", "ack_write", "gc",
    } <= names, names
    # flat: on the loop's thread no two stage spans overlap
    for (n0, _s0, e0, _), (n1, s1, _e1, _) in zip(spans, spans[1:]):
        assert e0 <= s1, f"{n0} overlaps {n1}"
    # arguments ride the first segment of a stage (a segment that
    # resumes it after a nested stage carries none)
    def args(stage, key):
        return [st[key] for n, _s, _e, st in spans
                if n == SPAN_PREFIX + stage and key in st]

    assert 2 in args("gc", "generation")
    assert max(args("coalesce", "publishes")) >= 1
    assert max(args("channel", "packets")) >= 1


@pytest.fixture
def counted(monkeypatch):
    """TraceMe constructions and Python clock reads, counted."""
    seen = {"traceme": 0, "clock": 0}

    class CountingTraceMe(profiler.TraceMe):
        def __init__(self, *a, **kw):
            seen["traceme"] += 1
            super().__init__(*a, **kw)

    monkeypatch.setattr(profiler, "TraceMe", CountingTraceMe)
    for name in ("time", "time_ns", "monotonic", "monotonic_ns",
                 "perf_counter", "perf_counter_ns", "process_time"):
        real = getattr(time, name)

        def clock(real=real):
            seen["clock"] += 1
            return real()

        monkeypatch.setattr(time, name, clock)
    return seen


def _transitions():
    prev = STAGE_MARK.enter("decode")
    STAGE_MARK.enter("channel")
    inner = STAGE_MARK.enter("coalesce")
    STAGE_MARK.leave(inner)
    STAGE_MARK.leave(prev)


def test_no_trace_means_no_traceme_and_no_clock(counted):
    before = STAGE_MARK.stage
    assert STAGE_MARK.span is None
    _transitions()
    assert counted == {"traceme": 0, "clock": 0}
    assert STAGE_MARK.stage == before and STAGE_MARK.span is None


def test_a_running_trace_gets_one_span_per_segment(counted, tmp_path):
    prev_thread, prev_stage = STAGE_MARK.thread, STAGE_MARK.stage
    STAGE_MARK.thread, STAGE_MARK.stage = threading.get_ident(), ""
    jax.profiler.start_trace(str(tmp_path))
    try:
        _transitions()
    finally:
        jax.profiler.stop_trace()
        STAGE_MARK.thread, STAGE_MARK.stage = prev_thread, prev_stage
    # decode, channel, coalesce, channel again: the last leave closes
    assert counted["traceme"] == 4
    assert STAGE_MARK.span is None
    (spans,) = _host_spans(str(tmp_path)).values()
    assert [n for n, *_ in spans] == [
        SPAN_PREFIX + s for s in ("decode", "channel", "coalesce", "channel")
    ]


def test_a_collection_inside_a_transition_opens_no_second_span(
    monkeypatch, tmp_path
):
    """The `gc` hook can fire while a transition builds its span: the
    collection moves the stage and back, the spans stay flat."""
    collected = []

    class CollectingTraceMe(profiler.TraceMe):
        def __init__(self, *a, **kw):
            if not collected:
                collected.append(a[0])
                gc.collect()
            super().__init__(*a, **kw)

    monkeypatch.setattr(profiler, "TraceMe", CollectingTraceMe)
    prev_thread, prev_stage = STAGE_MARK.thread, STAGE_MARK.stage
    STAGE_MARK.stage = ""
    STAGE_MARK.attach()
    jax.profiler.start_trace(str(tmp_path))
    try:
        _transitions()
    finally:
        jax.profiler.stop_trace()
        STAGE_MARK.detach()
        STAGE_MARK.thread, STAGE_MARK.stage = prev_thread, prev_stage
    assert collected == [SPAN_PREFIX + "decode"]
    assert STAGE_MARK.span is None
    (spans,) = _host_spans(str(tmp_path)).values()
    assert [n for n, *_ in spans] == [
        SPAN_PREFIX + s for s in ("decode", "channel", "coalesce", "channel")
    ]
    for (n0, _s0, e0, _), (n1, s1, _e1, _) in zip(spans, spans[1:]):
        assert e0 <= s1, f"{n0} overlaps {n1}"
