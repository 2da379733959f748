"""Listener publishes through the dispatch engine, over real sockets.

A PUBLISH that arrives on a listener of a node whose dispatch engine is
running is matched by the device kernels (here XLA:CPU), acked only
after the engine resolves it, and refused with quota-exceeded when the
engine's admission control sheds it. Also: boot refuses a mesh that
does not fit, the compile-cache placement, and the chip smoke's phases
at a tiny size.
"""

import argparse
import asyncio
import json
import os
import sys

import pytest

from emqx_tpu.boot import Node
from emqx_tpu.broker import frame as F
from emqx_tpu.broker.packet import (
    MQTT_V4, MQTT_V5, RC, Connack, Connect, Puback, Publish, Suback,
    SubOpts, Subscribe, Type,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Client:
    def __init__(self, port, ver=MQTT_V5):
        self.port = port
        self.ver = ver
        self.parser = F.Parser(proto_ver=ver)
        self.inbox = asyncio.Queue()

    async def connect(self, cid):
        self.r, self.w = await asyncio.open_connection("127.0.0.1", self.port)
        self.task = asyncio.ensure_future(self._read())
        self.send(Connect(client_id=cid, proto_ver=self.ver))
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
        self.w.write(b"".join(F.serialize(p, self.ver) for p in pkts))

    async def expect(self, typ, timeout=10.0):
        pkt = await asyncio.wait_for(self.inbox.get(), timeout)
        assert isinstance(pkt, typ), pkt
        return pkt

    async def subscribe(self, flt):
        self.send(Subscribe(1, [(flt, SubOpts(qos=1))]))
        await self.expect(Suback)

    def close(self):
        self.task.cancel()
        self.w.close()


async def boot(tmp_path, **perf):
    node = Node(config_text=json.dumps({
        "node": {"data_dir": str(tmp_path / "d")},
        "listeners": {"tcp": {"default": {"bind": "127.0.0.1:0"}}},
        "api": {"enable": False},
        "broker": {"perf": perf},
    }))
    await node.start()
    return node, node.listeners.get("tcp", "default").listen_addr[1]


def batches(node):
    return node.broker.router.telemetry.counters.get("dispatch_batches_total", 0)


async def test_listener_publish_matches_on_the_device(tmp_path):
    node, port = await boot(tmp_path)
    try:
        assert node.broker.engine is not None
        sub = await Client(port).connect("sub")
        await sub.subscribe("a/+")
        await sub.subscribe("a/#")
        pub = await Client(port).connect("pub")
        b0 = batches(node)
        pub.send(Publish(topic="a/b", payload=b"q0", qos=0))
        pub.send(Publish(topic="a/c", payload=b"q1", qos=1, packet_id=7))
        ack = await pub.expect(Puback)
        assert (ack.type, ack.packet_id, ack.code) == (Type.PUBACK, 7, 0)
        pub.send(Publish(topic="a/d", payload=b"q2", qos=2, packet_id=8))
        rec = await pub.expect(Puback)
        assert (rec.type, rec.packet_id, rec.code) == (Type.PUBREC, 8, 0)
        assert batches(node) > b0
        got = [await sub.expect(Publish) for _ in range(3)]
        # the host-trie oracle: both filters match, and the fanout plan
        # delivers once per subscribing client
        assert sorted(node.broker.router.match_filters("a/b")) == ["a/#", "a/+"]
        # ... in publish order
        assert [(p.topic, p.payload) for p in got] == [
            ("a/b", b"q0"), ("a/c", b"q1"), ("a/d", b"q2"),
        ]
        pub.close()
        sub.close()
    finally:
        await node.stop()


async def test_engine_puback_reason_without_subscribers(tmp_path):
    node, port = await boot(tmp_path)
    try:
        pub = await Client(port).connect("pub")
        b0 = batches(node)
        pub.send(Publish(topic="nobody/here", payload=b"x", qos=1, packet_id=3))
        ack = await pub.expect(Puback)
        assert ack.code == RC.NO_MATCHING_SUBSCRIBERS
        v3 = await Client(port, MQTT_V4).connect("pub3")
        v3.send(Publish(topic="nobody/here", payload=b"x", qos=1, packet_id=4))
        assert (await v3.expect(Puback)).code == 0
        assert batches(node) > b0
        pub.close()
        v3.close()
    finally:
        await node.stop()


@pytest.mark.parametrize("ver", [MQTT_V5, MQTT_V4])
async def test_engine_refusal_answers_quota_exceeded(tmp_path, ver):
    # two outstanding publishes fill the queue; the packets arrive in
    # one segment, so no flush runs before the QoS1/QoS2 are admitted
    node, port = await boot(tmp_path, tpu_queue_max_depth=2)
    try:
        pub = await Client(port, ver).connect("pub")
        pub.send(
            Publish(topic="t/1", qos=0),
            Publish(topic="t/2", qos=0),
            Publish(topic="t/3", qos=1, packet_id=5),
            Publish(topic="t/4", qos=2, packet_id=6),
        )
        if ver == MQTT_V5:
            a1 = await pub.expect(Puback)
            a2 = await pub.expect(Puback)
            assert (a1.type, a1.code) == (Type.PUBACK, RC.QUOTA_EXCEEDED)
            assert (a2.type, a2.code) == (Type.PUBREC, RC.QUOTA_EXCEEDED)
        else:
            with pytest.raises(asyncio.TimeoutError):
                await pub.expect(Puback, timeout=0.5)
        counters = node.broker.router.telemetry.counters
        assert counters.get("queue_shed_total", 0) >= 2
        # a refused QoS2 is not awaiting PUBREL: the resend publishes
        await asyncio.sleep(0.05)
        pub.send(Publish(topic="t/4", qos=2, packet_id=6))
        rec = await pub.expect(Puback)
        assert rec.type == Type.PUBREC and rec.code in (0, RC.NO_MATCHING_SUBSCRIBERS)
        pub.close()
    finally:
        await node.stop()


async def test_no_engine_keeps_host_publish(tmp_path):
    node, port = await boot(tmp_path, tpu_match_enable=False)
    try:
        assert node.broker.engine is None
        sub = await Client(port).connect("sub")
        await sub.subscribe("h/#")
        pub = await Client(port).connect("pub")
        pub.send(Publish(topic="h/1", payload=b"x", qos=1, packet_id=1))
        assert (await pub.expect(Puback)).code == 0
        assert (await sub.expect(Publish)).payload == b"x"
        assert batches(node) == 0
        pub.close()
        sub.close()
    finally:
        await node.stop()


async def test_parallel_mesh_too_wide_fails_boot(tmp_path):
    import jax

    node = Node(config_text=json.dumps({
        "node": {"data_dir": str(tmp_path / "d")},
        "listeners": {"tcp": {"default": {"bind": "127.0.0.1:0"}}},
        "api": {"enable": False},
        "parallel": {"enable": True, "dp": 1, "sub": 2 * len(jax.devices())},
    }))
    with pytest.raises(RuntimeError, match="parallel.enable needs a mesh"):
        await node.start()


def test_compile_cache_placement(monkeypatch):
    import jax

    from emqx_tpu import compile_cache

    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == os.path.join(ROOT, ".jax_cache")
    monkeypatch.setenv(compile_cache.ENV, "/somewhere/cache")
    assert compile_cache.cache_dir() == "/somewhere/cache"
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.enable() == "/somewhere/cache"
        assert jax.config.jax_compilation_cache_dir == "/somewhere/cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
    # the tests themselves never turn the cache on
    assert jax.config.jax_enable_compilation_cache is False


def _smoke():
    sys.path.insert(0, ROOT)
    import chip_smoke

    return chip_smoke


async def test_chip_smoke_phases_tiny_on_cpu(tmp_path, capsys):
    cs = _smoke()
    args = argparse.Namespace(
        routes=2048, sessions=32, publishes=256, seed=3,
        data_dir=str(tmp_path / "smoke"),
    )
    await cs.serve(args, "cpu", 1)
    out = capsys.readouterr().out
    assert "equal to the host oracle" in out
    assert '"recompiles_at_serve_total": 0' in out


def test_chip_smoke_main_refuses_cpu(monkeypatch, capsys):
    cs = _smoke()
    monkeypatch.setattr(cs, "build_native", lambda: 0.0)
    assert cs.main([]) == 1
    out = capsys.readouterr().out
    assert "no TPU" in out and '"ok"' not in out


async def test_one_connection_keeps_many_qos1_in_flight(tmp_path):
    # a publisher that writes 32 QoS1 publishes without waiting gets
    # them coalesced into device batches, and its PUBACKs in order
    node, port = await boot(tmp_path)
    try:
        sub = await Client(port).connect("sub")
        await sub.subscribe("w/#")
        pub = await Client(port).connect("pub")
        b0 = batches(node)
        pub.send(*(
            Publish(topic=f"w/{i}", payload=b"%d" % i, qos=1, packet_id=i + 1)
            for i in range(32)
        ))
        acks = [await pub.expect(Puback) for _ in range(32)]
        assert [a.packet_id for a in acks] == list(range(1, 33))
        assert all(a.code == 0 for a in acks)
        assert 0 < batches(node) - b0 < 32
        got = [await sub.expect(Publish) for _ in range(32)]
        assert [p.payload for p in got] == [b"%d" % i for i in range(32)]
        pub.close()
        sub.close()
    finally:
        await node.stop()


async def test_traced_publish_takes_host_path_and_is_counted(tmp_path):
    """With an external tracer set, a listener publish is served by a
    device batch, the traced-host-path counter stays 0, and the engine
    leaves the host publish's mqtt.publish / broker.route /
    broker.dispatch spans, under one root per publish."""
    node, port = await boot(tmp_path)
    try:
        from emqx_tpu.obs.otel import MemoryTracer

        node.broker.tracer = tracer = MemoryTracer()
        sub = await Client(port).connect("sub")
        await sub.subscribe("tr/+")
        pub = await Client(port).connect("pub")
        b0 = batches(node)
        pub.send(Publish(topic="tr/1", payload=b"x", qos=1, packet_id=1))
        assert (await pub.expect(Puback)).code == 0
        assert (await sub.expect(Publish)).payload == b"x"
        counters = node.broker.router.telemetry.counters
        assert counters.get("traced_host_publish_total", 0) == 0
        assert batches(node) == b0 + 1
        by_name = {}
        for sp in tracer.spans:
            by_name.setdefault(sp.name, []).append(sp)
        (root,) = [
            r for r in by_name["mqtt.publish"]
            if r.attrs.get("mqtt.topic") == "tr/1"
        ]
        assert root.attrs["mqtt.qos"] == 1
        assert root.attrs["mqtt.clientid"] == "pub"
        assert root.attrs["mqtt.deliveries"] == 1
        (route,) = [s for s in by_name["broker.route"] if s.parent_id == root.span_id]
        (disp,) = [s for s in by_name["broker.dispatch"] if s.parent_id == root.span_id]
        assert route.attrs["broker.matched_filters"] == 1
        assert disp.attrs["broker.deliveries"] == 1
        assert route.trace_id == disp.trace_id == root.trace_id
        assert root.start_ns <= route.start_ns <= route.end_ns
        assert route.end_ns <= disp.start_ns <= disp.end_ns <= root.end_ns
        pub.close()
        sub.close()
    finally:
        await node.stop()


async def test_engine_rewarms_a_grown_table_off_the_loop():
    # boot warms an empty table; a table that outgrew those shapes is
    # re-warmed on a worker thread at its first batch, the loop keeps
    # running meanwhile, and no publish compiles at serve time
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.pubsub import Broker
    from emqx_tpu.broker.session import SessionConfig

    b = Broker()
    eng = b.enable_dispatch_engine(queue_depth=8, deadline_ms=0.5)
    eng.warmup()
    key0 = b.router.shape_key()
    sess, _ = b.open_session("c", clean_start=True, cfg=SessionConfig())
    got = []
    sess.outgoing_sink = got.extend
    for i in range(1500):
        b.subscribe(sess, f"g{i % 5}/d{i}/+/#", SubOpts(qos=0))
    assert b.router.shape_key() != key0
    ticks = 0

    async def tick():
        nonlocal ticks
        while True:
            ticks += 1
            await asyncio.sleep(0.001)

    ticker = asyncio.ensure_future(tick())
    futs = [
        eng.submit(Message(topic=f"g{i % 5}/d{i}/x/y", payload=b"p"))
        for i in range(20)
    ]
    assert eng._rewarm is not None
    ticks0 = ticks
    counts = await asyncio.gather(*futs)
    ticker.cancel()
    assert counts == [1] * 20 and len(got) == 20
    assert ticks > ticks0  # the loop ran while the shapes compiled
    c = b.router.telemetry.counters
    assert c["rewarms_total"] == 1
    assert c["recompiles_warmup_total"] > 0
    assert c.get("recompiles_at_serve_total", 0) == 0
    assert eng.warmup_info["rewarms"] == 1
    # same shapes again: no second pass
    await eng.publish(Message(topic="g1/d1/x/y", payload=b"p"))
    assert c["rewarms_total"] == 1
    await eng.stop()
    import gc as _gc

    _gc.unfreeze()


async def test_served_hash_batch_moves_one_buffer_each_way():
    # one engine batch on the hash leg crosses the host-device link
    # twice: the packed topics in, the packed result out (the
    # three-field layout moved 3 + 4)
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.pubsub import Broker
    from emqx_tpu.broker.session import SessionConfig

    b = Broker()
    eng = b.enable_dispatch_engine(queue_depth=8, deadline_ms=0.5)
    sess, _ = b.open_session("c", clean_start=True, cfg=SessionConfig())
    got = []
    sess.outgoing_sink = got.extend
    for i in range(16):
        b.subscribe(sess, f"k{i}/+/v/#", SubOpts(qos=0))
    eng.warmup()
    c = b.router.telemetry.counters
    batches0 = c.get("dispatch_batches_total", 0)
    moved0 = c.get("transfer_buffers_total", 0)
    assert await eng.publish(Message(topic="k3/a/v/w", payload=b"p")) == 1
    assert c["dispatch_batches_total"] - batches0 == 1
    assert c["transfer_buffers_total"] - moved0 == 2
    assert len(got) == 1
    await eng.stop()
    import gc as _gc

    _gc.unfreeze()


def test_host_trie_replays_in_steps_and_in_order():
    from emqx_tpu.models.router import Router

    r = Router(max_levels=8)
    r.add_routes([(f"s/{i}/+", f"d{i}") for i in range(300)])
    r.delete_route("s/7/+", "d7")
    r.add_route("s/7/+", "e7")
    r.delete_route("s/9/+", "d9")
    backlog = r.trie_backlog()
    assert backlog > 100
    left = r.drain_trie_step(100)
    assert left == backlog - 100
    while left:
        left = r.drain_trie_step(100)
    assert r.trie_backlog() == 0
    assert r.match_filters("s/7/x") == ["s/7/+"]
    assert r.match_filters("s/9/x") == []
    assert r.match_filters("s/299/x") == ["s/299/+"]
    assert r.filter_dests("s/7/+") == {"e7": 1}


def test_mesh_below_floor_is_not_counted_degraded():
    import jax

    from emqx_tpu.models.router import Router
    from emqx_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(n_dp=1, n_sub=2, devices=jax.devices()[:2])
    r = Router(max_levels=8, mesh=mesh, mesh_min_rows_per_shard=1 << 20)
    r.add_routes([(f"m/{i}/#", f"d{i}") for i in range(16)])
    assert r.match_filters_batch(["m/3/x"]) == [["m/3/#"]]
    tel = r.telemetry
    assert r.device_table.degraded
    assert tel.gauges["mesh_degraded_single_device"] == 1
    assert tel.counters.get("mesh_degraded_single_device_total", 0) == 0


async def test_subscribe_storm_replays_the_host_trie_on_the_loop():
    # the broker replays a storm's deferred trie inserts a step per
    # loop turn, so no later host read pays them all at once
    from emqx_tpu.broker.pubsub import Broker
    from emqx_tpu.models.router import TRIE_REPLAY_STEP

    b = Broker()
    sess, _ = b.open_session("c", clean_start=True)
    n = 2 * TRIE_REPLAY_STEP + 10
    for i in range(n):
        b.subscribe(sess, f"st/{i}/+", SubOpts(qos=0))
    assert b._trie_drain_scheduled and b.router.trie_backlog() > 0
    for _ in range(8):
        await asyncio.sleep(0)
    assert b.router.trie_backlog() == 0 and not b._trie_drain_scheduled
    assert b.router.match_filters(f"st/{n - 1}/x") == [f"st/{n - 1}/+"]
