#!/usr/bin/env python3
"""Chip smoke: serve MQTT publishes on the TPU through the normal broker.

One process does everything, because a chip belongs to one process:
the node, its listeners and the MQTT socket clients. `make` is the only
subprocess. Phases:

  1. `make -B -C native` builds every extension from the committed sources.
  2. JAX must find a TPU (else exit 1, no result line).
  3. `Node` boots from etc/emqx.conf with ephemeral ports.
  4. BASELINE config #2's table (2^20 wildcard filters of the
     `t{i%997}/r{i%13}/d{i}/+/m/#` shape) loads through the broker's
     subscribe path, over in-process sessions, plus socket subscribers
     whose filters overlap the stream; the route arrays must be on the TPU.
  5. Socket publishers send Zipf-skewed QoS 0/1 publishes; every delivery,
     socket and in-process, must equal the host-trie oracle's, and every
     QoS 1 publish must be PUBACKed with success. The engine re-warms the
     grown table's kernel shapes by itself, off the event loop.
  6. The collector must show device dispatches and no host fallback,
     breaker trip, serve-time recompile or audit divergence.

`--chips 4` boots the sub-sharded mesh (parallel.enable, dp=1, sub=4)
instead and checks that the table is split over 4 TPU devices. The last
stdout line is the JSON result; everything else goes on earlier lines.
"""

from __future__ import annotations

import argparse
import asyncio
import collections
import gc
import json
import os
import shutil
import struct
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
DATA_DIR = os.path.join(ROOT, ".smoke_data")

ROUTES = 1 << 20  # BASELINE config #2: 1M wildcard subscriptions
SESSIONS = 4096  # in-process sessions holding the table
SOCKET_SUBS = 8
PUBLISHERS = 4
PUBLISHES = 4096
QOS1_SHARE = 0.5
ZIPF_S = 1.1
WINDOW = 64  # publishes a socket publisher writes before awaiting PUBACKs
KEEPALIVE_S = 60  # the clients' MQTT keepalive; they PINGREQ at half of it
# a publish may wait for the engine's off-loop re-warm of the grown
# table's shapes, which compiles cold on a fresh machine
TIMEOUT_S = 600.0

# counters that must stay 0: any of them means a publish was answered
# by the host instead of the device, or the device path degraded
MUST_BE_ZERO = (
    "host_fallback_total",
    "breaker_fallback_total",
    "breaker_degraded_batches_total",
    "fanout_host_fallback_total",
    "traced_host_publish_total",
    "recompiles_at_serve_total",
    "audit_divergence_total",
)


class SmokeError(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeError(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def filter_of(i: int) -> str:
    return f"t{i % 997}/r{i % 13}/d{i}/+/m/#"


# --- phase 1 -------------------------------------------------------------


def build_native() -> float:
    t0 = time.monotonic()
    r = subprocess.run(
        ["make", "-B", "-C", os.path.join(ROOT, "native")],
        capture_output=True, text=True, timeout=600,
    )
    if r.returncode:
        raise SmokeError(f"make -B -C native failed:\n{r.stderr[-4000:]}")
    from emqx_tpu import framec, jsonc
    from emqx_tpu.ds import kvstore
    from emqx_tpu.ops import speedups

    for name, mod in (
        ("speedups", speedups.load()),
        ("frame", framec.load()),
        ("json", jsonc.load()),
        ("kvstore", kvstore._LIB),
    ):
        check(mod is not None, f"native {name} extension did not load")
    return time.monotonic() - t0


# --- phase 2 -------------------------------------------------------------


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "jax": jax.__version__,
    }


# --- phase 3 -------------------------------------------------------------


def node_overrides(data_dir: str, sub: int = 0) -> str:
    conf = {
        "node": {"data_dir": data_dir},
        "listeners": {
            "tcp": {"default": {"bind": "127.0.0.1:0"}},
            "ws": {"default": {"bind": "127.0.0.1:0", "path": "/mqtt"}},
        },
        "api": {"bind": "127.0.0.1:0"},
        # audit every publish against the host oracle (obs/sentinel.py)
        "broker": {"perf": {"tpu_audit_sample_n": 1}},
    }
    if sub:
        conf["parallel"] = {"enable": True, "dp": 1, "sub": sub}
    return json.dumps(conf)


async def boot(data_dir: str, sub: int = 0):
    from emqx_tpu.boot import Node

    shutil.rmtree(data_dir, ignore_errors=True)
    node = Node(
        config_files=[os.path.join(ROOT, "etc", "emqx.conf")],
        config_text=node_overrides(data_dir, sub),
    )
    t0 = time.monotonic()
    await node.start()
    eng = node.broker.engine
    check(eng is not None and eng.warmed, "dispatch engine not running")
    info = eng.warmup_info
    log(
        f"boot: {time.monotonic() - t0:.3f}s, engine warmup "
        f"{info['seconds']:.3f}s, {info.get('aot_shapes', 0)} shapes compiled"
    )
    return node


# --- socket clients --------------------------------------------------------


class Client:
    """Minimal MQTT v5 client over asyncio streams (emqx_tpu.broker.frame)."""

    def __init__(self, cid: str, on_publish=None):
        self.cid = cid
        self.on_publish = on_publish
        self.acks: dict = {}
        self._next_id = 0
        self.reader = self.pinger = None

    async def connect(self, port: int) -> None:
        from emqx_tpu.broker import frame
        from emqx_tpu.broker.packet import MQTT_V5, Connect

        self.r, self.w = await asyncio.open_connection("127.0.0.1", port)
        self.parser = frame.Parser(proto_ver=MQTT_V5)
        self.connack = asyncio.get_running_loop().create_future()
        self.reader = asyncio.ensure_future(self._read())
        self.send(Connect(
            client_id=self.cid, proto_ver=MQTT_V5, keepalive=KEEPALIVE_S,
        ))
        code = await asyncio.wait_for(self.connack, TIMEOUT_S)
        check(code == 0, f"{self.cid}: CONNACK {code}")
        self.pinger = asyncio.ensure_future(self._ping())

    async def _ping(self) -> None:
        from emqx_tpu.broker.packet import Pingreq

        while True:
            await asyncio.sleep(KEEPALIVE_S / 2)
            self.send(Pingreq())

    def send(self, pkt) -> None:
        from emqx_tpu.broker import frame
        from emqx_tpu.broker.packet import MQTT_V5

        self.w.write(frame.serialize(pkt, MQTT_V5))

    def packet_id(self) -> int:
        self._next_id = self._next_id % 65535 + 1
        return self._next_id

    def expect_ack(self, packet_id: int) -> "asyncio.Future":
        fut = asyncio.get_running_loop().create_future()
        self.acks[packet_id] = fut
        return fut

    async def subscribe(self, flt: str) -> None:
        from emqx_tpu.broker.packet import Subscribe, SubOpts

        pid = self.packet_id()
        fut = self.expect_ack(pid)
        self.send(Subscribe(packet_id=pid, filters=[(flt, SubOpts(qos=0))]))
        codes = await asyncio.wait_for(fut, TIMEOUT_S)
        check(codes == [0], f"{self.cid}: SUBACK {codes} for {flt}")

    async def _read(self) -> None:
        from emqx_tpu.broker.packet import Connack, Puback, Publish, Suback

        while True:
            data = await self.r.read(1 << 16)
            if not data:
                return
            for pkt in self.parser.feed(data):
                if isinstance(pkt, Publish):
                    self.on_publish(self.cid, pkt.payload)
                elif isinstance(pkt, Connack):
                    self.connack.set_result(pkt.code)
                elif isinstance(pkt, (Puback, Suback)):
                    fut = self.acks.pop(pkt.packet_id, None)
                    if fut is not None:
                        fut.set_result(
                            pkt.code if isinstance(pkt, Puback) else pkt.codes
                        )

    async def close(self) -> None:
        for task in (self.pinger, self.reader):
            if task is not None:
                task.cancel()
        self.w.close()


# --- phase 4 -------------------------------------------------------------


class Deliveries:
    """Every delivery, keyed by the message id in the payload's first
    8 bytes; the next 8 carry the publish's perf_counter_ns stamp."""

    def __init__(self):
        self.by_msg = collections.defaultdict(list)
        self.latency_ns: list = []
        self.total = 0

    def record(self, cid: str, payload: bytes) -> None:
        mid, sent = struct.unpack_from("<QQ", payload)
        self.by_msg[mid].append(cid)
        self.latency_ns.append(time.perf_counter_ns() - sent)
        self.total += 1


def hot_filters(n_routes: int, seed: int) -> np.ndarray:
    """Filter ids in Zipf rank order (rank 0 is the hottest)."""
    return np.random.default_rng(seed).permutation(n_routes)


def socket_filters(hot: np.ndarray) -> list:
    """Filters of the socket subscribers: each overlaps the stream,
    from everything (`#`) to one hot filter subscribed twice."""
    h = [int(x) for x in hot[:SOCKET_SUBS]]
    return [
        "#",
        f"t{h[0] % 997}/#",
        f"+/r{h[1] % 13}/#",
        filter_of(h[2]),
        f"t{h[3] % 997}/r{h[3] % 13}/d{h[3]}/+/m/+",
        f"+/+/d{h[4]}/#",
        f"t{h[5] % 997}/+/+/+/m/#",
        f"+/r{h[6] % 13}/+/+/m/+",
    ]


async def load_table(node, n_routes: int, n_sessions: int, dlv: Deliveries):
    """Subscribe the config #2 filters through Broker.subscribe, spread
    over in-process sessions as the chaos fleet builds them."""
    from emqx_tpu.broker.packet import SubOpts
    from emqx_tpu.broker.session import SessionConfig

    b = node.broker
    cfg = SessionConfig(
        session_expiry_interval=3600.0, max_mqueue_len=16,
        mqueue_store_qos0=False, durable=False,
    )
    opts = SubOpts(qos=0)
    t0 = time.monotonic()
    for s_idx in range(n_sessions):
        cid = f"fleet{s_idx}"
        sess, _ = b.open_session(cid, clean_start=True, cfg=cfg)

        def sink(pkts, cid=cid):
            for p in pkts:
                dlv.record(cid, p.payload)

        sess.outgoing_sink = sink
        for i in range(s_idx, n_routes, n_sessions):
            b.subscribe(sess, filter_of(i), opts)
        if s_idx % 64 == 63:
            await asyncio.sleep(0)
    log(
        f"table: {n_routes} filters over {n_sessions} sessions subscribed "
        f"in {time.monotonic() - t0:.3f}s"
    )


def route_arrays(dt) -> list:
    import jax

    leaves = []
    for name in ("_dev", "_dev_meta", "_dev_slots", "_dev_residual"):
        leaves += [
            x for x in jax.tree_util.tree_leaves(getattr(dt, name, None))
            if isinstance(x, jax.Array)
        ]
    return leaves


def check_resident(node, platform: str, n_chips: int) -> None:
    """The route arrays live on the device(s), split over the mesh."""
    dt = node.broker.router.device_table
    check(not getattr(dt, "degraded", False), "mesh table degraded to one chip")
    arrays = route_arrays(dt)
    check(bool(arrays) and dt._dev is not None, "no route arrays on the device")
    devs = set()
    for a in arrays:
        devs |= a.devices()
    check(
        all(d.platform == platform for d in devs),
        f"route arrays on {sorted(str(d) for d in devs)}",
    )
    if n_chips > 1:
        for a in (dt._dev.words, dt._dev_slots.fp):
            check(
                len(a.devices()) == n_chips and not a.sharding.is_fully_replicated,
                f"route array {a.shape} not sharded over {n_chips} devices",
            )
    log(
        f"resident: {dt._dev.words.shape[0]} filter rows, "
        f"{sum(a.nbytes for a in arrays)} bytes of route arrays on "
        f"{len(devs)} {platform} device(s)"
    )


# --- phase 5 -------------------------------------------------------------


def draw_topics(hot: np.ndarray, n: int, seed: int) -> list:
    """Zipf-skewed topics over the table: filter rank k drawn with weight
    1/k^s; the `+` level and the level under `#` are fresh words, so every
    topic is new to the match cache."""
    rng = np.random.default_rng(seed + 1)
    w = 1.0 / np.arange(1, len(hot) + 1, dtype=np.float64) ** ZIPF_S
    cdf = np.cumsum(w)
    ranks = np.searchsorted(cdf, rng.random(n) * cdf[-1])
    words = rng.integers(0, 1 << 30, size=(n, 2))
    out = []
    for k, (u, v) in zip(ranks, words):
        i = int(hot[k])
        out.append(f"t{i % 997}/r{i % 13}/d{i}/w{u}/m/z{v}")
    return out


async def publish_all(pubs: list, topics: list, qos: np.ndarray) -> int:
    """Each socket publisher sends its share in windows, awaiting the
    window's PUBACKs before the next; returns QoS 1 publishes acked."""
    from emqx_tpu.broker.packet import Publish

    async def run(k: int, client: Client) -> int:
        acked = 0
        mine = list(range(k, len(topics), len(pubs)))
        for w0 in range(0, len(mine), WINDOW):
            futs = []
            for mid in mine[w0:w0 + WINDOW]:
                payload = struct.pack("<QQ", mid, time.perf_counter_ns())
                q = int(qos[mid])
                pid = None
                if q:
                    pid = client.packet_id()
                    futs.append((mid, client.expect_ack(pid)))
                client.send(Publish(
                    topic=topics[mid], payload=payload, qos=q, packet_id=pid,
                ))
            await client.w.drain()
            for mid, fut in futs:
                code = await asyncio.wait_for(fut, TIMEOUT_S)
                check(code == 0, f"PUBACK {code:#x} for {topics[mid]}")
                acked += 1
        return acked

    return sum(await asyncio.gather(*(run(k, c) for k, c in enumerate(pubs))))


def expected_receivers(router, topic: str) -> collections.Counter:
    """Host-trie oracle: one delivery to each subscriber of any filter
    that matches the topic (the broker's fanout plan dedups clients)."""
    return collections.Counter({
        dest
        for flt in router.match_filters(topic)
        for dest in router.filter_dests(flt)
    })


async def check_deliveries(router, topics: list, dlv: Deliveries) -> int:
    want = [expected_receivers(router, t) for t in topics]
    total = sum(sum(c.values()) for c in want)
    deadline = time.monotonic() + TIMEOUT_S
    while dlv.total < total and time.monotonic() < deadline:
        await asyncio.sleep(0.05)
    await asyncio.sleep(0.2)  # let any surplus delivery land too
    bad = [
        (topics[m], dict(want[m]), dict(collections.Counter(dlv.by_msg.get(m, []))))
        for m in range(len(topics))
        if collections.Counter(dlv.by_msg.get(m, [])) != want[m]
    ]
    check(
        not bad and dlv.total == total,
        f"{len(bad)} of {len(topics)} publishes delivered unlike the oracle "
        f"({dlv.total} deliveries, oracle {total}); first: {bad[:3]}",
    )
    return total


# --- phase 6 -------------------------------------------------------------


def check_collector(node) -> None:
    tel = node.broker.router.telemetry
    c = tel.counters
    eng = node.broker.engine
    out = {k: int(c.get(k, 0)) for k in ("dispatch_batches_total",) + MUST_BE_ZERO}
    for k in ("audit_total", "audit_dropped_total", "audit_skipped_stale_total"):
        out[k] = int(c.get(k, 0))
    out["breaker_state"] = eng.breaker_state
    log("collector: " + json.dumps(out, sort_keys=True))
    check(out["dispatch_batches_total"] > 0, "no device dispatch")
    check(out["audit_total"] > 0, "the sentinel audited nothing")
    bad = {k: out[k] for k in MUST_BE_ZERO if out[k]}
    check(not bad, f"nonzero fallback/recompile/divergence counters: {bad}")
    check(eng.breaker_state == "closed", f"breaker {eng.breaker_state}")


def percentile_ms(ns: list, q: float) -> float:
    return float(np.percentile(np.asarray(ns, dtype=np.float64), q)) / 1e6


class LoopStalls:
    """The event loop's longest stall while it runs (a task that asks to
    wake every 10 ms keeps the worst lateness), and the full garbage
    collections that ran meanwhile, a known cause of such stalls."""

    TICK_S = 0.01

    def __init__(self):
        self.worst_s = 0.0
        self.gc_full: list = []
        self._gc_t0 = 0.0
        gc.callbacks.append(self._on_gc)
        self.task = asyncio.ensure_future(self._run())

    async def _run(self) -> None:
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(self.TICK_S)
            self.worst_s = max(self.worst_s, time.monotonic() - t0 - self.TICK_S)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._gc_t0 = time.monotonic()
        else:
            self.gc_full.append(time.monotonic() - self._gc_t0)

    def stop(self) -> str:
        self.task.cancel()
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)
        return (
            f"longest event-loop stall while serving {self.worst_s * 1e3:.3f} ms; "
            f"{len(self.gc_full)} full gc pass(es), longest "
            f"{max(self.gc_full, default=0.0) * 1e3:.3f} ms"
        )


async def serve(args, platform: str, n_chips: int) -> None:
    """Phases 3-6 on one node; raises SmokeError on any failed check."""
    dlv = Deliveries()
    node = await boot(args.data_dir, sub=n_chips if n_chips > 1 else 0)
    clients: list = []
    stalls = None
    try:
        router = node.broker.router
        tel = router.telemetry
        eng = node.broker.engine
        await load_table(node, args.routes, args.sessions, dlv)
        port = node.listeners.get("tcp", "default").listen_addr[1]
        hot = hot_filters(args.routes, args.seed)
        stalls = LoopStalls()
        for j, flt in enumerate(socket_filters(hot)):
            c = Client(f"sub{j}", on_publish=dlv.record)
            clients.append(c)
            await c.connect(port)
            await c.subscribe(flt)
        pubs = []
        for k in range(PUBLISHERS):
            c = Client(f"pub{k}")
            clients.append(c)
            pubs.append(c)
            await c.connect(port)
        topics = draw_topics(hot, args.publishes, args.seed)
        qos = (
            np.random.default_rng(args.seed + 2).random(len(topics)) < QOS1_SHARE
        ).astype(np.int8)
        batches0 = tel.counters.get("dispatch_batches_total", 0)
        t0 = time.monotonic()
        acked = await publish_all(pubs, topics, qos)
        pub_s = time.monotonic() - t0
        loop_report = stalls.stop()
        check(acked == int(qos.sum()), "a QoS 1 publish was not PUBACKed")
        # the table grew from empty after boot: the engine must have
        # re-warmed its shapes by itself before serving them
        info = eng.warmup_info
        log(
            f"re-warm (by the engine): {info.get('rewarms', 0)} pass(es), "
            f"{info.get('rewarm_shapes', 0)} shapes in "
            f"{info.get('rewarm_seconds', 0.0):.3f}s, of which "
            f"{info.get('rewarm_sync_seconds', 0.0):.3f}s uploading on the loop"
        )
        log(f"loop: {loop_report}")
        check(info.get("rewarms", 0) > 0, "the engine did not re-warm the grown table")
        check_resident(node, platform, n_chips)
        # a subscriber dropped mid-run would shrink the oracle with it
        for c, flt in zip(clients, socket_filters(hot)):
            check(
                c.cid in router.filter_dests(flt),
                f"socket subscriber {c.cid} lost its route {flt}",
            )
        n_dlv = await check_deliveries(router, topics, dlv)
        log(
            f"publish: {len(topics)} publishes ({acked} QoS1 PUBACKed) from "
            f"{len(pubs)} socket publishers in {pub_s:.3f}s, "
            f"{tel.counters.get('dispatch_batches_total', 0) - batches0} "
            f"device batches; {n_dlv} deliveries equal to the host oracle"
        )
        node.broker.sentinel.run_audits()
        check_collector(node)
        if n_chips > 1:
            degraded = tel.counters.get("mesh_degraded_single_device_total", 0)
            log(f"mesh: mesh_degraded_single_device_total {degraded}")
            check(degraded == 0, "the mesh degraded to one chip")
        ix = router.index
        log(
            "info (no claim): residual dense-leg rows "
            f"{len(ix.residual_rows) if ix is not None else 0}; publish->deliver "
            f"p50 {percentile_ms(dlv.latency_ns, 50):.3f} ms, "
            f"p99 {percentile_ms(dlv.latency_ns, 99):.3f} ms"
        )
    finally:
        if stalls is not None:
            stalls.stop()
        for c in clients:
            await c.close()
        await node.stop()
        if node.broker.engine is not None:
            await node.broker.engine.stop(drain=False)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--routes", type=int, default=ROUTES)
    ap.add_argument("--sessions", type=int, default=SESSIONS)
    ap.add_argument("--publishes", type=int, default=PUBLISHES)
    ap.add_argument("--data-dir", default=DATA_DIR)
    args = ap.parse_args(argv)
    try:
        log(f"native: rebuilt in {build_native():.3f}s")
        dev = device_info()
        log(f"device: {json.dumps(dev)}")
        check(dev["platform"] == "tpu", f"no TPU: JAX found {dev['platform']}")
        check(
            dev["count"] >= args.chips,
            f"--chips {args.chips} needs {args.chips} TPUs, found {dev['count']}",
        )
        from emqx_tpu import compile_cache

        log(f"compile cache: {compile_cache.enable()}")
        asyncio.run(serve(args, "tpu", args.chips))
    except Exception as e:
        log(f"FAILED: {type(e).__name__}: {e}")
        return 1
    print(json.dumps({
        "ok": True,
        "device": {k: dev[k] for k in ("platform", "kind", "count")},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
