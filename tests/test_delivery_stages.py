"""ISSUE 17 — the delivery-path microscope.

Four surfaces under test:

  * queue-stage sub-decomposition: the sentinel's opaque
    `queue`+`deliver` wall decomposes into six first-class sub-stages
    (submit_wait, coalesce, plan_resolve, dispatch_loop,
    session_write, ack_sweep) that SUM back to the wall within the
    10% tolerance — under a live storm, on single-device AND sharded
    brokers;
  * delivery-identity: the timed plan walk
    (`_deliver_plan_timed`) must produce byte-identical sink output
    to the untimed hot loop it mirrors — the instrumentation can
    never change what subscribers receive;
  * the ring slot timeline: per-slot launch->land spans;
  * the sampling profiler + loop-lag ticker: probe-free stack
    attribution with bounded tables, collapsed-stack output, bounded
    auto-arm; and the lag ticker that keeps co-tenant scheduling
    delay out of `queue`;
  * cross-node trace propagation: a forwarded publish yields
    REMOTE-side sub-stage samples stamped with the ORIGINATING span's
    trace id (the Dapper contract over the broker RPC plane).
"""

import asyncio
import threading
import time

from emqx_tpu.broker.message import Message
from emqx_tpu.broker.packet import SubOpts
from emqx_tpu.broker.pubsub import Broker
from emqx_tpu.obs.profiler import (
    DELIVERY_STAGES,
    STAGE_MARK,
    LoopLagMonitor,
    SamplingProfiler,
)
from emqx_tpu.obs.sentinel import DECOMP_TOLERANCE, PublishSentinel


def _mk_subs(broker, topic_filter, n_qos0=4, n_qos1=4, prefix="c"):
    sinks = []
    for i in range(n_qos0 + n_qos1):
        s, _ = broker.open_session(f"{prefix}{i}", clean_start=True)
        collected = []
        s.outgoing_sink = collected.append
        sinks.append(collected)
        qos = 0 if i < n_qos0 else 1
        broker.subscribe(s, topic_filter, SubOpts(qos=qos))
    return sinks


async def _storm(eng, topics, waves=5):
    for w in range(waves):
        await asyncio.gather(
            *[
                eng.publish(Message(topic=t, payload=b"w%d" % w))
                for t in topics
            ]
        )
        await asyncio.sleep(0)


def _assert_decomposition(sentinel):
    # every declared sub-stage recorded at least once
    assert sorted(sentinel.delivery_hist) == sorted(DELIVERY_STAGES)
    # aggregate closure: the sub-stage seconds sum to within the
    # tolerance of the queue+deliver wall they decompose
    sub_sum = sum(h.sum for h in sentinel.delivery_hist.values())
    wall = (
        sentinel.stage_hist["queue"].sum
        + sentinel.stage_hist["deliver"].sum
    )
    assert wall > 0
    assert abs(sub_sum - wall) <= DECOMP_TOLERANCE * wall, (
        f"sub-stage sum {sub_sum:.6f}s vs wall {wall:.6f}s"
    )
    # the per-span self-check agrees
    snap = sentinel.decomposition_snapshot()
    assert snap["in_band"] >= 1
    assert snap["in_band_ratio"] >= 0.7
    # fan sizes were recorded for the sampled publishes
    assert sentinel.fan_hist.total >= snap["in_band"]


async def test_substages_sum_to_wall_single_device():
    broker = Broker()
    broker._fanout_min_fan = 0
    broker.sentinel = PublishSentinel(broker, sample_n=1)
    eng = broker.enable_dispatch_engine(queue_depth=8, deadline_ms=0.2)
    _mk_subs(broker, "ds/+/v")
    await _storm(eng, [f"ds/{i}/v" for i in range(6)])
    await eng.stop()
    _assert_decomposition(broker.sentinel)


async def test_substages_sum_to_wall_sharded():
    import jax

    from emqx_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(n_dp=1, n_sub=4, devices=jax.devices()[:4])
    broker = Broker(mesh=mesh)
    broker._fanout_min_fan = 0
    broker.sentinel = PublishSentinel(broker, sample_n=1)
    eng = broker.enable_dispatch_engine(queue_depth=8, deadline_ms=0.2)
    _mk_subs(broker, "dm/+/v")
    await _storm(eng, [f"dm/{i}/v" for i in range(6)])
    await eng.stop()
    _assert_decomposition(broker.sentinel)


async def test_stage_toggle_stops_substage_feed():
    """broker.perf.tpu_delivery_stages=false must zero the sub-stage
    feed without touching the older queue/deliver attribution."""
    broker = Broker()
    broker._fanout_min_fan = 0
    st = broker.sentinel = PublishSentinel(broker, sample_n=1)
    st.delivery_stages_enabled = False
    eng = broker.enable_dispatch_engine(queue_depth=8, deadline_ms=0.2)
    _mk_subs(broker, "dt/+/v")
    await _storm(eng, [f"dt/{i}/v" for i in range(4)], waves=2)
    await eng.stop()
    assert not st.delivery_hist
    assert st.fan_hist.total == 0
    assert st.stage_hist["queue"].total >= 1  # old contract untouched


def test_timed_plan_matches_plain_plan_output():
    """The instrumented walk must be delivery-identical to the hot
    loop: same deliveries, byte-identical sink output, same session
    inflight state — across the bcast / rest / other legs, QoS0 fast
    paths, QoS1 bookkeeping, and a disconnected session."""
    from emqx_tpu.obs.sentinel import StageSpan

    results = []
    for spanned in (False, True):
        broker = Broker()
        broker._fanout_min_fan = 0
        sinks = {}
        for i in range(6):
            s, _ = broker.open_session(f"p{i}", clean_start=True)
            out = sinks[f"p{i}"] = []
            s.outgoing_sink = out.append
            broker.subscribe(s, "tp/+/v", SubOpts(qos=0 if i < 3 else 1))
            if i == 5:
                s.connected = False
        msg = Message(topic="tp/1/v", payload=b"payload", qos=1)
        pairs = broker.router.match_pairs(msg.topic)
        key = tuple(flt for flt, _ in pairs)
        span = StageSpan("tp/1/v", "t-identity") if spanned else None
        n = broker._dispatch_direct(msg, pairs, key, span)
        flat = {
            cid: [bytes(p.payload) for batch in out for p in batch]
            for cid, out in sinks.items()
        }
        inflight = {
            cid: len(broker.sessions[cid].inflight)
            for cid in sinks
            if cid in broker.sessions
        }
        results.append((n, flat, inflight))
        if spanned:
            # the span actually measured the walk it mirrored
            assert set(span.subs) >= {"dispatch_loop", "session_write"}
            assert span.fan == n
    assert results[0] == results[1], (
        "instrumented delivery diverged from the hot loop"
    )


async def test_ring_occupancy_timeline():
    broker = Broker()
    broker._fanout_min_fan = 0
    eng = broker.enable_dispatch_engine(queue_depth=4, deadline_ms=0.2)
    _mk_subs(broker, "rg/+/v", n_qos0=4, n_qos1=0)
    topics = [f"rg/{i}/v" for i in range(4)]
    await _storm(eng, topics, waves=2)
    await asyncio.sleep(0.15)  # the ring drains: an idle gap opens
    await _storm(eng, topics, waves=2)
    await eng.stop()
    ring = eng.ring_status()
    assert ring["slots_total"] >= 2
    assert ring["timeline"], "no slot spans recorded"
    for slot in ring["timeline"]:
        assert set(slot) == {"launch", "land", "span_ms", "mode",
                             "publishes"}
        assert slot["land"] >= slot["launch"]
        assert slot["publishes"] >= 1
    tel = broker.router.telemetry
    assert tel.family_hist["ring_slot_span_seconds"].total == \
        ring["slots_total"]


async def test_loop_lag_monitor():
    ll = LoopLagMonitor(interval_s=0.02)
    assert ll.start()
    assert not ll.start()  # idempotent while running
    await asyncio.sleep(0.2)
    ll.stop()
    assert ll.ticks_total >= 3
    assert ll.hist.total == ll.ticks_total
    st = ll.status()
    assert st["recent_ms"] and not st["running"]


def test_loop_lag_needs_running_loop():
    assert LoopLagMonitor().start() is False


def _busy_thread(stop_event):
    """A worker with a recognizable frame for the sampler to catch."""
    while not stop_event.is_set():
        sum(i * i for i in range(500))


def test_profiler_samples_and_collapsed_output():
    stop = threading.Event()
    t = threading.Thread(target=_busy_thread, args=(stop,), daemon=True)
    t.start()
    prof = SamplingProfiler(hz=200.0, target_thread_id=t.ident)
    try:
        STAGE_MARK.stage = "dispatch_loop"
        assert prof.start()
        assert not prof.start()  # idempotent
        time.sleep(0.4)
    finally:
        prof.stop()
        STAGE_MARK.stage = ""
        stop.set()
        t.join()
    st = prof.status()
    assert st["samples_total"] >= 5
    assert not st["running"]
    # the busy worker burned CPU: on-CPU classification saw some of it
    assert st["cpu_samples_total"] >= 1
    # stacks bucketed under the live stage mark
    assert "dispatch_loop" in st["stage_samples"]
    rows = prof.top_stacks(stage="dispatch_loop", n=10)
    assert rows and any(
        "_busy_thread" in fr for r in rows for fr in r["stack"]
    )
    # collapsed output is flamegraph.pl input: frames;...;frame count
    for line in prof.collapsed().splitlines():
        body, count = line.rsplit(" ", 1)
        assert int(count) >= 1
        assert body.startswith("stage:")
    prof.reset()
    assert prof.status()["samples_total"] == 0


def test_profiler_overflow_is_bounded_and_counted():
    stop = threading.Event()
    t = threading.Thread(target=_busy_thread, args=(stop,), daemon=True)
    t.start()
    prof = SamplingProfiler(
        hz=500.0, target_thread_id=t.ident, max_stacks=0
    )
    try:
        prof.start()
        time.sleep(0.2)
    finally:
        prof.stop()
        stop.set()
        t.join()
    st = prof.status()
    assert st["samples_total"] >= 1
    # with a zero-stack table EVERY sample overflows into the one
    # explicit bucket — counted, never silently dropped
    assert st["overflow_total"] == st["samples_total"]
    assert st["unique_stacks"] <= len(prof.stacks)
    rows = prof.top_stacks(n=5)
    assert rows and rows[0]["stack"] == ["<overflow>"]


def test_profiler_arm_window_self_stops():
    stop = threading.Event()
    t = threading.Thread(target=_busy_thread, args=(stop,), daemon=True)
    t.start()
    prof = SamplingProfiler(hz=200.0, target_thread_id=t.ident)
    try:
        prof.arm_for(0.05)
        assert prof.running
        deadline = time.monotonic() + 5.0
        while prof.running and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not prof.running, "armed sampler never disarmed"
        assert prof.arms_total == 1
    finally:
        prof.stop()
        stop.set()
        t.join()


def test_flight_bundle_auto_arms_profiler(tmp_path):
    from emqx_tpu.obs import Observability

    broker = Broker()
    obs = Observability(
        broker,
        trace_dir=str(tmp_path / "t"),
        flight_dir=str(tmp_path / "f"),
    )
    try:
        assert not obs.profiler.running
        obs.flight.snapshot("arm-test")
        assert obs.profiler.running  # the bundle armed it
        assert obs.profiler.arms_total == 1
        bundle = obs.flight.store.list()
        assert bundle
        data = obs.flight.store.read(bundle[0]["name"])
        assert "profile" in data  # the snapshot ships sampler state
    finally:
        obs.stop()
    assert not obs.profiler.running


def test_forwarded_span_unit():
    broker = Broker()
    st = PublishSentinel(broker, sample_n=4)
    # no propagation header -> no forced span
    assert st.forwarded_span(Message(topic="x", payload=b"")) is None
    msg = Message(topic="x", payload=b"")
    msg.headers["sentinel_trace"] = "trace-123"
    span = st.forwarded_span(msg)
    assert span is not None and span.trace_id == "trace-123"
    assert st.forwarded_spans_total == 1
    # sampling off disables the forced remote span too
    st.sample_n = 0
    assert st.forwarded_span(msg) is None


async def test_cluster_trace_propagation():
    """A forwarded publish across a REAL 2-node cluster must produce
    remote-side sub-stage samples whose exemplar carries the
    ORIGINATING span's trace id."""
    from emqx_tpu.cluster import ClusterNode

    async def wait_until(pred, timeout=30.0, msg="condition"):
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while not pred():
            assert loop.time() < deadline, f"timeout waiting for {msg}"
            await asyncio.sleep(0.02)

    a = ClusterNode("n0", heartbeat_interval=0.05, miss_threshold=3)
    b = ClusterNode("n1", heartbeat_interval=0.05, miss_threshold=3)
    addr = await a.start()
    await b.start()
    await b.join(addr)
    try:
        for n in (a, b):
            n.broker.sentinel = PublishSentinel(n.broker, sample_n=1)
            n.broker._fanout_min_fan = 0
        s, _ = b.broker.open_session("remote-sub", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        b.broker.subscribe(s, "xn/+/v", SubOpts(qos=0))
        await wait_until(
            lambda: "n1" in a.cluster_router.match_routes("xn/1/v"),
            msg="route replication",
        )
        a.broker.publish(Message(topic="xn/1/v", payload=b"fwd"))
        await wait_until(
            lambda: b.broker.sentinel.forwarded_spans_total >= 1,
            msg="remote forwarded span",
        )
        local = [
            e for e in a.broker.sentinel.exemplars
            if e["topic"] == "xn/1/v"
        ]
        remote = [
            e for e in b.broker.sentinel.exemplars
            if e["topic"] == "xn/1/v"
        ]
        assert local and remote
        # the Dapper contract: one trace id, both sides
        assert remote[-1]["trace_id"] == local[-1]["trace_id"]
        assert remote[-1]["trace_id"]
        # the remote side decomposed its delivery into sub-stages
        assert "plan_resolve" in remote[-1]["subs_ms"]
        assert "dispatch_loop" in remote[-1]["subs_ms"]
        assert remote[-1]["fan"] >= 1
        assert b.broker.sentinel.delivery_hist
    finally:
        await a.stop()
        await b.stop()


def test_sampled_ack_clock_gating():
    broker = Broker()
    st = PublishSentinel(broker, sample_n=2)
    got = [st.maybe_ack_clock() for _ in range(4)]
    assert sum(1 for c in got if c is not None) == 2  # 1-in-2 ticks
    st.sample_n = 0
    assert st.maybe_ack_clock() is None
    before = dict(st.delivery_hist)
    st.observe_delivery("ack_sweep", 0.001)
    assert st.delivery_hist["ack_sweep"].total == (
        before["ack_sweep"].total + 1 if "ack_sweep" in before else 1
    )
