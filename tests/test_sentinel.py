"""Publish-path sentinel (obs/sentinel): the ISSUE-5 acceptance chain.

Fault injection: corrupt one device row / slot table / fanout plan and
assert the shadow-oracle audit detects it within one sampling window
and produces the full chain — divergence counter, flight-recorder
snapshot, alarm, quarantine to the host-walk fallback, clean-sync
recovery — on both single-device and sharded tables. Plus stage
attribution, SLO burn-rate alarms, and the cluster rollup."""

import asyncio
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.broker.packet import SubOpts
from emqx_tpu.broker.pubsub import Broker
from emqx_tpu.obs import Observability
from emqx_tpu.obs.sentinel import STAGES, SloObjective, StageSpan
from emqx_tpu.ops.hash_index import SlotArrays


def make(tmp_path, mesh=None, **obs_kw):
    b = Broker(mesh=mesh)
    obs = Observability(
        b,
        node_name="n1@host",
        trace_dir=str(tmp_path / "trace"),
        flight_dir=str(tmp_path / "flight"),
        **obs_kw,
    )
    obs.sentinel.sample_n = 1  # every served publish audited
    obs.sentinel.warmup_left = 0  # attribution asserted from span one
    b._fanout_min_fan = 0
    return b, obs


def subscribe_fan(b, flt="a/+/c", n=6):
    for i in range(n):
        s, _ = b.open_session(f"c{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        b.subscribe(s, flt, SubOpts(qos=i % 3))


def corrupt_slot_table(router):
    """Simulate device memory decay: every cuckoo bucket id becomes -1,
    so the hash kernel stops surfacing every classed filter while the
    host state stays pristine."""
    dt = router.device_table
    sl = dt._dev_slots
    bad = np.full(np.asarray(sl.bucket).shape, -1, np.asarray(sl.bucket).dtype)
    dt._dev_slots = SlotArrays(
        sl.fp, jax.device_put(bad, sl.bucket.sharding), sl.probe
    )


async def _drive(b, eng, topics):
    ns = await asyncio.gather(
        *[eng.publish(Message(topic=t, payload=b"x")) for t in topics]
    )
    await asyncio.sleep(0)  # let the deferred audit turn run
    b.sentinel.run_audits()
    return ns


async def _chain(b, obs, tmp_path):
    """The corruption->detection->recovery chain, shared by the
    single-device and sharded variants."""
    eng = b.enable_dispatch_engine(
        queue_depth=4, deadline_ms=0.2, match_cache_size=64
    )
    subscribe_fan(b)
    r = b.router
    tel = r.telemetry
    ns = await _drive(b, eng, [f"a/{i}/c" for i in range(4)])
    assert ns == [6, 6, 6, 6]
    assert tel.counters["audit_clean_total"] >= 4
    assert "audit_divergence_total" not in tel.counters

    corrupt_slot_table(r)
    snaps_before = len(obs.flight.store.list())
    (n,) = await _drive(b, eng, ["a/zz/c"])  # fresh topic: cache miss
    assert n == 0  # the corrupt device really did mis-serve
    # detected within ONE sampling window: counter + flight snapshot +
    # alarm + quarantine
    assert tel.counters["audit_divergence_total"] == 1
    assert r.quarantined_filters() == ["a/+/c"]
    assert tel.counters["audit_quarantine_total"] == 1
    assert obs.alarms.is_active("xla_audit_divergence")
    snaps = obs.flight.store.list()
    assert len(snaps) > snaps_before
    assert any("audit_divergence" in s["name"] for s in snaps)
    bundle = obs.flight.store.read(
        next(s["name"] for s in snaps if "audit_divergence" in s["name"])
    )
    assert bundle["reason"] == "audit_divergence"
    assert bundle["details"]["kind"] == "match"
    assert "a/+/c" in bundle["details"]["filters"]

    # clean-sync recovery: the next batched match re-uploads the
    # dirtied rows + index state, auto-unquarantines (counted), and
    # the device serves correctly again
    out = r.match_filters_finish(r.match_filters_begin(["a/q/c"]))
    assert out == [["a/+/c"]]
    assert r.quarantined_filters() == []
    assert tel.counters["audit_unquarantine_total"] == 1
    (n2,) = await _drive(b, eng, ["a/yy/c"])
    assert n2 == 6
    assert tel.counters["audit_divergence_total"] == 1  # no re-fire
    await eng.stop()


async def test_corruption_chain_single_device(tmp_path):
    b, obs = make(tmp_path)
    try:
        await _chain(b, obs, tmp_path)
    finally:
        obs.stop()


async def test_corruption_chain_sharded(tmp_path):
    from emqx_tpu.parallel import mesh as mesh_mod

    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    b, obs = make(tmp_path, mesh=mesh_mod.make_mesh(n_dp=2, n_sub=4))
    try:
        await _chain(b, obs, tmp_path)
    finally:
        obs.stop()


async def test_fanout_plan_divergence_detected(tmp_path):
    # the dest-segment failure mode: the plan that serves is not the
    # plan the oracle would build (a client dropped from the fan)
    b, obs = make(tmp_path)
    try:
        eng = b.enable_dispatch_engine(queue_depth=2, deadline_ms=0.2)
        subscribe_fan(b, n=8)
        await _drive(b, eng, ["a/1/c"])
        key = ("a/+/c",)
        entry = b._fanout_cache[key]
        clock, plan = entry[0], entry[1]
        mem, other = plan
        assert len(mem) == 8
        b._fanout_cache[key] = (clock, (mem[:-1], other))  # drop a client
        (n,) = await _drive(b, eng, ["a/1/c"])
        assert n == 7  # the corrupt plan really served short
        tel = b.router.telemetry
        assert tel.counters["audit_divergence_total"] == 1
        assert obs.sentinel.divergences[-1]["kind"] == "fanout"
        assert obs.alarms.is_active("xla_audit_divergence")
        # quarantine covers the plan's filters; recovery via clean sync
        assert b.router.quarantined_filters() == ["a/+/c"]
        out = b.router.match_filters_finish(
            b.router.match_filters_begin(["a/2/c"])
        )
        assert out == [["a/+/c"]]
        (n2,) = await _drive(b, eng, ["a/3/c"])
        assert n2 == 8
        await eng.stop()
    finally:
        obs.stop()


async def test_overlay_corrects_inflight_batch(tmp_path):
    # a batch LAUNCHED against the corrupt table before the audit
    # quarantined it must still finish with host-true results — the
    # pipeline's in-flight window is exactly where the host-walk
    # fallback serves
    b, obs = make(tmp_path)
    try:
        subscribe_fan(b)
        r = b.router
        r.match_filters_batch(["a/w/c"])  # warm + sync
        corrupt_slot_table(r)
        p = r.match_filters_begin(["a/x/c"])  # launched while corrupt
        assert r.quarantine_filters(["a/+/c"]) == 1
        out = r.match_filters_finish(p)
        assert out == [["a/+/c"]]  # overlay re-added the dropped filter
        assert (
            r.telemetry.counters["audit_quarantine_overlay_total"] >= 1
        )
    finally:
        obs.stop()


async def test_audit_skips_stale_generation(tmp_path):
    # a route mutation between serve and audit must be SKIPPED, not
    # reported as divergence: the oracle would answer for a different
    # generation than the one that served
    b, obs = make(tmp_path)
    try:
        eng = b.enable_dispatch_engine(queue_depth=2, deadline_ms=0.2)
        subscribe_fan(b)
        # hold the deferred drain so the mutation deterministically
        # lands between serve and audit
        b.sentinel._drain_scheduled = True
        ns = await asyncio.gather(
            *[eng.publish(Message(topic="a/1/c", payload=b"x"))]
        )
        assert ns == [6]
        # mutate BEFORE the audit drains
        s, _ = b.open_session("late", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        b.subscribe(s, "a/#", SubOpts(qos=0))
        b.sentinel._drain_scheduled = False
        b.sentinel.run_audits()
        tel = b.router.telemetry
        assert tel.counters.get("audit_skipped_stale_total", 0) >= 1
        assert "audit_divergence_total" not in tel.counters
        await eng.stop()
    finally:
        obs.stop()


async def test_stage_attribution_and_exemplars(tmp_path):
    b, obs = make(tmp_path)
    try:
        eng = b.enable_dispatch_engine(queue_depth=4, deadline_ms=0.2)
        subscribe_fan(b)
        await _drive(b, eng, [f"a/{i}/c" for i in range(8)])
        st = obs.sentinel
        assert st.spans_total == 8
        for stage in ("queue", "encode", "kernel", "fetch", "deliver"):
            assert stage in st.stage_hist, stage
            assert st.stage_hist[stage].total >= 1
        assert set(st.stage_hist) <= set(STAGES)
        ex = list(st.exemplars)
        assert ex and ex[-1]["topic"].startswith("a/")
        assert len(ex[-1]["trace_id"]) == 32
        assert ex[-1]["stages_ms"]
        # the JSON surface carries the same numbers
        snap = st.stage_snapshot()
        assert snap["total"]["count"] == 8
        assert snap["exemplars"][-1] == ex[-1]
        await eng.stop()
    finally:
        obs.stop()


async def test_unsampled_path_is_probe_free(tmp_path):
    b, obs = make(tmp_path)
    try:
        st = obs.sentinel
        st.sample_n = 10**9  # never sample
        eng = b.enable_dispatch_engine(queue_depth=4, deadline_ms=0.2)
        subscribe_fan(b)
        await _drive(b, eng, [f"a/{i}/c" for i in range(8)])
        assert st.spans_total == 0
        assert st.stage_hist == {}
        assert not st.exemplars
        assert "audit_total" not in b.router.telemetry.counters
        await eng.stop()
    finally:
        obs.stop()


def test_slo_objective_multiwindow_burn():
    o = SloObjective("x", target=0.99, fast_window_s=10.0,
                     slow_window_s=100.0, burn_threshold=5.0, min_events=4)
    now = 1000.0
    for i in range(8):
        o.record(False, now=now + i)
    st = o.evaluate(now=now + 8)
    # 100% errors against a 1% budget = 100x burn in BOTH windows
    assert st["fast_burn"] == 100.0 and st["slow_burn"] == 100.0
    assert st["breached"]
    # recovery: enough successes drop the FAST window under threshold
    for i in range(400):
        o.record(True, now=now + 20 + i * 0.01)
    st = o.evaluate(now=now + 24)
    assert st["fast_burn"] is not None and st["fast_burn"] <= 5.0
    assert not st["breached"]


async def test_slo_breach_raises_and_clears_alarm(tmp_path):
    b, obs = make(tmp_path)
    try:
        st = obs.sentinel
        st.slo_publish_ms = 0.0  # every sampled publish violates
        slo = st.slo["publish_latency"]
        slo.min_events = 4
        eng = b.enable_dispatch_engine(queue_depth=4, deadline_ms=0.2)
        subscribe_fan(b)
        await _drive(b, eng, [f"a/{i}/c" for i in range(8)])
        assert slo.evaluate()["breached"]
        assert obs.alarms.is_active("xla_slo_publish_latency_burn")
        # recovery: objective satisfied again -> alarm clears (budget
        # widened so the recovery fits a test-sized sample; the exact
        # burn math is covered by test_slo_objective_multiwindow_burn)
        st.slo_publish_ms = 1e9
        slo.target = 0.5
        await _drive(b, eng, [f"a/r{i}/c" for i in range(64)])
        assert not slo.evaluate()["breached"]
        assert not obs.alarms.is_active("xla_slo_publish_latency_burn")
        await eng.stop()
    finally:
        obs.stop()


async def test_cluster_rollup(tmp_path):
    from emqx_tpu.cluster.node import ClusterBroker, ClusterNode

    b1, b2 = ClusterBroker(), ClusterBroker()
    o1 = Observability(b1, flight=False, trace_dir=str(tmp_path / "t1"))
    o2 = Observability(b2, flight=False, trace_dir=str(tmp_path / "t2"))
    n1 = ClusterNode("n1", broker=b1)
    n2 = ClusterNode("n2", broker=b2)
    try:
        a1 = await n1.start()
        await n2.start()
        await n2.join(a1)
        # give node 2 some audited traffic so the rollup carries it
        b2.sentinel.sample_n = 1
        b2._fanout_min_fan = 0
        eng = b2.enable_dispatch_engine(queue_depth=2, deadline_ms=0.2)
        subscribe_fan(b2)
        await _drive(b2, eng, ["a/1/c", "a/2/c"])
        await eng.stop()
        roll = await n1.sentinel_rollup()
        assert set(roll["per_node"]) == {"n1", "n2"}
        assert roll["cluster"]["nodes"] == 2
        assert roll["cluster"]["unreachable"] == 0
        assert roll["cluster"]["audit_total"] >= 2
        assert roll["cluster"]["audit_divergence"] == 0
        assert roll["per_node"]["n2"]["audit_total"] >= 2
    finally:
        await n2.stop()
        await n1.stop()
        o1.stop()
        o2.stop()


async def test_sentinel_surfaces(tmp_path):
    # ctl command + REST endpoint + telemetry-endpoint exemplar merge
    from emqx_tpu.mgmt.cli import Ctl

    b, obs = make(tmp_path)
    try:
        eng = b.enable_dispatch_engine(queue_depth=2, deadline_ms=0.2)
        subscribe_fan(b)
        await _drive(b, eng, ["a/1/c", "a/2/c"])
        ctl = Ctl(b, obs=obs)
        out = ctl.run(["sentinel", "status"])
        assert "audit" in out and "slo" in out
        assert "diverged" in out
        stages = ctl.run(["sentinel", "stages"])
        assert "deliver" in stages
        st = obs.sentinel
        status = st.status()
        assert status["enabled"] and status["audit"]["total"] >= 2
        assert status["audit"]["divergence"] == 0
        assert status["slo"]["publish_latency"]["target"] == 0.999
        summ = st.summary()
        assert summ["audit_divergence"] == 0
        await eng.stop()
    finally:
        obs.stop()


def test_sync_publish_path_is_sampled_too(tmp_path):
    # the live socket path (Broker.publish, host-trie match) executes
    # device-resolved fanout plans: sampled sync publishes must feed
    # the audit + deliver-stage attribution as well
    b, obs = make(tmp_path)
    try:
        subscribe_fan(b)
        n = b.publish(Message(topic="a/1/c", payload=b"x"))
        assert n == 6
        b.sentinel.run_audits()
        tel = b.router.telemetry
        assert tel.counters["audit_total"] >= 1
        assert "audit_divergence_total" not in tel.counters
        assert "deliver" in obs.sentinel.stage_hist
        # corrupt the CACHED plan the sync path will execute
        key = ("a/+/c",)
        entry = b._fanout_cache[key]
        clock, (mem, other) = entry[0], entry[1]
        b._fanout_cache[key] = (clock, (mem[:-1], other))
        assert b.publish(Message(topic="a/1/c", payload=b"x")) == 5
        b.sentinel.run_audits()
        assert tel.counters["audit_divergence_total"] == 1
        assert obs.sentinel.divergences[-1]["kind"] == "fanout"
    finally:
        obs.stop()


def test_quarantine_refuses_device_fanout(tmp_path):
    b, obs = make(tmp_path)
    try:
        subscribe_fan(b, n=8)
        r = b.router
        r.match_filters_batch(["a/1/c"])
        assert r.resolve_fanout_begin(("a/+/c",), min_fan=0) is not None
        r.quarantine_filters(["a/+/c"])
        assert r.resolve_fanout_begin(("a/+/c",), min_fan=0) is None
        assert (
            r.telemetry.counters["audit_quarantine_resolve_refusals_total"]
            == 1
        )
        # but the host oracle path still builds the full plan
        mem, other = b._build_fanout_plan(
            [("a/+/c", r.filter_dests("a/+/c"))]
        )
        assert len(mem) == 8
    finally:
        obs.stop()


async def test_full_audit_queue_counts_its_drops():
    from emqx_tpu.obs.sentinel import PublishSentinel

    b = Broker()
    st = PublishSentinel(b, sample_n=1, max_pending_audits=2)
    for _ in range(5):
        st.capture_audit("t/1", (), [], b.router.generation)
    assert b.router.telemetry.counters["audit_dropped_total"] == 3
    await asyncio.sleep(0)
    assert b.router.telemetry.counters["audit_total"] == 2
