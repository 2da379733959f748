"""Prometheus exposition lint: scrape `prometheus_text` output and
validate the text-format invariants a real Prometheus server enforces —
TYPE lines, metric/label syntax, one family per name, histogram
`_bucket`/`_sum`/`_count` structure with cumulative `le` buckets.
Guards the exporter against the classic silent failure: a scrape that
looks fine in tests and 400s at ingestion."""

import asyncio
import re

import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.broker.packet import SubOpts
from emqx_tpu.broker.pubsub import Broker
from emqx_tpu.obs import prometheus_text

_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_TYPE_RE = re.compile(rf"^# TYPE ({_NAME}) (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    rf"^({_NAME})"
    rf"(?:\{{({_NAME}=\"[^\"\\]*\"(?:,{_NAME}=\"[^\"\\]*\")*)\}})?"
    r" (-?[0-9.e+-]+|\+Inf|NaN)$"
)


def _scraped_broker():
    broker = Broker()
    s, _ = broker.open_session("c1", clean_start=True)
    s.outgoing_sink = lambda pkts: None
    broker.subscribe(s, "t/#", SubOpts(qos=0))
    broker.publish(Message(topic="t/1", payload=b"x"))
    # drive the device match path so emqx_xla_* families populate
    broker.router.add_routes([(f"k{i}/+/v/#", f"d{i}") for i in range(16)])
    broker.router.match_filters_batch([f"k{i}/a/v/w" for i in range(8)])
    return broker


def _family_of(sample_name: str, histograms) -> str:
    for suffix in ("_bucket", "_sum", "_count"):
        if sample_name.endswith(suffix) and sample_name[: -len(suffix)] in histograms:
            return sample_name[: -len(suffix)]
    return sample_name


def _lint(text):
    assert text.endswith("\n")
    types = {}  # family -> kind
    samples_seen_for = set()
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("#"):
            m = _TYPE_RE.match(line)
            assert m, f"malformed comment line: {line!r}"
            fam = m.group(1)
            # one TYPE line per family, declared before any sample
            assert fam not in types, f"duplicate TYPE for {fam}"
            assert fam not in samples_seen_for, f"TYPE after samples: {fam}"
            types[fam] = m.group(2)
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"malformed sample line: {line!r}"
        histograms = {f for f, k in types.items() if k == "histogram"}
        fam = _family_of(m.group(1), histograms)
        assert fam in types, f"sample without TYPE: {line!r}"
        samples_seen_for.add(fam)
    # every declared family produced at least one sample
    assert set(types) == samples_seen_for
    return types


def test_exposition_lint():
    _lint(prometheus_text(_scraped_broker(), "n1@host"))


def test_histogram_families_well_formed():
    text = prometheus_text(_scraped_broker(), "n1@host")
    fam = "emqx_xla_dispatch_duration_seconds"
    assert f"# TYPE {fam} histogram" in text
    legs = {}
    for line in text.splitlines():
        if line.startswith(f"{fam}_bucket{{"):
            labels = line[line.index("{") + 1 : line.index("}")]
            le = re.search(r'le="([^"]+)"', labels).group(1)
            leg = re.search(r'leg="([^"]+)"', labels).group(1)
            legs.setdefault(leg, []).append((le, int(line.rsplit(" ", 1)[1])))
    assert "hash" in legs and "encode" in legs
    for leg, buckets in legs.items():
        les = [le for le, _ in buckets]
        counts = [c for _, c in buckets]
        assert les[-1] == "+Inf", f"{leg}: no terminal +Inf bucket"
        assert counts == sorted(counts), f"{leg}: buckets not cumulative"
        assert f'{fam}_sum{{node="n1@host",leg="{leg}"}}' in text
        assert f'{fam}_count{{node="n1@host",leg="{leg}"}}' in text
        # _count equals the +Inf bucket
        count_line = next(
            l for l in text.splitlines()
            if l.startswith(f'{fam}_count{{node="n1@host",leg="{leg}"}}')
        )
        assert int(count_line.rsplit(" ", 1)[1]) == counts[-1]


def test_xla_families_present_after_match():
    text = prometheus_text(_scraped_broker(), "n1@host")
    assert 'emqx_xla_recompiles_total{node="n1@host"}' in text
    assert 'emqx_xla_device_table_bytes{node="n1@host"}' in text
    assert 'emqx_xla_jit_cache_entries{node="n1@host",kernel="match_ids_hash"}' in text
    # dispatch counts actually populated (non-zero _count for hash leg)
    m = re.search(
        r'emqx_xla_dispatch_duration_seconds_count\{node="n1@host",leg="hash"\} (\d+)',
        text,
    )
    assert m and int(m.group(1)) >= 1


def test_max_watermark_gauges_emitted():
    # stats `.max` watermarks were silently dropped before; they now
    # export as emqx_*_max gauge families
    text = prometheus_text(_scraped_broker(), "n1@host")
    assert "# TYPE emqx_sessions_count_max gauge" in text
    assert 'emqx_sessions_count_max{node="n1@host"}' in text


def test_obs_families_lint(tmp_path):
    # the ISSUE-2 families — hook durations, flight counters, otel
    # exporter counters, slow-subs gauges, per-topic counters — must
    # pass the same exposition lint and all land on ONE scrape
    from emqx_tpu.obs import Observability
    from emqx_tpu.obs.otel import OtelTracer

    broker = Broker()
    obs = Observability(
        broker,
        node_name="n1@host",
        trace_dir=str(tmp_path / "t"),
        flight_dir=str(tmp_path / "f"),
    )
    try:
        broker.tracer = OtelTracer()
        s, _ = broker.open_session("c1", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, "t/#", SubOpts(qos=0))
        obs.topic_metrics.register("t/1")
        broker.publish(Message(topic="t/1", payload=b"x"))
        obs.slow_subs.track("c9", "t/slow", 900.0)
        broker.router.add_routes([(f"k{i}/+/v/#", f"d{i}") for i in range(16)])
        broker.router.match_filters_batch([f"k{i}/a/v/w" for i in range(8)])
        obs.flight.snapshot("lint")
        text = obs.prometheus_text()
        types = _lint(text)
        for fam, kind in (
            ("emqx_hook_duration_seconds", "histogram"),
            ("emqx_flight_events_total", "counter"),
            ("emqx_flight_snapshots_total", "counter"),
            ("emqx_flight_frozen", "gauge"),
            ("emqx_otel_spans_exported", "counter"),
            ("emqx_otel_spans_dropped", "counter"),
            ("emqx_slow_subs_tracked", "gauge"),
            ("emqx_slow_subs_max_timespan_ms", "gauge"),
            ("emqx_topic_messages_in_total", "counter"),
            ("emqx_topic_messages_out_total", "counter"),
        ):
            assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
        # labeled samples carry the right values
        assert 'emqx_topic_messages_in_total{node="n1@host",topic="t/1"} 1' in text
        assert 'emqx_slow_subs_tracked{node="n1@host"} 1' in text
        assert 'emqx_flight_snapshots_total{node="n1@host"} 1' in text
        # hook histogram is cumulative with a terminal +Inf (same
        # structural contract as the xla dispatch family)
        hook_counts = [
            int(l.rsplit(" ", 1)[1])
            for l in text.splitlines()
            if l.startswith(
                'emqx_hook_duration_seconds_bucket{node="n1@host",'
                'hook="message.publish"'
            )
        ]
        assert hook_counts and hook_counts == sorted(hook_counts)
    finally:
        obs.stop()


async def test_pipeline_and_cache_families_lint():
    # ISSUE-3 families: the generation-stamped match-cache counters and
    # the dispatch-engine pipeline gauges/histogram must pass the same
    # exposition lint on the same scrape
    from emqx_tpu.broker.dispatch_engine import DispatchEngine

    broker = Broker()
    s, _ = broker.open_session("c1", clean_start=True)
    s.outgoing_sink = lambda pkts: None
    broker.subscribe(s, "k0/#", SubOpts(qos=0))
    broker.router.add_routes([(f"k{i}/+/v/#", f"d{i}") for i in range(16)])
    # tiny cache so the evictions counter populates too
    eng = DispatchEngine(
        broker, queue_depth=8, deadline_ms=0.5, match_cache_size=4
    )
    topics = [f"k{i}/a/v/w" for i in range(8)]
    for _ in range(2):  # second wave produces hits
        await asyncio.gather(
            *[eng.publish(Message(topic=t, payload=b"x")) for t in topics]
        )
    await eng.stop()
    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_match_cache_hits", "counter"),
        ("emqx_xla_match_cache_misses", "counter"),
        ("emqx_xla_match_cache_evictions", "counter"),
        ("emqx_xla_pipeline_depth", "gauge"),
        ("emqx_xla_pipeline_coalesce", "gauge"),
        ("emqx_xla_match_cache_hit_ratio", "gauge"),
        ("emqx_xla_pipeline_queue_wait_seconds", "histogram"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the queue-wait histogram is structurally sound: cumulative with a
    # terminal +Inf whose count equals _count
    buckets = [
        int(l.rsplit(" ", 1)[1])
        for l in text.splitlines()
        if l.startswith('emqx_xla_pipeline_queue_wait_seconds_bucket{')
    ]
    assert buckets and buckets == sorted(buckets)
    count_line = next(
        l for l in text.splitlines()
        if l.startswith('emqx_xla_pipeline_queue_wait_seconds_count')
    )
    assert int(count_line.rsplit(" ", 1)[1]) == buckets[-1] == 16


def test_fanout_families_lint():
    # ISSUE-4 families: the device-resolved fanout counters, dedup
    # gauge, and resolve-latency histogram must ride the same scrape,
    # driven through a REAL device resolve (not hand-poked counters)
    broker = Broker()
    broker._fanout_min_fan = 0
    for i in range(12):
        s, _ = broker.open_session(f"f{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, "fo/+/v", SubOpts(qos=i % 3))
        if i < 6:
            broker.subscribe(s, "fo/#", SubOpts(qos=2))
    broker.publish(Message(topic="fo/1/v", payload=b"x"))  # miss -> device
    broker.publish(Message(topic="fo/1/v", payload=b"x"))  # hit
    s, _ = broker.open_session("late", clean_start=True)
    s.outgoing_sink = lambda pkts: None
    broker.subscribe(s, "fo/#", SubOpts(qos=0))
    broker.publish(Message(topic="fo/1/v", payload=b"x"))  # stale -> device
    # a small fan below min_fan: the live-fan gate sends it to the host
    broker._fanout_min_fan = 1024
    broker.subscribe(s, "sm/+", SubOpts(qos=1))
    broker.publish(Message(topic="sm/1", payload=b"x"))  # miss -> host
    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_fanout_plan_hits", "counter"),
        ("emqx_xla_fanout_plan_misses", "counter"),
        ("emqx_xla_fanout_plan_stale", "counter"),
        ("emqx_xla_fanout_device_plans_total", "counter"),
        ("emqx_xla_fanout_small_fan_plans_total", "counter"),
        ("emqx_xla_fanout_dedup_ratio", "gauge"),
        ("emqx_xla_fanout_resolve_seconds", "histogram"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the resolve histogram observed one sample per device plan
    count_line = next(
        l for l in text.splitlines()
        if l.startswith("emqx_xla_fanout_resolve_seconds_count")
    )
    plans_line = next(
        l for l in text.splitlines()
        if l.startswith("emqx_xla_fanout_device_plans_total")
    )
    assert int(count_line.rsplit(" ", 1)[1]) == int(
        plans_line.rsplit(" ", 1)[1]
    ) >= 2
    small_line = next(
        l for l in text.splitlines()
        if l.startswith("emqx_xla_fanout_small_fan_plans_total")
    )
    assert int(small_line.rsplit(" ", 1)[1]) == 1
    # dedup ratio reflects the overlapping-filter fan (> 1 client/plan)
    ratio_line = next(
        l for l in text.splitlines()
        if l.startswith("emqx_xla_fanout_dedup_ratio")
    )
    assert float(ratio_line.rsplit(" ", 1)[1]) > 1.0


async def test_sentinel_families_lint():
    # ISSUE-5 families: the publish sentinel's stage-attribution
    # histogram, audit counters, and SLO burn gauges must pass the same
    # exposition lint, driven through a REAL pipelined run including a
    # detected divergence (not hand-poked counters)
    from emqx_tpu.obs.sentinel import PublishSentinel

    broker = Broker()
    broker._fanout_min_fan = 0
    broker.sentinel = PublishSentinel(broker, sample_n=1)
    eng = broker.enable_dispatch_engine(queue_depth=4, deadline_ms=0.2)
    for i in range(6):
        s, _ = broker.open_session(f"c{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, "sn/+/v", SubOpts(qos=0))
    topics = [f"sn/{i}/v" for i in range(4)]
    await asyncio.gather(
        *[eng.publish(Message(topic=t, payload=b"x")) for t in topics]
    )
    await asyncio.sleep(0)
    broker.sentinel.run_audits()
    # inject a fanout divergence so the audit_divergence/quarantine
    # counters populate on the scrape
    key = ("sn/+/v",)
    entry = broker._fanout_cache[key]
    clock, (mem, other) = entry[0], entry[1]
    broker._fanout_cache[key] = (clock, (mem[:-1], other))
    await eng.publish(Message(topic="sn/0/v", payload=b"x"))
    await asyncio.sleep(0)
    broker.sentinel.run_audits()
    await eng.stop()
    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_publish_stage_seconds", "histogram"),
        ("emqx_xla_slo_burn_rate", "gauge"),
        ("emqx_xla_slo_breached", "gauge"),
        ("emqx_xla_audit_total", "counter"),
        ("emqx_xla_audit_clean_total", "counter"),
        ("emqx_xla_audit_divergence_total", "counter"),
        ("emqx_xla_audit_quarantine_total", "counter"),
        ("emqx_xla_audit_quarantined_filters", "gauge"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the stage family is cumulative per stage label with terminal +Inf
    fam = "emqx_xla_publish_stage_seconds"
    stages = {}
    for line in text.splitlines():
        if line.startswith(f"{fam}_bucket{{"):
            labels = line[line.index("{") + 1 : line.index("}")]
            stage = re.search(r'stage="([^"]+)"', labels).group(1)
            stages.setdefault(stage, []).append(
                int(line.rsplit(" ", 1)[1])
            )
    for need in ("queue", "encode", "kernel", "fetch", "deliver"):
        assert need in stages, need
        assert stages[need] == sorted(stages[need])
    # both objectives render both burn windows
    for obj in ("publish_latency", "audit_clean"):
        for window in ("fast", "slow"):
            assert (
                f'emqx_xla_slo_burn_rate{{node="n1@host",objective="{obj}",'
                f'window="{window}"}}'
            ) in text


def test_null_telemetry_scrape_stays_clean():
    from emqx_tpu.obs.kernel_telemetry import NULL

    broker = Broker()
    broker.router.telemetry = NULL
    text = prometheus_text(broker, "n1@host")
    assert "emqx_xla_" not in text
    assert "# TYPE emqx_topics_count gauge" in text


async def test_breaker_and_queue_families_lint(tmp_path):
    # ISSUE-8 families: every emqx_xla_breaker_* / emqx_xla_queue_*
    # family the device failure domain exports must render on a real
    # driven scrape — trip, degrade, probe failure, recovery, shed,
    # block, deadline expiry, slow-batch deadline — and pass the lint
    import time as _time

    from emqx_tpu.broker.dispatch_engine import QueueOverloadError
    from emqx_tpu.chaos.faults import DeviceFaultInjector
    from emqx_tpu.obs.alarm import Alarms

    broker = Broker()
    for i in range(4):
        s, _ = broker.open_session(f"c{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, f"q/{i}/+", SubOpts(qos=0))
    eng = broker.enable_dispatch_engine(
        queue_depth=4, deadline_ms=0.5, breaker_threshold=2,
        breaker_deadline_ms=1.0, probe_backoff_ms=5.0,
        probe_backoff_max_ms=20.0, queue_max_depth=64,
    )
    eng.alarms = Alarms(broker)
    inj = DeviceFaultInjector().install(broker.router)
    tel = broker.router.telemetry

    # slow batch -> deadline counter; sticky -> trip; heal -> recovery
    inj.stall(0.005, n=1, legs=("match_finish",))
    await eng.publish(Message(topic="q/0/slow", payload=b"x"))
    inj.fail_sticky()
    for w in range(4):
        await eng.publish(Message(topic=f"q/1/t{w}", payload=b"x"))
        if eng.breaker_state == "open":
            break
    assert eng.breaker_state == "open"
    inj.heal()
    t0 = _time.monotonic()
    while eng.breaker_state != "closed" and _time.monotonic() - t0 < 10:
        await asyncio.sleep(0.01)
    assert eng.breaker_state == "closed"
    # shed + block + deadline expiry
    eng.queue_max_depth = 1
    futs = [
        eng.submit(Message(topic=f"q/2/s{i}", payload=b"x"))
        for i in range(3)
    ]
    res = await asyncio.gather(*futs, return_exceptions=True)
    assert any(isinstance(r, QueueOverloadError) for r in res)
    eng.queue_policy = "block"
    eng.queue_deadline_s = 0.02
    futs = [
        eng.submit(Message(topic=f"q/2/b{i}", payload=b"x"))
        for i in range(3)
    ]
    await asyncio.sleep(0.1)
    await eng.drain()
    await asyncio.gather(*futs, return_exceptions=True)
    eng.queue_max_depth = 64
    await eng.stop()

    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_breaker_state", "gauge"),
        ("emqx_xla_breaker_consecutive_failures", "gauge"),
        ("emqx_xla_breaker_trips_total", "counter"),
        ("emqx_xla_breaker_recoveries_total", "counter"),
        ("emqx_xla_breaker_device_failures_total", "counter"),
        ("emqx_xla_breaker_degraded_batches_total", "counter"),
        ("emqx_xla_breaker_deadline_exceeded_total", "counter"),
        ("emqx_xla_breaker_probe_total", "counter"),
        ("emqx_xla_queue_shed_total", "counter"),
        ("emqx_xla_queue_blocked_total", "counter"),
        ("emqx_xla_queue_deadline_expired_total", "counter"),
        ("emqx_xla_queue_depth", "gauge"),
        ("emqx_xla_queue_waiters", "gauge"),
        ("emqx_xla_queue_overloaded", "gauge"),
        ("emqx_xla_device_suspends_total", "counter"),
        ("emqx_xla_device_resumes_total", "counter"),
        ("emqx_xla_device_resyncs_total", "counter"),
        ("emqx_xla_chaos_device_faults_total", "counter"),
        ("emqx_xla_chaos_device_stalls_total", "counter"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    assert tel.counters["breaker_trips_total"] == 1
    assert tel.counters["breaker_recoveries_total"] == 1


async def test_transfer_and_warmup_families_lint():
    """ISSUE-9 families: the transfer-pipeline telemetry
    (emqx_xla_transfer_{seconds,bytes,inflight}) and the AOT-warmup /
    serve-time-recompile counters must ride the broker scrape, driven
    through a REAL warmed engine serving real publishes — never
    hand-set gauges."""
    from emqx_tpu.broker.dispatch_engine import DispatchEngine

    broker = Broker()
    s, _ = broker.open_session("c1", clean_start=True)
    s.outgoing_sink = lambda pkts: None
    broker.subscribe(s, "k0/#", SubOpts(qos=0))
    broker.router.add_routes(
        [(f"k{i}/+/v/#", f"d{i}") for i in range(16)]
    )
    eng = DispatchEngine(
        broker, queue_depth=8, deadline_ms=0.5, match_cache_size=0,
        transfer_chunk_kb=64, gc_guard=False,
    )
    info = eng.warmup()
    assert info["transfer_chunk_kb"] == 64
    topics = [f"k{i}/a/v/w" for i in range(8)]
    await asyncio.gather(
        *[eng.publish(Message(topic=t, payload=b"x")) for t in topics]
    )
    await eng.stop()
    tel = broker.router.telemetry
    # warmed shapes cover every pow2 bucket up to queue_depth: the
    # serve wave above must not have retraced
    assert tel.counters["aot_warmups_total"] >= 1
    assert tel.counters.get("recompiles_at_serve_total", 0) == 0
    assert tel.counters["transfer_bytes"] > 0
    assert tel.gauges["transfer_inflight"] == 0  # all tickets collected
    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_transfer_seconds", "histogram"),
        ("emqx_xla_transfer_bytes", "counter"),
        ("emqx_xla_transfer_inflight", "gauge"),
        ("emqx_xla_aot_warmups_total", "counter"),
        ("emqx_xla_recompiles_at_serve_total", "counter"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"


def test_transfer_buffers_family_lint():
    """`emqx_xla_transfer_buffers_total` (buffers the match launches moved
    across the host-device link) rides the scrape as a counter: two per
    hash batch, the packed topics in and the packed result out."""
    broker = _scraped_broker()
    tel = broker.router.telemetry
    assert tel.counters["dispatch_batches_total"] >= 1
    assert tel.counters["transfer_buffers_total"] == (
        2 * tel.counters["dispatch_batches_total"]
    )
    text = prometheus_text(broker, "n1@host")
    assert _lint(text).get("emqx_xla_transfer_buffers_total") == "counter"
    n = tel.counters["transfer_buffers_total"]
    assert f'emqx_xla_transfer_buffers_total{{node="n1@host"}} {n}' in text


@pytest.mark.parametrize("name, want", [
    ("match_device_topics_total", 8),  # the 8 uncached topics of one hash batch
    ("match_device_pairs_total", 8),  # each matched by k{i}/+/v/# alone
])
def test_match_device_pair_families_lint(name, want):
    """`emqx_xla_match_device_{topics,pairs}_total` (topics the device
    hash leg answered and the verified pairs it gave them) ride the
    scrape as counters."""
    broker = _scraped_broker()
    tel = broker.router.telemetry
    assert tel.counters.get("host_fallback_total", 0) == 0
    assert tel.counters[name] == want
    text = prometheus_text(broker, "n1@host")
    assert _lint(text).get(f"emqx_xla_{name}") == "counter"
    assert f'emqx_xla_{name}{{node="n1@host"}} {want}' in text


def test_shard_fault_and_failover_families_lint():
    """ISSUE-11 families: the shard-scoped injector's LABELED counter
    (emqx_xla_fault_injected_total{leg,shard}) and the shard
    failure-domain counters/gauges must render on a real driven scrape
    — injected shard faults, a suspend/overlay/resume cycle, and a
    live evacuate/rebalance on an N-1 mesh — and pass the same lint."""
    import jax

    from emqx_tpu.chaos.faults import DeviceFaultInjector, DeviceLinkError
    from emqx_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(n_dp=1, n_sub=4, devices=jax.devices()[:4])
    broker = Broker(mesh=mesh)
    for i in range(4):
        s, _ = broker.open_session(f"c{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, f"q/{i}/+", SubOpts(qos=0))
    r = broker.router
    topics = [f"q/{i}/v" for i in range(4)]
    r.match_filters_batch(topics)  # warm device path

    # shard-targeted faults feed the labeled ledger deterministically
    inj = DeviceFaultInjector(seed=11).install(r)
    inj.fail_transient(2, legs=("match_begin",), shards=[1])
    for _ in range(2):
        try:
            inj.check("match_begin")
        except DeviceLinkError:
            pass
    inj.fail_sticky(shards=[2])
    try:
        inj.check("sync")
    except DeviceLinkError:
        pass
    inj.heal()

    # suspend one shard (host overlay serves its slice), then run a
    # real evacuate -> N-1 device serve -> rebalance-back cycle
    assert r.suspend_shard(0)
    r.match_filters_batch(topics)
    r.resume_shard(0)
    assert r.evacuate_shard(1)
    r.match_filters_batch(topics)
    assert r.rebalance_shard(1)

    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_fault_injected_total", "counter"),
        ("emqx_xla_chaos_device_faults_total", "counter"),
        ("emqx_xla_shard_suspends_total", "counter"),
        ("emqx_xla_shard_resumes_total", "counter"),
        ("emqx_xla_shard_overlay_total", "counter"),
        ("emqx_xla_shard_evacuations_total", "counter"),
        ("emqx_xla_shard_rebalances_total", "counter"),
        ("emqx_xla_shards_suspended", "gauge"),
        ("emqx_xla_shards_lost", "gauge"),
        ("emqx_xla_mesh_shards", "gauge"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the labeled samples carry per-(leg,shard) attribution
    assert re.search(
        r'emqx_xla_fault_injected_total\{node="n1@host",'
        r'leg="match_begin",shard="1"\} 2(\.0)?$',
        text,
        re.M,
    ), text
    assert re.search(
        r'emqx_xla_fault_injected_total\{node="n1@host",'
        r'leg="sync",shard="2"\} 1(\.0)?$',
        text,
        re.M,
    )
    # full mesh restored by the end of the drive
    m = re.search(r'emqx_xla_mesh_shards\{node="n1@host"\} (\d+)', text)
    assert m and int(m.group(1)) == 4
    assert re.search(r'emqx_xla_shards_lost\{node="n1@host"\} 0', text)


def test_ds_crash_consistency_families_lint(tmp_path):
    """ISSUE-12 families: the durable tier's `emqx_ds_*` ledger must
    render on a scrape driven through a REAL fault walk — an injected
    ENOSPC that fail-stops a shard, a torn-tail reopen, and a
    probe-verified recovery — and pass the same exposition lint."""
    import pytest

    from emqx_tpu.broker.message import Message as Msg
    from emqx_tpu.chaos.faults import DiskFaultInjector
    from emqx_tpu.ds.api import Db
    from emqx_tpu.ds.storage import ShardFailedError

    inj = DiskFaultInjector(seed=3).install()
    try:
        db = Db("messages", data_dir=str(tmp_path), n_shards=1,
                buffer_flush_ms=1000)
        db.store_batch(
            [Msg(topic="t/a", payload=b"%d" % i, from_client="c")
             for i in range(5)]
        )
        inj.fail_sticky("enospc", legs=("append",), paths=("messages",))
        with pytest.raises(ShardFailedError):
            db.store_batch([Msg(topic="t/a", payload=b"x", from_client="c")])
        inj.heal()
        # scrape WHILE failed: the read-only gauge is up
        text = prometheus_text(_scraped_broker(), "n1@host")
        assert re.search(
            r'emqx_ds_shard_read_only\{node="n1@host"\} 1(\.0)?$', text, re.M
        )
        # torn tail + recovery drive the replay counters
        db.kill()
        DiskFaultInjector.tear_tail(str(tmp_path / "messages" / "shard_0.kv"))
        db = Db("messages", data_dir=str(tmp_path), n_shards=1,
                buffer_flush_ms=1000)
        assert not db.failed_shards()
        db.close()
    finally:
        inj.heal()
        inj.uninstall()

    text = prometheus_text(_scraped_broker(), "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_ds_wal_torn_records_total", "counter"),
        ("emqx_ds_wal_crc_failures_total", "counter"),
        ("emqx_ds_wal_replayed_records_total", "counter"),
        ("emqx_ds_wal_upgraded_files_total", "counter"),
        ("emqx_ds_shard_failures_total", "counter"),
        ("emqx_ds_shard_recoveries_total", "counter"),
        ("emqx_ds_shard_read_only", "gauge"),
        ("emqx_ds_recovery_last_ms", "gauge"),
        ("emqx_ds_fault_injected_total", "counter"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the fault ledger carries per-leg attribution (the sticky ENOSPC
    # fired on the append leg), and the counters saw the walk
    assert re.search(
        r'emqx_ds_fault_injected_total\{node="n1@host",leg="append"\} \d+',
        text,
    )
    m = re.search(
        r'emqx_ds_wal_torn_records_total\{node="n1@host"\} (\d+)', text
    )
    assert m and int(m.group(1)) >= 1
    m = re.search(
        r'emqx_ds_shard_failures_total\{node="n1@host"\} (\d+)', text
    )
    assert m and int(m.group(1)) >= 1
    # the shard came back: nothing read-only on the final scrape
    assert re.search(
        r'emqx_ds_shard_read_only\{node="n1@host"\} 0(\.0)?$', text, re.M
    )


async def test_cluster_selfheal_families_lint():
    """ISSUE-13 families: every emqx_cluster_* family the split-brain
    failure domain exports must render on a real driven scrape — a
    3-node walk through silent replica drift (anti-entropy repair), a
    one-way blackhole (asymmetry), and a full partition with a
    conflicting registry claim healed by autoheal — and pass the lint.
    Never hand-set counters."""
    from emqx_tpu.chaos.faults import ReplicaDriftInjector
    from emqx_tpu.cluster import ClusterNode
    from emqx_tpu.cluster.metrics import CLUSTER_METRICS

    async def wait_until(pred, timeout=30.0, msg="condition"):
        loop = asyncio.get_running_loop()
        deadline = loop.time() + timeout
        while not pred():
            assert loop.time() < deadline, f"timeout waiting for {msg}"
            await asyncio.sleep(0.02)

    def sess(node, cid):
        s, _ = node.broker.open_session(cid, clean_start=True)
        s.outgoing_sink = lambda pkts: None
        return s

    c0 = CLUSTER_METRICS.snapshot()
    nodes, addrs = [], []
    for i in range(3):
        n = ClusterNode(
            f"n{i}", heartbeat_interval=0.05, miss_threshold=2
        )
        addrs.append(await n.start())
        nodes.append(n)
    a, b, c = nodes
    for n in (b, c):
        await n.join(addrs[0])
    try:
        # leg 1 — silent drift: b ACKs but drops one op batch; the
        # digest exchange repairs it (antientropy_* counters). Let the
        # join-time member_up resync drain first — it bypasses the
        # wrapped push and would repair the drift honestly
        await wait_until(
            lambda: not a._resync and not b._resync and not c._resync,
            msg="join-time resync drained",
        )
        inj = ReplicaDriftInjector(b)
        inj.drop_next(1)
        a.broker.subscribe(
            sess(a, "lint-w"), "lint/drift/+", SubOpts(qos=0)
        )
        await wait_until(
            lambda: inj.dropped_batches >= 1, msg="drop injection"
        )
        inj.uninstall()
        await wait_until(
            lambda: "n0" in b.cluster_router.match_routes("lint/drift/x"),
            msg="anti-entropy repair",
        )
        # leg 2 — one-way blackhole: a drops frames from c; c declares
        # a down, a counts the asymmetry (asymmetry/suspect/nodedown)
        await wait_until(
            lambda: tuple(c.rpc.listen_addr) in a.rpc._addr_node,
            msg="hello seen",
        )
        a.rpc.partition(c.rpc.listen_addr, direction="in")
        await wait_until(
            lambda: "n2" in a.membership.asym_peers
            and "n0" not in c.membership.members,
            msg="asymmetry detection",
        )
        a.rpc.heal()
        await wait_until(
            lambda: "n0" in c.membership.members,
            msg="one-way heal",
        )
        # leg 3 — full split with a conflicting claim: c goes minority
        # (partition/minority), the duplicate registry claim resolves
        # on heal (heal/autoheal_rejoin/registry_conflicts)
        sess(a, "lint-dup")
        for o in (a, b):
            c.rpc.partition(o.rpc.listen_addr)
            o.rpc.partition(c.rpc.listen_addr)
        await wait_until(
            lambda: c.membership.minority, msg="minority declaration"
        )
        sess(c, "lint-dup")
        for n in nodes:
            n.rpc.heal()
        await wait_until(
            lambda: not c.membership.needs_rejoin
            and "n2" in a.membership.members
            and c.registry.get("lint-dup") == "n0",
            msg="autoheal + conflict resolution",
        )
    finally:
        for n in nodes:
            await n.stop()

    c1 = CLUSTER_METRICS.snapshot()
    for ctr in (
        "suspect_total",
        "nodedown_total",
        "partition_total",
        "heal_total",
        "autoheal_rejoin_total",
        "asymmetry_total",
        "antientropy_checks_total",
        "antientropy_divergence_total",
        "antientropy_repairs_total",
        "registry_conflicts_total",
    ):
        assert c1[ctr] > c0.get(ctr, 0), f"{ctr} did not move"

    text = prometheus_text(Broker(), "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_cluster_suspect_total", "counter"),
        ("emqx_cluster_nodedown_total", "counter"),
        ("emqx_cluster_partition_total", "counter"),
        ("emqx_cluster_heal_total", "counter"),
        ("emqx_cluster_autoheal_rejoin_total", "counter"),
        ("emqx_cluster_asymmetry_total", "counter"),
        ("emqx_cluster_antientropy_checks_total", "counter"),
        ("emqx_cluster_antientropy_divergence_total", "counter"),
        ("emqx_cluster_antientropy_repairs_total", "counter"),
        ("emqx_cluster_registry_conflicts_total", "counter"),
        ("emqx_cluster_member_state", "gauge"),
        ("emqx_cluster_minority", "gauge"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # per-peer detector gauge carries the peer label
    assert re.search(
        r'emqx_cluster_member_state\{node="n1@host",peer="n\d+"\} \d',
        text,
    )


def test_retained_rule_where_and_json_families_lint():
    """ISSUE-14 families: the retained-match device leg
    (emqx_xla_retained_* + emqx_retainer_*), the batched-WHERE leg
    (emqx_xla_rule_where_*), and the JSON codec seam (emqx_json_*)
    must all render on ONE scrape driven through real work — a device
    retained read with a host escalation, a windowed publish_batch
    with vectorized/fallback/uncompiled rows, and codec traffic — and
    pass the same exposition lint."""
    from emqx_tpu import jsonc
    from emqx_tpu.rules import RuleEngine

    broker = Broker()
    tel = broker.router.telemetry

    # --- retained leg: device read + deep-filter host escalation +
    # an expiry purge (read-repair) so every counter moves
    ret = broker.retainer
    ret.enable_device(telemetry=tel)
    for n in ("rm/a", "rm/b", "rm/c/d"):
        broker.publish(Message(topic=n, payload=b"v", retain=True))
    broker.publish(
        Message(
            topic="rm/ttl", payload=b"v", retain=True, timestamp=100.0,
            props={"message_expiry_interval": 1},
        )
    )
    deep = "/".join("w" for _ in range(20))  # past max_levels: host plan
    out = ret.retained_read_finish(
        ret.retained_read_begin(["rm/+", deep + "/#"], now=200.0)
    )
    assert sorted(m.topic for m in out[0]) == ["rm/a", "rm/b"]
    assert ret.expired_total == 1
    assert tel.counters.get("retained_device_reads_total", 0) >= 1
    assert tel.counters.get("retained_host_fallback_total", 0) >= 1

    # --- batched WHERE leg: one window with vectorized rows, an
    # OTHER-lane fallback row, and an uncompilable rule
    eng = RuleEngine(broker)
    eng.batch_where_enabled = True
    eng.install(broker.hooks)
    eng.create_rule("lv", 'SELECT qos FROM "rw/#" WHERE payload.flag')
    eng.create_rule(
        "lu", "SELECT qos FROM \"rw/#\" WHERE lower(topic) = 'rw/0'"
    )
    broker.publish_batch(
        [
            Message(topic="rw/0", payload=b'{"flag": true}'),
            Message(topic="rw/1", payload=b'{"flag": [1]}'),  # fallback
        ]
    )
    assert tel.counters.get("rule_where_batch_rows_total", 0) >= 2
    assert tel.counters.get("rule_where_fallback_rows_total", 0) >= 1
    assert tel.counters.get("rule_where_uncompiled_rows_total", 0) >= 2

    # --- codec leg: the publishes above already rode the seam
    # (payload.* decode); make one explicit call each way too
    jsonc.loads(jsonc.dumps({"k": 1}))

    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_retainer_entries", "gauge"),
        ("emqx_retainer_expired_total", "counter"),
        ("emqx_retainer_dropped_full_total", "counter"),
        ("emqx_xla_retained_device_reads_total", "counter"),
        ("emqx_xla_retained_host_fallback_total", "counter"),
        ("emqx_xla_retained_probe_seconds", "histogram"),
        ("emqx_xla_rule_where_batch_rows_total", "counter"),
        ("emqx_xla_rule_where_fallback_rows_total", "counter"),
        ("emqx_xla_rule_where_uncompiled_rows_total", "counter"),
        ("emqx_xla_rule_where_batch_seconds", "histogram"),
        ("emqx_json_native_enabled", "gauge"),
        ("emqx_json_native_loads_total", "counter"),
        ("emqx_json_native_dumps_total", "counter"),
        ("emqx_json_fallback_loads_total", "counter"),
        ("emqx_json_fallback_dumps_total", "counter"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the retained store gauge carries the live entry count
    m = re.search(r'emqx_retainer_entries\{node="n1@host"\} (\d+)', text)
    assert m and int(m.group(1)) == len(ret)
    # no serve-time retraces anywhere in the drive
    assert tel.counters.get("recompiles_at_serve_total", 0) == 0


def test_mesh_scaling_families_lint():
    """ISSUE-15 families: the device-side combine histogram, the fused
    one-dispatch sync gauge, the small-table degrade counter, and the
    per-shard transfer ledger must render on a real driven scrape — a
    full sharded upload, churn riding the fused row+slot scatter, and a
    degrade/upgrade flip on the admission knob — and pass the lint."""
    import jax

    from emqx_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(n_dp=1, n_sub=4, devices=jax.devices()[:4])
    broker = Broker(mesh=mesh)
    for i in range(32):
        s, _ = broker.open_session(f"c{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, f"m/{i}/+/v/#", SubOpts(qos=0))
    r = broker.router
    tel = r.telemetry
    topics = [f"m/{i}/a/v/w" for i in range(8)]
    # full upload: every shard receives its row slice (labeled ledger),
    # and the device-side combine times the cross-shard reduction
    r.match_filters_batch(topics)

    # native delete + re-add dirties rows AND hash slots without a
    # rebuild, so the next sync rides the fused one-dispatch scatter
    r.delete_route("m/3/+/v/#", "c3")
    r.add_route("m/3/+/v/#", "c3")
    r.match_filters_batch(topics)
    assert tel.gauges.get("mesh_sync_batch_rows", 0) > 0

    # admission-knob flip: degrade to single-device, serve, upgrade back
    dt = r.device_table
    dt.min_rows_per_shard = 1 << 30
    r.match_filters_batch(topics)
    assert dt.degraded
    dt.min_rows_per_shard = 0
    r.match_filters_batch(topics)
    assert not dt.degraded

    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_mesh_combine_seconds", "histogram"),
        ("emqx_xla_mesh_sync_batch_rows", "gauge"),
        ("emqx_xla_mesh_degraded_single_device_total", "counter"),
        ("emqx_xla_mesh_degraded_single_device", "gauge"),
        ("emqx_xla_mesh_shard_transfer_rows_total", "counter"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # the transfer ledger carries per-shard attribution for every shard
    for shard in range(4):
        assert re.search(
            r'emqx_xla_mesh_shard_transfer_rows_total\{node="n1@host",'
            rf'shard="{shard}"\}} [1-9]',
            text,
            re.M,
        ), f"shard {shard} missing from transfer ledger"
    # exactly one degrade flip, and the mesh is back to full service
    assert tel.counters["mesh_degraded_single_device_total"] == 1
    assert re.search(
        r'emqx_xla_mesh_degraded_single_device\{node="n1@host"\} 0', text
    )


def test_mesh_scope_families_lint():
    """ISSUE-20 families: the mesh microscope's per-stage decomposition
    histogram (every one of the six sub-stages must appear as a label),
    the dispatch-wall and combine-occupancy histograms, the
    decomposition self-check counters/gauge, the collective-cost
    ledger, the sampled shard skew, and the per-chip ring occupancy —
    all rendered from a REAL driven 4-device scrape and passed through
    the same exposition lint. Never hand-poked."""
    import jax

    from emqx_tpu.obs.mesh_scope import MESH_STAGES, MeshScope
    from emqx_tpu.parallel import mesh as mesh_mod

    mesh = mesh_mod.make_mesh(n_dp=1, n_sub=4, devices=jax.devices()[:4])
    broker = Broker(mesh=mesh)
    r = broker.router
    tel = r.telemetry
    sc = MeshScope(telemetry=tel, sample_n=1)
    r.device_table.scope = sc
    for i in range(32):
        s, _ = broker.open_session(f"mc{i}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        broker.subscribe(s, f"m/{i}/+/v/#", SubOpts(qos=0))
    # warmup pre-warms the combine probe (warmup_escalated tail), so
    # the sampled splits below never retrace at serve time
    r.warmup_shapes(max_batch=16)
    tel.mark_serving()
    topics = [f"m/{i}/a/v/w" for i in range(8)]
    for _ in range(3):
        r.match_filters_batch(topics)

    text = prometheus_text(broker, "n1@host")
    types = _lint(text)
    for fam, kind in (
        ("emqx_xla_mesh_stage_seconds", "histogram"),
        ("emqx_xla_mesh_dispatch_wall_seconds", "histogram"),
        ("emqx_xla_mesh_combine_occupancy", "histogram"),
        ("emqx_xla_mesh_decomp_in_band_total", "counter"),
        ("emqx_xla_mesh_decomp_out_of_band_total", "counter"),
        ("emqx_xla_mesh_collective_gather_bytes_total", "counter"),
        ("emqx_xla_mesh_scope_samples_total", "counter"),
        ("emqx_xla_mesh_scope_split_skipped_total", "counter"),
        ("emqx_xla_mesh_decomp_last_ratio", "gauge"),
        ("emqx_xla_mesh_shard_skew_hits", "gauge"),
        ("emqx_xla_mesh_ring_occupancy_ratio", "gauge"),
    ):
        assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
    # every sub-stage of the taxonomy is a live label on the scrape
    # (the static gate's no-orphan-stage leg leans on this)
    for stage in MESH_STAGES:
        assert re.search(
            r'emqx_xla_mesh_stage_seconds_bucket\{node="n1@host",'
            rf'nchips="4",stage="{stage}",le=',
            text,
        ), f"stage {stage} missing from the scrape"
    # per-chip attribution for all four serving chips
    for d in jax.devices()[:4]:
        assert re.search(
            r'emqx_xla_mesh_ring_occupancy_ratio\{node="n1@host",'
            rf'chip="{int(d.id)}"\}}',
            text,
        ), f"chip {d.id} missing from ring occupancy"
    # the decomposition held on every dispatch and sampling was live
    m = re.search(
        r'emqx_xla_mesh_decomp_in_band_total\{node="n1@host"\} (\d+)', text
    )
    assert m and int(m.group(1)) > 0
    m = re.search(
        r'emqx_xla_mesh_scope_samples_total\{node="n1@host"\} (\d+)', text
    )
    assert m and int(m.group(1)) > 0
    # sampled probes never retraced at serve time
    assert tel.counters.get("recompiles_at_serve_total", 0) == 0


async def test_delivery_stage_ring_and_profiler_families_lint(tmp_path):
    """ISSUE-17 families: the queue-stage sub-decomposition
    (emqx_xla_delivery_*), the ring slot timeline
    (emqx_xla_ring_slot_span_seconds), the sampling profiler counters/gauges
    (emqx_xla_profiler_*), and the event-loop lag histogram
    (emqx_xla_loop_lag_seconds) must all render on ONE scrape driven
    through a REAL dense-sampled engine run — mixed QoS so every one
    of the six sub-stages records, two publish waves separated by an
    idle window — and pass the same
    exposition lint. Never hand-poked counters."""
    from emqx_tpu.obs import Observability
    from emqx_tpu.obs.profiler import DELIVERY_STAGES

    broker = Broker()
    broker._fanout_min_fan = 0
    obs = Observability(
        broker,
        node_name="n1@host",
        trace_dir=str(tmp_path / "t"),
        flight_dir=str(tmp_path / "f"),
    )
    try:
        obs.sentinel.sample_n = 1  # every publish carries a span
        assert obs.loop_lag.start()  # async context: ticker runs
        obs.profiler.arm_for(10.0)
        eng = broker.enable_dispatch_engine(queue_depth=4, deadline_ms=0.2)
        for i in range(8):
            s, _ = broker.open_session(f"c{i}", clean_start=True)
            s.outgoing_sink = lambda pkts: None
            # half QoS0 (session_write fast path), half QoS1
            # (ack_sweep inflight bookkeeping)
            broker.subscribe(s, "dl/+/v", SubOpts(qos=0 if i < 4 else 1))
        topics = [f"dl/{i}/v" for i in range(6)]
        await asyncio.gather(
            *[eng.publish(Message(topic=t, payload=b"x")) for t in topics]
        )
        await asyncio.sleep(0.15)  # the ring idles between the waves
        await asyncio.gather(
            *[eng.publish(Message(topic=t, payload=b"y")) for t in topics]
        )
        await eng.stop()
        obs.profiler.stop()
        st = broker.sentinel
        # all six sub-stages recorded on the live path
        assert sorted(st.delivery_hist) == sorted(DELIVERY_STAGES)
        # the decomposition self-check held for (nearly) every span
        snap = st.decomposition_snapshot()
        assert snap["in_band"] >= 8
        assert snap["in_band_ratio"] >= 0.75
        # the ring saw multiple slots and the idle window
        ring = eng.ring_status()
        assert ring["slots_total"] >= 2

        text = obs.prometheus_text()
        types = _lint(text)
        for fam, kind in (
            ("emqx_xla_delivery_stage_seconds", "histogram"),
            ("emqx_xla_delivery_fan", "histogram"),
            ("emqx_xla_delivery_decomp_in_band_total", "counter"),
            ("emqx_xla_delivery_decomp_out_of_band_total", "counter"),
            ("emqx_xla_delivery_decomp_last_ratio", "gauge"),
            ("emqx_xla_ring_slot_span_seconds", "histogram"),
            ("emqx_xla_loop_lag_seconds", "histogram"),
            ("emqx_xla_profiler_samples_total", "counter"),
            ("emqx_xla_profiler_cpu_samples_total", "counter"),
            ("emqx_xla_profiler_overflow_total", "counter"),
            ("emqx_xla_profiler_running", "gauge"),
            ("emqx_xla_profiler_unique_stacks", "gauge"),
        ):
            assert types.get(fam) == kind, f"{fam}: {types.get(fam)}"
        # the stage family is cumulative per stage label, every label
        # is a declared sub-stage, and every declared sub-stage renders
        fam = "emqx_xla_delivery_stage_seconds"
        stages = {}
        for line in text.splitlines():
            if line.startswith(f"{fam}_bucket{{"):
                labels = line[line.index("{") + 1 : line.index("}")]
                stage = re.search(r'stage="([^"]+)"', labels).group(1)
                stages.setdefault(stage, []).append(
                    int(line.rsplit(" ", 1)[1])
                )
        assert sorted(stages) == sorted(DELIVERY_STAGES)
        for stage, counts in stages.items():
            assert counts == sorted(counts), f"{stage}: not cumulative"
            assert counts[-1] >= 1, f"{stage}: never observed"
        # the fan histogram counted every sampled publish's fan size —
        # minus the first two spans the warmup exclusion kept out of
        # the serve stats (broker.perf.tpu_warmup_sample_skip)
        assert st.warmup_skipped == 2
        m = re.search(
            r'emqx_xla_delivery_fan_count\{node="n1@host"\} (\d+)', text
        )
        assert m and int(m.group(1)) == 10
        # fan is a COUNT, not a latency (ISSUE 19 satellite): the
        # snapshot must be unitless (no *_ms keys) and the exposition
        # _sum must render as a plain number, not nanosecond-padded
        # seconds
        fan_snap = st.fan_hist.snapshot()
        assert not any(k.endswith("_ms") for k in fan_snap), fan_snap
        assert {"p50", "p99", "p999"} <= set(fan_snap)
        m = re.search(
            r'emqx_xla_delivery_fan_sum\{node="n1@host"\} (\S+)', text
        )
        assert m and not re.match(r"^\d+\.\d{9}$", m.group(1)), (
            "fan _sum rendered with seconds-style nanosecond padding: "
            f"{m.group(1) if m else None}"
        )
        # the profiler took samples while armed over the drive
        m = re.search(
            r'emqx_xla_profiler_samples_total\{node="n1@host"\} (\d+)',
            text,
        )
        assert m and int(m.group(1)) >= 1
    finally:
        obs.stop()
