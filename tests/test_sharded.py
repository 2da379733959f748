"""Multi-chip sharded match/update on the virtual 8-device CPU mesh.

Validates the tp/dp layout (table over 'sub', topics over 'dp'), the
XLA-inserted psum for counts, and the shard-local delta scatter —
without TPU hardware, per the reference's cth_cluster pattern of
faking a cluster on one host (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from emqx_tpu.ops import match as M
from emqx_tpu.ops.table import FilterTable
from emqx_tpu.parallel import mesh as mesh_mod
from emqx_tpu.parallel.sharded_match import make_sharded_kernels


@pytest.fixture(scope="module")
def mesh8():
    assert len(jax.devices()) == 8, "conftest must fake 8 CPU devices"
    return mesh_mod.make_mesh(n_dp=2, n_sub=4)


def build_table(n=64):
    t = FilterTable(max_levels=4, capacity=1024)
    rows = {}
    for i in range(n):
        rows[i] = t.add(f"a/{i}/+")
    t.add("a/#")
    t.add("$SYS/#")
    return t, rows


def test_sharded_counts_and_packed_match_host(mesh8):
    table, _rows = build_table()
    topics = [f"a/{i}/x" for i in range(20)] + ["$SYS/y", "b", "a"]
    enc = M.encode_topics(table.vocab, topics, table.max_levels).fields()

    match_counts, match_packed, _ = make_sharded_kernels(mesh8)
    f_dev = mesh_mod.put_filters(table.snapshot(), mesh8)
    t_dev = mesh_mod.put_topics(enc, mesh8)

    counts = np.asarray(match_counts(f_dev, t_dev))[: len(topics)]
    packed = np.asarray(match_packed(f_dev, t_dev))[: len(topics)]

    expected = M.oracle_match_rows(table, topics)
    assert list(counts) == [len(e) for e in expected]
    for i in range(len(topics)):
        assert np.array_equal(M.unpack_indices(packed[i]), expected[i]), topics[i]


def test_sharded_apply_delta(mesh8):
    table, rows = build_table()
    match_counts, _, apply_delta = make_sharded_kernels(mesh8)
    f_dev = mesh_mod.put_filters(table.snapshot(), mesh8)
    table.drain_dirty()  # snapshot upload covered the initial adds

    # host-side mutation: remove a/0/+, add b/#
    table.remove(rows[0])
    new_row = table.add("b/#")
    dirty = table.drain_dirty()

    k = 16  # fixed-size padded delta batch
    idx = np.empty(k, np.int32)
    idx[: len(dirty)] = dirty
    idx[len(dirty) :] = dirty[-1]
    f_dev = apply_delta(
        f_dev,
        jnp.asarray(idx.reshape(1, k)),
        jnp.asarray(table.words[idx].reshape(1, k, -1)),
        jnp.asarray(table.prefix_len[idx].reshape(1, k)),
        jnp.asarray(table.has_hash[idx].reshape(1, k)),
        jnp.asarray(table.root_wild[idx].reshape(1, k)),
        jnp.asarray(table.active[idx].reshape(1, k)),
    )

    topics = ["a/0/x", "b/z", "a/5/x"]
    enc = M.encode_topics(table.vocab, topics, table.max_levels).fields()
    t_dev = mesh_mod.put_topics(enc, mesh8)
    counts = np.asarray(match_counts(f_dev, t_dev))[: len(topics)]
    expected = M.oracle_match_rows(table, topics)
    assert list(counts) == [len(e) for e in expected]
    # and the specific new row is live on whatever shard owns it
    packed_fn = make_sharded_kernels(mesh8)[1]
    packed = np.asarray(packed_fn(f_dev, t_dev))
    assert new_row in M.unpack_indices(packed[1])


def test_mesh_defaults():
    m = mesh_mod.make_mesh()
    assert m.shape[mesh_mod.DP_AXIS] * m.shape[mesh_mod.SUB_AXIS] == 8
    assert m.shape[mesh_mod.DP_AXIS] == 1  # default: shard the table
    m2 = mesh_mod.make_mesh(n_sub=2)
    assert m2.shape[mesh_mod.DP_AXIS] == 4


def test_topic_padding(mesh8):
    table, _ = build_table(8)
    topics = ["a/1/x", "a/2/x", "a/3/x"]  # 3 does not divide dp=2
    enc = M.encode_topics(table.vocab, topics, table.max_levels).fields()
    t_dev = mesh_mod.put_topics(enc, mesh8)
    assert t_dev.ids.shape[0] == 4
    match_counts, _, _ = make_sharded_kernels(mesh8)
    f_dev = mesh_mod.put_filters(table.snapshot(), mesh8)
    counts = np.asarray(match_counts(f_dev, t_dev))
    assert list(counts[:3]) == [2, 2, 2]  # a/i/+ and a/#
    assert counts[3] == 0  # the pad row matches nothing


# --- mesh-integrated broker path (VERDICT r1 item 5) --------------------


def test_mesh_router_matches_oracle(mesh8):
    from emqx_tpu.models.router import Router

    r = Router(max_levels=4, mesh=mesh8)
    for i in range(40):
        r.add_route(f"a/{i}/+", f"c{i}")
    r.add_route("a/#", "call")
    r.add_route("b/exact", "cex")
    topics = [f"a/{i}/x" for i in range(10)] + ["b/exact", "zzz"]
    got = r.match_batch(topics)
    # oracle: the single-topic host path
    want = [r.match_routes(t) for t in topics]
    assert got == want
    # route churn flows through the shard_map delta scatter
    r.delete_route("a/0/+", "c0")
    r.add_route("new/+", "cn")
    got2 = r.match_batch(["a/0/x", "new/y"])
    assert got2 == [{"call"}, {"cn"}]


def test_mesh_router_escalates_on_overflow(mesh8):
    from emqx_tpu.models.router import Router

    r = Router(max_levels=4, mesh=mesh8)
    r.device_table.default_mh = 4  # force per-block overflow
    for i in range(200):
        r.add_route(f"w/{i}/#", f"c{i}")
    got = r.match_batch(["w/5/x"])
    assert got == [{"c5"}]
    wide = r.match_batch([f"w/{i}/t" for i in range(64)])
    assert all(g == {f"c{i}"} for i, g in enumerate(wide))


def test_mesh_broker_publish_batch(mesh8):
    """ClusterBroker.publish_batch end-to-end on the mesh router."""
    from emqx_tpu.broker.message import Message
    from emqx_tpu.broker.packet import SubOpts
    from emqx_tpu.cluster.node import ClusterBroker
    from emqx_tpu.models.router import Router

    b = ClusterBroker()
    b.router = Router(max_levels=8, mesh=mesh8)
    outs = {}
    for i in range(30):
        s, _ = b.open_session(f"c{i}", True)
        b.subscribe(s, f"room/{i}/+", SubOpts(qos=0))
        outs[f"c{i}"] = []
        s.outgoing_sink = outs[f"c{i}"].extend
    s_all, _ = b.open_session("watcher", True)
    b.subscribe(s_all, "room/#", SubOpts(qos=0))
    outs["watcher"] = []
    s_all.outgoing_sink = outs["watcher"].extend
    msgs = [Message(topic=f"room/{i}/t", payload=b"x") for i in range(30)]
    counts = b.publish_batch(msgs)
    assert counts == [2] * 30  # per-room subscriber + watcher
    assert all(len(outs[f"c{i}"]) == 1 for i in range(30))
    assert len(outs["watcher"]) == 30


# --- the PRODUCTION hash kernel on the mesh (VERDICT r2 #2) -----------


def oracle_rows(table, rows_of, topics):
    """Row sets straight from the pure oracle."""
    import emqx_tpu.ops.topic as T

    out = []
    for t in topics:
        tw = T.words(t)
        out.append(
            {r for f, r in rows_of.items() if T.match(tw, T.words(f))}
        )
    return out


def test_mesh_hash_kernel_matches_oracle_with_churn(mesh8):
    """Router(mesh=...) must run the cuckoo hash kernel (not the dense
    demo), stay oracle-exact through add/delete churn, and keep the
    dense kernel only for residual rows."""
    import random

    from emqx_tpu.models.router import Router
    from emqx_tpu.ops import topic as T

    rng = random.Random(31)
    r = Router(max_levels=6, mesh=mesh8)
    assert r.index is not None, "mesh Router must carry the class index"

    live = {}
    for i in range(300):
        f = rng.choice(
            [f"s/{i}/+", f"s/{i}/#", f"+/x/{i}", f"s/{i}/t/{i % 7}", "#"]
        )
        r.add_route(f, f"d{i}")
        live.setdefault(f, set()).add(f"d{i}")

    topics = [f"s/{rng.randrange(320)}/t/{rng.randrange(9)}" for _ in range(40)]
    topics += [f"q/x/{rng.randrange(320)}" for _ in range(10)]
    topics += ["$SYS/broker", "s/5/t"]

    def check():
        got = r.match_batch(topics)
        routes = r.routes()
        for t, g in zip(topics, got):
            tw = T.words(t)
            want = {d for (f, d) in routes if T.match(tw, T.words(f))}
            assert g == want, (t, g, want)

    check()

    # churn: delete a third, add fresh filters, re-check (exercises the
    # shard_map slot-delta scatter, not just the full upload)
    victims = rng.sample(sorted(live), len(live) // 3)
    for f in victims:
        for d in sorted(live[f]):
            r.delete_route(f, d)
        del live[f]
    for i in range(40):
        f = f"n/{i}/+"
        r.add_route(f, f"nd{i}")
    topics.extend(f"n/{i}/z" for i in range(0, 40, 7))
    check()

    # the hash index carries the classed rows; residuals only overflow
    assert len(r.index) > 0
    assert not r.index.residual_rows


def test_sharded_100k_routes_churn_growth_oracle():
    """VERDICT r3 weak #4: the sharded cuckoo path at a scale where
    bucket ranges straddle shards under churn and rebuild growth —
    100k routes on the 8-device mesh, device sync between growth
    phases, oracle equality throughout, and the n_buckets % n_sub
    invariant held at every checkpoint."""
    from emqx_tpu.models.router import Router

    mesh = mesh_mod.make_mesh(n_dp=2, n_sub=4)
    r = Router(max_levels=8, mesh=mesh)
    N = 100_000
    pairs = [
        (f"s/{i % 997}/d{i}/+/#" if i % 3 else f"exact/{i}", f"n{i % 11}")
        for i in range(N)
    ]
    topics = [f"s/{i % 997}/d{i * 3 + 1}/x/y" for i in range(256)]
    topics += [f"exact/{i * 7}" for i in range(64)]

    def check(ts):
        got = [sorted(set(o)) for o in r.match_filters_batch(ts)]
        want = [sorted(set(r.match_filters(t))) for t in ts]
        assert got == want
        assert r.index.n_buckets % 4 == 0  # sub-shard divisibility

    # phase 1: 30k -> device sync -> growth continues to 100k (the
    # device table must survive rebuild-growth re-uploads)
    for i in range(0, 30_000, 1000):
        r.add_routes(pairs[i : i + 1000])
    buckets_a = r.index.n_buckets
    check(topics[:64])
    for i in range(30_000, N, 1000):
        r.add_routes(pairs[i : i + 1000])
    assert r.index.n_buckets > buckets_a  # growth actually happened
    check(topics)

    # phase 2: churn a third out, then a fresh wave in
    for f, d in pairs[::3]:
        r.delete_route(f, d)
    more = [(f"g2/{i % 313}/z{i}/+/#", f"n{i % 5}") for i in range(40_000)]
    for i in range(0, len(more), 1000):
        r.add_routes(more[i : i + 1000])
    check(topics + [f"g2/5/z{5 + 313 * k}/a/b" for k in range(8)])
    assert len(r.index) > 100_000


# --- shard failure domain: padded N-1 meshes + live evacuation ---------


def _oracle_check(r, topics, tag):
    got = r.match_filters_finish(r.match_filters_begin(topics))
    for t, g in zip(topics, got):
        want = sorted(r.match_filters(t))
        assert sorted(g) == want, (tag, t, sorted(g), want)


def _churn_pairs(n=300):
    pairs = [(f"a/{i}/+", f"s{i}") for i in range(n)]
    pairs += [("b/#", "sb"), ("exact/topic/x", "sx"), ("c/+/d", "scd")]
    return pairs


_CHURN_TOPICS = [f"a/{i}/z" for i in range(0, 300, 7)] + [
    "b/q/w", "exact/topic/x", "c/9/d", "no/match/here",
]


def test_non_divisible_mesh_serves_pow2_capacity():
    """shard_rows ceil-pads: a 3-way sub split must serve a pow2
    table (512 rows / 1024 buckets do NOT divide by 3) with trailing
    inert pad rows/slots — the layout every N-1 survivor mesh runs."""
    from emqx_tpu.models.router import Router

    mesh = mesh_mod.make_mesh(n_dp=1, n_sub=3, devices=jax.devices()[:3])
    assert mesh_mod.shard_rows(512, mesh) == 171  # ceil, not floor
    r = Router(mesh=mesh)
    r.add_routes(_churn_pairs())
    r.device_table.sync()
    _oracle_check(r, _CHURN_TOPICS, "mesh(1,3)")
    # churn on the padded layout: deltas target logical ids
    r.delete_routes([(f"a/{i}/+", f"s{i}") for i in range(7)])
    r.add_routes([(f"p/{i}/+", f"p{i}") for i in range(23)])
    r.device_table.sync()
    _oracle_check(
        r, _CHURN_TOPICS + [f"p/{i}/q" for i in range(23)],
        "mesh(1,3) churn",
    )


def test_evacuate_restore_oracle_exact(mesh8):
    """Live evacuation on the (2,4) mesh: losing sub column 1 drops a
    whole device COLUMN (2 chips), the survivor mesh serves the full
    table bit-identically, churn lands while degraded, and restore
    rebuilds the original layout."""
    from emqx_tpu.models.router import Router

    r = Router(mesh=mesh8)
    r.add_routes(_churn_pairs())
    r.device_table.sync()
    dt = r.device_table
    _oracle_check(r, _CHURN_TOPICS, "pre")
    assert dt.n_shards == 4 and dt.shard_gen == 0

    assert r.evacuate_shard(1)
    assert dt.lost_shards == {1}
    assert dt.n_shards == 3 and dt.shard_gen == 1
    _oracle_check(r, _CHURN_TOPICS, "N-1")
    # churn while degraded: adds + deletes flow through the survivor
    # mesh's delta scatter
    r.add_routes([(f"deg/{i}", f"d{i}") for i in range(40)])
    r.delete_routes([(f"a/{i}/+", f"s{i}") for i in range(5)])
    dt.sync()
    _oracle_check(
        r, [f"deg/{i}" for i in range(40)] + _CHURN_TOPICS, "N-1 churn"
    )

    assert r.rebalance_shard(1)
    assert not dt.lost_shards and dt.n_shards == 4
    assert dt.shard_gen == 2
    _oracle_check(r, _CHURN_TOPICS, "restored")
    # idempotence + validation edges
    assert not r.rebalance_shard(1)  # not lost
    assert not r.evacuate_shard(99)  # out of range


def test_evacuate_last_survivor_refused(mesh8):
    from emqx_tpu.models.router import Router

    r = Router(mesh=mesh8)
    r.add_routes(_churn_pairs(20))
    r.device_table.sync()
    for s in range(3):
        assert r.evacuate_shard(s)
    with pytest.raises(RuntimeError, match="no survivor"):
        r.device_table.evacuate_shard(3)
    _oracle_check(r, _CHURN_TOPICS[:10], "single survivor")
    for s in range(3):
        assert r.rebalance_shard(s)
    assert r.device_table.n_shards == 4
    _oracle_check(r, _CHURN_TOPICS[:10], "restored from 1")


def test_suspend_shard_overlay_serves_host_truth(mesh8):
    """A suspended shard's slice is corrected from host truth by the
    finish overlay while the other shards' answers pass through — and
    the whole table is never host-degraded."""
    from emqx_tpu.models.router import Router

    r = Router(mesh=mesh8)
    r.add_routes(_churn_pairs())
    r.device_table.sync()
    tel = r.telemetry
    assert r.suspend_shard(2)
    assert not r.suspend_shard(2)  # idempotent
    assert not r.device_suspended
    _oracle_check(r, _CHURN_TOPICS, "overlay")
    assert tel.counters.get("shard_overlay_total", 0) > 0
    r.resume_shard(2)
    assert not r._suspended_shards
    _oracle_check(r, _CHURN_TOPICS, "resumed")


def test_shard_ownership_maps_cover_row_and_slot(mesh8):
    from emqx_tpu.models.router import Router

    r = Router(mesh=mesh8)
    r.add_routes(_churn_pairs())
    r.device_table.sync()
    dt = r.device_table
    n_sub = 4
    for f in ("a/7/+", "b/#", "exact/topic/x"):
        owners = r._shard_owners(f)
        assert owners, f
        assert all(0 <= s < n_sub for s in owners), (f, owners)
    # a host-resident (never-added) filter has no device owner
    assert r._shard_owners("not/a/route") == set()
    # every row maps into range under the padded layout
    cap = r.table.capacity
    assert dt.shard_of_row(0) == 0
    assert dt.shard_of_row(cap - 1) == n_sub - 1


@pytest.mark.slow
def test_sharded_broker_at_scale(tmp_path):
    """ISSUE-15 acceptance: the COMPLETE broker on the full 8-device
    mesh at >=1M routes — publishes served through the device-combined
    match with the sentinel shadow audit live, shared-subscription
    groups electing members per publish, and NATIVE delete churn
    (unsubscribe -> router delete_route, no rebuild) interleaved with
    the storm waves. After every wave the full-truth sweep must be
    oracle-equal with zero silent divergence, and the whole serve
    window must stay inside the AOT-warmed shape set:
    recompiles_at_serve_total == 0 on the mesh path."""
    import asyncio

    from emqx_tpu.broker.packet import SubOpts
    from emqx_tpu.chaos import ChaosEngine

    async def go():
        eng = await ChaosEngine.standalone(
            sessions=1_000_000,
            data_dir=str(tmp_path),
            mesh=mesh_mod.make_mesh(n_dp=1, n_sub=8),
            sample_n=64,
        )
        b = eng.broker
        try:
            await eng.setup()
            assert len(b.sessions) >= 1_000_000
            # shared-subscription groups on UNIQUE real filters: when a
            # wave drops every member, the row leaves the device table
            # through the native delete path (no rebuild), and comes
            # back through the fused delta scatter
            opts = SubOpts(qos=0)
            shared = []
            for j in range(16):
                flt = f"$share/g{j}/shgrp/{j}/+"
                members = []
                for m in range(4):
                    s, _ = b.open_session(
                        f"shared-{j}-{m}", clean_start=True, cfg=eng.fleet.cfg
                    )
                    s.outgoing_sink = eng.fleet.sink
                    b.subscribe(s, flt, opts)
                    members.append(s)
                shared.append((flt, members))
            await eng.burst([f"shgrp/{j}/t" for j in range(16)])
            # warm the audit-sweep batch shape (512 groups + chaos
            # filters pads past the engine's queue-depth ladder), then
            # arm the serve-time recompile gate via the engine pass
            eng.router.warmup_shapes(max_batch=1024)
            info = b.engine.warmup()
            assert info.get("mesh_shards") == 8, info
            assert not info.get("mesh_degraded"), info
            tel = eng.router.telemetry

            for wave in range(3):
                eng.storm_start()
                await asyncio.sleep(0.8)
                # native delete churn under the live storm: one shared
                # group fully drains (device row removed) and a slice
                # of fleet sessions unsubscribe/resubscribe
                flt, members = shared[wave]
                for s in members:
                    assert b.unsubscribe(s, flt)
                for g in range(wave * 64, wave * 64 + 64):
                    cid = eng.fleet.clients[g]
                    s = b.sessions[cid]
                    f = eng.fleet.filter_of(g % eng.fleet.groups)
                    b.unsubscribe(s, f)
                    b.subscribe(s, f, opts)
                for s in members:  # the group comes back for next waves
                    b.subscribe(s, flt, opts)
                await asyncio.sleep(0.4)
                await eng.storm_stop()
                assert eng.storm_errors == 0
                # shared delivery still elects exactly one member
                deliveries = await eng.burst([f"shgrp/{wave}/t"])
                assert deliveries >= 1
                sweep = await eng.audit_sweep()
                assert sweep["silent_divergences"] == 0, (wave, sweep)
            # the shadow audit actually sampled the storm
            assert tel.counters.get("audit_total", 0) > 0
            assert tel.counters.get("recompiles_at_serve_total", 0) == 0, (
                dict(tel.counters)
            )
        finally:
            await eng.close()

    asyncio.run(go())
