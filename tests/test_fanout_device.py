"""Device-resolved fanout (ISSUE 4): the CSR destination store +
dedup/max-QoS kernel must reproduce `Broker._build_fanout_plan`
bit-identically — same dedup winner, same max-QoS tie-break, same plan
order — under interleaved subscribe/unsubscribe/publish churn, on
single-device and sharded tables, covering shared-group legs, durable/
exotic sessions, and QoS ties; plus the per-filter plan-stamp scheme:
a subscribe on filter A must NOT invalidate a cached plan for disjoint
filter B (no global-generation orphaning)."""

import asyncio

import pytest

from emqx_tpu.broker.message import Message
from emqx_tpu.broker.packet import SubOpts
from emqx_tpu.broker.pubsub import Broker
from emqx_tpu.broker.session import Session
from emqx_tpu.parallel import mesh as mesh_mod


def _broker(**kw):
    b = Broker(**kw)
    b._fanout_min_fan = 0  # device path even for tiny fans
    return b


def _sub(b, cid, flt, qos=0):
    s = b.sessions.get(cid)
    if s is None:
        s, _ = b.open_session(cid, True)
        s.outgoing_sink = lambda pkts: None
    b.subscribe(s, flt, SubOpts(qos=qos))
    return s


def _plans(b, topic):
    """(device plan, host oracle plan) for one topic's matched set."""
    pairs = b.router.match_pairs(topic)
    key = tuple(f for f, _ in pairs)
    h = b.router.resolve_fanout_begin(key, min_fan=0)
    assert h is not None, f"device path refused {key}"
    return b.router.resolve_fanout_finish(h), b._build_fanout_plan(pairs)


def _assert_identical(b, topic):
    dev, orc = _plans(b, topic)
    assert dev == orc, f"{topic}: device {dev} != oracle {orc}"


# --- oracle parity ---------------------------------------------------------


def test_device_plan_is_bit_identical_to_oracle():
    b = _broker()
    for i in range(24):
        _sub(b, f"c{i}", "room/+/t", qos=i % 3)
    for i in range(12):
        _sub(b, f"c{i}", "room/#", qos=(i + 1) % 3)
    _assert_identical(b, "room/7/t")
    # identity, not just equality: same session and SubOpts objects
    dev, orc = _plans(b, "room/7/t")
    for (dc, ds, do), (oc, os_, oo) in zip(dev[0], orc[0]):
        assert dc == oc and ds is os_ and do is oo


def test_max_qos_tie_break_first_filter_wins():
    # equal granted QoS on two overlapping filters: the oracle keeps
    # the FIRST seen (strict > compare) — the kernel must too
    b = _broker()
    s = _sub(b, "c1", "a/+", qos=1)
    b.subscribe(s, "a/#", SubOpts(qos=1))
    dev, orc = _plans(b, "a/b")
    assert dev == orc and len(dev[0]) == 1
    # winner carries the a/+ subopts object (first in pairs order)
    assert dev[0][0][2] is b.suboptions[("a/+", "c1")]
    # now a strictly higher QoS on the later filter must win
    b.subscribe(s, "a/#", SubOpts(qos=2))
    dev, orc = _plans(b, "a/b")
    assert dev == orc
    assert dev[0][0][2] is b.suboptions[("a/#", "c1")]


def test_shared_group_legs_stay_out_of_the_direct_plan():
    b = _broker()
    for i in range(8):
        _sub(b, f"d{i}", "s/+/x")
    _sub(b, "g1", "$share/grp/s/+/x")
    _sub(b, "g2", "$share/grp/s/+/x")
    dev, orc = _plans(b, "s/1/x")
    assert dev == orc
    assert {c for c, _s, _o in dev[0]} == {f"d{i}" for i in range(8)}
    # full publish still elects exactly one group member on top
    n = b.publish(Message(topic="s/1/x", payload=b"x"))
    assert n == 9


def test_exotic_sessions_take_the_other_leg():
    class Exotic(Session):
        pass

    b = _broker()
    for i in range(4):
        _sub(b, f"m{i}", "t/+")
    e = Exotic("x1")
    e.outgoing_sink = lambda pkts: None
    b.sessions["x1"] = e
    b.subscribe(e, "t/+", SubOpts(qos=1))
    dev, orc = _plans(b, "t/5")
    assert dev == orc
    assert [c for c, _f, _o in dev[1]] == ["x1"]
    assert dev[1][0][1] == "t/+"  # other entries carry the filter


def test_durable_sessions_resolve_identically(tmp_path):
    # durable (DS) sessions route through the ps-router, not the live
    # router: they must appear in NEITHER plan — and the device resolve
    # must agree with the oracle about that
    from emqx_tpu.ds import Db
    from emqx_tpu.ds.session_ds import DurableSessionManager

    b = _broker()
    db = Db("messages", data_dir=str(tmp_path), n_shards=1)
    b.enable_durable(DurableSessionManager(db, state_dir=str(tmp_path)))
    for i in range(6):
        _sub(b, f"m{i}", "dur/+")
    from emqx_tpu.broker.session import SessionConfig

    ds, _ = b.open_session("d1", True, SessionConfig(session_expiry_interval=60))
    b.subscribe(ds, "dur/+", SubOpts(qos=1))
    dev, orc = _plans(b, "dur/9")
    assert dev == orc
    assert {c for c, _s, _o in dev[0]} == {f"m{i}" for i in range(6)}
    assert dev[1] == []


def test_absent_session_clients_are_skipped():
    b = _broker()
    for i in range(6):
        _sub(b, f"c{i}", "gone/+")
    _assert_identical(b, "gone/1")
    # close two sessions: the oracle drops them (sessions.get is None);
    # the registry note must make the kernel path agree
    b.close_session(b.sessions["c1"])
    b.close_session(b.sessions["c4"], discard=True)
    dev, orc = _plans(b, "gone/1")
    assert dev == orc
    assert {c for c, _s, _o in dev[0]} == {"c0", "c2", "c3", "c5"}


# --- churn oracle (the satellite) -----------------------------------------


def _churn_fanout_check(b, topics, steps=6):
    """Interleaved subscribe/unsubscribe/publish batches: the device
    plan must equal the host oracle after EVERY mutation, and publish
    (which exercises the plan cache + device resolve) must agree with
    a fresh oracle count."""
    extras = []
    for step in range(steps):
        if step % 3 == 0:
            for i in range(4):
                extras.append(_sub(b, f"e{step}-{i}", "fan/#", qos=i % 3))
        elif step % 3 == 1:
            _sub(b, f"e{step}", "fan/+/q", qos=2)
            if extras:
                b.unsubscribe(extras.pop(0), "fan/#")
        else:
            for s in extras[:2]:
                b.unsubscribe(s, "fan/#")
            del extras[:2]
        for t in topics:
            _assert_identical(b, t)
        for t in topics:
            pairs = b.router.match_pairs(t)
            want = b._build_fanout_plan(pairs)
            got = b.publish(Message(topic=t, payload=b"x"))
            assert got == len(want[0]) + len(want[1]), f"step {step} {t}"


def test_churn_oracle_single_device():
    b = _broker()
    for i in range(12):
        _sub(b, f"c{i}", "fan/+/q", qos=i % 3)
    _churn_fanout_check(b, ["fan/1/q", "fan/2/q"])
    tel = b.router.telemetry
    assert tel.counters["fanout_device_plans_total"] > 0


def test_churn_oracle_sharded():
    b = _broker(mesh=mesh_mod.make_mesh(n_dp=2, n_sub=4), max_levels=4)
    for i in range(12):
        _sub(b, f"c{i}", "fan/+/q", qos=i % 3)
    _churn_fanout_check(b, ["fan/1/q"], steps=4)


def test_row_recycle_keeps_plans_exact():
    # free a filter row, recycle it for an unrelated filter: the old
    # segment must not bleed into the new row's plans
    b = _broker()
    s = [_sub(b, f"c{i}", "old/+", qos=1) for i in range(5)]
    _assert_identical(b, "old/1")
    for i, sess in enumerate(s):
        b.unsubscribe(sess, "old/+")
    for i in range(3):
        _sub(b, f"n{i}", "new/+")
    _assert_identical(b, "new/1")
    dev, _ = _plans(b, "new/1")
    assert {c for c, _s, _o in dev[0]} == {"n0", "n1", "n2"}


# --- escalation / thresholds ----------------------------------------------


def test_min_fan_and_deep_filters_fall_back_to_host():
    b = Broker()  # default min_fan: small fans resolve host-side
    _sub(b, "c1", "tiny/+")
    pairs = b.router.match_pairs("tiny/1")
    key = tuple(f for f, _ in pairs)
    assert b.router.resolve_fanout_begin(key, min_fan=1024) is None
    # deep (host-resident) filters refuse the device path entirely
    deep = "/".join(["x"] * 20) + "/#"
    _sub(b, "c2", deep)
    pairs = b.router.match_pairs("/".join(["x"] * 21))
    key = tuple(f for f, _ in pairs)
    assert b.router.resolve_fanout_begin(key, min_fan=0) is None
    assert b.router.telemetry.counters["fanout_host_fallback_total"] >= 1
    # publishes still deliver through the host walk
    n = b.publish(Message(topic="tiny/1", payload=b"x"))
    assert n == 1


def test_small_fan_gate_leaves_dest_store_alone(monkeypatch):
    # a small-fan table subscribed through the broker (native add_route:
    # rows only MARKED pending): a window of plan misses resolves on the
    # host walk without rebuilding a segment or probing fan_of
    b = Broker()  # default min_fan 1024
    for k in range(6):
        for j in range(1 + k % 3):
            _sub(b, f"c{(k + j) % 6}", f"room/{k}/+", qos=j % 3)
        _sub(b, f"c{k}", f"room/{k}/t", qos=1)
    _sub(b, "c0", "room/#", qos=2)
    _sub(b, "c4", "room/#")
    r = b.router
    ds = r.dest_store
    topics = [f"room/{k}/t" for k in range(6)]
    keys = [tuple(f for f, _ in r.match_pairs(t)) for t in topics]
    rows = {r._fanout_row(f) for key in keys for f in key}
    assert None not in rows and rows <= ds.pending_rows
    pending0 = set(ds.pending_rows)
    assert ds.dirty_rows == [] and ds.dirty_edges == []
    built = []
    monkeypatch.setattr(ds, "set_row", lambda *a: built.append(a))
    monkeypatch.setattr(ds, "fan_of", lambda rows: built.append(rows))
    tel = r.telemetry
    misses0 = tel.counters.get("fanout_plan_misses", 0)
    small0 = tel.counters.get("fanout_small_fan_plans_total", 0)
    # two publishes a topic: the grouped window walk and the single one
    lives = [Message(topic=t, payload=b"x") for t in topics for _ in (0, 1)]
    lives.append(Message(topic="room/0/t", payload=b"y"))
    flists = [list(k) for k in keys for _ in (0, 1)] + [list(keys[0])]
    results, _meta = b.dispatch_window(lives[:-1], flists[:-1])
    results += b.dispatch_window(lives[-1:], flists[-1:])[0]
    assert built == []
    assert ds.pending_rows == pending0
    assert ds.dirty_rows == [] and ds.dirty_edges == []
    misses = tel.counters["fanout_plan_misses"] - misses0
    assert misses == len(keys)
    assert tel.counters["fanout_small_fan_plans_total"] - small0 == misses
    assert tel.counters.get("fanout_device_plans_total", 0) == 0
    for key, t in zip(keys, topics):
        pairs = r.match_pairs(t)
        oracle = b._build_fanout_plan(pairs)
        assert b._fanout_cache[key][1] == oracle
    want = [len(b._build_fanout_plan(r.match_pairs(m.topic))[0])
            for m in lives]
    assert results == want


def _grow_to_min_fan(b, flt, case):
    """Live fan of (flt, 'g/#') from 2 up to 4 by subscribe,
    unsubscribe and resubscribe; yields after every mutation with the
    live fan it left."""
    s1 = _sub(b, "c1", flt, qos=0)
    yield 3
    b.unsubscribe(s1, flt)
    yield 2
    b.subscribe(s1, flt, SubOpts(qos=1))
    yield 3
    if case == "shared":
        _sub(b, "m1", f"$share/grp/{flt}")  # a group dest joins the fan
    else:
        _sub(b, "c2", "g/#", qos=0)
    yield 4


@pytest.mark.parametrize("case", ["wild", "exact", "shared"])
def test_growing_fan_crosses_min_fan_gate(case):
    b = Broker()
    b._fanout_min_fan = 4
    flt = "g/1" if case == "exact" else "g/+"
    _sub(b, "c0", flt, qos=0)
    _sub(b, "c0", "g/#", qos=2)  # c0's max-QoS winner is the g/# leg
    r = b.router
    tel = r.telemetry
    row = r._fanout_row(flt)
    assert row in r.dest_store.pending_rows

    def publish():
        small0 = tel.counters.get("fanout_small_fan_plans_total", 0)
        dev0 = tel.counters.get("fanout_device_plans_total", 0)
        pairs = r.match_pairs("g/1")
        want = b._build_fanout_plan(pairs)
        n = b.publish(Message(topic="g/1", payload=b"x"))
        assert b._fanout_cache[tuple(f for f, _ in pairs)][1] == want
        small = tel.counters.get("fanout_small_fan_plans_total", 0) - small0
        dev = tel.counters.get("fanout_device_plans_total", 0) - dev0
        return n, want, small, dev

    n, _want, small, dev = publish()
    assert (small, dev) == (1, 0) and row in r.dest_store.pending_rows
    for live in _grow_to_min_fan(b, flt, case):
        n, want, small, dev = publish()
        if live < 4:
            assert (small, dev) == (1, 0)
            assert row in r.dest_store.pending_rows
        else:
            # crossed: the row is rebuilt once and the kernel serves
            assert (small, dev) == (0, 1)
            assert row not in r.dest_store.pending_rows
    mem = want[0]
    winners = [(c, o.qos) for c, _s, o in mem]
    if case == "shared":
        assert winners == [("c0", 2), ("c1", 1)]
        assert n == 3  # plus one elected group member
    else:
        assert winners == [("c0", 2), ("c1", 1), ("c2", 0)]
        assert n == 3
    # the launched kernel agrees with the oracle on the same state
    _assert_identical(b, "g/1")


# --- per-filter plan stamps (the regression the ISSUE names) --------------


def test_disjoint_filter_churn_keeps_plans_fresh():
    b = _broker()
    for i in range(6):
        _sub(b, f"a{i}", "alpha/+")
    for i in range(6):
        _sub(b, f"b{i}", "beta/+")
    b.publish(Message(topic="alpha/1", payload=b"x"))
    key_a = ("alpha/+",)
    assert b._plan_fresh(key_a)
    tel = b.router.telemetry
    hits0 = tel.counters.get("fanout_plan_hits", 0)
    # churn on DISJOINT filter beta/+: alpha's plan must survive
    _sub(b, "b9", "beta/+")
    b.unsubscribe(b.sessions["b0"], "beta/+")
    assert b._plan_fresh(key_a), "disjoint churn orphaned alpha's plan"
    b.publish(Message(topic="alpha/2", payload=b"x"))
    assert tel.counters.get("fanout_plan_hits", 0) == hits0 + 1
    # churn on alpha itself DOES stale it
    _sub(b, "a9", "alpha/+")
    assert not b._plan_fresh(key_a)
    # and the clock still bumps for introspection compat
    c0 = b._fanout_gen
    _sub(b, "a10", "alpha/+")
    assert b._fanout_gen > c0


def test_shared_leg_cache_uses_filter_stamps_too():
    b = _broker()
    for i in range(4):
        _sub(b, f"c{i}", "sh/+")
    _sub(b, "g1", "$share/g/sh/+")
    b.publish(Message(topic="sh/1", payload=b"x"))
    skey = ("$shared", ("sh/+",))
    assert skey in b._fanout_cache
    entry = b._fanout_cache[skey]
    _sub(b, "zz", "unrelated/+")  # disjoint: shared legs stay cached
    assert b._plan_entry_fresh(entry, ("sh/+",))
    _sub(b, "g2", "$share/g/sh/+")  # group membership churn stales
    assert not b._plan_entry_fresh(b._fanout_cache[skey], ("sh/+",))


# --- engine integration ----------------------------------------------------


async def test_engine_device_resolved_plans_match_sync():
    b = _broker()
    for i in range(24):
        _sub(b, f"c{i}", f"room/{i % 6}/+", qos=i % 3)
    for i in range(8):
        _sub(b, f"c{i}", "room/#", qos=2)
    eng = b.enable_dispatch_engine(queue_depth=8, deadline_ms=0.5)
    topics = [f"room/{i % 6}/t" for i in range(18)]
    msgs = [Message(topic=t, payload=b"x") for t in topics]
    counts = await asyncio.gather(*[eng.publish(m) for m in msgs])
    sync = [b.publish(Message(topic=t, payload=b"y")) for t in topics]
    assert counts == sync
    # second wave: the match cache answers at begin time, so the
    # engine launches overlapped resolves; results must not change
    _sub(b, "late", "room/#", qos=1)  # stale every room plan
    counts2 = await asyncio.gather(
        *[eng.publish(Message(topic=t, payload=b"z")) for t in topics]
    )
    sync2 = [b.publish(Message(topic=t, payload=b"w")) for t in topics]
    assert counts2 == sync2
    assert b.router.telemetry.counters.get("fanout_device_plans_total", 0) > 0
    await eng.stop()
