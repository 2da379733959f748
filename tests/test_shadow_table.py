"""BASELINE #3's mixed '+'/'#' table in the AWS IoT device-shadow shape
(the benchmark's `shadow4m`, small): each thing holds five filters over
the `$aws/things/{thing}/shadow/{op}/{result}` tree (its exact response
topic, `+` at level 5 or 4, `#` at depth 4 or 3), beside the root-wild
filters `#` and `+/things/#`, all subscribed through `Broker.subscribe`.
Batches at every pow2 size go down the device path (JAX on the CPU) and
must equal a brute-force MQTT 5.0 section 4.7 matcher and the host trie.
"""

import random

import pytest

from emqx_tpu.broker.packet import SubOpts
from emqx_tpu.broker.pubsub import Broker

THINGS = 2000
ROWS = THINGS * 5
SESSIONS = 40
SKELETONS = ("L/L/L/L/L/L", "L/L/L/L/L/+", "L/L/L/L/+/L", "L/L/L/L/#", "L/L/L/#")
ROOT_WILD = ("#", "+/things/#")


def _words(i):
    return ["$aws", "things", f"thing{i // 5}", "shadow", f"o{i % 3}", f"r{i % 4}"]


def _topic(i):
    return "/".join(_words(i))


def _filter(i):
    out = []
    for w, s in zip(_words(i), SKELETONS[i % 5].split("/")):
        if s == "#":
            out.append("#")
            break
        out.append(w if s == "L" else "+")
    return "/".join(out)


def brute_match(topic, flt):
    """MQTT 5.0 section 4.7, level by level: '+' takes one level, '#'
    the rest (the parent level included), and a topic starting with '$'
    is not matched by a filter whose first level is a wildcard."""
    tw, fw = topic.split("/"), flt.split("/")
    if topic.startswith("$") and fw[0] in ("+", "#"):
        return False
    for k, w in enumerate(fw):
        if w == "#":
            return True
        if k >= len(tw) or (w != "+" and w != tw[k]):
            return False
    return len(fw) == len(tw)


@pytest.fixture(scope="module")
def shadow():
    broker = Broker()
    opts = SubOpts(qos=0)
    sessions = []
    for k in range(SESSIONS):
        s, _ = broker.open_session(f"thing-holder{k}", clean_start=True)
        s.outgoing_sink = lambda pkts: None
        sessions.append(s)
    filters = [_filter(i) for i in range(ROWS)]
    for i, f in enumerate(filters):
        broker.subscribe(sessions[i % SESSIONS], f, opts)
    apps, _ = broker.open_session("fleet-app", clean_start=True)
    apps.outgoing_sink = lambda pkts: None
    for f in ROOT_WILD:
        broker.subscribe(apps, f, opts)
    return broker, filters + list(ROOT_WILD)


def test_shadow_table_is_five_classes_of_one_tree(shadow):
    _broker, filters = shadow
    assert filters[:5] == [
        "$aws/things/thing0/shadow/o0/r0", "$aws/things/thing0/shadow/o1/+",
        "$aws/things/thing0/shadow/+/r2", "$aws/things/thing0/shadow/#",
        "$aws/things/thing0/#",
    ]
    assert len(set(filters)) == ROWS + len(ROOT_WILD)


@pytest.mark.parametrize("b", [1 << k for k in range(7)])
def test_shadow_batch_on_the_device_equals_the_reference(shadow, b):
    broker, filters = shadow
    r = broker.router
    tel = r.telemetry
    rng = random.Random(b)
    # the first topic is an exact row's, so its exact and wildcard
    # routes are both due; the rest are uniform over the rows
    rows = [5 * rng.randrange(THINGS)] + [rng.randrange(ROWS) for _ in range(b - 1)]
    topics = [_topic(i) for i in rows]
    before = dict(tel.counters)
    p = r.match_filters_begin(topics)
    got = r.match_filters_finish(p)
    assert p.mode == "hash" and p.hash_pending is not None
    for i, t, fs in zip(rows, topics, got):
        want = sorted(f for f in filters if brute_match(t, f))
        assert sorted(fs) == want, t
        assert sorted(r.match_filters(t)) == want, t  # the host-trie oracle
        assert not set(fs) & set(ROOT_WILD), t
        assert 2 <= len(fs) <= 3
        thing = f"$aws/things/thing{i // 5}"
        assert {f"{thing}/#", f"{thing}/shadow/#"} <= set(fs)
    assert set(got[0]) >= {topics[0], f"$aws/things/thing{rows[0] // 5}/#"}

    def moved(k):
        return tel.counters.get(k, 0) - before.get(k, 0)

    assert moved("host_fallback_total") == 0
    assert moved("match_device_topics_total") == b
    assert moved("match_device_pairs_total") == sum(len(fs) for fs in got)


def test_cached_and_host_batches_count_no_device_pairs(shadow):
    """A batch the match cache answers, and one the host trie answers,
    leave both device counters where they were."""
    broker, filters = shadow
    r = broker.router
    tel = r.telemetry
    topics = [_topic(i) for i in (3, 17, 4242)]
    r.enable_match_cache(64)
    try:
        r.match_filters_batch(topics)  # misses: the device answers
        before = dict(tel.counters)
        r.match_filters_batch(topics)  # every topic a cache hit
        assert tel.counters.get("match_cache_hits", 0) - before.get("match_cache_hits", 0) == 3
        for k in ("match_device_topics_total", "match_device_pairs_total"):
            assert tel.counters[k] == before[k]
    finally:
        r.match_cache = None
    r.device_suspended = True  # the breaker open: the host trie answers
    try:
        before = dict(tel.counters)
        got = r.match_filters_batch(topics)
        assert [sorted(fs) for fs in got] == [
            sorted(f for f in filters if brute_match(t, f)) for t in topics
        ]
        for k in ("match_device_topics_total", "match_device_pairs_total"):
            assert tel.counters[k] == before[k]
    finally:
        r.device_suspended = False
