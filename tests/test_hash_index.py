"""Pattern-class hash index tests: kernel + host verify vs the oracle.

Same strategy as test_match.py (the reference property-tests every
index implementation against emqx_topic:match/2); here the object
under test is the B×C hash-probe kernel plus its host-side bucket
expansion, exercised both directly and through Router.match_batch.
"""

import random

import numpy as np
import pytest

from emqx_tpu.models.router import Router
from emqx_tpu.ops import hash_index as H
from emqx_tpu.ops import match as M
from emqx_tpu.ops import topic as T
from emqx_tpu.ops.table import FilterTable

from test_match import random_filter, random_topic


def oracle_dests(routes, topic):
    tw = T.words(topic)
    return {d for (f, d) in routes if T.match(tw, T.words(f))}


def build_indexed(filters):
    table = FilterTable(max_levels=6, capacity=1024)
    ix = H.ClassIndex(table.max_levels, min_slots=64)
    rows = []
    for f in filters:
        row = table.add(f)
        ix.add_row(row, table)
        rows.append(row)
    return table, ix, rows


def hash_match_rows(table, ix, topics, max_hits=4096):
    """Kernel + host verify + bucket expansion -> per-topic row sets."""
    enc = M.encode_topics(table.vocab, topics, table.max_levels)
    meta = H.ClassMeta(*(np.array(a) for a in ix.meta))
    slots = H.SlotArrays(*(np.array(a) for a in ix.slots))
    ti, bi, total, amb = H.split_hash_result(
        np.asarray(H.match_ids_hash(meta, slots, enc, max_hits=max_hits)), max_hits
    )
    total = int(total)
    assert int(amb) == 0, "full-fingerprint collision in a test table"
    assert total <= max_hits, "test tables must fit the bound"
    out = [set() for _ in topics]
    for t_idx, bid in zip(np.asarray(ti)[:total], np.asarray(bi)[:total]):
        t_idx, bid = int(t_idx), int(bid)
        if bid < 0:  # phase-2 reject inside the kernel
            continue
        if T.match(T.words(topics[t_idx]), ix.bucket_filter(bid)):
            out[t_idx].update(ix.bucket_rows(bid))
    return out


def assert_hash_matches_oracle(table, ix, topics):
    expected = M.oracle_match_rows(table, topics)
    got = hash_match_rows(table, ix, topics)
    for i, t in enumerate(topics):
        exp = set(int(r) for r in expected[i]) - ix.residual_rows
        assert got[i] == exp, (
            f"hash mismatch for {t!r}: got "
            f"{sorted('/'.join(table.filter_words(r)) for r in got[i])} "
            f"expected {sorted('/'.join(table.filter_words(r)) for r in exp)}"
        )


DECOY = 1000  # the bucket id decoy lanes carry (no live bucket has it)


def _decoy_slots(ix, bid, decoys):
    """ix's slots with the key of bucket `bid` moved behind `decoys`
    lanes whose probe byte equals its own but whose full fingerprint
    does not, in the kernel's lane order (its first candidate bucket's
    four lanes, then its alternate's)."""
    mask = ix.n_buckets - 1
    h1, fp = int(ix._bkt_h1[bid]), int(ix._bkt_fp[bid])
    b1 = h1 & mask
    b2 = b1 ^ ((fp | 1) * H._ALT_MUL & mask)
    sfp, sbkt, probe = (np.array(a) for a in ix.slots)
    p8 = max(fp >> 24, 1)
    key = int(ix._bkt_slot[bid])
    sfp[key], sbkt[key] = 0, -1
    probe[key // H.BUCKET_W] &= ~np.uint32(0xFF << 8 * (key % H.BUCKET_W))
    lanes = [(b, ln) for b in (b1, b2) for ln in range(H.BUCKET_W)]
    for k, (b, ln) in enumerate(lanes[: decoys + 1]):
        slot = b * H.BUCKET_W + ln
        assert sbkt[slot] < 0, "the lanes before the key must be free"
        sfp[slot] = fp if k == decoys else fp ^ 1
        sbkt[slot] = bid if k == decoys else DECOY
        probe[b] |= np.uint32(p8 << 8 * ln)
    return H.SlotArrays(sfp, sbkt, probe)


@pytest.mark.parametrize("decoys, amb", [(0, 0), (1, 0), (2, 0), (3, 1)])
def test_verify_covers_three_byte_matching_lanes(decoys, amb):
    """A pair whose key sits behind up to two decoy lanes (three lanes
    byte-match) is still answered exactly on the device; behind three
    (four lanes) it goes to `amb`, the host trie's batch."""
    table, ix, rows = build_indexed(["a/+/c", "x/y"])
    bid = int(ix._row_bucket[rows[0]])
    slots = _decoy_slots(ix, bid, decoys)
    meta = H.ClassMeta(*(np.array(a) for a in ix.meta))
    enc = M.encode_topics(table.vocab, ["a/b/c", "x/y"], table.max_levels)
    ti, bi, total, got_amb = H.split_hash_result(
        np.asarray(H.match_ids_hash(meta, slots, enc, max_hits=64)), 64
    )
    assert int(got_amb) == amb
    hits = {(int(t), int(b)) for t, b in zip(ti[: int(total)], bi[: int(total)]) if b >= 0}
    if not amb:
        assert (0, bid) in hits and not any(b == DECOY for _t, b in hits)


def test_basic_classes():
    table, ix, _ = build_indexed(
        ["a/b/c", "a/+/c", "a/#", "#", "+/b/#", "$SYS/#", "a//b", "+", "x/y"]
    )
    assert not ix.residual_rows
    assert_hash_matches_oracle(
        table, ix, ["a/b/c", "a/x/c", "a", "x", "$SYS/broker", "a//b", "", "x/y"]
    )


def test_bucket_shares_slot_across_dests():
    """100k routes on one filter must cost ONE slot (the bucket rule)."""
    table, ix, rows = build_indexed(["t/+/x"] * 500)
    assert len(ix) == 1  # one live bucket
    got = hash_match_rows(table, ix, ["t/9/x"])
    assert got[0] == set(rows)


def test_property_random_tables_with_churn():
    rng = random.Random(7)
    for _ in range(8):
        table = FilterTable(max_levels=6, capacity=1024)
        ix = H.ClassIndex(table.max_levels, min_slots=32)  # force rebuilds
        live = []
        for _ in range(rng.randint(50, 400)):
            f = random_filter(rng)
            row = table.add(f)
            ix.add_row(row, table)
            live.append(row)
        for row in rng.sample(live, len(live) // 3):
            ix.remove_row(row)
            table.remove(row)
            live.remove(row)
        for _ in range(rng.randint(0, 60)):
            row = table.add(random_filter(rng))
            ix.add_row(row, table)
            live.append(row)
        topics = [random_topic(rng) for _ in range(64)]
        assert_hash_matches_oracle(table, ix, topics)


def test_tombstones_keep_probe_chains():
    # many filters in one class to build probe clusters, then delete some
    table = FilterTable(max_levels=4, capacity=1024)
    ix = H.ClassIndex(table.max_levels, min_slots=32)
    rows = {}
    for i in range(200):
        f = f"lvl/{i}/+"
        rows[f] = table.add(f)
        ix.add_row(rows[f], table)
    for i in range(0, 200, 3):
        f = f"lvl/{i}/+"
        ix.remove_row(rows[f])
        table.remove(rows[f])
        del rows[f]
    topics = [f"lvl/{i}/zz" for i in range(0, 200, 7)]
    assert_hash_matches_oracle(table, ix, topics)


def test_class_budget_overflow_residual():
    table = FilterTable(max_levels=8, capacity=1024)
    ix = H.ClassIndex(table.max_levels, class_budget=4, min_slots=32)
    # 4 distinct skeletons fill the budget; later skeletons go residual
    for f in ["a/b", "a/+", "a/#", "+/b/c"]:
        ix.add_row(table.add(f), table)
    assert not ix.residual_rows
    r5 = table.add("+/+/+/x")  # 5th skeleton
    ix.add_row(r5, table)
    assert r5 in ix.residual_rows
    # same-skeleton filters still get classed
    r6 = table.add("q/+")
    ix.add_row(r6, table)
    assert r6 not in ix.residual_rows
    # removing residual rows maintains the set
    ix.remove_row(r5)
    table.remove(r5)
    assert not ix.residual_rows
    # class retirement frees budget for a new skeleton
    ix.remove_row(r6)  # 'a/+' skeleton still held by row 1
    table.remove(r6)


def test_class_retirement_reuses_budget():
    table = FilterTable(max_levels=4, capacity=1024)
    ix = H.ClassIndex(table.max_levels, class_budget=2, min_slots=32)
    r1 = table.add("a/b")
    ix.add_row(r1, table)
    r2 = table.add("c/+")
    ix.add_row(r2, table)
    r3 = table.add("x/y/z")  # budget exhausted -> residual
    ix.add_row(r3, table)
    assert r3 in ix.residual_rows
    ix.remove_row(r1)
    table.remove(r1)  # retires the 'a/b' skeleton class
    r4 = table.add("q/r/s")  # new skeleton fits the freed class slot
    ix.add_row(r4, table)
    assert r4 not in ix.residual_rows
    assert_hash_matches_oracle(table, ix, ["q/r/s", "c/9", "a/b"])


def test_router_hash_path_vs_oracle():
    rng = random.Random(11)
    routes = []
    r = Router(max_levels=6)
    assert r.index is not None
    for i in range(500):
        f = random_filter(rng)
        d = f"n{rng.randint(0, 5)}"
        routes.append((f, d))
        r.add_route(f, d)
    for _ in range(120):
        f, d = routes.pop(rng.randrange(len(routes)))
        r.delete_route(f, d)
    topics = [random_topic(rng) for _ in range(96)]
    got = r.match_batch(topics)
    for i, t in enumerate(topics):
        assert got[i] == oracle_dests(routes, t), t
        assert got[i] == r.match_routes(t), t


def test_router_residual_and_hash_combined():
    """Router with a tiny class budget: some filters hash-classed, some
    residual-dense — match_batch must merge both legs correctly."""
    r = Router(max_levels=8)
    assert r.index is not None
    r.index.class_budget = 2
    r.index._class_free = [1, 0]
    routes = []
    for f, d in [
        ("a/+", "n1"),
        ("b/+", "n2"),  # same skeleton as a/+
        ("a/#", "n3"),
        ("+/+/c", "n4"),  # 3rd skeleton -> residual
        ("x/y/z/w", "n5"),  # 4th skeleton -> residual
        ("exact/topic", "n6"),
    ]:
        r.add_route(f, d)
        routes.append((f, d))
    assert r.index.residual_rows
    topics = ["a/1", "b/2", "a", "q/r/c", "x/y/z/w", "exact/topic", "$SYS/x"]
    got = r.match_batch(topics)
    for i, t in enumerate(topics):
        assert got[i] == oracle_dests(routes, t), t


def test_router_overflow_escalation():
    """More matches than the initial max_hits bound: the exact-total
    retry must return the full result (no silent truncation)."""
    r = Router(max_levels=4)
    routes = []
    for i in range(3000):
        f = f"f/{i}/#"
        r.add_route(f, f"n{i}")
        routes.append((f, f"n{i}"))
    # every topic f/i/x matches exactly one filter... instead use shared
    # prefix wildcards so a single topic matches thousands of buckets
    for i in range(2000):
        f = f"w/{i}/+"
        r.add_route(f, f"m{i}")
        routes.append((f, f"m{i}"))
    topics = [f"w/{i}/q" for i in range(1500)]  # 1500 matches + exacts
    got = r.match_batch(topics)
    for i, t in enumerate(topics):
        assert got[i] == oracle_dests(routes, t), t


def test_hash_host_device_agreement():
    """The host placement hash and the device probe hash must be
    bit-identical — a direct check, not just end-to-end."""
    table, ix, _ = build_indexed(["dev/+/room/#", "dev/a/room/#"])
    enc = M.encode_topics(table.vocab, ["dev/a/room/1"], table.max_levels)
    meta = H.ClassMeta(*(np.array(a) for a in ix.meta))
    slots = H.SlotArrays(*(np.array(a) for a in ix.slots))
    ti, bi, total, _amb = H.split_hash_result(
        np.asarray(H.match_ids_hash(meta, slots, enc, max_hits=64)), 64
    )
    # both pairs must be found via their stored (h1, fp)
    assert int(total) == 2


def test_deep_skeleton_goes_residual():
    """plen > 32 can't be expressed in the uint32 plus-mask — such rows
    must degrade to the residual (dense) path, not crash or misroute."""
    r = Router(max_levels=40)
    deep = "/".join(["a"] * 33) + "/+"
    r.add_route(deep, "n1")
    r.add_route("a/+", "n2")
    assert r.index is not None and len(r.index.residual_rows) == 1
    t = "/".join(["a"] * 34)
    got = r.match_batch([t, "a/zz"])
    assert got[0] == {"n1"}
    assert got[1] == {"n2"}


def test_amb_collision_falls_back_to_host_exactly():
    """VERDICT r3 weak #9: the amb>0 escape hatch. Two distinct filters
    are FORGED into a full 32+32-bit fingerprint collision (the
    ~2^-32/pair event brute force can't reach) by rewriting one
    bucket's hashes; the kernel must report amb>0 and the Router must
    re-match on the host trie, staying oracle-exact."""
    from emqx_tpu.models.router import Router

    r = Router(max_levels=8)
    r.add_route("col/+/x", "nodeA")
    r.add_route("col/+/y", "nodeB")
    r.add_route("other/t", "nodeC")
    ix = r.index
    bidA = ix._row_bucket[r._filter_row["col/+/x"]]
    bidB = ix._row_bucket[r._filter_row["col/+/y"]]
    # forge: bucket B collides with A on ALL hash bits, then re-place
    ix._bkt_h1[bidB] = ix._bkt_h1[bidA]
    ix._bkt_fp[bidB] = ix._bkt_fp[bidA]
    ix._rebuild(ix.n_buckets)

    # spy on the host-fallback path
    calls = {"n": 0}
    orig = r._host_trie

    def spy():
        calls["n"] += 1
        return orig()

    r._host_trie = spy

    topics = ["col/9/x", "col/9/y", "other/t", "col/9/z", "miss/x"]
    got = [sorted(o) for o in r.match_filters_batch(topics)]
    assert calls["n"] >= 1, "amb fallback never engaged"
    assert got == [
        ["col/+/x"], ["col/+/y"], ["other/t"], [], [],
    ]
    # dest resolution stays exact too
    assert r.match_routes("col/9/x") == {"nodeA"}
    assert r.match_routes("col/9/y") == {"nodeB"}


# --- the packed launch: one buffer in, one buffer out ---------------------------


def _random_router(rng):
    r = Router(max_levels=6)
    routes = []
    for i in range(300):
        f = random_filter(rng)
        d = f"n{i % 7}"
        r.add_route(f, d)
        routes.append((f, d))
    return r, routes


def _case_batch(b):
    """`b` is the pow2 batch the launch pads to: b // 2 + 1 live topics
    (b itself at 1 and 2), so every batch above 2 carries padding rows."""

    def build():
        rng = random.Random(b)
        r, routes = _random_router(rng)
        n = b if b <= 2 else b // 2 + 1
        return r, routes, [random_topic(rng) for _ in range(n)]

    return build


def _case_dollar():
    r = Router(max_levels=6)
    routes = [("#", "n1"), ("+/a/#", "n2"), ("$SYS/#", "n3"),
              ("$SYS/+/b", "n4"), ("+/+/b", "n5"), ("$x/a", "n6")]
    for f, d in routes:
        r.add_route(f, d)
    topics = ["$SYS/a/b", "$SYS/x", "$x/a", "sys/a/b", "$SYS", "q/a"]
    return r, routes, topics


def _case_deep():
    r = Router(max_levels=4)
    routes = [("a/#", "n1"), ("a/+/c/#", "n2"), ("+/+/+/+", "n3"),
              ("a/b/c/d", "n4"), ("+/b/#", "n5")]
    for f, d in routes:
        r.add_route(f, d)
    topics = ["a/b/c/d/e/f", "a/b/c/d", "a/x/c/d/e", "z/b/c/d/e/f/g/h"]
    return r, routes, topics


def _case_overflow():
    """128 classes (a or + at each of 7 levels, then +) that every topic
    matches: 16 topics flag 2,048 pairs against the launch's 1,024."""
    r = Router(max_levels=8)
    routes = []
    for m in range(128):
        f = "/".join("+" if m >> i & 1 else "a" for i in range(7)) + "/+"
        r.add_route(f, f"n{m}")
        routes.append((f, f"n{m}"))
    return r, routes, [f"a/a/a/a/a/a/a/x{k}" for k in range(16)]


def _case_amb():
    """A bucket forged into a full fingerprint collision with another
    (as test_amb_collision_falls_back_to_host_exactly plants it)."""
    r = Router(max_levels=8)
    routes = [("col/+/x", "nodeA"), ("col/+/y", "nodeB"), ("other/t", "nodeC")]
    for f, d in routes:
        r.add_route(f, d)
    ix = r.index
    bid_a = ix._row_bucket[r._filter_row["col/+/x"]]
    bid_b = ix._row_bucket[r._filter_row["col/+/y"]]
    ix._bkt_h1[bid_b] = ix._bkt_h1[bid_a]
    ix._bkt_fp[bid_b] = ix._bkt_fp[bid_a]
    ix._rebuild(ix.n_buckets)
    return r, routes, ["col/9/x", "col/9/y", "other/t", "col/9/z", "miss/x"]


PACKED_CASES = {
    **{f"b{b}": _case_batch(b) for b in (1, 2, 4, 8, 16, 32, 64)},
    "dollar": _case_dollar,
    "deep": _case_deep,
    "overflow": _case_overflow,
    "amb": _case_amb,
}


@pytest.mark.parametrize("case", list(PACKED_CASES))
def test_packed_launch_matches_host_trie(case):
    """The hash leg's one-buffer launch gives what the host trie gives,
    topic for topic, and the packed row holds each topic's true level
    count and '$' flag."""
    r, routes, topics = PACKED_CASES[case]()
    tel = r.telemetry
    p = r.match_filters_begin(topics)
    got = r.match_filters_finish(p)
    assert p.mode == "hash" and p.hash_pending is not None
    b = 1 << (len(topics) - 1).bit_length()
    lv = r.max_levels
    assert p.enc.ids.shape == (b, lv + 2) and p.enc.ids.dtype == np.int32
    for i, t in enumerate(topics):
        assert sorted(got[i]) == sorted(r.match_filters(t)), t
        assert {d for f in got[i] for d in r.filter_dests(f)} == oracle_dests(routes, t), t
        assert p.enc.ids[i, lv] == len(t.split("/"))
        assert p.enc.ids[i, lv + 1] == t.startswith("$")
    # padding rows: zero levels, '$'-rooted
    assert not p.enc.ids[len(topics):, : lv + 1].any()
    assert p.enc.ids[len(topics):, lv + 1].all()
    # the escalation re-run and the host fallback engage where planted
    retries = tel.counters.get("hash_overflow_retries_total", 0)
    fallbacks = tel.counters.get("ambiguous_batches_total", 0)
    assert retries == (1 if case == "overflow" else 0)
    assert fallbacks == (1 if case == "amb" else 0)


def test_warmed_pow2_batches_serve_without_new_shapes():
    """After warmup_shapes(64) a served batch of each pow2 size records no
    new shape key: the packed kernel is warm at every batch it can get."""
    rng = random.Random(3)
    r, _routes = _random_router(rng)
    r.warmup_shapes(64)
    tel = r.telemetry
    tel.mark_serving()
    buckets = tel.shape_buckets()
    for b in (1, 2, 4, 8, 16, 32, 64):
        r.match_filters_batch([random_topic(rng) for _ in range(b)])
    assert tel.shape_buckets() == buckets
    assert tel.counters["recompiles_at_serve_total"] == 0
