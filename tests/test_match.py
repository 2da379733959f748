"""Property tests: the batched TPU match kernel vs the pure oracle.

Mirrors the reference's test strategy where emqx_topic:match/2 is the
oracle every index implementation is checked against
(apps/emqx/test — e.g. emqx_topic_index_SUITE property tests).
"""

import random

import numpy as np
import pytest

from emqx_tpu.ops import match as M
from emqx_tpu.ops import topic as T
from emqx_tpu.ops.table import FilterTable, FilterTooDeep


def random_filter(rng, max_levels=6, vocab=("a", "b", "c", "dev", "")):
    n = rng.randint(1, max_levels)
    ws = [rng.choice(list(vocab) + ["+"]) for _ in range(n)]
    if rng.random() < 0.35:
        ws[-1] = "#"
    if rng.random() < 0.1:
        ws[0] = rng.choice(["$SYS", "$x"])
    return "/".join(ws)


def random_topic(rng, max_levels=7, vocab=("a", "b", "c", "dev", "", "zz")):
    n = rng.randint(1, max_levels)
    ws = [rng.choice(vocab) for _ in range(n)]
    if rng.random() < 0.15:
        ws[0] = rng.choice(["$SYS", "$x"])
    return "/".join(ws)


def assert_kernel_matches_oracle(table, topics):
    enc_t = M.encode_topics(table.vocab, topics, table.max_levels).fields()
    filters = table.snapshot()
    dense = np.asarray(M.match_dense(filters, enc_t))
    packed = np.asarray(M.match_packed(filters, enc_t, chunk=256))
    expected = M.oracle_match_rows(table, topics)
    for i, t in enumerate(topics):
        got_dense = np.flatnonzero(dense[i])
        got_packed = M.unpack_indices(packed[i])
        exp = expected[i]
        assert np.array_equal(got_dense, exp), (
            f"dense mismatch for {t!r}: got "
            f"{[('/'.join(table.filter_words(r))) for r in got_dense]} "
            f"expected {[('/'.join(table.filter_words(r))) for r in exp]}"
        )
        assert np.array_equal(got_packed, exp), f"packed mismatch for {t!r}"
    counts = np.asarray(M.match_counts(filters, enc_t))
    assert np.array_equal(counts, [len(e) for e in expected])


def test_basic_match():
    table = FilterTable(max_levels=8, capacity=1024)
    for f in ["a/b/c", "a/+/c", "a/#", "#", "+/b/#", "$SYS/#", "a//b", "+"]:
        table.add(f)
    assert_kernel_matches_oracle(
        table,
        ["a/b/c", "a/x/c", "a", "x", "$SYS/broker", "a//b", "", "a/b/c/d/e"],
    )


def test_property_random_tables():
    rng = random.Random(42)
    for round_ in range(8):
        table = FilterTable(max_levels=6, capacity=1024)
        rows = [table.add(random_filter(rng)) for _ in range(rng.randint(1, 300))]
        # tombstone a third of them
        for r in rng.sample(rows, len(rows) // 3):
            table.remove(r)
        # and add a few more (exercises row recycling)
        for _ in range(rng.randint(0, 50)):
            table.add(random_filter(rng))
        topics = [random_topic(rng) for _ in range(64)]
        assert_kernel_matches_oracle(table, topics)


def test_deep_topics_against_shallow_filters():
    table = FilterTable(max_levels=4, capacity=1024)
    table.add("a/#")
    table.add("a/b/c/d")  # exactly at the level limit
    table.add("#")
    with pytest.raises(FilterTooDeep):
        table.add("a/b/c/d/e")  # exact filter deeper than limit
    with pytest.raises(FilterTooDeep):
        table.add("a/b/c/d/e/#")
    deep = "a/" + "/".join("xyz%d" % i for i in range(20))
    assert_kernel_matches_oracle(table, [deep, "a/b/c/d", "a/b/c/d/e/f"])


def test_dollar_isolation():
    table = FilterTable(max_levels=4)
    table.add("#")
    table.add("+/x")
    table.add("$SYS/#")
    table.add("$SYS/+")
    assert_kernel_matches_oracle(
        table, ["$SYS/x", "$SYSTEM", "a/x", "x", "$SYS"]
    )


def test_row_recycling_updates_semantics():
    table = FilterTable(max_levels=4)
    r1 = table.add("a/b")
    table.remove(r1)
    r2 = table.add("c/#")
    assert r1 == r2  # recycled
    assert_kernel_matches_oracle(table, ["a/b", "c/x"])


def test_vocab_refcount_release():
    table = FilterTable(max_levels=4)
    r1 = table.add("aa/bb")
    r2 = table.add("aa/cc")
    assert table.vocab.lookup("aa") != 0
    table.remove(r1)
    assert table.vocab.lookup("aa") != 0  # still referenced by r2
    table.remove(r2)
    assert table.vocab.lookup("aa") == 0  # released


def test_growth():
    table = FilterTable(max_levels=4, capacity=32)
    rows = [table.add("t/%d" % i) for i in range(100)]
    assert table.capacity == 128 and table.grew
    assert len(table) == 100
    assert_kernel_matches_oracle(table, ["t/5", "t/77", "t/100"])
    for r in rows:
        table.remove(r)
    assert len(table) == 0


def test_match_ids_compaction():
    rng = random.Random(9)
    table = FilterTable(max_levels=6, capacity=1024)
    for _ in range(300):
        table.add(random_filter(rng))
    topics = [random_topic(rng) for _ in range(40)]
    enc_t = M.encode_topics(table.vocab, topics, table.max_levels).fields()
    filters = table.snapshot()
    expected = M.oracle_match_rows(table, topics)
    ti, ri, total = (np.asarray(a) for a in M.match_ids(filters, enc_t, max_hits=4096, chunk=256))
    assert total == sum(len(e) for e in expected)
    got = [[] for _ in topics]
    for t_idx, row in zip(ti[:total], ri[:total]):
        got[t_idx].append(row)
    for i in range(len(topics)):
        assert sorted(got[i]) == list(expected[i]), topics[i]
    # overflow detection: tiny bound
    _, _, total2 = M.match_ids(filters, enc_t, max_hits=32, chunk=256)
    if sum(len(e) for e in expected) > 32:
        assert int(total2) > 32


def test_match_ids_overflow_bound():
    table = FilterTable(max_levels=4, capacity=1024)
    for _ in range(100):
        table.add("#")  # every topic matches all 100
    enc_t = M.encode_topics(table.vocab, ["a"] * 8, table.max_levels).fields()
    ti, ri, total = M.match_ids(table.snapshot(), enc_t, max_hits=64, chunk=256)
    assert int(total) == 800 > 64  # overflow signalled, caller falls back


def test_packed_equals_dense_large():
    rng = random.Random(1)
    table = FilterTable(max_levels=6, capacity=2048)
    for _ in range(1500):
        table.add(random_filter(rng))
    topics = [random_topic(rng) for _ in range(33)]
    enc_t = M.encode_topics(table.vocab, topics, table.max_levels).fields()
    filters = table.snapshot()
    dense = np.asarray(M.match_dense(filters, enc_t))
    packed = np.asarray(M.match_packed(filters, enc_t, chunk=512))
    for i in range(len(topics)):
        assert np.array_equal(np.flatnonzero(dense[i]), M.unpack_indices(packed[i]))
