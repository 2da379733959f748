"""The batched wildcard-match kernel — the north-star hot path.

Replaces the reference's per-publish ordered-set skip-scan
(apps/emqx/src/emqx_trie_search.erl:192-226: one `ets:next` walk per
topic, O(matches × levels) pointer chases) with ONE XLA dispatch that
matches a whole batch of inbound topics against every filter row in
HBM simultaneously:

    match[b, n] = active[n]
                & ~(dollar[b] & root_wild[n])              # $-root rule
                & (tlen[b] == plen[n]  if not has_hash[n]
                   else tlen[b] >= plen[n])                # level count
                & all_{i < plen[n]} (W[n,i] == '+' or W[n,i] == t[b,i])

The per-level reduction is unrolled over the (static, small) max_levels
axis so XLA fuses the whole predicate into a single elementwise pass
over the [B, N] plane — bandwidth-bound streaming of the N×L filter
table from HBM, amortized across the topic batch.

Outputs come in two shapes:
  * match_dense  -> bool[B, N]           (tests / small tables)
  * match_packed -> uint32[B, N//32]     (production: 32× smaller,
    chunked over N with lax.map so peak memory stays ~[B, chunk])
plus match_counts for metrics. Host-side `unpack_indices` turns packed
bits back into row-id arrays via numpy unpackbits.

Correctness contract: identical match *set* to the oracle
emqx_tpu.ops.topic.match for every filter representable in the table
(property-tested in tests/test_match.py).
"""

from __future__ import annotations

import functools
from typing import List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import topic as topic_mod
from .table import EncodedFilters
from .vocab import PLUS, Vocab


class EncodedTopics(NamedTuple):
    """A batch of inbound topic names, dictionary-encoded, one array
    per field: the input of the dense and mesh kernels, which take
    their three leaves as host views of a `PackedTopics` buffer."""

    ids: np.ndarray  # int32 [B, L]  (first L levels; OOV beyond vocab)
    lens: np.ndarray  # int32 [B]    (TRUE level count, may exceed L)
    dollar: np.ndarray  # bool [B]   (first level starts with '$')


class PackedTopics(NamedTuple):
    """The same batch in ONE int32 [B, L + 2] buffer, so a launch moves
    one host->device buffer instead of three: columns 0..L-1 hold the
    level ids, column L the true level count, column L + 1 the '$'
    flag. What `encode_topics` returns and the hash kernel takes; the
    field keeps the name `ids`, so the kernel's operand stays
    `topics_ids`."""

    ids: np.ndarray  # int32 [B, L + 2]

    def fields(self) -> EncodedTopics:
        """The three-field form: views of the buffer (numpy or jax)."""
        lv = self.ids.shape[-1] - 2
        return EncodedTopics(
            self.ids[..., :lv], self.ids[..., lv], self.ids[..., lv + 1] != 0
        )

    @classmethod
    def of(cls, enc: EncodedTopics) -> "PackedTopics":
        """Pack a batch encoded elsewhere (jax arrays, e.g. generated
        on the device): the inverse of `fields`."""
        return cls(jnp.concatenate(
            [enc.ids, enc.lens[..., None], enc.dollar[..., None].astype(jnp.int32)],
            axis=-1,
        ).astype(jnp.int32))


def encode_topics(
    vocab: Vocab,
    topics: Sequence[str],
    max_levels: int,
    pad_to: int = 0,
) -> PackedTopics:
    """Encode topic names for the kernels into one packed buffer.
    Topics deeper than max_levels are still matched correctly against
    any representable filter: only the first `plen <= max_levels`
    levels are ever compared, and the true length is kept for the
    exact/'#' length checks.

    `pad_to` (when > len(topics)) grows the batch axis with INERT
    rows — zero levels, $-rooted — that match no representable filter
    (a 0-level topic only satisfies the length rule against a bare
    '#', which the $-root rule then rejects). Kernel shapes stay
    pow2-bounded instead of retracing per coalesce size; callers drop
    result rows with topic index >= len(topics), the same guard as
    mesh dp padding."""
    b = max(len(topics), pad_to)
    buf = np.zeros((b, max_levels + 2), np.int32)
    if pad_to > len(topics):
        buf[len(topics):, max_levels + 1] = 1
    lk = vocab.lookup
    for i, t in enumerate(topics):
        ws = t.split("/")
        row = buf[i]
        row[max_levels] = len(ws)
        row[max_levels + 1] = ws[0].startswith("$")
        for j, w in enumerate(ws[:max_levels]):
            row[j] = lk(w)
    return PackedTopics(buf)


def _match_block(
    t_ids: jnp.ndarray,  # int32 [B, L]
    t_len: jnp.ndarray,  # int32 [B]
    t_dollar: jnp.ndarray,  # bool [B]
    words: jnp.ndarray,  # int32 [N, L]
    plen: jnp.ndarray,  # int32 [N]
    has_hash: jnp.ndarray,  # bool [N]
    root_wild: jnp.ndarray,  # bool [N]
    active: jnp.ndarray,  # bool [N]
) -> jnp.ndarray:  # bool [B, N]
    max_levels = t_ids.shape[1]
    tl = t_len[:, None]  # [B, 1]
    pl = plen[None, :]  # [1, N]
    len_ok = jnp.where(has_hash[None, :], tl >= pl, tl == pl)
    ok = len_ok & active[None, :] & ~(t_dollar[:, None] & root_wild[None, :])
    # unrolled per-level word compare; positions >= plen are don't-care
    for i in range(max_levels):
        w = words[:, i][None, :]  # [1, N]
        t = t_ids[:, i][:, None]  # [B, 1]
        ok &= (i >= pl) | (w == PLUS) | (w == t)
    return ok


def _pack_bits(ok: jnp.ndarray) -> jnp.ndarray:
    """bool [B, N] -> uint32 [B, N//32], bit k of word j = row j*32+k."""
    b, n = ok.shape
    grouped = ok.reshape(b, n // 32, 32).astype(jnp.uint32)
    weights = (jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32))[None, None, :]
    return (grouped * weights).sum(axis=-1, dtype=jnp.uint32)


@jax.jit
def match_dense(filters: EncodedFilters, topics: EncodedTopics) -> jnp.ndarray:
    """bool [B, N] match matrix. For tests and small tables — O(B*N)
    bytes; use match_packed for production sizes."""
    return _match_block(
        topics.ids, topics.lens, topics.dollar, *filters
    )


@functools.partial(jax.jit, static_argnames=("chunk",))
def match_packed(
    filters: EncodedFilters, topics: EncodedTopics, chunk: int = 65536
) -> jnp.ndarray:
    """uint32 [B, N//32] packed match bitmap, chunked over the filter
    axis so peak intermediate memory is [B, chunk] regardless of N."""
    n = filters.words.shape[0]
    chunk = min(chunk, n)
    assert n % chunk == 0, (n, chunk)
    n_chunks = n // chunk

    def one(args):
        words, plen, hh, rw, act = args
        ok = _match_block(
            topics.ids, topics.lens, topics.dollar, words, plen, hh, rw, act
        )
        return _pack_bits(ok)  # [B, chunk//32]

    xs = (
        filters.words.reshape(n_chunks, chunk, -1),
        filters.prefix_len.reshape(n_chunks, chunk),
        filters.has_hash.reshape(n_chunks, chunk),
        filters.root_wild.reshape(n_chunks, chunk),
        filters.active.reshape(n_chunks, chunk),
    )
    ys = jax.lax.map(one, xs)  # [n_chunks, B, chunk//32]
    b = topics.ids.shape[0]
    return jnp.transpose(ys, (1, 0, 2)).reshape(b, n // 32)


@functools.partial(jax.jit, static_argnames=("max_hits", "chunk"))
def match_ids(
    filters: EncodedFilters,
    topics: EncodedTopics,
    max_hits: int = 4096,
    chunk: int = 65536,
):
    """Device-side compaction: returns (topic_idx int32 [max_hits],
    row_idx int32 [max_hits], total int32). Each valid slot i holds one
    matching (topic, filter-row) pair; slots beyond the true hit count
    are -1. If total > max_hits the result overflowed — the caller must
    fall back to match_packed. This keeps the device→host transfer
    proportional to the number of MATCHES, not the table size
    (PERF_NOTES.md: packed bitmaps are 128MB/batch at 1M rows; matches
    are a few KB)."""
    n = filters.words.shape[0]
    chunk = min(chunk, n)
    assert n % chunk == 0, (n, chunk)
    n_chunks = n // chunk
    b = topics.ids.shape[0]

    def step(carry, xs):
        t_buf, r_buf, pos = carry
        words, plen, hh, rw, act, off = xs
        ok = _match_block(
            topics.ids, topics.lens, topics.dollar, words, plen, hh, rw, act
        )  # [B, chunk]
        cnt = ok.sum(dtype=jnp.int32)
        idx = jnp.nonzero(ok.reshape(-1), size=max_hits, fill_value=-1)[0]
        valid = idx >= 0
        ti = jnp.where(valid, idx // chunk, -1).astype(jnp.int32)
        ri = jnp.where(valid, idx % chunk + off, -1).astype(jnp.int32)
        # valid entries are dense at the front; write them at pos+rank
        dst = jnp.where(valid, pos + jnp.arange(max_hits, dtype=jnp.int32), max_hits)
        t_buf = t_buf.at[dst].set(ti, mode="drop")
        r_buf = r_buf.at[dst].set(ri, mode="drop")
        return (t_buf, r_buf, pos + cnt), None

    xs = (
        filters.words.reshape(n_chunks, chunk, -1),
        filters.prefix_len.reshape(n_chunks, chunk),
        filters.has_hash.reshape(n_chunks, chunk),
        filters.root_wild.reshape(n_chunks, chunk),
        filters.active.reshape(n_chunks, chunk),
        jnp.arange(n_chunks, dtype=jnp.int32) * chunk,
    )
    init = (
        jnp.full(max_hits, -1, jnp.int32),
        jnp.full(max_hits, -1, jnp.int32),
        jnp.int32(0),
    )
    (t_buf, r_buf, total), _ = jax.lax.scan(step, init, xs)
    return t_buf, r_buf, total


@jax.jit
def match_counts(filters: EncodedFilters, topics: EncodedTopics) -> jnp.ndarray:
    """int32 [B] — matches per topic (metrics / routing decisions)."""
    ok = _match_block(topics.ids, topics.lens, topics.dollar, *filters)
    return ok.sum(axis=1, dtype=jnp.int32)


def unpack_indices(packed_row: np.ndarray) -> np.ndarray:
    """uint32 [N//32] -> int64 row ids of set bits (host, numpy)."""
    bits = np.unpackbits(
        np.ascontiguousarray(packed_row, dtype=np.uint32).view(np.uint8),
        bitorder="little",
    )
    return np.flatnonzero(bits)


def unpack_all(packed: np.ndarray) -> List[np.ndarray]:
    """uint32 [B, N//32] -> per-topic arrays of matched row ids."""
    return [unpack_indices(packed[i]) for i in range(packed.shape[0])]


class GenMatchCache:
    """Generation-stamped topic -> matched-filters cache.

    The front line of the publish hot path: hot topics resolve to
    their full match result (a tuple of filter strings) with one dict
    probe and skip the kernel entirely. Every route mutation bumps the
    owning Router's generation; entries carry the generation they were
    computed at and are lazily discarded on mismatch — churn costs one
    stale probe per re-touched topic, never an O(n) wholesale clear
    (the EMQX route-cache invalidation model, without the flush).

    Eviction at capacity is O(1) FIFO (oldest-inserted key): stale
    entries age out through it, and hot topics re-enter immediately on
    their next publish, so the steady-state contents track the live
    hot set.
    """

    __slots__ = ("capacity", "data", "hits", "misses", "evictions")

    def __init__(self, capacity: int = 8192):
        assert capacity > 0
        self.capacity = capacity
        self.data: dict = {}  # topic -> (generation, filters tuple)
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self.data)

    def get(self, topic: str, generation: int):
        """Filters tuple on a current-generation hit, else None."""
        e = self.data.get(topic)
        if e is not None:
            if e[0] == generation:
                self.hits += 1
                return e[1]
            # lazy discard: the slot frees now, the entry re-fills from
            # the kernel result at this topic's next publish
            del self.data[topic]
        self.misses += 1
        return None

    def put(self, topic: str, generation: int, filters) -> None:
        data = self.data
        if topic not in data and len(data) >= self.capacity:
            # FIFO evict exactly one entry — bounded, O(1), no clear
            del data[next(iter(data))]
            self.evictions += 1
        data[topic] = (generation, filters)

    def hit_ratio(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0


def oracle_match_rows(
    table, topics: Sequence[str]
) -> List[np.ndarray]:
    """Reference result via the pure-Python oracle (emqx_topic.erl:80-116
    semantics) — the ground truth the kernel is tested against."""
    out = []
    live = [(row, table.filter_words(row)) for row in table.rows()]
    for t in topics:
        tw = topic_mod.words(t)
        out.append(
            np.array(
                [row for row, fw in live if topic_mod.match(tw, fw)], np.int64
            )
        )
    return out
