"""The route table: Topic/Filter -> destinations, with a TPU-resident
wildcard matcher kept coherent by batched incremental sync.

Reproduces the reference v2 routing split (apps/emqx/src/emqx_router.erl):
  * exact-topic routes in a plain host hash table
    (?ROUTE_TAB ets bag, emqx_router.erl:511-516 first leg) — these
    never need the device;
  * wildcard routes in BOTH a host trie (ops/host_index.py — the
    single-publish cut-through path) and the flattened device table
    (ops/table.py + ops/match.py — the batched scale path);
  * a (filter, dest) pair is one logical route; duplicates refcount
    (bag semantics of mria route tables).

Device coherence mirrors emqx_router_syncer (apps/emqx/src/
emqx_router_syncer.erl:57 ?MAX_BATCH_SIZE 1000): dirty rows drain in
fixed-size scatter batches through one pre-compiled donated XLA update,
so steady-state sync never recompiles; only capacity growth re-uploads.

Destinations are opaque hashables — node ids, session ids, or
(group, dest) tuples for shared subscriptions (emqx_broker.erl:405-406
routes to {Group, Node} dests the same way).
"""

from __future__ import annotations

import functools
from typing import Dict, Hashable, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.kernel_telemetry import NULL as _NULL_TEL
from ..obs.profiler import STAGE_MARK
from ..obs.kernel_telemetry import (
    LEG_DENSE,
    LEG_ENCODE,
    LEG_FALLBACK,
    LEG_HASH,
    LEG_UNPACK,
    KernelTelemetry,
)
from ..ops import fanout as fanout_ops
from ..ops import hash_index as hash_ops
from ..ops import match as match_ops
from ..ops import speedups as _speedups
from ..ops import topic as topic_mod
from ..ops import transfer as transfer_ops
from ..ops.hash_index import ClassIndex, ClassMeta, SlotArrays
from ..ops.host_index import TopicTrie
from ..ops.table import (
    EncodedFilters,
    FilterTable,
    FilterTooDeep,
    pad_pow2_batches,
)

Dest = Hashable

SYNC_BATCH_SIZE = 1024  # rows per scatter step (ref: ?MAX_BATCH_SIZE 1000)
# deferred host-trie ops replayed per event-loop turn after a subscribe
# storm: ~70 ms a turn at the ~8.4 us/op the chip host showed, not
# counting a collector pass that lands inside it
TRIE_REPLAY_STEP = 8192


def _next_pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_rows(
    dev: EncodedFilters,
    rows: jnp.ndarray,  # int32 [n_batches, K]
    words: jnp.ndarray,  # int32 [n_batches, K, L]
    prefix_len: jnp.ndarray,  # int32 [n_batches, K]
    has_hash: jnp.ndarray,  # bool [n_batches, K]
    root_wild: jnp.ndarray,  # bool [n_batches, K]
    active: jnp.ndarray,  # bool [n_batches, K]
) -> EncodedFilters:
    """Apply all delta batches in ONE dispatch (scan over the batch
    axis), so a bulk route sync pays one launch, not one per batch."""

    def step(d, xs):
        r, w, p, h, rw_, a = xs
        return (
            EncodedFilters(
                d.words.at[r].set(w),
                d.prefix_len.at[r].set(p),
                d.has_hash.at[r].set(h),
                d.root_wild.at[r].set(rw_),
                d.active.at[r].set(a),
            ),
            None,
        )

    out, _ = jax.lax.scan(
        step, dev, (rows, words, prefix_len, has_hash, root_wild, active)
    )
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_slots(
    slots: SlotArrays,
    idx: jnp.ndarray,  # int32 [n_batches, K] — flat slot indices
    fp: jnp.ndarray,  # uint32 [n_batches, K]
    bucket: jnp.ndarray,  # int32 [n_batches, K]
    probe: jnp.ndarray,  # uint32 [n_batches, K] — merged probe WORDS
) -> SlotArrays:
    """Batched in-place update of the hash-slot arrays (same shape
    discipline as _scatter_rows: padding rewrites the last slot).
    Probe words scatter at idx//W; duplicate indices in one batch all
    carry the same host-merged word, so last-write-wins is safe."""
    from ..ops.hash_index import BUCKET_W

    def step(s, xs):
        i, f, b, pw = xs
        return (
            SlotArrays(
                s.fp.at[i].set(f),
                s.bucket.at[i].set(b),
                s.probe.at[i // BUCKET_W].set(pw),
            ),
            None,
        )

    out, _ = jax.lax.scan(step, slots, (idx, fp, bucket, probe))
    return out


class DeviceTable:
    """Device-resident mirror of a FilterTable (and optionally its
    pattern-class hash index), synced by batched scatter updates
    (double-buffer-free: XLA donation updates in place)."""

    def __init__(
        self,
        table: FilterTable,
        device=None,
        index: Optional[ClassIndex] = None,
        telemetry=None,
    ) -> None:
        self.table = table
        self.device = device
        self.index = index
        self.telemetry = telemetry if telemetry is not None else _NULL_TEL
        self._dev: Optional[EncodedFilters] = None
        self._synced_capacity = 0
        self._dev_meta: Optional[ClassMeta] = None
        self._dev_slots: Optional[SlotArrays] = None
        self._dev_residual: Optional[jnp.ndarray] = None
        self.fanout: Optional[fanout_ops.FanoutDeviceState] = None
        # chaos fault seam (emqx_tpu/chaos/faults.py): one attribute
        # read per sync when absent
        self.fault_injector = None
        # transfer chunk cap (ops/transfer.chunk_hits): bounds the
        # compacted-pair result buffers to what the link streams in
        # one RTT; None = unbounded (the exact-size escalation retry
        # keeps correctness either way)
        self.transfer_chunk_hits: Optional[int] = None

    def attach_fanout(self, store: fanout_ops.DestStore) -> None:
        """Mirror a CSR destination store on this device — the
        resolve-side counterpart of the filter mirror, same sync
        discipline (ops/fanout.FanoutDeviceState)."""
        self.fanout = fanout_ops.FanoutDeviceState(
            store, device=self.device, telemetry=self.telemetry
        )

    def _put(self, a: np.ndarray) -> jnp.ndarray:
        a = np.ascontiguousarray(a)
        return jax.device_put(a, self.device) if self.device is not None else jnp.asarray(a)

    def _upload_full(self) -> None:
        snap = self.table.snapshot()
        self._dev = EncodedFilters(*(self._put(a) for a in snap))
        self._synced_capacity = self.table.capacity

    def _sync_index(self) -> None:
        ix = self.index
        assert ix is not None
        if ix.meta_dirty or self._dev_meta is None:
            # upload only the pow2-packed active-class prefix: kernel
            # work is B x C x probes, so C must track the live class
            # count, not the budget (see ClassIndex.active_hi)
            self._dev_meta = ClassMeta(
                *(self._put(np.array(a)) for a in ix.packed_meta())
            )
            ix.meta_dirty = False
        if ix.rebuilt or self._dev_slots is None:
            ix.dirty_slots.clear()
            self._dev_slots = SlotArrays(*(self._put(np.array(a)) for a in ix.slots))
            ix.rebuilt = False
        elif ix.dirty_slots:
            dirty = np.unique(np.asarray(ix.dirty_slots, np.int32))
            ix.dirty_slots.clear()
            idx = pad_pow2_batches(dirty, SYNC_BATCH_SIZE)
            self.telemetry.record_shape(
                "_scatter_slots", (idx.shape[0], len(ix.slots.fp))
            )
            self._dev_slots = _scatter_slots(
                self._dev_slots,
                jnp.asarray(idx),
                jnp.asarray(ix.slots.fp[idx]),
                jnp.asarray(ix.slots.bucket[idx]),
                jnp.asarray(ix.slots.probe[idx // hash_ops.BUCKET_W]),
            )
        if ix.residual_dirty or self._dev_residual is None or (
            self._dev_residual.shape[0] != self.table.capacity
        ):
            mask = np.zeros(self.table.capacity, bool)
            if ix.residual_rows:
                mask[list(ix.residual_rows)] = True
            self._dev_residual = self._put(mask)
            ix.residual_dirty = False

    def hash_state(self) -> Tuple[ClassMeta, SlotArrays]:
        assert self._dev_meta is not None and self._dev_slots is not None
        return self._dev_meta, self._dev_slots

    def residual_filters(self) -> EncodedFilters:
        """EncodedFilters view whose active mask covers only residual
        (budget-overflow) rows — input to the dense fallback kernel."""
        assert self._dev is not None and self._dev_residual is not None
        return self._dev._replace(active=self._dev_residual)

    def sync(self) -> int:
        """Bring device state up to date; returns rows written."""
        fi = self.fault_injector
        if fi is not None:
            fi.check("sync")
        tel = self.telemetry
        t0 = tel.clock()
        pending = len(self.table.dirty)
        n, full = self._sync_impl()
        if tel.enabled and (n or full):
            tel.record_sync(
                rows=n, seconds=tel.clock() - t0, pending=pending, full=full
            )
            tel.observe_device_table(self)
        return n

    def _sync_impl(self) -> Tuple[int, bool]:
        """(rows written, was a full re-upload)."""
        t = self.table
        if self._dev is None or t.grew or t.capacity != self._synced_capacity:
            n = len(t.dirty)
            t.drain_dirty()
            self._upload_full()
            if self.index is not None:
                self._sync_index()
            return n, True
        dirty = t.drain_dirty()
        total = len(dirty)
        if total == 0:
            if self.index is not None:
                self._sync_index()
            return 0, False
        # pad to [n_batches, K] via the shared sync shape discipline
        # (ops.table.pad_pow2_batches: idempotent padding, pow2 batch
        # count so recompiles stay log-bounded)
        rows = pad_pow2_batches(dirty, SYNC_BATCH_SIZE)
        self.telemetry.record_shape(
            "_scatter_rows", (rows.shape[0], t.capacity, t.max_levels)
        )
        self._dev = _scatter_rows(
            self._dev,
            jnp.asarray(rows),
            jnp.asarray(t.words[rows]),
            jnp.asarray(t.prefix_len[rows]),
            jnp.asarray(t.has_hash[rows]),
            jnp.asarray(t.root_wild[rows]),
            jnp.asarray(t.active[rows]),
        )
        if self.index is not None:
            self._sync_index()
        return total, False

    def filters(self) -> EncodedFilters:
        assert self._dev is not None, "sync() before matching"
        return self._dev

    def invalidate(self) -> None:
        """Drop the device copy: the next sync uploads it in full."""
        self._dev = self._dev_meta = self._dev_slots = None
        self._dev_residual = None

    def shape_key(self) -> tuple:
        """What the match and churn-sync kernels compile for, read from
        the host tables: the next sync uploads arrays of these shapes,
        so a new key means new XLA shapes."""
        ix = self.index
        return (
            self.table.capacity,
            None if ix is None else (
                ix.packed_len(), len(ix.slots.fp), bool(ix.residual_rows),
            ),
            self.transfer_chunk_hits,
        )

    def warmup_deltas(self) -> int:
        """Pre-trace the churn-sync scatters (row, slot) at their two
        smallest pow2 batch counts, re-applying row/slot 0's current
        host truth (a no-op write), so a serve-time subscribe wave
        syncs without compiling. Requires a completed sync(); returns
        the number of kernels warmed."""
        if self._dev is None:
            return 0
        t = self.table
        tel = self.telemetry
        warmed = 0
        for n_b in (1, 2):
            rows = np.zeros((n_b, SYNC_BATCH_SIZE), np.int32)
            tel.record_shape("_scatter_rows", (n_b, t.capacity, t.max_levels))
            self._dev = _scatter_rows(
                self._dev,
                jnp.asarray(rows),
                jnp.asarray(t.words[rows]),
                jnp.asarray(t.prefix_len[rows]),
                jnp.asarray(t.has_hash[rows]),
                jnp.asarray(t.root_wild[rows]),
                jnp.asarray(t.active[rows]),
            )
            warmed += 1
            ix = self.index
            if ix is None or self._dev_slots is None:
                continue
            tel.record_shape("_scatter_slots", (n_b, len(ix.slots.fp)))
            self._dev_slots = _scatter_slots(
                self._dev_slots,
                jnp.asarray(rows),
                jnp.asarray(ix.slots.fp[rows]),
                jnp.asarray(ix.slots.bucket[rows]),
                jnp.asarray(ix.slots.probe[rows // hash_ops.BUCKET_W]),
            )
            warmed += 1
        return warmed

    # --- unified batched-match surface -------------------------------
    # The SAME begin/finish contract ShardedDeviceTable exposes, so the
    # Router pipelines one code path over both table kinds instead of
    # maintaining parallel single-device/mesh implementations (the
    # SNIPPETS one-mesh-context shape). Every begin LAUNCHES its
    # kernel and immediately starts the device->host copy of the
    # compacted result buffers (ops/transfer.FetchTicket), so batch
    # N's transfer rides under batch N+1's encode+launch; the finish
    # half pays only the residual wait. Handles carry the ticket as
    # their LAST element (the engine's readiness probe relies on it).

    def _cap_hits(self, mh: int) -> int:
        cap = self.transfer_chunk_hits
        if cap is not None and mh > cap >= 1024:
            # floor-pow2 of the chunk budget: shapes stay log-bounded
            mh = 1 << (cap.bit_length() - 1)
        return mh

    def match_hash_begin(self, enc: match_ops.PackedTopics):
        """Launch the pattern-class hash kernel + begin the result
        transfer; no host fetch is forced. One buffer crosses the link
        each way: the packed topics in, the packed result out. Returns
        an opaque handle for match_hash_finish (ticket last)."""
        meta, slots = self.hash_state()
        b = int(enc.ids.shape[0])
        mh = self._cap_hits(max(1024, _next_pow2(2 * b)))
        shape = (b, int(meta.plen.shape[0]), int(slots.fp.shape[0]))
        self.telemetry.record_shape("match_ids_hash", shape + (mh,))
        dev = hash_ops.match_ids_hash(meta, slots, enc, max_hits=mh)
        prev = STAGE_MARK.enter("ticket_start")
        ticket = transfer_ops.start_fetch((dev,), self.telemetry)
        STAGE_MARK.leave(prev)
        self.telemetry.count("transfer_buffers_total", len(enc) + 1)
        return (enc, mh, shape, ticket)

    def match_hash_finish(self, pending):
        """Force a begun hash match, escalating once on compaction
        overflow. Returns (ti, bi, amb): candidate arrays sliced to
        the true hit count — entries with bi < 0 (phase-2 rejects) or
        ti beyond the live batch (pow2 padding) are the caller's to
        skip, same contract as the sharded finish."""
        enc, mh, shape, ticket = pending
        ti, bi, total, amb = hash_ops.split_hash_result(ticket.wait()[0], mh)
        total = int(total)
        if total > mh:
            tel = self.telemetry
            tel.count("hash_overflow_retries_total")
            mh = _next_pow2(total)
            tel.record_shape("match_ids_hash", shape + (mh,))
            meta, slots = self.hash_state()
            dev = hash_ops.match_ids_hash(meta, slots, enc, max_hits=mh)
            out = transfer_ops.start_fetch((dev,), tel).wait()[0]
            tel.count("transfer_buffers_total", len(enc) + 1)
            ti, bi, _t, amb = hash_ops.split_hash_result(out, mh)
        return ti[:total], bi[:total], int(amb)

    def match_ids_begin(self, enc: match_ops.PackedTopics, residual: bool = False):
        """Launch the dense compaction kernel (full table, or the
        residual unclassed rows) + begin the result transfer. Same
        handle contract as match_hash_begin. The dense kernel takes
        the batch's three fields, as views of the packed buffer."""
        enc = enc.fields()
        filters = self.residual_filters() if residual else self.filters()
        b = int(enc.ids.shape[0])
        if residual:
            mh = self._cap_hits(max(1024, _next_pow2(2 * b)))
        else:
            mh = self._cap_hits(max(4096, _next_pow2(4 * b)))
        shape = (b, int(filters.words.shape[0]))
        self.telemetry.record_shape("match_ids", shape + (mh,))
        dev = match_ops.match_ids(filters, enc, max_hits=mh)
        prev = STAGE_MARK.enter("ticket_start")
        ticket = transfer_ops.start_fetch(dev, self.telemetry)
        STAGE_MARK.leave(prev)
        self.telemetry.count("transfer_buffers_total", len(enc) + len(dev))
        return (enc, filters, mh, shape, ticket)

    def match_ids_finish(self, pending):
        """Force a begun dense match, escalating once on overflow.
        Returns (ti, ri) valid-pair arrays — ti may include pow2
        batch-padding topic indices the caller drops."""
        enc, filters, mh, shape, ticket = pending
        ti, ri, total = ticket.wait()
        total = int(total)
        if total > mh:
            tel = self.telemetry
            tel.count("escalations_total")
            mh = _next_pow2(total)
            tel.record_shape("match_ids", shape + (mh,))
            dev = match_ops.match_ids(filters, enc, max_hits=mh)
            ti, ri, _t = transfer_ops.start_fetch(dev, tel).wait()
            tel.count("transfer_buffers_total", len(enc) + len(dev))
        return np.asarray(ti)[:total], np.asarray(ri)[:total]


class _PendingMatch:
    """An in-flight batched match: kernels LAUNCHED, results not yet
    fetched. Produced by Router.match_filters_begin, consumed exactly
    once (in begin order) by Router.match_filters_finish. Holding one
    of these while encoding/dispatching the next batch is what lets
    host work overlap device execution — JAX dispatch is asynchronous,
    so the arrays stored here are promises, not data."""

    __slots__ = (
        "topics",       # the sub-batch actually sent to the kernels
        "enc",          # PackedTopics of `topics` (pow2-padded)
        "out",          # per-sub-topic result lists (exact-deep prefilled)
        "mode",         # cached | host | hash | dense
        "gen",          # router generation captured before the kernels
        "full_out",     # full-batch skeleton when the match cache fronted it
        "sub_idx",      # index of each sub-topic within the original batch
        "span",         # sentinel StageSpan (or None): per-stage publish
                        # latency attribution for sampled batches
        # begin handles from the unified device-table surface (single
        # device and mesh alike); each carries its FetchTicket as the
        # last element, so readiness is a handle[-1].ready() probe
        "hash_pending",      # match_hash_begin handle
        "hash_elapsed",      # host seconds spent launching the hash leg
        "residual_pending",  # match_ids_begin(residual=True) handle
        "residual_elapsed",
        "dense_pending",     # match_ids_begin handle (no-index path)
        "dense_elapsed",
    )

    def __init__(self) -> None:
        for s in self.__slots__:
            setattr(self, s, None)


class Router:
    """Topic/filter -> dests with exact/wildcard split and device
    offload for batched wildcard matching."""

    def __init__(
        self,
        max_levels: int = 16,
        device=None,
        use_hash_index: bool = True,
        mesh=None,
        telemetry=None,
        mesh_min_rows_per_shard: int = 0,
    ) -> None:
        """With `mesh` (a jax.sharding.Mesh), the wildcard table lives
        SUB-SHARDED across the mesh and batched matching runs the
        PRODUCTION pattern-class cuckoo kernel with its slot table
        bucket-partitioned over the 'sub' axis
        (parallel/sharded_match.py make_sharded_hash_kernel) — the
        broker's publish path on a pod; the dense partitioned kernel
        serves only residual (unclassed) rows, exactly as on one
        chip. `mesh_min_rows_per_shard` > 0 enables the admission
        knob: while the table holds fewer rows per shard than this,
        serving degrades to the mesh's first device (small tables
        never amortize mesh launch+combine overhead)."""
        self.max_levels = max_levels
        # route-transition callbacks: fired when a (filter, dest) pair
        # first appears / finally disappears — the seam the cluster
        # layer announces route writes through (the sync_route analog,
        # emqx_broker.erl:778-795)
        self.on_dest_added = None
        self.on_dest_removed = None
        # exact topics: dest store (host hash for the single-publish
        # cut-through) + device rows for the batched path
        self._exact: Dict[str, Dict[Dest, int]] = {}
        self._exact_row: Dict[str, int] = {}
        self._exact_deep: Set[str] = set()
        # wildcard filters: ONE device row per DISTINCT filter; the
        # dest fan lives host-side per filter. This is the reference's
        # route-table/subscriber-table split (emqx_router ?ROUTE_TAB
        # keyed by topic vs emqx_broker ?SUBSCRIBER ets) — a 100k-wide
        # fanout is one row in HBM, not 100k copies of the filter.
        self.table = FilterTable(max_levels=max_levels)
        self._trie = TopicTrie()  # host cut-through; ids are table rows
        # trie writes from batched route adds are DEFERRED and drained
        # before the next host-path read (the reference has the same
        # write-visibility seam: subscribers wait on the router-syncer
        # flush, emqx_broker.erl:187-193). The device path never reads
        # the host trie, so storms skip the per-route trie walk.
        # parallel lists (filter words-or-string, row) — two bare
        # appends beat a tuple allocation per route on the storm path
        self._trie_pending_f: List[object] = []
        self._trie_pending_r: List[int] = []
        # True when the pending op list was DROPPED (write-only storms
        # outgrew it — see _trie_gc): the next host read rebuilds the
        # trie from live state instead of replaying. The counter
        # amortizes the single-row delete path's backlog check.
        self._trie_stale = False
        self._trie_gc_tick = 0
        self._wild: Dict[str, Dict[Dest, int]] = {}
        self._filter_row: Dict[str, int] = {}
        # row -> filter string, indexed by table row (None = free); a
        # flat list because rows are dense ints, the match path reads
        # it per candidate, and the native core writes it raw
        self._row_filter: List[Optional[str]] = [None] * self.table.capacity
        # filters too deep for the flattened table: host-only, in their
        # own depth-unlimited trie (ids are filter strings)
        self._deep: Dict[str, Dict[Dest, int]] = {}
        self._deep_trie = TopicTrie()
        # route-set generation: FilterTable.generation covers every
        # table-resident mutation; this aux counter covers the host-only
        # stores (deep filters, too-deep exact topics) the table can't
        # see. match caches stamp entries with generation and lazily
        # discard on mismatch — no O(n) clears on the mutation path.
        self._aux_gen = 0
        # generation-stamped topic -> filters cache fronting the device
        # path (enable_match_cache); None keeps the kernel path bare
        self.match_cache: Optional[match_ops.GenMatchCache] = None
        self.mesh = mesh
        # kernel telemetry: always-on by default (obs/kernel_telemetry).
        # Pass NULL (or any NullKernelTelemetry) to run the hot path
        # with bound no-op hooks instead.
        self.telemetry = (
            telemetry if telemetry is not None else KernelTelemetry()
        )
        if mesh is not None:
            from ..parallel.sharded_match import ShardedDeviceTable

            self.index = ClassIndex(max_levels) if use_hash_index else None
            self.device_table = ShardedDeviceTable(
                self.table, mesh, index=self.index,
                telemetry=self.telemetry,
            )
            self.device_table.min_rows_per_shard = mesh_min_rows_per_shard
        else:
            self.index = ClassIndex(max_levels) if use_hash_index else None
            self.device_table = DeviceTable(
                self.table, device=device, index=self.index,
                telemetry=self.telemetry,
            )
        # CSR destination store — the resolve half of the publish path
        # (ops/fanout.py): one segment of (client, packed subopts)
        # edges per table-resident filter row, fed by the same route
        # transitions that maintain the dest dicts so segment order ==
        # dict insertion order (the oracle's iteration order). Filters
        # without a row (deep-trie / too-deep exacts) stay host-only and
        # resolve_fanout_begin refuses them — identical escalation
        # shape to the match path.
        self.dest_store = fanout_ops.DestStore(
            row_capacity=self.table.capacity
        )
        self.device_table.attach_fanout(self.dest_store)
        # live-suboption seam for lazy segment rebuilds: the Broker
        # installs `(flt, dest) -> (SubOpts, session) | None`; None
        # (standalone routers) stores every client edge as SKIP, which
        # matches the oracle (no suboption -> not in the plan)
        self.fanout_opts_lookup = None
        # device failure domain (broker/dispatch_engine.py breaker +
        # emqx_tpu/chaos/faults.py): `fault_injector` is the chaos seam
        # at the XLA boundary (None costs one attribute read per leg);
        # `device_suspended` routes every batched match and fanout
        # resolve through the host walk — degraded-but-correct service
        # while the circuit breaker is open.
        self.fault_injector = None
        self.device_suspended = False
        # shard failure domain (ShardedDeviceTable only): sub-axis
        # columns whose bucket slice is answered by the host overlay in
        # match_filters_finish while their chip is sick — the OTHER
        # shards keep serving on device (contrast device_suspended,
        # which forfeits the whole mesh)
        self._suspended_shards: Set[int] = set()
        # shadow-audit quarantine (obs/sentinel.py): filters whose
        # device rows diverged from the host oracle. While quarantined
        # a filter is answered by the host walk (overlay in
        # match_filters_finish, refusal in resolve_fanout_begin); its
        # row is re-marked dirty so the next table sync rewrites device
        # state from host truth, which auto-unquarantines (counted).
        self._quarantined: Dict[str, Optional[int]] = {}
        # native churn core state (native/speedups.cc): the handle
        # caches the C side's entire attribute/buffer fetch so a
        # ONE-pair add/delete rides the same core as a 1000-row storm
        # with ~zero per-call setup. headroom counts how many fresh
        # rows the last _reserve_native pre-grew for; reserve (and the
        # post-rebuild path) recreate the handle because growth
        # REPLACES the numpy arrays the handle's buffers pin.
        # _churn_reserve is the pre-grow chunk for single-row adds
        # (broker.perf.tpu_churn_reserve).
        self._churn_reserve = 512
        self._native_headroom = 0
        self._churn_handle = None
        # bound C entry points (None without the toolchain): one attr
        # read on the single-pair hot paths instead of a module lookup
        sp = _speedups.load()
        self._add_core = sp.add_route_core if sp is not None else None
        self._del_core = sp.del_route_core if sp is not None else None

    @property
    def generation(self) -> int:
        """Monotonic route-set generation: bumps on every mutation that
        can change which filters match a topic. The validity stamp for
        GenMatchCache entries and the broker's fanout-plan cache."""
        return self.table.generation + self._aux_gen

    def enable_match_cache(
        self, capacity: int = 8192
    ) -> match_ops.GenMatchCache:
        """Attach (or resize) the generation-stamped topic->filters
        cache in front of the batched match path. Idempotent for a
        matching capacity; hot topics then skip the kernel entirely."""
        if self.match_cache is None or self.match_cache.capacity != capacity:
            self.match_cache = match_ops.GenMatchCache(capacity)
        return self.match_cache

    # --- shadow-audit quarantine (obs/sentinel.py) ----------------------

    def quarantine_filters(self, filters: Sequence[str]) -> int:
        """Move `filters` to the host-walk fallback: the batched match
        path overlays their answers from the host state and the fanout
        kernel refuses their rows, until the next table sync rewrites
        the rows from host truth. Returns newly quarantined count."""
        tel = self.telemetry
        added = 0
        for f in filters:
            if f in self._quarantined:
                continue
            row = self._fanout_row(f)
            self._quarantined[f] = row
            if row is not None:
                # force a device rewrite of this row at the next sync —
                # content is unchanged host-side, so no generation bump
                # from the table itself
                self.table.dirty.append(row)
                # dest segment rebuilds from the dest dict at the next
                # resolve (post-unquarantine), through the live
                # suboption seam — same lazy path as the storm feed
                self.dest_store.pending_rows.add(row)
            added += 1
        if added:
            # cached match results were populated from the now-suspect
            # device output: stale them all via the aux generation
            self._aux_gen += 1
            # the divergence localizes to filters, not to WHICH device
            # array decayed — re-upload the whole hash-index device
            # state (meta + slots + residual mask) at the next sync,
            # not just the row scatter, so a corrupt slot table heals
            # too. Full index upload is the route-churn rebuild path,
            # so the cost is bounded and already shape-stable.
            ix = self.index
            if ix is not None:
                ix.meta_dirty = True
                ix.rebuilt = True
                ix.residual_dirty = True
            if tel.enabled:
                tel.count("audit_quarantine_total", added)
                tel.set_gauge(
                    "audit_quarantined_filters", len(self._quarantined)
                )
        return added

    def quarantined_filters(self) -> List[str]:
        return sorted(self._quarantined)

    def _quarantine_overlay(
        self, topics: Sequence[str], out: List[List[str]]
    ) -> None:
        """Rewrite kernel answers for quarantined filters from host
        truth: a filter the device wrongly dropped is re-added, one it
        wrongly surfaced is removed. Runs only while the quarantine set
        is non-empty — the steady-state cost is one falsy test in
        match_filters_finish. Covers batches LAUNCHED against the
        corrupt table that finish after the audit quarantined it (the
        pipeline's in-flight window)."""
        q = []
        for f in self._quarantined:
            routed = (
                f in self._wild or f in self._deep or f in self._exact
            )
            q.append((f, topic_mod.words(f), routed))
        served = 0
        for i, t in enumerate(topics):
            tw = topic_mod.words(t)
            lst = out[i]
            for f, fw, routed in q:
                hit = routed and topic_mod.match(tw, fw)
                if hit and f not in lst:
                    lst.append(f)
                elif not hit and f in lst:
                    lst.remove(f)
            served += 1
        tel = self.telemetry
        if tel.enabled and served:
            tel.count("audit_quarantine_overlay_total", served)

    def _maybe_unquarantine(self) -> None:
        """Called after a device sync: once the dirtied rows drained,
        the device rows were rewritten from host truth — the clean
        table sync that ends the quarantine."""
        if self.table.dirty:
            return  # quarantined rows not yet synced (mid-storm)
        n = len(self._quarantined)
        self._quarantined.clear()
        self._aux_gen += 1
        tel = self.telemetry
        if tel.enabled:
            tel.count("audit_unquarantine_total", n)
            tel.set_gauge("audit_quarantined_filters", 0)

    # --- device failure domain (dispatch-engine circuit breaker) --------

    def suspend_device(self) -> bool:
        """Open-breaker mode: every batched match and fanout resolve
        answers from host truth until resume_device(). Returns True on
        the closed->open transition. The sync delta stream stops; the
        dirty backlog is dropped once it outgrows the table (see the
        host leg of match_filters_begin) because recovery re-uploads
        full state anyway."""
        if self.device_suspended:
            return False
        self.device_suspended = True
        tel = self.telemetry
        if tel.enabled:
            tel.count("device_suspends_total")
            tel.set_gauge("device_suspended", 1)
        return True

    def resume_device(self) -> None:
        """Close-breaker mode: device serving resumes. Callers run
        device_resync() + a verified canary FIRST — resuming against
        stale device state would serve the corruption the suspension
        existed to avoid."""
        if not self.device_suspended:
            return
        self.device_suspended = False
        tel = self.telemetry
        if tel.enabled:
            tel.count("device_resumes_total")
            tel.set_gauge("device_suspended", 0)

    def device_resync(self) -> None:
        """Force the next sync to re-upload FULL device state from host
        truth: table snapshot, index meta/slots/residual, and the
        fanout CSR mirror — the quarantine clean-sync machinery reused
        by breaker recovery, where an outage dropped the delta stream
        and no scatter replay can be trusted."""
        dt = self.device_table
        dt._dev = None  # _sync_impl's full-upload branch (both tables)
        ix = self.index
        if ix is not None:
            ix.meta_dirty = True
            ix.rebuilt = True
            ix.residual_dirty = True
        fan = getattr(dt, "fanout", None)
        if fan is not None:
            fan._seg_off = None  # FanoutDeviceState full-upload branch
        # cached match entries may have been populated host-side during
        # the outage; stale them so the recovered device re-earns trust
        # under the sentinel's audit rather than hiding behind hits
        self._aux_gen += 1
        if self.telemetry.enabled:
            self.telemetry.count("device_resyncs_total")

    def canary_match(self, topics: Sequence[str]) -> List[List[str]]:
        """Device-path probe for the breaker's recovery loop: run the
        batched kernels for `topics` IGNORING suspension and the match
        cache (the probe must exercise the link and the kernels, not a
        dict). Raises on any device fault; returns per-topic filter
        lists for the caller to compare against match_filters."""
        prev = self.device_suspended
        cache = self.match_cache
        self.device_suspended = False
        self.match_cache = None
        try:
            return self.match_filters_finish(
                self.match_filters_begin(topics)
            )
        finally:
            self.device_suspended = prev
            self.match_cache = cache

    def match_filters_host(self, p: "_PendingMatch") -> List[List[str]]:
        """Host re-serve of a begun batch whose device leg failed:
        answer every sub-topic from host truth (the oracle the device
        path is bit-identical to by contract) and merge into the cached
        prefix — correct regardless of what the kernels did, so the
        dispatch engine's failover hands publishers exactly what a
        healthy device would have."""
        out = [self.match_filters(t) for t in p.topics]
        tel = self.telemetry
        if tel.enabled and p.topics:
            tel.count("host_fallback_total")
        if p.full_out is None:
            return out
        full = p.full_out
        for j, i in enumerate(p.sub_idx):
            full[i] = out[j]
        return full

    # --- shard failure domain (ShardedDeviceTable chip loss) -------------

    def suspend_shard(self, shard: int) -> bool:
        """Open the breaker for ONE sub-axis column: topics keep going
        through the device kernels, but answers owned by the sick
        shard's row/bucket slice are corrected from host truth by the
        overlay in match_filters_finish — the same discipline as the
        quarantine overlay, scoped by ownership instead of by filter.
        Falls back to whole-device suspension when the table has no
        mesh. Returns True on the closed->open transition."""
        dt = self.device_table
        if getattr(dt, "mesh", None) is None:
            return self.suspend_device()
        if shard in self._suspended_shards:
            return False
        self._suspended_shards.add(shard)
        # match-cache entries may hold the sick shard's answers
        self._aux_gen += 1
        tel = self.telemetry
        if tel.enabled:
            tel.count("shard_suspends_total")
            tel.set_gauge("shards_suspended", len(self._suspended_shards))
        return True

    def resume_shard(self, shard: int) -> None:
        if shard not in self._suspended_shards:
            return
        self._suspended_shards.discard(shard)
        self._aux_gen += 1
        tel = self.telemetry
        if tel.enabled:
            tel.count("shard_resumes_total")
            tel.set_gauge("shards_suspended", len(self._suspended_shards))

    def _shard_owners(self, flt: str) -> Set[int]:
        """The sub-axis columns whose device state can answer (or
        wrongly drop) `flt` under the CURRENT mesh: the shard holding
        its table row (dense/residual leg) plus — for classed filters —
        the shard holding its bucket's cuckoo slot (the hash kernel
        probes by slot position, which cuckoo may have placed under
        either hash position)."""
        dt = self.device_table
        owners: Set[int] = set()
        row = self._fanout_row(flt)
        if row is None:
            return owners  # deep/host-resident: device never answers it
        owners.add(dt.shard_of_row(row))
        ix = self.index
        if ix is not None and row < len(ix._row_bucket):
            bid = int(ix._row_bucket[row])
            if bid >= 0:
                slot = int(ix._bkt_slot[bid])
                if slot >= 0:
                    owners.add(dt.shard_of_slot(slot))
        return owners

    def _shard_overlay(
        self, topics: Sequence[str], out: List[List[str]]
    ) -> None:
        """Rewrite kernel answers owned by suspended shards from host
        truth: drop every surfaced filter a sick shard served, then
        re-add from the host walk exactly the matches a sick shard
        owns. O(answer + host-match) per topic — no enumeration of the
        suspect slice, which can be a million rows."""
        sus = self._suspended_shards
        owners = self._shard_owners
        served = 0
        for i, t in enumerate(topics):
            lst = out[i]
            keep = [f for f in lst if not (owners(f) & sus)]
            truth = [
                f for f in self.match_filters(t) if owners(f) & sus
            ]
            if truth or len(keep) != len(lst):
                out[i] = keep + truth
            served += 1
        tel = self.telemetry
        if tel.enabled and served:
            tel.count("shard_overlay_total", served)

    def probe_shard(self, shard: int) -> None:
        """Direct link probe of one (possibly evacuated) chip for the
        shard breaker's recovery loop: raises while the chip's fault is
        still programmed. The injector's shard_probe leg deliberately
        ignores lost_shards — probing the evacuated chip is the point."""
        fi = self.fault_injector
        if fi is not None:
            # literal = chaos.faults.SHARD_PROBE_LEG (importing chaos
            # here would cycle through broker -> models)
            fi.check("shard_probe", shard=shard)

    def evacuate_shard(self, shard: int) -> bool:
        """Live evacuation: remap the lost shard's row/bucket slices
        onto the surviving chips (new shard-map generation), re-upload
        from host truth through the full-resync machinery, and lift the
        host overlay — N-1 chips serving the whole table on device.
        The EMQX analog is node evacuation (emqx_eviction_agent): move
        live routing state off the failing member, keep serving."""
        dt = self.device_table
        if getattr(dt, "mesh", None) is None:
            return False  # single-device table: nothing to re-shard
        if not dt.evacuate_shard(shard):
            return False
        self._aux_gen += 1
        tel = self.telemetry
        if tel.enabled:
            tel.count("shard_evacuations_total")
            tel.set_gauge("shards_lost", len(dt.lost_shards))
        dt.sync()  # full re-upload onto the survivor mesh
        self.resume_shard(shard)
        return True

    def rebalance_shard(self, shard: int) -> bool:
        """Rebalance-back: re-admit a recovered chip (restore the full
        mesh layout) and re-upload from host truth. Callers verify the
        chip first (probe + canary) — the emqx_node_rebalance analog."""
        dt = self.device_table
        if getattr(dt, "mesh", None) is None:
            return False
        if not dt.restore_shard(shard):
            return False
        self._aux_gen += 1
        tel = self.telemetry
        if tel.enabled:
            tel.count("shard_rebalances_total")
            tel.set_gauge("shards_lost", len(dt.lost_shards))
        dt.sync()
        return True

    # --- chaos corruption seam (emqx_tpu/chaos) --------------------------

    def chaos_corrupt_rows(self, filters: Sequence[str]) -> int:
        """Fault injection: empty the DEVICE copy of the given filters'
        cuckoo slots while host truth stays pristine — the device-row
        corruption leg of the chaos scenario engine. The hash kernel
        stops surfacing exactly these filters, so a served publish on a
        matching topic diverges from the host oracle and the sentinel's
        detect→quarantine→clean-sync chain must engage. Scoped: every
        other filter keeps serving correctly. Returns slots corrupted
        (0 when a filter is host-resident/unclassed or the device state
        isn't built yet — callers warm the table first). The quarantine
        recovery sync re-uploads index state, which heals this."""
        ix = self.index
        dt = self.device_table
        sl = getattr(dt, "_dev_slots", None)
        if ix is None or sl is None:
            return 0
        slots = []
        for f in filters:
            row = self._fanout_row(f)
            if row is None or row >= len(ix._row_bucket):
                continue
            b = int(ix._row_bucket[row])
            if b < 0:
                continue  # residual/unclassed: dense leg, not slotted
            slots.append(int(ix._bkt_slot[b]))
        if not slots:
            return 0
        bucket = np.asarray(sl.bucket).copy()
        bucket[slots] = -1
        dt._dev_slots = SlotArrays(
            sl.fp, jax.device_put(bucket, sl.bucket.sharding), sl.probe
        )
        if self.telemetry.enabled:
            self.telemetry.count("chaos_corrupt_slots_total", len(slots))
        return len(slots)

    def chaos_corrupt_slots(self) -> int:
        """Fault injection: full device slot-table decay — every bucket
        id becomes -1, so the hash kernel stops surfacing every classed
        filter (the whole-table memory-decay failure mode the sentinel
        suite injects by hand). Returns slots decayed."""
        dt = self.device_table
        sl = getattr(dt, "_dev_slots", None)
        if sl is None:
            return 0
        arr = np.asarray(sl.bucket)
        bad = np.full(arr.shape, -1, arr.dtype)
        dt._dev_slots = SlotArrays(
            sl.fp, jax.device_put(bad, sl.bucket.sharding), sl.probe
        )
        if self.telemetry.enabled:
            self.telemetry.count("chaos_corrupt_slots_total", arr.size)
        return int(arr.size)

    # --- CSR dest-store feed (the device ?SUBSCRIBER mirror) ------------

    def _fanout_row(self, flt: str) -> Optional[int]:
        row = self._filter_row.get(flt)
        if row is None:
            row = self._exact_row.get(flt)
        return row

    def _fanout_added(self, flt: str, dest: Dest) -> None:
        """First-appear route transition -> CSR edge append, in dest
        dict order. Tuple dests (shared groups, cluster composites) are
        stored client-less with the shared bit; str dests start SKIP
        until the broker's fanout_note_opts upgrade arrives."""
        row = self._fanout_row(flt)
        if row is None:
            return  # deep/host-resident filter: resolve falls back
        ds = self.dest_store
        ds.ensure_rows(self.table.capacity)
        if isinstance(dest, str):
            ds.add(row, dest, fanout_ops.SKIP_BIT, flt)
        else:
            ds.add(row, dest, fanout_ops.SHARED_BIT, flt)

    def _fanout_add_batch(self, pairs_iter) -> None:
        """Storm-path feed: first-appear pairs only MARK their rows
        pending (~0.3us/route — the full eager segment bookkeeping cost
        a measured 2.4x insert-RPS regression on the native add_routes
        path). _fanout_flush rebuilds a pending row from its dest dict
        the first time a resolve needs it."""
        fr = self._filter_row
        xr = self._exact_row
        pending_add = self.dest_store.pending_rows.add
        for flt, dest in pairs_iter:
            row = fr.get(flt)
            if row is None:
                row = xr.get(flt)
                if row is None:
                    continue  # deep/host-resident: host fallback covers
            pending_add(row)

    def _fanout_flush(self, rows) -> None:
        """Rebuild any pending segments among `rows` from their dest
        dicts (dict order == oracle order) through the broker's live
        suboption seam — the lazy half of the storm feed."""
        ds = self.dest_store
        pending = ds.pending_rows
        if not pending:
            return
        lookup = self.fanout_opts_lookup
        rf = self._row_filter
        for row in rows:
            if row in pending:
                flt = rf[row]
                ds.set_row(row, flt, self.filter_dests(flt), lookup)
                pending.discard(row)

    def _fanout_removed(self, flt: str, dest: Dest) -> None:
        row = self._fanout_row(flt)
        if row is not None:
            self.dest_store.remove(row, dest)

    def fanout_note_opts(self, flt: str, client: str, opts, session) -> None:
        """Complete a subscribe on the CSR store: stamp the edge with
        its live suboption word/object and track the session object for
        the vectorized plan build. No-op for host-resident filters and
        for routes the broker never subscribed (node dests)."""
        row = self._fanout_row(flt)
        if row is not None:
            self.dest_store.set_opts(row, client, opts, session)

    # --- device-resolved fanout (the aggre/1 kernel) --------------------

    def resolve_fanout_begin(self, filters: Sequence[str], min_fan: int = 0):
        """Launch the dedup/max-QoS plan kernel for one matched filter
        set (in pairs order), or None when the set must resolve
        host-side: a host-resident filter in the set, a fan below
        `min_fan` (host walk is cheaper), an empty fan, or a fan beyond
        the kernel's packing cap — the same escalate-to-host shape as
        the match path's deep-trie leg.

        The `min_fan` gate reads the LIVE fan, the sizes of the filters'
        dest dicts (shared-group dests included), before touching the
        CSR store: a small-fan set neither rebuilds its pending rows nor
        reads `fan_of`, and its rows stay pending until a wide resolve
        needs them. A set whose live fan is below `min_fan` resolves on
        the host even where its segments, tombstones included, sum above
        it; the two paths give the same plan."""
        if not filters:
            return None
        if self.device_suspended:
            # breaker open: every plan resolves host-side until the
            # recovery canary verifies the re-uploaded device state
            if self.telemetry.enabled:
                self.telemetry.count("fanout_host_fallback_total")
            return None
        if self._quarantined:
            # a quarantined filter's dest segment is suspect: the whole
            # set resolves host-side until the clean sync clears it
            for f in filters:
                if f in self._quarantined:
                    if self.telemetry.enabled:
                        self.telemetry.count("fanout_host_fallback_total")
                        self.telemetry.count(
                            "audit_quarantine_resolve_refusals_total"
                        )
                    return None
        rows = []
        live = 0
        fr = self._filter_row
        xr = self._exact_row
        for f in filters:
            row = fr.get(f)
            if row is None:
                row = xr.get(f)
                if row is None:
                    if self.telemetry.enabled:
                        self.telemetry.count("fanout_host_fallback_total")
                    return None
                live += len(self._exact[f])
            else:
                live += len(self._wild[f])
            rows.append(row)
        if live < max(min_fan, 1):
            if self.telemetry.enabled:
                self.telemetry.count("fanout_small_fan_plans_total")
            return None
        self._fanout_flush(rows)
        fan = self.dest_store.fan_of(rows)
        if fan > fanout_ops.MAX_FAN:
            return None
        fi = self.fault_injector
        if fi is not None:
            fi.check("fanout_begin")
        return self.device_table.fanout.resolve_begin(rows, fan)

    def resolve_fanout_finish(self, handle):
        """Finish a begun resolve: fetch the winner edges, record the
        dedup ratio, and materialize the oracle-ordered (mem, other)
        plan — bit-identical to Broker._build_fanout_plan over the same
        host state."""
        fi = self.fault_injector
        if fi is not None:
            fi.check("fanout_finish")
        win, fan = self.device_table.fanout.resolve_finish(handle)
        tel = self.telemetry
        if tel.enabled:
            tel.count("fanout_device_plans_total")
            tel.set_gauge(
                "fanout_dedup_ratio", round(fan / max(1, len(win)), 6)
            )
        return self.dest_store.build_plan(win)

    # --- write path (emqx_router:do_add_route / do_delete_route) -------

    def _ensure_row_filter(self) -> None:
        """Keep the row->filter list sized to the table capacity."""
        rf = self._row_filter
        cap = self.table.capacity
        if len(rf) < cap:
            rf.extend([None] * (cap - len(rf)))

    def _reserve_native(self, n: int) -> None:
        """Pre-grow every structure up to `n` fresh rows could touch —
        table free rows, vocab refcount array, row->filter list, class
        index — so the C core can hold raw buffers for the whole call
        (no growth mid-call), then rebuild the churn handle over the
        (possibly replaced) arrays. Growth points move at most one
        reserve chunk earlier than the python path's; final sizes are
        identical (pow2)."""
        t = self.table
        while len(t._free) < n:
            t._grow()
        v = t.vocab
        v.ensure_refs(v._next + n * (t.max_levels + 1))
        self._ensure_row_filter()
        if self.index is not None:
            self.index.reserve(n, t.capacity)
        self._native_headroom = n
        self._churn_handle = _speedups.load().make_churn_handle(self)
        self._trie_gc()  # amortized backlog bound for single-row adds

    def _handle(self):
        """The churn-core capsule; built on demand (deletes need no
        reserve — they only append to the free lists)."""
        h = self._churn_handle
        if h is None:
            h = self._churn_handle = _speedups.load().make_churn_handle(
                self
            )
        return h

    def _drop_native_state(self) -> None:
        """Python-fallback mutations bypass the headroom accounting and
        may replace arrays the handle pins — drop both."""
        self._native_headroom = 0
        self._churn_handle = None

    def add_route(self, flt: str, dest: Dest) -> None:
        core = self._add_core
        if core is not None:
            # allocation-free single-pair C entry (the broker's
            # per-subscribe hot path), with ZERO per-call setup: the
            # reserve pre-pass runs once per _churn_reserve adds and
            # the churn handle carries the C side's whole
            # attribute/buffer fetch between calls; the generation
            # bump and the dest-store pending mark happen IN the core.
            # Flags: 1 fresh, 2 need_rebuild, 8 deep changed.
            if self._native_headroom < 1:
                self._reserve_native(self._churn_reserve)
            self._native_headroom -= 1
            flags = core(self._churn_handle, flt, dest)
            if flags:
                if flags & 8:
                    self._aux_gen += 1
                if flags & 2:
                    self.index._rebuild(self.index.n_buckets * 2)
                    self._churn_handle = _speedups.load().make_churn_handle(
                        self
                    )
                if flags & 1 and self.on_dest_added is not None:
                    self.on_dest_added(flt, dest)
            return
        self._drop_native_state()
        if not topic_mod.is_wildcard(flt):
            fresh_topic = flt not in self._exact
            dests = self._exact.setdefault(flt, {})
            fresh = dest not in dests
            dests[dest] = dests.get(dest, 0) + 1
            if fresh_topic:
                # exact topics ride the SAME device hash table as
                # wildcard-free classes (VERDICT r2 #3): one literal-
                # only skeleton per depth, so 10M exact topics cost
                # ~max_levels classes and the batched publish path
                # resolves them in the same kernel dispatch as
                # wildcards. Too-deep topics stay host-only (the same
                # FilterTooDeep degradation wildcards get).
                try:
                    row = self.table.add(flt)
                except FilterTooDeep:
                    self._exact_deep.add(flt)
                    self._aux_gen += 1
                else:
                    self._exact_row[flt] = row
                    self._ensure_row_filter()
                    self._row_filter[row] = flt
                    if self.index is not None:
                        self.index.add_row(row, self.table)
            if fresh:
                self._fanout_added(flt, dest)
                if self.on_dest_added is not None:
                    self.on_dest_added(flt, dest)
            return
        dests = self._wild.get(flt)
        if dests is None and flt in self._deep:
            dests = self._deep[flt]
        if dests is None:
            try:
                row = self.table.add(flt)
            except FilterTooDeep:
                dests = self._deep.setdefault(flt, {})
                self._deep_trie.insert(topic_mod.words(flt), flt)
                self._aux_gen += 1
            else:
                dests = self._wild.setdefault(flt, {})
                self._filter_row[flt] = row
                self._ensure_row_filter()
                self._row_filter[row] = flt
                self._trie_pending_f.append(self.table.filter_words(row))
                self._trie_pending_r.append(row)
                if self.index is not None:
                    self.index.add_row(row, self.table)
        fresh = dest not in dests
        dests[dest] = dests.get(dest, 0) + 1
        if fresh:
            self._fanout_added(flt, dest)
            if self.on_dest_added is not None:
                self.on_dest_added(flt, dest)

    def add_routes(self, pairs: Sequence[Tuple[str, Dest]]) -> None:
        """Batched add_route — the router-syncer write path. The
        reference flushes route writes in <=1000-op batches through
        emqx_router:do_batch (emqx_router_syncer.erl:57,
        emqx_router.erl:255-273); this is that batch entry: dest/dict
        bookkeeping stays per-pair, but NEW filters go through the
        vectorized table scatter + class-index bulk placement, which is
        what subscribe storms (reconnect waves) hit."""
        new_exact: List[str] = []
        new_exact_parts: List[List[str]] = []
        new_wild: List[str] = []
        new_wild_parts: List[List[str]] = []
        exact_t = self._exact
        wild_t = self._wild
        deep_t = self._deep
        ne_append = new_exact.append
        nep_append = new_exact_parts.append
        nw_append = new_wild.append
        nwp_append = new_wild_parts.append
        sp = _speedups.load()
        if sp is not None:
            # native one-pass path: reserve headroom for the batch (a
            # no-op when a prior reserve already covers it — the C core
            # holds raw buffer pointers, so nothing may grow mid-call),
            # then hand the whole batch to add_routes_core
            B = len(pairs)
            if self._native_headroom < B:
                self._reserve_native(max(B, self._churn_reserve))
            self._native_headroom -= B
            # generation bumps and dest-store pending marks happen in
            # the core; the aux generation (host-only deep stores)
            # stays a len-delta here
            deep0 = len(self._deep) + len(self._exact_deep)
            fresh, need_rebuild = sp.add_routes_core(
                self._churn_handle,
                pairs if isinstance(pairs, list) else list(pairs),
            )
            if len(self._deep) + len(self._exact_deep) != deep0:
                self._aux_gen += 1
            if need_rebuild:
                self.index._rebuild(self.index.n_buckets * 2)
                self._churn_handle = sp.make_churn_handle(self)
            if fresh:
                on_added = self.on_dest_added
                if on_added is not None:
                    for flt, dest in fresh:
                        on_added(flt, dest)
            return
        self._drop_native_state()
        # pure-python path (no toolchain):
        # scan — split each filter ONCE (the parts ride into add_bulk),
        # classify wildness by C-level list-contains, and register the
        # fresh dest dict immediately so in-batch duplicates dedup on
        # the same membership probe as cross-batch ones
        parts_all = [flt.split("/") for flt, _d in pairs]
        wildness = [("+" in ws or "#" in ws) for ws in parts_all]
        for (flt, _dest), ws, wild in zip(pairs, parts_all, wildness):
            if wild:
                if flt not in wild_t and flt not in deep_t:
                    wild_t[flt] = {}
                    nw_append(flt)
                    nwp_append(ws)
            elif flt not in exact_t:
                exact_t[flt] = {}
                ne_append(flt)
                nep_append(ws)
        idx_rows: List[int] = []
        idx_flts: List[str] = []
        if new_exact:
            rows = self.table.add_bulk(new_exact, new_exact_parts)
            self._ensure_row_filter()  # add_bulk may have grown capacity
            row_filter = self._row_filter
            exact_row = self._exact_row
            ir_append = idx_rows.append
            if_append = idx_flts.append
            for flt, row in zip(new_exact, rows):
                if row < 0:
                    self._exact_deep.add(flt)
                    self._aux_gen += 1
                else:
                    exact_row[flt] = row
                    row_filter[row] = flt
                    ir_append(row)
                    if_append(flt)
        if new_wild:
            rows = self.table.add_bulk(new_wild, new_wild_parts)
            self._ensure_row_filter()  # add_bulk may have grown capacity
            row_filter = self._row_filter
            filter_row = self._filter_row
            ir_append = idx_rows.append
            if_append = idx_flts.append
            tpf_append = self._trie_pending_f.append
            tpr_append = self._trie_pending_r.append
            for flt, row in zip(new_wild, rows):
                if row < 0:
                    # too deep for the flattened table: migrate the
                    # just-registered dest dict to the deep-trie store
                    deep_t[flt] = wild_t.pop(flt)
                    self._deep_trie.insert(topic_mod.words(flt), flt)
                    self._aux_gen += 1
                else:
                    filter_row[flt] = row
                    row_filter[row] = flt
                    tpf_append(flt)
                    tpr_append(row)
                    ir_append(row)
                    if_append(flt)
        if idx_rows and self.index is not None:
            self.index.add_rows(idx_rows, self.table, idx_flts)
        # dest bookkeeping per pair (duplicates in the batch included)
        on_added = self.on_dest_added
        fresh_pairs: List[Tuple[str, Dest]] = []
        fp_append = fresh_pairs.append
        for (flt, dest), wild in zip(pairs, wildness):
            if not wild:
                dests = exact_t[flt]
            else:
                dests = wild_t.get(flt)
                if dests is None:
                    dests = deep_t[flt]
            v = dests.get(dest)
            if v is None:
                dests[dest] = 1
                fp_append((flt, dest))
                if on_added is not None:
                    on_added(flt, dest)
            else:
                dests[dest] = v + 1
        if fresh_pairs:
            self._fanout_add_batch(fresh_pairs)

    def delete_routes(self, pairs: Sequence[Tuple[str, Dest]]) -> None:
        """Batched delete_route (the syncer's delete leg). With the
        native core this is ONE C pass over the pairs (the
        do_delete_route mirror of add_routes_core): dest refcounts,
        index un-indexing, table tombstones, and deferred host-trie
        removals all land in C; the wrapper batch-feeds the dest store
        (pending marks for surviving filters, one vectorized free for
        vanished rows) and fires on_dest_removed per vanished pair —
        the write path unsubscribe storms, session-expiry sweeps, and
        nodedown purges execute."""
        sp = _speedups.load()
        if sp is None:
            self._drop_native_state()
            for flt, dest in pairs:
                self._delete_route_py(flt, dest)
            return
        # generation bumps and surviving-filter pending marks happen
        # in the core (the lazy storm feed); dead rows free in one
        # vectorized pass here
        deep0 = len(self._deep) + len(self._exact_deep)
        vanished, removed_rows = sp.del_routes_core(
            self._handle(),
            pairs if isinstance(pairs, list) else list(pairs),
        )
        if len(self._deep) + len(self._exact_deep) != deep0:
            self._aux_gen += 1
        if vanished:
            if removed_rows:
                self.dest_store.free_rows(removed_rows)
                self._trie_gc()
            on_removed = self.on_dest_removed
            if on_removed is not None:
                for flt, dest in vanished:
                    on_removed(flt, dest)

    def _trie_gc(self) -> None:
        """Bound the deferred host-trie op list: a write-only workload
        (pure storms, purge cycles with no host-path reads in between)
        never drains it, so when the replay backlog outweighs the live
        filter set, DROP it and mark the trie stale — the next host
        read rebuilds from live state (_host_trie), which subsumes
        every dropped op by construction. The mutation-path cost is an
        O(1) length check (plus the occasional list clear); nothing is
        ever replayed twice and no storm leg pays a rebuild."""
        pf = self._trie_pending_f
        if self._trie_stale:
            if pf:
                # still stale (no read since): keep memory flat
                pf.clear()
                self._trie_pending_r.clear()
            return
        if len(pf) > 4 * len(self._filter_row) + 1024:
            self._trie_stale = True
            pf.clear()
            self._trie_pending_r.clear()

    def delete_route(self, flt: str, dest: Dest) -> None:
        core = self._del_core
        if core is not None:
            # allocation-free single-pair delete (unsubscribe hot
            # path; the churn handle makes per-call setup ~zero and
            # deletes need no reserve pre-pass; the generation bump
            # and surviving-filter pending mark happen IN the core).
            # Packed flags: 1 vanished, 2 row freed (id in bits 8+),
            # 8 deep changed.
            h = self._churn_handle
            if h is None:
                h = self._churn_handle = _speedups.load().make_churn_handle(
                    self
                )
            flags = core(h, flt, dest)
            if flags:
                if flags & 8:
                    self._aux_gen += 1
                if flags & 1:
                    if flags & 2:
                        self.dest_store.free_row(flags >> 8)
                        tick = self._trie_gc_tick + 1
                        if tick >= 1024:
                            self._trie_gc_tick = 0
                            self._trie_gc()
                        else:
                            self._trie_gc_tick = tick
                    if self.on_dest_removed is not None:
                        self.on_dest_removed(flt, dest)
            return
        self._drop_native_state()
        self._delete_route_py(flt, dest)

    def _delete_route_py(self, flt: str, dest: Dest) -> None:
        """Pure-python delete leg (the fallback and the oracle the C
        core is parity-tested against)."""
        if not topic_mod.is_wildcard(flt):
            dests = self._exact.get(flt)
            if not dests or dest not in dests:
                return
            dests[dest] -= 1
            if dests[dest] == 0:
                del dests[dest]
                self._fanout_removed(flt, dest)
                if not dests:
                    del self._exact[flt]
                    row = self._exact_row.pop(flt, None)
                    if row is not None:
                        self.dest_store.free_row(row)
                        self._row_filter[row] = None
                        if self.index is not None:
                            self.index.remove_row(row)
                        self.table.remove(row)
                    else:
                        self._exact_deep.discard(flt)
                        self._aux_gen += 1
                if self.on_dest_removed is not None:
                    self.on_dest_removed(flt, dest)
            return
        deep = False
        dests = self._wild.get(flt)
        if dests is None:
            dests = self._deep.get(flt)
            deep = True
        if dests is None or dest not in dests:
            return
        dests[dest] -= 1
        if dests[dest]:
            return
        del dests[dest]
        self._fanout_removed(flt, dest)
        if not dests:
            if deep:
                del self._deep[flt]
                self._deep_trie.remove(topic_mod.words(flt), flt)
                self._aux_gen += 1
            else:
                del self._wild[flt]
                row = self._filter_row.pop(flt)
                self.dest_store.free_row(row)
                self._row_filter[row] = None
                self._host_trie().remove(topic_mod.words(flt), row)
                if self.index is not None:
                    self.index.remove_row(row)
                self.table.remove(row)
        if self.on_dest_removed is not None:
            self.on_dest_removed(flt, dest)

    def has_route(self, flt: str, dest: Dest) -> bool:
        if not topic_mod.is_wildcard(flt):
            return dest in self._exact.get(flt, ())
        return dest in self._wild.get(flt, ()) or dest in self._deep.get(flt, ())

    def topic_count(self) -> int:
        """O(1) routed-topic count (the stores are disjoint) — the
        monitor samples this every interval; materializing the sorted
        10M-row list there would stall the event loop for seconds."""
        return len(self._exact) + len(self._wild) + len(self._deep)

    def topics(self) -> List[str]:
        """All routed topics/filters (emqx_router:topics/0)."""
        out = list(self._exact)
        out.extend(self._wild)
        out.extend(self._deep)
        return sorted(set(out))

    def dests(self, flt: str) -> List[Dest]:
        """All destinations routed for one topic/filter
        (emqx_router:lookup_routes/1)."""
        if not topic_mod.is_wildcard(flt):
            return list(self._exact.get(flt, ()))
        return list(self._wild.get(flt, ())) + list(self._deep.get(flt, ()))

    def routes(self) -> List[Tuple[str, Dest]]:
        """Every (filter, dest) pair — the full-table stream the
        cluster bootstrap dump walks (emqx_router:stream/1)."""
        out: List[Tuple[str, Dest]] = []
        for table in (self._exact, self._wild, self._deep):
            for flt, dests in table.items():
                out.extend((flt, d) for d in dests)
        return out

    def stats(self) -> Dict[str, int]:
        return {
            "exact_topics": len(self._exact),
            "wildcard_filters": len(self._wild),
            "wildcard_routes": sum(len(d) for d in self._wild.values()),
            "deep_routes": sum(len(d) for d in self._deep.values()),
            "table_rows": len(self.table),
            "table_capacity": self.table.capacity,
        }

    # --- read path (emqx_router:match_routes) ---------------------------

    def _host_trie(self) -> "TopicTrie":
        """The host trie with any deferred storm writes drained.
        Pending entries carry words tuples (single-add path) or raw
        filter strings (native bulk path — split here, off the storm
        hot loop). The native DELETE leg defers its trie removals into
        the same ordered list with the row encoded as -(row+1), so
        interleaved add/delete storms replay in arrival order — the
        router-syncer write-visibility seam (a host read observes every
        mutation that preceded it, exactly once)."""
        if self._trie_stale:
            # the op backlog was dropped mid-storm (_trie_gc): rebuild
            # from live state, which reflects every mutation up to NOW
            # — any ops still pending are subsumed, so they drop too
            t = TopicTrie()
            ins = t.insert
            words = self.table.filter_words
            for _flt, row in self._filter_row.items():
                ins(words(row), row)
            self._trie = t
            self._trie_pending_f.clear()
            self._trie_pending_r.clear()
            self._trie_stale = False
            return t
        pf = self._trie_pending_f
        if pf:
            self._replay_trie(pf, self._trie_pending_r)
            pf.clear()
            self._trie_pending_r.clear()
        return self._trie

    def _replay_trie(self, fs, rows) -> None:
        trie = self._trie
        ins = trie.insert
        rem = trie.remove
        for ws, row in zip(fs, rows):
            w = tuple(ws.split("/")) if type(ws) is str else ws
            if row >= 0:
                ins(w, row)
            else:
                rem(w, -row - 1)

    def trie_backlog(self) -> int:
        """Deferred host-trie ops the next host read would replay."""
        return len(self._trie_pending_f)

    def drain_trie_step(self, budget: int) -> int:
        """Replay up to `budget` of the oldest deferred host-trie ops
        (in order, so reads stay exact); returns the ops left. An
        event-loop caller spreads a subscribe storm's backlog over many
        short turns instead of paying it all in its first host read."""
        pf = self._trie_pending_f
        if self._trie_stale or len(pf) <= budget:
            self._host_trie()
            return 0
        pr = self._trie_pending_r
        self._replay_trie(pf[:budget], pr[:budget])
        del pf[:budget]
        del pr[:budget]
        return len(pf)

    def match_filters(self, topic: str) -> List[str]:
        """All routed filters matching one topic (exact key included).
        The primary match result: expansion to destinations is a host
        dict walk per filter (the ?SUBSCRIBER-table leg of the
        reference's dispatch, emqx_broker.erl:726-760)."""
        tw = topic_mod.words(topic)
        out: List[str] = []
        if topic in self._exact:
            out.append(topic)
        for row in self._host_trie().match(tw):
            out.append(self._row_filter[row])
        if self._deep:
            out.extend(self._deep_trie.match(tw))
        return out

    def filter_dests(self, flt: str) -> Dict[Dest, int]:
        """Dest refcount map for a matched filter (read-only view)."""
        if not topic_mod.is_wildcard(flt):
            return self._exact.get(flt, {})
        d = self._wild.get(flt)
        return d if d is not None else self._deep.get(flt, {})

    def match_pairs(self, topic: str) -> List[Tuple[str, Dict[Dest, int]]]:
        """(filter, dests) pairs for one topic — dispatch uses the
        filter for direct subopts lookup instead of re-matching.

        Exact-leg fast path: when no wildcard filter is routed at all
        (pure telemetry tables — BASELINE config #1's shape), the
        answer is one dict probe; the words split, trie descent, and
        filter-name indirection all drop out. That walk was the 4.6us
        the VERDICT flagged against the native baseline's 1.1us — a
        C-map detour can't win here because CPython dicts already ARE
        open-addressed C hash tables; the cost was ceremony, not
        hashing."""
        if not (self._wild or self._deep or self._trie_pending_f):
            d = self._exact.get(topic)
            return [(topic, d)] if d else []
        out = []
        d = self._exact.get(topic)
        if d:
            out.append((topic, d))
        tw = topic_mod.words(topic)
        row_filter = self._row_filter
        wild = self._wild
        for row in self._host_trie().match(tw):
            f = row_filter[row]
            out.append((f, wild[f]))
        if self._deep:
            deep = self._deep
            for f in self._deep_trie.match(tw):
                out.append((f, deep[f]))
        return out

    def match_routes(self, topic: str) -> Set[Dest]:
        """Single-topic host path: exact hash + trie walk. This is the
        low-latency cut-through used for cold/low-rate topics.

        Wildcard-free fast path: ONE dict probe + the set copy — no
        words split, no match_pairs indirection, no list build. This
        is the pure-telemetry shape (BASELINE config #1) where the r4
        VERDICT measured the ceremony losing to the native C++ walk;
        the probe itself is already an open-addressed C hash hit."""
        if not (self._wild or self._deep or self._trie_pending_f):
            d = self._exact.get(topic)
            return set(d) if d else set()
        pairs = self.match_pairs(topic)
        if len(pairs) == 1:
            return set(pairs[0][1])
        dests: Set[Dest] = set()
        for _f, dmap in pairs:
            dests.update(dmap)
        return dests

    def match_filters_begin(
        self, topics: Sequence[str], span=None
    ) -> _PendingMatch:
        """Phase 1 of the pipelined batched match: probe the
        generation-stamped match cache, sync the device table, encode
        the uncached remainder, and LAUNCH the match kernels without
        forcing any device->host transfer. JAX dispatch is async, so
        after begin() returns the device executes this batch while the
        host encodes the next one and fetches the previous one — the
        double-buffering seam broker/dispatch_engine pipelines through.
        Every begin() must be finished exactly once, in begin order, by
        match_filters_finish; match_filters_batch composes the two for
        the synchronous path, so results are bit-identical either way.

        `span` is the sentinel's per-batch StageSpan (obs/sentinel.py):
        when a sampled publish rides this batch, begin/finish attribute
        their encode/kernel/fetch time into it; None (every unsampled
        batch) costs a handful of is-None tests."""
        tel = self.telemetry
        clock = tel.clock
        p = _PendingMatch()
        p.span = span
        p.gen = self.generation
        cache = self.match_cache
        if cache is not None and topics:
            full: List[Optional[List[str]]] = []
            sub_idx: List[int] = []
            for i, t in enumerate(topics):
                f = cache.get(t, p.gen)
                if f is None:
                    sub_idx.append(i)
                    full.append(None)
                else:
                    # a fresh list per hit: callers may extend/consume
                    full.append(list(f))
            if tel.enabled:
                nh = len(topics) - len(sub_idx)
                if nh:
                    tel.count("match_cache_hits", nh)
                if sub_idx:
                    tel.count("match_cache_misses", len(sub_idx))
                tel.set_gauge(
                    "match_cache_hit_ratio", round(cache.hit_ratio(), 6)
                )
                tel.set_gauge("match_cache_entries", len(cache))
            p.full_out = full
            p.sub_idx = sub_idx
            sub = [topics[i] for i in sub_idx]
        else:
            sub = list(topics)
        p.topics = sub
        if not sub:
            p.mode = "cached"
            return p
        if self.device_suspended:
            # breaker open: the whole uncached remainder serves from
            # host truth at finish — no encode, no sync, no kernels.
            # The dirty backlog is dropped once it outgrows the table:
            # recovery re-uploads full state, which subsumes it, and a
            # churn storm during a long outage must not grow it
            # unboundedly.
            p.mode = "host"
            t = self.table
            if len(t.dirty) > t.capacity:
                t.drain_dirty()
            if tel.enabled:
                tel.count("breaker_degraded_batches_total")
            return p
        fi = self.fault_injector
        if fi is not None:
            fi.check("match_begin")
        tel.count("dispatch_batches_total")
        self.device_table.sync()
        if self._quarantined:
            self._maybe_unquarantine()
        # match_launch sub-marks (ISSUE 20 satellite): the engine's
        # outer "match_launch" stamp was one opaque 835/2203-sample
        # bucket — the sampler now sees encode vs launch vs
        # ticket_start, with the outer stamp left as the residual
        # (sync, cache bookkeeping). Saved/restored so non-engine
        # callers keep whatever stage was live.
        mark = STAGE_MARK
        prev_stage = mark.enter("encode")
        t0 = clock()
        # the batch axis pads to the next pow2 with inert topics (zero
        # levels, $-rooted: match NOTHING by the length + $-root rules)
        # so the jit shape space stays log-bounded — arbitrary coalesce
        # sizes were a fresh XLA trace per size, the 400ms-class p99
        # outlier the AOT warmup + this padding eliminate together.
        # finish drops ti >= len(sub), the same guard as dp padding.
        p.enc = enc = match_ops.encode_topics(
            self.table.vocab, sub, self.max_levels,
            pad_to=_next_pow2(len(sub)),
        )
        enc_dt = clock() - t0
        tel.record_dispatch(LEG_ENCODE, enc_dt)
        if span is not None:
            span.add("encode", enc_dt)
        # exact topics are device rows (wildcard-free classes), so the
        # kernel surfaces them; only too-deep exacts need the host dict
        if self._exact_deep:
            p.out = [[t] if t in self._exact_deep else [] for t in sub]
        else:
            p.out = [[] for _ in sub]
        # ONE launch path for both table kinds: DeviceTable and
        # ShardedDeviceTable expose the same match_{hash,ids}_begin/
        # finish halves (each begin also starts its result transfer)
        mark.enter("launch")
        ix = self.index
        if ix is not None:
            p.mode = "hash"
            if len(ix):
                t0 = clock()
                p.hash_pending = self.device_table.match_hash_begin(enc)
                p.hash_elapsed = clock() - t0
            if ix.residual_rows:
                # launch the residual-dense leg NOW so it overlaps the
                # hash fetch; the (~never) amb host-fallback in finish
                # simply discards it
                t0 = clock()
                p.residual_pending = self.device_table.match_ids_begin(
                    enc, residual=True
                )
                p.residual_elapsed = clock() - t0
            mark.leave(prev_stage)
            if span is not None and p.hash_elapsed is not None:
                span.add("kernel", p.hash_elapsed)
            return p
        p.mode = "dense"
        t0 = clock()
        p.dense_pending = self.device_table.match_ids_begin(enc)
        p.dense_elapsed = clock() - t0
        mark.leave(prev_stage)
        if span is not None:
            span.add("kernel", p.dense_elapsed)
        return p

    def match_filters_finish(self, p: _PendingMatch) -> List[List[str]]:
        """Phase 2 of the pipelined batched match: force the
        device->host transfers for a begun batch, escalate on
        compaction overflow, run the host verify/unpack stages, fold in
        deep-trie matches, populate the match cache, and return
        per-topic filter lists — bit-identical to the synchronous
        single-phase result."""
        tel = self.telemetry
        clock = tel.clock
        out = p.out
        topics = p.topics
        span = p.span
        t_fetch = clock() if span is not None else 0.0
        if p.mode == "host":
            # breaker-open batch: serve every sub-topic from host truth
            # (exact + trie + deep in one walk) — degraded capacity,
            # identical answers
            t0 = clock()
            out = p.out = [self.match_filters(t) for t in topics]
            tel.record_dispatch(LEG_FALLBACK, clock() - t0)
        elif p.mode != "cached":
            fi = self.fault_injector
            if fi is not None:
                fi.check("match_finish")
        if p.mode == "hash":
            ix = self.index
            host_fallback = False
            device_pairs = 0  # verified (topic, filter) pairs of the device legs
            if p.hash_pending is not None:
                t0 = clock()
                ti, bi, amb = self.device_table.match_hash_finish(
                    p.hash_pending
                )
                tel.record_dispatch(
                    LEG_HASH, p.hash_elapsed + clock() - t0
                )
                if amb:
                    # >1 lane of one pair passed the full-fingerprint
                    # check: distinct filters colliding on all 32 bits
                    # (~2^-32/pair). The kernel kept one arbitrarily,
                    # so re-match the batch on the host trie — exact,
                    # and covers residual rows too.
                    tel.count("ambiguous_batches_total")
                    host_fallback = True
                else:
                    t0 = clock()
                    twords: List = [None] * len(topics)
                    for t_idx, bid in zip(ti, bi):
                        t_idx, bid = int(t_idx), int(bid)
                        if bid < 0 or t_idx >= len(topics):
                            # phase-2 reject / dp-padding topic
                            continue
                        if twords[t_idx] is None:
                            twords[t_idx] = topic_mod.words(topics[t_idx])
                        fw = ix.bucket_filter(bid)
                        if topic_mod.match(twords[t_idx], fw):
                            for row in ix.bucket_rows(bid):
                                out[t_idx].append(self._row_filter[row])
                                device_pairs += 1
                    tel.record_dispatch(LEG_UNPACK, clock() - t0)
            if host_fallback:
                tel.count("host_fallback_total")
                t0 = clock()
                for i, t in enumerate(topics):
                    # indexed exact topics are NOT in the trie — the
                    # dest dict is their host source of truth
                    if t in self._exact_row:
                        out[i].append(t)
                    for row in self._host_trie().match(topic_mod.words(t)):
                        out[i].append(self._row_filter[row])
                tel.record_dispatch(LEG_FALLBACK, clock() - t0)
            elif p.residual_pending is not None:
                t0 = clock()
                ti, ri = self.device_table.match_ids_finish(
                    p.residual_pending
                )
                b = len(topics)
                for t_idx, row in zip(ti, ri):
                    if t_idx < b:  # drop pow2/dp padding rows
                        out[int(t_idx)].append(self._row_filter[int(row)])
                        device_pairs += 1
                tel.record_dispatch(
                    LEG_DENSE, p.residual_elapsed + clock() - t0
                )
            if not host_fallback and (
                p.hash_pending is not None or p.residual_pending is not None
            ):
                # topics the device answered and the pairs it gave them
                # (cache hits and host-trie batches are in neither)
                tel.count("match_device_topics_total", len(topics))
                tel.count("match_device_pairs_total", device_pairs)
        elif p.mode == "dense":
            t0 = clock()
            ti, ri = self.device_table.match_ids_finish(p.dense_pending)
            b = len(topics)
            for t_idx, row in zip(ti, ri):
                if t_idx < b:  # drop pow2/dp padding rows
                    out[int(t_idx)].append(self._row_filter[int(row)])
            tel.record_dispatch(LEG_DENSE, p.dense_elapsed + clock() - t0)
        if p.mode not in ("cached", "host"):
            # (host mode already folded deep matches via match_filters
            # and needs no quarantine overlay: it IS host truth)
            if self._deep:
                for i, t in enumerate(topics):
                    out[i].extend(self._deep_trie.match(topic_mod.words(t)))
            if self._quarantined and out:
                self._quarantine_overlay(topics, out)
            if self._suspended_shards and out:
                self._shard_overlay(topics, out)
        if span is not None:
            # transfer = residual device->host wait the tickets
            # actually blocked for (zero when the eager copies landed
            # under the next batch's launch); fetch = everything else
            # finish forces: overflow escalation, verify/unpack,
            # deep-trie fold
            waited = 0.0
            for h in (p.hash_pending, p.residual_pending, p.dense_pending):
                if h is not None:
                    waited += h[-1].waited
            if waited:
                span.add("transfer", waited)
            span.add("fetch", clock() - t_fetch - waited)
        if p.full_out is None:
            return out if out is not None else []
        # merge the kernel results into the cached prefix and stamp the
        # cache with the generation captured at begin: a mutation that
        # landed mid-flight leaves these entries stale-on-arrival, so
        # the next lookup recomputes — exactness over hit ratio
        full = p.full_out
        cache = self.match_cache
        if out:
            ev0 = cache.evictions
            for j, i in enumerate(p.sub_idx):
                flts = out[j]
                full[i] = flts
                cache.put(topics[j], p.gen, tuple(flts))
            ev = cache.evictions - ev0
            if ev and tel.enabled:
                tel.count("match_cache_evictions", ev)
        return full

    def match_finish_ready(self, p: "_PendingMatch") -> bool:
        """True when finishing `p` will not block on a device->host
        transfer: every begun leg's FetchTicket has landed host-side.
        The dispatch engine's ring uses this to collect slots in
        completion order without stalling the event loop; cached and
        host-mode batches are always ready."""
        for h in (p.hash_pending, p.residual_pending, p.dense_pending):
            if h is not None and not h[-1].ready():
                return False
        return True

    def set_transfer_chunk(self, chunk_kb: float) -> None:
        """Bound per-dispatch compacted-result buffers to a transfer
        chunk (KB) sized to the link (ops/transfer.chunk_hits); 0
        lifts the bound. Applies to both table kinds."""
        self.device_table.transfer_chunk_hits = transfer_ops.chunk_hits(
            chunk_kb
        )

    def shape_key(self) -> tuple:
        """The device table's compiled-shape key (DeviceTable.shape_key);
        the dispatch engine re-warms when it moves."""
        return self.device_table.shape_key()

    def warmup_shapes(self, max_batch: int = 64, sync: bool = True) -> int:
        """AOT-warm every kernel shape bucket a production dispatch
        can hit: run the REAL begin/finish halves over all-padding
        batches (zero live topics — inert by the length + $-root
        rules) for each pow2 batch size up to `max_batch`. Combined
        with the pow2 batch padding in match_filters_begin this makes
        the serve-time shape space exactly the warmed set, so no
        production publish ever pays an XLA retrace (the 400ms-class
        launch outliers in PERF_NOTES r6's decomposition). Returns
        shape buckets warmed; counted as `aot_warmups_total`.

        `sync=False` warms the device state as last synced: the engine
        syncs on the event loop, which owns the host tables, and runs
        the rest on a worker thread."""
        if self.device_suspended:
            return 0
        dt = self.device_table
        if sync:
            dt.sync()
        warmed = 0
        b = 1
        cap = _next_pow2(max(1, max_batch))
        ix = self.index
        mesh_warm = getattr(dt, "warmup_escalated", None)
        while b <= cap:
            enc = match_ops.encode_topics(
                self.table.vocab, (), self.max_levels, pad_to=b
            )
            if ix is not None:
                # the hash leg even for an empty index: the first
                # wildcard subscribe must not change what is compiled
                dt.match_hash_finish(dt.match_hash_begin(enc))
                warmed += 1
                if ix.residual_rows:
                    dt.match_ids_finish(dt.match_ids_begin(enc, residual=True))
                    warmed += 1
            else:
                dt.match_ids_finish(dt.match_ids_begin(enc))
                warmed += 1
            if mesh_warm is not None:
                # mesh tables also pre-build the first escalation step
                # (2x capacity) per batch shape: a serve-time overflow
                # then re-dispatches warm instead of compiling cold
                warmed += mesh_warm(enc)
            b *= 2
        # pre-trace the churn-sync scatters too, so the first
        # serve-time subscribe wave doesn't pay a compile either
        warmed += dt.warmup_deltas()
        tel = self.telemetry
        if tel.enabled and warmed:
            tel.count("aot_warmups_total", warmed)
        return warmed

    def match_filters_batch(self, topics: Sequence[str]) -> List[List[str]]:
        """Batched device path: ONE XLA dispatch for all wildcard
        matching, host hash for exact topics. The hot loop of
        emqx_broker:do_publish expressed over a topic batch.

        With the pattern-class index (default) the wildcard leg is a
        B×C hash-probe kernel returning (topic, bucket) candidates that
        the host verifies against the oracle before expanding to dests;
        rows the index couldn't class (skeleton budget) fall back to
        the dense kernel over a residual mask. Result transfers stay
        proportional to the number of matches either way, with one
        exact-size retry on overflow. Composed from the begin/finish
        pipeline phases, so the synchronous and pipelined paths are one
        code path (and bit-identical by construction)."""
        if not topics:
            return []
        return self.match_filters_finish(self.match_filters_begin(topics))

    def match_pairs_batch(
        self, topics: Sequence[str]
    ) -> List[List[Tuple[str, Dict[Dest, int]]]]:
        return [
            [(f, self.filter_dests(f)) for f in flts]
            for flts in self.match_filters_batch(topics)
        ]

    def match_batch(self, topics: Sequence[str]) -> List[Set[Dest]]:
        out: List[Set[Dest]] = []
        for flts in self.match_filters_batch(topics):
            dests: Set[Dest] = set()
            for f in flts:
                dests.update(self.filter_dests(f))
            out.append(dests)
        return out
