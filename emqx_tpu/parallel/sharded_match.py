"""Multi-chip match + table update over a (dp, sub) mesh.

Two styles, both idiomatic:

* The *match* path relies on XLA SPMD auto-partitioning: the dense
  predicate is elementwise over the [B, N] plane, so sharded inputs
  ([B]→'dp', [N]→'sub') partition it with zero communication; count
  reductions become one psum over 'sub' that XLA inserts on its own.
  (This replaces the reference's full-table replication + local match,
  emqx_router.erl:133-162 — ICI is fast enough to partition instead.)

* The *update* path (route add/delete deltas) uses shard_map because
  each 'sub' shard must translate global row ids into its local slice:
  every shard receives the same delta batch (deltas are tiny — ≤1024
  rows, mirroring emqx_router_syncer batches) and applies the rows it
  owns with a masked scatter; rows outside the shard drop out. This is
  the mria-rlog analog: one write stream, applied shard-locally.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..obs.profiler import STAGE_MARK
from ..ops.match import EncodedTopics, PackedTopics, _match_block, _pack_bits
from ..ops.table import EncodedFilters
from .mesh import DP_AXIS, SUB_AXIS, filter_sharding, topic_sharding


def _shard_map_unchecked(f, *, mesh, in_specs, out_specs):
    """shard_map with the replication check disabled: the combine
    kernels' all_gather -> nonzero recompaction IS replicated over
    'sub' (every member computes from the identical gathered vector),
    but the static rep-inference can't see through the fixed-size
    nonzero."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def make_sharded_kernels(mesh: Mesh):
    """Compile the mesh-partitioned kernels. Returns
    (match_counts, match_packed, apply_delta)."""

    f_shard = filter_sharding(mesh)
    t_shard = topic_sharding(mesh)
    counts_out = NamedSharding(mesh, P(DP_AXIS))
    packed_out = NamedSharding(mesh, P(DP_AXIS, SUB_AXIS))
    repl = NamedSharding(mesh, P())

    @functools.partial(
        jax.jit,
        in_shardings=(f_shard, t_shard),
        out_shardings=counts_out,
    )
    def match_counts(filters: EncodedFilters, topics: EncodedTopics):
        ok = _match_block(topics.ids, topics.lens, topics.dollar, *filters)
        return ok.sum(axis=1, dtype=jnp.int32)  # XLA: psum over 'sub'

    @functools.partial(
        jax.jit,
        in_shardings=(f_shard, t_shard),
        out_shardings=packed_out,
    )
    def match_packed(filters: EncodedFilters, topics: EncodedTopics):
        ok = _match_block(topics.ids, topics.lens, topics.dollar, *filters)
        return _pack_bits(ok)

    n_sub = mesh.shape[SUB_AXIS]

    def _apply_delta_local(dev: EncodedFilters, rows, words, plen, hh, rw, act):
        # dev leaves are the LOCAL shard [N/n_sub, ...]; rows are
        # GLOBAL ids with a leading delta-batch axis [n_b, K, ...] —
        # all batches apply inside ONE dispatch via scan (one launch
        # per sync, as in the single-device _scatter_rows).
        local_n = dev.words.shape[0]
        offset = jax.lax.axis_index(SUB_AXIS).astype(jnp.int32) * local_n

        def step(d, xs):
            r, w, p, h, rw_, a = xs
            local = r - offset
            # rows outside this shard scatter out of range -> dropped
            oob = (local < 0) | (local >= local_n)
            local = jnp.where(oob, local_n, local)
            return (
                EncodedFilters(
                    d.words.at[local].set(w, mode="drop"),
                    d.prefix_len.at[local].set(p, mode="drop"),
                    d.has_hash.at[local].set(h, mode="drop"),
                    d.root_wild.at[local].set(rw_, mode="drop"),
                    d.active.at[local].set(a, mode="drop"),
                ),
                None,
            )

        out, _ = jax.lax.scan(step, dev, (rows, words, plen, hh, rw, act))
        return out

    dev_specs = EncodedFilters(
        P(SUB_AXIS, None), P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS)
    )
    # rows, words, plen, hh, rw, act — all replicated to every shard
    delta_specs = (
        P(None, None), P(None, None, None), P(None, None),
        P(None, None), P(None, None), P(None, None),
    )

    @functools.partial(jax.jit, donate_argnums=0)
    def apply_delta(
        dev: EncodedFilters,
        rows: jnp.ndarray,  # int32 [n_b, K] global row ids
        words: jnp.ndarray,  # int32 [n_b, K, L]
        plen: jnp.ndarray,
        hh: jnp.ndarray,
        rw: jnp.ndarray,
        act: jnp.ndarray,
    ) -> EncodedFilters:
        return jax.shard_map(
            _apply_delta_local,
            mesh=mesh,
            in_specs=(dev_specs,) + delta_specs,
            out_specs=dev_specs,
        )(dev, rows, words, plen, hh, rw, act)

    return match_counts, match_packed, apply_delta


def _combine_pairs(a, b, valid_key, mh):
    """Device-side cross-shard reduction: gather every shard's
    compacted [mh] buffers over 'sub' (tiled — one [n_sub*mh] vector,
    replicated across the axis by the collective) and recompact the
    valid entries into ONE [mh] result. This is the combine that used
    to run on host: the finish leg now fetches N-independent bytes and
    merges nothing. Safe under the same escalation contract — if the
    psum'd total fits mh then every per-shard count fit mh too, so the
    per-shard compaction upstream dropped nothing."""
    a_all = jax.lax.all_gather(a, SUB_AXIS, tiled=True)
    b_all = jax.lax.all_gather(b, SUB_AXIS, tiled=True)
    pos = jnp.nonzero(valid_key(a_all), size=mh, fill_value=-1)[0]
    pv = pos >= 0
    ps = jnp.maximum(pos, 0)
    ca = jnp.where(pv, a_all[ps], -1).astype(jnp.int32)
    cb = jnp.where(pv, b_all[ps], -1).astype(jnp.int32)
    return ca, cb


def make_combine_probe_kernel(mesh: Mesh, mh: int):
    """Combine-only probe for the mesh microscope (obs/mesh_scope.py):
    EXACTLY the cross-shard reduction of the match kernels
    (`_combine_pairs` over 'sub' plus the psum'd total) on synthetic
    per-shard buffers built on-device, so its device span isolates the
    `combine_collective` leg of a real dispatch without duplicating
    either match kernel — the reduction cost depends only on (n_sub,
    mh), which this probe shares with both the dense and hash paths.
    The salted scalar input keeps the gathered buffers from being
    constant-folded — every probe pays the real collective."""

    def _local(salt):
        sub_i = jax.lax.axis_index(SUB_AXIS).astype(jnp.int32)
        iot = jnp.arange(mh, dtype=jnp.int32)
        # one salted valid entry per shard — occupancy does not change
        # the gather cost (the buffers are flat [n_sub*mh] either way)
        a = jnp.where(iot == 0, salt + sub_i + 1, -1)
        b = jnp.where(iot == 0, salt * 2 + 1, -1)
        ca, cb = _combine_pairs(a, b, lambda t: t >= 0, mh)
        total = jax.lax.psum((a >= 0).sum(dtype=jnp.int32), SUB_AXIS)
        return ca[None, :], cb[None, :], total.reshape(1, 1)

    @jax.jit
    def probe(salt):
        return _shard_map_unchecked(
            _local,
            mesh=mesh,
            in_specs=(P(),),
            out_specs=(
                P(DP_AXIS, None), P(DP_AXIS, None), P(DP_AXIS, None),
            ),
        )(salt)

    return probe


def make_match_ids_kernel(mesh: Mesh, max_hits_per_block: int):
    """Sharded compaction kernel with DEVICE-SIDE combine: every
    (dp, sub) block matches its LOCAL [B/dp, N/sub] tile, compacts its
    hits to fixed-size (topic, row) id buffers with GLOBAL indices
    (axis_index offsets), then the shards reduce over 'sub' on-device
    (all_gather + recompaction, totals via psum) so ONE dispatch
    returns ONE combined buffer whose transfer size is independent of
    the shard count — the multi-chip version of ops.match.match_ids
    without the per-shard host merge that inverted the scaling curve
    (PERF_NOTES.md r15). Returns (ti [dp, mh], ri [dp, mh],
    totals [dp, 1]); slots are -1 beyond each dp block's true count,
    and a block whose total exceeds max_hits_per_block overflowed
    (caller escalates)."""

    f_specs = EncodedFilters(
        P(SUB_AXIS, None), P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS)
    )
    t_specs = EncodedTopics(P(DP_AXIS, None), P(DP_AXIS), P(DP_AXIS))
    mh = max_hits_per_block

    def _local(ids, lens, dollar, words, plen, hh, rw, act):
        dp_i = jax.lax.axis_index(DP_AXIS).astype(jnp.int32)
        sub_i = jax.lax.axis_index(SUB_AXIS).astype(jnp.int32)
        ok = _match_block(ids, lens, dollar, words, plen, hh, rw, act)
        b_loc, n_loc = ok.shape
        cnt = ok.sum(dtype=jnp.int32)
        idx = jnp.nonzero(ok.reshape(-1), size=mh, fill_value=-1)[0]
        valid = idx >= 0
        ti = jnp.where(valid, idx // n_loc + dp_i * b_loc, -1).astype(jnp.int32)
        ri = jnp.where(valid, idx % n_loc + sub_i * n_loc, -1).astype(jnp.int32)
        cti, cri = _combine_pairs(ti, ri, lambda t: t >= 0, mh)
        total = jax.lax.psum(cnt, SUB_AXIS)
        return cti[None, :], cri[None, :], total.reshape(1, 1)

    @jax.jit
    def match_ids(filters: EncodedFilters, topics: EncodedTopics):
        return _shard_map_unchecked(
            _local,
            mesh=mesh,
            in_specs=(
                t_specs.ids, t_specs.lens, t_specs.dollar,
                f_specs.words, f_specs.prefix_len, f_specs.has_hash,
                f_specs.root_wild, f_specs.active,
            ),
            out_specs=(
                P(DP_AXIS, None),
                P(DP_AXIS, None),
                P(DP_AXIS, None),
            ),
        )(
            topics.ids, topics.lens, topics.dollar,
            filters.words, filters.prefix_len, filters.has_hash,
            filters.root_wild, filters.active,
        )

    return match_ids


def make_sharded_hash_kernel(
    mesh: Mesh, max_hits_per_block: int, n_buckets: Optional[int] = None
):
    """The PRODUCTION pattern-class cuckoo kernel, bucket-partitioned
    over the 'sub' axis (VERDICT r2 #2: the mesh must run the 67x hash
    path, not the dense demo). Each shard owns a contiguous bucket
    range of the global table; it probes only the candidate buckets
    that fall inside its slice, so a pair whose b1/b2 land on
    different shards is served by both — each emits its own candidate
    with the GLOBAL bucket id, and the host union (plus its oracle
    verify) merges them. Meta and the per-(topic,class) hash mixing
    are replicated (B×C u32 ops — cheap); the O(table) state is what
    partitions, exactly the HBM-capacity reason to go multi-chip.

    Returns kernel(meta, slots, topics) ->
    (ti [dp, mh], bi [dp, mh], totals [dp, 1], amb [1,1]): the
    candidates are combined ON-DEVICE over 'sub' (all_gather +
    recompaction, same reduction as make_match_ids_kernel) so the
    fetch is one shard-count-independent buffer; totals are the
    psum'd flagged-pair counts for escalation, amb the mesh-wide
    ambiguity (see ops.hash_index.match_ids_hash).

    `n_buckets` is the LOGICAL global bucket count (pow2 — the host
    index's n_buckets). It must be passed whenever the per-shard slice
    carries trailing pad buckets (an N-1 survivor mesh, where n_sub no
    longer divides the pow2 count): the hash mask is `n_buckets - 1`,
    NOT `nb_loc * n_sub - 1`, and pad buckets are simply never probed
    because every b1/b2 lands below n_buckets. None keeps the
    divisible-layout default (nb_loc * n_sub)."""
    from ..ops.hash_index import BUCKET_W, _ALT_MUL, _FP_CLS, _FP_MUL
    from ..ops.hash_index import _FP_SEED, _FP_XOR, _H1_CLS, _H1_MUL, _H1_SEED

    mh = max_hits_per_block
    meta_specs = (P(None),) * 5
    slot_specs = (P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS))
    t_specs = (P(DP_AXIS, None), P(DP_AXIS), P(DP_AXIS))
    n_sub = mesh.shape[SUB_AXIS]  # static (jax.lax.axis_size is >=0.5)

    def _local(plen, has_hash, root_wild, plus, active, sfp, sbkt, probe,
               ids, lens, dollar):
        dp_i = jax.lax.axis_index(DP_AXIS).astype(jnp.int32)
        sub_i = jax.lax.axis_index(SUB_AXIS).astype(jnp.int32)
        b_loc, max_levels = ids.shape
        c = plen.shape[0]
        nb_loc = probe.shape[0]
        nb_global = n_buckets if n_buckets is not None else nb_loc * n_sub
        tl = lens[:, None]
        pl = plen[None, :]
        len_ok = jnp.where(has_hash[None, :], tl >= pl, tl == pl)
        elig = len_ok & active[None, :] & ~(
            dollar[:, None] & root_wild[None, :]
        )
        cids = jnp.arange(c, dtype=jnp.uint32)
        h1 = jnp.broadcast_to(
            jnp.uint32(_H1_SEED) ^ (cids * jnp.uint32(_H1_CLS)), (b_loc, c)
        )
        fp = jnp.broadcast_to(
            jnp.uint32(_FP_SEED) + (cids * jnp.uint32(_FP_CLS)), (b_loc, c)
        )
        for i in range(max_levels):
            lit = (i < plen) & (((plus >> i) & 1) == 0)
            x = jnp.where(
                lit[None, :],
                ids[:, i : i + 1].astype(jnp.uint32) + 1,
                jnp.uint32(0),
            )
            h1 = (h1 ^ x) * jnp.uint32(_H1_MUL)
            fp = (fp ^ (x * jnp.uint32(_FP_XOR))) * jnp.uint32(_FP_MUL)
        mask = jnp.uint32(nb_global - 1)
        b1 = h1 & mask
        b2 = b1 ^ (((fp | jnp.uint32(1)) * jnp.uint32(_ALT_MUL)) & mask)
        off = (sub_i * nb_loc).astype(jnp.int32)
        p8 = jnp.maximum(fp >> jnp.uint32(24), jnp.uint32(1))
        rep = p8 * jnp.uint32(0x01010101)

        def local_hit(b):
            lb = b.astype(jnp.int32) - off
            inside = (lb >= 0) & (lb < nb_loc)
            w = probe[jnp.clip(lb, 0, nb_loc - 1)]
            x = w ^ rep
            hz = ((x - jnp.uint32(0x01010101)) & ~x
                  & jnp.uint32(0x80808080)) != 0
            return inside & hz, lb, w

        hit1, l1, wp1 = local_hit(b1)
        hit2, l2, wp2 = local_hit(b2)
        pairhit = elig & (hit1 | hit2)
        total = pairhit.sum(dtype=jnp.int32)
        pflat = jnp.nonzero(
            pairhit.reshape(-1), size=mh, fill_value=-1
        )[0]
        pvalid = pflat >= 0
        psafe = jnp.maximum(pflat, 0)
        ph1 = hit1.reshape(-1)[psafe]
        ph2 = hit2.reshape(-1)[psafe]
        pl1 = l1.reshape(-1)[psafe]
        pl2 = l2.reshape(-1)[psafe]
        pfp = fp.reshape(-1)[psafe]
        pw1 = wp1.reshape(-1)[psafe]
        pw2 = wp2.reshape(-1)[psafe]
        # two-lane sparse verify (mirrors match_ids_hash phase 2): the
        # probe words pin the candidate lanes exactly; verify the
        # first two LOCAL byte-matching lanes, route >2 to amb. Lane
        # validity folds the shard-ownership mask per bucket.
        pp8 = jnp.maximum(pfp >> jnp.uint32(24), jnp.uint32(1))
        lid = jnp.arange(2 * BUCKET_W, dtype=jnp.int32)
        use1 = lid < BUCKET_W
        lvalid = jnp.where(use1[None, :], ph1[:, None], ph2[:, None])
        lane_byte = jnp.where(
            use1[None, :],
            pw1[:, None] >> (jnp.uint32(8) * (lid[None, :].astype(jnp.uint32) & jnp.uint32(3))),
            pw2[:, None] >> (jnp.uint32(8) * (lid[None, :].astype(jnp.uint32) & jnp.uint32(3))),
        ) & jnp.uint32(0xFF)
        bm = (lane_byte == pp8[:, None]) & lvalid & pvalid[:, None]
        nbm = bm.sum(axis=1, dtype=jnp.int32)
        ln1 = jnp.argmax(bm, axis=1)
        bm2 = bm & (lid[None, :] != ln1[:, None])
        ln2 = jnp.argmax(bm2, axis=1)

        def lslot_of(ln):
            s = (
                jnp.where(ln < BUCKET_W, pl1, pl2) * BUCKET_W
                + (ln % BUCKET_W)
            )
            return jnp.clip(s, 0, sfp.shape[0] - 1)

        s1 = lslot_of(ln1)
        s2 = lslot_of(ln2)
        f1 = sfp[s1]
        f2 = sfp[s2]
        ok1 = (nbm >= 1) & (f1 == pfp)
        ok2 = (nbm >= 2) & (f2 == pfp)
        nmatch = ok1.astype(jnp.int32) + ok2.astype(jnp.int32)
        found = nmatch > 0
        win = jnp.where(ok1, s1, s2)
        g_bkt = sbkt[win]
        ok = found & (g_bkt >= 0)
        ti = jnp.where(
            ok, psafe // c + dp_i * b_loc, -1
        ).astype(jnp.int32)
        bi = jnp.where(ok, g_bkt, -1).astype(jnp.int32)
        amb = jax.lax.psum(
            jax.lax.psum(
                ((nmatch > 1) | (pvalid & (nbm > 2))).sum(dtype=jnp.int32),
                SUB_AXIS,
            ),
            DP_AXIS,
        )
        # device-side combine over 'sub': valid candidates <= flagged
        # pairs, so the psum'd flagged total remains a sound overflow
        # trigger for the combined buffer
        cti, cbi = _combine_pairs(ti, bi, lambda t: t >= 0, mh)
        total = jax.lax.psum(total, SUB_AXIS)
        return (
            cti[None, :], cbi[None, :], total.reshape(1, 1),
            amb.reshape(1, 1),
        )

    @jax.jit
    def kernel(meta, slots, topics):
        return _shard_map_unchecked(
            _local,
            mesh=mesh,
            in_specs=meta_specs + slot_specs + t_specs,
            out_specs=(
                P(DP_AXIS, None),
                P(DP_AXIS, None),
                P(DP_AXIS, None),
                P(None, None),
            ),
        )(
            meta.plen, meta.has_hash, meta.root_wild, meta.plus, meta.active,
            slots.fp, slots.bucket, slots.probe,
            topics.ids, topics.lens, topics.dollar,
        )

    return kernel


def make_slot_delta_kernel(mesh: Mesh):
    """shard_map scatter for incremental cuckoo-slot sync: every shard
    receives the same (global slot idx, fp, bucket, probe word) delta
    batches and applies the slots/probe words it owns (mode='drop'
    discards out-of-slice rows) — one write stream, applied
    shard-locally, the same mria-rlog shape as the filter-row delta."""
    from ..ops.hash_index import BUCKET_W

    def _local(sfp, sbkt, probe, idx, fpv, bktv, pwv):
        n_loc = sfp.shape[0]
        nb_loc = probe.shape[0]
        sub_i = jax.lax.axis_index(SUB_AXIS).astype(jnp.int32)
        s_off = sub_i * n_loc
        b_off = sub_i * nb_loc

        def step(carry, xs):
            cfp, cbkt, cpw = carry
            i, f, b, pw = xs
            # clamp negatives to one-past-end: jnp negative indices WRAP
            # (they'd corrupt the tail of lower shards); only >= n is
            # dropped by mode='drop' (same guard as _apply_delta_local)
            ls = i - s_off
            ls = jnp.where((ls < 0) | (ls >= n_loc), n_loc, ls)
            lb = i // BUCKET_W - b_off
            lb = jnp.where((lb < 0) | (lb >= nb_loc), nb_loc, lb)
            return (
                (
                    cfp.at[ls].set(f, mode="drop"),
                    cbkt.at[ls].set(b, mode="drop"),
                    cpw.at[lb].set(pw, mode="drop"),
                ),
                None,
            )

        (sfp, sbkt, probe), _ = jax.lax.scan(
            step, (sfp, sbkt, probe), (idx, fpv, bktv, pwv)
        )
        return sfp, sbkt, probe

    specs = (P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS))
    dspecs = ((P(None, None),) * 4)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def apply(sfp, sbkt, probe, idx, fpv, bktv, pwv):
        return jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=specs + dspecs,
            out_specs=specs,
        )(sfp, sbkt, probe, idx, fpv, bktv, pwv)

    return apply


def make_mesh_sync_kernel(mesh: Mesh):
    """FUSED churn sync: apply a filter-row delta batch AND a
    cuckoo-slot delta batch in ONE shard_map dispatch with every
    device buffer donated. The steady-state churn loop used to pay two
    launches per sync (row scatter, then slot scatter); at mesh scale
    the second launch was pure serial overhead. Delta streams are replicated (tiny — syncer
    batches); each shard applies the rows/slots it owns via the same
    masked mode='drop' scatters as the split kernels."""
    from ..ops.hash_index import BUCKET_W

    def _local(dev, sfp, sbkt, probe,
               rows, words, plen, hh, rw, act,
               sidx, sfpv, sbktv, spwv):
        local_n = dev.words.shape[0]
        n_loc = sfp.shape[0]
        nb_loc = probe.shape[0]
        sub_i = jax.lax.axis_index(SUB_AXIS).astype(jnp.int32)
        r_off = sub_i * local_n
        s_off = sub_i * n_loc
        b_off = sub_i * nb_loc

        def rstep(d, xs):
            r, w, p, h, rw_, a = xs
            local = r - r_off
            oob = (local < 0) | (local >= local_n)
            local = jnp.where(oob, local_n, local)
            return (
                EncodedFilters(
                    d.words.at[local].set(w, mode="drop"),
                    d.prefix_len.at[local].set(p, mode="drop"),
                    d.has_hash.at[local].set(h, mode="drop"),
                    d.root_wild.at[local].set(rw_, mode="drop"),
                    d.active.at[local].set(a, mode="drop"),
                ),
                None,
            )

        dev, _ = jax.lax.scan(rstep, dev, (rows, words, plen, hh, rw, act))

        def sstep(carry, xs):
            cfp, cbkt, cpw = carry
            i, f, b, pw = xs
            ls = i - s_off
            ls = jnp.where((ls < 0) | (ls >= n_loc), n_loc, ls)
            lb = i // BUCKET_W - b_off
            lb = jnp.where((lb < 0) | (lb >= nb_loc), nb_loc, lb)
            return (
                (
                    cfp.at[ls].set(f, mode="drop"),
                    cbkt.at[ls].set(b, mode="drop"),
                    cpw.at[lb].set(pw, mode="drop"),
                ),
                None,
            )

        (sfp, sbkt, probe), _ = jax.lax.scan(
            sstep, (sfp, sbkt, probe), (sidx, sfpv, sbktv, spwv)
        )
        return dev, sfp, sbkt, probe

    dev_specs = EncodedFilters(
        P(SUB_AXIS, None), P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS)
    )
    slot_specs = (P(SUB_AXIS), P(SUB_AXIS), P(SUB_AXIS))
    row_dspecs = (
        P(None, None), P(None, None, None), P(None, None),
        P(None, None), P(None, None), P(None, None),
    )
    slot_dspecs = (P(None, None),) * 4

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
    def apply(dev, sfp, sbkt, probe,
              rows, words, plen, hh, rw, act,
              sidx, sfpv, sbktv, spwv):
        return jax.shard_map(
            _local,
            mesh=mesh,
            in_specs=(dev_specs,) + slot_specs + row_dspecs + slot_dspecs,
            out_specs=(dev_specs,) + slot_specs,
        )(dev, sfp, sbkt, probe, rows, words, plen, hh, rw, act,
          sidx, sfpv, sbktv, spwv)

    return apply


class ShardedDeviceTable:
    """Mesh-resident mirror of a FilterTable: rows sub-sharded across
    the mesh, topics dp-sharded, batched delta sync through the
    shard_map scatter. The multi-device counterpart of
    models.router.DeviceTable behind the same sync()/match surface —
    replication-as-partitioning instead of the reference's full
    per-node table replica (emqx_router.erl:133-162). With `index`,
    the pattern-class cuckoo table is ALSO mesh-resident (buckets
    sub-sharded) and match_hash runs the production kernel; the dense
    kernel then serves only residual (unclassed) rows."""

    DELTA_BATCH = 1024  # rows per apply_delta call (syncer batch size)

    def __init__(
        self,
        table,
        mesh: Mesh,
        max_hits_per_block: int = 2048,
        index=None,
        telemetry=None,
    ):
        from . import mesh as mesh_mod
        from ..obs.kernel_telemetry import NULL as _null_tel

        self.table = table
        self.mesh = mesh
        self.index = index
        self.telemetry = telemetry if telemetry is not None else _null_tel
        self._mesh_mod = mesh_mod
        # shard failure domain: `_mesh0` is the full N-chip layout;
        # `lost_shards` holds ORIGINAL sub-axis columns evacuated off
        # the mesh (chip loss); `shard_gen` bumps on every re-shard so
        # in-flight handles/caches can detect a layout change
        self._mesh0 = mesh
        self.lost_shards: set = set()
        self.shard_gen = 0
        self._dev: Optional[EncodedFilters] = None
        self._synced_capacity = 0
        _mc, _mp, self._apply_delta = make_sharded_kernels(mesh)
        self._match_ids_cache: dict = {}
        self._hash_cache: dict = {}
        self.default_mh = max_hits_per_block
        # sticky escalation floor: the combined result buffer budgets
        # the SUM of per-shard hits, so once a batch overflows, every
        # later batch of the same workload would too — re-dispatching
        # each time is exactly the N-x overhead this path removes. The
        # floor persists for the life of the layout.
        self._mh_floor = 0
        self._dev_meta = None
        self._dev_slots = None
        self._dev_residual = None
        self._apply_slot_delta = (
            make_slot_delta_kernel(mesh) if index is not None else None
        )
        self._mesh_sync = (
            make_mesh_sync_kernel(mesh) if index is not None else None
        )
        # degrade-to-single-device admission (tpu_mesh_min_rows_per_shard
        # knob): below this many table rows per shard the mesh
        # launch+combine overhead exceeds the kernel work it spreads,
        # so serving falls back to a plain DeviceTable on the mesh's
        # first chip. 0 (the direct-construction default) never
        # degrades.
        self.min_rows_per_shard = 0
        self.degraded = False
        self._single = None
        self.fanout = None
        # chaos fault seam (emqx_tpu/chaos/faults.py) — same contract
        # as the single-device DeviceTable: one attribute read per sync
        self.fault_injector = None
        # transfer chunk cap (ops/transfer.chunk_hits) — same contract
        # as DeviceTable.transfer_chunk_hits
        self.transfer_chunk_hits = None
        # mesh microscope seam (obs/mesh_scope.MeshScope): None keeps
        # the served path at one attribute read per dispatch — the
        # tpu_mesh_scope_enable=false contract
        self.scope = None
        self._probe_cache: dict = {}

    def attach_fanout(self, store) -> None:
        """Mirror a CSR destination store on the mesh (replicated: the
        fan tables are small next to the sub-sharded filter state, and
        every shard needs every segment) — the same resolve begin/
        finish surface as the single-device DeviceTable."""
        from ..ops.fanout import FanoutDeviceState

        self.fanout = FanoutDeviceState(
            store, mesh=self.mesh, telemetry=self.telemetry
        )

    # --- shard failure domain (chip loss / evacuation / rebalance) --------

    @property
    def n_shards(self) -> int:
        return self.mesh.shape[SUB_AXIS]

    def shard_of_row(self, row: int) -> int:
        """The sub-axis column serving a table row under the CURRENT
        mesh (trailing-pad slices: ceil(capacity / n_sub) rows each)."""
        return row // self._mesh_mod.shard_rows(self.table.capacity, self.mesh)

    def shard_of_slot(self, slot: int) -> int:
        """The sub-axis column serving a cuckoo slot position under the
        current mesh (slot slices stay bucket-aligned)."""
        from ..ops.hash_index import BUCKET_W

        n_sub = self.mesh.shape[SUB_AXIS]
        nb = self.index.n_buckets
        nb_loc = -(-nb // n_sub)
        return slot // (nb_loc * BUCKET_W)

    def _survivor_mesh(self) -> Mesh:
        import numpy as np

        arr = np.asarray(self._mesh0.devices)  # [n_dp, n_sub0]
        keep = [
            i for i in range(arr.shape[1]) if i not in self.lost_shards
        ]
        return self._mesh_mod.make_mesh(
            n_dp=arr.shape[0],
            n_sub=len(keep),
            devices=arr[:, keep].reshape(-1).tolist(),
        )

    def evacuate_shard(self, shard: int) -> bool:
        """Drop one ORIGINAL sub-axis column from the mesh and re-shard
        the table over the survivors (N-1 serving). The caller owns the
        follow-up `sync()` that re-uploads every slice from host truth
        through the normal full-resync machinery. Returns True when the
        mesh changed. Adding to `lost_shards` FIRST matters: the fault
        injector consults it, so the evacuation resync already runs
        without touching the lost chip while its fault is still live."""
        n_sub0 = self._mesh0.shape[SUB_AXIS]
        if shard < 0 or shard >= n_sub0 or shard in self.lost_shards:
            return False
        if len(self.lost_shards) + 1 >= n_sub0:
            raise RuntimeError(
                f"cannot evacuate shard {shard}: no survivor would remain"
            )
        self.lost_shards.add(shard)
        self._rebuild_mesh(self._survivor_mesh())
        return True

    def restore_shard(self, shard: int) -> bool:
        """Rebalance a recovered chip back in: restore the full layout
        (or the wider survivor layout while other chips are still
        lost). Caller owns the follow-up full `sync()`."""
        if shard not in self.lost_shards:
            return False
        self.lost_shards.discard(shard)
        self._rebuild_mesh(
            self._mesh0 if not self.lost_shards else self._survivor_mesh()
        )
        return True

    def _rebuild_mesh(self, mesh: Mesh) -> None:
        """Swap the serving mesh: recompile the shard_map kernels for
        the new layout, drop every device-resident array so the next
        sync() is a full re-upload from host truth, and re-mirror the
        fanout store."""
        self.mesh = mesh
        _mc, _mp, self._apply_delta = make_sharded_kernels(mesh)
        self._match_ids_cache.clear()
        self._hash_cache.clear()
        self._probe_cache.clear()
        self._apply_slot_delta = (
            make_slot_delta_kernel(mesh) if self.index is not None else None
        )
        self._mesh_sync = (
            make_mesh_sync_kernel(mesh) if self.index is not None else None
        )
        self._dev = None
        self._dev_meta = None
        self._dev_slots = None
        self._dev_residual = None
        self._synced_capacity = 0
        if self.fanout is not None:
            self.attach_fanout(self.fanout.store)
        self.shard_gen += 1
        tel = self.telemetry
        if tel.enabled:
            tel.set_gauge("mesh_shards", self.mesh.shape[SUB_AXIS])
            tel.set_gauge("shards_lost", len(self.lost_shards))

    # --- degrade-to-single-device admission (small tables) ----------------

    def _below_floor(self) -> bool:
        thr = self.min_rows_per_shard
        return bool(thr) and self.table.capacity // max(1, self.n_shards) < thr

    def _decide_mode(self) -> None:
        """Flip between mesh serving and the single-device fallback
        when the per-shard row count crosses `min_rows_per_shard`.
        Capacity is grow-only, so a workload flips at most once each
        way; each flip forces a full re-upload on the new path (the
        other path's device state is dropped, not kept coherent)."""
        want = self._below_floor()
        if want == self.degraded:
            return
        tel = self.telemetry
        if want:
            from ..models.router import DeviceTable

            single = DeviceTable(
                self.table,
                device=self._mesh_mod.primary_device(self.mesh),
                index=self.index,
                telemetry=self.telemetry,
            )
            single.transfer_chunk_hits = self.transfer_chunk_hits
            self._single = single
            if tel.enabled and self._dev is not None:
                # a flip away from a mesh that served; a table that
                # starts below the floor is admission, not degradation
                tel.count("mesh_degraded_single_device_total")
        else:
            self._single = None
            self._dev = None
            self._dev_meta = None
            self._dev_slots = None
            self._dev_residual = None
            self._synced_capacity = 0
        self.degraded = want
        if tel.enabled:
            tel.set_gauge("mesh_degraded_single_device", int(want))

    def _match_kernel(self, mh: int):
        k = self._match_ids_cache.get(mh)
        if k is None:
            k = make_match_ids_kernel(self.mesh, mh)
            self._match_ids_cache[mh] = k
        return k

    def _hash_kernel(self, mh: int):
        # keyed on (mh, logical bucket count): capacity growth changes
        # the hash mask, and on an N-1 mesh the mask can no longer be
        # derived from the padded per-shard slice width
        nb = self.index.n_buckets
        k = self._hash_cache.get((mh, nb))
        if k is None:
            k = make_sharded_hash_kernel(self.mesh, mh, n_buckets=nb)
            self._hash_cache[(mh, nb)] = k
        return k

    def _nchips(self) -> int:
        return int(self.mesh.devices.size)

    def _combine_probe(self, mh: int):
        """Cached combine-only probe kernel for the CURRENT layout
        (mesh microscope sampled splits; see
        make_combine_probe_kernel). Cleared on every re-shard."""
        k = self._probe_cache.get(mh)
        if k is None:
            k = make_combine_probe_kernel(self.mesh, mh)
            self._probe_cache[mh] = k
        return k

    def _put_repl(self, a):
        return jax.device_put(a, NamedSharding(self.mesh, P()))

    def _put_sub(self, a, pad_value=0):
        """Sub-shard a host array, ceil-padding the leading axis to a
        multiple of n_sub with `pad_value` (trailing pad — logical ids
        keep their positions; see mesh.shard_rows)."""
        import numpy as np

        pad = (-a.shape[0]) % self.mesh.shape[SUB_AXIS]
        if pad:
            width = ((0, pad),) + ((0, 0),) * (a.ndim - 1)
            a = np.pad(a, width, constant_values=pad_value)
        self._count_shard_rows(
            np.full(self.mesh.shape[SUB_AXIS],
                    a.shape[0] // self.mesh.shape[SUB_AXIS], np.int64)
        )
        return jax.device_put(a, NamedSharding(self.mesh, P(SUB_AXIS)))

    def _count_shard_rows(self, per_shard) -> None:
        """Per-shard host->device transfer accounting
        (emqx_xla_mesh_shard_transfer_rows_total{shard=...}): the
        combined fetch is shard-count-independent, so the upload side
        is where per-shard skew shows up."""
        tel = self.telemetry
        if not tel.enabled:
            return
        for s, n in enumerate(per_shard):
            if n:
                tel.count_labeled(
                    "mesh_shard_transfer_rows_total",
                    {"shard": str(s)},
                    int(n),
                )

    def _sync_index(self) -> None:
        import numpy as np

        from ..ops.hash_index import BUCKET_W, ClassMeta, SlotArrays

        ix = self.index
        assert ix is not None
        n_sub = self.mesh.shape[SUB_AXIS]
        # buckets per shard is ceil(n_buckets / n_sub): when n_sub does
        # not divide the pow2 count (an N-1 survivor mesh) the trailing
        # pad buckets are inert — fp=0 can never byte-match (p8 >= 1),
        # bucket=-1 is rejected by the kernel's g_bkt >= 0 check, and
        # the logical hash mask (n_buckets - 1) never probes them.
        nb_pad = (-ix.n_buckets) % n_sub
        if ix.meta_dirty or self._dev_meta is None:
            self._dev_meta = ClassMeta(
                *(self._put_repl(np.array(a)) for a in ix.packed_meta())
            )
            ix.meta_dirty = False
        if ix.rebuilt or self._dev_slots is None:
            ix.dirty_slots.clear()
            fp = np.array(ix.slots.fp)
            bkt = np.array(ix.slots.bucket)
            if nb_pad:
                # slot pad must stay bucket-aligned (per-shard slots ==
                # buckets-per-shard * BUCKET_W), which _put_sub's plain
                # ceil-pad would not produce
                sp = nb_pad * BUCKET_W
                fp = np.pad(fp, (0, sp))
                bkt = np.pad(bkt, (0, sp), constant_values=-1)
            self._dev_slots = SlotArrays(
                self._put_sub(fp),
                self._put_sub(bkt),
                self._put_sub(np.array(ix.slots.probe)),
            )
            ix.rebuilt = False
        elif ix.dirty_slots:
            dirty = np.unique(np.asarray(ix.dirty_slots, np.int32))
            ix.dirty_slots.clear()
            total = len(dirty)
            k = self.DELTA_BATCH
            n_b = 1 << max(0, -(-total // k) - 1).bit_length()
            idx = np.full(n_b * k, dirty[-1], np.int32)
            idx[:total] = dirty
            shape2 = (n_b, k)
            self.telemetry.record_shape(
                "mesh_slot_delta", (n_b, len(ix.slots.fp))
            )
            out = self._apply_slot_delta(
                self._dev_slots.fp,
                self._dev_slots.bucket,
                self._dev_slots.probe,
                jnp.asarray(idx.reshape(shape2)),
                jnp.asarray(ix.slots.fp[idx].reshape(shape2)),
                jnp.asarray(ix.slots.bucket[idx].reshape(shape2)),
                jnp.asarray(
                    ix.slots.probe[idx // BUCKET_W].reshape(shape2)
                ),
            )
            self._dev_slots = SlotArrays(*out)
        cap_padded = (
            self.table.capacity + (-self.table.capacity) % n_sub
        )
        if ix.residual_dirty or self._dev_residual is None or (
            self._dev_residual.shape[0] != cap_padded
        ):
            mask = np.zeros(self.table.capacity, bool)
            if ix.residual_rows:
                mask[list(ix.residual_rows)] = True
            self._dev_residual = self._put_sub(mask)
            ix.residual_dirty = False

    def sync(self) -> int:
        fi = self.fault_injector
        if fi is not None:
            fi.check("sync")
        self._decide_mode()
        if self.degraded:
            # single-device fallback owns its own sync telemetry; the
            # fault check stays at this wrapper (the injector reasons
            # about mesh shards, not the fallback device)
            self._single.transfer_chunk_hits = self.transfer_chunk_hits
            return self._single.sync()
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

    def _sync_impl(self):
        t = self.table
        sc = self.scope
        if self._dev is None or t.grew or t.capacity != self._synced_capacity:
            rec = sc.begin("sync", self._nchips()) if sc is not None else None
            n = len(t.dirty)
            t.drain_dirty()
            snap = t.snapshot()
            if rec is not None:
                sc.lap(rec, "host_encode")
            self._dev = self._mesh_mod.put_filters(snap, self.mesh)
            self._synced_capacity = t.capacity
            if rec is not None:
                sc.lap(rec, "h2d_stage")
                sc.finish_sync(rec)
            if self.index is not None:
                self._sync_index()
            return n, True
        dirty = t.drain_dirty()  # ndarray: row id 0 alone is falsy —
        if len(dirty) == 0:      # test LENGTH, never truthiness
            if self.index is not None:
                self._sync_index()
            return 0, False
        import numpy as np

        rec = sc.begin("sync", self._nchips()) if sc is not None else None
        total = len(dirty)
        arr = np.asarray(dirty, np.int32)
        # ONE dispatch for the whole churn: pad to [n_b, K] (n_b pow2
        # so recompiles stay log-bounded) and scan inside the kernel
        k = self.DELTA_BATCH
        n_b = 1 << max(0, -(-total // k) - 1).bit_length()  # pow2 ceil-div
        idx = np.full(n_b * k, arr[-1], np.int32)
        idx[:total] = arr
        shape2 = (n_b, k)
        tel = self.telemetry
        if tel.enabled:
            n_sub = self.mesh.shape[SUB_AXIS]
            rs = self._mesh_mod.shard_rows(t.capacity, self.mesh)
            self._count_shard_rows(
                np.bincount(
                    np.clip(arr // rs, 0, n_sub - 1), minlength=n_sub
                )
            )
        ix = self.index
        if (
            ix is not None
            and ix.dirty_slots
            and not ix.rebuilt
            and self._dev_slots is not None
            and self._mesh_sync is not None
        ):
            # steady-state churn touches rows AND cuckoo slots: apply
            # both delta streams in ONE fused dispatch (the split
            # kernels pay two serial launches per sync)
            from ..ops.hash_index import BUCKET_W, SlotArrays

            sdirty = np.unique(np.asarray(ix.dirty_slots, np.int32))
            ix.dirty_slots.clear()
            s_total = len(sdirty)
            s_nb = 1 << max(0, -(-s_total // k) - 1).bit_length()
            sidx = np.full(s_nb * k, sdirty[-1], np.int32)
            sidx[:s_total] = sdirty
            s_shape2 = (s_nb, k)
            tel.record_shape(
                "mesh_sync",
                (n_b, s_nb, t.capacity, t.max_levels, len(ix.slots.fp)),
            )
            if tel.enabled:
                tel.set_gauge("mesh_sync_batch_rows", total + s_total)
            if rec is not None:
                sc.lap(rec, "host_encode")
            # staged args hoisted so the microscope can lap the host
            # gather + device placement (h2d_stage) apart from the
            # fused kernel dispatch (program_launch)
            staged = (
                jnp.asarray(idx.reshape(shape2)),
                jnp.asarray(t.words[idx].reshape(shape2 + (t.max_levels,))),
                jnp.asarray(t.prefix_len[idx].reshape(shape2)),
                jnp.asarray(t.has_hash[idx].reshape(shape2)),
                jnp.asarray(t.root_wild[idx].reshape(shape2)),
                jnp.asarray(t.active[idx].reshape(shape2)),
                jnp.asarray(sidx.reshape(s_shape2)),
                jnp.asarray(ix.slots.fp[sidx].reshape(s_shape2)),
                jnp.asarray(ix.slots.bucket[sidx].reshape(s_shape2)),
                jnp.asarray(
                    ix.slots.probe[sidx // BUCKET_W].reshape(s_shape2)
                ),
            )
            if rec is not None:
                sc.lap(rec, "h2d_stage")
            out = self._mesh_sync(
                self._dev,
                self._dev_slots.fp,
                self._dev_slots.bucket,
                self._dev_slots.probe,
                *staged,
            )
            if rec is not None:
                sc.lap(rec, "program_launch")
                sc.finish_sync(rec)
            self._dev = out[0]
            self._dev_slots = SlotArrays(*out[1:])
            self._sync_index()  # meta/residual legs only — slots done
            return total, False
        tel.record_shape(
            "apply_delta", (n_b, t.capacity, t.max_levels)
        )
        if tel.enabled:
            tel.set_gauge("mesh_sync_batch_rows", total)
        if rec is not None:
            sc.lap(rec, "host_encode")
        staged = (
            jnp.asarray(idx.reshape(shape2)),
            jnp.asarray(t.words[idx].reshape(shape2 + (t.max_levels,))),
            jnp.asarray(t.prefix_len[idx].reshape(shape2)),
            jnp.asarray(t.has_hash[idx].reshape(shape2)),
            jnp.asarray(t.root_wild[idx].reshape(shape2)),
            jnp.asarray(t.active[idx].reshape(shape2)),
        )
        if rec is not None:
            sc.lap(rec, "h2d_stage")
        self._dev = self._apply_delta(self._dev, *staged)
        if rec is not None:
            sc.lap(rec, "program_launch")
            sc.finish_sync(rec)
        if self.index is not None:
            self._sync_index()
        return total, False

    def _block_mh(self) -> int:
        """Per-block hit capacity, bounded by the transfer chunk when
        one is set (ops/transfer.chunk_hits semantics — oversize
        results escalate through the exact-size retry, so the bound
        costs a counted re-dispatch, never correctness), then raised
        to the sticky escalation floor: the combined buffer budgets
        the dp-block TOTAL across shards, so a workload that
        overflowed once would overflow every batch — the floor trades
        one-time extra transfer width for never re-dispatching."""
        mh = self.default_mh
        cap = self.transfer_chunk_hits
        if cap is not None and mh > cap >= 1024:
            mh = 1 << (cap.bit_length() - 1)
        return max(mh, self._mh_floor)

    def match_ids_begin(self, enc: PackedTopics, residual: bool = False):
        """Launch the sharded dense compaction kernel WITHOUT forcing
        any device->host transfer AND begin the result copy
        (ops/transfer.FetchTicket, handle's last element — the same
        begin contract as the single-device DeviceTable): the
        pipelined publish path overlaps this batch's mesh execution +
        device->host transfer with the next batch's host-side encode.
        Returns an opaque handle for match_ids_finish."""
        if self.degraded:
            return ("1dev",) + self._single.match_ids_begin(enc, residual)
        assert self._dev is not None, "sync() before matching"
        enc = enc.fields()
        dev = self._dev
        if residual:
            assert self._dev_residual is not None
            dev = dev._replace(active=self._dev_residual)
        sc = self.scope
        rec = None
        if sc is not None:
            rec = sc.begin("ids", self._nchips())
            enc = self._mesh_mod.pad_topics(enc, self.mesh)
            sc.lap(rec, "host_encode")
        t_dev = self._mesh_mod.put_topics(enc, self.mesh)
        if rec is not None:
            sc.lap(rec, "h2d_stage")
        mh = self._block_mh()
        self.telemetry.record_shape(
            "mesh_match_ids", (int(t_dev.ids.shape[0]), mh)
        )
        from ..ops import transfer as transfer_ops

        out = self._match_kernel(mh)(dev, t_dev)
        if rec is not None:
            sc.lap(rec, "program_launch")
        prev = STAGE_MARK.enter("ticket_start")
        ticket = transfer_ops.start_fetch(out, self.telemetry)
        STAGE_MARK.leave(prev)
        self.telemetry.count("transfer_buffers_total", len(t_dev) + len(out))
        if rec is not None:
            sc.attach(rec, ticket)
        return (dev, t_dev, mh, rec, ticket)

    def match_ids_finish(self, pending):
        """Force the transfers for a begun dense match, escalating
        per-block capacity on overflow (sticky: the new capacity
        becomes the floor for later begins). Returns (ti 1d, ri 1d)
        host arrays of equal length (valid pairs only)."""
        import numpy as np

        if pending[0] == "1dev":
            return self._single.match_ids_finish(pending[1:])
        dev, t_dev, mh, rec, ticket = pending
        tel = self.telemetry
        t0 = tel.clock()
        ti, ri, totals = ticket.wait()
        totals = np.asarray(totals)
        mh0 = mh
        while int(totals.max(initial=0)) > mh:
            tel.count("escalations_total")
            mh = max(mh * 2, 1 << int(totals.max()).bit_length())
            tel.record_shape(
                "mesh_match_ids", (int(t_dev.ids.shape[0]), mh)
            )
            self._mh_floor = max(self._mh_floor, mh)
            out = self._match_kernel(mh)(dev, t_dev)
            tel.count("transfer_buffers_total", len(out))
            ti, ri, totals = out
            totals = np.asarray(totals)
        ti = np.asarray(ti).reshape(-1)
        ri = np.asarray(ri).reshape(-1)
        keep = ti >= 0
        if tel.enabled:
            tel.observe_family("mesh_combine_seconds", tel.clock() - t0)
        sc = self.scope
        if sc is not None and rec is not None and mh == mh0:
            # escalated dispatches re-ran synchronously — their clock
            # pairs no longer describe one dispatch, so they are
            # dropped (the escalation is already counted above)
            shards = None
            if rec.sampled:
                rs = self._mesh_mod.shard_rows(
                    self.table.capacity, self.mesh
                )
                shards = ri[keep] // rs
            sc.finish(
                rec, self, ticket, mh,
                hits=int(keep.sum()), shard_ids=shards,
            )
        return ti[keep], ri[keep]

    def match_ids(self, enc: PackedTopics, residual: bool = False):
        """All (topic, row) hit pairs for an encoded topic batch via
        the dense kernel. With residual=True the active mask narrows
        to the class index's residual rows (the unclassed fallback).
        Returns (ti 1d, ri 1d) host arrays of equal length (valid
        pairs only), escalating per-block capacity on overflow.
        Composed from the begin/finish pipeline halves."""
        return self.match_ids_finish(self.match_ids_begin(enc, residual))

    def match_hash_begin(self, enc: PackedTopics):
        """Launch the mesh-sharded production hash kernel without a
        host fetch AND begin the result transfer (ticket last, same
        contract as DeviceTable.match_hash_begin). Returns an opaque
        handle for match_hash_finish."""
        if self.degraded:
            return ("1dev",) + self._single.match_hash_begin(enc)
        assert self._dev_slots is not None, "sync() before matching"
        enc = enc.fields()
        sc = self.scope
        rec = None
        if sc is not None:
            rec = sc.begin("hash", self._nchips())
            enc = self._mesh_mod.pad_topics(enc, self.mesh)
            sc.lap(rec, "host_encode")
        t_dev = self._mesh_mod.put_topics(enc, self.mesh)
        if rec is not None:
            sc.lap(rec, "h2d_stage")
        mh = self._block_mh()
        self.telemetry.record_shape(
            "mesh_match_ids_hash", (int(t_dev.ids.shape[0]), mh)
        )
        from ..ops import transfer as transfer_ops

        out = self._hash_kernel(mh)(self._dev_meta, self._dev_slots, t_dev)
        if rec is not None:
            sc.lap(rec, "program_launch")
        prev = STAGE_MARK.enter("ticket_start")
        ticket = transfer_ops.start_fetch(out, self.telemetry)
        STAGE_MARK.leave(prev)
        self.telemetry.count("transfer_buffers_total", len(t_dev) + len(out))
        if rec is not None:
            sc.attach(rec, ticket)
        return (t_dev, mh, rec, ticket)

    def match_hash_finish(self, pending):
        """Force the transfers for a begun hash match, escalating
        per-block capacity on overflow (sticky floor, same policy as
        match_ids_finish). Same result contract as match_hash."""
        import numpy as np

        if pending[0] == "1dev":
            return self._single.match_hash_finish(pending[1:])
        t_dev, mh, rec, ticket = pending
        tel = self.telemetry
        t0 = tel.clock()
        ti, bi, totals, amb = ticket.wait()
        totals = np.asarray(totals)
        mh0 = mh
        while int(totals.max(initial=0)) > mh:
            tel.count("hash_overflow_retries_total")
            mh = max(mh * 2, 1 << int(totals.max()).bit_length())
            tel.record_shape(
                "mesh_match_ids_hash", (int(t_dev.ids.shape[0]), mh)
            )
            self._mh_floor = max(self._mh_floor, mh)
            out = self._hash_kernel(mh)(self._dev_meta, self._dev_slots, t_dev)
            tel.count("transfer_buffers_total", len(out))
            ti, bi, totals, amb = out
            totals = np.asarray(totals)
        ti = np.asarray(ti).reshape(-1)
        bi = np.asarray(bi).reshape(-1)
        keep = ti >= 0
        if tel.enabled:
            tel.observe_family("mesh_combine_seconds", tel.clock() - t0)
        sc = self.scope
        if sc is not None and rec is not None and mh == mh0:
            shards = None
            if rec.sampled:
                n_sub = self.mesh.shape[SUB_AXIS]
                nb_loc = -(-self.index.n_buckets // n_sub)
                shards = bi[keep] // nb_loc
            sc.finish(
                rec, self, ticket, mh,
                hits=int(keep.sum()), shard_ids=shards,
            )
        return ti[keep], bi[keep], int(np.asarray(amb).reshape(-1)[0])

    def match_hash(self, enc: PackedTopics):
        """(topic, bucket) candidates via the mesh-sharded production
        hash kernel. Returns (ti 1d, bi 1d, amb int): global topic
        indices (may include dp-padding rows — callers drop
        t_idx >= batch), global bucket ids, and the mesh-wide
        ambiguity count (amb > 0 -> caller re-matches on a host path,
        see ops.hash_index.match_ids_hash)."""
        return self.match_hash_finish(self.match_hash_begin(enc))

    # --- mesh AOT warmup (recompiles_at_serve_total == 0 discipline) ------

    def invalidate(self) -> None:
        """Drop the device copy: the next sync uploads it in full."""
        if self._single is not None:
            self._single.invalidate()
        self._dev = self._dev_meta = self._dev_slots = None
        self._dev_residual = None

    def shape_key(self) -> tuple:
        """DeviceTable.shape_key for the mesh: the serving mode the next
        sync picks (_decide_mode), the shard layout and the per-block
        hit capacity join the host shapes."""
        if self._below_floor():
            single = self._single
            if single is None:
                return ("single",)
            return ("single",) + single.shape_key()
        ix = self.index
        return (
            "mesh",
            self.shard_gen,
            self._block_mh(),
            self.table.capacity,
            None if ix is None else (
                ix.packed_len(), ix.n_buckets, bool(ix.residual_rows),
            ),
            self.scope is not None,
        )

    def warmup_deltas(self) -> int:
        """Pre-trace the churn sync kernels (row delta, slot delta,
        fused row+slot) at their small pow2 batch shapes so the first
        serve-time churn wave hits a warm compile cache — the mesh
        counterpart of Router.warmup_shapes' match-kernel ladder.
        Re-applies row/slot 0's CURRENT host truth, so every warm
        dispatch is semantically a no-op. Requires a completed full
        sync(); returns the number of kernels warmed."""
        if self.degraded:
            return self._single.warmup_deltas()
        if self._dev is None:
            return 0
        import numpy as np

        t = self.table
        k = self.DELTA_BATCH
        tel = self.telemetry
        warmed = 0
        for n_b in (1, 2):
            shape2 = (n_b, k)
            idx = np.zeros(n_b * k, np.int32)
            row_args = (
                jnp.asarray(idx.reshape(shape2)),
                jnp.asarray(t.words[idx].reshape(shape2 + (t.max_levels,))),
                jnp.asarray(t.prefix_len[idx].reshape(shape2)),
                jnp.asarray(t.has_hash[idx].reshape(shape2)),
                jnp.asarray(t.root_wild[idx].reshape(shape2)),
                jnp.asarray(t.active[idx].reshape(shape2)),
            )
            tel.record_shape("apply_delta", (n_b, t.capacity, t.max_levels))
            self._dev = self._apply_delta(self._dev, *row_args)
            warmed += 1
            ix = self.index
            if ix is None or self._dev_slots is None:
                continue
            from ..ops.hash_index import BUCKET_W, SlotArrays

            slot_args = (
                jnp.asarray(idx.reshape(shape2)),
                jnp.asarray(ix.slots.fp[idx].reshape(shape2)),
                jnp.asarray(ix.slots.bucket[idx].reshape(shape2)),
                jnp.asarray(ix.slots.probe[idx // BUCKET_W].reshape(shape2)),
            )
            tel.record_shape("mesh_slot_delta", (n_b, len(ix.slots.fp)))
            out = self._apply_slot_delta(
                self._dev_slots.fp, self._dev_slots.bucket,
                self._dev_slots.probe, *slot_args,
            )
            self._dev_slots = SlotArrays(*out)
            warmed += 1
            if self._mesh_sync is None:
                continue
            tel.record_shape(
                "mesh_sync",
                (n_b, n_b, t.capacity, t.max_levels, len(ix.slots.fp)),
            )
            out = self._mesh_sync(
                self._dev,
                self._dev_slots.fp, self._dev_slots.bucket,
                self._dev_slots.probe, *row_args, *slot_args,
            )
            self._dev = out[0]
            self._dev_slots = SlotArrays(*out[1:])
            warmed += 1
        return warmed

    def warmup_escalated(self, enc: PackedTopics) -> int:
        """Pre-build the first escalation step (2x the current block
        capacity) for both match kernels at this batch shape: a
        serve-time overflow then re-dispatches against a warm cache
        and the shape key is already recorded, keeping
        recompiles_at_serve_total at 0. Dispatch-only — results are
        dropped unfetched (compilation happens at call time; no
        blocking fetch on this path)."""
        if self.degraded or self._dev is None:
            return 0
        t_dev = self._mesh_mod.put_topics(enc.fields(), self.mesh)
        b = int(t_dev.ids.shape[0])
        mh2 = self._block_mh() * 2
        warmed = 0
        self.telemetry.record_shape("mesh_match_ids", (b, mh2))
        self._match_kernel(mh2)(self._dev, t_dev)
        warmed += 1
        if self._dev_slots is not None:
            self.telemetry.record_shape("mesh_match_ids_hash", (b, mh2))
            self._hash_kernel(mh2)(self._dev_meta, self._dev_slots, t_dev)
            warmed += 1
        sc = self.scope
        if sc is not None:
            # pre-warm the microscope's combine-only probe at the
            # current block capacity and its first escalation so
            # serve-time sampled splits never compile
            warmed += sc.warm_probe(self, self._block_mh())
            warmed += sc.warm_probe(self, mh2)
        return warmed
