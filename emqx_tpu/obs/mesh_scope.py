"""Mesh microscope — per-dispatch decomposition of every mesh match /
sync dispatch into first-class sub-stages (ISSUE 20).

ROADMAP item 2 demands a monotone 1→8 curve "or the measured per-leg
excuse committed", but the r15 blame (N serialized per-shard program
launches, O(N) flat all_gather buffers) was inferred from totals, not
measured per leg. This module is the instrument: launch/land clock
pairs around the begin halves plus a FetchTicket land hook decompose
the dispatch wall into

    host_encode        host-side batch pad (mesh.pad_topics)
    h2d_stage          device_put of the padded batch onto the mesh
    program_launch     host dispatch span of the jitted shard_map call
                       (the N-serialized per-shard launch overhead,
                       measured directly)
    shard_compute      device span minus the combine leg
    combine_collective all_gather + recompaction + psum, isolated by a
                       sampled combine-only probe dispatch
    d2h_transfer       residual blocking wait paid at finish
                       (FetchTicket.waited)

self-checked against the dispatch wall with the PR 17 discipline: the
stage sum must land within DECOMP_TOLERANCE of the wall, in/out-of-band
counters + a last-ratio gauge make decomposition drift a dashboard
fact instead of a silent lie.

The combine leg cannot be host-timed inside one dispatch (XLA fuses
the whole shard_map program), so it is measured *differentially*: every
`sample_n`-th dispatch, after its real measurement completes, the scope
re-dispatches a combine-only probe kernel with the same (n_sub, mh)
reduction shape (parallel.sharded_match.make_combine_probe_kernel) and
uses its device span as the collective cost; unsampled dispatches split
their device span by the last measured fraction. Probes run only at
shapes pre-warmed through `warm_probe` (warmup_escalated calls it), so
`recompiles_at_serve_total` stays 0 — an unwarmed shape skips the split
and counts `emqx_xla_mesh_scope_split_skipped_total`.

Collective-cost ledger per dispatch: gathered-buffer bytes
(dp * n_sub * mh * 2 int32 lanes — the O(N) flat gather item 2 names),
max_hits vs actual-hits occupancy (the ragged-combine headroom), and
sampled per-shard hit skew. Plus the per-chip generalization of PR 17's
ring timeline: launch→land spans credited to every serving chip
(`emqx_xla_mesh_ring_occupancy_ratio{chip}`), evacuated chips stop
accruing.

Attachment is a None-seam on ShardedDeviceTable (`table.scope`), the
same zero-cost-when-disabled contract as the chaos fault injector: with
`broker.perf.tpu_mesh_scope_enable=false` the attribute stays None and
the served path pays one attribute read per dispatch, no clocks, no
land hooks.
"""

from __future__ import annotations

from time import perf_counter
from typing import Any, Dict, List, Optional

from .kernel_telemetry import (
    CountHistogram,
    StreamingHistogram,
    render_histogram_lines,
)

# the mesh dispatch sub-stage taxonomy; every name must have a live
# recording site (tests/test_static_gate.py extends the no-orphan-stage
# leg to this tuple) and lint coverage
MESH_STAGES = (
    "host_encode",
    "h2d_stage",
    "program_launch",
    "shard_compute",
    "combine_collective",
    "d2h_transfer",
)

# PR 17 discipline: stage sum within 10% of the dispatch wall, checked
# on every ticketed dispatch
DECOMP_TOLERANCE = 0.10


class _Record:
    """One in-flight instrumented dispatch (begin → finish)."""

    __slots__ = (
        "kind", "nchips", "t0", "t_last", "launch_end", "laps", "sampled",
    )

    def __init__(self, kind: str, nchips: int, t0: float, sampled: bool):
        self.kind = kind
        self.nchips = nchips
        self.t0 = t0
        self.t_last = t0
        self.launch_end = t0
        self.laps: Dict[str, float] = {}
        self.sampled = sampled


class MeshScope:
    """Per-dispatch mesh decomposition + collective-cost ledger."""

    def __init__(self, telemetry=None, sample_n: int = 64) -> None:
        self.telemetry = telemetry
        self.sample_n = max(1, int(sample_n or 1))
        self.clock = perf_counter
        self.dispatches = 0
        self.splits_sampled = 0
        self.split_skipped = 0
        # decomposition self-check (sentinel's in/out-of-band shape)
        self.decomp_in_band = 0
        self.decomp_out_of_band = 0
        self.decomp_last_ratio = 0.0
        # (stage, nchips) -> StreamingHistogram; nchips -> wall hist
        self.stage_hist: Dict[tuple, StreamingHistogram] = {}
        self.wall_hist: Dict[int, StreamingHistogram] = {}
        # collective-cost ledger
        self.gather_bytes_total = 0
        self.gather_bytes_last = 0
        self.occupancy_hist: Dict[int, CountHistogram] = {}
        self.occupancy_last = 0.0
        self.combine_frac: Dict[int, float] = {}
        self.shard_skew: Optional[Dict[str, float]] = None
        # per-chip busy ledger: chip id -> [busy_s, last_busy_end]
        self.chips: Dict[int, List[float]] = {}
        self._track_t0: Optional[float] = None
        # probe shapes proven warm: (shard_gen, mh)
        self._probe_warm: set = set()
        self._chip_cache: tuple = (-1, ())

    # --- begin-half hooks (clock laps only — never force host values) -----

    def begin(self, kind: str, nchips: int) -> _Record:
        self.dispatches += 1
        sampled = kind != "sync" and (self.dispatches % self.sample_n == 0)
        return _Record(kind, nchips, self.clock(), sampled)

    def lap(self, rec: _Record, stage: str) -> None:
        """Fold the span since the previous mark into `stage`."""
        now = self.clock()
        rec.laps[stage] = rec.laps.get(stage, 0.0) + (now - rec.t_last)
        rec.t_last = now

    def attach(self, rec: _Record, ticket) -> None:
        """Install the land hook on a just-issued FetchTicket: the
        engine's ready() polls (every _RING_POLL_S) stamp the land
        time, giving the launch/land clock pair the device-span split
        rests on."""
        rec.launch_end = rec.t_last
        ticket.land_clock = self.clock

    # --- finish-half ------------------------------------------------------

    def _observe_stage(self, rec: _Record, stage: str, seconds: float) -> None:
        key = (stage, rec.nchips)
        h = self.stage_hist.get(key)
        if h is None:
            h = self.stage_hist[key] = StreamingHistogram()
        h.observe(max(0.0, seconds))

    def finish(
        self,
        rec: _Record,
        table,
        ticket,
        mh: int,
        hits: int,
        shard_ids=None,
    ) -> None:
        """Complete a ticketed match dispatch: split the device span,
        fold the ledger, credit the chips, self-check against the
        wall."""
        t_land = ticket.landed_at
        waited = ticket.waited
        now = self.clock()
        if t_land is None:  # hook lost (host-fallback arrays) — bound it
            t_land = now - waited
        dev_span = max(0.0, t_land - rec.launch_end)
        n_sub = int(table.mesh.devices.shape[-1])
        dp = rec.nchips // max(1, n_sub)
        # combine split: sampled dispatches re-measure via the probe;
        # the rest reuse the last measured fraction for this width
        if rec.sampled:
            probe_s = self._probe_span(table, mh)
            if probe_s is not None:
                self.splits_sampled += 1
                if dev_span > 0:
                    self.combine_frac[rec.nchips] = max(
                        0.0, min(1.0, probe_s / dev_span)
                    )
        frac = self.combine_frac.get(rec.nchips)
        combine_s = dev_span * frac if frac is not None else 0.0
        self._observe_stage(rec, "shard_compute", dev_span - combine_s)
        self._observe_stage(rec, "combine_collective", combine_s)
        self._observe_stage(rec, "d2h_transfer", waited)
        for stage, s in rec.laps.items():
            self._observe_stage(rec, stage, s)
        # --- collective ledger -------------------------------------------
        gb = dp * n_sub * mh * 2 * 4  # two int32 lanes, gathered flat
        self.gather_bytes_total += gb
        self.gather_bytes_last = gb
        occ = hits / float(max(1, dp * mh))
        self.occupancy_last = occ
        oh = self.occupancy_hist.get(rec.nchips)
        if oh is None:
            oh = self.occupancy_hist[rec.nchips] = CountHistogram()
        oh.observe(occ)
        if shard_ids is not None and len(shard_ids):
            import numpy as np

            per = np.bincount(
                np.clip(shard_ids, 0, n_sub - 1), minlength=n_sub
            )
            self.shard_skew = {
                "min": int(per.min()),
                "median": float(np.median(per)),
                "max": int(per.max()),
            }
        # --- per-chip busy (launch→land credited to serving chips) --------
        self._credit_chips(table, rec.launch_end, t_land)
        # --- wall self-check ----------------------------------------------
        wall = max(1e-9, (t_land - rec.t0) + waited)
        stage_sum = (
            sum(rec.laps.values()) + dev_span + waited
        )
        self.decomp_last_ratio = stage_sum / wall
        if abs(stage_sum - wall) <= DECOMP_TOLERANCE * wall:
            self.decomp_in_band += 1
        else:
            self.decomp_out_of_band += 1
        wh = self.wall_hist.get(rec.nchips)
        if wh is None:
            wh = self.wall_hist[rec.nchips] = StreamingHistogram()
        wh.observe(wall)

    def finish_sync(self, rec: _Record) -> None:
        """Complete a sync dispatch: lap stages only (no ticket, no
        device-span split — the donated outputs never transfer back)."""
        for stage, s in rec.laps.items():
            self._observe_stage(rec, stage, s)
        wall = max(1e-9, self.clock() - rec.t0)
        wh = self.wall_hist.get(rec.nchips)
        if wh is None:
            wh = self.wall_hist[rec.nchips] = StreamingHistogram()
        wh.observe(wall)

    # --- combine probe ----------------------------------------------------

    def warm_probe(self, table, mh: int) -> int:
        """Pre-build + pre-dispatch the combine-only probe for this
        layout/mh so serve-time sampled splits hit a warm cache
        (recompiles_at_serve_total == 0 discipline). Idempotent."""
        key = (table.shard_gen, mh)
        if key in self._probe_warm:
            return 0
        tel = self.telemetry
        if tel is not None:
            n_sub = int(table.mesh.devices.shape[-1])
            tel.record_shape("mesh_scope_probe", (n_sub, mh))
        k = table._combine_probe(mh)
        import jax.numpy as jnp

        k(jnp.int32(0))  # compile + one throwaway dispatch
        self._probe_warm.add(key)
        return 1

    def _probe_span(self, table, mh: int) -> Optional[float]:
        """Device span of one combine-only dispatch at the live
        reduction shape, or None when the shape was never warmed (the
        split is skipped, counted, and the last fraction keeps
        serving)."""
        if (table.shard_gen, mh) not in self._probe_warm:
            self.split_skipped += 1
            return None
        from ..ops import transfer as transfer_ops
        import jax.numpy as jnp

        k = table._combine_probe(mh)
        # a fresh salt per probe: no two probes are the same computation
        salt = jnp.int32(self.dispatches & 0x7FFFFFFF)
        out = k(salt)
        t_launched = self.clock()
        tk = transfer_ops.start_fetch(out)
        tk.land_clock = self.clock
        tk.wait()
        land = tk.landed_at if tk.landed_at is not None else self.clock()
        return max(0.0, land - t_launched)

    # --- per-chip timeline ------------------------------------------------

    def _chips_of(self, table) -> tuple:
        gen = table.shard_gen
        if self._chip_cache[0] != gen:
            ids = tuple(
                int(d.id) for d in table.mesh.devices.reshape(-1)
            )
            self._chip_cache = (gen, ids)
        return self._chip_cache[1]

    def _credit_chips(self, table, t_launch: float, t_land: float) -> None:
        if self._track_t0 is None:
            self._track_t0 = t_launch
        for cid in self._chips_of(table):
            ent = self.chips.get(cid)
            if ent is None:
                ent = self.chips[cid] = [0.0, 0.0]
            # overlapped ring slots must not double-count busy time
            start = max(t_launch, ent[1])
            if t_land > start:
                ent[0] += t_land - start
                ent[1] = t_land

    def chip_ratios(self) -> Dict[int, float]:
        out = {}
        t0 = self._track_t0
        for cid, (busy, last_end) in sorted(self.chips.items()):
            elapsed = max(1e-9, last_end - (t0 if t0 is not None else last_end))
            out[cid] = min(1.0, busy / elapsed) if elapsed > 1e-9 else 0.0
        return out

    # --- surfaces ---------------------------------------------------------

    def stage_wall_ratio(self, nchips: int) -> float:
        """Sum of recorded stage seconds over recorded wall seconds for
        one mesh width — the committed-artifact gate asserts >= 0.9."""
        wh = self.wall_hist.get(nchips)
        if wh is None or wh.sum <= 0:
            return 0.0
        ssum = sum(
            h.sum for (st, n), h in self.stage_hist.items() if n == nchips
        )
        return ssum / wh.sum

    def status(self) -> Dict[str, Any]:
        widths = sorted(self.wall_hist)
        total = self.decomp_in_band + self.decomp_out_of_band
        return {
            "enabled": True,
            "sample_n": self.sample_n,
            "dispatches": self.dispatches,
            "splits_sampled": self.splits_sampled,
            "split_skipped": self.split_skipped,
            "decomp": {
                "tolerance": DECOMP_TOLERANCE,
                "in_band": self.decomp_in_band,
                "out_of_band": self.decomp_out_of_band,
                "in_band_ratio": (
                    self.decomp_in_band / total if total else 1.0
                ),
                "last_ratio": round(self.decomp_last_ratio, 4),
            },
            "stages": {
                str(n): {
                    st: self.stage_hist[(st, n)].snapshot()
                    for st in MESH_STAGES
                    if (st, n) in self.stage_hist
                }
                for n in widths
            },
            "wall": {
                str(n): self.wall_hist[n].snapshot() for n in widths
            },
            "stage_wall_ratio": {
                str(n): round(self.stage_wall_ratio(n), 4) for n in widths
            },
            "collective": {
                "gather_bytes_total": self.gather_bytes_total,
                "gather_bytes_last": self.gather_bytes_last,
                "occupancy_last": round(self.occupancy_last, 6),
                "occupancy": {
                    str(n): h.snapshot()
                    for n, h in sorted(self.occupancy_hist.items())
                },
                "combine_frac": {
                    str(n): round(f, 4)
                    for n, f in sorted(self.combine_frac.items())
                },
            },
            "shard_skew": self.shard_skew,
            "chips": {
                str(c): round(r, 4) for c, r in self.chip_ratios().items()
            },
        }

    def prometheus_lines(self, node_name: str = "emqx@127.0.0.1") -> List[str]:
        """emqx_xla_mesh_* scope families. Labeled histograms render
        here (the collector has no labeled-histogram surface), same
        pattern as the sentinel's stage exposition."""
        node = f'node="{node_name}"'
        lines: List[str] = []
        if self.stage_hist:
            fam = "emqx_xla_mesh_stage_seconds"
            lines.append(f"# TYPE {fam} histogram")
            for (st, n) in sorted(self.stage_hist):
                render_histogram_lines(
                    lines, fam,
                    f'{node},nchips="{n}",stage="{st}"',
                    self.stage_hist[(st, n)], emit_type=False,
                )
        if self.wall_hist:
            fam = "emqx_xla_mesh_dispatch_wall_seconds"
            lines.append(f"# TYPE {fam} histogram")
            for n in sorted(self.wall_hist):
                render_histogram_lines(
                    lines, fam, f'{node},nchips="{n}"',
                    self.wall_hist[n], emit_type=False,
                )
        if self.occupancy_hist:
            fam = "emqx_xla_mesh_combine_occupancy"
            lines.append(f"# TYPE {fam} histogram")
            for n in sorted(self.occupancy_hist):
                render_histogram_lines(
                    lines, fam, f'{node},nchips="{n}"',
                    self.occupancy_hist[n], emit_type=False,
                )
        for fam, val in (
            ("emqx_xla_mesh_decomp_in_band_total", self.decomp_in_band),
            ("emqx_xla_mesh_decomp_out_of_band_total",
             self.decomp_out_of_band),
            ("emqx_xla_mesh_collective_gather_bytes_total",
             self.gather_bytes_total),
            ("emqx_xla_mesh_scope_samples_total", self.splits_sampled),
            ("emqx_xla_mesh_scope_split_skipped_total", self.split_skipped),
        ):
            lines.append(f"# TYPE {fam} counter")
            lines.append(f"{fam}{{{node}}} {val}")
        fam = "emqx_xla_mesh_decomp_last_ratio"
        lines.append(f"# TYPE {fam} gauge")
        lines.append(f"{fam}{{{node}}} {round(self.decomp_last_ratio, 6)}")
        if self.shard_skew is not None:
            fam = "emqx_xla_mesh_shard_skew_hits"
            lines.append(f"# TYPE {fam} gauge")
            for stat in ("min", "median", "max"):
                lines.append(
                    f'{fam}{{{node},stat="{stat}"}} '
                    f"{self.shard_skew[stat]}"
                )
        ratios = self.chip_ratios()
        if ratios:
            fam = "emqx_xla_mesh_ring_occupancy_ratio"
            lines.append(f"# TYPE {fam} gauge")
            for cid, r in ratios.items():
                lines.append(
                    f'{fam}{{{node},chip="{cid}"}} {round(r, 6)}'
                )
        return lines
