"""Sums the program's stage spans over the traced window.

While a `jax.profiler` trace runs, the broker writes one host span per
stage segment, named `emqx.<stage>` (emqx_tpu/obs/profiler.py, STAGES),
on the `/host:CPU` thread line of its event loop. This reads the
window's `.xplane.pb` (found by `devtrace.find_xplane`), clips every
such span to the `bench.window_start`/`bench.window_end` marks and sums
the seconds by stage. The sums are cached per trace file, so the
metrics that read them parse a trace once.

A run without a trace, or a program that writes no stage spans, gives
None: its metrics are left out of the result line.

`gap_stages` reads the longest idle gaps of the first chip by stage:
the share of each gap that each stage's segments cover, summed, where
`devtrace`'s labeller names a gap by its single longest host event. By
hand, on a kept trace:

    python3 benchmark/hostspans.py <trace dir>
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, List, Optional, Sequence

import numpy as np

import devtrace

PREFIX = "emqx."
_CACHE: Dict[str, Dict[str, float]] = {}


def stage_seconds(path: str) -> Dict[str, float]:
    """{stage: seconds inside the window} of the trace at `path`."""
    got = _CACHE.get(path)
    if got is not None:
        return got
    from jax.profiler import ProfileData

    marks: Dict[str, float] = {}
    spans = []
    for p in ProfileData.from_file(path).planes:
        if p.name != devtrace.HOST_PLANE:
            continue
        for line in p.lines:
            for e in line.events:
                name = e.name
                if name.startswith(PREFIX):
                    s = float(e.start_ns)
                    spans.append((name[len(PREFIX):], s, s + float(e.duration_ns)))
                elif name in (devtrace.MARK_START, devtrace.MARK_END):
                    marks[name] = float(e.start_ns)
    out: Dict[str, float] = {}
    if devtrace.MARK_START in marks and devtrace.MARK_END in marks:
        lo, hi = marks[devtrace.MARK_START], marks[devtrace.MARK_END]
        for stage, s, e in spans:
            c = devtrace.clip(s, e, lo, hi)
            if c is not None:
                out[stage] = out.get(stage, 0.0) + (c[1] - c[0]) / 1e9
    _CACHE[path] = out
    return out


def per_unit_us(ctx, stages: Sequence[str], units) -> Optional[float]:
    """1e6 x the window's seconds in `stages`, over `units` (publishes,
    batches): None without a trace, stage spans or units."""
    trace_dir = getattr(ctx.window, "trace", None)
    if trace_dir is None or not units:
        return None
    try:
        path = devtrace.find_xplane(trace_dir)
    except FileNotFoundError:
        return None
    seconds = stage_seconds(path)
    if not seconds:
        return None
    return 1e6 * sum(seconds.get(s, 0.0) for s in stages) / units


def gap_stages(path: str, top: int = devtrace.TOP) -> List[dict]:
    """The window's `top` longest idle gaps of the first chip (as
    `devtrace.reduce` finds them), longest first, each as {"gap_s",
    "covered_pct": share of the gap inside any stage span, "longest_pct":
    the longest single segment's share, "stages_pct": {stage: share of
    the gap its segments cover}}. [] without window marks."""
    from jax.profiler import ProfileData

    marks: Dict[str, float] = {}
    spans = []
    device: List[List] = []
    for p in ProfileData.from_file(path).planes:
        if p.name == devtrace.HOST_PLANE:
            for line in p.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        s = float(e.start_ns)
                        spans.append((e.name[len(PREFIX):], s, s + float(e.duration_ns)))
                    elif e.name in (devtrace.MARK_START, devtrace.MARK_END):
                        marks[e.name] = float(e.start_ns)
        elif p.name.startswith(devtrace.DEVICE_PREFIX):
            device.append([
                (float(e.start_ns), float(e.start_ns + e.duration_ns))
                for line in p.lines if line.name == devtrace.OPS_LINE
                for e in line.events
            ])
    if devtrace.MARK_START not in marks or devtrace.MARK_END not in marks:
        return []
    lo, hi = marks[devtrace.MARK_START], marks[devtrace.MARK_END]
    busy: List = []
    for ops in device:  # the first chip with work in the window
        busy = devtrace.union(
            [c for c in (devtrace.clip(s, e, lo, hi) for s, e in ops) if c]
        )
        if busy:
            break
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    names = sorted({n for n, _s, _e in spans})
    pos = {n: i for i, n in enumerate(names)}
    idx = np.array([pos[n] for n, _s, _e in spans], dtype=np.int64)
    starts = np.array([s for _n, s, _e in spans], dtype=np.float64)
    ends = np.array([e for _n, _s, e in spans], dtype=np.float64)
    out = []
    for gs, ge in gaps[:top]:
        ov = np.clip(np.minimum(ends, ge) - np.maximum(starts, gs), 0.0, None)
        by = np.bincount(idx, weights=ov, minlength=len(names))
        g = ge - gs
        out.append({
            "gap_s": g / 1e9,
            "covered_pct": 100.0 * float(by.sum()) / g,
            "longest_pct": 100.0 * float(ov.max(initial=0.0)) / g,
            "stages_pct": {
                names[i]: 100.0 * float(by[i]) / g
                for i in np.argsort(-by) if by[i] > 0
            },
        })
    return out


if __name__ == "__main__":
    arg = sys.argv[1]
    xplane = devtrace.find_xplane(arg) if os.path.isdir(arg) else arg
    print(json.dumps({"stage_seconds": stage_seconds(xplane)}))
    for gap in gap_stages(xplane):
        print(json.dumps(gap))
