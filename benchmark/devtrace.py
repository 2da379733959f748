"""Reduces a `jax.profiler` trace of the window to device numbers.

The trace is the `.xplane.pb` that `jax.profiler.stop_trace` writes
under `<dir>/plugins/profile/<time>/`, read with
`jax.profiler.ProfileData` (nothing else is needed). The harness marks
the window's start and end with host annotations named
`bench.window_start` and `bench.window_end`; every interval below is
clipped to the span between them.

  * device planes are those named `/device:TPU:<n>`; their "XLA Ops"
    line holds one event per operation that ran on the chip;
  * busy time is the union of those operation intervals, per chip;
  * a kernel's calls are the events of the "XLA Modules" line whose
    module name (before the "(<fingerprint>)" that tells its compiled
    variants apart) is one of the kernel's TRACE_NAMES; the names of the
    operations a variant ran in its first call (HLO text, parameter names
    and shapes included) are kept for the kernel's shape reader;
  * idle gaps are the stretches between busy intervals, each labelled by
    the host event (thread lines of `/host:CPU`) that overlaps it most,
    with the share of the gap it covers: Python on the broker's loop is
    not traced, so a low share means untraced host work.
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
MARK_START = "bench.window_start"
MARK_END = "bench.window_end"
TOP = 10  # entries of each breakdown list


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(s: float, e: float, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def module_name(name: str) -> str:
    return name.split("(")[0].strip()


class Summary:
    """What the reduction found, in seconds."""

    def __init__(self):
        self.window_s = 0.0
        self.busy_s: Dict[str, float] = {}  # per device plane
        self.ops: Dict[str, float] = {}  # op name -> device seconds (all chips)
        self.modules: Dict[str, List[Tuple[float, dict]]] = {}  # name -> calls
        self.module_ops: Dict[str, str] = {}  # variant -> its ops' names
        self.gaps: List[Tuple[float, str]] = []  # (seconds, host label)

    @property
    def busy_mean_s(self) -> float:
        return sum(self.busy_s.values()) / len(self.busy_s) if self.busy_s else 0.0

    def kernel_calls(self, names) -> List[Tuple[float, dict]]:
        out: List[Tuple[float, dict]] = []
        for n in names:
            out += self.modules.get(n, [])
        return out

    def breakdown(self) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        return {
            "device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for v, k in self.gaps[:TOP]],
        }


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.duration_ns), e


def reduce(path: str) -> Summary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes = list(pd.planes)
    host = [p for p in planes if p.name == HOST_PLANE]
    marks: Dict[str, float] = {}
    host_events: List[Tuple[float, float, str]] = []
    for p in host:
        for line in p.lines:
            for name, s, d, _e in _events(line):
                if name in (MARK_START, MARK_END):
                    marks[name] = s
                elif d > 0:
                    host_events.append((s, s + d, f"{line.name}: {name}"))
    if MARK_START not in marks or MARK_END not in marks:
        raise ValueError("the trace holds no window marks")
    lo, hi = marks[MARK_START], marks[MARK_END]
    out = Summary()
    out.window_s = (hi - lo) / 1e9
    busy_all: List[Tuple[float, float]] = []
    for p in planes:
        if not p.name.startswith(DEVICE_PREFIX):
            continue
        busy: List[Tuple[float, float]] = []
        ops: List[Tuple[float, str]] = []
        firsts: Dict[str, Tuple[float, float]] = {}
        for line in p.lines:
            if line.name == OPS_LINE:
                for name, s, d, _e in _events(line):
                    c = clip(s, s + d, lo, hi)
                    if c is None:
                        continue
                    busy.append(c)
                    ops.append((s, name))
                    out.ops[name] = out.ops.get(name, 0.0) + (c[1] - c[0]) / 1e9
            elif line.name == MODULES_LINE:
                for name, s, d, e in _events(line):
                    if lo <= s < hi:
                        stats = dict(e.stats, module=name)
                        out.modules.setdefault(module_name(name), []).append(
                            (d / 1e9, stats)
                        )
                        firsts.setdefault(name, (s, s + d))
        for name, (s, e) in firsts.items():
            out.module_ops[name] = "\n".join(n for t, n in ops if s <= t < e)
        u = union(busy)
        out.busy_s[p.name] = sum(e - s for s, e in u) / 1e9
        if not busy_all:
            busy_all = u
    out.gaps = _label_gaps(busy_all, lo, hi, host_events)
    return out


def _label_gaps(busy, lo, hi, host_events) -> List[Tuple[float, str]]:
    """The longest idle stretches of the first chip, each named by the
    host event that overlaps it most (or "no host event")."""
    gaps = []
    t = lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    gaps = gaps[:TOP]
    host_events.sort()
    out = []
    for gs, ge in gaps:
        best, label = 0.0, "no host event"
        for hs, he, name in host_events:
            if hs >= ge:
                break
            ov = min(he, ge) - max(hs, gs)
            if ov > best:
                best, label = ov, name
        share = 100.0 * best / (ge - gs)
        out.append(((ge - gs) / 1e9, f"{label} ({share:.1f}% of the gap)"))
    return out


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def serialize(planes) -> bytes:
    """An XSpace holding `planes`: [(plane name, [(line name, [(event
    name, start_ns, duration_ns, {stat: value})])])]."""
    from jax.profiler import ProfileData

    out = []
    for pid, (pname, lines) in enumerate(planes, 1):
        names: Dict[str, int] = {}
        stats: Dict[str, int] = {}
        body = []
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for name, start, dur, st in events:
                mid = names.setdefault(name, len(names) + 1)
                sv = []
                for k, v in st.items():
                    sid = stats.setdefault(k, len(stats) + 1)
                    if isinstance(v, str):
                        sv.append(f"stats {{ metadata_id: {sid} str_value: {_q(v)} }}")
                    elif isinstance(v, float):
                        sv.append(f"stats {{ metadata_id: {sid} double_value: {v!r} }}")
                    else:
                        sv.append(f"stats {{ metadata_id: {sid} int64_value: {int(v)} }}")
                evs.append(
                    f"events {{ metadata_id: {mid} offset_ps: {int(start) * 1000} "
                    f"duration_ps: {int(dur) * 1000} {' '.join(sv)} }}"
                )
            body.append(
                f"lines {{ id: {lid} name: {_q(lname)} timestamp_ns: 0 {' '.join(evs)} }}"
            )
        for name, mid in names.items():
            body.append(f"event_metadata {{ key: {mid} value {{ id: {mid} name: {_q(name)} }} }}")
        for name, sid in stats.items():
            body.append(f"stat_metadata {{ key: {sid} value {{ id: {sid} name: {_q(name)} }} }}")
        out.append(f"planes {{ id: {pid} name: {_q(pname)} {' '.join(body)} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(out))


def trim(src: str, dst: str, seconds: float = 0.05) -> None:
    """Keep the first `seconds` of a window's trace (device planes and
    host thread lines; stats kept where they are scalars) with new
    window marks around it: a small recorded trace for the tests."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(src)
    lo = hi = None
    for p in pd.planes:
        if p.name == HOST_PLANE:
            for line in p.lines:
                for name, s, _d, _e in _events(line):
                    if name == MARK_START:
                        lo = s
    if lo is None:
        raise ValueError("the trace holds no window mark")
    hi = lo + seconds * 1e9
    planes = []
    for p in pd.planes:
        if not (p.name == HOST_PLANE or p.name.startswith(DEVICE_PREFIX)):
            continue
        lines = []
        for line in p.lines:
            evs = []
            for name, s, d, e in _events(line):
                if lo <= s < hi and name not in (MARK_START, MARK_END):
                    st = {
                        k: v for k, v in dict(e.stats).items()
                        if isinstance(v, (int, float, str))
                    }
                    evs.append((name, s - lo + 1000, d, st))
            if evs:
                lines.append((line.name, evs))
        if p.name == HOST_PLANE:
            lines.append(("bench", [
                (MARK_START, 1000, 0, {}), (MARK_END, hi - lo + 1000, 0, {}),
            ]))
        planes.append((p.name, lines))
    with open(dst, "wb") as f:
        f.write(serialize(planes))


def dump(path: str, per_line: int = 3) -> None:
    """Print a trace's planes, lines, event counts, the busiest event
    names and a few events with their stats: for reading a trace by hand
    before writing a reduction against it."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for p in pd.planes:
        lines = list(p.lines)
        print(f"plane {p.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            tot: Dict[str, float] = {}
            for e in evs:
                tot[e.name] = tot.get(e.name, 0.0) + e.duration_ns
            top = sorted(tot.items(), key=lambda kv: -kv[1])[:8]
            print(f"  line {line.name!r}: {len(evs)} events; busiest "
                  + "; ".join(f"{k} {v / 1e6:.3f}ms" for k, v in top))
            for e in evs[:per_line]:
                print(f"    {e.name!r} start {e.start_ns} dur {e.duration_ns} "
                      f"stats {dict(e.stats)}")


if __name__ == "__main__":
    import sys

    dump(find_xplane(sys.argv[1]) if os.path.isdir(sys.argv[1]) else sys.argv[1])
