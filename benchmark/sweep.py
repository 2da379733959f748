#!/usr/bin/env python3
"""The knee of an open-loop cell: windows at rising offered rates.

    python3 benchmark/sweep.py --workload plus1m.fleet_open --seed 5 \
        --seconds 10 --rates 1000,2000,3000,4000

One set-up, then one window per rate (each after `warm_settle_s` of
traffic at that rate). For each rate it prints a JSON line: the rate
offered and achieved, deliver p50/p90/p99, publishes refused or unanswered,
the engine's queue at the window's end, and the backlog's growth: the
p90 of the window's last quarter of publishes minus its first quarter's.
The knee is the highest rate whose windows' median p90 stays within
`--slo-ms` (the broker's own `tpu_slo_publish_p99_ms` default, 50 ms),
printed also with p99 in p90's place,
with no backlog growth and nothing refused; offer each rate several
times, after a first window the knee leaves out as a warm-up. Run it
once, on the chip, and write 0.8 x the knee into the mix's file as its
`rate`.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402
import reference as ref  # noqa: E402
import spec as specs  # noqa: E402


def window_summary(run, w, reference) -> dict:
    lat = run.latencies_ns(w) / 1e6
    client, msg, at = run.deliveries()
    mine = (msg >> H.WINDOW_BITS) == w.number
    idx = msg[mine] & ((1 << H.WINDOW_BITS) - 1)
    n = len(w.pubs["due"])
    q = n // 4
    first = lat[idx < q]
    last = lat[idx >= n - q]
    verdict = run.judge(w, reference.receivers)
    sent = w.pubs["sent"]
    span = (sent.max() - sent.min()) / 1e9 if n > 1 else 0.0
    return {
        "rate": w.rate,
        "achieved_per_s": n / span if span else 0.0,
        "deliveries": int(len(lat)),
        "deliver_p50_ms": float(np.percentile(lat, 50)) if len(lat) else None,
        "deliver_p90_ms": float(np.percentile(lat, 90)) if len(lat) else None,
        "deliver_p99_ms": float(np.percentile(lat, 99)) if len(lat) else None,
        "growth_p90_ms": (
            float(np.percentile(last, 90) - np.percentile(first, 90))
            if len(first) and len(last) else None
        ),
        "late_p99_ms": float(np.percentile(sent - w.pubs["due"], 99)) / 1e6,
        "queue_at_end": w.outstanding_end,
        "attempted": verdict.attempted,
        "failed": verdict.failed,
        "correct": verdict.correct,
        "publishes_per_batch": (
            w.delta["publishes"] / w.delta["batches"] if w.delta["batches"] else None
        ),
        "loop_stall_max_ms": w.loop_stall_s * 1e3,
    }


async def sweep(cell, seed: int, seconds: float, rates, platform="tpu") -> list:
    run = H.Run(cell, seed, seconds, t_start=time.monotonic(), platform=platform)
    windows = []
    try:
        await run.setup()
        for r in rates:
            w = await run.window(r)
            windows.append(w)
            H.log(f"rate {r}: window done")
    finally:
        await run.teardown()
    reference = ref.Reference(run.subs())
    return [window_summary(run, w, reference) for w in windows]


def knee(rows: list, slo_ms: float, tail: str = "deliver_p90_ms") -> float:
    """The highest rate whose windows, taken together, keep the median of
    their `tail` latencies within the SLO, grow no backlog beyond half of
    it, refuse nothing and pass the comparison (a rate may be offered
    several times; the first window after set-up is left out as a
    warm-up)."""
    by_rate: dict = {}
    for r in rows[1:] if len(rows) > 1 else rows:
        by_rate.setdefault(r["rate"], []).append(r)
    best = 0.0
    for rate, rs in by_rate.items():
        lat = sorted(r[tail] or float("inf") for r in rs)
        growth = sorted(r["growth_p90_ms"] or 0.0 for r in rs)
        ok = (
            lat[len(lat) // 2] <= slo_ms
            and growth[len(growth) // 2] <= slo_ms / 2
            and all(r["failed"] == 0 and r["correct"] for r in rs)
        )
        if ok:
            best = max(best, rate)
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True, help="comma-separated publishes/s")
    ap.add_argument("--slo-ms", type=float, default=50.0)
    args = ap.parse_args(argv)
    rates = [float(x) for x in args.rates.split(",")]
    try:
        cell = specs.find_cell(args.workload)
        rows = asyncio.run(sweep(cell, args.seed, args.seconds, rates))
    except Exception as e:
        H.log(f"FAILED: {type(e).__name__}: {e}")
        return 1
    for r in rows:
        print(json.dumps(r), flush=True)
    for tail in ("deliver_p90_ms", "deliver_p99_ms"):
        k = knee(rows, args.slo_ms, tail)
        print(json.dumps({"tail": tail, "knee_per_s": k,
                          "rate_at_0.8_knee": round(0.8 * k)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
