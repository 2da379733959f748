#!/usr/bin/env python3
"""The benchmark's command: one run of one cell of BENCHMARK.json.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs the cell on the chips of this machine (it exits 1 and prints no
result when JAX finds no TPU, or fewer than the cell asks for), then
prints one JSON line last on stdout: `correct`, `attempted`, `failed`,
`metrics` (the cell's end-to-end metrics, or with `--trace 1` its
per-layer ones), `device`, with `--trace 1` a `breakdown`, and last
`checks`, the numbers compared with their limits. The same checks are
the last lines on stderr. `--control 1` also judges the control (the
match at fingerprint precision) in the program's place on the same
publishes, and prints its checks as a `control` line before the result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness as H  # noqa: E402
import reference as ref  # noqa: E402
import spec as specs  # noqa: E402

CONTROL_BITS = 10  # the control's per-level fingerprint width


class Ctx:
    """What a metric reader sees of one window (benchmark/metrics/*.py)."""

    def __init__(self, run: "H.Run", w: "H.Window", summary, t_start: float):
        self.run = run
        self.window = w
        self.seconds = run.seconds
        self.trace = summary
        self.t_start = t_start
        d = w.delta
        self.counters = d["counters"]
        self.publishes = d["publishes"]
        self.batches = d["batches"]
        self.queue_wait = d["queue_wait"]
        self.legs = d["legs"]
        self.loop_stall_s = w.loop_stall_s
        self.device_kind = run.device.get("kind")
        self._lat = None

    def latencies_ns(self) -> np.ndarray:
        if self._lat is None:
            self._lat = self.run.latencies_ns(self.window)
        return self._lat

    def deliveries_in_window(self) -> int:
        return self.run.delivered_in(self.window)

    def late_ns(self) -> np.ndarray:
        p = self.window.pubs
        return p["sent"] - p["due"]

    def setup_s(self) -> float:
        return self.window.t_ns / 1e9 - self.t_start

    def loop(self) -> str:
        return self.run.traffic["loop"]

    def kernel(self, name: str):
        return specs.kernel(name)

    def peaks(self) -> dict:
        return specs.peaks(self.device_kind)


def read_metrics(entries, ctx: Ctx) -> dict:
    out = {}
    for m in entries:
        v = specs.metric_reader(m["name"]).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def print_checks(checks: dict, what: str = "check") -> None:
    for k, c in checks.items():
        H.log(f"{what} {k}: {c['value']} (limit {c['limit']})")


async def run_cell(cell, seed: int, seconds: float, trace: bool, *,
                   platform="tpu", control: bool = False,
                   t_start: float = T_START, native: bool = True) -> dict:
    run = H.Run(cell, seed, seconds, t_start=t_start, platform=platform,
                trace=trace, native=native)
    try:
        await run.setup()
        w = await run.window()
        peak = run.memory_peak() if platform is not None else 0
    finally:
        await run.teardown()
    summary = None
    device = {k: run.device[k] for k in ("platform", "kind", "count")}
    device["memory_peak_bytes"] = peak
    if trace:
        from devtrace import find_xplane, reduce

        summary = reduce(find_xplane(w.trace))
        device["busy_s"] = summary.busy_mean_s
        device["window_s"] = summary.window_s
    ctx = Ctx(run, w, summary, t_start)
    metrics = read_metrics(cell.metrics(trace), ctx)
    t0 = time.monotonic()
    reference = ref.Reference(run.subs())
    verdict = run.judge(w, reference.receivers)
    H.log(f"reference: every publish judged in {time.monotonic() - t0:.3f}s")
    for line in verdict.examples:
        H.log(f"  {line}")
    lat = ctx.latencies_ns()
    if len(lat):
        H.log("info: deliver p50/p90/p99/p99.9 ms " + " ".join(
            f"{v / 1e6:.3f}" for v in np.percentile(lat, [50, 90, 99, 99.9])
        ))
    counters = {
        k: w.delta["counters"].get(k, 0)
        for k in ("dispatch_batches_total", "host_fallback_total",
                  "ambiguous_batches_total")
    }
    H.log("window counters: " + json.dumps(counters, sort_keys=True))
    result = {
        "correct": verdict.correct,
        "attempted": verdict.attempted,
        "failed": verdict.failed,
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        result["breakdown"] = summary.breakdown()
    if control:
        result["control"] = judge_control(run, w, reference)
    result["checks"] = verdict.checks()
    return result


def judge_control(run, w, reference) -> dict:
    """The control in the program's place: for each publish of the
    window, the clients the fingerprint-precision match picks."""
    ctrl = ref.Control(run.subs(), CONTROL_BITS)
    topic = run.table.topic
    delivered = {
        int(m): list(ctrl.receivers(topic(int(dev))))
        for m, dev in zip(w.pubs["msg"], w.pubs["device"])
    }
    code = np.where(w.pubs["qos"] > 0, 0, -1)
    pubs = dict(w.pubs, code=code, ack=np.zeros_like(code))
    v = ref.compare(pubs, topic, reference.receivers, delivered, {}, 0)
    return v.checks()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = specs.find_cell(args.workload)
        result = asyncio.run(run_cell(
            cell, args.seed, args.seconds, bool(args.trace),
            control=bool(args.control),
        ))
    except Exception as e:
        H.log(f"FAILED: {type(e).__name__}: {e}")
        return 1
    if "control" in result:
        print_checks(result["control"], "control")
        print(json.dumps({"control": result.pop("control")}), flush=True)
    print_checks(result["checks"])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
