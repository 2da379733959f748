"""The readers of the program's stage spans (benchmark/hostspans.py and
the ingress, launch and delivery metrics), on small written traces."""

from __future__ import annotations

import os
import sys
from types import SimpleNamespace

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import hostspans  # noqa: E402
import spec as specs  # noqa: E402

US = 1000  # ns


def _trace(tmp_path, loop_events, name="a"):
    """A trace dir as jax.profiler.stop_trace leaves it, whose host plane
    holds the window marks (10 us .. 1010 us) and `loop_events`."""
    d = tmp_path / name
    run = d / "plugins" / "profile" / "2026_01_01_00_00_00"
    run.mkdir(parents=True)
    planes = [
        ("/device:TPU:0", [("XLA Ops", [("op", 20 * US, 5 * US, {})])]),
        ("/host:CPU", [
            ("bench", [
                (devtrace.MARK_START, 10 * US, 0, {}),
                (devtrace.MARK_END, 1010 * US, 0, {}),
            ]),
            ("python3", loop_events),
        ]),
    ]
    (run / "host.xplane.pb").write_bytes(devtrace.serialize(planes))
    return str(d)


def _ctx(trace_dir, publishes=10, batches=4):
    return SimpleNamespace(
        window=SimpleNamespace(trace=trace_dir),
        publishes=publishes,
        counters={"dispatch_batches_total": batches},
    )


def _span(stage, start_us, dur_us, **stats):
    return ("emqx." + stage, start_us * US, dur_us * US, stats)


LOOP = [
    _span("decode", 0, 20),  # 10 us of it inside the window
    _span("channel", 20, 30, packets=3),
    _span("coalesce", 50, 5),
    _span("launch", 55, 40),
    _span("ticket_start", 95, 8),
    _span("match_fetch", 103, 10),
    _span("dispatch_loop", 113, 25),
    _span("session_write", 138, 15),
    _span("dispatch_loop", 153, 5),
    _span("plan_resolve", 158, 7),
    _span("ack_sweep", 165, 3),
    _span("ack_write", 168, 12),
    _span("decode", 1000, 40),  # 10 us of it inside the window
    _span("channel", 1200, 50),  # after the window
    ("PjitFunction(match_ids_hash)", 60 * US, 30 * US, {}),
]


def _read(metric, ctx):
    return specs.metric_reader(metric).read(ctx)


def test_stage_seconds_are_clipped_to_the_window(tmp_path):
    path = devtrace.find_xplane(_trace(tmp_path, LOOP))
    got = hostspans.stage_seconds(path)
    assert got == {
        "decode": pytest.approx(20e-6), "channel": pytest.approx(30e-6),
        "coalesce": pytest.approx(5e-6), "launch": pytest.approx(40e-6),
        "ticket_start": pytest.approx(8e-6),
        "match_fetch": pytest.approx(10e-6),
        "dispatch_loop": pytest.approx(30e-6),
        "session_write": pytest.approx(15e-6),
        "plan_resolve": pytest.approx(7e-6), "ack_sweep": pytest.approx(3e-6),
        "ack_write": pytest.approx(12e-6),
    }
    assert hostspans.stage_seconds(path) is got  # parsed once


@pytest.mark.parametrize("cell", ["open", "closed"])
def test_the_three_readers(tmp_path, cell):
    ctx = _ctx(_trace(tmp_path, LOOP), publishes=10, batches=4)
    # (decode 20 + channel 30 + ack_write 12) us over 10 publishes
    assert _read(f"ingress_host_us.{cell}", ctx) == pytest.approx(6.2)
    # (launch 40 + ticket_start 8) us over 4 batches
    assert _read(f"launch_host_us.{cell}", ctx) == pytest.approx(12.0)
    # (plan_resolve 7 + dispatch_loop 30 + session_write 15 + ack_sweep 3)
    assert _read(f"delivery_host_us.{cell}", ctx) == pytest.approx(5.5)


def test_gap_stages_sum_each_gap_by_stage(tmp_path):
    # the chip runs 20..25 us of the 10..1010 us window: two idle gaps
    path = devtrace.find_xplane(_trace(tmp_path, LOOP))
    long_gap, short_gap = hostspans.gap_stages(path)
    assert long_gap["gap_s"] == pytest.approx(985e-6)
    # every stage segment after 25 us, the Pjit host event left out
    assert long_gap["covered_pct"] == pytest.approx(100 * 165 / 985)
    assert long_gap["longest_pct"] == pytest.approx(100 * 40 / 985)
    assert list(long_gap["stages_pct"])[:2] == ["launch", "dispatch_loop"]
    assert long_gap["stages_pct"]["dispatch_loop"] == pytest.approx(100 * 30 / 985)
    assert long_gap["stages_pct"]["channel"] == pytest.approx(100 * 25 / 985)
    assert short_gap == {
        "gap_s": pytest.approx(10e-6), "covered_pct": pytest.approx(100.0),
        "longest_pct": pytest.approx(100.0),
        "stages_pct": {"decode": pytest.approx(100.0)},
    }
    assert hostspans.gap_stages(path, top=1) == [long_gap]


@pytest.mark.parametrize(
    "metric", ["ingress_host_us.open", "launch_host_us.closed",
               "delivery_host_us.open"],
)
def test_no_spans_no_trace_no_units_read_none(tmp_path, metric):
    bare = _trace(tmp_path, [("PjitFunction(match_ids_hash)", 60 * US, 30 * US, {})])
    assert _read(metric, _ctx(bare)) is None  # a program without stage spans
    assert _read(metric, _ctx(None)) is None  # an untraced run
    empty = tmp_path / "empty"
    empty.mkdir()
    assert _read(metric, _ctx(str(empty))) is None  # no trace file
    spans = _trace(tmp_path, LOOP, name="b")
    assert _read(metric, _ctx(spans, publishes=0, batches=0)) is None
