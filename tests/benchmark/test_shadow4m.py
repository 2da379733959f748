"""The shadow4m configuration (BASELINE #3's mixed '+'/'#' table in the
AWS IoT device-shadow shape) and the reader of `match_pairs_per_topic`,
on the CPU."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import reference as ref  # noqa: E402
import spec as specs  # noqa: E402
import table as tbl  # noqa: E402

SKELETONS = ["L/L/L/L/L/L", "L/L/L/L/L/+", "L/L/L/L/+/L", "L/L/L/L/#", "L/L/L/#"]


def _shape(f):
    return "/".join(w if w in ("+", "#") else "L" for w in f.split("/"))


def test_shadow4m_rows_are_the_aws_shadow_shape():
    """Row i is thing i div 5's response topic; its filter takes skeleton
    i mod 5, so each thing holds one filter of each of the five classes,
    each held by another session."""
    conf = specs.load_config("shadow4m")
    t = tbl.Table(conf, seed=3)
    assert t.n == conf["things"] * 5 == 4_000_000
    assert t.topic(7) == "$aws/things/thing1/shadow/o1/r3"
    assert t.filter(7) == "$aws/things/thing1/shadow/+/r3"
    assert [_shape(t.filter(i)) for i in range(5)] == SKELETONS
    assert {t.holder(i) for i in range(5, 10)} == {5, 6, 7, 8, 9}
    assert [t.filter(i) for i in range(5, 10)] == [
        "$aws/things/thing1/shadow/o2/r1", "$aws/things/thing1/shadow/o0/+",
        "$aws/things/thing1/shadow/+/r3", "$aws/things/thing1/shadow/#",
        "$aws/things/thing1/#",
    ]


def test_shadow4m_publishes_match_two_or_three_table_filters():
    """The reference over a 200,000-row prefix of the table (the rows
    repeat with period 60) and the cell's socket filters: every sampled
    publish matches 2 or 3 table filters, 2.80 on average, and the
    root-wild socket filters receive nothing, by MQTT's '$' rule."""
    conf = dict(specs.load_config("shadow4m"), filters=200_000)
    t = tbl.Table(conf, seed=2 ** 31 + 5)
    fs = t.filters()
    sock = t.socket_filters()
    assert sock[:3] == ["#", "+/things/#", "$aws/things/+/shadow/o0/r2"]
    r = ref.Reference(
        [(t.holder(i), f) for i, f in enumerate(fs)]
        + [(conf["sessions"] + j, f) for j, f in enumerate(sock)]
    )
    table = set(fs)
    n_table = []
    for dev in tbl.device_draw(5000, t.n, 2 ** 31 + 5, 1):
        got = r.filters(t.topic(int(dev)))
        assert not {"#", "+/things/#"} & set(got)
        n_table.append(sum(f in table for f in got))
    assert set(n_table) == {2, 3}
    assert np.mean(n_table) == pytest.approx(2.80, abs=0.02)


def test_shadow4m_cell_reads_what_plus1m_closed_reads():
    """The new cell reports the closed cells' end-to-end and per-layer
    metrics, the new `match_pairs_per_topic.closed` among them."""
    bench = specs.load_benchmark()
    new = specs.Cell(bench, "shadow4m.fleet_closed")
    old = specs.Cell(bench, "plus1m.fleet_closed")
    assert new.chips == 1 and new.traffic == old.traffic
    assert [m["name"] for m in new.end_to_end] == [m["name"] for m in old.end_to_end]
    names = [m["name"] for m in new.per_layer]
    assert names == [m["name"] for m in old.per_layer]
    assert "match_pairs_per_topic.closed" in names


class _Ctx:
    def __init__(self, counters):
        self.counters = counters


@pytest.mark.parametrize("counters, want", [
    ({}, None),  # a program without the counters
    ({"match_device_topics_total": 0, "match_device_pairs_total": 0}, None),
    ({"match_device_topics_total": 64, "match_device_pairs_total": 184}, 2.875),
])
def test_match_pairs_per_topic_reader(counters, want):
    reader = specs.metric_reader("match_pairs_per_topic.closed")
    assert reader.read(_Ctx(counters)) == want
