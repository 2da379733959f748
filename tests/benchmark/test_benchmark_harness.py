"""The benchmark's harness on the CPU: no chip, small sizes.

Covers loading the cells' pieces by name, the reference and its control,
the kernel reckoning, the trace reduction on a small recorded trace, a
tiny rehearsal of each cell through the real broker and load generator
(with the comparison shown to fail on a planted wrong delivery and on
faults planted in the served path), and the command refusing to run
without a TPU.
"""

from __future__ import annotations

import asyncio
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, BENCH)

import devtrace  # noqa: E402
import harness as H  # noqa: E402
import mqtt  # noqa: E402
import reference as ref  # noqa: E402
import run as R  # noqa: E402
import spec as specs  # noqa: E402
import table as tbl  # noqa: E402

CELLS = [w["name"] for w in specs.load_benchmark()["workloads"]]
TESTDATA = os.path.join(BENCH, "testdata")


# --- pieces found by name ------------------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_every_piece_of_a_cell_is_found_by_name(name):
    cell = specs.find_cell(name)
    assert cell.conf["filters"] > 0 and cell.traffic["loop"] in ("open", "closed")
    assert any(m["name"] == "setup_s" for m in cell.end_to_end)
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.end_to_end + cell.per_layer:
        assert callable(specs.metric_reader(m["name"]).read)
    k = specs.kernel("match_ids_hash")
    assert k.TRACE_NAMES and callable(k.shape_of)
    assert specs.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_benchmark_json_keeps_the_contract_shape():
    bench = specs.load_benchmark()
    assert bench["command"] == ["python3", "benchmark/run.py"]
    names = [c["name"] for c in bench["configs"]]
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
        assert specs.load_config(c["name"])["name"] == c["name"]
    for w in bench["workloads"]:
        assert w["config"] in names and w["chips"] == 1 and len(w["why"]) <= 200
    for m in bench["per_layer"]:
        assert m["moves"] in {e["name"] for e in bench["end_to_end"]}
        assert set(m["workloads"]) <= set(CELLS)
    for e in bench["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25


@pytest.mark.parametrize("bad", ["has space", "a/b", "a,b", ".lead", "x" * 65, "μs", ""])
def test_bad_names_are_refused(bad):
    with pytest.raises(specs.SpecError):
        specs.check_name(bad)


@pytest.mark.parametrize("bad", ["tokens per second", "", "x" * 17, "µs"])
def test_bad_units_are_refused(bad):
    with pytest.raises(specs.SpecError):
        specs.check_unit(bad)


def test_missing_pieces_are_refused():
    with pytest.raises(specs.SpecError):
        specs.metric_reader("no_such_metric.open")
    with pytest.raises(specs.SpecError):
        specs.kernel("no_such_kernel")
    with pytest.raises(specs.SpecError):
        specs.peaks("TPU v99")
    with pytest.raises(specs.SpecError):
        specs.find_cell("no_such.cell")


def test_a_new_cell_is_new_files_and_entries_only(tmp_path):
    """A configuration, a mix and a metric added as files, with entries
    in BENCHMARK.json, load without touching any existing file."""
    shutil.copytree(BENCH, tmp_path / "benchmark")
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    conf = json.loads(open(os.path.join(BENCH, "configs", "plus1m.json")).read())
    conf.update(name="tiny", filters=64, sessions=4)
    (tmp_path / "benchmark" / "configs" / "tiny.json").write_text(json.dumps(conf))
    mix = {"loop": "closed", "connections": 2, "inflight": 1, "topic_draw": {"zipf": 1.1},
           "qos1_share": 1.0, "payload_bytes": 64, "warm_settle_s": 0.1}
    (tmp_path / "benchmark" / "traffic" / "trickle.json").write_text(json.dumps(mix))
    (tmp_path / "benchmark" / "metrics" / "batches_seen.py").write_text(
        "def read(ctx):\n    return ctx.batches or None\n"
    )
    bench["configs"].append({"name": "tiny", "source": "https://example.org/tiny",
                             "file": "benchmark/configs/tiny.json", "reduced": [],
                             "why": "a test"})
    bench["workloads"].append({"name": "tiny.trickle", "config": "tiny",
                               "traffic": "trickle", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "batches_seen.trickle", "unit": "batch",
                               "better": "higher", "source": "program_counter",
                               "layer": "dispatch engine", "moves": "setup_s",
                               "workloads": ["tiny.trickle"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = specs.find_cell("tiny.trickle", root=str(tmp_path))
    assert cell.conf["filters"] == 64 and cell.traffic["inflight"] == 1
    names = [m["name"] for m in cell.per_layer]
    assert names == ["batches_seen.trickle"]
    reader = specs.metric_reader(names[0], str(tmp_path / "benchmark"))

    class Ctx:
        batches = 7

    assert reader.read(Ctx()) == 7


# --- tables and traffic --------------------------------------------------------


def test_plus1m_rows_are_the_config2_shape():
    """emqx_broker_bench's device/{id}/+/{num}/#: subscriber id holds one
    filter per num, and is the in-process session that holds it."""
    t = tbl.Table(specs.load_config("plus1m"), seed=3)
    assert t.n == 1000 * 1000
    assert t.filter(12345) == "device/345/+/12/#"
    assert t.topic(12345) == "device/345/foo/12/bar"
    assert t.filters()[:2] == ["device/0/+/0/#", "device/1/+/0/#"]
    assert t.holder(12345) == 345
    assert len(set(t.filters()[:5000])) == 5000


MIXED = {  # a table of several skeletons over hashed levels
    "filters": 4096, "sessions": 64, "levels": [
        {"prefix": "s", "of": "hash", "card": 100}, {"prefix": "f", "of": "hash", "card": 100},
        {"prefix": "l", "of": "hash", "card": 1000}, {"prefix": "d", "of": "row"},
        {"prefix": "c", "of": "hash", "card": 50}, {"prefix": "m", "of": "hash", "card": 10}],
    "skeletons": ["L/L/L/+/L/L", "L/L/+/L/L/L", "L/L/L/+/L/L/#", "L/L/L/L/+/L/#",
                  "L/+/L/L/L/#", "L/L/L/L/L/+", "L/L/L/L/#", "L/L/+/L/L/L/#"],
}


def test_mixed1m_rows_take_the_eight_skeletons():
    t = tbl.Table(MIXED, seed=9)
    fs = t.filters()
    assert fs == [t.filter(i) for i in range(len(fs))]
    shapes = {tuple(w if w in "+#" else "L" for w in f.split("/")) for f in fs}
    assert len(shapes) == 8
    r = ref.Reference((t.holder(i), f) for i, f in enumerate(fs))
    for i in range(0, 4096, 97):
        assert fs[i] in r.filters(t.topic(i))


def test_socket_filters_name_hot_rows():
    t = tbl.Table(specs.load_config("plus1m"), seed=3)
    fs = t.socket_filters()
    hot = t.hot_rows()
    assert fs[0] == "#" and fs[3] == t.filter(int(hot[2]))
    assert fs[1] == f"device/{int(hot[0]) % 1000}/#"
    r = ref.Reference([(0, f) for f in fs])
    assert r.filters(t.topic(int(hot[3]))) == ["#", fs[4]]


def test_topic_draw_is_data():
    u = tbl.device_draw(20000, 1000, 2 ** 31 + 9, 4, "uniform")
    z = tbl.device_draw(20000, 1000, 2 ** 31 + 9, 4, {"zipf": 1.1})
    assert np.array_equal(z, tbl.device_draw(20000, 1000, 2 ** 31 + 9, 4, {"zipf": 1.1}))
    assert u.min() >= 0 and u.max() < 1000 and z.min() >= 0 and z.max() < 1000
    top = np.bincount(z, minlength=1000).max()
    assert top > 20 * np.bincount(u, minlength=1000).max()
    # the hottest row is the same in every stream of a seed
    z2 = tbl.device_draw(20000, 1000, 2 ** 31 + 9, 5, {"zipf": 1.1})
    assert np.bincount(z2).argmax() == np.bincount(z).argmax()


@pytest.mark.parametrize("bad", ["zipf", {"zipf": 0}, {"zipf": 1.1, "x": 1}, None])
def test_bad_topic_draws_are_refused(bad, tmp_path):
    os.makedirs(tmp_path / "traffic")
    mix = {"loop": "open", "topic_draw": bad}
    (tmp_path / "traffic" / "m.json").write_text(json.dumps(mix))
    with pytest.raises(specs.SpecError):
        specs.load_traffic("m", str(tmp_path))


def test_seeds_offer_the_same_load_in_another_order():
    a = tbl.poisson_offsets(1000, 10.0, 1, 5)
    b = tbl.poisson_offsets(1000, 10.0, 2 ** 31 + 7, 5)
    assert not np.allclose(a, b)
    assert a[0] == 0 and a[-1] < 10.0
    gaps = [np.sort(np.diff(np.append(x, 10.0))) for x in (a, b)]
    assert np.allclose(gaps[0], gaps[1])
    q = tbl.qos_draw(1001, 0.5, 2 ** 31 + 7, 1)
    assert q.sum() == 500


# --- the MQTT codec of the load generator ---------------------------------------


def test_codec_frames_split_across_reads():
    pkt = mqtt.publish(mqtt.topic_field("a/b"), b"x" * 300, 1, 7)
    ack = b"\x40\x03\x00\x07\x97"
    r = mqtt.Reader()
    data = pkt + ack
    got = []
    for i in range(0, len(data), 5):
        got += [(t, f, bytes(b)) for t, f, b in r.feed(data[i:i + 5])]
    assert [g[0] for g in got] == [mqtt.PUBLISH, mqtt.PUBACK]
    assert bytes(mqtt.publish_payload(got[0][1], memoryview(got[0][2]))) == b"x" * 300
    assert mqtt.puback(memoryview(got[1][2])) == (7, 0x97)


# --- the reference and its control ---------------------------------------------


def test_reference_follows_mqtt5_topic_filter_rules():
    r = ref.Reference([
        (1, "sport/#"), (2, "sport/+/player1"), (3, "+/+"), (4, "#"),
        (5, "+/tennis/#"), (1, "sport/tennis/player1"), (6, "$SYS/#"),
    ])
    assert r.receivers("sport") == {1, 4}
    assert r.receivers("sport/tennis/player1") == {1, 2, 4, 5}
    assert r.receivers("sport/tennis") == {1, 3, 4, 5}
    assert r.receivers("$SYS/x") == {6}
    assert r.receivers("a//b") == {4}


def test_control_fails_the_comparison_at_the_cells_table_size():
    """The control (match at fingerprint precision) in the program's
    place, on publishes of the plus1m cell's own 2^20-filter table: the
    comparison must find its answers wrong, where the reference put in
    the same place passes."""
    t = tbl.Table(specs.load_config("plus1m"), seed=21)
    n_sess = t.conf["sessions"]
    subs = [(t.holder(i), f) for i, f in enumerate(t.filters())]
    subs += [(n_sess + j, f) for j, f in enumerate(t.socket_filters())]
    reference = ref.Reference(subs)
    control = ref.Control(subs, R.CONTROL_BITS)
    n = 3000
    devs = tbl.device_draw(n, t.n, 21, 1)
    pubs = {"msg": np.arange(n), "device": devs, "qos": np.zeros(n, np.int64),
            "ack": np.zeros(n, np.int64), "code": np.full(n, -1)}
    for judged, bad in ((reference, False), (control, True)):
        delivered = {m: list(judged.receivers(t.topic(int(d))))
                     for m, d in zip(range(n), devs)}
        v = ref.compare(pubs, t.topic, reference.receivers, delivered, {}, 0)
        assert v.correct is not bad
        if bad:
            assert v.values["mismatched_publishes"] >= 10


# --- kernel reckoning ------------------------------------------------------------


def test_match_ids_hash_cost_from_shapes():
    k = specs.kernel("match_ids_hash")
    ops, nbytes = k.cost({"B": 8, "C": 8, "L": 16, "H": 1024})
    assert ops == 7 * 8 * 8 * 16 + 20 * 64 + 100 * 1024
    assert nbytes == 4 * 8 * 16 + 40 + 88 + 8 * 64 + 20 * 1024 + 8
    ops2, nbytes2 = k.cost({"B": 64, "C": 8, "L": 16, "H": 1024})
    assert ops2 > ops and nbytes2 > nbytes


# --- the trace reduction -----------------------------------------------------------


def test_trace_reduction_on_a_small_recorded_trace():
    path = os.path.join(TESTDATA, "window.xplane.pb")
    s = devtrace.reduce(path)
    assert 0 < s.window_s < 1
    assert s.busy_s and 0 < s.busy_mean_s < s.window_s
    k = specs.kernel("match_ids_hash")
    calls = s.kernel_calls(k.TRACE_NAMES)
    assert calls
    for _seconds, stats in calls:
        assert k.shape_of(s.module_ops[stats["module"]]) == {
            "B": 64, "C": 8, "L": 16, "H": 1024}
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    busy = sum(v for _k, v in b["device_ops"])
    assert busy <= s.window_s * len(s.busy_s) + 1e-9


def test_trace_union_and_gap_labels(tmp_path):
    planes = [
        ("/device:TPU:0", [
            ("XLA Ops", [("a", 2000, 1000, {}), ("b", 2500, 1000, {}), ("a", 8000, 500, {})]),
            ("XLA Modules", [("jit_match_ids_hash(7)", 2000, 1500, {})]),
        ]),
        ("/host:CPU", [("main", [
            ("bench.window_start", 1000, 0, {}), ("bench.window_end", 10000, 0, {}),
            ("wait", 3500, 4000, {}),
        ])]),
    ]
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(devtrace.serialize(planes))
    s = devtrace.reduce(str(path))
    assert s.window_s == pytest.approx(9e-6)
    assert s.busy_mean_s == pytest.approx(2e-6)
    assert s.ops == {"a": pytest.approx(1.5e-6), "b": pytest.approx(1e-6)}
    assert [d for d, _ in s.kernel_calls(("jit_match_ids_hash",))] == [pytest.approx(1.5e-6)]
    assert s.gaps[0] == (pytest.approx(4.5e-6), "main: wait (88.9% of the gap)")


# --- rehearsals through the broker ----------------------------------------------------


def _tiny(name: str):
    cell = specs.find_cell(name)
    cell.conf.update(filters=16384, sessions=8)  # more rows than the match cache holds
    cell.traffic.update(connections=8, warm_settle_s=0.3)
    if cell.traffic["loop"] == "open":
        cell.traffic["rate"] = 700
    return cell


def _rehearse(cell, tmp_path, monkeypatch, seed=2 ** 31 + 11):
    monkeypatch.setattr(H, "RUN_DIR", str(tmp_path))
    run = H.Run(cell, seed, 1.0, t_start=time.monotonic(), platform=None,
                native=False)

    async def go():
        try:
            await run.setup()
            return await run.window()
        finally:
            await run.teardown()

    w = asyncio.run(go())
    return run, w, ref.Reference(run.subs())


@pytest.fixture(scope="module")
def rehearsals(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    try:
        return {
            name: _rehearse(_tiny(name), tmp_path_factory.mktemp(name), mp)
            for name in CELLS
        }
    finally:
        mp.undo()


@pytest.mark.parametrize("name", CELLS)
def test_tiny_rehearsal_passes_the_comparison(rehearsals, name):
    run, w, reference = rehearsals[name]
    v = run.judge(w, reference.receivers)
    assert v.correct, v.examples
    assert v.attempted > 50 and v.failed == 0
    assert v.values["host_fallback_pct"] <= v.LIMITS["host_fallback_pct"]
    assert w.delta["counters"].get("dispatch_batches_total", 0) > 0
    ctx = R.Ctx(run, w, None, run.t_start)
    cell = run.cell
    got = R.read_metrics(cell.end_to_end, ctx)
    assert "setup_s" in got and len(got) == len(cell.end_to_end)
    layer = R.read_metrics(cell.per_layer, ctx)
    assert layer[f"publishes_per_batch.{name.split('_')[-1]}"]["value"] >= 1
    assert not any(k.startswith("device_idle_share") for k in layer)


@pytest.mark.parametrize("name", CELLS)
def test_a_planted_wrong_delivery_fails_the_comparison(rehearsals, name):
    run, w, reference = rehearsals[name]
    sink = run.sink
    saved = sink.client[:]
    try:
        mine = [i for i, m in enumerate(sink.msg) if m >> H.WINDOW_BITS == w.number]
        i = mine[len(mine) // 2]
        sink.client[i] = (sink.client[i] + 1) % run.conf["sessions"]
        v = run.judge(w, reference.receivers)
        assert not v.correct and v.values["mismatched_publishes"] >= 1
    finally:
        sink.client[:] = saved


def _planted(monkeypatch, fault):
    from emqx_tpu.broker.pubsub import Broker
    from emqx_tpu.models.router import Router

    if fault == "device_path_off":
        orig = Router.match_filters_begin

        def begin(self, topics, **kw):
            self.device_suspended = True  # the breaker open: host answers
            return orig(self, topics, **kw)

        monkeypatch.setattr(Router, "match_filters_begin", begin)
    elif fault == "host_answers":
        orig = Router.match_filters_finish

        def finish(self, p):
            if p.mode == "hash":  # right answers, but from the host trie
                return self.match_filters_host(p)
            return orig(self, p)

        monkeypatch.setattr(Router, "match_filters_finish", finish)
    elif fault == "answer_altered":
        orig = Router.match_filters_finish

        def finish(self, p):
            out = orig(self, p)
            if out and out[0]:
                out[0] = out[0][:-1]  # the first topic loses a matched filter
            return out

        monkeypatch.setattr(Router, "match_filters_finish", finish)
    else:
        orig = Broker.dispatch_window

        def window(self, lives, filter_lists, spans=None, capture_errors=False):
            h = len(lives) // 2  # half the batch (all of a batch of one) dropped
            res, meta = orig(self, lives[h:], filter_lists[h:],
                             None if spans is None else spans[h:], capture_errors)
            return [0] * h + list(res), [((), [])] * h + list(meta)

        monkeypatch.setattr(Broker, "dispatch_window", window)


FAULTS = {  # a fault planted in the served path -> the checks it fails
    "answer_altered": ("mismatched_publishes", "unanswered_publishes"),
    "half_batch_left_out": ("mismatched_publishes", "unanswered_publishes"),
    "device_path_off": ("breaker_degraded_batches_total",),
    "host_answers": ("host_fallback_pct",),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_fault_in_the_served_path_fails_the_comparison(tmp_path, monkeypatch, fault):
    cell = _tiny("plus1m.fleet_open")
    _planted(monkeypatch, fault)
    run, w, reference = _rehearse(cell, tmp_path, monkeypatch, seed=77)
    v = run.judge(w, reference.receivers)
    assert not v.correct
    assert any(v.values[k] > v.LIMITS[k] for k in FAULTS[fault]), v.checks()


# --- the command --------------------------------------------------------------------


def _command(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "plus1m.fleet_open",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def _has_result(stdout: str) -> bool:
    for line in stdout.strip().splitlines()[-1:]:
        try:
            return "correct" in json.loads(line)
        except ValueError:
            return False
    return False


def test_the_command_refuses_to_run_without_a_tpu():
    r = _command(ROOT)
    assert r.returncode != 0
    assert not _has_result(r.stdout)
    assert "no TPU" in r.stderr


def test_the_command_refuses_in_a_checkout_of_the_benchmark_alone(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmark")
    shutil.copytree(os.path.join(ROOT, "tests", "benchmark"), tmp_path / "tests" / "benchmark")
    r = _command(str(tmp_path))
    assert r.returncode != 0
    assert not _has_result(r.stdout)
