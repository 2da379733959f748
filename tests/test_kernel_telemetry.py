"""Kernel-telemetry tests: dispatch histograms populate from the real
match path, the recompile tracker stays flat under steady shapes,
DeviceTable gauges follow route churn, and the null collector records
nothing (the hot path stays branch-free either way)."""

import json

import numpy as np

from emqx_tpu.models.router import Router
from emqx_tpu.obs.kernel_telemetry import (
    BOUNDS,
    CLAMP_BOUND,
    NULL,
    KernelTelemetry,
    NullKernelTelemetry,
    StreamingHistogram,
)


def _routed(n_wild=64, n_exact=32, **kw):
    r = Router(max_levels=8, **kw)
    pairs = [(f"t{i}/+/x/#", f"d{i}") for i in range(n_wild)]
    pairs += [(f"ex/{i}/up", f"e{i}") for i in range(n_exact)]
    r.add_routes(pairs)
    return r


# --- histogram math -------------------------------------------------------


def test_histogram_observe_and_percentiles():
    h = StreamingHistogram()
    for v in (1e-4, 2e-4, 4e-4, 8e-4):
        h.observe(v)
    assert h.total == 4
    assert abs(h.sum - 1.5e-3) < 1e-12
    # percentiles honor bucket bounds: p50 lands between the 2nd and
    # 3rd sample's buckets, well inside [1e-4, 8e-4]
    p50 = h.percentile(50)
    assert 1e-4 <= p50 <= 8e-4
    assert h.percentile(100) >= h.percentile(50) >= h.percentile(0)
    # empty histogram answers 0.0, not NaN
    assert StreamingHistogram().percentile(99) == 0.0


def test_histogram_bucket_zero_is_the_clamp():
    # bucket zero's upper bound IS the bench epsilon clamp ceiling —
    # the round-5 "p25 silently on the clamp" bug becomes a query
    assert BOUNDS[0] == CLAMP_BOUND
    sat = StreamingHistogram()
    for _ in range(8):
        sat.observe(1e-5)  # pinned at the bench EPS clamp
    assert sat.clamp_saturated()
    assert sat.percentile(25) <= CLAMP_BOUND
    ok = StreamingHistogram()
    for _ in range(8):
        ok.observe(1e-3)
    assert not ok.clamp_saturated()
    assert ok.percentile(25) > CLAMP_BOUND


def test_histogram_merge_aligns_buckets():
    a, b = StreamingHistogram(), StreamingHistogram()
    a.observe(1e-4)
    b.observe(1e-2)
    a.merge(b)
    assert a.total == 2 and abs(a.sum - 0.0101) < 1e-9


# --- the instrumented match path -----------------------------------------


def test_dispatch_histograms_populated_after_match_batch():
    r = _routed()
    out = r.match_filters_batch([f"t{i}/a/x/y" for i in range(8)])
    assert out[0] == ["t0/+/x/#"]
    tel = r.telemetry
    assert tel.enabled
    # encode + hash legs saw the batch; sync saw the route upload
    assert tel.histogram("encode").total == 1
    assert tel.histogram("hash").total == 1
    assert tel.histogram("sync").total >= 1
    assert tel.counters["dispatch_batches_total"] == 1
    # snapshot is JSON-able and carries the same counts
    snap = json.loads(json.dumps(tel.snapshot()))
    assert snap["enabled"] is True
    assert snap["dispatch"]["hash"]["count"] == 1
    assert snap["counters"]["dispatch_batches_total"] == 1


def test_recompile_counter_flat_then_increments_on_new_shape():
    r = _routed()
    topics8 = [f"t{i}/a/x/y" for i in range(8)]
    r.match_filters_batch(topics8)
    tel = r.telemetry
    base = tel.counters["recompiles_total"]
    # same batch shape repeated: no new jit cache entries
    for _ in range(3):
        r.match_filters_batch(topics8)
    assert tel.counters["recompiles_total"] == base
    # a new batch size is a new shape bucket -> counter increments
    r.match_filters_batch([f"t{i}/a/x/y" for i in range(16)])
    assert tel.counters["recompiles_total"] > base
    assert tel.shape_buckets()["match_ids_hash"] >= 2


def test_retrace_warning_fires_on_shape_churn():
    tel = KernelTelemetry(retrace_warn_after=3)
    for i in range(4):
        tel.record_shape("k", (i,))
    assert tel.counters["retrace_warnings_total"] == 1
    # re-dispatching known shapes never re-warns
    tel.record_shape("k", (0,))
    assert tel.counters["retrace_warnings_total"] == 1


def test_sync_gauges_track_route_churn():
    r = _routed(n_wild=40, n_exact=10)
    r.device_table.sync()
    tel = r.telemetry
    g = tel.gauges
    assert g["device_table_rows"] == len(r.table) == 50
    assert g["device_table_capacity"] == r.table.capacity
    assert g["device_table_bytes"] > 0
    assert g["pending_deltas"] == 0
    assert 0.0 < g["slot_load_factor"] < 1.0
    rows_before = g["device_table_rows"]
    r.delete_routes([(f"t{i}/+/x/#", f"d{i}") for i in range(40)])
    r.device_table.sync()
    assert tel.gauges["device_table_rows"] == rows_before - 40 == len(r.table)
    assert tel.counters["sync_rows_total"] >= 50


def test_escalation_counter_on_dense_overflow():
    # dense path (no index): 5 filters x 1024 topics = 5120 matches
    # > the 4096 initial max_hits -> one escalated re-dispatch
    r = Router(max_levels=8, use_hash_index=False)
    r.add_routes([(f"a/#" if i == 0 else f"a/{'+/' * i}#", f"d{i}")
                  for i in range(5)])
    out = r.match_filters_batch(["a/b/c/d/e"] * 1024)
    assert len(out) == 1024 and len(out[0]) >= 1
    tel = r.telemetry
    assert tel.counters.get("escalations_total", 0) >= 1
    assert tel.histogram("dense").total >= 1


# --- null collector -------------------------------------------------------


def test_null_collector_records_nothing():
    r = _routed(telemetry=NULL)
    out = r.match_filters_batch([f"t{i}/a/x/y" for i in range(8)])
    assert out[0] == ["t0/+/x/#"]  # matching unaffected
    assert r.telemetry is NULL and not r.telemetry.enabled
    assert r.telemetry.snapshot() == {"enabled": False}
    assert r.telemetry.prometheus_lines() == []
    assert r.telemetry.shape_buckets() == {}
    assert NULL.clock() == 0.0  # no syscall on the disabled path


def test_null_collector_hot_path_overhead_bounded():
    # the <2% budget is asserted properly in the bench microharness;
    # here just guard against gross regressions (an instrumented batch
    # must stay within 1.5x of the null-collector batch on CPU, where
    # the dispatch dominates both)
    import time

    r_on = _routed(n_wild=128)
    r_off = _routed(n_wild=128, telemetry=NullKernelTelemetry())
    topics = [f"t{i % 128}/a/x/y" for i in range(64)]
    r_on.match_filters_batch(topics)  # compile
    r_off.match_filters_batch(topics)

    def med(r):
        ts = []
        for _ in range(15):
            t0 = time.perf_counter()
            r.match_filters_batch(topics)
            ts.append(time.perf_counter() - t0)
        return float(np.median(ts))

    assert med(r_on) < 1.5 * med(r_off)


# --- bench integration ----------------------------------------------------


def test_record_samples_returns_batch_view():
    tel = KernelTelemetry()
    b1 = tel.record_samples("#2", [1e-5] * 6)
    assert b1.clamp_saturated()
    b2 = tel.record_samples("#2", [5e-3] * 18)
    assert not b2.clamp_saturated()
    # the collector accumulated both batches under one leg...
    assert tel.histogram("#2").total == 24
    # ...and the run-wide series is NOT saturated (6 of 24 in bucket 0)
    assert not tel.histogram("#2").clamp_saturated()


def test_dispatch_percentile_merges_device_legs():
    tel = KernelTelemetry()
    tel.record_dispatch("hash", 1e-4)
    tel.record_dispatch("dense", 1e-2)
    p99 = tel.dispatch_percentile(99)
    assert p99 > 1e-3  # sees the slow dense leg, not just hash
    assert tel.dispatch_percentile(99, legs=("hash",)) < 1e-3
