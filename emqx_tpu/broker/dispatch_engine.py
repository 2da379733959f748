"""Pipelined async dispatch engine for the publish hot path.

BENCH_r05 put the chip-resident match kernel at ~0.09-0.39 ms/batch
while end-to-end publish sat at 250 ms p50: the synchronous
encode → dispatch → device-to-host walk pays the full link round trip
per publish, so the kernel win evaporates before it reaches a socket.
This module is the host-side dispatch discipline that closes that gap,
the emqx_broker pool-worker batching analog re-shaped for an
accelerator link:

  * **Micro-batching queue** — concurrent publishes coalesce into one
    kernel dispatch. The batch closes adaptively: flush when
    `queue_depth` topics are waiting OR when the oldest enqueued
    publish has waited `deadline_ms` (sub-millisecond by default),
    whichever comes first — bounded added latency, unbounded
    coalescing win under load.

  * **Pipelining** — a flush only LAUNCHES the batch
    (Router.match_filters_begin: cache probe, encode, host-to-device
    transfer, kernel dispatch); the device-to-host fetch + fanout
    (match_filters_finish) happens on a later event-loop turn, or when
    the in-flight window exceeds `pipeline_depth`. JAX dispatch is
    asynchronous and the device tables update in place through donated
    buffers, so while batch N executes on the device the host encodes
    and uploads batch N+1 and drains the result pairs of batch N-1 —
    the classic double-buffer, for both DeviceTable and
    ShardedDeviceTable (both sit behind the same begin/finish seam).

  * **Generation-stamped match cache** — in front of the queue,
    Router's GenMatchCache (ops/match.py) resolves hot topics with one
    dict probe and no kernel at all; route mutations bump the router
    generation and stale entries lazily rebuild, so churn never does
    an O(n) clear.

  * **Fanout-resolve overlap** — topics the match cache answers at
    begin time have known filter sets before the kernel fetch: their
    stale/missing fanout plans launch `Router.resolve_fanout_begin`
    (the device dedup/max-QoS kernel, ops/fanout.py) in the same
    flush, so the deduped plan materializes on device while the match
    hash fetch for the uncached remainder is still in flight; plans
    install stamped with the begin-time clock (stale-on-arrival if a
    mutation landed mid-flight).

Exactness contract: every result is produced by the same
begin/finish code path the synchronous `Broker.publish_batch` →
`Router.match_filters_batch` composes, and delivery runs through the
same `Broker._pre_publish`/`Broker._dispatch` — pipelined + cached
results are bit-identical to the synchronous path (oracle-checked in
tests/test_dispatch_engine.py and bench.py's pipeline exactness
stage).

**Device failure domain** (the emqx_olp / emqx_limiter analog for the
accelerator link — see PARITY.md):

  * **Failover** — a device batch that fails (XlaRuntimeError-class,
    injected or real) or blows the per-batch `breaker_deadline_ms` is
    transparently re-served through the host match walk
    (`Router.match_filters_host` — bit-identical by the oracle
    contract), so publishers never see a transient device fault.

  * **Circuit breaker** — `breaker_threshold` CONSECUTIVE device
    failures trip the breaker: `Router.suspend_device()` routes ALL
    match + fanout traffic host-side (degraded-but-correct), the
    `xla_device_breaker` alarm raises, and the flight recorder
    freezes a `device_breaker_trip` bundle.

  * **Recovery** — a background canary probe with bounded exponential
    backoff re-dispatches a sentinel batch through the real kernels;
    on success it re-uploads FULL device state (the quarantine
    clean-sync machinery: `Router.device_resync`) and verifies a
    second canary against the host oracle before closing the breaker
    and clearing the alarm — the recovered device re-earns trust
    under the sentinel's shadow audit, never by assumption.

  * **Admission control** — the dispatch queue is bounded
    (`queue_max_depth` outstanding publishes). Overload either SHEDS
    (fail fast with `QueueOverloadError`, counted, `xla_queue_overload`
    alarm at the high watermark, cleared at the low watermark) or
    BLOCKS (publishers park on a waiter list drained as capacity
    frees) per `queue_policy`; blocked publishers carry a
    `queue_deadline_ms` so a wedged device can never hang them
    indefinitely. The emqx_olp load-shed / emqx_limiter token-bucket
    analog for the device link.

Telemetry (obs/kernel_telemetry, scraped as `emqx_xla_*`): queue-wait
histogram family `pipeline_queue_wait_seconds`, gauges
`pipeline_depth` / `pipeline_coalesce`, the cache's
hits/misses/evictions counters recorded by the Router, plus the
failure-domain families `emqx_xla_breaker_*` / `emqx_xla_queue_*`
(state, trips, recoveries, fallbacks, probes, sheds, blocks,
deadline expiries — all transitions counted).
"""

from __future__ import annotations

import asyncio
import contextlib
import functools
import gc
import logging
from collections import deque
from contextlib import nullcontext
from typing import Deque, Dict, List, Optional, Set, Tuple

from ..obs.otel import publish_root
from ..obs.profiler import STAGE_MARK
from .message import Message

log = logging.getLogger("emqx_tpu.broker.dispatch_engine")

ALARM_BREAKER = "xla_device_breaker"
ALARM_OVERLOAD = "xla_queue_overload"

# breaker_state gauge encoding
_STATE_GAUGE = {"closed": 0, "open": 1, "half_open": 2}


class EngineStopped(RuntimeError):
    """The dispatch engine stopped; queued publishers fail
    deterministically instead of hanging."""


class QueueOverloadError(RuntimeError):
    """Admission control shed this publish (queue at high watermark
    under the `shed` policy) — fail fast, counted, alarmed."""


class QueueDeadlineExceeded(RuntimeError):
    """A blocked publish waited past `queue_deadline_ms` for queue
    capacity — the engine fails it rather than hanging the publisher
    on a wedged device."""


class _AggregateCount:
    """Future-compatible shim folding N per-publish delivery counts
    into ONE awaitable — the storm surface (submit_many) enqueues a
    whole chunk against a single future instead of paying a Future
    allocation + callback wake per publish. Only the three methods
    _flush/_collect_one actually touch are implemented."""

    __slots__ = ("_fut", "_left", "_total")

    def __init__(self, fut: "asyncio.Future", n: int) -> None:
        self._fut = fut
        self._left = n
        self._total = 0

    def done(self) -> bool:
        return self._fut.done()

    def set_result(self, n: int) -> None:
        self._total += n
        self._left -= 1
        if self._left <= 0 and not self._fut.done():
            self._fut.set_result(self._total)

    def set_exception(self, exc: BaseException) -> None:
        self._left -= 1
        if not self._fut.done():
            self._fut.set_exception(exc)

    def add_many(self, total: int, k: int) -> None:
        """Fold k publishes' combined count in ONE call — the window
        dispatch completes a whole submit_many chunk per collect
        instead of ticking set_result per publish."""
        self._total += total
        self._left -= k
        if self._left <= 0 and not self._fut.done():
            self._fut.set_result(self._total)


class DispatchEngine:
    """One engine per Broker. All entry points must run on the
    broker's event loop; the engine holds no locks — ordering comes
    from the loop plus the FIFO in-flight window (begin/finish pairs
    complete strictly in begin order, the Router contract)."""

    def __init__(
        self,
        broker,
        queue_depth: int = 64,
        deadline_ms: float = 0.5,
        pipeline_depth: int = 2,
        match_cache_size: int = 8192,
        breaker_enable: bool = True,
        breaker_threshold: int = 4,
        breaker_deadline_ms: float = 250.0,
        probe_backoff_ms: float = 100.0,
        probe_backoff_max_ms: float = 5000.0,
        queue_max_depth: int = 8192,
        queue_policy: str = "shed",
        queue_deadline_ms: float = 1000.0,
        queue_low_watermark: int = 0,
        transfer_chunk_kb: float = 0.0,
        aot_warm: bool = True,
        gc_guard: bool = True,
        alarms=None,
        flight=None,
    ) -> None:
        self.broker = broker
        self.router = broker.router
        if match_cache_size:
            self.router.enable_match_cache(match_cache_size)
        self.telemetry = self.router.telemetry
        self.queue_depth = max(1, queue_depth)
        self.deadline_s = max(0.0, deadline_ms) / 1e3
        self.pipeline_depth = max(1, pipeline_depth)
        # --- device failure domain (breaker) knobs
        self.breaker_enabled = bool(breaker_enable)
        self.breaker_threshold = max(1, breaker_threshold)
        self.breaker_deadline_s = max(0.0, breaker_deadline_ms) / 1e3
        self.probe_backoff_s = max(0.001, probe_backoff_ms) / 1e3
        self.probe_backoff_max_s = max(
            self.probe_backoff_s, probe_backoff_max_ms / 1e3
        )
        # --- admission control knobs
        self.queue_max_depth = max(1, queue_max_depth)
        assert queue_policy in ("shed", "block"), queue_policy
        self.queue_policy = queue_policy
        self.queue_deadline_s = max(0.001, queue_deadline_ms) / 1e3
        self.queue_low_watermark = (
            queue_low_watermark
            if queue_low_watermark
            else max(1, self.queue_max_depth // 2)
        )
        # --- transfer pipeline knobs (ops/transfer.py)
        # chunk_kb: bound on a ring slot's compacted-result buffer;
        # 0 = auto-size from the link probe at warmup (BDP). aot_warm:
        # pre-trace every kernel shape bucket at warmup so production
        # dispatches never pay an XLA retrace. gc_guard: keep
        # collector pauses out of launch/collect critical sections
        # (gc.freeze of steady state at warmup + per-flush pause).
        self.transfer_chunk_kb = float(transfer_chunk_kb)
        self.aot_warm = bool(aot_warm)
        self.gc_guard = bool(gc_guard)
        self.warmed = False
        self.warmup_info: dict = {}
        # router.shape_key() the shape ladder was last warmed for, and
        # the off-loop re-warm in flight when the table outgrew it
        self._warm_key = None
        self._rewarm: Optional[asyncio.Future] = None
        # alarms/flight: explicit wiring wins; otherwise resolved
        # lazily through the attached sentinel (boot order attaches
        # the engine first and the obs bundle later — or vice versa in
        # tests — so neither order may lose the surfaces)
        self.alarms = alarms
        self.flight = flight
        self._queue: List[tuple] = []  # (msg, future, enqueue clock, span)
        # dispatched-but-unfetched batches: (pending match, entries)
        self._inflight: Deque[tuple] = deque()
        self._inflight_pubs = 0  # publishes inside _inflight entries
        self._waiters: Deque[tuple] = deque()  # block-policy parked items
        self._timer = None
        self._waiter_timer = None
        self._drain_scheduled = False
        self._pumping = False
        self._overloaded = False
        self.batches_total = 0
        self.publishes_total = 0
        self.closed = False
        # --- breaker state machine: closed -> open -> half_open -> closed
        self.breaker_state = "closed"
        self._consecutive_failures = 0
        self._probe_task: Optional[asyncio.Task] = None
        # --- shard breaker (ShardedDeviceTable chip loss): failures
        # whose exception carries a `shard` attribute are accounted
        # here PER SHARD and never feed _consecutive_failures — one
        # sick chip must not forfeit the whole mesh
        self._shard_failures: Dict[int, int] = {}
        self._shard_open: Set[int] = set()
        self._shard_probe_tasks: Dict[int, asyncio.Task] = {}
        self.last_device_error: Optional[str] = None
        # canary topics: the most recent distinct batch heads, so the
        # recovery probe dispatches realistic traffic, not synthetics
        self._recent_topics: Deque[str] = deque(maxlen=8)
        # --- ring slot timeline: launch->land spans per ring slot, on
        # the host clock (device idle time is the device trace's)
        self._ring_slots_total = 0
        self._ring_timeline: Deque[Dict] = deque(maxlen=64)
        tel = self.telemetry
        if tel.enabled:
            tel.set_gauge("breaker_state", 0)
            tel.set_gauge("breaker_consecutive_failures", 0)
            tel.set_gauge("queue_depth", 0)
            tel.set_gauge("queue_waiters", 0)
            tel.set_gauge("queue_overloaded", 0)

    # --- obs wiring -------------------------------------------------------

    def _get_alarms(self):
        if self.alarms is not None:
            return self.alarms
        st = self.broker.sentinel
        return st.alarms if st is not None else None

    def _get_flight(self):
        if self.flight is not None:
            return self.flight
        st = self.broker.sentinel
        return st.flight if st is not None else None

    # --- warmup: chunk sizing + AOT shape pre-trace + GC discipline ------

    def warmup(self) -> dict:
        """One-time serve-readiness pass (boot calls it after attach;
        bench calls it before timed windows; idempotent):

          1. size the transfer chunk — `transfer_chunk_kb` as given, or
             auto from a link probe (RTT floor x fetch bandwidth, the
             BDP) — and push it into the device table;
          2. AOT-warm every kernel shape bucket the engine can dispatch
             (pow2 batch ladder up to queue_depth through the REAL
             begin/finish halves), then flip the telemetry to serving:
             any later retrace counts as `recompiles_at_serve_total`;
          3. freeze the now-steady object graph out of the cyclic
             collector (gc.freeze) so gen-2 passes never scan the
             table/session bulk from inside a timed launch — paired
             with the per-flush collector pause in _flush/_collect_one.

        Once warmed, the engine re-warms by itself whenever the table
        outgrows the warmed shapes (_rewarm_pending), off the loop.

        Returns a summary dict, kept as `warmup_info`."""
        router = self.router
        tel = self.telemetry
        t_start = tel.clock()
        info: dict = {}
        chunk_kb = self.transfer_chunk_kb
        if not chunk_kb:
            from ..ops import transfer as transfer_ops

            try:
                rtt_s, bw = transfer_ops.probe_link()
                chunk_kb = transfer_ops.auto_chunk_kb(rtt_s, bw)
                info["link_rtt_ms"] = round(rtt_s * 1e3, 3)
                info["link_mb_per_s"] = round(bw / 1e6, 1)
            except Exception as e:
                # a dead link at boot is the breaker's business, not
                # warmup's — leave the chunk unbounded, note it
                tel.count("warmup_probe_failures_total")
                log.warning("link probe failed during warmup: %r", e)
                chunk_kb = 0
        if chunk_kb:
            router.set_transfer_chunk(chunk_kb)
        self.transfer_chunk_kb = chunk_kb
        info["transfer_chunk_kb"] = chunk_kb
        if self.aot_warm:
            try:
                with tel.warming():
                    info["aot_shapes"] = router.warmup_shapes(
                        self.queue_depth
                    )
            except Exception as e:
                # a device that cannot even warm up is the breaker's
                # business — boot comes up degraded, never dead
                tel.count("warmup_failures_total")
                log.warning("AOT warmup failed: %r", e)
                self._device_failure(e)
        dt = router.device_table
        if getattr(dt, "mesh", None) is not None:
            # mesh serve state at readiness: shard count and whether
            # the admission knob degraded to single-device (small
            # table at warmup — the mesh kernels are then warmed on
            # the upgrade resync, not here)
            info["mesh_shards"] = dt.n_shards
            info["mesh_degraded"] = bool(dt.degraded)
        tel.mark_serving()
        if self.gc_guard and not self.warmed:
            gc.collect()
            gc.freeze()
        self.warmed = True
        self._warm_key = router.shape_key()
        info["seconds"] = tel.clock() - t_start
        info["rewarms"] = 0
        self.warmup_info = info
        return info

    def _rewarm_pending(self) -> bool:
        """True while the open batch must wait for an off-loop re-warm.

        When the host tables outgrew the warmed shapes (the table grew,
        a pattern class appeared), the first batch after that syncs
        the device on the loop, which owns the host tables, and starts
        the shape ladder's compile on a worker thread. The loop keeps
        serving sockets meanwhile; the queued publishes launch when
        the warm lands (_rewarm_done), so none compiles at serve time.
        In-flight batches are collected first: the warm must be the
        only device user while it runs."""
        if self._rewarm is not None:
            return True
        router = self.router
        if not (self.warmed and self.aot_warm) or router.device_suspended:
            return False
        if router.shape_key() == self._warm_key:
            return False
        while self._inflight:
            self._collect_one()
        if self._rewarm is not None:
            return True  # a collect above re-entered _flush and began it
        tel = self.telemetry
        t0 = tel.clock()
        dt = router.device_table
        try:
            # a full upload: the changed shapes need one anyway, and a
            # scatter of an unwarmed batch count would compile here
            dt.invalidate()
            dt.sync()
        except Exception:
            # the launch below meets the same fault and fails over
            tel.count("warmup_failures_total")
            return False
        key = router.shape_key()
        info = self.warmup_info
        info["rewarm_sync_seconds"] = (
            info.get("rewarm_sync_seconds", 0.0) + tel.clock() - t0
        )
        try:
            self._rewarm = asyncio.get_running_loop().run_in_executor(
                None, self._rewarm_shapes
            )
        except RuntimeError:
            return False  # the loop is shutting down: nothing to warm for
        self._rewarm.add_done_callback(
            functools.partial(self._rewarm_done, key, t0)
        )
        return True

    def _rewarm_shapes(self) -> int:
        """Worker thread: compile the ladder over the synced state."""
        with self.telemetry.warming():
            return self.router.warmup_shapes(self.queue_depth, sync=False)

    def _rewarm_done(self, key, t0: float, fut: "asyncio.Future") -> None:
        """Loop: account the re-warm, then launch what queued behind it."""
        self._rewarm = None
        self._warm_key = key
        tel = self.telemetry
        try:
            shapes = fut.result()
        except Exception as e:
            # a device that cannot warm is the breaker's business
            shapes = 0
            tel.count("warmup_failures_total")
            log.warning("AOT re-warm failed: %r", e)
            self._device_failure(e)
        seconds = tel.clock() - t0
        tel.count("rewarms_total")
        info = self.warmup_info
        info["rewarms"] = info.get("rewarms", 0) + 1
        info["rewarm_shapes"] = info.get("rewarm_shapes", 0) + shapes
        info["rewarm_seconds"] = info.get("rewarm_seconds", 0.0) + seconds
        if self.gc_guard:
            # the grown table is the new steady state: keep it out of
            # full collector passes too, as warmup() did at boot
            gc.freeze()
        while self._queue and self._rewarm is None and not self.closed:
            self._flush()

    def _gc_pause(self) -> bool:
        """Suspend the cyclic collector for a launch/collect critical
        section; returns whether it was running (restore token)."""
        if not self.gc_guard:
            return False
        was = gc.isenabled()
        if was:
            gc.disable()
        return was

    @staticmethod
    def _gc_resume(was: bool) -> None:
        if was:
            gc.enable()

    # --- async publish surface -------------------------------------------

    async def publish(self, msg: Message) -> int:
        """Enqueue one publish and await its delivery count. The
        pipelined analog of Broker.publish — identical hooks, identical
        match results, identical dispatch."""
        return await self.submit(msg)

    def _check_open(self) -> None:
        if self.closed:
            raise EngineStopped("dispatch engine stopped")

    def submit(self, msg: Message) -> "asyncio.Future":
        """Enqueue without awaiting; returns the delivery-count future.
        Flushes immediately at queue_depth, else arms the sub-ms
        deadline timer for the batch the first enqueue opened."""
        self._check_open()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        # publish sentinel (obs/sentinel.py): a 1/sample_n publish gets
        # a stage span + a deferred shadow-oracle audit; every other
        # publish pays one attribute read + one counter increment
        st = self.broker.sentinel
        span = st.maybe_span(msg) if st is not None else None
        if self._admit((msg, fut, self.telemetry.clock(), span), loop):
            if len(self._queue) >= self.queue_depth:
                self._flush()
            elif self._timer is None:
                self._timer = loop.call_later(
                    self.deadline_s, self._on_deadline
                )
        return fut

    def submit_many(self, msgs) -> "asyncio.Future":
        """Storm surface: enqueue a chunk of publishes as one unit and
        return ONE future resolving to the summed delivery count. Same
        hooks, same match path, same sentinel sampling per message as
        submit() — only the per-publish Future ceremony is amortized,
        which is what lets a million-session soak generator saturate
        the pipeline from a single driver task. Admission control
        applies per message: a shed message fails the aggregate."""
        self._check_open()
        loop = asyncio.get_running_loop()
        fut = loop.create_future()
        if not msgs:
            fut.set_result(0)
            return fut
        agg = _AggregateCount(fut, len(msgs))
        st = self.broker.sentinel
        clock = self.telemetry.clock
        for msg in msgs:
            span = st.maybe_span(msg) if st is not None else None
            # _flush REPLACES self._queue with a fresh list — re-read
            # it each append rather than holding a stale binding
            if self._admit((msg, agg, clock(), span), loop):
                if len(self._queue) >= self.queue_depth:
                    self._flush()
        if self._queue and self._timer is None:
            self._timer = loop.call_later(self.deadline_s, self._on_deadline)
        return fut

    # --- admission control (the emqx_olp analog) --------------------------

    def outstanding(self) -> int:
        """Publishes the engine currently owns: batched + in flight.
        Blocked waiters are excluded — they ARE the backpressure."""
        return len(self._queue) + self._inflight_pubs

    def _admit(self, item: tuple, loop) -> bool:
        """True when the item entered the batch queue; False when it
        was shed (future failed) or parked on the waiter list."""
        tel = self.telemetry
        if self.outstanding() < self.queue_max_depth:
            self._queue.append(item)
            return True
        self._overload(tel)
        if self.queue_policy == "block":
            tel.count("queue_blocked_total")
            self._waiters.append(item)
            tel.set_gauge("queue_waiters", len(self._waiters))
            if self._waiter_timer is None:
                self._waiter_timer = loop.call_later(
                    self.queue_deadline_s / 2, self._expire_waiters
                )
            return False
        tel.count("queue_shed_total")
        _msg, fut, _t, _span = item
        if not fut.done():
            fut.set_exception(
                QueueOverloadError(
                    f"dispatch queue overloaded "
                    f"({self.outstanding()}/{self.queue_max_depth} "
                    f"outstanding, policy=shed)"
                )
            )
        return False

    def _overload(self, tel) -> None:
        if self._overloaded:
            return
        self._overloaded = True
        tel.set_gauge("queue_overloaded", 1)
        alarms = self._get_alarms()
        if alarms is not None:
            try:
                alarms.ensure(
                    ALARM_OVERLOAD,
                    details={
                        "outstanding": self.outstanding(),
                        "max_depth": self.queue_max_depth,
                        "policy": self.queue_policy,
                    },
                    message=(
                        f"dispatch queue overloaded "
                        f"({self.queue_policy} policy engaged)"
                    ),
                )
            except Exception:
                tel.count("queue_alarm_failures_total")
                log.exception("overload alarm failed")

    def _maybe_clear_overload(self) -> None:
        if not self._overloaded:
            return
        if self.outstanding() > self.queue_low_watermark or self._waiters:
            return
        self._overloaded = False
        tel = self.telemetry
        tel.set_gauge("queue_overloaded", 0)
        alarms = self._get_alarms()
        if alarms is not None:
            alarms.ensure_deactivated(ALARM_OVERLOAD)

    def _pump_waiters(self) -> None:
        """Admit parked publishers as capacity frees (block policy).
        Re-entrancy guarded: pumping flushes, flushes collect, and a
        collect completion calls back in here."""
        if self._pumping or not self._waiters:
            return
        self._pumping = True
        tel = self.telemetry
        now = tel.clock()
        try:
            while self._waiters and (
                self.outstanding() < self.queue_max_depth
            ):
                item = self._waiters.popleft()
                _msg, fut, t_in, _span = item
                if fut.done():
                    continue
                if now - t_in > self.queue_deadline_s:
                    tel.count("queue_deadline_expired_total")
                    fut.set_exception(
                        QueueDeadlineExceeded(
                            f"waited {now - t_in:.3f}s for queue capacity "
                            f"(deadline {self.queue_deadline_s:.3f}s)"
                        )
                    )
                    continue
                self._queue.append(item)
                if len(self._queue) >= self.queue_depth:
                    self._flush()
        finally:
            self._pumping = False
            tel.set_gauge("queue_waiters", len(self._waiters))
        self._maybe_clear_overload()

    def _expire_waiters(self) -> None:
        """Waiter-deadline sweep: a blocked publisher past its queue
        deadline fails deterministically — a wedged device can slow
        the broker, never hang its publishers."""
        self._waiter_timer = None
        tel = self.telemetry
        now = tel.clock()
        keep: Deque[tuple] = deque()
        expired = 0
        while self._waiters:
            item = self._waiters.popleft()
            _msg, fut, t_in, _span = item
            if fut.done():
                continue
            if now - t_in > self.queue_deadline_s:
                expired += 1
                fut.set_exception(
                    QueueDeadlineExceeded(
                        f"waited {now - t_in:.3f}s for queue capacity "
                        f"(deadline {self.queue_deadline_s:.3f}s)"
                    )
                )
            else:
                keep.append(item)
        self._waiters = keep
        if expired:
            tel.count("queue_deadline_expired_total", expired)
        tel.set_gauge("queue_waiters", len(self._waiters))
        if self._waiters and not self.closed:
            self._waiter_timer = asyncio.get_running_loop().call_later(
                self.queue_deadline_s / 2, self._expire_waiters
            )
        else:
            self._maybe_clear_overload()

    def _on_deadline(self) -> None:
        self._timer = None
        if self._queue:
            self._flush()

    # --- batch close + pipeline ------------------------------------------

    def _flush(self) -> None:
        """Close the current batch: run the publish hooks, LAUNCH the
        match kernels (no device->host fetch), and push the pending
        batch onto the in-flight window. Collection happens on a later
        loop turn (_drain) or immediately for whatever exceeds the
        pipeline depth. A device fault at launch fails over to a
        host-mode batch — publishers never see it."""
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self._rewarm_pending():
            return
        # one batch is at most queue_depth publishes (the warmed ladder's
        # top): a queue that grew behind a re-warm flushes in slices
        if len(self._queue) <= self.queue_depth:
            batch, self._queue = self._queue, []
        else:
            batch = self._queue[: self.queue_depth]
            del self._queue[: self.queue_depth]
        # collector pauses must not land inside the launch window (the
        # gen-2-pass-in-a-timed-batch outlier PERF_NOTES r5/r6 chased);
        # the pause spans launch + any forced over-depth collects and
        # restores on exit, so collection happens BETWEEN batches
        gc_tok = self._gc_pause()
        try:
            tel = self.telemetry
            broker = self.broker
            router = self.router
            st = broker.sentinel
            now = tel.clock()
            entries = []
            topics = []
            bspan = None
            # batched-WHERE window: rule predicates hit inside the
            # publish-hook fold defer into one columnar drain when the
            # window closes — the whole coalesced batch shares one
            # column extraction per referenced path
            rb = getattr(broker, "rule_batcher", None)
            win = (
                rb.batch_window()
                if rb is not None and rb.batch_where_enabled
                else nullcontext()
            )
            mark = STAGE_MARK
            prev_stage = mark.enter("coalesce")
            if mark.span is not None:
                mark.span.set_metadata(publishes=len(batch))
            # external tracer (obs/otel.py): each publish's mqtt.publish
            # root opens here; one attribute read per untraced batch
            tr = broker.tracer
            roots = (
                [publish_root(tr, msg) for msg, _f, _t, _s in batch]
                if tr is not None else None
            )
            with win:
                for msg, fut, t_in, span in batch:
                    tel.observe_family(
                        "pipeline_queue_wait_seconds", now - t_in
                    )
                    if span is not None and bspan is None and st is not None:
                        bspan = st.batch_span()
                    live = broker._pre_publish(msg)
                    if span is not None:
                        # queue sub-decomposition: submit_wait is
                        # submit()->flush fire; coalesce is this
                        # publish's wait inside the flush fold (its own
                        # hook walk included). submit_wait + coalesce
                        # == queue exactly, by construction — the
                        # sum-to-wall contract starts here.
                        t_end = tel.clock()
                        span.add("queue", t_end - t_in)
                        span.add_sub("submit_wait", now - t_in)
                        span.add_sub("coalesce", t_end - now)
                    entries.append((live, fut, span))
                    if live is not None:
                        topics.append(live.topic)
            self.batches_total += 1
            self.publishes_total += len(batch)
            if topics:
                self._recent_topics.append(topics[0])
            # match_launch: cache probe and table sync; the router marks
            # its encode, launch and ticket_start inside it
            mark.enter("match_launch")
            otel = (
                self._otel_route(tr, roots, entries)
                if roots is not None else None
            )
            try:
                pending = router.match_filters_begin(topics, span=bspan)
            except Exception as e:
                # launch-side device fault (encode/sync/kernel dispatch):
                # re-begin in host mode — the cache probe re-runs (cheap,
                # correct) and finish serves from host truth
                tel.count("breaker_begin_failures_total")
                self._device_failure(e)
                pending = self._host_begin(topics, bspan)
            # device-resolved fanout overlap: topics the match cache
            # answered at begin time have known filter sets NOW — launch
            # their plan resolves immediately so the deduped plan
            # materializes on device while the match hash fetch for the
            # uncached remainder is still in flight
            fanout_pending = None
            mark.enter("plan_resolve")
            if (
                broker._fanout_device
                and pending.full_out is not None
                and not router.device_suspended
            ):
                seen = set()
                for flts in pending.full_out:
                    if flts is None:
                        continue
                    fkey = tuple(flts)
                    if fkey in seen:
                        continue
                    seen.add(fkey)
                    if broker._plan_fresh(fkey):
                        continue
                    try:
                        h = router.resolve_fanout_begin(
                            fkey, min_fan=broker._fanout_min_fan
                        )
                    except Exception as e:
                        # fanout launch fault: the dispatch path rebuilds
                        # plans host-side — skip the overlap, note the link
                        tel.count("fanout_host_fallback_total")
                        self._device_failure(e)
                        break
                    if h is not None:
                        if fanout_pending is None:
                            fanout_pending = []
                        fanout_pending.append(
                            (fkey, broker._fanout_clock, h)
                        )
            mark.leave(prev_stage)
            self._inflight.append(
                (pending, entries, fanout_pending, bspan, tel.clock(), otel)
            )
            self._inflight_pubs += len(entries)
            tel.set_gauge("pipeline_depth", len(self._inflight))
            tel.set_gauge("pipeline_coalesce", len(batch))
            tel.set_gauge("queue_depth", self.outstanding())
            while len(self._inflight) > self.pipeline_depth:
                self._collect_one()
        finally:
            self._gc_resume(gc_tok)
        if self._inflight and not self._drain_scheduled:
            self._drain_scheduled = True
            asyncio.get_running_loop().call_soon(self._drain)

    def _host_begin(self, topics, bspan):
        """Begin a batch with the device forced out of the loop (the
        failover path when match_filters_begin itself raised)."""
        router = self.router
        prev = router.device_suspended
        router.device_suspended = True
        try:
            return router.match_filters_begin(topics, span=bspan)
        finally:
            router.device_suspended = prev

    # seconds between readiness re-probes while the ring head's
    # transfer is still in flight (the loop is yielded, not blocked)
    _RING_POLL_S = 0.0002

    def _head_ready(self) -> bool:
        """True when collecting the ring head will not block: the
        match legs' AND any overlapped fanout resolves' transfer
        tickets have all landed host-side."""
        pending, _entries, fanout_pending, _bspan, _t, _otel = self._inflight[0]
        if not self.router.match_finish_ready(pending):
            return False
        if fanout_pending is not None:
            for _fkey, _clock, h in fanout_pending:
                if not h[0].ready():
                    return False
        return True

    def _drain(self) -> None:
        """Collect ring slots in COMPLETION order without ever
        blocking the event loop on a transfer still in flight:
        delivery order stays strictly begin order (the Router's
        finish contract — bit-exactness depends on it), but a head
        whose transfer has not landed yields the loop and re-probes,
        so the host keeps encoding/launching instead of stalling in
        np.asarray. Over-depth slots still force-collect (the ring is
        the backpressure bound)."""
        self._drain_scheduled = False
        while self._inflight:
            if (
                len(self._inflight) > self.pipeline_depth
                or self._head_ready()
            ):
                self._collect_one()
                continue
            self._drain_scheduled = True
            asyncio.get_running_loop().call_later(
                self._RING_POLL_S, self._drain
            )
            return
        self.telemetry.set_gauge("pipeline_depth", 0)

    def _collect_one(self) -> None:
        """Fetch + deliver the OLDEST in-flight batch (begin order).
        A device fault here re-serves the whole batch through the host
        walk; a slow-but-successful device batch past the breaker
        deadline counts toward the breaker without being re-served
        (its results are already correct)."""
        pending, entries, fanout_pending, bspan, t_launch, otel = (
            self._inflight.popleft()
        )
        broker = self.broker
        router = self.router
        st = broker.sentinel
        tel = self.telemetry
        tclock = tel.clock
        device_batch = pending.mode not in ("cached", "host")
        gc_tok = self._gc_pause()
        # match_fetch: device->host transfer + unpack of the match result
        mark = STAGE_MARK
        prev_stage = mark.enter("match_fetch")
        try:
            t0 = tclock()
            try:
                filter_lists = router.match_filters_finish(pending)
            except Exception as e:
                # transient device fault: re-serve the WHOLE batch from
                # host truth — bit-identical by the oracle contract, so
                # publishers never see it; the failure still counts toward
                # the breaker
                tel.count("breaker_fallback_total", len(entries))
                self._device_failure(e)
                fanout_pending = None  # overlapped resolves died with it
                try:
                    filter_lists = router.match_filters_host(pending)
                except Exception as e2:  # host truth failed: nothing left
                    tel.count("publish_failures_total", len(entries))
                    for _live, fut, _span in entries:
                        if not fut.done():
                            fut.set_exception(e2)
                    if otel is not None:
                        self._otel_finish(otel, entries, [e2] * len(entries))
                    self._ring_land(tclock(), t_launch, "failed", len(entries))
                    self._batch_done(len(entries))
                    return
            else:
                if device_batch and self.breaker_enabled:
                    if (
                        self.breaker_deadline_s
                        and tclock() - t0 > self.breaker_deadline_s
                    ):
                        # slow is a fault even when it is not wrong: the
                        # results serve, the breaker still hears about it
                        tel.count("breaker_deadline_exceeded_total")
                        self._device_failure(None)
                    else:
                        self._device_success()
            if otel is not None:
                self._otel_dispatch(otel, entries, filter_lists)
            if fanout_pending is not None:
                # install the overlapped plans before delivering: stamped
                # with the clock captured at begin, so a mutation that
                # landed mid-flight leaves them stale-on-arrival and the
                # dispatch below rebuilds — exactness over hit ratio
                mark.enter("plan_resolve")
                t_res = tclock() if bspan is not None else 0.0
                for fkey, clock, h in fanout_pending:
                    try:
                        plan = router.resolve_fanout_finish(h)
                    except Exception as e:
                        # the dispatch path rebuilds host-side; counted so
                        # a dying link can't fail resolves silently
                        tel.count("fanout_host_fallback_total")
                        self._device_failure(e)
                        continue
                    broker._store_plan(fkey, clock, plan)
                if bspan is not None:
                    bspan.add("resolve", tclock() - t_res)
            self._ring_land(tclock(), t_launch, pending.mode, len(entries))
            # the vectorized delivery half: ONE window dispatch for the
            # whole collected batch (plan resolution per unique filter
            # set, session-grouped writes) instead of a per-publish
            # _dispatch loop — see Broker.dispatch_window
            mark.enter("dispatch_loop")
            results, meta = broker.dispatch_window(
                [e[0] for e in entries],
                filter_lists,
                spans=[e[2] for e in entries],
                capture_errors=True,
            )
            # aggregate completion: consecutive publishes sharing a
            # submit_many aggregate fold into one add_many instead of a
            # per-publish set_result tick
            pend_fut = None
            pend_total = 0
            pend_k = 0

            def _flush_agg() -> None:
                nonlocal pend_fut, pend_total, pend_k
                if pend_fut is None:
                    return
                if type(pend_fut) is _AggregateCount:
                    pend_fut.add_many(pend_total, pend_k)
                elif not pend_fut.done():
                    pend_fut.set_result(pend_total)
                pend_fut = None
                pend_total = 0
                pend_k = 0

            for idx, (live, fut, span) in enumerate(entries):
                n = results[idx]
                if isinstance(n, BaseException):
                    # a delivery-side failure is the publisher's to
                    # see (host bug, not a device fault) — counted,
                    # then propagated
                    _flush_agg()
                    tel.count("publish_failures_total")
                    if not fut.done():
                        fut.set_exception(n)
                    continue
                if live is not None and span is not None and st is not None:
                    if bspan is not None:
                        span.merge(bspan)
                    st.finish_span(span)
                    # shadow-oracle audit of exactly what was served:
                    # the matched filter set + the (filter, dests)
                    # pairs, stamped with the begin generation so churn
                    # mid-flight skips rather than false-positives
                    key, pairs = meta[idx]
                    st.capture_audit(
                        live.topic, key, pairs, pending.gen,
                        span.trace_id,
                    )
                if fut is pend_fut:
                    pend_total += n
                    pend_k += 1
                else:
                    _flush_agg()
                    pend_fut = fut
                    pend_total = n
                    pend_k = 1
            _flush_agg()
            if otel is not None:
                self._otel_finish(otel, entries, results)
            self._batch_done(len(entries))
        finally:
            mark.leave(prev_stage)
            self._gc_resume(gc_tok)

    def _batch_done(self, n_pubs: int) -> None:
        self._inflight_pubs -= n_pubs
        if self._waiters:
            self._pump_waiters()
        else:
            self._maybe_clear_overload()

    # --- external tracer (obs/otel.py) ------------------------------------
    # A batch with broker.tracer set gives each publish the spans the
    # synchronous host publish gives it: an mqtt.publish root opened at
    # the flush, broker.route from the match begin to its finish, and
    # broker.dispatch over the window dispatch. `otel` is (tracer,
    # [[root, child span or None]] in entry order).

    @staticmethod
    def _otel_route(tr, roots, entries) -> tuple:
        pairs = []
        for root, (live, _fut, _span) in zip(roots, entries):
            if live is None:  # a publish hook dropped it
                root.set("mqtt.dropped", True)
                pairs.append([root, None])
            else:
                pairs.append(
                    [root, tr.start_span("broker.route", root.trace_id, root)]
                )
        return tr, pairs

    @staticmethod
    def _otel_dispatch(otel, entries, filter_lists) -> None:
        tr, pairs = otel
        flts = iter(filter_lists)
        for pair, (live, _fut, _span) in zip(pairs, entries):
            root, rs = pair
            if rs is None:
                continue
            rs.set("broker.matched_filters", len(next(flts)))
            tr.finish(rs)
            pair[1] = tr.start_span("broker.dispatch", root.trace_id, root)
            live.headers["trace_root"] = root  # cluster leg parents here

    @staticmethod
    def _otel_finish(otel, entries, results) -> None:
        tr, pairs = otel
        for (root, sp), (live, _fut, _span), n in zip(pairs, entries, results):
            if live is not None:
                live.headers.pop("trace_root", None)
            if isinstance(n, BaseException):
                root.set("error", repr(n))
            elif sp is not None:
                sp.set("broker.deliveries", n)
                root.set("mqtt.deliveries", n)
            if sp is not None:
                tr.finish(sp)
            tr.finish(root)

    # --- ring slot timeline ------------------------------------------------

    def _ring_land(
        self, t_land: float, t_launch: float, mode: str, n_pubs: int
    ) -> None:
        """One ring slot landed: record its launch->land span (host
        clock) and stamp the timeline."""
        tel = self.telemetry
        self._ring_slots_total += 1
        tel.observe_family("ring_slot_span_seconds", t_land - t_launch)
        self._ring_timeline.append(
            {
                "launch": round(t_launch, 6),
                "land": round(t_land, 6),
                "span_ms": round((t_land - t_launch) * 1e3, 4),
                "mode": mode,
                "publishes": n_pubs,
            }
        )

    def ring_status(self) -> Dict:
        out = {
            "slots_total": self._ring_slots_total,
            "timeline": list(self._ring_timeline),
        }
        # mesh microscope: per-chip generalization of the ring ledger
        # (launch→land spans per serving chip + the stage decomposition)
        scope = getattr(
            getattr(self.broker.router, "device_table", None), "scope", None
        )
        if scope is not None:
            out["mesh_scope"] = scope.status()
        return out

    # --- circuit breaker (trip -> degrade -> probe -> resync -> close) ----

    def note_device_failure(self, exc: Optional[BaseException]) -> None:
        """Seam for device faults observed OUTSIDE the engine's own
        batches (the broker's synchronous match/fanout legs): they
        count toward the same breaker."""
        self._device_failure(exc)

    def note_device_success(self) -> None:
        """Sync-path counterpart: a successful device leg resets the
        consecutive-failure count, so sparse transient faults spread
        over hours can never accumulate into a spurious trip."""
        self._device_success()

    def _device_failure(self, exc: Optional[BaseException]) -> None:
        tel = self.telemetry
        tel.count("breaker_device_failures_total")
        if exc is not None:
            self.last_device_error = repr(exc)
        if not self.breaker_enabled:
            return
        shard = getattr(exc, "shard", None)
        if shard is not None:
            # chip-granular fault: per-shard ledger, whole-device
            # breaker untouched (the other shards are fine)
            n = self._shard_failures.get(shard, 0) + 1
            self._shard_failures[shard] = n
            if shard not in self._shard_open and n >= self.breaker_threshold:
                self._trip_shard(int(shard), exc)
            return
        self._consecutive_failures += 1
        tel.set_gauge(
            "breaker_consecutive_failures", self._consecutive_failures
        )
        if (
            self.breaker_state == "closed"
            and self._consecutive_failures >= self.breaker_threshold
        ):
            self._trip_breaker(exc)

    def _device_success(self) -> None:
        if self._consecutive_failures:
            self._consecutive_failures = 0
            self.telemetry.set_gauge("breaker_consecutive_failures", 0)
        # a clean mesh-wide dispatch clears the ledgers of shards that
        # have NOT tripped (sparse transients can't accumulate); open
        # shards stay open — their probe loop owns recovery
        if self._shard_failures:
            for s in list(self._shard_failures):
                if s not in self._shard_open:
                    del self._shard_failures[s]

    def _set_state(self, state: str) -> None:
        self.breaker_state = state
        self.telemetry.set_gauge("breaker_state", _STATE_GAUGE[state])

    def _trip_breaker(self, exc: Optional[BaseException]) -> None:
        """closed -> open: all traffic host-side (degraded-but-
        correct), alarm raised, flight bundle frozen, probe armed."""
        tel = self.telemetry
        self._set_state("open")
        self.router.suspend_device()
        tel.count("breaker_trips_total")
        details = {
            "consecutive_failures": self._consecutive_failures,
            "threshold": self.breaker_threshold,
            "last_error": self.last_device_error,
        }
        log.error(
            "device breaker TRIPPED after %d consecutive failures "
            "(last: %s) — all publish traffic degraded to the host "
            "walk; canary probe armed",
            self._consecutive_failures, self.last_device_error,
        )
        alarms = self._get_alarms()
        if alarms is not None:
            try:
                alarms.ensure(
                    ALARM_BREAKER,
                    details=details,
                    message="XLA device breaker open: publish path "
                            "degraded to host walk",
                )
            except Exception:
                tel.count("breaker_alarm_failures_total")
                log.exception("breaker alarm failed")
        fl = self._get_flight()
        if fl is not None:
            fl.recorder.record("breaker.trip", "", details)
            fl.maybe_trigger("device_breaker_trip", details)
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            # no loop (offline/bench sync path): recovery happens on
            # the next probe_once() a caller drives explicitly
            return
        t = loop.create_task(self._probe_loop())
        self._probe_task = t
        t.add_done_callback(self._probe_done)

    def _probe_done(self, task: "asyncio.Task") -> None:
        if self._probe_task is task:
            self._probe_task = None
        if not task.cancelled() and task.exception() is not None:
            self.telemetry.count("breaker_probe_crashes_total")
            log.error(
                "breaker probe loop died", exc_info=task.exception()
            )

    async def _probe_loop(self) -> None:
        """Bounded-exponential-backoff canary: re-dispatch a sentinel
        batch through the real kernels; on success, full clean resync
        then a VERIFIED canary before closing."""
        backoff = self.probe_backoff_s
        while not self.closed and self.breaker_state == "open":
            await asyncio.sleep(backoff)
            backoff = min(backoff * 2.0, self.probe_backoff_max_s)
            if self.closed or self.breaker_state != "open":
                return
            if self.probe_once():
                return

    def probe_once(self) -> bool:
        """One canary attempt (also the offline/bench entry): link
        canary -> full state resync -> oracle-verified canary ->
        close. Returns True when the breaker closed."""
        tel = self.telemetry
        router = self.router
        tel.count("breaker_probe_total")
        self._set_state("half_open")
        topics = list(self._recent_topics) or ["$breaker/canary"]
        try:
            # step 1: does the link dispatch at all? (stale state OK)
            router.canary_match(topics)
            # step 2: the outage dropped the delta stream — re-upload
            # FULL device state from host truth (quarantine clean-sync
            # machinery), then verify the device answers the oracle
            router.device_resync()
            served = router.canary_match(topics)
            oracle = [sorted(router.match_filters(t)) for t in topics]
            if [sorted(x) for x in served] != oracle:
                raise RuntimeError(
                    "post-resync canary diverged from host oracle"
                )
        except Exception as e:
            tel.count("breaker_probe_failures_total")
            self.last_device_error = repr(e)
            self._set_state("open")
            return False
        self._close_breaker(topics)
        return True

    def _close_breaker(self, canary_topics) -> None:
        tel = self.telemetry
        self._consecutive_failures = 0
        tel.set_gauge("breaker_consecutive_failures", 0)
        self._set_state("closed")
        self.router.resume_device()
        tel.count("breaker_recoveries_total")
        log.warning(
            "device breaker CLOSED: full state re-uploaded, canary "
            "verified against host oracle on %d topics",
            len(canary_topics),
        )
        alarms = self._get_alarms()
        if alarms is not None:
            alarms.ensure_deactivated(ALARM_BREAKER)
        fl = self._get_flight()
        if fl is not None:
            fl.recorder.record(
                "breaker.close", "", {"canary_topics": len(canary_topics)}
            )

    # --- shard breaker (chip-granular failure domain) ---------------------

    @property
    def open_shards(self) -> Set[int]:
        return set(self._shard_open)

    def _trip_shard(self, shard: int, exc: Optional[BaseException]) -> None:
        """One chip crossed the threshold: suspend ONLY its slice
        (host overlay), then evacuate its row/bucket range onto the
        survivor mesh so service returns to full device speed at N-1,
        and arm a per-shard recovery probe. The whole-device breaker
        stays closed — the other chips never stop serving."""
        tel = self.telemetry
        self._shard_open.add(shard)
        tel.count("breaker_shard_trips_total")
        tel.set_gauge("breaker_open_shards", len(self._shard_open))
        self.router.suspend_shard(shard)
        details = {
            "shard": shard,
            "failures": self._shard_failures.get(shard, 0),
            "threshold": self.breaker_threshold,
            "last_error": self.last_device_error,
        }
        log.error(
            "shard breaker TRIPPED for shard %d (last: %s) — slice "
            "host-overlaid, evacuating onto survivor mesh",
            shard, self.last_device_error,
        )
        alarms = self._get_alarms()
        if alarms is not None:
            try:
                alarms.ensure(
                    ALARM_BREAKER,
                    details=details,
                    message=f"XLA shard breaker open: shard {shard} "
                            "slice degraded, evacuating",
                )
            except Exception:
                tel.count("breaker_alarm_failures_total")
                log.exception("shard breaker alarm failed")
        fl = self._get_flight()
        if fl is not None:
            fl.recorder.record("breaker.shard_trip", "", details)
            fl.maybe_trigger("device_breaker_trip", details)
        try:
            # live evacuation: re-shard over survivors + full re-upload
            # from host truth; on failure the host overlay stays as the
            # degraded-but-correct fallback until the probe heals it
            if self.router.evacuate_shard(shard):
                tel.count("breaker_shard_evacuations_total")
                # recompile the survivor-mesh kernel shapes off the
                # deadline-gated serving path
                self.router.warmup_shapes(max_batch=64)
        except Exception:
            tel.count("breaker_shard_evacuation_failures_total")
            log.exception(
                "shard %d evacuation failed; slice stays host-overlaid",
                shard,
            )
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return  # offline path: caller drives probe_shard_once()
        t = loop.create_task(self._shard_probe_loop(shard))
        self._shard_probe_tasks[shard] = t
        t.add_done_callback(
            lambda task, s=shard: self._shard_probe_done(s, task)
        )

    def _shard_probe_done(self, shard: int, task: "asyncio.Task") -> None:
        if self._shard_probe_tasks.get(shard) is task:
            del self._shard_probe_tasks[shard]
        if not task.cancelled() and task.exception() is not None:
            self.telemetry.count("breaker_probe_crashes_total")
            log.error(
                "shard %d probe loop died", shard,
                exc_info=task.exception(),
            )

    async def _shard_probe_loop(self, shard: int) -> None:
        backoff = self.probe_backoff_s
        while not self.closed and shard in self._shard_open:
            await asyncio.sleep(backoff)
            backoff = min(backoff * 2.0, self.probe_backoff_max_s)
            if self.closed or shard not in self._shard_open:
                return
            if self.probe_shard_once(shard):
                return

    def probe_shard_once(self, shard: int) -> bool:
        """One recovery attempt for an evacuated chip: direct link
        probe -> rebalance back to the full mesh (full state re-upload)
        -> oracle-verified canary -> close. On canary divergence the
        chip is re-evacuated — it re-earns trust, never gets it."""
        tel = self.telemetry
        router = self.router
        tel.count("breaker_probe_total")
        topics = list(self._recent_topics) or ["$breaker/canary"]
        try:
            # step 1: is the chip's link back? (raises while sticky)
            router.probe_shard(shard)
            # step 2: rebalance back to N and verify against the oracle
            router.rebalance_shard(shard)
            served = router.canary_match(topics)
            oracle = [sorted(router.match_filters(t)) for t in topics]
            if [sorted(x) for x in served] != oracle:
                raise RuntimeError(
                    f"post-rebalance canary diverged on shard {shard}"
                )
        except Exception as e:
            tel.count("breaker_probe_failures_total")
            self.last_device_error = repr(e)
            dt = router.device_table
            if shard not in getattr(dt, "lost_shards", set()):
                # rebalance half-landed or canary diverged: evacuate
                # again so serving stays on the verified survivor mesh
                with contextlib.suppress(Exception):
                    router.evacuate_shard(shard)
            return False
        self._close_shard(shard, topics)
        return True

    def _close_shard(self, shard: int, canary_topics) -> None:
        tel = self.telemetry
        self._shard_open.discard(shard)
        self._shard_failures.pop(shard, None)
        tel.set_gauge("breaker_open_shards", len(self._shard_open))
        tel.count("breaker_shard_recoveries_total")
        log.warning(
            "shard breaker CLOSED for shard %d: rebalanced back to "
            "full mesh, canary verified on %d topics",
            shard, len(canary_topics),
        )
        if not self._shard_open and self.breaker_state == "closed":
            alarms = self._get_alarms()
            if alarms is not None:
                alarms.ensure_deactivated(ALARM_BREAKER)
        fl = self._get_flight()
        if fl is not None:
            fl.recorder.record(
                "breaker.shard_close", "",
                {"shard": shard, "canary_topics": len(canary_topics)},
            )

    # --- lifecycle --------------------------------------------------------

    async def drain(self) -> None:
        """Flush the open batch, admit + serve every blocked waiter,
        and collect everything in flight."""
        while self._queue or self._inflight or self._waiters or self._rewarm:
            if self._rewarm is not None:
                # its landing callback flushes what queued behind it
                await asyncio.wait({self._rewarm})
                await asyncio.sleep(0)
                continue
            if self._waiters:
                self._pump_waiters()
            if self._queue:
                self._flush()
            while self._inflight:
                self._collect_one()
            if not (self._queue or self._waiters or self._rewarm):
                break
        await asyncio.sleep(0)  # let resolved futures' awaiters run

    async def stop(self, drain: bool = True) -> None:
        """Stop the engine. drain=True (default) completes everything
        first; drain=False is the abort path: in-flight batches still
        complete (their kernels already launched), but queued and
        blocked publishers fail deterministically with EngineStopped —
        never a silent hang."""
        if self.closed:
            return
        if drain:
            await self.drain()
        self.closed = True
        if self._rewarm is not None:
            # the worker thread is using the device: let it finish
            await asyncio.wait({self._rewarm})
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None
        if self._waiter_timer is not None:
            self._waiter_timer.cancel()
            self._waiter_timer = None
        while self._inflight:
            self._collect_one()
        aborted = 0
        err = EngineStopped("dispatch engine stopped")
        for _msg, fut, _t, _span in self._queue:
            if not fut.done():
                fut.set_exception(err)
                aborted += 1
        self._queue = []
        while self._waiters:
            _msg, fut, _t, _span = self._waiters.popleft()
            if not fut.done():
                fut.set_exception(err)
                aborted += 1
        if aborted:
            self.telemetry.count("queue_aborted_total", aborted)
        if self._probe_task is not None:
            self._probe_task.cancel()
            with contextlib.suppress(Exception, asyncio.CancelledError):
                await self._probe_task
            self._probe_task = None
        for t in list(self._shard_probe_tasks.values()):
            t.cancel()
            with contextlib.suppress(Exception, asyncio.CancelledError):
                await t
        self._shard_probe_tasks.clear()
        if self.gc_guard and self.warmed:
            # hand the frozen steady state back to the collector —
            # a stopped engine's broker graph must stay reclaimable
            gc.unfreeze()
        await asyncio.sleep(0)

    def status(self) -> dict:
        cache = self.router.match_cache
        counters = getattr(self.telemetry, "counters", {})
        return {
            "queue_depth": self.queue_depth,
            "deadline_ms": self.deadline_s * 1e3,
            "pipeline_depth": self.pipeline_depth,
            "queued": len(self._queue),
            "inflight": len(self._inflight),
            "batches_total": self.batches_total,
            "publishes_total": self.publishes_total,
            "coalesce_factor": round(
                self.publishes_total / self.batches_total, 3
            ) if self.batches_total else 0.0,
            "breaker": {
                "enabled": self.breaker_enabled,
                "state": self.breaker_state,
                "threshold": self.breaker_threshold,
                "consecutive_failures": self._consecutive_failures,
                "deadline_ms": self.breaker_deadline_s * 1e3,
                "trips": counters.get("breaker_trips_total", 0),
                "recoveries": counters.get("breaker_recoveries_total", 0),
                "fallback_publishes": counters.get(
                    "breaker_fallback_total", 0
                ),
                "degraded_batches": counters.get(
                    "breaker_degraded_batches_total", 0
                ),
                "probes": counters.get("breaker_probe_total", 0),
                "probe_failures": counters.get(
                    "breaker_probe_failures_total", 0
                ),
                "last_device_error": self.last_device_error,
            },
            "shard_breaker": {
                "open_shards": sorted(self._shard_open),
                "failures": dict(sorted(self._shard_failures.items())),
                "lost_shards": sorted(
                    getattr(self.router.device_table, "lost_shards", ())
                ),
                "shard_gen": getattr(
                    self.router.device_table, "shard_gen", 0
                ),
                "trips": counters.get("breaker_shard_trips_total", 0),
                "evacuations": counters.get(
                    "breaker_shard_evacuations_total", 0
                ),
                "recoveries": counters.get(
                    "breaker_shard_recoveries_total", 0
                ),
            },
            "admission": {
                "max_depth": self.queue_max_depth,
                "low_watermark": self.queue_low_watermark,
                "policy": self.queue_policy,
                "queue_deadline_ms": self.queue_deadline_s * 1e3,
                "outstanding": self.outstanding(),
                "waiters": len(self._waiters),
                "overloaded": self._overloaded,
                "shed": counters.get("queue_shed_total", 0),
                "blocked": counters.get("queue_blocked_total", 0),
                "deadline_expired": counters.get(
                    "queue_deadline_expired_total", 0
                ),
            },
            "match_cache": None if cache is None else {
                "capacity": cache.capacity,
                "entries": len(cache),
                "hits": cache.hits,
                "misses": cache.misses,
                "evictions": cache.evictions,
                "hit_ratio": round(cache.hit_ratio(), 6),
            },
            "ring": self.ring_status(),
        }
