"""One run of one cell: the broker as its users run it, driven from outside.

Set-up (all of it counted in `setup_s`):
  1. `make -C native` (a no-op when the objects are fresh);
  2. `emqx_tpu.boot.Node` from etc/emqx.conf, with only the overrides the
     configuration file lists (ephemeral ports, a data dir);
  3. the configuration's table, generated from the seed and subscribed
     through `Broker.subscribe` over in-process sessions;
  4. the load generator processes (new interpreters, no JAX) connect their
     socket subscribers and publishers through the MQTT TCP listener;
  5. warm-up traffic until the engine's own re-warm of the grown table has
     landed (`warmup_info["rewarms"]`), `Router.trie_backlog()` is 0 and
     deliveries flow, then `warm_settle_s` more seconds of it.
Then the window: `--seconds` of the mix's traffic, timed on
CLOCK_MONOTONIC, which the generator processes share. With `--trace 1`
a `jax.profiler` trace covers the window. After it, every answer due is
awaited, the program is stopped, and the reference judges every publish.
"""

from __future__ import annotations

import asyncio
import gc
import importlib.machinery
import importlib.util
import json
import os
import resource
import shutil
import struct
import subprocess
import sys
import threading
import time
import traceback
from array import array
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (BENCH_DIR, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import reference as ref  # noqa: E402
import table as tbl  # noqa: E402

RUN_DIR = os.path.join(ROOT, ".bench_run")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
LOADGEN = os.path.join(BENCH_DIR, "loadgen.py")
WINDOW_BITS = 40  # message id = window number << 40 | index in the window
LEAD_S = 0.3  # the window starts this long after it is announced
WARM_TIMEOUT_S = 600.0  # warm-up may wait on a cold re-warm compile
DRAIN_S = 60.0  # answers due in the window are awaited this long
mono_ns = time.monotonic_ns


class RunError(RuntimeError):
    pass


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RunError(what)


# --- set-up steps -------------------------------------------------------------


def _make(*args: str) -> None:
    r = subprocess.run(
        ["make", "-C", os.path.join(ROOT, "native"), *args],
        capture_output=True, text=True, timeout=600,
    )
    check(r.returncode == 0, f"make -C native {' '.join(args)} failed:\n"
          f"{r.stderr[-4000:]}")


def _imports(name: str, path: str) -> bool:
    """Whether this interpreter can load the extension at `path` (an
    object built for another Python's ABI cannot)."""
    try:
        loader = importlib.machinery.ExtensionFileLoader(name, path)
        spec = importlib.util.spec_from_file_location(name, path, loader=loader)
        importlib.util.module_from_spec(spec)
    except ImportError:
        return False
    return True


def build_native() -> float:
    """Build native/ where its objects are missing or stale: older than
    their sources, or built for another Python."""
    t0 = time.monotonic()
    _make()
    native = os.path.join(ROOT, "native")
    for so in sorted(os.listdir(native)):
        if so.startswith("_emqx_") and so.endswith(".so"):
            if not _imports(so[:-3], os.path.join(native, so)):
                _make("-B", so)
    from emqx_tpu import framec, jsonc
    from emqx_tpu.ds import kvstore
    from emqx_tpu.ops import speedups

    for name, mod in (
        ("speedups", speedups.load()), ("frame", framec.load()),
        ("json", jsonc.load()), ("kvstore", kvstore._LIB),
    ):
        check(mod is not None, f"native {name} extension did not load")
    return time.monotonic() - t0


def device_info(platform: Optional[str], chips: int) -> dict:
    """The devices as JAX reports them; refuses a run without `chips`
    devices of `platform` (None: whatever JAX found, for rehearsals)."""
    import jax

    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }
    if platform is not None:
        check(info["platform"] == platform,
              f"no {platform.upper()}: JAX found {info['platform']}")
        check(info["count"] >= chips,
              f"the cell needs {chips} {platform} chips, JAX found {info['count']}")
    return info


def enable_compile_cache() -> str:
    """JAX's persistent cache at the checkout's fixed `.jax_cache`, the
    directory emqx_tpu/compile_cache.py uses when none is given."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from emqx_tpu import compile_cache

    return compile_cache.enable()


def raise_fd_limit() -> int:
    """Lift the soft open-file limit to the hard one: every publisher
    connection is a socket on both sides of the loopback."""
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft < hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    return hard


def node_overrides(conf: dict, data_dir: str) -> dict:
    over = json.loads(json.dumps(conf["node_overrides"]))
    over.setdefault("node", {})["data_dir"] = data_dir
    return over


class LoopStalls:
    """The event loop's worst lateness: a task asks to wake every 10 ms
    and keeps how late it woke; the full (generation 2) garbage
    collections, a known cause of such stalls, as (end time, seconds)
    (both copied from chip_smoke.py); and, for the log, where the loop
    thread was when it had not woken the ticker for 100 ms (a sampler
    thread reads its stack)."""

    TICK_S = 0.01
    SAMPLE_S = 0.1

    def __init__(self):
        self.worst_s = 0.0
        self.gc_full: List[tuple] = []
        self._gc_t0 = 0.0
        self.beat = time.monotonic()
        self.stacks: Dict[str, int] = {}
        self._stop = threading.Event()
        self._loop_thread = threading.get_ident()
        gc.callbacks.append(self._on_gc)
        self.task = asyncio.ensure_future(self._run())
        self._sampler = threading.Thread(target=self._sample, daemon=True)
        self._sampler.start()

    def _sample(self) -> None:
        while not self._stop.wait(self.SAMPLE_S / 2):
            if time.monotonic() - self.beat < self.SAMPLE_S:
                continue
            frame = sys._current_frames().get(self._loop_thread)
            if frame is None:
                continue
            where = " <- ".join(
                f"{os.path.basename(f.filename)}:{f.lineno}:{f.name}"
                for f in reversed(traceback.extract_stack(frame)[-6:])
            )
            self.stacks[where] = self.stacks.get(where, 0) + 1

    def top(self, n: int = 3) -> List[str]:
        return [
            f"{k} x{v}"
            for k, v in sorted(self.stacks.items(), key=lambda kv: -kv[1])[:n]
        ]

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        now = time.monotonic()
        if phase == "start":
            self._gc_t0 = now
        else:
            self.gc_full.append((now, now - self._gc_t0))

    async def _run(self) -> None:
        while True:
            t0 = self.beat = time.monotonic()
            await asyncio.sleep(self.TICK_S)
            self.worst_s = max(self.worst_s, time.monotonic() - t0 - self.TICK_S)

    def take(self) -> float:
        w, self.worst_s = self.worst_s, 0.0
        self.stacks = {}
        return w

    def stop(self) -> None:
        self.task.cancel()
        self._stop.set()
        self._sampler.join(timeout=5)
        if self._on_gc in gc.callbacks:
            gc.callbacks.remove(self._on_gc)


class Gen:
    """One load generator process and its line protocol."""

    def __init__(self, role: str, spec: dict):
        self.role = role
        self.path = os.path.join(RUN_DIR, f"{role}.json")
        spec = dict(spec, role=role, out=os.path.join(RUN_DIR, role))
        with open(self.path, "w") as f:
            json.dump(spec, f)
        self.out = spec["out"]
        self.proc = None

    async def start(self) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        self.proc = await asyncio.create_subprocess_exec(
            sys.executable, LOADGEN, self.path,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            env=env,
        )

    async def expect(self, word: str, timeout: float) -> List[str]:
        try:
            line = await asyncio.wait_for(self.proc.stdout.readline(), timeout)
        except asyncio.TimeoutError:
            raise RunError(f"loadgen {self.role}: no '{word}' in {timeout:.0f}s")
        parts = line.decode().split()
        check(bool(parts) and parts[0] == word,
              f"loadgen {self.role} said {line!r}, expected {word!r}")
        return parts[1:]

    def send(self, line: str) -> None:
        self.proc.stdin.write((line + "\n").encode())

    async def close(self) -> None:
        if self.proc is None or self.proc.returncode is not None:
            return
        try:
            self.send("quit")
            await asyncio.wait_for(self.proc.wait(), 10)
        except (asyncio.TimeoutError, ConnectionError):
            self.proc.kill()
            await self.proc.wait()


class Sink:
    """Deliveries to the in-process sessions: client, message id and
    arrival time, recorded on the broker's loop as they happen."""

    def __init__(self):
        self.client = array("q")
        self.msg = array("q")
        self.at = array("q")
        self.warm = 0

    def for_session(self, k: int):
        client, msg, at = self.client, self.msg, self.at
        unpack = struct.unpack_from

        def sink(pkts) -> None:
            now = mono_ns()
            for p in pkts:
                (m,) = unpack("<Q", p.payload)
                client.append(k)
                msg.append(m)
                at.append(now)
                if m & tbl.WARM_BIT:
                    self.warm += 1

        return sink


def _snap(node, eng) -> dict:
    tel = node.broker.router.telemetry
    out = {
        "counters": dict(tel.counters),
        "publishes": eng.publishes_total,
        "batches": eng.batches_total,
        "t_ns": mono_ns(),
    }
    h = tel.family_hist.get("pipeline_queue_wait_seconds")
    out["queue_wait"] = (h.sum, h.total) if h is not None else (0.0, 0)
    out["legs"] = {leg: (h.sum, h.total) for leg, h in tel.hist.items()}
    return out


def _delta(a: dict, b: dict) -> dict:
    keys = set(a["counters"]) | set(b["counters"])
    legs = set(a["legs"]) | set(b["legs"])
    return {
        "counters": {
            k: b["counters"].get(k, 0) - a["counters"].get(k, 0) for k in keys
        },
        "publishes": b["publishes"] - a["publishes"],
        "batches": b["batches"] - a["batches"],
        "queue_wait": (
            b["queue_wait"][0] - a["queue_wait"][0],
            b["queue_wait"][1] - a["queue_wait"][1],
        ),
        "legs": {
            k: (
                b["legs"].get(k, (0.0, 0))[0] - a["legs"].get(k, (0.0, 0))[0],
                b["legs"].get(k, (0, 0))[1] - a["legs"].get(k, (0, 0))[1],
            )
            for k in legs
        },
        "seconds": (b["t_ns"] - a["t_ns"]) / 1e9,
    }


class Window:
    """What one measured window left behind."""

    def __init__(self, number: int, t_ns: int, end_ns: int, rate: float):
        self.number = number
        self.t_ns = t_ns
        self.end_ns = end_ns
        self.rate = rate
        self.delta: dict = {}
        self.loop_stall_s = 0.0
        self.pubs: Dict[str, np.ndarray] = {}
        self.trace = None
        self.outstanding_end = 0
        self.gc_full: List[float] = []
        self.stall_stacks: List[str] = []


class Run:
    """Set-up, windows and teardown of one cell in one process."""

    def __init__(self, cell, seed: int, seconds: float, *, t_start: float,
                 platform: Optional[str] = "tpu", trace: bool = False,
                 native: bool = True):
        self.cell = cell
        self.conf = cell.conf
        self.traffic = cell.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.t_start = t_start
        self.platform = platform
        self.trace = trace
        self.native = native
        self.table = tbl.Table(self.conf, self.seed)
        self.sink = Sink()
        self.node = None
        self.gens: List[Gen] = []
        self.windows: List[Window] = []
        self.stalls = None
        self.device: dict = {}
        self.timings: Dict[str, float] = {}

    # set-up -----------------------------------------------------------------

    async def setup(self) -> None:
        os.makedirs(RUN_DIR, exist_ok=True)
        raise_fd_limit()
        self.device = device_info(self.platform, self.cell.chips)
        log(f"device: {json.dumps(self.device)}")
        if self.native:
            self.timings["native_s"] = build_native()
        if self.platform is not None:
            log(f"compile cache: {enable_compile_cache()}")
        from emqx_tpu.boot import Node

        data_dir = os.path.join(RUN_DIR, "node")
        shutil.rmtree(data_dir, ignore_errors=True)
        t = time.monotonic()
        self.node = Node(
            config_files=[os.path.join(ROOT, "etc", "emqx.conf")],
            config_text=json.dumps(node_overrides(self.conf, data_dir)),
        )
        await self.node.start()
        self.eng = self.node.broker.engine
        check(self.eng is not None and self.eng.warmed, "dispatch engine not running")
        self.router = self.node.broker.router
        self.boot_key = self.router.shape_key()
        self.timings["boot_s"] = time.monotonic() - t
        t = time.monotonic()
        self.filters = self.table.filters()
        self.socket_filters = self.table.socket_filters()
        await self._subscribe()
        self.timings["subscribe_s"] = time.monotonic() - t
        self.stalls = LoopStalls()
        t = time.monotonic()
        port = self.node.listeners.get("tcp", "default").listen_addr[1]
        base = {
            "port": port, "seed": self.seed, "seconds": self.seconds,
            "conf": self.conf, "traffic": self.traffic,
        }
        sub = Gen("sub", dict(base, filters=self.socket_filters))
        pub = Gen("pub", base)
        self.gens = [sub, pub]
        for g in self.gens:
            await g.start()
            (connect_s,) = await g.expect("ready", 600)
            log(f"loadgen {g.role}: connected in {float(connect_s):.3f}s")
        self.sub, self.pub = sub, pub
        self.timings["connect_s"] = time.monotonic() - t
        t = time.monotonic()
        await self._warm_up()
        self.timings["warm_s"] = time.monotonic() - t
        log("set-up: " + json.dumps({k: round(v, 3) for k, v in self.timings.items()}))

    async def _subscribe(self) -> None:
        from emqx_tpu.broker.packet import SubOpts
        from emqx_tpu.broker.session import SessionConfig

        b = self.node.broker
        n_sess = int(self.conf["sessions"])
        cfg = SessionConfig(
            session_expiry_interval=3600.0, max_mqueue_len=16,
            mqueue_store_qos0=False, durable=False,
        )
        opts = SubOpts(qos=int(self.conf["sub_qos"]))
        filters = self.filters
        for k in range(n_sess):
            sess, _ = b.open_session(f"fleet{k}", clean_start=True, cfg=cfg)
            sess.outgoing_sink = self.sink.for_session(k)
            for i in range(k, len(filters), n_sess):
                b.subscribe(sess, filters[i], opts)
            if k % 64 == 63:
                await asyncio.sleep(0)

    async def _warm_up(self) -> None:
        eng, router = self.eng, self.router
        self.pub.send(f"warm {float(self.traffic.get('rate', 0))}")
        deadline = time.monotonic() + WARM_TIMEOUT_S
        grown = router.shape_key() != self.boot_key
        while True:
            check(time.monotonic() < deadline, "warm-up did not settle")
            landed = not grown or eng.warmup_info.get("rewarms", 0) >= 1
            if landed and router.trie_backlog() == 0 and self.sink.warm > 0:
                break
            await asyncio.sleep(0.05)
        info = eng.warmup_info
        log(
            f"warm-up: re-warm {info.get('rewarms', 0)} pass(es), "
            f"{info.get('rewarm_shapes', 0)} shapes in "
            f"{info.get('rewarm_seconds', 0.0):.3f}s; trie backlog 0"
        )
        await asyncio.sleep(float(self.traffic["warm_settle_s"]))

    # windows ----------------------------------------------------------------

    async def window(self, rate: Optional[float] = None) -> Window:
        """One measured window; `rate` overrides the mix's (a sweep)."""
        loop = asyncio.get_running_loop()
        rate = float(self.traffic.get("rate", 0) if rate is None else rate)
        number = len(self.windows) + 1
        if number > 1:
            self.pub.send(f"warm {rate}")
            await asyncio.sleep(float(self.traffic["warm_settle_s"]))
        trace_dir = None
        if self.trace:
            import jax

            trace_dir = os.path.join(RUN_DIR, "trace")
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0  # no per-call Python events
            opts.host_tracer_level = 2  # runtime events label idle gaps
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_ns = mono_ns() + int(LEAD_S * 1e9)
        w = Window(number, t_ns, t_ns + int(self.seconds * 1e9), rate)
        snaps = {}

        def mark(key: str) -> None:
            snaps[key] = _snap(self.node, self.eng)
            if key == "start":
                self.stalls.take()
            else:
                w.stall_stacks = self.stalls.top()
                w.loop_stall_s = self.stalls.take()
                w.outstanding_end = self.eng.outstanding()
                w.gc_full = [
                    d for end, d in self.stalls.gc_full if end >= t_ns / 1e9
                ]
            if self.trace:
                import jax

                with jax.profiler.TraceAnnotation(f"bench.window_{key}"):
                    pass

        self.pub.send(f"window {t_ns} {rate}")
        loop.call_at(t_ns / 1e9, mark, "start")
        loop.call_at(w.end_ns / 1e9, mark, "end")
        await asyncio.sleep(max(0.0, (w.end_ns - mono_ns()) / 1e9) + 0.05)
        (_n, outstanding) = await self.pub.expect("done", DRAIN_S + 60)
        log(f"window {number}: generator done, {outstanding} QoS 1 unanswered; "
            f"loop stall {w.loop_stall_s * 1e3:.1f} ms, full collections "
            f"{[round(d * 1e3, 1) for d in w.gc_full]} ms; node files written "
            f"in it: {self._written(w)}")
        for where in w.stall_stacks:
            log(f"  loop held >= {LoopStalls.SAMPLE_S * 1e3:.0f} ms at {where}")
        await self._settle()
        if self.trace:
            import jax

            jax.profiler.stop_trace()
            w.trace = trace_dir
        w.delta = _delta(snaps["start"], snaps["end"])
        with np.load(self.pub.out + ".npz") as z:
            w.pubs = {k: z[k] for k in z.files}
        self.windows.append(w)
        return w

    def _written(self, w: Window) -> List[str]:
        """Files the node wrote under its data dir during the window."""
        lo = time.time() - (mono_ns() - w.t_ns) / 1e9
        hi = lo + self.seconds
        out = []
        for d, _dirs, files in os.walk(os.path.join(RUN_DIR, "node")):
            for f in files:
                t = os.path.getmtime(os.path.join(d, f))
                if lo <= t <= hi:
                    out.append(f)
        return sorted(out)[:8]

    async def _settle(self) -> None:
        """Wait until the engine holds nothing and deliveries stopped."""
        deadline = time.monotonic() + DRAIN_S
        last, quiet_since = -1, time.monotonic()
        while time.monotonic() < deadline:
            n = len(self.sink.msg)
            if n != last or self.eng.outstanding():
                last, quiet_since = n, time.monotonic()
            elif time.monotonic() - quiet_since >= 0.5:
                return
            await asyncio.sleep(0.05)

    # teardown ---------------------------------------------------------------

    def memory_peak(self) -> int:
        import jax

        peak = 0
        for d in jax.local_devices()[: self.cell.chips]:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    async def teardown(self) -> None:
        """Stop the generators (collecting the subscribers' records) and
        the node, and free the program's state."""
        if self.stalls is not None:
            self.stalls.stop()
        sub = getattr(self, "sub", None)
        self.socket_dl = None
        if sub is not None and sub.proc is not None and sub.proc.returncode is None:
            try:
                sub.send("stop")
                await sub.expect("done", 60)
                with np.load(sub.out + ".npz") as z:
                    self.socket_dl = {k: z[k] for k in z.files}
            except (RunError, ConnectionError, OSError) as e:
                log(f"subscriber records lost: {e}")
        for g in self.gens:
            await g.close()
        if self.node is not None:
            await self.node.stop()
            eng = self.node.broker.engine
            if eng is not None:
                await eng.stop(drain=False)
        self.node = self.eng = self.router = None
        gc.collect()

    # judging ----------------------------------------------------------------

    def deliveries(self):
        """All deliveries: (client, msg, at); socket subscriber j is client
        sessions + j."""
        n_sess = int(self.conf["sessions"])
        c = [np.frombuffer(self.sink.client, np.int64)]
        m = [np.frombuffer(self.sink.msg, np.int64)]
        a = [np.frombuffer(self.sink.at, np.int64)]
        if self.socket_dl is not None:
            c.append(self.socket_dl["sub"] + n_sess)
            m.append(self.socket_dl["msg"])
            a.append(self.socket_dl["at"])
        return np.concatenate(c), np.concatenate(m), np.concatenate(a)

    def subs(self):
        n_sess = int(self.conf["sessions"])
        for i, f in enumerate(self.filters):
            yield self.table.holder(i), f
        for j, f in enumerate(self.socket_filters):
            yield n_sess + j, f

    def judge(self, w: Window, receivers_of) -> ref.Verdict:
        client, msg, at = self.deliveries()
        n_sess = int(self.conf["sessions"])
        mine = (msg >> WINDOW_BITS) == w.number
        delivered = ref.group_deliveries(msg[mine], client[mine])
        local = mine & (client < n_sess)
        local_done: Dict[int, int] = {}
        for m, t in zip(msg[local].tolist(), at[local].tolist()):
            if t > local_done.get(m, 0):
                local_done[m] = t
        counters = w.delta["counters"]
        v = ref.compare(
            w.pubs, self.table.topic, receivers_of, delivered, local_done,
            counters.get("queue_shed_total", 0),
        )
        v.device_path(counters)
        return v

    def latencies_ns(self, w: Window) -> np.ndarray:
        """Due time to arrival of every delivery of the window's publishes."""
        _client, msg, at = self.deliveries()
        mine = (msg >> WINDOW_BITS) == w.number
        due = w.pubs["due"]
        idx = msg[mine] & ((1 << WINDOW_BITS) - 1)
        return at[mine] - due[idx]

    def delivered_in(self, w: Window) -> int:
        """Deliveries, of any publish, that arrived inside the window."""
        _client, _msg, at = self.deliveries()
        return int(((at >= w.t_ns) & (at < w.end_ns)).sum())
