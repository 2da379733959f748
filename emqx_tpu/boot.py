"""Node boot orchestration — the emqx_machine analog.

The reference boots a sorted application list (gproc, esockd, ...,
emqx; apps/emqx_machine/src/emqx_machine_boot.erl:34-47), starts
autocluster, installs signal handlers, and tears everything down
through a terminator. Here `Node` wires every subsystem from one
checked config in dependency order:

    config -> broker(+caps/auth/modules/governance/durable) ->
    observability -> cluster(+DS replication) -> listeners ->
    gateways -> cluster links -> management API -> plugins

and stops them in reverse. `main()` is the release entry
(`python -m emqx_tpu.boot -c etc/emqx.conf`), with SIGINT/SIGTERM
triggering a graceful stop (emqx_machine_terminator analog).
"""

from __future__ import annotations

import asyncio
import logging
import os
import signal
from typing import List, Optional

log = logging.getLogger("emqx_tpu.boot")


class Node:
    def __init__(
        self,
        config_files: Optional[List[str]] = None,
        config_text: str = "",
    ):
        from .config.config import Config
        from .config.default_schema import broker_schema

        self.config = Config.load(
            broker_schema(), files=config_files or (), text=config_text
        )
        self.broker = None
        self.cluster_node = None
        self.listeners = None
        self.gateways = None
        self.mgmt = None
        self.obs = None
        self.auth = None
        self.durable_mgr = None
        self.durable_db = None
        self.replicator = None
        self.plugins = None
        self.chaos = None
        self.bridge_registry = None
        self.license = None
        self.ft = None
        self.telemetry = None
        self.links: list = []
        self.modules: list = []
        self._stopping = False

    # --- boot order ------------------------------------------------------

    async def start(self) -> None:
        cfg = self.config
        data_dir = cfg.get("node.data_dir")
        os.makedirs(data_dir, exist_ok=True)
        node_name = cfg.get("node.name")

        # 0. native speedups build at BOOT, not at the first subscribe
        # storm: load() compiles the extension on first call (up to
        # ~2min on a cold toolchain), which must never land inside the
        # route-write hot path of a live broker
        from .ops import speedups as _speedups

        _speedups.load()
        # before the first compile: the engine warmup below compiles
        # the whole shape ladder, which a warm cache reads back
        from . import compile_cache

        compile_cache.enable()

        # 1. broker core (+ caps from the mqtt zone config)
        from .broker.caps import MqttCaps
        from .cluster.node import ClusterBroker, ClusterNode
        from .models.retainer import PersistentRetainer

        mesh = None
        if cfg.get("parallel.enable"):
            # multi-chip route matching: shard the cuckoo match table
            # over a (dp, sub) jax mesh (SURVEY.md §7 stage 6). The
            # same Router code runs on 1 chip when disabled.
            import jax

            from .parallel.mesh import make_mesh

            n_dp = cfg.get("parallel.dp")
            n_sub = cfg.get("parallel.sub") or None
            devs = jax.devices()
            want = n_dp * n_sub if n_sub else len(devs)
            if want < 2 or len(devs) < want or want % n_dp:
                # a mesh that does not fit must stop the boot: serving
                # single-device instead would hide the missing chips
                raise RuntimeError(
                    f"parallel.enable needs a mesh of dp={n_dp} x "
                    f"sub={n_sub or 'all'} (>= 2 devices), found "
                    f"{len(devs)} {devs[0].platform} device(s)"
                )
            mesh = make_mesh(n_dp=n_dp, n_sub=n_sub, devices=devs[:want])
            log.info("parallel mesh: %s", dict(mesh.shape))
        broker = ClusterBroker(
            shared_strategy=cfg.get("broker.shared_subscription_strategy"),
            mesh=mesh,
            mesh_min_rows_per_shard=(
                cfg.get("broker.perf.tpu_mesh_min_rows_per_shard")
                if mesh is not None else 0
            ),
        )
        if mesh is not None and cfg.get("broker.perf.tpu_mesh_scope_enable"):
            # mesh microscope (obs/mesh_scope.py): per-dispatch stage
            # decomposition + collective-cost ledger. Attaches on the
            # device table's None-seam; disabled leaves the served
            # path at one attribute read per dispatch.
            from .obs.mesh_scope import MeshScope

            dt = broker.router.device_table
            if hasattr(dt, "scope"):
                dt.scope = MeshScope(
                    telemetry=broker.router.telemetry,
                    sample_n=cfg.get("broker.perf.tpu_mesh_scope_sample_n"),
                )
        broker.caps = MqttCaps(
            max_packet_size=cfg.get("mqtt.max_packet_size"),
            max_clientid_len=cfg.get("mqtt.max_clientid_len"),
            max_topic_levels=cfg.get("mqtt.max_topic_levels"),
            max_qos_allowed=cfg.get("mqtt.max_qos_allowed"),
            max_topic_alias=cfg.get("mqtt.max_topic_alias"),
            retain_available=cfg.get("mqtt.retain_available"),
            wildcard_subscription=cfg.get("mqtt.wildcard_subscription"),
            shared_subscription=cfg.get("mqtt.shared_subscription"),
            exclusive_subscription=cfg.get("mqtt.exclusive_subscription"),
        )
        if cfg.get("retainer.enable"):
            broker.retainer = PersistentRetainer(
                os.path.join(data_dir, "retained"),
                max_retained=cfg.get("retainer.max_retained_messages") or 1_000_000,
            )
        # publish hot path: the generation-stamped fanout-plan cap and
        # the pipelined micro-batching dispatch engine + match cache
        # (broker/dispatch_engine.py), gated on the TPU offload knob
        broker._fanout_cap = cfg.get("broker.perf.tpu_fanout_cache_size")
        broker._fanout_device = cfg.get("broker.perf.tpu_fanout_enable")
        broker._fanout_min_fan = cfg.get("broker.perf.tpu_fanout_min_fan")
        broker.router._churn_reserve = cfg.get(
            "broker.perf.tpu_churn_reserve"
        )
        if cfg.get("broker.perf.tpu_match_enable"):
            broker.enable_dispatch_engine(
                queue_depth=cfg.get("broker.perf.tpu_dispatch_queue_depth"),
                deadline_ms=cfg.get("broker.perf.tpu_dispatch_deadline_ms"),
                pipeline_depth=cfg.get("broker.perf.tpu_pipeline_depth"),
                match_cache_size=cfg.get("broker.perf.tpu_match_cache_size"),
                # device failure domain: breaker + admission control
                breaker_enable=cfg.get("broker.perf.tpu_breaker_enable"),
                breaker_threshold=cfg.get(
                    "broker.perf.tpu_breaker_threshold"
                ),
                breaker_deadline_ms=cfg.get(
                    "broker.perf.tpu_breaker_deadline_ms"
                ),
                probe_backoff_ms=cfg.get(
                    "broker.perf.tpu_breaker_probe_backoff_ms"
                ),
                probe_backoff_max_ms=cfg.get(
                    "broker.perf.tpu_breaker_probe_backoff_max_ms"
                ),
                queue_max_depth=cfg.get("broker.perf.tpu_queue_max_depth"),
                queue_policy=cfg.get("broker.perf.tpu_queue_policy"),
                queue_deadline_ms=cfg.get(
                    "broker.perf.tpu_queue_deadline_ms"
                ),
                queue_low_watermark=cfg.get(
                    "broker.perf.tpu_queue_low_watermark"
                ),
                # transfer-pipelined dispatch: chunk sizing + AOT
                # shape warmup + GC discipline (ISSUE 9)
                transfer_chunk_kb=cfg.get(
                    "broker.perf.tpu_transfer_chunk_kb"
                ),
                aot_warm=cfg.get("broker.perf.tpu_aot_warm"),
                gc_guard=cfg.get("broker.perf.tpu_gc_guard"),
            )
            # serve-readiness pass: probe/size the transfer chunk,
            # pre-trace every kernel shape bucket, freeze steady
            # state out of the collector — after this, a retrace
            # counts as recompiles_at_serve_total
            broker.engine.warmup()
        # retained-match device leg: back the retainer with the cuckoo
        # index (the SUBSCRIBE-side inverse of routing); the host trie
        # walk stays the oracle and escalation path
        if getattr(broker, "retainer", None) is not None and cfg.get(
            "broker.perf.tpu_retained_enable"
        ):
            broker.retainer.enable_device(
                telemetry=getattr(broker.router, "telemetry", None),
                n_shards=cfg.get("broker.perf.tpu_retained_shards") or 1,
            )
        # JSON codec seam: flip the process-global native gate so every
        # rules/bridge/REST decode rides native/json.cc (stdlib replay
        # on any parity-risk kwargs or codec error)
        from .jsonc import set_native_enabled

        set_native_enabled(bool(cfg.get("broker.perf.json_native")))
        # wire-frame codec seam: same shape for the framec gate — every
        # transport serialize/parse rides native/frame.cc with the
        # Python codec replay on anything outside the native surface
        from .framec import set_native_enabled as set_frame_native

        set_frame_native(bool(cfg.get("broker.perf.frame_native")))
        # native delivery ledger: per-session inflight/packet-id/
        # overflow bookkeeping in the speedups.cc delivery_* legs
        from .broker.delivery import set_native_enabled as set_delivery_native

        set_delivery_native(bool(cfg.get("broker.perf.tpu_delivery_native")))
        self.broker = broker

        # 2. auth pipeline — chains/sources materialize from config
        # (emqx_authn_chains + emqx_authz source registration); an
        # unknown backend fails BOOT rather than running open
        from .auth.bridge import AuthPipeline
        from .auth.factory import provider_from_conf, source_from_conf
        from .auth.authn import GLOBAL_CHAIN

        authz_conf = cfg.get("authorization") or {}
        self.auth = AuthPipeline()
        self.auth.authz.no_match = authz_conf.get("no_match", "allow")
        for i, aconf in enumerate(cfg.get("authentication") or []):
            if aconf.get("enable", True) is False:
                continue
            provider = provider_from_conf(aconf)
            self.auth.authn.create_authenticator(
                GLOBAL_CHAIN,
                aconf.get("id", f"authn-{i}"),
                provider,
            )
        for sconf in authz_conf.get("sources") or []:
            if sconf.get("enable", True) is False:
                continue
            self.auth.authz.add_source(source_from_conf(sconf))
        self.auth.install(broker.hooks)

        # 3. feature modules
        from .modules import AutoSubscribe, DelayedPublish, TopicRewrite

        if cfg.get("delayed.enable"):
            d = DelayedPublish(
                broker, max_delayed_messages=cfg.get("delayed.max_delayed_messages")
            )
            d.enable()
            self.modules.append(d)
        rw_rules = cfg.get("rewrite")
        if rw_rules:
            rw = TopicRewrite(broker, rw_rules)
            rw.enable()
            self.modules.append(rw)
        auto_topics = cfg.get("auto_subscribe.topics")
        if auto_topics:
            a = AutoSubscribe(broker, auto_topics)
            a.enable()
            self.modules.append(a)

        # 3b. file transfer + telemetry
        self.ft = None
        if cfg.get("file_transfer.enable"):
            from .ft import FileTransfer

            self.ft = FileTransfer(
                broker,
                storage_dir=os.path.join(data_dir, "file_transfer"),
                max_file_size=cfg.get("file_transfer.max_file_size"),
                segments_ttl=cfg.get("file_transfer.segments_ttl") / 1000.0,
            )
            self.ft.enable()

            async def _ft_gc_loop():
                ttl = max(1.0, cfg.get("file_transfer.segments_ttl") / 1000.0)
                while True:
                    await asyncio.sleep(ttl)
                    try:
                        self.ft.gc()
                    except Exception:
                        log.exception("file-transfer gc failed")

            self._ft_gc_task = asyncio.ensure_future(_ft_gc_loop())
        self.telemetry = None
        if cfg.get("telemetry.enable"):
            from .mgmt.telemetry import Telemetry

            self.telemetry = Telemetry(broker, node_name=node_name)
            self.telemetry.start()

        # 4. rule engine
        from .rules.engine import RuleEngine

        self.rules = RuleEngine(
            broker, ignore_sys=cfg.get("rule_engine.ignore_sys_message")
        )
        # batched WHERE leg: compile the vectorizable predicate subset
        # to columnar mask evaluation over coalesced publish batches
        # (non-compilable predicates fall back to eval_expr per row)
        self.rules.batch_where_enabled = bool(
            cfg.get("broker.perf.tpu_rule_where_enable")
        )
        # hook the engine into 'message.publish' (also publishes the
        # rule_batcher handle the coalesced publish paths probe) —
        # without this a booted node's rules never see a publish
        self.rules.install(broker.hooks)
        from .bridges.bridge import BridgeRegistry

        self.bridge_registry = BridgeRegistry(broker, rules=self.rules)
        for rid, rconf in (cfg.get("rule_engine.rules") or {}).items():
            self.rules.create_rule(
                rid,
                rconf["sql"],
                rconf.get("actions") or [],
                enable=rconf.get("enable", True),
                description=rconf.get("description", ""),
            )

        # 5. durable sessions (+ storage)
        if cfg.get("durable_sessions.enable"):
            from .ds import Db
            from .ds.session_ds import DurableSessionManager

            ds_dir = cfg.get("durable_storage.messages.data_dir") or os.path.join(
                data_dir, "ds"
            )
            self.durable_db = Db(
                "messages",
                data_dir=ds_dir,
                n_shards=cfg.get("durable_storage.messages.n_shards"),
            )
            self.durable_mgr = DurableSessionManager(
                self.durable_db, state_dir=ds_dir
            )
            broker.enable_durable(self.durable_mgr)
            # boot-side crash recovery: the Db open above already
            # replayed every shard WAL (CRC-verified, torn tails cut)
            # and the manager resumed durable sessions at their
            # committed positions. Compact any bloated WAL now so the
            # NEXT restart's replay stays bounded, then surface what
            # recovery found.
            compacted = self.durable_db.maybe_compact()
            self.ds_recovery = {
                "db": self.durable_db.recovery_report(),
                "sessions": self.durable_mgr.recovery_report(),
                "compacted_shards": compacted,
            }
            rep = self.ds_recovery["db"]
            log.info(
                "durable tier recovered: %d shard(s) in %.1fms, "
                "%d session(s) resumed%s",
                len(rep["shards"]),
                rep["open_ms"],
                self.ds_recovery["sessions"]["sessions"],
                f", compacted {compacted}" if compacted else "",
            )

        # 6. observability ($SYS, alarms, traces, slow subs, prometheus)
        from .obs import Observability

        self.obs = Observability(
            broker,
            node_name=node_name,
            trace_dir=os.path.join(data_dir, "trace"),
            flight_dir=os.path.join(data_dir, "flight"),
            config=cfg,
        )
        self.obs.start(cfg.get("sys_topics.sys_heartbeat_interval") / 1000.0)
        if self.obs.sentinel is not None:
            st = self.obs.sentinel
            log.info(
                "publish sentinel attached: audit 1/%s%s, slo publish "
                "p99 %sms",
                st.sample_n or "off",
                " +quarantine" if st.quarantine_enabled else "",
                st.slo_publish_ms,
            )

        # 6b. durable-tier failure domain: a shard fail-stop (failed
        # fsync / ENOSPC / EIO) raises the ds_shard_failed alarm and
        # snapshots a flight bundle; recovery clears the alarm
        if self.durable_db is not None:
            obs = self.obs

            def _on_shard_failed(shard_id: int, exc: BaseException) -> None:
                obs.alarms.ensure(
                    f"ds_shard_failed_{shard_id}",
                    details={"shard": shard_id, "error": str(exc)},
                    message=f"durable shard {shard_id} fail-stopped: {exc}",
                )
                if obs.flight is not None:
                    obs.flight.maybe_trigger(
                        "ds_shard_failed",
                        {"shard": shard_id, "error": str(exc)},
                    )

            self.durable_db.storage.on_shard_failed = _on_shard_failed

        # 7. cluster membership + DS replication
        seeds = cfg.get("cluster.static_seeds")
        if seeds or cfg.get("cluster.discovery_strategy") == "static":
            node = ClusterNode(
                node_name,
                broker=broker,
                cookie=cfg.get("node.cookie"),
                autoheal=cfg.get("cluster.autoheal"),
                partition_policy=cfg.get("cluster.partition_policy"),
            )
            node.attach_obs(
                alarms=self.obs.alarms, flight=self.obs.flight
            )
            await node.start()
            self.cluster_node = node
            for seed in seeds:
                host, _, port = seed.rpartition(":")
                try:
                    await node.join((host, int(port)))
                    break
                except Exception:
                    log.warning("seed %s unreachable", seed)
            if self.durable_mgr is not None and cfg.get(
                "durable_storage.messages.backend"
            ) == "builtin_raft":
                from .ds.replication import ReplicatedDs

                self.replicator = ReplicatedDs(node, self.durable_mgr)
                # reboot catch-up: entries the cluster committed while
                # this node was down exist only on the peers — pull
                # them before serving (best-effort: no peers yet on a
                # cold cluster boot is fine, adverts gap-heal later)
                caught = await self.replicator.catch_up()
                if caught:
                    log.info(
                        "DS replication caught up %d entr%s from peers",
                        caught, "y" if caught == 1 else "ies",
                    )

        # 7b. chaos scenario engine (emqx_tpu/chaos) — ARMED, not run:
        # the engine binds to this node's broker/cluster/sentinel so an
        # operator can drive soak scenarios against the live node; the
        # full million-session soak runs standalone (python -m
        # emqx_tpu.chaos) or as the bench --soak stage
        self.chaos = None
        if cfg.get("chaos.enable"):
            from .chaos.engine import ChaosEngine

            self.chaos = ChaosEngine(
                broker,
                self.obs,
                node=self.cluster_node,
                sessions=cfg.get("chaos.sessions"),
                groups=cfg.get("chaos.groups"),
                zipf_s=cfg.get("chaos.zipf_s"),
                storm_chunk=cfg.get("chaos.storm_chunk"),
                sample_n=cfg.get("chaos.audit_sample_n"),
            )
            log.info(
                "chaos engine armed: %s sessions, 1/%s audit sampling",
                cfg.get("chaos.sessions"),
                cfg.get("chaos.audit_sample_n"),
            )

        # 8. listeners (+ the node-wide TLS-PSK identity store the
        # QUIC listeners authenticate against — ref: apps/emqx_psk)
        from .broker.listeners import Listeners

        psk_conf = cfg.get("psk_authentication") or {}
        psk_store = None
        if psk_conf.get("enable"):
            from .broker.tls_extras import PskStore

            psk_store = PskStore(
                init_file=psk_conf.get("init_file"),
                separator=psk_conf.get("separator") or ":",
            )
        self.psk_store = psk_store
        self.listeners = Listeners(broker, config=cfg, psk_store=psk_store)
        lconf = cfg.get("listeners")
        if not any(
            (lconf or {}).get(t) for t in ("tcp", "ssl", "ws", "wss", "quic")
        ):
            lconf = {"tcp": {"default": {"bind": "0.0.0.0:1883"}}}
        await self.listeners.start_all(lconf)

        # 9. gateways
        from .gateway import GatewayRegistry

        self.gateways = GatewayRegistry(broker)
        for gname, gconf in (cfg.get("gateway") or {}).items():
            if gconf.get("enable", True):
                await self.gateways.load(gname, gconf)

        # 10. cluster links
        if cfg.get("cluster_link.enable"):
            from .cluster.link import ClusterLink, LinkServer

            cluster_name = cfg.get("cluster.name")
            # an empty links list means deny-all route ops, never
            # allow-any — pass the (possibly empty) list through
            server = LinkServer(
                broker,
                cluster_name,
                allowed_clusters=[
                    l["name"] for l in cfg.get("cluster_link.links")
                ],
            )
            server.enable()
            self.link_server = server
            for lk in cfg.get("cluster_link.links"):
                link = ClusterLink(
                    broker,
                    cluster_name,
                    lk["name"],
                    lk["server"],
                    topics=lk.get("topics") or [],
                    username=lk.get("username"),
                    password=(lk.get("password") or "").encode() or None,
                )
                await link.start()
                self.links.append(link)

        # 10b. license / connection-quota enforcement (ref:
        # apps/emqx_license — the connect gate registers at the
        # 'client.connect' hookpoint, quota visible via /api/v5/license)
        from .license import LicenseChecker

        lic_conf = cfg.get("license") or {}
        cluster_node = self.cluster_node

        def _licensed_count() -> int:
            # the entitlement is CLUSTER-wide (emqx_license_resources
            # aggregates the count over all nodes): when clustered, the
            # replicated client registry carries every node's clients;
            # standalone falls back to the local live-transport count
            if cluster_node is not None and cluster_node.registry:
                return len(cluster_node.registry)
            return broker.connected_count()

        self.license = LicenseChecker(
            key=lic_conf.get("key") or "default",
            count_fn=_licensed_count,
            alarms=getattr(self.obs, "alarms", None),
            public_key_pem=lic_conf.get("public_key"),
            low_watermark=lic_conf.get("connection_low_watermark", "75%"),
            high_watermark=lic_conf.get("connection_high_watermark", "80%"),
            persist_fn=lambda key: cfg.update("license.key", key),
        )
        self.license.attach(broker)

        # 11. plugins (restarts previously enabled ones) — before the
        # API so the REST surface can manage them
        from .plugins import PluginManager

        self.plugins = PluginManager(
            broker,
            install_dir=cfg.get("plugins.install_dir")
            or os.path.join(data_dir, "plugins"),
        )

        # 12. management API
        if cfg.get("api.enable"):
            from .broker.listeners import parse_bind
            from .mgmt.api import ManagementApi

            self.mgmt = ManagementApi(
                broker,
                config=cfg,
                rules=self.rules,
                banned=self.auth.banned,
                node=self.cluster_node,
                node_name=node_name,
                obs=self.obs,
                backup_dir=os.path.join(data_dir, "backup"),
                ft=self.ft,
                gateways=self.gateways,
                listeners=self.listeners,
                plugins=self.plugins,
                bridges=self.bridge_registry,
                license=self.license,
            )
            host, port = parse_bind(cfg.get("api.bind"))
            await self.mgmt.start(host, port)

        # 13. ctl command surface (emqx ctl analog)
        from .mgmt.cli import Ctl

        self.ctl = Ctl(
            broker,
            config=cfg,
            rules=self.rules,
            banned=self.auth.banned,
            node=self.cluster_node,
            node_name=node_name,
            plugins=self.plugins,
            gateways=self.gateways,
            listeners=self.listeners,
            license=self.license,
            obs=self.obs,
        )
        # the stage register's spans and its `gc` stage follow this loop
        from .obs.profiler import STAGE_MARK

        STAGE_MARK.attach()
        log.info("node %s started", node_name)

    async def stop(self) -> None:
        if self._stopping:
            return
        self._stopping = True
        for name in [p["name"] for p in (self.plugins.list() if self.plugins else [])]:
            try:
                # shutdown stop must not persist disabled state — the
                # next boot restarts previously-enabled plugins
                self.plugins.stop(name, persist=False)
            except Exception:
                pass
        if self.mgmt is not None:
            await self.mgmt.stop()
        if getattr(self, "bridge_registry", None) is not None:
            await self.bridge_registry.stop_all()
        for link in self.links:
            try:
                await link.stop()
            except Exception:
                pass
        if self.gateways is not None:
            await self.gateways.unload_all()
        if self.listeners is not None:
            await self.listeners.stop_all()
        if self.cluster_node is not None:
            await self.cluster_node.stop()
        if getattr(self, "_ft_gc_task", None) is not None:
            self._ft_gc_task.cancel()
            self._ft_gc_task = None
        if self.auth is not None:
            # backend-connected providers (redis/pg/mysql/...) hold
            # sockets that must close with the node
            self.auth.authn.destroy_all()
            self.auth.authz.destroy_all()
        if self.telemetry is not None:
            self.telemetry.stop()
        if self.obs is not None:
            self.obs.stop()
        if self.durable_mgr is not None:
            self.durable_mgr.close()
        if self.durable_db is not None:
            self.durable_db.close()
        retainer = getattr(self.broker, "retainer", None)
        if retainer is not None and hasattr(retainer, "close"):
            retainer.close()
        from .obs.profiler import STAGE_MARK

        STAGE_MARK.detach()
        log.info("node stopped")

    async def run_forever(self) -> None:
        """Start, then park until SIGINT/SIGTERM; graceful stop."""
        await self.start()
        stop_ev = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop_ev.set)
            except NotImplementedError:
                pass
        try:
            await stop_ev.wait()
        finally:
            await self.stop()


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser(description="emqx_tpu broker node")
    ap.add_argument("-c", "--config", action="append", default=[],
                    help="config file (repeatable; later override earlier)")
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s %(name)s %(message)s",
    )
    asyncio.run(Node(config_files=args.config).run_forever())


if __name__ == "__main__":
    main()
