"""Standalone server (reference L7: standalone/.../NewFiloServerMain.scala:25
— boot memstore + shard recovery, start HTTP API, periodic flush + retention
maintenance; v2-style static shard ownership, no cluster singleton).

Config is a JSON dict (HOCON analog), e.g.::

    {
      "dataset": "prometheus",
      "shards": 8,
      "spread": 3,
      "http_port": 9090,
      "store_root": "/var/lib/filodb-tpu",       # omit for memory-only
      "flush_interval_s": 3600,
      "retention_hours": 72,
      "max_chunk_size": 400,
      "downsample": {"enabled": false, "periods_m": [5, 60]}
    }
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time

from .api.http import device_facts, serve_background
from .coordinator.planner import QueryEngine
from .core.schemas import Dataset
from .memstore.memstore import TimeSeriesMemStore
from .memstore.shard import StoreConfig
from .metrics import REGISTRY
from .store.columnstore import LocalColumnStore, NullColumnStore
from .store.flush import FlushCoordinator, recover_shard

log = logging.getLogger("filodb_tpu.server")


def process_start_time() -> float | None:
    """When the kernel started this process, unix seconds: field 22 of
    ``/proc/self/stat`` (clock ticks after boot; the command in field 2 may
    hold spaces, so count from its closing bracket) on ``btime`` of
    ``/proc/stat``. None where there is no such file."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/stat") as f:
            btime = next(int(ln.split()[1]) for ln in f
                         if ln.startswith("btime "))
        return btime + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError, StopIteration):
        return None


class FiloServer:
    def __init__(self, config: dict | None = None):
        # the restart-to-serving timeline (filodb_startup_seconds{stage}):
        # each stage ends where the next begins, so what a slow start spent
        # has one owner (doc/observability.md "Start-up and set-up")
        entered, t_entered = time.time(), time.perf_counter()
        from .config import load_config

        cfg = load_config(overrides=config or {})
        self.config = cfg
        # before any jit dispatch: compiled kernels persist across restarts
        # (config "compile_cache_dir"; doc/perf.md)
        from .ops.compile_cache import enable_from_config

        enable_from_config(cfg)
        self.dataset = cfg["dataset"]
        self.n_shards = int(cfg["shards"])
        self.spread = int(cfg["spread"])
        self.http_port = int(cfg["http_port"])
        self.flush_interval_s = float(cfg["flush_interval_s"])
        self.store_config = StoreConfig(
            max_chunk_size=int(cfg["max_chunk_size"]),
            retention_ms=int(float(cfg["retention_hours"]) * 3_600_000),
            groups_per_shard=int(cfg["groups_per_shard"]),
            max_partitions=int(cfg["max_partitions_per_shard"]),
            index_backend=cfg["index_backend"],
        )
        # multi-host: join the JAX distributed runtime (no-op single-process)
        # and own only this process's shard slice (reference v2 cluster:
        # ordinal -> shards, FiloDbClusterDiscovery)
        from .parallel.multihost import init_distributed, shards_for_process

        dist_cfg = cfg.get("distributed") or {}
        self.peers = tuple(dist_cfg.get("peers") or ())
        self.seeds = tuple(dist_cfg.get("seeds") or ())
        self.advertise_url = dist_cfg.get("advertise_url")
        self.refresh_interval_s = float(dist_cfg.get("refresh_interval_s") or 30)
        self.is_distributed = init_distributed(
            dist_cfg.get("coordinator"),
            dist_cfg.get("num_processes"),
            dist_cfg.get("process_id"),
        )
        # the backend comes up here, under a stage of its own, and not
        # inside whatever touches a device first
        device_facts()
        t_backend = time.perf_counter()
        if dist_cfg.get("owned_shards") is not None:
            owned = list(dist_cfg["owned_shards"])  # explicit (k8s static / tests)
        elif self.is_distributed:
            owned = shards_for_process(self.n_shards)
        elif self.peers or self.seeds:
            # peers/seeds configured but nothing assigns THIS process a
            # slice: every host would own (and ingest) everything, and
            # scattered queries would double-count — refuse at startup
            raise ValueError(
                "distributed.peers/seeds requires distributed.owned_shards "
                "or a JAX coordinator to assign this process's shard slice"
            )
        else:
            owned = range(self.n_shards)
        self.memstore = TimeSeriesMemStore(self.store_config)
        # total_shards pins the routing modulus to the CLUSTER size even when
        # this process owns a partial slice
        self.memstore.setup(Dataset(self.dataset), owned, total_shards=self.n_shards)
        # shard plane view for GET /debug/cluster: this process's slice of
        # the static topology, ACTIVE from boot (v2 static ownership). An
        # embedding control plane may attach a ReplicationPlane to
        # self.replication — its richer snapshot (replicas, watermarks,
        # rebalances) takes over the endpoint.
        from .coordinator.cluster import ShardManager, ShardStatus

        self.replication = None
        self.cluster_manager = ShardManager(self.n_shards,
                                            shards_per_node=self.n_shards)
        self.cluster_manager.nodes.append("self")
        for s in owned:
            self.cluster_manager.mapper.update(s, ShardStatus.ACTIVE, "self")
        for q in cfg.get("quotas", []):
            for sh in self.memstore.shards(self.dataset):
                sh.cardinality.set_quota(tuple(q["prefix"]), int(q["quota"]))
        root = cfg.get("store_root")
        self.column_store = LocalColumnStore(root) if root else NullColumnStore()
        if root:
            for sh in self.memstore.shards(self.dataset):
                sh.odp_store = self.column_store
        downsampler = None
        if cfg["downsample"]["enabled"]:
            from .downsample.downsampler import ShardDownsampler

            downsampler = ShardDownsampler(
                self.memstore, self.dataset,
                periods_ms=tuple(int(m) * 60_000 for m in cfg["downsample"]["periods_m"]),
            )
        self.downsampler = downsampler
        preagg = None
        if cfg.get("preagg_rules"):
            from .coordinator.lpopt import AggRuleProvider, ExcludeAggRule, IncludeAggRule
            from .downsample.preagg import PreaggMaintainer

            rules = []
            for i, r in enumerate(cfg["preagg_rules"]):
                if "metric_regex" not in r or ("include_tags" in r) == ("exclude_tags" in r):
                    raise ValueError(
                        f"preagg_rules[{i}] must have metric_regex and exactly one "
                        f"of include_tags/exclude_tags: {r}"
                    )
                if "include_tags" in r:
                    rules.append(IncludeAggRule(r["metric_regex"], frozenset(r["include_tags"])))
                else:
                    rules.append(ExcludeAggRule(r["metric_regex"], frozenset(r["exclude_tags"])))
            self.agg_rules = AggRuleProvider(rules)
            preagg = PreaggMaintainer(self.memstore, self.dataset, self.agg_rules)
        else:
            self.agg_rules = None
        self.preagg = preagg
        self.flusher = FlushCoordinator(self.memstore, self.column_store, downsampler, preagg)
        from .coordinator.planner import PlannerParams

        qcfg = cfg["query"]
        self.scheduler = None
        if int(qcfg.get("parallelism", 0)) > 0:
            from .coordinator.scheduler import QueryScheduler

            self.scheduler = QueryScheduler(
                parallelism=int(qcfg["parallelism"]),
                max_queued=int(qcfg.get("max_queued", 64)),
            )
        # fault tolerance: shared breaker registry + retry budget for remote
        # children (query/faults.py); both engines (scattering + local) share
        # the registry so peer health is judged once per process
        from .config import DEFAULTS
        from .query.faults import BreakerRegistry, RetryPolicy

        # layer user values over config.py DEFAULTS (the single source of
        # truth): a user config providing a partial retry/breaker dict
        # replaces the whole dict in load_config's one-level merge
        rcfg = {**DEFAULTS["query"]["retry"], **(qcfg.get("retry") or {})}
        bcfg = {**DEFAULTS["query"]["breaker"], **(qcfg.get("breaker") or {})}
        self.breakers = BreakerRegistry(
            window=int(bcfg["window"]),
            failure_rate=float(bcfg["failure_rate"]),
            min_calls=int(bcfg["min_calls"]),
            cooldown_s=float(bcfg["cooldown_s"]),
        )
        self.retry_policy = RetryPolicy(
            max_attempts=int(rcfg["max_attempts"]),
            base_backoff_s=float(rcfg["base_backoff_s"]),
            max_backoff_s=float(rcfg["max_backoff_s"]),
        )
        # slow-query log: threshold rides PlannerParams, the ring size is
        # process-global (the log is shared across engines)
        slow_thr = qcfg.get("slow_query_threshold_s", DEFAULTS["query"]["slow_query_threshold_s"])
        from .metrics import SLOW_QUERY_LOG

        SLOW_QUERY_LOG.configure(int(qcfg.get("slow_query_log_max", 64) or 64))
        # query observatory (obs/querylog.py): size the per-query cost
        # record ring and publish its depth at scrape time
        from .obs.querylog import QUERY_LOG
        from .telemetry import register_querylog_collector

        QUERY_LOG.configure(int(qcfg.get("querylog_max", 512) or 512))
        register_querylog_collector()
        # kernel & compile observatory (obs/kernels.py): size the
        # per-executable registry + recompile-storm detector and publish
        # the live executable count at scrape time (/debug/kernels)
        from .obs.kernels import KERNELS, register_kernel_obs_collector

        kcfg = {**DEFAULTS["kernel_obs"], **(cfg.get("kernel_obs") or {})}
        KERNELS.configure(
            max_entries=int(kcfg["max_executables"]),
            storm_threshold=int(kcfg["storm_threshold"]),
            storm_window_s=float(kcfg["storm_window_s"]),
        )
        register_kernel_obs_collector()
        # work cost model (query/costmodel.py): per-fingerprint predicted
        # device-seconds, fed back from completed querylog records — it
        # prices admission and drives the adaptive batch window below
        from .query.costmodel import COST_MODEL

        cmcfg = {**DEFAULTS["query"]["costmodel"],
                 **(qcfg.get("costmodel") or {})}
        COST_MODEL.configure(
            prior_cost_s=float(cmcfg["prior_cost_s"]),
            alpha=float(cmcfg["alpha"]),
            cold_multiplier=float(cmcfg["cold_multiplier"]),
        )
        prior_cost_s = float(cmcfg["prior_cost_s"])
        # query dispatch scheduler (query/scheduler.py): ONE process-wide
        # micro-batcher + admission controller shared by every engine
        # (scattering, local and _system) so concurrent queries coalesce
        # and tenant quotas act process-wide, whichever engine serves them
        self.dispatch_scheduler = None
        batch_window_ms = float(qcfg.get("batch_window_ms", 0) or 0)
        scfg = {**DEFAULTS["standing"], **(cfg.get("standing") or {})}
        self.standing_config = scfg
        # result plane (doc/perf.md): serving-edge streaming knobs + the
        # node-to-node exchange format. peer_exchange=json pins BOTH sides
        # of this node to decimal JSON (serving edge stops honoring Arrow
        # Accept; outgoing scatter legs stop advertising it).
        rpcfg = {**DEFAULTS["result_plane"], **(cfg.get("result_plane") or {})}
        self.result_plane_config = rpcfg
        from .coordinator import planners as _planners

        _planners.PEER_EXCHANGE = str(rpcfg.get("peer_exchange", "arrow"))
        # standing-query promotion rides the scheduler's per-key recurrence
        # ring, so an enabled standing engine needs the scheduler object
        # even when batching is off (window 0 = ring only, no batching)
        pwcfg = {**DEFAULTS["query"]["prewarm"],
                 **(qcfg.get("prewarm") or {})}
        self.prewarm_config = pwcfg
        if batch_window_ms > 0 or scfg.get("enabled", True):
            from .query.scheduler import DispatchScheduler

            self.dispatch_scheduler = DispatchScheduler(
                batch_window_ms, int(qcfg.get("batch_max", 32) or 32),
                key_ring_max=int(scfg.get("key_ring_max", 512) or 512),
                window_cap_ms=float(
                    qcfg.get("batch_window_cap_ms", 0) or 0),
                load_ref_cost_s=float(
                    qcfg.get("batch_load_ref_cost_s", 0.25) or 0.25),
                prior_cost_s=prior_cost_s,
                prewarm_min_count=int(pwcfg.get("min_count", 3) or 3),
            )
        self.admission = None
        quotas = qcfg.get("tenant_quotas") or {}
        admission_max_queued = int(qcfg.get("admission_max_queued", 0) or 0)
        if quotas or admission_max_queued:
            from .query.scheduler import AdmissionController

            self.admission = AdmissionController(
                quotas, max_queued=admission_max_queued,
                prior_cost_s=prior_cost_s,
            )
        common = dict(
            spread=self.spread,
            lookback_ms=int(qcfg["lookback_ms"]),
            max_series=int(qcfg["max_series"]),
            deadline_s=float(qcfg["timeout_s"]),
            agg_rules=self.agg_rules,
            scheduler=self.scheduler,
            num_shards=self.n_shards,
            allow_partial_results=bool(qcfg.get("allow_partial_results", False)),
            fused_aggregate=bool(qcfg.get("fused_aggregate", True)),
            retry_policy=self.retry_policy,
            breakers=self.breakers,
            slow_query_threshold_s=float(slow_thr) if slow_thr is not None else None,
            batch_window_ms=batch_window_ms,
            dispatch_scheduler=self.dispatch_scheduler,
            admission=self.admission,
        )
        self.engine = QueryEngine(
            self.memstore, self.dataset,
            PlannerParams(
                peer_endpoints=self.peers,
                remote_auth_token=cfg.get("http_auth_token"),
                **common,
            ),
        )
        # peers hit this engine (X-FiloDB-Local): answers from owned shards
        # only, never re-scatters — the multi-host anti-recursion guard.
        # It runs OFF the bounded scheduler: scattering root queries hold
        # scheduler workers while blocking on peer HTTP, so routing the
        # peers' subqueries through the same pool would deadlock the cluster
        # (every worker waiting on the other host). Subquery concurrency is
        # bounded by the peers' own scheduler caps.
        self.local_engine = (
            QueryEngine(
                self.memstore, self.dataset,
                PlannerParams(**{**common, "scheduler": None}),
            )
            if (self.peers or self.seeds) else None
        )
        if (self.peers or self.seeds) and not cfg.get("http_auth_token"):
            log.warning(
                "multi-host peers configured WITHOUT http_auth_token: any "
                "client sending X-FiloDB-Local reaches the shard-local "
                "engine (partial results, no admission control) — set a "
                "token so only peers can"
            )
        # standing-query engine (filodb_tpu/standing/): promotion over the
        # scheduler's recurrence ring, delta-maintained partials on ingest
        # append, SSE push fan-out + the recording-rules API. One per
        # process, bound to the scattering engine (standing queries over
        # this node's primary dataset).
        self.standing = None
        if scfg.get("enabled", True):
            from .standing import StandingEngine

            self.standing = StandingEngine(self.engine, scfg)
        # sketch rollup tier (downsample/rollup.py): standing maintainer
        # folds per-period summary blocks over the ingest path; the
        # planner substitutes them for eligible long-range window queries
        # (params.rollups below); the chooser trains the rollup set on
        # the querylog. /debug/rollups is the admin surface.
        rcfg = {**DEFAULTS["rollup"], **(cfg.get("rollup") or {})}
        self.rollups = None
        self.rollup_chooser = None
        if rcfg.get("enabled", True):
            from .downsample.chooser import RollupChooser
            from .downsample.rollup import RollupManager

            self.rollups = RollupManager(
                self.memstore,
                grace_ms=int(rcfg["grace_ms"]),
                max_entries=int(rcfg["max_entries"]),
                tick_s=float(rcfg["tick_s"]),
            )
            self.engine.planner.params.rollups = self.rollups
            ccfg = {**DEFAULTS["rollup"]["chooser"],
                    **(rcfg.get("chooser") or {})}
            if ccfg.get("enabled", True):
                self.rollup_chooser = RollupChooser(
                    self.rollups,
                    resolutions_ms=tuple(
                        int(r) for r in ccfg["resolutions_ms"]
                    ),
                    min_count=int(ccfg["min_count"]),
                    min_span_ms=int(ccfg["min_span_ms"]),
                    idle_s=float(ccfg["idle_s"]),
                    interval_s=float(ccfg["interval_s"]),
                )
                self.rollups.chooser = self.rollup_chooser
        self.profiler = None
        if cfg["profiler"]["enabled"]:
            from .metrics import SamplingProfiler

            self.profiler = SamplingProfiler(cfg["profiler"]["interval_ms"] / 1000.0)
        # self-telemetry (telemetry.py): config-gated REGISTRY -> _system
        # dataset pipeline + an engine so the server's own metrics answer
        # PromQL through the standard (fused) query path (?dataset=_system)
        tcfg = cfg.get("telemetry") or {}
        self.self_scraper = None
        self.system_engine = None
        scrape_interval = tcfg.get("self_scrape_interval_s")
        if scrape_interval:
            from .telemetry import SYSTEM_DATASET, SelfScraper

            self.memstore.setup(Dataset(SYSTEM_DATASET), owned,
                                total_shards=self.n_shards)
            self.system_engine = QueryEngine(
                self.memstore, SYSTEM_DATASET,
                PlannerParams(**{**common, "scheduler": None}),
            )
            self.self_scraper = SelfScraper(
                self.memstore, SYSTEM_DATASET,
                interval_s=float(scrape_interval),
                spread=int(tcfg.get("self_scrape_spread", 1)),
            )
        # SLO burn-rate recording rules (obs/slo.py): a second standing
        # maintainer bound to the _system engine keeps the observatory's
        # own rollups — availability and latency burn rates — as real
        # series. enabled null = auto (on exactly when _system exists and
        # the standing engine is on).
        from .obs.slo import DEFAULTS as SLO_DEFAULTS

        slo_cfg = {**SLO_DEFAULTS, **(cfg.get("slo") or {})}
        self.slo_config = slo_cfg
        self.system_standing = None
        slo_on = slo_cfg.get("enabled")
        if slo_on is None:
            slo_on = self.system_engine is not None and scfg.get("enabled", True)
        if slo_on and self.system_engine is not None:
            from .standing import StandingEngine

            self.system_standing = StandingEngine(self.system_engine, scfg)
        # alerting plane (obs/alerting.py + obs/notify.py): rule groups
        # evaluated on the _system standing engine, state written back as
        # ALERTS/ALERTS_FOR_STATE, firing alerts fanned out to webhook
        # receivers through the shared breaker/retry plane. enabled null =
        # auto (on exactly when the _system standing engine runs).
        from .obs.alerting import DEFAULTS as ALERT_DEFAULTS

        acfg = {**ALERT_DEFAULTS, **(cfg.get("alerting") or {})}
        self.alerting_config = acfg
        self.alerting = None
        alert_on = acfg.get("enabled")
        if alert_on is None:
            alert_on = self.system_standing is not None
        if alert_on and self.system_standing is not None:
            from .obs.alerting import AlertingEngine
            from .obs.notify import Notifier, Receiver

            notifier = None
            recv = [Receiver.from_config(r)
                    for r in (acfg.get("receivers") or [])]
            if recv:
                notifier = Notifier(
                    recv, breakers=self.breakers, retry=self.retry_policy,
                    deadline_s=float(acfg.get("notify_deadline_s", 10.0)),
                    tick_s=float(acfg.get("notify_tick_s", 1.0)),
                )
            self.alerting = AlertingEngine(self.system_standing, acfg,
                                           notifier=notifier)
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        self._http = None
        self._grpc = None
        self.grpc_port = cfg.get("grpc_port")
        self.bootstrapper = None
        self.registry = None
        self._startup = {"backend": t_backend - t_entered,
                         "store": time.perf_counter() - t_backend}
        started = process_start_time()
        if started is not None:
            REGISTRY.gauge("process_start_time_seconds").set(started)
            self._startup["import"] = max(entered - started, 0.0)

    def _cluster_snapshot(self) -> dict:
        """GET /debug/cluster payload: the replication plane's snapshot when
        one is attached, else the static shard-ownership view."""
        if self.replication is not None:
            return self.replication.snapshot()
        return self.cluster_manager.snapshot()

    # -- lifecycle --------------------------------------------------------

    def recover(self) -> dict[int, int]:
        """Rebuild shards from the column store; returns per-shard replay
        offsets for the ingestion sources. Downsample datasets recover too
        (they have no replay stream — their tail rebuilds from raw flushes)."""
        offsets = {}
        owned = self.memstore.shard_nums(self.dataset)
        for s in owned:
            offsets[s] = recover_shard(self.memstore, self.column_store, self.dataset, s)
        if self.downsampler is not None:
            from .core.schemas import Dataset as _DS
            from .downsample.downsampler import DS_GAUGE

            for period in self.downsampler.periods_ms:
                ds = self.downsampler.dataset_for(period)
                self.memstore.setup(_DS(ds, schemas=[DS_GAUGE]), owned,
                                    total_shards=self.n_shards)
                for s in owned:
                    recover_shard(self.memstore, self.column_store, ds, s)
        log.info("recovered %d shards: %s", len(owned), offsets)
        return offsets

    def start(self, port: int | None = None) -> int:
        t_start = time.perf_counter()
        self.recover()
        t_recovered = time.perf_counter()
        if self.profiler is not None:
            self.profiler.start()
        self._http, actual_port = serve_background(
            self.engine, port=self.http_port if port is None else port,
            auth_token=self.config.get("http_auth_token"),
            local_engine=self.local_engine,
            flush_hook=self.flush_now,
            dataset_engines=(
                {self.system_engine.dataset: self.system_engine}
                if self.system_engine is not None else None
            ),
            standing=self.standing,
            standing_system=self.system_standing,
            rollups=self.rollups,
            alerting=self.alerting,
            cluster=self._cluster_snapshot,
            result_plane=self.result_plane_config,
        )
        if self.standing is not None:
            self.standing.start()
        if self.rollups is not None:
            self.rollups.start()
        if self.rollup_chooser is not None:
            self.rollup_chooser.start()
        if self.system_standing is not None:
            # register + start the SLO maintainer AFTER the HTTP edge is
            # up: rules evaluate from live-traffic metrics the edge emits
            from .obs.slo import register_slo_rules

            self.slo_rules = register_slo_rules(self.system_standing,
                                                self.slo_config)
            if self.alerting is not None:
                # rule files load AFTER the SLO set registers (alert exprs
                # threshold the burn series those rules record) and BEFORE
                # the maintainer thread starts; rehydration restores
                # pending/firing state from the ALERTS_FOR_STATE series a
                # previous process wrote, so a restart never resets a
                # firing alert's for: clock
                self.alerting.load_rule_files()
                self.alerting.rehydrate()
                self.alerting.start()
            self.system_standing.start()
        if self.self_scraper is not None:
            self.self_scraper.start()
        if self.profiler is not None:
            # /debug/profile is config-gated: wired only when the profiler
            # block enables sampling
            self._http.RequestHandlerClass.profiler_hook = staticmethod(
                self.profiler.report
            )
        if self.seeds:
            # seed bootstrap (reference akka-bootstrapper): discover peers
            # via /__members, expose our own membership, keep refreshing so
            # joins propagate and dead peers age out of the scatter set
            from .coordinator.bootstrap import MemberRegistry, SeedBootstrapper

            self_url = self.advertise_url or f"http://127.0.0.1:{actual_port}"
            self.registry = MemberRegistry(self_url)

            def on_change(peers):
                # compose with any statically configured peers (e.g. grpc://
                # endpoints) — discovery must never drop them from scatter
                merged = tuple(dict.fromkeys(self.peers + tuple(peers)))
                log.info("cluster membership changed: peers=%s", list(merged))
                self.engine.planner.params.peer_endpoints = merged

            self.bootstrapper = SeedBootstrapper(
                self.registry, self.seeds,
                auth_token=self.config.get("http_auth_token"),
                on_change=on_change,
            )
            def on_join(url, node_id=None):
                if node_id and node_id == self.registry.node_id:
                    self.registry.mark_self_alias(url)  # our own announce
                    return
                new = self.registry.learn([url])
                self.registry.touch([url])  # they reached us: direct contact
                if new:
                    on_change(self.registry.peers())

            self._http.RequestHandlerClass.members_hook = staticmethod(self.registry.snapshot)
            self._http.RequestHandlerClass.join_hook = staticmethod(on_join)

            def join():
                try:
                    self.bootstrapper.bootstrap()
                except Exception:  # noqa: BLE001
                    log.exception("seed bootstrap failed; refresh loop keeps trying")
                self.bootstrapper.start(self.refresh_interval_s)

            t0 = threading.Thread(target=join, daemon=True, name="filodb-join")
            t0.start()
            self._threads.append(t0)
        if self.grpc_port is not None:
            from .api.grpc_exec import serve_grpc

            self._grpc, self.grpc_port = serve_grpc(
                self.engine, port=int(self.grpc_port),
                auth_token=self.config.get("http_auth_token"),
                local_engine=self.local_engine,
                host=self.config.get("grpc_host") or "127.0.0.1",
            )
            log.info("filodb-tpu gRPC RemoteExec on :%d", self.grpc_port)
        t = threading.Thread(target=self._maintenance_loop, daemon=True)
        t.start()
        self._threads.append(t)
        if (self.dispatch_scheduler is not None
                and self.prewarm_config.get("enabled", True)
                and int(self.prewarm_config.get("per_tick", 2) or 0) > 0):
            tp = threading.Thread(target=self._prewarm_loop, daemon=True,
                                  name="filodb-prewarm")
            tp.start()
            self._threads.append(tp)
        startup = {**self._startup,
                   "store": self._startup["store"] + t_recovered - t_start,
                   "listen": time.perf_counter() - t_recovered}
        for stage, seconds in startup.items():
            REGISTRY.gauge("filodb_startup_seconds", stage=stage).set(seconds)
        log.info("filodb-tpu serving on :%d (%d shards)", actual_port, self.n_shards)
        log.info("kernels run on platform=%(platform)s "
                 "device_kind=%(device_kind)s device_count=%(device_count)d",
                 device_facts())
        return actual_port

    def stop(self):
        self._stop.set()
        if self.rollup_chooser is not None:
            self.rollup_chooser.stop()
        if self.rollups is not None:
            self.rollups.stop()
        if self.standing is not None:
            self.standing.stop()
        if self.alerting is not None:
            self.alerting.stop()
        if self.system_standing is not None:
            self.system_standing.stop()
        if self.self_scraper is not None:
            self.self_scraper.stop()
        if self.bootstrapper is not None:
            self.bootstrapper.stop()
        if self._http:
            self._http.shutdown()
        if self._grpc is not None:
            self._grpc.stop(grace=0.5)
        if self.scheduler is not None:
            self.scheduler.shutdown()

    def _prewarm_loop(self):
        """Background executable pre-warm (query/scheduler.py
        prewarm_tick): trace+compile the programs of recurrence-ring keys
        about to go hot, OFF the serving path, so the first real poll of a
        recurring dashboard pays zero compiles."""
        interval = float(self.prewarm_config.get("interval_s", 5.0) or 5.0)
        limit = int(self.prewarm_config.get("per_tick", 2) or 2)
        while not self._stop.wait(interval):
            try:
                self.dispatch_scheduler.prewarm_tick(limit=limit)
            except Exception:  # noqa: BLE001
                log.exception("prewarm tick failed")

    def _maintenance_loop(self):
        """Periodic flush + retention eviction + tenant metering (reference
        flush timer + evictForHeadroom + TenantIngestionMetering)."""
        from .metering import TenantIngestionMetering

        metering = TenantIngestionMetering(self.memstore, self.dataset)
        last_flush = time.time()
        while not self._stop.wait(min(self.flush_interval_s, 60.0)):
            now = time.time()
            if now - last_flush >= self.flush_interval_s:
                try:
                    self.flush_now()
                except Exception:  # noqa: BLE001
                    log.exception("flush failed")
                last_flush = now
            for ds in list(self.memstore._datasets):
                for sh in self.memstore.shards(ds):
                    sh.evict_for_retention()
                    sh.evict_for_headroom()
            try:
                metering.publish()
            except Exception:  # noqa: BLE001
                log.exception("metering failed")

    def flush_now(self):
        """Flush the primary dataset, then any downsample/aux datasets the
        flush itself populated (so they persist and recover too). Returns
        the TOTAL across all datasets (the /admin/flush contract)."""
        res = self.flusher.flush_all(self.dataset)
        for ds in list(self.memstore._datasets):
            if ds != self.dataset:
                r = self.flusher.flush_all(ds)
                res.chunks_written += r.chunks_written
                res.partkeys_written += r.partkeys_written
                res.groups_flushed += r.groups_flushed
        return res


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser("filodb-tpu-server")
    p.add_argument("--config", help="JSON config file")
    p.add_argument("--port", type=int, default=None)
    args = p.parse_args(argv)
    cfg = {}
    if args.config:
        with open(args.config) as f:
            cfg = json.load(f)
    logging.basicConfig(level=logging.INFO)
    srv = FiloServer(cfg)
    port = srv.start(port=args.port)
    print(f"listening on :{port}")
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        srv.stop()


if __name__ == "__main__":
    main()
