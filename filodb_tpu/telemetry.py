"""Self-telemetry: the server scrapes ITSELF into a ``_system`` dataset.

This is a Prometheus-compatible TSDB — its own metrics should be queryable
through its own (fused) PromQL path, not only through an external
Prometheus. The :class:`SelfScraper` samples the process ``REGISTRY`` every
``telemetry.self_scrape_interval_s`` seconds, renders the standard text
exposition, and feeds it through the PRODUCTION ingest parser
(``gateway.parsers.prom_text_to_batches_and_exemplars`` — TYPE comments
route counters and histogram families to the counter schema) into the
memstore's ``_system`` dataset. ``rate(filodb_kernel_dispatch_seconds_count[5m])``
and per-tenant byte dashboards then run through the standard query API
(``?dataset=_system``) and the fused single-dispatch path like any other
workload.

The query observatory (obs/querylog.py) rides this pipeline into
``_system``: the per-phase histograms
(``filodb_query_phase_seconds{phase,dataset}``) and the per-tenant /
per-path cumulative aggregates
(``filodb_tenant_phase_seconds_total{phase,ws,ns}``,
``filodb_query_path_total{path,dataset}``) are ordinary registry families,
so every scrape ingests them as real series and
``histogram_quantile(0.99, sum by (le)
(rate(filodb_query_phase_seconds_bucket{phase="render"}[5m])))`` answers
through the fused path — which is also what the SLO burn-rate recording
rules (obs/slo.py) evaluate against.

Also here: the query-log ring-depth collector.
"""

from __future__ import annotations

import logging
import threading
import time

from .metrics import REGISTRY

log = logging.getLogger("filodb_tpu.telemetry")

SYSTEM_DATASET = "_system"


class SelfScraper:
    """Config-gated internal collector: REGISTRY -> text exposition ->
    prom parser -> ``_system`` dataset, every ``interval_s`` seconds."""

    def __init__(self, memstore, dataset: str = SYSTEM_DATASET,
                 interval_s: float = 15.0, spread: int = 1,
                 registry=REGISTRY, ws: str = "system", ns: str = "filodb"):
        self.memstore = memstore
        self.dataset = dataset
        self.interval_s = float(interval_s)
        self.spread = int(spread)
        self.registry = registry
        self.ws = ws
        self.ns = ns
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    def scrape_once(self, now_ms: int | None = None) -> int:
        """One scrape cycle; returns samples ingested (synchronous — the
        unit the tests drive directly)."""
        from .gateway.parsers import prom_text_to_batches_and_exemplars

        if now_ms is None:
            now_ms = int(time.time() * 1000)
        text = self.registry.expose()
        batches, _exemplars = prom_text_to_batches_and_exemplars(
            text, now_ms, ws=self.ws, ns=self.ns
        )
        n = 0
        for batch in batches:
            n += self.memstore.ingest_routed(self.dataset, batch, self.spread)
        REGISTRY.counter("filodb_self_scrapes").inc()
        REGISTRY.counter("filodb_self_scrape_samples").inc(n)
        return n

    def start(self) -> None:
        if self._thread is not None and self._thread.is_alive():
            return  # idempotent, like SamplingProfiler.start
        self._stop.clear()
        self._thread = threading.Thread(
            target=self._run, daemon=True, name="filodb-self-scrape"
        )
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1)

    def _run(self) -> None:
        while not self._stop.wait(self.interval_s):
            try:
                self.scrape_once()
            except Exception:  # noqa: BLE001 — telemetry must never kill serving
                log.exception("self-scrape failed")


# -- query-observatory collector ---------------------------------------------


def register_querylog_collector(registry=REGISTRY) -> None:
    """Expose the query-log ring's depth as ``filodb_querylog_entries``,
    refreshed at scrape time (keyed — re-registration replaces). The
    per-phase/per-tenant/per-path aggregates need no collector: they are
    plain counters/histograms bumped at record time (obs/querylog.py) and
    every self-scrape carries them into ``_system``."""
    from .obs.querylog import QUERY_LOG

    def collect():
        registry.gauge("filodb_querylog_entries").set(float(len(QUERY_LOG)))

    registry.register_collector("querylog", collect)
