"""Kernel & compile observatory — the process-global executable registry
(doc/observability.md "Kernel & compile observatory").

PR 12's query observatory decomposes host wall time into phases, but its
``dispatch`` phase is one opaque number conflating XLA compilation, batch
queue skew and actual device execution. The ROADMAP's cost-model-driven
scheduling item needs *measured per-executable device costs* (Tailwind
prices admission by estimated accelerator work, PAPERS.md) and the
workload-chosen-rollup item needs per-kernel-variant costs joined to the
querylog fingerprint — so every jitted kernel entry point in ``ops/``
reports each dispatch here, keyed by the full static signature of the
executable it ran:

    family | variant | epilogue | shapes | mesh | batch

- **family**  — the instrumented entry point's kernel name (the same label
  ``filodb_kernel_dispatch_seconds{kernel=}`` carries), e.g.
  ``fused_sum_rate`` / ``mesh_fused_hist_quantile_sum_rate`` /
  ``batch_fused_sum_rate`` / ``segment_aggregate``.
- **variant** — the grid-class kernel variant the dispatch ladder chose
  (``mxu`` | ``jitter`` | ``masked`` | ``pallas`` | ``general`` |
  ``hist_shared`` | ``hist_jitter`` | ``hist_general`` | ...).
- **epilogue** — the fused epilogue statics (``agg:sum``, ``topk:5:False``,
  ``quantile``, ``hist:quantile``...).
- **shapes**  — the PADDED device shapes that select the XLA executable
  (``S4096xT720xJ64xG2``): padding discipline means a handful of stable
  buckets, so key count stays bounded in steady state.
- **mesh**    — device count under shard_map, ``-`` for single-device.
- **batch**   — batched-lane composition (``Q8U2``: 8 padded lanes, 2
  unique windows), ``-`` for unbatched.

Per key the registry records compile count + compile seconds (the dispatch
that grew the jit cache paid trace+compile inline — that wall time IS the
measurable compile cost), per-dispatch counts and device-time
:class:`~filodb_tpu.metrics.MicroHistogram` p50/p99 (the host wall of the
dispatch call: the device's own time is on a profiler trace, and every
caller's wait for it is ``filodb_transfer_ready_seconds``), executable bytes
(the persistent compile cache's serialized entry, when one was written)
and compile provenance: ``persistent`` (loaded from the on-disk XLA cache),
``in_process`` (the jit cache hit — the steady state) or ``fresh`` (traced
and compiled from nothing). Provenance reconciles BY CONSTRUCTION with
``filodb_compile_cache_{hits,misses}_total{tier=}`` — both are fed from
the same classification call (ops/compile_cache.classify_dispatch).

**Recompile storms**: a family re-compiling more than
``kernel_obs.storm_threshold`` times inside ``storm_window_s`` is the
SURVEY §7 failure mode (shape churn defeating the padding discipline).
The registry keeps a per-family ring of recent compile keys; on crossing
the threshold it counts ``filodb_xla_recompile_storms_total{family}`` and
annotates the family in ``/debug/kernels`` with the UNSTABLE DIMENSION —
the key component(s) that actually varied across the window's compiles
(``shapes`` churn reads very differently from an ``epilogue`` sweep).

Overhead contract: pure host-side metadata accounting (shape tuples, one
small lock) — no device sync on the default path; the warm canonical query
stays exactly ONE kernel dispatch and records ZERO new compiles with the
observatory on (asserted in tests/test_kernel_obs.py).
"""

from __future__ import annotations

import threading
import time
import weakref
from collections import deque

from ..metrics import REGISTRY, MicroHistogram

# the ONE canonical key-dimension order (doc/observability.md documents the
# anatomy; tools/check_metrics.py lints that every ops/ jit wrapper
# registers with this registry)
KEY_DIMS = ("family", "variant", "epilogue", "shapes", "mesh", "batch")

_PROVENANCE = ("fresh", "persistent", "in_process")


def _fmt(v) -> str:
    if v is None or v == "" or v == ():
        return "-"
    return str(v)


def executable_key(parts: dict) -> str:
    """Stable ``dim=value|...`` string over :data:`KEY_DIMS` — the join key
    querylog records carry (``executable_key``) and ``/debug/kernels``
    tables are indexed by."""
    return "|".join(f"{d}={_fmt(parts.get(d))}" for d in KEY_DIMS)


def hist_quantile_est(h, q: float) -> float:
    """Linear-interpolated quantile estimate from a fixed-bucket histogram
    (host-side rendering for /debug/kernels — same scheme PromQL's
    histogram_quantile applies to classic buckets)."""
    total = h.total
    if total <= 0:
        return 0.0
    rank = q * total
    cum = 0
    prev_bound = 0.0
    for bound, count in zip(h.BOUNDS, h.counts):
        if count > 0 and cum + count >= rank:
            frac = (rank - cum) / count
            return prev_bound + (bound - prev_bound) * frac
        cum += count
        prev_bound = bound
    return float(h.BOUNDS[-1])


class _ExecRecord:
    """One executable's accounting. Mutated under the registry lock."""

    __slots__ = (
        "key", "parts", "compiles", "compile_seconds", "dispatches",
        "device_hist", "provenance", "executable_bytes", "first_seen_s",
        "last_dispatch_s", "last_compile_s",
    )

    def __init__(self, key: str, parts: dict):
        self.key = key
        self.parts = dict(parts)
        self.compiles = 0
        self.compile_seconds = 0.0
        self.dispatches = 0
        self.device_hist = MicroHistogram()
        self.provenance = {p: 0 for p in _PROVENANCE}
        self.executable_bytes: int | None = None
        self.first_seen_s = time.time()
        # born "just dispatched": a fresh record must never sort below
        # genuinely stale entries in the LRU eviction (it is inserted
        # BEFORE the dispatch stamps it — evicting it would orphan the
        # update and freeze the table at capacity)
        self.last_dispatch_s = self.first_seen_s
        self.last_compile_s = 0.0

    def snapshot(self) -> dict:
        h = self.device_hist
        return {
            "key": self.key,
            **{d: _fmt(self.parts.get(d)) for d in KEY_DIMS},
            "compiles": self.compiles,
            "compile_ms": round(self.compile_seconds * 1e3, 3),
            "dispatches": self.dispatches,
            "device_p50_ms": round(hist_quantile_est(h, 0.5) * 1e3, 4),
            "device_p99_ms": round(hist_quantile_est(h, 0.99) * 1e3, 4),
            "device_total_ms": round(h.sum * 1e3, 3),
            "executable_bytes": self.executable_bytes,
            "cache": dict(self.provenance),
            "first_seen": round(self.first_seen_s, 3),
            "last_dispatch": round(self.last_dispatch_s, 3),
        }


class ExecutableRegistry:
    """Process-global registry of lowered XLA executables and their costs.

    Capture is always on (like the query log); ``configure`` sizes the
    table and the storm detector from the ``kernel_obs`` config block.
    ``observe_dispatch`` is the ONE ingestion point — every
    ``metrics.record_kernel_dispatch`` call forwards here with the key
    parts the dispatch site knows statically."""

    def __init__(self, max_entries: int = 1024, storm_threshold: int = 5,
                 storm_window_s: float = 60.0):
        self._lock = threading.Lock()
        self._records: dict[str, _ExecRecord] = {}
        # registered jit wrappers per ops module: the lint anchor
        # (tools/check_metrics.py) and the snapshot's in-process
        # compile-cache sizes; weakrefs so a registry never pins a module
        self._jits: dict[str, weakref.ref] = {}
        self._jit_meta: dict[str, dict] = {}
        # per-family ring of recent compile events: (monotonic_t, parts)
        self._compile_ring: dict[str, deque] = {}
        self._storm_active: dict[str, bool] = {}
        self._storms: dict[str, dict] = {}
        self.max_entries = int(max_entries)
        self.storm_threshold = int(storm_threshold)
        self.storm_window_s = float(storm_window_s)
        self._local = threading.local()

    def configure(self, max_entries: int | None = None,
                  storm_threshold: int | None = None,
                  storm_window_s: float | None = None) -> None:
        with self._lock:
            if max_entries is not None:
                self.max_entries = max(int(max_entries), 16)
            if storm_threshold is not None:
                self.storm_threshold = max(int(storm_threshold), 1)
            if storm_window_s is not None:
                self.storm_window_s = max(float(storm_window_s), 1.0)

    # -- jit wrapper registration (the lint anchor) -----------------------

    def register_jits(self, module: str, **jits) -> None:
        """Register a module's jit wrappers under stable names.

        Every ``jax.jit`` call site in ``ops/`` must be registered here
        (tools/check_metrics.py AST-lints wrapper names against these
        calls): registration is what lets the observatory report each
        wrapper's live in-process cache size — the ground truth the
        per-dispatch ``compiled`` deltas are measured against — and keeps
        a new kernel from silently dispatching outside the observatory."""
        with self._lock:
            for name, fn in jits.items():
                if fn is None:
                    continue
                full = f"{module}.{name}"
                try:
                    self._jits[full] = weakref.ref(fn)
                except TypeError:
                    # jit wrappers are weakref-able; a plain callable
                    # (tests registering stand-ins) rides a lambda ref
                    self._jits[full] = (lambda f=fn: f)
                self._jit_meta[full] = {
                    "donated": tuple(getattr(fn, "_donate_argnums", ()) or ()),
                }

    def registered_jits(self) -> dict[str, dict]:
        """Live view of registered wrappers: in-process cache sizes plus
        any static metadata (donation) captured at registration."""
        out: dict[str, dict] = {}
        with self._lock:
            items = list(self._jits.items())
            meta = dict(self._jit_meta)
        for full, ref in items:
            fn = ref()
            if fn is None:
                continue
            try:
                size = int(fn._cache_size())
            except Exception:  # noqa: BLE001 — a stand-in without a jit cache
                size = -1
            out[full] = {"cache_size": size,
                         "donated": list(meta.get(full, {}).get("donated", ()))}
        return out

    # -- dispatch ingestion ------------------------------------------------

    def observe_dispatch(self, family: str, seconds: float,
                         compiled: bool | None = None,
                         parts: dict | None = None) -> str:
        """Account one kernel dispatch (called from
        ``metrics.record_kernel_dispatch`` — the one funnel every ops/
        entry point already routes through). Returns the executable key
        and stashes it thread-locally for the engine's querylog capture
        (``last_dispatch``)."""
        p = dict(parts or {})
        unknown = set(p) - set(KEY_DIMS)
        if unknown:
            # mirror PhaseRecorder: a typo'd dimension must fail loudly,
            # never mint an unjoinable key shape
            raise ValueError(
                f"unknown executable-key dimension(s) {sorted(unknown)} "
                f"(canonical: {KEY_DIMS})"
            )
        p["family"] = family
        key = executable_key(p)
        is_compile = bool(compiled)
        provenance, entry_bytes = "in_process", None
        if compiled is not None:
            from ..ops.compile_cache import classify_dispatch

            provenance, entry_bytes = classify_dispatch(is_compile)
        device_s = float(seconds)
        now = time.time()
        with self._lock:
            rec = self._records.get(key)
            if rec is None:
                rec = self._records[key] = _ExecRecord(key, p)
            rec.dispatches += 1
            rec.last_dispatch_s = now
            # evict AFTER the new record carries its dispatch stamp: the
            # LRU min() must only ever pick a genuinely idle entry
            self._evict_locked()
            if compiled is not None:
                rec.provenance[provenance] = rec.provenance.get(provenance, 0) + 1
            if is_compile:
                rec.compiles += 1
                rec.compile_seconds += float(seconds)
                rec.last_compile_s = now
                if entry_bytes is not None:
                    rec.executable_bytes = entry_bytes
                self._note_compile_locked(family, p)
            else:
                rec.device_hist.observe(device_s)
        REGISTRY.counter("filodb_kernel_exec_dispatches", family=family).inc()
        if is_compile:
            REGISTRY.counter("filodb_xla_compiles", family=family).inc()
            REGISTRY.counter("filodb_xla_compile_seconds",
                             family=family).inc(float(seconds))
        self._local.last = {
            "executable_key": key,
            "compile_miss": is_compile,
            "family": family,
        }
        return key

    def _evict_locked(self) -> None:
        while len(self._records) > self.max_entries:
            oldest = min(self._records.values(),
                         key=lambda r: r.last_dispatch_s)
            del self._records[oldest.key]

    def _note_compile_locked(self, family: str, parts: dict) -> None:
        """Recompile-storm detection: slide the family's compile ring and,
        on crossing the threshold, identify which key dimension actually
        churned (the annotation /debug/kernels serves — "shapes keeps
        changing" is actionable; "something recompiles" is not)."""
        ring = self._compile_ring.setdefault(family, deque())
        now = time.monotonic()
        ring.append((now, {d: _fmt(parts.get(d)) for d in KEY_DIMS}))
        horizon = now - self.storm_window_s
        while ring and ring[0][0] < horizon:
            ring.popleft()
        if len(ring) > self.storm_threshold:
            if not self._storm_active.get(family):
                self._storm_active[family] = True
                REGISTRY.counter("filodb_xla_recompile_storms",
                                 family=family).inc()
            unstable = [
                d for d in KEY_DIMS
                if d != "family" and len({p[d] for _, p in ring}) > 1
            ]
            self._storms[family] = {
                "time": time.time(),
                "compiles_in_window": len(ring),
                "window_s": self.storm_window_s,
                "unstable_dims": unstable or ["none (cache churn/eviction)"],
            }
        elif len(ring) <= max(self.storm_threshold // 2, 1):
            self._storm_active[family] = False

    # -- engine-side capture ----------------------------------------------

    def last_dispatch(self) -> dict | None:
        """This thread's most recent dispatch identity: the
        ``{executable_key, compile_miss, family}`` the engine folds into
        the query's cost record (batched launches ride the scheduler's
        request stamping instead — the leader's thread observed them)."""
        return getattr(self._local, "last", None)

    # -- introspection -----------------------------------------------------

    def device_p50_ms(self, key: str) -> float:
        """Warm-dispatch device-time p50 (ms) for one executable key —
        the cost model's registry join: a querylog record with no kernel
        time of its own (fully cache-served) still prices at what its
        executable measurably costs when it does run."""
        with self._lock:
            rec = self._records.get(key)
            if rec is None:
                return 0.0
            return hist_quantile_est(rec.device_hist, 0.5) * 1e3

    def storm_annotations(self) -> dict[str, dict]:
        """Copy of the live recompile-storm annotations (family ->
        {time, compiles_in_window, window_s, unstable_dims}) — the
        scheduler's pre-warm trigger reads these without paying for a
        full snapshot."""
        with self._lock:
            return {k: dict(v) for k, v in self._storms.items()}

    def snapshot(self, limit: int | None = None) -> dict:
        """The /debug/kernels rendering:
        per-executable table sorted by dispatches, storm annotations,
        registered-wrapper cache sizes and the detector config."""
        with self._lock:
            recs = sorted(self._records.values(),
                          key=lambda r: (-r.dispatches, r.key))
            storms = {k: dict(v) for k, v in self._storms.items()}
        if limit is not None:
            recs = recs[: max(int(limit), 0)]
        return {
            "executables": [r.snapshot() for r in recs],
            "storms": storms,
            "jits": self.registered_jits(),
            "config": {
                "max_executables": self.max_entries,
                "storm_threshold": self.storm_threshold,
                "storm_window_s": self.storm_window_s,
            },
        }

    def totals(self) -> dict:
        """Aggregate proof line: compiles/dispatches and
        the fused/batched/mesh families that actually served traffic."""
        with self._lock:
            recs = list(self._records.values())
        fams = sorted({r.parts.get("family", "") for r in recs})
        return {
            "executables": len(recs),
            "dispatches": sum(r.dispatches for r in recs),
            "compiles": sum(r.compiles for r in recs),
            "compile_ms": round(sum(r.compile_seconds for r in recs) * 1e3, 3),
            "families": fams,
            "fused_families": [f for f in fams if "fused" in f],
        }

    def clear(self) -> None:
        """Test hook: drop accounting state (registered jits are kept —
        module-level registration happens once per process)."""
        with self._lock:
            self._records.clear()
            self._compile_ring.clear()
            self._storms.clear()
            self._storm_active.clear()


KERNELS = ExecutableRegistry()


def register_kernel_obs_collector() -> None:
    """Scrape-time gauge: live registry size (the executables the process
    is serving from — a steadily growing value is the storm detector's
    slow-burn sibling)."""

    def refresh():
        with KERNELS._lock:
            n = len(KERNELS._records)
        REGISTRY.gauge("filodb_xla_executables").set(float(n))

    REGISTRY.register_collector("kernel_obs", refresh)
