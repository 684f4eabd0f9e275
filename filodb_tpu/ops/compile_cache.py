"""Persistent JAX compilation cache (SURVEY §7: recompilation is the #1
risk; the cold stage+compile of the canonical 100k-series query is tens of
seconds).

XLA executables for the shape-bucketed kernel set are small and extremely
reusable — padding discipline (staging.pad_series/pad_time, kernels
.pad_steps) means a production process compiles a handful of programs and
then never again. Persisting them to disk makes that true ACROSS process
restarts too: a rolling deploy or crash-restart skips straight to warm
dispatch latencies instead of re-paying multi-second XLA compiles.

Placement — ONE rule, shared by the server and ``chip_smoke.py``
(``cache_dir``): ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it, else ``<checkout>/.jax-compile-cache``. The directory
is part of jax's cache key, so it is never derived from anything that moves
between runs (a data dir, ``$HOME``, a pid, the clock). The top-level
``compile_cache_dir`` config knob only turns the cache on (``"auto"``,
default) or off (``null``).

Thresholds are forced to zero so even the fast-compiling CPU-backend
programs persist (jax's defaults skip entries under 1s compile time, which
would exclude most of our kernel set on small shapes).
"""

from __future__ import annotations

import logging
import os
import threading

log = logging.getLogger("filodb_tpu.compile_cache")

_enabled_dir: str | None = None

# compile-provenance state (classify_dispatch): the persistent entries seen
# on disk so far — a compile event that added a file was a FRESH trace
# (jax wrote its serialized executable), one that didn't was served FROM
# the persistent cache. Initialized when the cache is enabled.
_seen_lock = threading.Lock()
_seen_entries: set[str] | None = None


def _list_entries(cache_dir: str) -> dict[str, int]:
    """{relative path: size} of every persistent-cache entry file."""
    out: dict[str, int] = {}
    for root, _dirs, files in os.walk(cache_dir):
        for f in files:
            p = os.path.join(root, f)
            try:
                out[os.path.relpath(p, cache_dir)] = os.path.getsize(p)
            except OSError:
                continue
    return out


def classify_dispatch(compiled: bool) -> tuple[str, int | None]:
    """Classify one kernel dispatch's compile provenance and feed the
    ``filodb_compile_cache_{hits,misses}_total{tier=}`` counters — the
    cache's own numbers the executable registry's per-key provenance must
    reconcile with (both sides are fed from THIS one call).

    - ``compiled=False``  -> ``("in_process", None)``: the jit cache hit —
      counted ``hits{tier=in_process}``, the steady state.
    - ``compiled=True``   -> the in-process cache missed
      (``misses{tier=in_process}``). With the persistent cache enabled the
      disk tells the rest: a NEW entry file means jax traced + compiled
      from nothing and persisted it (``("fresh", entry_bytes)``, counted
      ``misses{tier=persistent}`` — the returned size is the serialized
      executable, the observatory's executable-bytes figure); no new file
      means the compile was deserialized from disk
      (``("persistent", None)``, counted ``hits{tier=persistent}``).
      Without a persistent cache every compile is ``("fresh", None)``.

    Walks the cache dir only on compile events (rare by construction —
    SURVEY §7's whole point), never on warm dispatches.

    Attribution is best-effort under CONCURRENT compiles (mirroring the
    ``_jit_cache_size`` contract): two racing fresh compiles can swap
    classifications (the first diff sees the other's entry), and when a
    diff finds more than one new file the entry-bytes attribution is
    ambiguous and returns None rather than summing unrelated executables.
    The steady-state signal is exact — warm serving is all
    ``in_process``, and any persistent-tier activity at all means
    compiles are happening."""
    from ..metrics import REGISTRY

    if not compiled:
        REGISTRY.counter("filodb_compile_cache_hits",
                         tier="in_process").inc()
        return "in_process", None
    REGISTRY.counter("filodb_compile_cache_misses", tier="in_process").inc()
    if _enabled_dir is None:
        return "fresh", None
    with _seen_lock:
        global _seen_entries
        before = _seen_entries if _seen_entries is not None else {}
        now = _list_entries(_enabled_dir)
        new = [p for p in now if p not in before]
        _seen_entries = set(now)
    if new:
        REGISTRY.counter("filodb_compile_cache_misses",
                         tier="persistent").inc()
        # exactly one new entry (jax pairs each `…-cache` payload with an
        # `…-atime` sidecar — only the payload is the executable): it is
        # this compile's serialized form; several means racing compiles
        # landed together and per-file attribution would be a guess
        payloads = [p for p in new if not p.endswith("-atime")]
        return "fresh", (now[payloads[0]] if len(payloads) == 1 else None)
    REGISTRY.counter("filodb_compile_cache_hits", tier="persistent").inc()
    return "persistent", None


_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax-compile-cache",
)


def cache_dir() -> str:
    """The placement rule (module docstring): the environment's directory,
    else the checkout's."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or _CHECKOUT_CACHE_DIR


def enable_compile_cache() -> str | None:
    """Turn on jax's persistent compilation cache at ``cache_dir()``.

    Idempotent; returns the active dir, or None when the directory cannot
    be created (the cache is an optimization: a read-only checkout must not
    stop the server; chip_smoke.py, which needs it, checks jax's config
    for the directory). Must run before the first jit dispatch to benefit that
    process's cold start (later calls still help subsequent compiles)."""
    global _enabled_dir, _seen_entries
    import jax
    from jax.experimental.compilation_cache import compilation_cache as jcc

    d = cache_dir()
    if _enabled_dir == d:
        return d
    try:
        os.makedirs(d, exist_ok=True)
    except OSError as e:
        log.warning("persistent compile cache unavailable at %s: %s", d, e)
        return None
    # with the variable set at start-up jax already holds this value and
    # nothing here sets another
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # jax latches a cache-unused verdict at the FIRST compile: a process
    # that compiled anything before this call would silently never persist.
    # Reset so the dir takes effect — existing executables stay in the
    # in-process jit caches.
    jcc.reset_cache()
    _enabled_dir = d
    # seed the provenance baseline: entries already on disk must read as
    # persistent-cache HITS when a compile deserializes them, not as fresh
    # traces (classify_dispatch diffs against this set)
    with _seen_lock:
        _seen_entries = set(_list_entries(d))
    _register_ledger_account(d)
    log.info("persistent jax compile cache at %s", d)
    return d


class _CompileCacheProbe:
    """Ledger-account owner for the persistent compile cache: jax writes the
    entries, we only observe — the account is self-syncing from a disk walk
    (and also refreshes the entry-count gauge at scrape time).

    The walk is double-memoized: a TTL bounds how often the dir is stat'd
    at all, and past the TTL the walk itself only re-runs when the cache
    dir's mtime moved (jax writes entry files flat into the dir, so an
    add/remove bumps it) — steady state pays ONE stat per TTL instead of
    re-stat'ing every entry."""

    WALK_TTL_S = 15.0  # scrape-time collector: don't re-stat the dir per scrape

    def __init__(self, cache_dir: str):
        self.cache_dir = cache_dir
        self._stat_at = 0.0
        self._walked = False
        self._walked_bytes = 0
        self._walked_entries = 0
        self._mtime_ns = -1

    def walk_bytes(self) -> int:
        import time

        from ..metrics import REGISTRY

        now = time.monotonic()
        if self._walked and now - self._stat_at < self.WALK_TTL_S:
            return self._walked_bytes
        self._stat_at = now
        try:
            mtime_ns = os.stat(self.cache_dir).st_mtime_ns
        except OSError:
            mtime_ns = -2  # unreadable dir: fall through to the walk
        if self._walked and mtime_ns >= 0 and mtime_ns == self._mtime_ns:
            # nothing changed since the last walk — keep the memo (the
            # gauge re-sets cheaply so a registry reset still heals)
            REGISTRY.gauge("filodb_compile_cache_entries").set(
                float(self._walked_entries)
            )
            return self._walked_bytes
        total = entries = 0
        for root, _dirs, files in os.walk(self.cache_dir):
            for f in files:
                try:
                    total += os.path.getsize(os.path.join(root, f))
                    entries += 1
                except OSError:
                    continue
        REGISTRY.gauge("filodb_compile_cache_entries").set(float(entries))
        self._walked = True
        self._mtime_ns = mtime_ns
        self._walked_bytes = total
        self._walked_entries = entries
        return total


_probe: _CompileCacheProbe | None = None


def _register_ledger_account(cache_dir: str) -> None:
    """One compile-cache account in the device ledger (kind
    ``compile_cache``): re-registered (not stacked) when the dir changes."""
    global _probe
    from ..ledger import LEDGER

    # dropping the old probe unregisters its account via the weakref
    _probe = _CompileCacheProbe(cache_dir)
    LEDGER.register(
        _probe, "compile_cache", _CompileCacheProbe.walk_bytes,
        name=cache_dir, synced=True,
    )


def enable_from_config(config: dict) -> str | None:
    """``compile_cache_dir``: ``"auto"`` enables, ``null`` disables. A path
    is refused — placing the cache is JAX_COMPILATION_CACHE_DIR's job."""
    knob = config.get("compile_cache_dir", "auto")
    if not knob:
        return None
    if knob != "auto":
        raise ValueError(
            f"compile_cache_dir={knob!r}: the knob is \"auto\" or null; "
            "set JAX_COMPILATION_CACHE_DIR to place the cache"
        )
    return enable_compile_cache()
