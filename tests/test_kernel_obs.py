"""Kernel & compile observatory (doc/observability.md "Kernel & compile
observatory"): the process-global executable registry, recompile-storm
detection, the querylog -> /debug/kernels join, compile-cache provenance
reconciliation.

Contracts pinned here:

- the warm canonical query with the observatory enabled (capture is
  always on) stays exactly ONE kernel dispatch and records ZERO new
  compiles, and its registry key is STABLE across warm dispatches;
- a shape-varying dispatch loop triggers a recompile storm whose
  annotation names the unstable key dimension;
- query-log records carry ``executable_key`` + ``compile_miss`` that join
  to the registry's /debug/kernels table (engine-level and over HTTP);
- standing-query refreshes publish querylog records under
  ``path=standing:delta|standing:full`` (the maintainer used to bypass
  the querylog entirely);
- compile-cache hit/miss counters split by tier reconcile with the
  registry's per-executable provenance (both fed from classify_dispatch).
"""

from __future__ import annotations

import json
import os
import tempfile
import time
import urllib.parse
import urllib.request

import numpy as np
import pytest

from filodb_tpu.coordinator.planner import PlannerParams, QueryEngine
from filodb_tpu.core.schemas import Dataset
from filodb_tpu.memstore.memstore import TimeSeriesMemStore
from filodb_tpu.memstore.shard import StoreConfig
from filodb_tpu.metrics import REGISTRY
from filodb_tpu.obs.kernels import KERNELS, executable_key
from filodb_tpu.obs.querylog import QUERY_LOG
from filodb_tpu.ops import aggregations as AGG
from filodb_tpu.testkit import counter_batch, kernel_dispatch_total

pytestmark = pytest.mark.observability

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_600_000_000_000
N_SAMPLES = 240
START_S = (BASE + 600_000) / 1000
END_S = (BASE + 1_800_000) / 1000
Q = "sum(rate(http_requests_total[5m]))"


def _make_engine(n_shards=4, n_series=16, **params):
    ms = TimeSeriesMemStore(StoreConfig())
    ms.setup(Dataset("ds"), list(range(n_shards)))
    ms.ingest_routed(
        "ds", counter_batch(n_series=n_series, n_samples=N_SAMPLES,
                            start_ms=BASE),
        spread=3,
    )
    return ms, QueryEngine(ms, "ds", PlannerParams(**params))


def _counter_value(name: str, **labels) -> float:
    key = (name, tuple(sorted(labels.items())))
    with REGISTRY._lock:
        m = REGISTRY._metrics.get(key)
        return m.value if m is not None else 0.0


def _record_for(snap: dict, key: str) -> dict | None:
    for e in snap["executables"]:
        if e["key"] == key:
            return e
    return None


# ---------------------------------------------------------------------------
# executable registry


class TestExecutableRegistry:
    def test_warm_canonical_query_one_dispatch_zero_compiles_stable_key(self):
        _ms, eng = _make_engine()
        eng.query_range(Q, START_S, END_S, 60)  # stage + compile
        eng.query_range(Q, START_S, END_S, 60)  # warm
        rec = QUERY_LOG.entries(1)[0]
        assert rec["path"] == "fused"
        key = rec["executable_key"]
        assert key, "warm fused query must carry its executable key"
        before_snap = _record_for(KERNELS.snapshot(), key)
        assert before_snap is not None, "querylog key must be in the registry"
        before_disp = kernel_dispatch_total()

        eng.query_range(Q, START_S, END_S, 60)

        assert kernel_dispatch_total() - before_disp == 1
        rec2 = QUERY_LOG.entries(1)[0]
        # key STABLE across warm dispatches, and the warm launch did not
        # compile — the observatory must never perturb the steady state
        assert rec2["executable_key"] == key
        assert rec2["compile_miss"] is False
        after_snap = _record_for(KERNELS.snapshot(), key)
        assert after_snap["compiles"] == before_snap["compiles"], \
            "warm dispatch recorded a new compile"
        assert after_snap["dispatches"] == before_snap["dispatches"] + 1
        # key anatomy: every canonical dimension is present in order
        assert key.startswith("family=")
        for dim in ("variant=", "epilogue=", "shapes=", "mesh=", "batch="):
            assert f"|{dim}" in key

    def test_dispatch_metrics_and_provenance(self):
        _ms, eng = _make_engine(n_series=8)
        eng.query_range(Q, START_S, END_S, 60)
        eng.query_range(Q, START_S, END_S, 60)
        key = QUERY_LOG.entries(1)[0]["executable_key"]
        rec = _record_for(KERNELS.snapshot(), key)
        # warm dispatches classify as in-process compile-cache hits; the
        # per-family dispatch counter moved
        assert rec["cache"]["in_process"] >= 1
        fam = rec["family"]
        assert _counter_value("filodb_kernel_exec_dispatches",
                              family=fam) >= rec["dispatches"]

    def test_unknown_key_dimension_rejected(self):
        with pytest.raises(ValueError, match="unknown executable-key"):
            KERNELS.observe_dispatch("x", 0.001, compiled=False,
                                     parts={"bogus": "1"})

    def test_capacity_eviction_drops_stale_entries_not_the_new_one(self):
        from filodb_tpu.obs.kernels import ExecutableRegistry

        reg = ExecutableRegistry(max_entries=16)
        for i in range(16):
            reg.observe_dispatch(f"evict_fam{i}", 0.001,
                                 parts={"shapes": f"S{i}"})
        # a 17th family past capacity must displace a stale entry and
        # then accumulate normally — never self-evict on insert
        for _ in range(3):
            reg.observe_dispatch("evict_fresh", 0.001,
                                 parts={"shapes": "S99"})
        snap = reg.snapshot()
        assert len(snap["executables"]) == 16
        by_fam = {e["family"]: e for e in snap["executables"]}
        assert "evict_fresh" in by_fam, "new record was self-evicted"
        assert by_fam["evict_fresh"]["dispatches"] == 3
        assert "evict_fam0" not in by_fam  # the stale one paid

    def test_registered_jits_report_cache_sizes(self):
        # the unfused aggregate dispatches the scalar wrapper here, so the
        # cache size does not rest on what ran in this process before
        _ms, eng = _make_engine(fused_aggregate=False)
        eng.query_range(Q, START_S, END_S, 60)
        jits = KERNELS.registered_jits()
        assert "ops.aggregations._segment_aggregate_jit" in jits
        assert jits["ops.aggregations._segment_aggregate_jit"]["cache_size"] >= 1
        assert any(k.startswith("ops.kernels.") for k in jits)
        assert any(k.startswith("ops.hist_kernels.") for k in jits)


# ---------------------------------------------------------------------------
# recompile-storm detection


class TestRecompileStorm:
    def test_shape_varying_loop_triggers_storm_naming_dimension(self):
        fam = "segment_stdvar"
        # drop accounting state (compile rings included): the widened
        # window must not re-interpret compiles other suites paid
        KERNELS.clear()
        before = _counter_value("filodb_xla_recompile_storms", family=fam)
        KERNELS.configure(storm_threshold=3, storm_window_s=300.0)
        try:
            vals = np.ones((6, 4), np.float32)
            gids = np.zeros(6, np.int32)
            # 5 distinct static group counts -> 5 fresh lowerings of one
            # family inside the window: the shape-churn storm
            for g in (811, 821, 823, 827, 829):
                AGG.segment_aggregate("stdvar", vals, gids, g)
        finally:
            KERNELS.configure(storm_threshold=5, storm_window_s=60.0)
        storms = KERNELS.snapshot()["storms"]
        assert fam in storms, f"no storm recorded for {fam}: {storms}"
        assert storms[fam]["unstable_dims"] == ["shapes"], \
            "the storm annotation must name the churning key dimension"
        assert storms[fam]["compiles_in_window"] >= 4
        assert _counter_value("filodb_xla_recompile_storms",
                              family=fam) == before + 1, \
            "one storm event, not one count per compile past threshold"

    def test_stable_shapes_do_not_storm(self):
        fam = "segment_group"
        KERNELS.clear()  # isolate from other suites' segment_group compiles
        KERNELS.configure(storm_threshold=3, storm_window_s=300.0)
        try:
            vals = np.ones((5, 4), np.float32)
            gids = np.zeros(5, np.int32)
            for _ in range(8):  # one compile then warm: no churn
                AGG.segment_aggregate("group", vals, gids, 739)
        finally:
            KERNELS.configure(storm_threshold=5, storm_window_s=60.0)
        assert fam not in KERNELS.snapshot()["storms"]


# ---------------------------------------------------------------------------
# querylog join + HTTP surface


class TestDebugKernels:
    @pytest.fixture()
    def server(self):
        from filodb_tpu.api.http import serve_background

        _ms, eng = _make_engine()
        srv, port = serve_background(eng, port=0)
        yield eng, port
        srv.shutdown()

    def test_querylog_key_joins_debug_kernels_over_http(self, server):
        eng, port = server
        base = f"http://127.0.0.1:{port}"
        q = urllib.parse.urlencode({
            "query": Q, "start": START_S, "end": END_S, "step": 60,
        })
        for _ in range(2):
            with urllib.request.urlopen(f"{base}/api/v1/query_range?{q}") as r:
                assert json.loads(r.read())["status"] == "success"
        with urllib.request.urlopen(f"{base}/debug/querylog?limit=1") as r:
            rec = json.loads(r.read())["data"][0]
        assert rec["executable_key"]
        assert rec["compile_miss"] is False  # second call was warm
        with urllib.request.urlopen(f"{base}/debug/kernels") as r:
            kern = json.loads(r.read())["data"]
        keys = {e["key"] for e in kern["executables"]}
        assert rec["executable_key"] in keys, \
            "querylog record must join the /debug/kernels table by key"
        assert "storms" in kern and "config" in kern
        assert kern["jits"], "registered wrappers must be listed"
        # ?limit= pages the table
        with urllib.request.urlopen(f"{base}/debug/kernels?limit=1") as r:
            assert len(json.loads(r.read())["data"]["executables"]) == 1


# ---------------------------------------------------------------------------
# standing refreshes in the querylog (the maintainer used to bypass it)


class TestStandingQuerylog:
    def test_refresh_publishes_standing_path_records(self):
        from filodb_tpu.standing import StandingEngine

        base = int(time.time() * 1000) - 3_600_000
        ms = TimeSeriesMemStore(StoreConfig())
        ms.setup(Dataset("ds"), range(2))
        ms.ingest_routed(
            "ds", counter_batch(n_series=8, n_samples=300, start_ms=base),
            spread=1,
        )
        eng = QueryEngine(ms, "ds", PlannerParams())
        st = StandingEngine(eng, {"enabled": True})
        sq = st.register(Q, step_ms=60_000, span_ms=1_800_000)
        try:
            assert st.refresh(sq) is not None  # cold: full evaluation
            st.refresh(sq)  # nothing changed: retained (delta plane)
            recs = [e for e in QUERY_LOG.entries(8)
                    if e["path"].startswith("standing:")]
            assert len(recs) >= 2
            assert recs[0]["path"] == "standing:delta"  # retained serve
            assert recs[1]["path"] == "standing:full"
            assert recs[0]["id"] != recs[1]["id"], \
                "each refresh must ring its own record"
            assert recs[1]["executable_key"], \
                "the full refresh's fused dispatch must carry its key"
            assert all(r["status"] == "ok" for r in recs[:2])
            assert recs[1]["stats"]["kernel_ms"] >= 0
        finally:
            st.unregister(sq.qid)


# ---------------------------------------------------------------------------
# compile-cache provenance reconciliation (satellite: tiered counters)


@pytest.fixture
def cache_env(monkeypatch):
    """Point the ONE placement rule at a scratch dir through the
    environment; afterwards put the process back on the directory the
    rule gives without the patch (what every FiloServer test uses)."""
    from filodb_tpu.ops import compile_cache as CC

    def place(path):
        if path is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        else:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(path))

    yield place
    monkeypatch.undo()
    CC.enable_compile_cache()


class TestCompileCachePlacement:
    def test_environment_variable_wins_and_nothing_overrides_it(
            self, cache_env, tmp_path):
        import jax

        from filodb_tpu.ops import compile_cache as CC

        cache_env(tmp_path / "from-env")
        # neither a data dir nor the knob moves the cache off the variable
        for cfg in ({}, {"store_root": str(tmp_path / "data")},
                    {"compile_cache_dir": "auto"}):
            assert CC.enable_from_config(cfg) == str(tmp_path / "from-env")
            assert (jax.config.jax_compilation_cache_dir
                    == str(tmp_path / "from-env"))
        assert not (tmp_path / "data").exists()

    def test_unset_means_the_checkout_dir_never_a_derived_one(
            self, cache_env, tmp_path, monkeypatch):
        from filodb_tpu.ops import compile_cache as CC

        cache_env(None)
        want = os.path.join(REPO, ".jax-compile-cache")
        # not derived from store_root, home, pid or time
        monkeypatch.setenv("HOME", str(tmp_path / "home"))
        monkeypatch.setenv("XDG_CACHE_HOME", str(tmp_path / "xdg"))
        a = CC.enable_from_config({"store_root": str(tmp_path / "data")})
        b = CC.enable_from_config({})
        assert a == b == CC.cache_dir() == want
        assert not list(tmp_path.iterdir())

    def test_knob_is_on_or_off_and_refuses_a_path(self, cache_env, tmp_path):
        from filodb_tpu.ops import compile_cache as CC

        cache_env(tmp_path / "c")
        assert CC.enable_from_config({"compile_cache_dir": None}) is None
        with pytest.raises(ValueError, match="JAX_COMPILATION_CACHE_DIR"):
            CC.enable_from_config({"compile_cache_dir": str(tmp_path / "p")})
        assert not (tmp_path / "p").exists()


class TestCompileCacheProvenance:
    def test_tiers_reconcile_with_registry_provenance(self, cache_env,
                                                      tmp_path):
        from filodb_tpu.ops import compile_cache as CC

        cache_env(tmp_path)
        assert CC.enable_compile_cache() == str(tmp_path)
        h_ip0 = _counter_value("filodb_compile_cache_hits",
                               tier="in_process")
        m_ip0 = _counter_value("filodb_compile_cache_misses",
                               tier="in_process")
        m_p0 = _counter_value("filodb_compile_cache_misses",
                              tier="persistent")
        vals = np.ones((3, 5), np.float32)
        gids = np.zeros(3, np.int32)
        AGG.segment_aggregate("min", vals, gids, 677)  # fresh trace
        AGG.segment_aggregate("min", vals, gids, 677)  # warm
        assert _counter_value("filodb_compile_cache_misses",
                              tier="in_process") == m_ip0 + 1
        assert _counter_value("filodb_compile_cache_hits",
                              tier="in_process") >= h_ip0 + 1
        # the fresh trace wrote a persistent entry (thresholds are
        # forced to zero) -> a persistent-tier miss, and the registry's
        # record carries the same classification + the entry bytes
        assert _counter_value("filodb_compile_cache_misses",
                              tier="persistent") == m_p0 + 1
        key = executable_key({
            "family": "segment_min", "variant": "general",
            "epilogue": "agg:min", "shapes": "S3xJ5xG677",
        })
        rec = _record_for(KERNELS.snapshot(), key)
        assert rec["cache"]["fresh"] == 1
        assert rec["cache"]["in_process"] == 1
        assert rec["executable_bytes"] and rec["executable_bytes"] > 0

    def test_dir_walk_memoized_on_mtime(self):
        from filodb_tpu.ops.compile_cache import _CompileCacheProbe

        d = tempfile.mkdtemp(prefix="filodb-cc2-")
        with open(os.path.join(d, "entry-a"), "wb") as f:
            f.write(b"x" * 100)
        probe = _CompileCacheProbe(d)
        probe.WALK_TTL_S = 0.0  # isolate the mtime memo from the TTL
        assert probe.walk_bytes() == 100
        walked_mtime = probe._mtime_ns
        # nothing changed: the memo serves without re-walking
        os.unlink(os.path.join(d, "entry-a"))
        os.rmdir(d)  # even a VANISHED dir serves the memo until mtime moves
        probe._mtime_ns = walked_mtime
        # re-create with different content + a bumped mtime -> re-walk
        os.makedirs(d)
        with open(os.path.join(d, "entry-b"), "wb") as f:
            f.write(b"x" * 250)
        os.utime(d, ns=(walked_mtime + 10**9, walked_mtime + 10**9))
        assert probe.walk_bytes() == 250

