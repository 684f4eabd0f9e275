# filodb-tpu build/test shortcuts

NATIVE_DIR := filodb_tpu/native

.PHONY: all native test test-alerting test-chaos test-index test-ingest-chaos test-jitter test-multichip test-observability test-replica test-rollup test-scheduler test-standing serve clean

all: native

# one build definition: filodb_tpu/native/__init__.py (NativeLib) compiles
# each lib<stem>.so from its .cpp on first load and stamps it for this
# machine; this target just forces the five loads up front. Without g++ the
# runtime takes the numpy / pure-Python tiers and says so (native.tiers()).
native:
	python -c "from filodb_tpu import native; [print(k, '->', v) for k, v in native.tiers().items()]"

# default test run
test: native
	python -m pytest tests/ -q

# deterministic fault-injection suite (doc/robustness.md): retries,
# circuit breakers, partial results, shard-reassignment convergence
test-chaos: native
	python -m pytest tests/ -q -m chaos

# ingest-concurrency suite (doc/robustness.md "superblock consistency
# model"): superblock extend/revalidate under live ingest, staging-cache
# liveness vs the interval-aware insert guard, downsample claim/release
# races and crash-mid-commit redo
test-ingest-chaos: native
	python -m pytest tests/ -q -m ingest_chaos

# mesh-sharded fused suite (doc/perf.md "Mesh-sharded fused path"): sharded
# vs single-device vs reference parity over the full operator set, the
# warm-query-is-ONE-dispatch assertion on the forced 8-device CPU mesh, and
# the sharded canonical query + histogram_quantile end-to-end through the
# MULTICHIP dryrun entry
test-multichip: native
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m pytest tests/test_fused_mesh.py -q -m fused_mesh
	env JAX_PLATFORMS=cpu python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

# jitter-tolerant fused suite (doc/perf.md "Jitter-tolerant fused path"):
# fused-vs-reference parity on jitter5pct / jitter+holes grids across the
# epilogue families (hist_quantile included), warm single-dispatch
# assertions for regular/jittered/holey grids + the mesh twins on the
# forced 8-device CPU mesh, superblock grid-class isolation, and
# extension-under-ingest on a jittered block
test-jitter: native
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
		python -m pytest tests/test_fused_jitter.py -q -m fused_jitter

# query dispatch scheduler suite (doc/operations.md "Cross-query batching &
# admission control"): batched-vs-sequential bit parity across the epilogue
# families, the ONE-dispatch-per-coalesced-group assertion, tenant quota
# shedding + fairness, 429/Retry-After surfaces, batching-off golden
# equivalence
test-scheduler: native
	python -m pytest tests/ -q -m scheduler

# standing-query engine suite (doc/operations.md "Standing queries &
# recording rules"): delta-maintenance bit-equality vs full re-evaluation
# across regular/jitter/holes grids and under concurrent in-place
# extension, zero-dispatch retained refreshes, promotion/demotion
# hysteresis over the scheduler's recurrence ring, one-materialization SSE
# fan-out to N subscribers, and recording-rule write-back
test-standing: native
	python -m pytest tests/test_standing.py -q -m standing

# vectorized part-key index suite (doc/perf.md "Vectorized part-key
# index"): randomized property equivalence of the posting-bitmap index vs
# the retained set-based oracle (eq/in/literal-alt/prefix/general-regex/
# negative/empty-matcher x interval overlap x limit), incremental
# add/update_end_time/remove parity and a concurrent lookup-vs-ingest soak
test-index: native
	python -m pytest tests/test_index_bitmap.py -q -m index

# sketch rollup tier suite (doc/perf.md "Sketch rollup tier"): planner
# substitution (querylog path=rollup) + parity vs the raw path within the
# documented error bounds, bit-identical plan-time AND runtime fallback,
# chooser add/retire from querylog evidence, log-linear sketch property
# tests vs the numpy quantile oracle, psum-merge parity on the 8-device
# virtual mesh, and superblock pinning under eviction storms
test-rollup: native
	python -m pytest tests/test_rollup.py tests/test_sketch_property.py -q -m rollup

# replicated shard plane suite (doc/robustness.md "Replicated shard
# plane"): replica placement invariants, ingest fan-out with per-replica
# acks + lag watermarks, bit-equal failover to sibling replicas (control-
# plane kill, stale-mapping endpoint failure, open breaker as a routing
# signal), live rebalance with effect-log cutover proof + standing-query
# handoff, and the chaos storm: kill a node under 16 concurrent clients
# with partial results OFF and zero 5xx
test-replica: native
	python -m pytest tests/test_replica.py -q -m replica

# alerting plane suite (doc/observability.md "Alerting plane"): rule-file
# schema validation, the per-labelset pending→firing state machine with an
# injected clock (for:/keep_firing_for holds), ALERTS/ALERTS_FOR_STATE
# write-back + rehydration across restart, notification grouping/dedup +
# retry/backoff/breaker against a dead receiver, and the e2e proof:
# injected 5xx -> SLO burn -> firing -> exactly ONE grouped webhook
test-alerting: native
	python -m pytest tests/test_alerting.py -q -m alerting

# observability suite (doc/observability.md): trace propagation + stitching,
# slow-query log, query observatory (per-phase decomposition, query-log
# ring, _system round trips, SLO burn-rate rules), resource ledger +
# self-scrape, metrics exposition — plus the span-coverage + phase-coverage
# lint (every ExecPlan subclass executes under a span; every phase literal
# canonical and every fused path decomposed) and the metrics-doc lint
# (every filodb_* family emitted is documented, and vice versa)
test-observability: native test-alerting
	python tools/check_spans.py
	python tools/check_metrics.py
	python -m pytest tests/ -q -m "observability or chaos" --continue-on-collection-errors

serve:
	python -m filodb_tpu.cli serve --config conf/timeseries-dev.json

clean:
	rm -f $(NATIVE_DIR)/*.so $(NATIVE_DIR)/*.so.stamp
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
