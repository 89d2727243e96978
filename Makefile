# Build/test/bench entry points (counterpart of the reference's maven
# reactor + build/buildcpp.sh + ci/ scripts, SURVEY.md §2.5).

PY ?= python

.PHONY: test test-all fuzz native sanitizers bench bench-all chip-smoke dryrun \
        tpu-lower \
        jni-test kudo-bench metrics-smoke trace-smoke chaos-smoke \
        perf-smoke fusion-smoke doctor-smoke server-smoke \
        lifeguard-smoke ingest-smoke dist-smoke analysis-smoke \
        profile-smoke elastic-smoke slo-smoke attribution-smoke \
        spill-smoke cache-smoke stats-smoke \
        serve-bench \
        nightly-artifacts ci ci-nightly clean

# tier-1 set: slow-marked tests (the subprocess fleet twins of the
# dist-smoke gate) are excluded here exactly like the driver's verify
# command; `make test-all` runs everything
test:
	$(PY) -m pytest tests/ -q -m 'not slow'

test-all:
	$(PY) -m pytest tests/ -q

fuzz:
	bash scripts/fuzz_test.sh

# native C++ kernels (also built on-demand at import; this forces it)
native:
	bash native/build.sh

# ASAN+UBSAN and TSAN builds of the native runtime + check driver
# (reference: sanitizer maven profile, pom.xml:237-283)
sanitizers:
	bash native/build_sanitizers.sh

# one JSON line, on jax.devices()[0]; exits non-zero without a TPU unless
# the caller sets JAX_PLATFORMS=cpu (the line names its platform)
bench:
	$(PY) bench.py

# the served query path on one TPU chip, every answer checked against a
# numpy reference (exits non-zero without a TPU).  CPU rehearsal:
#   JAX_PLATFORMS=cpu python chip_smoke.py --size toy
chip-smoke:
	$(PY) chip_smoke.py

bench-all:
	$(PY) bench_all.py

# deviceless proof that every device engine still lowers for platform
# "tpu" (jax.export AOT cross-lowering) — catches TPU-lowering breakage
# without the chip
tpu-lower:
	$(PY) scripts/tpu_lowering_gate.py

# end-to-end JVM binding smoke: real JVM -> JNI shim -> embedded
# CPython -> runtime (reference: JUnit suites on GPU pods).  Uses
# bazel's embedded JRE; skips cleanly when no JVM exists.
jni-test:
	@bash scripts/run_jni_smoke.sh; rc=$$?; \
	if [ $$rc -eq 2 ]; then echo "jni-test: skipped (no JVM)"; \
	elif [ $$rc -ne 0 ]; then exit $$rc; fi

# observability spine gate: tiny TPC-DS model query with metrics
# enabled must light up the whole spine — non-empty Prometheus
# exposition with per-op latency histograms and shuffle byte counters,
# an OOM-retry journal event under force_retry_oom, and a
# metrics_report rendering of the journal dump
metrics-smoke:
	$(PY) scripts/metrics_smoke.py

# structured tracing gate: a TPC-DS model query with span tracing on
# must produce a CONNECTED query->stage->op span tree, a kudo
# write->merge trace-context round trip (KTRX header extension), a
# loadable Perfetto/Chrome JSON via tools/trace_export, and
# span-duration histograms in the Prometheus exposition
trace-smoke:
	$(PY) scripts/trace_smoke.py

# robustness gate: TPC-DS model queries under a seeded, hot-reloaded
# fault-injection config (forced GpuRetryOOM + GpuSplitAndRetryOOM) and
# a CRC-corrupted kudo shuffle table must recover to byte-identical
# results through the retry runtime, with retry metrics/spans recorded;
# a corrupted stream with CRC disabled must still fail loudly
chaos-smoke:
	$(PY) scripts/chaos_smoke.py

# compile-cache gate: a two-batch 64-column conversion must hit the
# kernel compile cache on the second batch (zero new XLA executables
# for to-rows / from-rows / row-hash), stay under a generous wall-time
# threshold, match the cache-disabled eager bytes, and surface
# srt_jit_cache_* through the exposition + metrics_report cache table
perf-smoke:
	$(PY) scripts/perf_smoke.py

# whole-stage fusion gate: the fused q3/q5/q72 catalog pipelines must
# be byte-identical to the hand-fused oracles, compile exactly ONE
# executable per stage with ZERO recompiles on a second same-bucket
# query, match the window (q89) and rollup+rank (q67) numpy goldens,
# and light up srt_stage_fusion_total + the metrics_report stages table
fusion-smoke:
	$(PY) scripts/fusion_smoke.py

# flight-recorder gate: a chaos-injected retry exhaustion must freeze
# exactly ONE rate-limited incident bundle under the byte budget, and
# srt-doctor on that bundle must name the injected fault rule as root
# cause and the task id holding device memory at incident time
doctor-smoke:
	$(PY) scripts/doctor_smoke.py

# query-server gate: 8+ interleaved TPC-DS model queries from four
# competing tenants through the multi-tenant server, under the fault
# injector, must finish byte-identical to their serial runs with
# fair-share evidence in the metrics journal (per-tenant accounting,
# no tenant starved) and an over-quota tenant receiving the typed
# ServerOverloaded backpressure response instead of crashing neighbors
server-smoke:
	$(PY) scripts/server_soak.py

# query-lifeguard gate: under an injected hang + forced OOM
# exhaustion, the poison (tenant, query, schema-digest) signature must
# be quarantined (typed refusal) while 8+ interleaved neighbor queries
# finish byte-identical to serial; the hang must freeze a query_hang
# flight-recorder bundle that srt-doctor can triage (hung query + op +
# quarantined signature); server_drain must finish in-flight work,
# refuse new submits typed, flush via dumpio, and a restart must serve
# same-bucket batches with zero new jit-cache compiles
lifeguard-smoke:
	$(PY) scripts/lifeguard_smoke.py

# production-ingest gate: seeded parquet written once, a file-backed
# q3 (footer prune -> page decode -> device columns -> shared cached
# pipeline) must return bytes identical to the in-memory catalog
# runner both standalone and through the query server, match pyarrow's
# decode of the same file, light up io_read spans + srt_io_* bytes/s
# evidence in the metrics journal, and hold the arrow_ingest zero-copy
# pointer-identity contract through the shim
ingest-smoke:
	$(PY) scripts/ingest_smoke.py

# distributed-shuffle gate: a 2-process CPU fleet runs q5 + q72 with
# the kudo socket shuffle between ranks; shuffle bytes must cross the
# process boundary (per-link srt_shuffle_link_* > 0 on both peers),
# results must be byte-identical to the single-process pipelines, an
# injected corrupt link must be NAK'd and healed by the link retry,
# and every process's spans must stitch into ONE connected trace via
# the KTRX header (one root, zero orphans, cross-process links)
dist-smoke:
	$(PY) scripts/dist_smoke.py

# static-analysis gate: srt-lint must exit 0 on the tree (every
# project invariant holds, catalog cross-checked against the docs,
# pre-existing violations fixed or reason-suppressed), plan-verify
# must accept every plan/catalog.py shape and reject a broken plan
# with a typed PlanVerifyError naming the node, and lockdep must
# report ZERO acquisition-order cycles under the server soak workload
# while detecting the synthetic ABBA with counter/journal/bundle/
# doctor evidence
analysis-smoke:
	$(PY) scripts/analysis_smoke.py

# query-profile gate: one profiled session over the fused q3/q5/q72
# catalog pipelines must produce an EXPLAIN ANALYZE tree matching the
# 5-executable stage count (pad-waste + compile evidence live); a
# real 2-process q5 fleet with SPARK_RAPIDS_TPU_PROFILE=1 must merge
# into ONE fleet profile whose per-rank shuffle-link bytes reconcile
# exactly with each rank's metrics dump; srt-explain --diff must exit
# nonzero on an injected slowdown; disabled-mode hooks must stay at
# attribute-read cost
profile-smoke:
	$(PY) scripts/profile_smoke.py

# elastic-fleet gate (ROADMAP item 3): 4-process q5 with one slow rank
# (speculation must win) and one killed+respawned rank (survivors must
# rebalance, the rejoined worker must converge by replay) — byte-
# identical on every rank, evidence in metrics + journal, ONE stitched
# trace, doctor naming the dead and slow ranks, plus the in-process
# hot-partition re-split check
elastic-smoke:
	$(PY) scripts/elastic_smoke.py

# telemetry-plane gate (ISSUE 16): disabled sampler at attribute-read
# cost, window-ring delta conservation + fresh windowed percentiles,
# an injected slow tenant tripping EXACTLY ONE slo_burn bundle that
# srt-doctor attributes to that tenant (healthy neighbor at/above its
# objective), a 2-process elastic fleet whose rank-0 merged timeseries
# reconciles EXACTLY with each rank's own registry dump, and a
# deterministic `srt-top --once --json` digest
slo-smoke:
	$(PY) scripts/slo_smoke.py

# time-attribution gate (ISSUE 17): a clean profiled q5's ledger must
# conserve (buckets sum to the wall), an injected retry burn must stay
# conserved with dominant_overhead naming the cause, a 2-process fleet
# under a slow:dst:ms link fault must return byte-identical results
# while the cross-rank critical path names the slowed exchange edge
# with zero clamped (negative) edges, srt-explain --diff must exit
# nonzero attributing the delta to a shuffle bucket, --json outputs
# must be digest-stable, and disabled hooks at attribute-read cost
attribution-smoke:
	$(PY) scripts/attribution_smoke.py

# tiered spill store gate: a 4x-over-budget join must complete
# out-of-core BYTE-identical to the in-memory answer, a chaos
# OOM must be rescued by ensure_headroom (spill, not shed), a corrupt
# spill file must recompute from source, srt-explain --where must
# render a nonzero spill_wait bucket, the doctor must name the
# spilling task + tier, and the disabled path must stay <1us/call
spill-smoke:
	$(PY) scripts/spill_smoke.py

# 100-query two-tenant replay over 10 ingest batches: warm repeats
# must come back cache_hit, byte-identical, >=10x faster; incremental
# q5 must fold one batch per epoch and match a cache-off full
# recompute; a repeat submit must compile ZERO new executables
cache-smoke:
	$(PY) scripts/cache_smoke.py

# fused q5+q72 with the stats plane armed: per-node actuals reconcile
# EXACTLY with numpy recomputation (byte-identical outputs, zero
# extra executables on repeat); a seeded 100x misestimate fires
# exactly one cardinality_misestimate bundle and srt-doctor names
# the node; the disabled hook stays at attribute-read cost
stats-smoke:
	$(PY) scripts/stats_smoke.py

# zipf-skewed multi-tenant serving replay -> BENCH_serve_r01.json
# (per-tenant p50/p99 admission-to-result, throughput, SLO attainment)
serve-bench:
	$(PY) scripts/serve_bench.py

# sharding-compile check on 8 virtual host devices
dryrun:
	JAX_PLATFORMS=cpu \
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('ok')"

# one-command premerge gate (reference ci/Jenkinsfile.premerge:196-232):
# unit tests + OOM fuzz (python AND native adaptors differentially) +
# sanitizer builds + TPU lowering gate + multichip dryrun +
# observability + tracing smokes + bench.
# Fails loudly on the first red step.  bench.py fails without a TPU:
# on a CPU-only machine run `JAX_PLATFORMS=cpu make ci`.
ci: test fuzz native sanitizers tpu-lower jni-test dryrun metrics-smoke \
    trace-smoke chaos-smoke perf-smoke fusion-smoke doctor-smoke \
    server-smoke lifeguard-smoke ingest-smoke dist-smoke analysis-smoke \
    profile-smoke elastic-smoke slo-smoke attribution-smoke spill-smoke \
    cache-smoke stats-smoke
	$(PY) bench.py
	@echo "ci: all gates green"

# multi-threaded GIL-free kudo write bench + bulk string path MB/s
# (skips cleanly without a JVM, same contract as jni-test)
kudo-bench:
	@bash scripts/run_kudo_bench.sh; rc=$$?; \
	if [ $$rc -eq 2 ]; then echo "kudo-bench: skipped (no JVM)"; \
	elif [ $$rc -ne 0 ]; then exit $$rc; fi

# nightly artifact bundle (reference nightly-build.sh deploy stage):
# source tree snapshot + native libraries + benchmark/evidence JSON
nightly-artifacts:
	rm -rf dist && mkdir -p dist
	git archive --format=tar.gz -o dist/spark-rapids-tpu-src.tar.gz HEAD
	cp native/*.so native/jni/*.so dist/ 2>/dev/null || true
	cp BENCH_EXTRA.json dist/ 2>/dev/null || true
	ls -l dist/

# one-command nightly gate (reference ci/nightly-build.sh:26-64):
# the premerge set + the kudo/bulk JVM bench + the full benchmark
# sweep + the artifact bundle.
ci-nightly: ci kudo-bench bench-all nightly-artifacts
	@echo "ci-nightly: all gates green"

clean:
	rm -rf native/build
	find . -name __pycache__ -type d -exec rm -rf {} +
