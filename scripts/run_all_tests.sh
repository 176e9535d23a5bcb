#!/bin/sh
# Both test tiers, fast first (fail fast on cheap breakage), then the slow
# nightly consistency suites. ~17 min total on the 8-device CPU mesh.
set -e
cd "$(dirname "$0")/.."
# lgbtlint first: the static-analysis gate (docs/ANALYSIS.md) is the
# cheapest stage (< 10 s, no test models trained) and a jit-discipline /
# atomic-IO / lock regression fails here with file:line before any suite
# spends minutes training models
echo "=== stage: lgbtlint static-analysis gate ==="
python -m lightgbm_tpu.analysis
# telemetry next: cheapest suite, and a broken observability layer makes
# every later perf triage lie
echo "=== stage: telemetry fast tier ==="
python -m pytest tests/test_telemetry.py -x -q
# fleet observability next: trace-context propagation, the Prometheus
# /metrics surface, the cross-process trace collector, and the SLO
# burn-rate state machine — the layer the serving and perf gates below
# report through (docs/OBSERVABILITY.md "Serving observability")
echo "=== stage: observability fast tier ==="
python -m pytest tests/test_observability.py -x -q -m 'not slow'
# the analysis-engine suite rides with it (per-rule tripping fixtures +
# the repo-clean findings==baseline gate test; no models trained)
echo "=== stage: analysis-engine fast tier ==="
python -m pytest tests/test_analysis.py -x -q
# robustness fast tier next: checkpoint/resume bit-identity and the chaos
# guard paths protect every longer suite below from wasted reruns (the
# multi-process kill/retry/hang cases are in the slow tier)
echo "=== stage: robustness fast tier ==="
python -m pytest tests/test_robustness.py -x -q -m 'not slow'
# serving fast tier: the online path (bucketed compiled predictor,
# micro-batcher, hot reload) is bit-identity-gated against predict, so a
# regression here flags scoring breakage before the long suites run
echo "=== stage: serving fast tier ==="
python -m pytest tests/test_serving.py tests/test_wire.py -x -q -m 'not slow'
# fleet resilience fast tier: deadline propagation, bounded overload
# shedding, circuit breaker, replica restart-with-backoff, and the
# poisoned-candidate fleet-wide reload (docs/SERVING.md fleet section)
echo "=== stage: serving fleet fast tier ==="
python -m pytest tests/test_fleet.py -x -q -m 'not slow'
# multi-tenant serving fast tier: the HBM-resident multi-model cache
# (LRU evict / manifest-verified readmit, evict-path in-flight drain),
# per-tenant routing bitwise over HTTP + stacked dispatch with zero
# fresh traces, per-model SLO/drift isolation, /explain pred_contrib
# contract, and per-tenant promotion pointer keying
# (docs/SERVING.md "Multi-tenant serving")
echo "=== stage: multi-tenant serving fast tier ==="
python -m pytest tests/test_multimodel.py -x -q -m 'not slow'
# data/model quality fast tier: the train-time quality sidecar (binned
# feature profile + score histogram), the PSI/JS drift monitor's
# fire/clear state machine, the bitwise train-vs-serve shadow audit, and
# the /drift + fleet-report surfaces (docs/OBSERVABILITY.md "Data &
# model quality") — a lying drift monitor poisons every rollout decision
echo "=== stage: data/model quality fast tier ==="
python -m pytest tests/test_quality.py -x -q -m 'not slow'
# closed-loop freshness fast tier: TPU-native refit bitwise vs the host
# reference (weighted + decay), streamed-fresh-data byte identity,
# checkpoint/resume bit-identity through refit, generation-pointer
# monotonicity, and the pointer-only pipeline end-to-end with
# poison/torn chaos arms (docs/ROBUSTNESS.md "Closed-loop freshness")
echo "=== stage: closed-loop pipeline fast tier ==="
python -m pytest tests/test_pipeline.py -x -q -m 'not slow'
# drift bench smoke: reduced rows + short alternating QPS windows —
# gates the full behavior arm (alert FIRES under a +6-sigma covariate
# shift, CLEARS on recovery, shadow audit is 0-mismatch over >= 500
# rows) and sanity-checks the quality-on/off QPS ratio at a loosened
# 10% tolerance (the strict 3% gate needs the full-size windows and
# lives with the committed artifact); BENCH_DRIFT_SMOKE=1
# never clobbers the committed BENCH_DRIFT.json artifact (the
# BENCH_GOSS lesson)
echo "=== stage: drift bench smoke (BENCH_DRIFT=1) ==="
BENCH_DRIFT=1 \
BENCH_DRIFT_SMOKE=1 \
BENCH_HISTORY=0 \
    python bench.py
# distributed fast tier on a 4-device CPU mesh: the reduce-scatter comms
# path (psum vs reduce_scatter bit-identity, comms-bytes counters,
# straggler split) runs on every CPU verify at a second device count —
# conftest keeps a pre-set device-count flag, so this exercises D=4 while
# the full suites below run the default 8
# keep any caller-provided XLA flags, overriding only the device count
echo "=== stage: distributed fast tier (D=4) ==="
XLA_FLAGS="$(printf '%s' "${XLA_FLAGS:-}" \
    | sed 's/--xla_force_host_platform_device_count=[0-9]*//') \
--xla_force_host_platform_device_count=4" \
    python -m pytest tests/test_distributed_fast.py -x -q
# fused-sharded iteration tier on the same 4-device mesh: the default
# one-launch-per-iteration mesh path must match the unfused pipeline
# (round-1 byte + structural ulp identity), keep its state sharded
# across iterations, and resume bit-identically from a sharded snapshot
# (docs/DISTRIBUTED.md "fused iteration & sharded state")
echo "=== stage: fused-sharded iteration tier (D=4) ==="
XLA_FLAGS="$(printf '%s' "${XLA_FLAGS:-}" \
    | sed 's/--xla_force_host_platform_device_count=[0-9]*//') \
--xla_force_host_platform_device_count=4" \
    python -m pytest tests/test_fused_sharded.py -x -q
# wide-data learners on the same 4-device mesh: feature-parallel must be
# BYTE-identical to serial across the layout matrix with zero histogram
# wire traffic, voting (PV-Tree) must pass its layout/compaction/resume
# matrix — the second device count for both (the full suites run the
# default 8)
echo "=== stage: feature/voting learner tier (D=4) ==="
XLA_FLAGS="$(printf '%s' "${XLA_FLAGS:-}" \
    | sed 's/--xla_force_host_platform_device_count=[0-9]*//') \
--xla_force_host_platform_device_count=4" \
    python -m pytest tests/test_feature_parallel.py tests/test_voting.py \
    -x -q -m 'not slow'
# 2D rows x feature-groups mesh on 4 devices (the 2x2 identity matrix):
# plain/bagging/GOSS/multiclass-batched vs serial, fused single launch,
# state placement, the d_feat analytic comms model vs the telemetry
# gauge, and the mesh_shape 2D validation paths (docs/DISTRIBUTED.md
# "2D mesh") — run at exactly the device count the mesh needs
echo "=== stage: 2D-mesh tier (D=4, data:2,feature:2) ==="
XLA_FLAGS="$(printf '%s' "${XLA_FLAGS:-}" \
    | sed 's/--xla_force_host_platform_device_count=[0-9]*//') \
--xla_force_host_platform_device_count=4" \
    python -m pytest tests/test_mesh2d.py -x -q -m 'not slow'
# wide-data bench smoke: reduced rows/features, single device count —
# gates the structural payload claims (feature ships ZERO histogram
# bytes, voting <= 2k elected columns, both beat data-parallel by the
# predicted bytes/round ratios) plus AUC; BENCH_WIDE_SMOKE=1 never
# clobbers the committed BENCH_WIDE.json artifact (the BENCH_GOSS lesson)
echo "=== stage: wide-data bench smoke (BENCH_TASK=wide) ==="
BENCH_TASK=wide \
BENCH_WIDE_SMOKE=1 \
BENCH_WIDE_F="${BENCH_WIDE_F:-512}" \
BENCH_WIDE_ROWS="${BENCH_WIDE_ROWS:-6000}" \
BENCH_HISTORY=0 \
    python bench.py
# out-of-core ingest fast tier: sketch-vs-exact boundary equivalence,
# chunk/rank determinism, stream-vs-inmem tree bit-identity, and the
# binned-cache corruption matrix (docs/INGEST.md) — the loaders every
# suite below constructs its datasets through
echo "=== stage: out-of-core ingest fast tier ==="
python -m pytest tests/test_ingest.py -x -q -m 'not slow'
echo "=== stage: full fast tier ==="
python -m pytest tests/ -x -q
# GOSS sampling bench: the row-compaction speedup gate (docs/PERF.md
# "sample-strategy speedups") — sampled trees must run >= 2x faster than
# the unsampled arm at matched AUC, or the stage fails.  Reduced rows /
# iters keep the CPU stage to a few minutes; BENCH_ROWS/BENCH_GOSS_ITERS
# pre-set by the caller are respected (full-size on TPU runs).
echo "=== stage: GOSS sampling bench (BENCH_TASK=goss) ==="
BENCH_TASK=goss \
BENCH_ROWS="${BENCH_ROWS:-100000}" \
BENCH_GOSS_ITERS="${BENCH_GOSS_ITERS:-5}" \
    python bench.py
# the histogram formulations: the rule that picks one (row by row), the
# layout matrix of onehot and stream against segsum, packed-wire byte
# halving, route-fusion bit-identity + validation/env plumbing
echo "=== stage: histogram backend fast tier ==="
python -m pytest tests/test_hist_backends.py -x -q -m 'not slow'
# perf sentinel: compiled-program cost budgets (per-entry XLA flops,
# peak-HBM bytes, launches/iter on a fixed small workload vs
# PERF_BUDGETS.json — deterministic, so the gate holds on any test box)
# plus the wall-clock history compare, which only bites where
# BENCH_HISTORY.jsonl already holds >= 3 same-host runs of a metric
# (docs/OBSERVABILITY.md "Perf-regression sentinel")
# out-of-core ingest bench (reduced-size smoke): trees must be bitwise
# identical across the in-memory loader, the streaming loader, and a
# binned-cache re-run, and the subprocess stream arm must hold its
# peak-RSS delta under the configured budget at the gated rows/s
# (docs/INGEST.md; full-size numbers live in BENCH_INGEST.json)
echo "=== stage: out-of-core ingest bench (BENCH_TASK=ingest) ==="
BENCH_TASK=ingest \
BENCH_INGEST_ID_ROWS="${BENCH_INGEST_ID_ROWS:-60000}" \
BENCH_INGEST_ROWS="${BENCH_INGEST_ROWS:-400000}" \
BENCH_INGEST_FEATURES="${BENCH_INGEST_FEATURES:-16}" \
BENCH_INGEST_SMOKE=1 \
BENCH_HISTORY=0 \
    python bench.py
echo "=== stage: perf sentinel (cost budgets + bench history) ==="
python scripts/perf_sentinel.py --budgets PERF_BUDGETS.json --measure \
    --history BENCH_HISTORY.jsonl
# serving throughput bench: the binary-wire hot path must sustain
# BENCH_SERVE_QPS_MIN (default 10k) loopback QPS with a bounded window
# p99, zero errors, zero serve_predict recompiles after warmup, and
# bitwise exactness vs Booster.predict on every bucket size for
# numeric(+NaN), categorical, and multiclass models — over the wire
# (docs/SERVING.md "Binary wire protocol"); appends serve_binary_qps
# to BENCH_HISTORY.jsonl for the sentinel's wall-clock compare
echo "=== stage: serving throughput bench (BENCH_SERVE=1) ==="
BENCH_SERVE=1 \
BENCH_SERVE_ROWS="${BENCH_SERVE_ROWS:-60000}" \
BENCH_SERVE_MODEL_ITERS="${BENCH_SERVE_MODEL_ITERS:-30}" \
BENCH_SERVE_SECS="${BENCH_SERVE_SECS:-4}" \
BENCH_SERVE_HTTP_SECS="${BENCH_SERVE_HTTP_SECS:-2}" \
    python bench.py
# fleet chaos bench: 3 replicas under sustained loopback load while
# chaos SIGKILLs one and wedges another mid-run, with a mid-chaos
# fleet-wide /reload — gates on zero non-503 errors, bitwise-exact
# responses per claimed model sha256, bounded p99, replica restarts,
# and promotion convergence; writes BENCH_FLEET.json
echo "=== stage: fleet chaos bench (BENCH_FLEET=1) ==="
BENCH_FLEET=1 \
BENCH_FLEET_ROWS="${BENCH_FLEET_ROWS:-20000}" \
BENCH_FLEET_MODEL_ITERS="${BENCH_FLEET_MODEL_ITERS:-10}" \
BENCH_FLEET_SECS="${BENCH_FLEET_SECS:-8}" \
    python bench.py
# closed-loop pipeline chaos bench (reduced-size smoke): one CLI
# invocation drives train -> TPU refit -> gate -> atomic promote ->
# observe against a live 2-replica fleet while chaos poisons the refit,
# truncates the candidate, SIGKILLs the pipeline pre-pointer-write,
# tears the pointer, and a covariate shift forces the automatic
# post-promotion rollback — all under bitwise-checked traffic;
# BENCH_PIPELINE_SMOKE=1 never clobbers the committed BENCH_PIPELINE.json
echo "=== stage: pipeline chaos bench smoke (BENCH_TASK=pipeline) ==="
BENCH_TASK=pipeline \
BENCH_PIPELINE_SMOKE=1 \
BENCH_HISTORY=0 \
    python bench.py
# multi-tenant serving bench (reduced-size smoke): N same-shape tenants
# take mixed wire-v2 + /explain traffic bitwise-checked per tenant with
# ZERO fresh traces after warmup, the cache budget squeeze churns LRU
# evict/readmit under load with zero non-503 errors, and ONE
# pipeline_model_id promotion (+ a refused poisoned candidate) leaves
# the sibling tenant bitwise; BENCH_MULTIMODEL_SMOKE=1 never clobbers
# the committed BENCH_MULTIMODEL.json artifact
echo "=== stage: multi-tenant bench smoke (BENCH_TASK=multimodel) ==="
BENCH_TASK=multimodel \
BENCH_MULTIMODEL_SMOKE=1 \
BENCH_HISTORY=0 \
    python bench.py
# native sanitizer tier: builds native/binner.cpp under ASan/UBSan and
# drives every extern-C entry point (incl. the categorical bitset
# walker's word-index edges) — the reference's sanitizer CI lanes.
# Runs as its own labeled stage so a toolchain-less box reports WHY the
# lane did not run instead of silently skipping inside the slow suite.
echo "=== stage: native sanitizer tier (ASan/UBSan) ==="
if command -v g++ >/dev/null 2>&1; then
    python -m pytest tests/test_native_sanitizers.py -x -q -m slow
else
    echo "NOTICE: no g++ toolchain on this machine — native ASan/UBSan"
    echo "lane SKIPPED (install g++ with libasan/libubsan to enable)"
fi
echo "=== stage: slow consistency tier ==="
# sanitizers already ran (or were skipped with notice) in their own
# stage above — don't rebuild and rerun the ASan/UBSan binary here
python -m pytest tests/ -x -q -m slow \
    --ignore=tests/test_native_sanitizers.py
