#!/usr/bin/env bash
# CI entry point (reference §2.12 runtests.sh role): build the optional
# native ETL library, then run the suite on the virtual 8-device CPU mesh
# (tests/conftest.py forces the platform), mirroring how the reference's
# Travis loop ran `mvn clean test` per matrix entry.
set -euo pipefail
cd "$(dirname "$0")"

make -C native || echo "native ETL build unavailable; numpy fallbacks"

# jaxlint gate (docs/static_analysis.md): AST analysis of the whole
# package against the committed analysis/baseline.json. Fails fast on
# any NEW trace-purity / host-sync / recompile / donation / lock
# finding — before spending minutes on the pytest suite.
JAX_PLATFORMS=cpu python tests/smoke_analysis.py

# Attention-kernel smoke (docs/perf_attention.md): interpret-mode fwd+bwd
# parity of the fused Pallas flash kernel vs dense_attention, plus the
# pallas/blockwise/dense dispatch fallback contract off-TPU (no crash,
# counter incremented, one-shot warning). Cheap (seconds) — gates before
# the suite like the jaxlint step.
JAX_PLATFORMS=cpu python tests/smoke_attention.py

# Pooling + fusion smoke (docs/perf_googlenet.md round 6): mask max-pool
# backward vs select-and-scatter autodiff, depthwise-conv avg pool vs
# reduce_window, the pooling_impl dispatch contract, and the sibling-
# conv fusion pass bitwise-forward on an initialized graph. Seconds —
# gates before the suite like the attention smoke.
JAX_PLATFORMS=cpu python tests/smoke_pooling.py

# Packed-varlen smoke (docs/perf_data_pipeline.md §PackToBucket, ISSUE
# 13): segment-masked flash kernel parity in interpret mode, the
# first-fit packing arithmetic, packed-score == unpacked-score
# exactness on a tiny net, and the packing metric families. Seconds.
JAX_PLATFORMS=cpu python tests/smoke_packing.py

python -m pytest tests/ -q "$@"

# Observability smoke (docs/observability.md): a real 2-epoch fit with
# span tracing on, then scrape GET /metrics off a live UIServer and
# assert train_iterations_total is nonzero. Fails the CI run if the
# registry, the endpoint, or the trace ring regresses end-to-end.
JAX_PLATFORMS=cpu python tests/smoke_observability.py

# Compile-cache smoke (docs/perf_compile_cache.md): run a tiny fit
# twice, each in a fresh process, against one temp persistent-cache dir
# and assert the second process reports cache HITS (warm start from
# disk, no XLA recompile) with both runs under the wall ceiling.
JAX_PLATFORMS=cpu python tests/smoke_compile_cache.py

# Resilience smoke (docs/robustness.md): SIGKILL a fitting child
# mid-checkpoint-write via the checkpoint.write fault point, auto-resume
# in a second process, and assert bitwise-identical params vs an
# uninterrupted same-seed control run.
JAX_PLATFORMS=cpu python tests/smoke_resilience.py

# Serving smoke (docs/serving.md): warmup a gateway, drive concurrent
# HTTP /predict traffic through a live checkpoint hot-swap, and assert
# zero dropped/errored requests, post-swap predictions bitwise from the
# new checkpoint, ZERO XLA compiles after warmup, and the serving
# metric families on the scrape surface.
JAX_PLATFORMS=cpu python tests/smoke_serving.py

# Serving chaos smoke (docs/serving.md §resilience): same gateway under
# a deterministic 20% serve.forward failure storm with an aggressive
# circuit breaker — every response typed (ok / batch_failed /
# breaker_open / shed), the breaker opens and recovers, zero compiles
# after warmup, zero hung requests (hard in-process alarm).
JAX_PLATFORMS=cpu python tests/smoke_chaos_serving.py

# Multi-model serving smoke (docs/serving.md §multi-model): three
# same-geometry heads fused into ONE channel-concatenated forward plus
# a batch-tier independent model, concurrent per-member HTTP traffic
# through a live PER-MEMBER hot-swap — all member requests 200, zero
# compiles after warmup, batch tier only ever sheds TYPED, starvation
# counter frozen without queued work. Hard signal.alarm guard.
JAX_PLATFORMS=cpu python tests/smoke_multimodel.py

# Request flight-recorder smoke (docs/observability.md §request flight
# recorder): recorder armed via env flag, concurrent HTTP through a
# fused pair + packed-admission model — every 200 response embeds a
# trace with monotonic non-overlapping phases summing to wall within
# 10%, zero compiles after warmup, and the exemplar ring captures
# EXACTLY the one chaos-delayed request with the delay attributed to
# the device phase. Hard signal.alarm guard.
JAX_PLATFORMS=cpu python tests/smoke_request_trace.py

# Serving control-loop smoke (docs/observability.md §"The serving
# control loop"): a live gateway with a deliberately mis-tuned linger
# under a tight tier SLO, AutoTuner at fast cadence, a batch-tier
# flood joining mid-run — >= 1 schema-valid ledgered move, zero
# guardrail violations, the linger measurably tightened, /debug/tuner
# rendering the decision trail over HTTP, and no freeze on a clean
# run. Hard signal.alarm guard.
JAX_PLATFORMS=cpu python tests/smoke_autotuner.py

# Cluster-health smoke (docs/robustness.md §cluster-health): fake-clock
# watchdog transitions (PeerLost/Desync), typed barrier timeout, and a
# real SIGTERM'd child writing a grace checkpoint then resuming
# bitwise-identically — under a hard signal.alarm so a watchdog
# regression can never wedge the gate itself.
JAX_PLATFORMS=cpu python tests/smoke_cluster_health.py

# Quantized hot-swap smoke (docs/serving.md §quantized): drive
# concurrent in-process traffic through a live `swap(quantize="int8")`
# promotion — zero non-typed failures, zero compiles after the
# quantized warm, post-swap drift within the canary budget, the
# precision="int8" label on entry/gauge/scrape — then a tight-budget
# gateway where the SAME swap canary-rejects, bumps the
# canary_rejected{precision="int8"} counter, and keeps serving the old
# fp32 tree bitwise. Canary both ways, one gate.
JAX_PLATFORMS=cpu python tests/smoke_quant_swap.py

# Decode smoke (docs/serving.md §decode): a gateway serving BOTH decode
# families (paged-KV transformer + streaming LSTM) under concurrent
# mixed-length HTTP /generate traffic — every response token-exact vs
# the naive full-recompute reference, typed 400/404 chain, a
# serve.decode_step chaos window isolated to exactly one rider with KV
# blocks drained, ZERO compiles after warmup, decode metric families
# scraped. Hard signal.alarm guard.
JAX_PLATFORMS=cpu python tests/smoke_decode.py

# Replica federation smoke (docs/serving.md §"Replica federation"): a
# front-end with two spawned replica subprocesses over real HTTP, a
# predict storm, a SIGKILL of one replica mid-traffic — every response
# 200 or typed, the dead replica evicted with the failover counters
# fired, the survivor still answering, every federation metric family
# in the /metrics scrape. Hard signal.alarm guard.
JAX_PLATFORMS=cpu python tests/smoke_federation.py
