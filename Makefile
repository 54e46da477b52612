# Convenience targets mirroring the reference's Makefile surface
# (all / benchmarking / tune / clean — reference Makefile:1-29).  The real
# build is standard Python packaging (pyproject.toml).

PY ?= python

.PHONY: all test benchmarking bench-explicit bench-small bench-blocktri \
	bench-blocktri-par bench-arrowhead bench-update bench-refine \
	bench-session tune audit lint lint-concurrency robust serve-smoke \
	serve-bench serve-replicas serve-trace clean

all: test

test:
	$(PY) -m pytest tests/ -x -q

# the reference's `make benchmarking` builds the bench drivers; here they
# are modules — run the whole driver suite on small shapes as a smoke
benchmarking:
	$(PY) -m capital_tpu.bench suite --n 1024 --m 8192 --k 256

# explicit-path constant tracker (docs/DISTRIBUTED.md "2.33x -> parity"):
# bench the explicit cholinv schedule and its persistent tile-cyclic
# spelling, appending unified ledger rows (measured + model copy-bytes +
# audit) so the BENCH/MULTICHIP trajectories carry the closure instead of
# it living only in docs.  Smoke shapes here; the flagship row on a TPU is
# --n 16384 --devices 1 (round-4 constant: 35.4 vs 68.0 TF/s).
bench-explicit:
	$(PY) -m capital_tpu.bench cholinv --n 1024 --mode explicit \
		--validate --ledger bench_explicit.jsonl
	$(PY) -m capital_tpu.bench cholinv --n 1024 --mode explicit \
		--balance tile_cyclic_persistent --devices 4 \
		--validate --ledger bench_explicit.jsonl

tune:
	$(PY) -m capital_tpu.autotune cholinv --n 2048 --out autotune_out

# small-N latency smoke (docs/PERF.md round 7): the batched-grid posv and
# lstsq buckets in --latency mode, per-dispatch p50/p95/p99 wall_ms on the
# CPU interpret rig, one bench:latency ledger record each.  The absolute
# numbers are emulation artifacts; what this pins is that the latency
# protocol, the fused kernels, and the ledger schema all work end to end.
bench-small:
	$(PY) -m capital_tpu.bench posv --platform cpu --n 32 --batch 4 \
		--nrhs 2 --dtype float32 --latency --calls 8 \
		--small-impl pallas --validate --ledger bench_small.jsonl
	$(PY) -m capital_tpu.bench lstsq --platform cpu --n 32 --batch 4 \
		--nrhs 2 --dtype float32 --latency --calls 8 \
		--small-impl pallas --validate --ledger bench_small.jsonl

# block-tridiagonal fast-path gate (docs/PERF.md round 11): the flagship
# (nblocks=64, b=128, f32) chain vs the SAME problems assembled dense at
# n=8192, gated at >= 25x per-problem wall-clock speedup with factor AND
# solve residuals held to the dense f32 tolerance — the structural
# O(n·b³) vs O(n³) win measured, not asserted.  CPU rig: the driver
# resolves 'auto' to the xla scan off-TPU (interpret-pallas would
# measure the emulator, not the algorithm).  The second row pins the
# --latency protocol + the bench:blocktri_latency ledger seam on a
# small validated shape.
bench-blocktri:
	rm -f bench_blocktri.jsonl
	$(PY) -m capital_tpu.bench blocktri --platform cpu --dtype float32 \
		--nblocks 64 --block 128 --batch 1 --nrhs 1 --validate \
		--min-speedup 25 --ledger bench_blocktri.jsonl
	$(PY) -m capital_tpu.bench blocktri --platform cpu --dtype float32 \
		--nblocks 8 --block 16 --batch 4 --nrhs 2 --latency --calls 8 \
		--validate --ledger bench_blocktri.jsonl

# block-arrowhead fast-path gate (docs/PERF.md round 15): the flagship
# (nblocks=64, b=128, s=32, f32) bordered chain vs the SAME problems
# assembled dense at n=8224, gated at >= 10x per-problem wall-clock
# speedup — lower than bench-blocktri's 25x ON PURPOSE: the arrowhead
# pays the widened chain solve (s extra columns every sweep) plus the
# Schur completion on top of the chain factor, so its structural margin
# is real but thinner.  The driver's f64-NumPy-side factor AND solve
# residual gates are always-on (no --validate flag to forget).  The
# second row pins the --latency protocol + the bench:arrowhead_latency
# ledger seam on a small shape.
bench-arrowhead:
	rm -f bench_arrowhead.jsonl
	$(PY) -m capital_tpu.bench arrowhead --platform cpu --dtype float32 \
		--nblocks 64 --block 128 --border 32 --batch 1 --nrhs 1 \
		--min-speedup 10 --ledger bench_arrowhead.jsonl
	$(PY) -m capital_tpu.bench arrowhead --platform cpu --dtype float32 \
		--nblocks 8 --block 16 --border 4 --batch 4 --nrhs 2 \
		--latency --calls 8 --ledger bench_arrowhead.jsonl

# parallel chain factorization gate (docs/PERF.md round 13): the
# partitioned (Spike) blocktri driver A/B'd against the sequential scan
# on the same problems.  On this 1-core rig the wall-clock columns are
# informational; the GATE is the jaxpr sequential scan-depth reduction
# (192 -> 45 trips at nblocks=64, P=8: >= 4x) plus pinned residual
# parity vs the sequential impl — both properties of the compiled
# program, honest regardless of core count.  The __graft_entry__ dry run
# then certifies the partitioned path on a real 8-device mesh (one chain
# per device, batch·P interiors distributed) with its own residual gate.
bench-blocktri-par:
	rm -f bench_blocktri_par.jsonl
	$(PY) -m capital_tpu.bench blocktri --platform cpu --dtype float32 \
		--nblocks 64 --block 16 --batch 2 --nrhs 2 --impl partitioned \
		--validate --min-depth-reduction 4 \
		--ledger bench_blocktri_par.jsonl
	$(PY) __graft_entry__.py

# online factor-maintenance gate (docs/PERF.md round 12): rank-k Cholesky
# update at the flagship serve shape (n=1024, k=16) vs refactor-from-
# resident-state — the honest cache-less alternative: the server already
# holds R, so the baseline reassembles S = RᵀR + VVᵀ and refactors
# (docs/PERF.md spells out why vs client-shipped-A the ratio would be
# smaller).  Gated at >= 5x per-problem wall-clock with f64-NumPy-side
# update AND downdate residuals held to tolerance, plus a 50-request
# serve smoke with mixed chol_update/posv_cached traffic gated on
# residency hit-rate >= 0.9 and zero steady-state recompiles
# (serve/factorcache.py).  obs serve-report re-gates the ledger record's
# factor_cache block — fails loudly if no record carries it.
bench-update:
	rm -f bench_update.jsonl
	$(PY) -m capital_tpu.bench update --platform cpu --n 1024 --k 16 \
		--batch 2 --dtype float32 --iters 5 --validate \
		--min-speedup 5 --min-hit-rate 0.9 --ledger bench_update.jsonl
	$(PY) -m capital_tpu.obs serve-report bench_update.jsonl \
		--min-residency-hit-rate 0.85
# (0.85, not the driver's 0.9: the record's factor_cache block carries
# LIFETIME counters, so the engine's per-bucket warmup lookups dilute the
# steady-state 0.92 the driver gates on delta counters)

# mixed-precision iterative-refinement gate (docs/PERF.md round 14): the
# guaranteed-tier posv program (f32 factor + f64 Wilkinson sweeps) vs the
# straight f64 factor on cond ~1e5 masters.  The speedup gate is on the
# FACTOR PHASE (f32 vs f64 potrf, >= 1.5x — measured ~1.9x, this rig's
# whole f32:f64 LAPACK gap); end-to-end latency rides the record ungated
# because on CPU the sweeps price in at XLA's ~2.4 GFLOP/s skinny-RHS
# potrs and land the ratio below 1 — docs/PERF.md round 14 owns that
# honesty note.  The accuracy half IS gated: refined backward error
# <= 10x the straight f64 factor's (measured ~0.9-1.8x) and <= the
# absolute f64 tolerance, all problems converged, plus the cond-1e12
# TSQR escalation probe (ortho <= 1e-13) and the mixed-tier serve smoke
# at zero steady-state recompiles.  obs serve-report then re-gates the
# smoke's request_stats record: sweep cap and converged fraction from
# the refine block (fails loudly if no record carries one).
bench-refine:
	rm -f bench_refine.jsonl
	$(PY) -m capital_tpu.bench refine --platform cpu --n 1024 --nrhs 4 \
		--batch 4 --dtype float64 --iters 3 --validate \
		--min-speedup 1.5 --max-resid-ratio 10 \
		--ledger bench_refine.jsonl
	$(PY) -m capital_tpu.obs serve-report bench_refine.jsonl \
		--max-refine-iters 6 --min-converged-frac 0.99

# streaming-session gate (docs/SERVING.md "Streaming sessions", round 19):
# the sliding-window steady-state cycle — extend(slide) onto the resident
# chain factor + contract(slide), a pure slice — vs refactoring the whole
# nblocks window, the only move a cache-less server has.  Gated >= 5x at
# the flagship geometry (structural ~nblocks/slide = 8x; measured ~9x on
# this rig), with always-on f64-NumPy residual gates on the MARGINALIZED
# slid window (head D <- L_k L_k^T — a wrong marginalization blows the
# gate) and the bitwise replay pin (extend-replay of the truncated chain
# == the contracted factor, max |delta| exactly 0).  The 50-request mixed
# session workload (bursty arrivals, long-tail lifetimes, all three
# accuracy tiers) then gates session hit-rate >= 0.85 post-warmup and
# zero steady-state recompiles; obs serve-report re-gates the ledger's
# serve:session_stats record — fails loudly if no record carries it.
bench-session:
	rm -f bench_session.jsonl
	$(PY) -m capital_tpu.bench session --platform cpu --dtype float32 \
		--nblocks 64 --block 128 --slide 8 --batch 1 --nrhs 2 \
		--iters 5 --min-speedup 5 --min-hit-rate 0.85 \
		--ledger bench_session.jsonl
	$(PY) -m capital_tpu.obs serve-report bench_session.jsonl \
		--min-session-hit-rate 0.85 --max-reseeds 0

# model-vs-compiled drift gate on the flagship configs (docs/OBSERVABILITY.md);
# compile-only — runs in CI without a TPU (exit non-zero on drift).  The
# bench.trace step is the phase-attribution gate: it decomposes a real
# (small-shape) cholinv wall into per-phase seconds, fails if the
# unattributed bubble fraction blows the budget OR if nothing could be
# attributed at all (dead-gate protection), and re-gates the ledger record
# through obs trace-report — the same double-entry discipline as lint.
# The generous 0.995 bound absorbs CPU-interpret emulation; what it pins
# is that attribution works end to end.
audit: serve-smoke serve-bench serve-replicas serve-trace bench-blocktri \
	bench-blocktri-par bench-arrowhead bench-update bench-refine \
	bench-session lint
	$(PY) -m capital_tpu.obs audit cholinv --n 4096 --platform cpu
	$(PY) -m capital_tpu.obs audit cacqr --m 16384 --n 512 --platform cpu
	$(PY) -m capital_tpu.obs robust-gate --platform cpu
	rm -f bench_trace.jsonl
	$(PY) -m capital_tpu.bench.trace cholinv --n 768 --bc 256 \
		--dtype float32 --iters 2 --platform cpu \
		--max-bubble-frac 0.995 --ledger bench_trace.jsonl
	$(PY) -m capital_tpu.obs trace-report bench_trace.jsonl \
		--max-bubble-frac 0.995

# static analysis gate (docs/STATIC_ANALYSIS.md): the program sanitizer over
# the flagship cholinv/cacqr/serve-bucket entry points (phase coverage,
# donation, cache-key hygiene, host sync, dtype drift, collective budget)
# plus the AST source lint, each appending one lint:report ledger record
# that `obs lint-report` re-gates — compile-only, no TPU needed
lint:
	rm -f lint_report.jsonl
	$(PY) -m capital_tpu.lint program --platform cpu \
		--ledger lint_report.jsonl
	$(PY) -m capital_tpu.lint source capital_tpu \
		--fail-on warn --ledger lint_report.jsonl
	$(PY) -m capital_tpu.lint concurrency --schedules 200 \
		--ledger lint_report.jsonl
	$(PY) -m capital_tpu.obs lint-report lint_report.jsonl \
		--require-pass program --require-pass source \
		--require-pass concurrency

# concurrency sanitizer alone (docs/STATIC_ANALYSIS.md "Concurrency
# sanitizer"): the guarded-by/lock-order static pass over the serve host
# plane plus the seeded interleaving explorer (>= 4 scenarios x 200
# schedules, every lint/invariants.py identity checked after every step)
# and the seeded-fault self-check that proves the gate is alive
lint-concurrency:
	$(PY) -m capital_tpu.lint concurrency --schedules 200

# serving self-check (docs/SERVING.md): mixed-bucket CPU workload through
# the SolveEngine, one serve:request_stats ledger record, gated on 100%
# post-warmup cache hit-rate (zero steady-state recompiles) + the pinned
# per-request residual gates inside the smoke itself.  --max-p99-ms-small
# gates the small-N (batched-grid pallas) request tail; the generous bound
# absorbs CPU-interpret emulation — what it pins is that the small path ran
# and reported (the gate fails loudly if no latency_ms_small block exists).
# The SECOND smoke is the cold-start proof: same workload, same (now warm)
# persistent cache dir, --max-compiles 0 — every executable must
# deserialize from disk, zero fresh XLA compiles (serve/cache.py).
# --max-queue-wait-ms fails loudly if no record carries the queue-wait /
# device latency split (serve/stats.py)
serve-smoke:
	rm -f serve_smoke.jsonl
	rm -rf serve_cache
	$(PY) -m capital_tpu.serve smoke --platform cpu --requests 50 \
		--persist-dir serve_cache --ledger serve_smoke.jsonl
	$(PY) -m capital_tpu.serve smoke --platform cpu --requests 50 \
		--persist-dir serve_cache --max-compiles 0 \
		--ledger serve_smoke.jsonl
	$(PY) -m capital_tpu.obs serve-report serve_smoke.jsonl \
		--min-hit-rate 1.0 --max-p99-ms-small 30000 \
		--max-queue-wait-ms 30000

# continuous-vs-sync A/B (docs/SERVING.md, docs/PERF.md): the fixed-seed
# closed-loop workload through both schedulers, one request_stats record
# per mode carrying the loadgen block (QPS, speedup) and the queue-wait /
# device split, gated on occupancy + zero steady-state recompiles via
# serve-report.  No speedup gate here: on shared CI hardware the overlap
# win is real but its magnitude is noisy — the record carries it, PERF.md
# tracks it
serve-bench:
	rm -f serve_bench.jsonl
	$(PY) -m capital_tpu.serve loadgen --platform cpu --requests 160 \
		--concurrency 16 --ledger serve_bench.jsonl
	$(PY) -m capital_tpu.obs serve-report serve_bench.jsonl \
		--min-hit-rate 1.0 --min-occupancy 0.25 \
		--max-queue-wait-ms 60000

# multi-replica serving smoke (docs/SERVING.md "Multi-replica serving"):
# 2 replicas behind the router sharing one persistent cache dir.  The COLD
# run warms the shared disk tier and proves the failure paths: an induced
# replica kill (in-flight requests re-dispatched, the replacement replica
# warms from disk, not by compiling) and an induced drain + resume under
# load — gated inside the smoke on zero dropped requests and zero
# steady-state recompiles.  The WARM run re-runs drain-only with
# --max-compiles 0: every replica must deserialize its whole ladder from
# the shared dir.  serve-report --aggregate then re-gates the ledger:
# >= 2 distinct replica tags (the it-really-was-multi-replica check) and
# aggregate hit-rate 1.0 across the merged records
serve-replicas:
	rm -f serve_replicas.jsonl
	rm -rf serve_replicas_cache
	$(PY) -m capital_tpu.serve replicas --platform cpu --replicas 2 \
		--requests 48 --persist-dir serve_replicas_cache \
		--kill-one --drain-one --ledger serve_replicas.jsonl
	$(PY) -m capital_tpu.serve replicas --platform cpu --replicas 2 \
		--requests 48 --persist-dir serve_replicas_cache \
		--drain-one --max-compiles 0 --ledger serve_replicas.jsonl
	$(PY) -m capital_tpu.obs serve-report serve_replicas.jsonl \
		--aggregate --min-replicas 2 --min-hit-rate 1.0

# per-request tracing + live-window telemetry gate (docs/OBSERVABILITY.md
# "Per-request tracing and live windows"): the smoke under --trace must
# land 100% complete monotonic span chains (admit -> ... -> respond) under
# the pinned 25 ms bubble tolerance — gated in-run AND re-gated from the
# ledger by serve-report (double-entry, same discipline as lint).  The
# loadgen leg runs both schedulers with 0.2 s rolling windows and a 60 s
# deadline, gated on >= 3 serve:window records whose internal coherence
# (percentile ordering, histogram/count sums) validate_serve_window pins
# on every read.  obs timeline then renders the chains end to end — it
# exits non-zero on an empty or malformed trace ledger, so a silently-dead
# producer can never pass
serve-trace:
	rm -f serve_trace.jsonl
	$(PY) -m capital_tpu.serve smoke --platform cpu --requests 42 \
		--trace --bubble-tol-ms 25 --ledger serve_trace.jsonl
	$(PY) -m capital_tpu.serve loadgen --platform cpu --requests 120 \
		--concurrency 8 --window-s 0.2 --min-windows 3 \
		--deadline-ms 60000 --trace --ledger serve_trace.jsonl
	$(PY) -m capital_tpu.obs serve-report serve_trace.jsonl \
		--min-trace-complete 1.0 --min-windows 3
	$(PY) -m capital_tpu.obs timeline serve_trace.jsonl

# breakdown detection / shifted-CholeskyQR recovery / fault-injection suite
# (docs/ROBUSTNESS.md); CPU rig — tests/conftest.py provides the 8-device
# virtual mesh and enables x64
robust:
	$(PY) -m pytest tests/test_robust.py tests/test_faultinject.py -q

clean:
	rm -rf autotune_out .pytest_cache bench_explicit.jsonl serve_smoke.jsonl \
		lint_report.jsonl bench_small.jsonl serve_bench.jsonl serve_cache \
		bench_trace.jsonl serve_replicas.jsonl serve_replicas_cache \
		bench_blocktri.jsonl bench_update.jsonl bench_refine.jsonl \
		bench_arrowhead.jsonl serve_trace.jsonl \
		bench_session.jsonl
	find . -name __pycache__ -type d -exec rm -rf {} +
