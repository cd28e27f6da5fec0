# tpu-batch build/test entry points (reference Makefile analog:
# kube-batch, verify, run-test, e2e, coverage targets).

PY ?= python
CPU_ENV := JAX_PLATFORMS=cpu

.PHONY: all native test e2e perf perf-quick bench bench-smoke sim-smoke soak-smoke chaos-smoke micro-smoke shard-smoke failover-smoke latency-smoke diverge-smoke congest-smoke serving-smoke quality-smoke bench-compare verify kbtlint typecheck ci image clean

all: native

# Native components (greedy baseline / CPU fallback).
native:
	$(MAKE) -C kube_batch_tpu/native/csrc

# Unit + action + solver + e2e suites on the virtual CPU mesh.
# Long-horizon soaks (@pytest.mark.slow, e.g. the 2k-cycle chaos
# acceptance storm) are excluded here — run them explicitly with
# `pytest -m slow`.
test:
	$(PY) -m pytest tests/ -x -q -m "not slow"

e2e:
	$(PY) -m pytest tests/e2e -x -q

# Density perf harness at the reference's kubemark design scale
# (doc/design/Benchmark/kubemark/kubemark-benchmarking.md:40), plus the
# BASELINE config (5) multitenant reclaim scenario at 1k nodes run with
# BOTH allocate actions (tpu-batch solver vs reference-parity greedy)
# so the artifact carries the comparison row. ~25 min wall; perf-quick
# is the CI-sized tier (~2 min).
perf:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu.perf --pods 3000 --nodes 100 \
		--group-size 30 --out perf-artifact.json
	env $(CPU_ENV) $(PY) -m kube_batch_tpu.perf --scenario multitenant-compare \
		--timeout 900 --nodes 1000 --group-size 10 --out perf-multitenant.json

perf-quick:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu.perf --pods 500 --nodes 50 \
		--group-size 10 --out perf-artifact-quick.json
	env $(CPU_ENV) $(PY) -m kube_batch_tpu.perf --scenario multitenant-compare \
		--timeout 240 --nodes 100 --group-size 10 \
		--out perf-multitenant-quick.json

# Headline benchmark (real accelerator when present).
bench:
	$(PY) bench.py

# Sparse-path smoke: small shapes through the full production cycle
# with the top-K candidate solver FORCED (KBT_SOLVER_TOPK=8), asserting
# via the new sparse stats that the path actually engaged — exit 4 on a
# silent dense fallback. Fast (~seconds); runs in CI alongside pytest.
bench-smoke:
	env $(CPU_ENV) KBT_SOLVER_TOPK=8 $(PY) bench.py --smoke

# Deterministic-simulator smoke: a short seeded fault run (bind
# failures + node flaps + an injected cycle crash) through the REAL
# scheduler/cache/actions stack; the CLI exits nonzero on ANY invariant
# violation (oversubscription, split gang, lost/double-bound task,
# fair-share breach). doc/design/simulator.md. KBT_CHECK_CONTRACTS=1
# arms the runtime tensor shape/dtype contract validation
# (solver/contracts.py — the twin of kbtlint's shape-contracts pass) at
# the tensorize and device-pack choke points.
sim-smoke:
	env $(CPU_ENV) KBT_CHECK_CONTRACTS=1 $(PY) -m kube_batch_tpu sim \
		--cycles 120 --seed 7 \
		--faults "bind:0.05,node-flap:0.02,crash:0.02" \
		--node-churn 0.03 --quiet

# Scaled-down soak (the 100k-cycle reference run's CI tier): 2k virtual
# cycles with per-cycle telemetry, then the leak/drift detectors fit
# every watermark series (RSS, alloc blocks, jit cache, metrics label
# cardinality, fairness drift) — exit 4 on ANY detector trip. Uses the
# native backend (built by `make native`, ordered before this in ci)
# so 2k cycles stay ~30 s. doc/design/observability.md.
soak-smoke:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim --cycles 2000 --seed 3 \
		--backend native --soak --quiet

# Chaos smoke: a seeded fault storm through the DEVICE solve path
# (backend dense, so the containment ladder — not the native
# default-route — absorbs the injected solver exceptions/hangs). The
# CLI exits 1 on any invariant violation and 3 on any cycle error
# (--fail-on-cycle-errors): a wedge or an uncontained device fault
# fails the build. doc/design/robustness.md. KBT_LOCK_DEBUG=2 arms the
# order-asserting lock proxies (utils/lockdebug.py) AND the
# guarded-write witness — a lock-order violation anywhere in the storm
# raises with both acquisition tracebacks, and a registered
# lock-guarded attribute written without its lock raises with the
# writing site; either fails the cycle (doc/design/static-analysis.md).
chaos-smoke:
	env $(CPU_ENV) KBT_LOCK_DEBUG=2 $(PY) -m kube_batch_tpu sim \
		--cycles 250 --seed 11 \
		--backend dense \
		--faults "solver-exc:0.08,solver-hang:0.02,bind:0.05" \
		--fail-on-cycle-errors --quiet

# Micro-cycle smoke: the chaos-smoke fault storm with event-driven
# micro cycles carrying placement between periodic cycles (periodic
# every 4th virtual cycle, warm-path micro cycles in between). The
# degradation ladder and breaker (PR 7) must contain the injected
# solver faults on the micro path too, and the invariant checker runs
# every cycle — exit 1 on any violation, 3 on any cycle error.
micro-smoke:
	env $(CPU_ENV) KBT_LOCK_DEBUG=2 $(PY) -m kube_batch_tpu sim \
		--cycles 250 --seed 11 \
		--backend dense --micro-every 4 \
		--faults "solver-exc:0.08,solver-hang:0.02,bind:0.05" \
		--fail-on-cycle-errors --quiet

# Multi-device sharded-sparse smoke: record a seeded churn run through
# the SINGLE-device sparse solve (forced K=8), then REPLAY it on >=4
# simulated host devices with the task-sharded shard_map sparse solve
# forced (KBT_SPARSE_SHARD_MODE=flat) — the replay verifier compares
# every cycle's placements byte-for-byte against the recording, so a
# sharded-vs-single divergence exits 2, and --require-sparse-sharded
# exits 5 if the sharded path silently never engaged.
# doc/design/sparse-candidate-solver.md (sharded-solve section).
shard-smoke:
	env $(CPU_ENV) KBT_SOLVER=jax $(PY) -m kube_batch_tpu sim \
		--cycles 40 --seed 5 --backend sparse --topk 8 \
		--node-churn 0.03 \
		--trace /tmp/kbt_shard_smoke.jsonl \
		--fail-on-cycle-errors --quiet
	env $(CPU_ENV) KBT_SOLVER=jax KBT_SPARSE_SHARD_MODE=flat \
		$(PY) -m kube_batch_tpu sim --host-devices 4 \
		--replay /tmp/kbt_shard_smoke.jsonl \
		--backend sparse --topk 8 \
		--require-sparse-sharded --require-device-selection \
		--fail-on-cycle-errors --quiet

# Failover kill drill: the leader is hard-stopped at EVERY seeded cut
# point (pre-solve / post-solve-pre-drain / mid-bind-drain / mid-close,
# sim/failover.py) with bind faults layered on top; each successor
# takes the lease, replays the bind-intent journal against cluster
# truth (cache/recovery.py) and repairs any partial gang. Exit 1 on any
# invariant violation across a failover boundary, 3 on cycle errors,
# 6 if a required cut never fired or a recovery reported errors — then
# the recorded trace is REPLAYED and must match byte-for-byte
# (placements AND the failover/recovery blocks), exit 2 otherwise.
# doc/design/robustness.md (failover section); the committed
# FAILOVER_r13.json is one full drill's report.
failover-smoke:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--cycles 60 --seed 13 --backend native --arrival-rate 3 \
		--faults "bind:0.03" \
		--kill-at "8:pre-solve,20:post-solve-pre-drain,32:mid-bind-drain,44:mid-close" \
		--trace /tmp/kbt_failover_smoke.jsonl \
		--require-kill-cuts all --fail-on-cycle-errors --quiet
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--replay /tmp/kbt_failover_smoke.jsonl --backend native \
		--require-kill-cuts all --fail-on-cycle-errors --quiet

# Cluster-truth anti-entropy smoke (doc/design/robustness.md, event-
# stream hardening): a 300-cycle storm over the whole event-fault
# grammar — dropped/duplicated/reordered/stale watch events, injected
# relist failures, and corrupted solver results — with the ingest
# guards, gap-repair relist, per-cycle anti-entropy sweep, and
# post-solve validation all armed. Exit 1 on any invariant violation,
# 3 on any cycle error, 7 if any divergence was left unrepaired at run
# end (or no event fault actually fired — a vacuous storm proves
# nothing); then the trace REPLAYS and placements must match
# byte-for-byte (exit 2 on divergence).
diverge-smoke:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--cycles 300 --seed 15 --backend dense \
		--faults "event-drop:0.06,event-dup:0.06,event-reorder:0.05,event-stale:0.05,relist-fail:0.25,solver-corrupt:0.04,bind:0.03" \
		--node-churn 0.02 --antientropy-every 1 \
		--trace /tmp/kbt_diverge_smoke.jsonl \
		--require-divergence-repaired --fail-on-cycle-errors --quiet
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--replay /tmp/kbt_diverge_smoke.jsonl --backend dense \
		--require-divergence-repaired --fail-on-cycle-errors --quiet

# Congested-regime steady-state smoke (doc/design/cycle-pipeline.md
# §micro steady state): micro cycles primary, periodic demoted to
# every 8th tick, 5 ms virtual ticks. Leg 1 — sustained 10k
# pod-arrivals/s (20 jobs × ~2.45 pods per 5 ms tick) with bind
# faults: every queue's arrival→bind total p99 must hold the < 10 ms
# SLO (exit 9) and at most 20% of micro cycles may defer to the
# periodic authority (exit 9) — the rank-stable subset/solve path has
# to keep placing through completion churn, not punt. Leg 2 — 400-job
# burst storms into HALF the cluster (over-subscribed on purpose):
# the carried backlog must engage the subset solver at least once
# (exit 9 if the storm never forms a backlog) and drain without
# invariant violations or cycle errors.
congest-smoke:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--cycles 400 --seed 17 --backend dense \
		--micro-every 8 --period 0.005 \
		--nodes 64 --node-cpu-m 16000 --node-mem-mi 32768 \
		--arrival-rate 20 --arrival-profile sustained \
		--max-jobs-in-flight 4096 \
		--faults "bind:0.03" \
		--require-queue-p99 0.010 --max-micro-defer-ratio 0.20 \
		--fail-on-cycle-errors --quiet
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--cycles 300 --seed 19 --backend dense \
		--micro-every 8 --period 0.005 \
		--nodes 32 --node-cpu-m 16000 --node-mem-mi 32768 \
		--arrival-rate 4 --arrival-profile burst \
		--burst-every 100 --burst-size 400 \
		--max-jobs-in-flight 8192 \
		--faults "bind:0.05" \
		--require-warm-subset --max-micro-defer-ratio 0.20 \
		--fail-on-cycle-errors --quiet

# Mixed serving+batch congested smoke (doc/design/serving.md): the
# congest-smoke regime (micro cycles primary, 5 ms virtual ticks) with
# a serving deployment stream layered on top — annotated SLO replicas
# (50 ms arrival->bind target), replica churn, a 20% spot slice and two
# topology tiers across the node pool, plus bind faults. Gates:
# --require-serving-engaged (exit 10 if no SLO-targeted placement ever
# happened — a vacuous run proves nothing), serving attainment >= 99%
# and ZERO SLO violations on the virtual clock (exit 10), the serving
# replica-floor invariant family armed every cycle (exit 1), cycle
# errors fatal (exit 3). Batch-only bit-parity with the serving plugin
# loaded is pinned separately by tests/sim/test_serving_sim.py.
serving-smoke:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--cycles 400 --seed 23 --backend dense \
		--micro-every 8 --period 0.005 \
		--nodes 64 --node-cpu-m 16000 --node-mem-mi 32768 \
		--arrival-rate 12 --arrival-profile sustained \
		--serving-rate 2 --serving-slo 0.05 --serving-churn 0.05 \
		--reserved-frac 0.8 --node-tiers 2 \
		--max-jobs-in-flight 4096 \
		--faults "bind:0.03" \
		--require-serving-engaged --min-serving-attainment 99 \
		--max-serving-violations 0 \
		--fail-on-cycle-errors --quiet

# Placement-latency SLI smoke (doc/design/observability.md §5): a
# short high-arrival burst run must (1) stamp pods at arrival and
# carry them to bind-applied with a total-stage p99 present, (2) land
# the placement_p99:<queue> / latency_entries series in the soak
# telemetry dump (the series the drift/leak detectors watch), and
# (3) emit a decision-audit JSONL that parses AND replays
# byte-identical (virtual-clock stamping; wall clock never enters a
# record). Exit 2/3/4 name the failing layer.
latency-smoke:
	env $(CPU_ENV) $(PY) tools/latency_smoke.py

# Placement-quality scorecard smoke (obs/quality.py,
# doc/design/quality.md): (1) record a churny run dumping the
# per-cycle scorecard stream and assert the scorecard actually engaged
# (one card per cycle, placements scored); (2) replay it — the
# in-trace card comparison exits 2 on divergence and the dumped JSONL
# must be byte-identical (same contract as the audit log); (3) run the
# 2-seed paired flat-vs-two-level mini-study TWICE and pin the
# paired-stats determinism (same seeds → byte-identical study JSON).
quality-smoke:
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--cycles 24 --seed 7 --backend native \
		--node-churn 0.05 --faults "evict:0.05" \
		--trace /tmp/kbt_quality_smoke.jsonl \
		--quality-out /tmp/kbt_quality_smoke.quality.jsonl \
		--fail-on-cycle-errors --quiet
	$(PY) -c "import json; cards = [json.loads(l) for l in \
		open('/tmp/kbt_quality_smoke.quality.jsonl')]; \
		assert len(cards) == 24, len(cards); \
		assert any(c['churn']['placements'] > 0 for c in cards), \
		'scorecard never scored a placement'"
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim \
		--replay /tmp/kbt_quality_smoke.jsonl --backend native \
		--quality-out /tmp/kbt_quality_smoke.quality.replay.jsonl \
		--fail-on-cycle-errors --quiet
	cmp /tmp/kbt_quality_smoke.quality.jsonl \
		/tmp/kbt_quality_smoke.quality.replay.jsonl
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim-study \
		--preset twolevel --seeds 2 --cycles 10 --nodes 8 \
		--workers 4 --out /tmp/kbt_quality_study_a.json --quiet
	env $(CPU_ENV) $(PY) -m kube_batch_tpu sim-study \
		--preset twolevel --seeds 2 --cycles 10 --nodes 8 \
		--workers 4 --out /tmp/kbt_quality_study_b.json --quiet
	cmp /tmp/kbt_quality_study_a.json /tmp/kbt_quality_study_b.json

# Bench regression sentinel across the two newest committed bench
# rounds (noise-aware: canary-normalized thresholds + the explicit
# allowlist), THEN its own self-test: an injected 20% cycle_ms
# regression must flip the exit code — a sentinel that cannot see a
# regression is decoration.
bench-compare:
	$(PY) tools/bench_compare.py \
		$$(ls BENCH_r*.json | sort | tail -2 | head -1) \
		$$(ls BENCH_r*.json | sort | tail -1) \
		--allow-file tools/bench_allowlist.json
	$(PY) tools/bench_compare.py \
		$$(ls BENCH_r*.json | sort | tail -2 | head -1) \
		$$(ls BENCH_r*.json | sort | tail -1) \
		--self-test --allow-file tools/bench_allowlist.json

# Static checks (reference verify: gofmt/goimports/golint,
# Makefile:13-17): byte-compile + the AST lint (unused/duplicate
# imports, star imports, syntax). The metrics census that used to run
# here as a standalone pytest moved into the unified kbtlint census
# pass (next target) — the runtime twin test still runs in `make test`.
verify:
	$(PY) -m compileall -q kube_batch_tpu tests bench.py chip_smoke.py __graft_entry__.py
	$(PY) tools/lint.py

# Project-invariant static analysis (doc/design/static-analysis.md):
# lock-order graph (cycles, fence-leaf rule, blocking work under
# cache.mutex), dirty-ledger completeness, jit hygiene, guarded-by
# lock-ownership inference, replay-determinism taint, solver tensor
# shape/dtype contracts, and the doc<->code censuses (metrics / KBT_*
# env vars / flight-record keys / /debug/vars keys — exact, both
# directions). Findings fail the build unless allowlisted WITH a
# reason (tools/kbtlint/allowlist.json; stale entries fail too). The
# wall-clock budget fails the build if the full run crawls past 6 s —
# a new pass must not silently tax every CI run. (Raised 5 -> 6 when
# the subset-solve/micro-steady-state work grew the linted tree past
# the old margin; same pass set, just more lines to walk.) Then the
# self-test: a seeded violation of every pass must flip the exit
# code — a checker that cannot see a violation is decoration.
kbtlint:
	$(PY) -m tools.kbtlint --budget-seconds 6
	$(PY) -m tools.kbtlint --self-test

# Strict-mode type-check baseline over solver/ + cache/ with a
# committed suppression ledger (tools/typecheck_baseline.json, ratchet
# semantics). Uses mypy --strict when installed; this image has none,
# so the stdlib annotation audit holds the line (the ledger records
# which tool banked it). doc/design/static-analysis.md.
typecheck:
	$(PY) tools/typecheck.py

# The exact CI pipeline (.github/workflows/ci.yml), runnable locally:
# verify -> native -> test -> perf smoke -> bench smoke
# (reference .travis.yml:21-25).
# The smoke run writes its OWN artifact: `make ci` after `make perf`
# must not clobber the committed design-scale perf-artifact.json with a
# 300-pod smoke (that is exactly how the r3 artifact ended up 300/20).
ci: verify kbtlint typecheck native test bench-smoke sim-smoke soak-smoke chaos-smoke micro-smoke shard-smoke failover-smoke diverge-smoke latency-smoke congest-smoke serving-smoke quality-smoke bench-compare
	env $(CPU_ENV) $(PY) -m kube_batch_tpu.perf --pods 300 --nodes 20 \
		--group-size 10 --out perf-smoke.json
	env $(CPU_ENV) $(PY) bench.py --config small

# Scheduler container (reference deployment/images/Dockerfile analog).
image:
	docker build -f deployment/images/Dockerfile -t tpu-batch:latest .

clean:
	$(MAKE) -C kube_batch_tpu/native/csrc clean
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
