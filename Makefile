# The commit gate. Run `make check` before EVERY snapshot commit —
# round 3 shipped with 38/252 tests red because this didn't exist.
# Mirrors the reference's CI gate (.github/workflows/tpcds.yml): the
# full suite plus the query-level validator matrix, both on the virtual
# 8-device CPU mesh. On the chip the proof is `python chip_smoke.py`
# (one process per chip).

PYENV = XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu

.PHONY: check check-fast check-faults check-supervisor check-trace \
	check-durability check-dist-obs check-network check-elastic \
	check-streaming check-profile check-zerocopy \
	check-pipeline \
	check-pipeline-soak \
	check-perf \
	check-perf-update check-obs check-history check-lint check-service \
	check-doctor check-flight check-executors test test-fast validate \
	validate-fast warm

check: check-lint test validate check-perf check-history check-service \
	check-doctor check-flight check-executors check-durability \
	check-dist-obs check-network check-elastic check-streaming \
	check-profile check-zerocopy
	@echo "CHECK OK — safe to commit"

# Static invariant gate (tools/blazelint): lock discipline, knob
# registry sync, resource pairing, hot-path gating, name-registry sync
# and a pyflakes-equivalent pass — stdlib ast only, no jax import, so
# it runs first (seconds) and fails fast. New findings must be fixed
# or added to LINT_BASELINE.json with a justification (README "Static
# analysis"). Emits LINT_r12.json.
check-lint:
	python -m tools.blazelint --json-out LINT_r12.json

# The every-commit bar (< 5 min): full unit suite minus the two
# slowest end-to-end suites, plus a 3-cell validator subset. Slow gates
# get skipped under pressure — that is how round 3 shipped red — so the
# fast tier exists to keep SOME query-level gate on every commit; run
# the full `make check` before snapshot commits.
check-fast: test-fast validate-fast
	@echo "CHECK-FAST OK — run full 'make check' before snapshots"

test:
	$(PYENV) python -m pytest tests/ -q

test-fast:
	$(PYENV) python -m pytest tests/ -q -x \
	  --ignore=tests/test_fuzz_scale.py \
	  --ignore=tests/test_validator.py

validate:
	$(PYENV) python validate.py --suite all

validate-fast:
	$(PYENV) python validate.py \
	  --queries q2_q06_core_agg,q3_join_agg_sort

# Chaos soak: sweep every fault-injection point x kind over the
# validator mini-catalogue; every armed run must recover to the pandas
# oracle (or fail classified) and leave no orphans/leaked reservations.
# Emits FAULTS_r06.json.
check-faults:
	$(PYENV) python tools/chaos_soak.py --kinds io,oom,stall \
	  --stall-ms 300 --json-out FAULTS_r06.json

# Supervisor soak: the same point x kind sweep — plus the "stall" kind —
# under the CONCURRENT supervised pool (4 workers, hang detection +
# straggler speculation armed). Stall cells must recover via watchdog
# kill + relaunch, answers must match the pandas oracle, and no cell may
# leave orphans or leaked reservations. Emits SUPERVISOR_r07.json.
check-supervisor:
	$(PYENV) python tools/chaos_soak.py --supervisor \
	  --json-out SUPERVISOR_r07.json

# Pipeline gate: I/O-bound shuffle microbench serial vs pipelined (must
# show >= 1.3x from overlapping synthetic I/O with consumer compute),
# plus the validator mini-catalogue with enable_pipeline off vs on (both
# directions within noise — the off path restores serial behavior, the
# on path must not slow real queries). Emits PIPELINE_r09.json.
check-pipeline:
	$(PYENV) python tools/pipeline_bench.py --json-out PIPELINE_r09.json

# Pipeline chaos soak: the fault sweep with the async pipeline layer
# kept live under every armed spec (pool-thread errors — including the
# io.prefetch queue hand-off — must classify + recover, answers must
# match the oracle, and no cell may leak prefetch streams, sinks, or
# pipeline memory reservations). Emits PIPELINE_SOAK_r09.json.
check-pipeline-soak:
	$(PYENV) python tools/chaos_soak.py --pipeline \
	  --json-out PIPELINE_SOAK_r09.json

# Trace gate: validator mini-catalogue tracing-off vs tracing-on — the
# enabled path must drop zero events at the default ring size and stay
# within noise of the disabled path, and the exported Chrome trace must
# be structurally valid. Emits TRACE_r08.json.
check-trace:
	$(PYENV) python tools/trace_report.py --bench --json-out TRACE_r08.json

# Perf-regression gate: the validator mini-catalogue against the
# committed PERF_BASELINE.json. Durations gate loosely (x2.5 + 2s —
# shared hosts are noisy); bytes_copied/moved per boundary gate tightly
# (x1.25 + 64KiB — byte counts are deterministic, a copy regression
# fails loudly). `make check-perf-update` rewrites the baseline after an
# intended change.
check-perf:
	$(PYENV) python tools/perf_baseline.py

check-perf-update:
	$(PYENV) python tools/perf_baseline.py --update

# Observability gate: catalogue A/B with resource accounting off vs on
# (sampler + live /metrics endpoint scraped mid-query and
# format-checked), one chaos cell under the monitor, and zero resource
# leaks. Emits OBS_r10.json.
check-obs:
	$(PYENV) python tools/perf_baseline.py --obs --json-out OBS_r10.json

# History gate: the catalogue recorded twice into a fresh history
# store, then a third pass with one 400ms serde.encode stall injected
# into q2 — the cross-run regression detector must flag the slowed
# stage with zero false positives on unperturbed stages, and the
# history-on catalogue must stay within noise of history-off. Emits
# HISTORY_r11.json.
check-history:
	$(PYENV) python tools/history_report.py --gate \
	  --json-out HISTORY_r11.json

# Multi-tenant service soak: 8 concurrent client sessions across 3
# tenants through runtime/service.QueryService — a clean round, a
# deterministic weighted-fairness probe, one round per representative
# (fault point x kind) with {"concurrent": true} specs, and an
# admission-stress round (1 slot, tiny queue). Every session must match
# the pandas oracle, rounds must leak nothing (consumers, pipeline
# streams, namespaced resources, orphans), breaker state must stay
# per-query, and overload must shed with typed rejections. Emits
# SERVICE_r13.json.
check-service:
	$(PYENV) python tools/chaos_soak.py --service \
	  --json-out SERVICE_r13.json

# Doctor gate: the validator catalogue run clean (every critical-path
# breakdown must sum to wall time within 5%, zero findings on clean
# queries), then two seeded perturbations the doctor must top-rank — a
# 400ms serde.encode stall (serde_bound) and a skewed-partition input
# (skewed_partition) — plus a byte-identical x3 determinism check and a
# mid-query scrape of the per-tenant blaze_slo_* gauges. Emits
# DOCTOR_r14.json.
check-doctor:
	$(PYENV) python tools/blaze_doctor.py --gate --json-out DOCTOR_r14.json

# Flight-recorder gate: the catalogue run clean with the recorder armed
# and live progress on (zero spurious dossiers, tap overhead under 1%
# min-of-repeats), a seeded 400ms serde.encode stall paired with an
# unmeetable 5ms tenant SLO through the service (exactly one slo_breach
# dossier, top finding serde_bound), and a mid-query /queries scrape
# (valid summary schema, monotone progress). Emits FLIGHT_r15.json.
check-flight:
	$(PYENV) python tools/blaze_inspect.py --gate --json-out FLIGHT_r15.json

# Process-executor gate (ISSUE 12): weak-scaling smoke at 1/2/4
# executor processes (task throughput must grow with seats), the
# validator catalogue carried by the pool at each seat count (answers
# diffed against the pandas oracle, >= 1 stage actually pooled), and
# SIGKILL / SIGTERM / hung kill-recovery rounds fired at a busy
# executor mid-stage — each must recover to the oracle with exactly one
# executor_death dossier, a shrink-then-recover capacity timeline, zero
# leaks, and zombie late results epoch-fenced. Emits EXECUTORS_r16.json.
check-executors:
	$(PYENV) python tools/chaos_soak.py --executors \
	  --json-out EXECUTORS_r16.json

# Durability gate (ISSUE 13): the corruption sweep bit-flips committed
# artifacts (shuffle .data frame, .index offsets, spill frame) at every
# CORRUPT_POINTS cell — each flip must be DETECTED by the checksum
# layer, the file QUARANTINED, shuffle outputs lineage-REPAIRED by
# re-running only the producing map task under a new epoch, and the
# answer still oracle-equal — plus the driver-crash round: a journaling
# subprocess driver SIGKILLed mid-query must, on restart, replay its
# write-ahead journal (verified committed stages reused with ZERO map
# tasks re-run, the crashed attempt billed failed with a driver_restart
# flight dossier) and answer oracle-equal. Emits DURABILITY_r17.json.
check-durability:
	$(PYENV) python tools/chaos_soak.py --durability --driver \
	  --json-out DURABILITY_r17.json

# Distributed-telemetry gate (ISSUE 14): a pooled chaos round (SIGKILL
# mid-stage) with the telemetry plane ON must answer oracle-equal AND
# yield ONE merged Chrome trace — driver + executor spans sharing
# query/task ids on per-executor pid rows, clock-aligned timestamps —
# with zero executors reporting dropped span rings and the run ledger
# carrying the workers' federated copy bytes; a telemetry on/off A/B
# over the pooled catalogue gates the plane's overhead below 2%.
# Emits DIST_OBS_r18.json.
check-dist-obs:
	$(PYENV) python tools/chaos_soak.py --dist-obs \
	  --json-out DIST_OBS_r18.json

# Partition-tolerance gate (ISSUE 15): every net.* wire-fault cell
# (delay / reset / blackhole / torn frame / duplicate delivery at the
# control channel, shuffle fetch, and telemetry paths) armed under a
# live 2-seat pool must answer oracle-equal with zero executor deaths
# and zero leaks; a transient control-socket reset must reconnect +
# resume (capacity untouched, no executor_death dossier, a
# control_reconnect trace event); an asymmetric partition held past
# executor_death_ms must cut exactly ONE dossier while the worker's
# lease expires and it self-fences (exit 17); and a rolling SIGTERM
# drain/restart of every seat under concurrent service load must lose
# zero queries with zero drain-attributed requeues. Emits
# NETWORK_r19.json.
check-network:
	$(PYENV) python tools/chaos_soak.py --network \
	  --json-out NETWORK_r19.json

# Elastic fleet & driver-HA gate (ISSUE 16): an 8-client catalogue
# burst against a 1-seat pool must autoscale UP on parked arrivals
# (typed scale_up decisions, ceiling respected) and drain back DOWN to
# the floor after quiesce through the decommission barrier (zero drain
# requeues, every answer oracle-equal); then a warm-standby subprocess
# must survive SIGKILL of the primary driver AND two of its four
# executors mid-query — epoch-bumped lease fencing, control-plane
# rebind with the two survivors ADOPTED, dead-writer journal replay,
# every query oracle-equal, exactly one driver_failover dossier, zero
# orphans. Emits ELASTIC_r20.json.
check-elastic:
	$(PYENV) python tools/chaos_soak.py --elastic \
	  --json-out ELASTIC_r20.json

# Durable exactly-once streaming gate (ISSUE 17): a checkpointed
# micro-batch stream over a growing parquet directory (QueryService
# session, 4-seat subprocess primary with fenced lease + manifest)
# must survive an executor SIGKILL mid-batch (checkpoints keep
# committing) AND a primary-driver SIGKILL with warm-standby takeover
# — the stream ADOPTED from its journal (streams_adoptable >= 1,
# never billed driver_restart), resumed from the last committed
# checkpoint (resumed_batches >= 1), final aggregation state
# pandas-oracle equal over EVERY published file (0 dropped, 0
# double-counted rows), checkpoint epochs strictly monotone across
# both drivers, exactly one driver_failover dossier. Emits
# STREAMING_r21.json.
check-streaming:
	$(PYENV) python tools/chaos_soak.py --streaming \
	  --json-out STREAMING_r21.json

# Continuous-profiling acceptance (ISSUE 19): seeded-stall attribution
# in the collapsed-stack export, pooled SIGKILL sidecar recovery of
# executor samples, and the profiler on/off overhead A/B (<2%).
check-profile:
	$(PYENV) python tools/chaos_soak.py --profile \
	  --json-out PROFILE_r23.json

# Zero-copy data-plane acceptance (tools/zerocopy_bench.py): same-host
# mmap shuffle A/B on the real server/client (latency collapse +
# moved-only booking), the q3 catalogue query on a live pool (mmap
# on/off, oracle-equal, copied-bytes drop), and a 2M-row string-heavy
# dict-encoding A/B against the pandas oracle. Emits ZEROCOPY_r24.json.
check-zerocopy:
	$(PYENV) python tools/zerocopy_bench.py \
	  --json-out ZEROCOPY_r24.json

# Pre-warm the persistent compile caches (runtime/compile_service):
# replays the shape manifest + the TPC-DS catalogue into the XLA cache.
# Drop JAX_PLATFORMS=cpu (run bare `python -m ...`) to warm an attached
# chip; override scale/budget via WARM_ARGS.
WARM_ARGS = --rows 20000 --budget-seconds 1800
warm:
	$(PYENV) python -m blaze_tpu.runtime.compile_service --warm $(WARM_ARGS)
