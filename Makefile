# Developer entry points (counterpart of /root/reference/Makefile).
PYTHON ?= python

.PHONY: test test-e2e chaos chaos-matrix demo trace-demo scrub-demo tail-demo failover-demo fleet-demo fleet-soak transform-demo multichip-demo hot-demo load-demo docs docker lint analyze mutation clean

test:
	$(PYTHON) -m pytest tests/ -q --ignore=tests/e2e

test-e2e:
	$(PYTHON) -m pytest tests/e2e -q

# Fault-injection / resilience suite, including the slow soak variants.
# Schedules are seeded (fault.seed / FaultSchedule(seed=...)), so runs are
# deterministic and reproducible. TSTPU_LOCK_WITNESS=1 arms the runtime
# LockWitness AND RaceWitness (utils/locks.py): every lock acquisition order
# observed under chaos must stay a DAG, and every sampled shared-attribute
# mutation must hold its statically inferred guard (analysis/races.py),
# validating both static proofs against real executions (conftest fails the
# session on any recorded violation).
chaos:
	TSTPU_LOCK_WITNESS=1 $(PYTHON) -m pytest tests/ -q -m chaos

# Unified failure-policy chaos matrix (ISSUE 19): sweeps every FaultPlane
# kind (error/latency/partial/flaky; partial on data sites only) across
# every guarded I/O seam — storage read/write, peer forward, gossip probe,
# merged GCM device launch — with real component harnesses, and gates each
# cell on the policy invariants: zero byte corruption (torn reads surface
# as clean refusals, never wrong bytes), retry amplification within the
# policy cap per the process ledger, breakers opening under sustained
# faults + fast-failing while open + re-closing behind the heal (fake-clock
# drill plus the live peer/gossip boards), deadline-scoped ops returning
# within a hard wall bound (shed, not hang), and per-cell SLO verdicts ok
# with real samples after recovery traffic refills the burned budget.
# Deterministic for a given --seed; writes + re-validates the report.
chaos-matrix:
	$(PYTHON) tools/chaos_matrix.py --out artifacts/chaos_matrix_report.json

demo:
	$(PYTHON) demo/run_demo.py

# End-to-end tracing gate: upload+fetch through the HTTP gateway under the
# memory backend, one trace tree (client -> gateway -> RSM -> storage),
# written to artifacts/trace.json and validated as Chrome trace-event JSON.
trace-demo:
	$(PYTHON) tools/trace_demo.py --out artifacts/trace.json

# Integrity-scrubber gate: seeded FaultSchedule damages a filesystem-backed
# store at rest (corrupt byte, truncation, deleted object, orphan); one scrub
# pass must detect 100% of it with zero false positives, repair everything
# from a shadow source, and a second pass must come back clean. Writes and
# re-validates artifacts/scrub_report.json.
scrub-demo:
	$(PYTHON) tools/scrub_demo.py --out artifacts/scrub_report.json

# Tail-tolerance gate: a seeded FaultSchedule with jittered delay ranges
# stalls every 4th storage fetch; the identical workload runs hedging-off
# then hedging-on and must show hedged p99 < unhedged p99 with ZERO payload
# diffs; the admission gate must shed with 429 + Retry-After when saturated;
# an expired x-deadline-ms must fail fast (504 DeadlineExceededException,
# well under one attempt-timeout). Writes and re-validates
# artifacts/tail_report.json.
tail-demo:
	$(PYTHON) tools/tail_demo.py --out artifacts/tail_report.json

# Replication gate: a 2-replica store under seeded traffic, the primary
# hard-killed mid-run by a *:raise@from=N fault schedule. 100% of fetches
# must succeed with byte-identical payloads (health-probed failover, p99
# inside the deadline budget), a write during the outage must miss the
# quorum and roll back with ZERO orphans on the surviving replica, and one
# anti-entropy pass must converge the revived replica (chunkChecksums
# arbitration for the corrupt copy; second pass reports zero diffs). Writes
# and re-validates artifacts/failover_report.json.
failover-demo:
	$(PYTHON) tools/failover_demo.py --out artifacts/failover_report.json

# Fleet-mode gate: 3 in-process sharded gateways (consistent-hash routing +
# peer chunk-cache tier + cross-instance single-flight) over one shared
# store. 24 concurrent cold fetches of a Zipfian hot chunk must cost EXACTLY
# ONE backend read; >= 80% of the zipf workload must be served by the
# owner/peer cache tier; one instance is hard-killed mid-run (storage dead
# via fetch:raise@from=N, gateway stopped, survivors re-ring) with ZERO byte
# diffs across all responses; and a greedy tenant saturating the admission
# gate is shed 429 while a polite tenant is served. Writes and re-validates
# artifacts/fleet_report.json.
# LockWitness armed: 3 instances' worth of gateways, caches, pools, and
# single-flight slots hammering each other is the richest lock interleaving
# any suite produces; the demo asserts zero order violations at the end.
fleet-demo:
	TSTPU_LOCK_WITNESS=1 $(PYTHON) tools/fleet_demo.py --out artifacts/fleet_report.json

# Fleet soak gate: N REAL sidecar processes (python -m tieredstorage_tpu.sidecar)
# joined by --fleet-peers into a gossip-membership fleet with R=2 replicated
# ownership, under a seeded Zipfian fetch load. One instance is SIGKILLed
# mid-load and later restarted. Gates: zero byte diffs across the kill and
# rejoin, gossip convergence to each new view within the bounded number of
# protocol periods, ordered-owner failover onto the surviving replica
# (failover_hits >= 1) with the repeat pass served by the cache tier (no
# cache arc lost), and — every process running TSTPU_LOCK_WITNESS=1 — zero
# lock-order and zero guarded-by violations reported by each member's
# runtime witnesses (GET /fleet/ping?witness=1). Writes and re-validates
# artifacts/fleet_soak_report.json.
fleet-soak:
	$(PYTHON) tools/fleet_soak.py --out artifacts/fleet_soak_report.json

# Fused-window gate: one pipelined multi-window transform through the
# production TpuTransformBackend path on the host platform must cost exactly
# ONE fused GCM device dispatch (plus one h2d staging transfer and one d2h
# fetch) per window — cross-checked against the ops-level launch counter —
# with wire bytes identical to the multi-dispatch reference ops, a byte-clean
# round trip, tamper rejection, and the default bench window shapes eligible
# for the Pallas kernels by pure host logic. A batched-mode cross-check
# (ISSUE 15) re-runs the decrypt workload through the cross-request
# WindowBatcher from concurrent threads: dispatches_per_window and
# hbm_roundtrips_per_window must stay <= 1 THROUGH the merge (they drop
# below 1), every merged launch must still donate its staged buffer, and
# the demultiplexed bytes must equal the unbatched path's. Writes and
# re-validates artifacts/transform_report.json.
transform-demo:
	$(PYTHON) tools/transform_demo.py --out artifacts/transform_report.json

# Multichip gate: the sharded transform path on 8 forced host devices — the
# SAME production-path drill the driver's dryrun_multichip runs (shared via
# tieredstorage_tpu/parallel/multichip.py). Sharded windows must be
# byte-identical to unsharded for fixed AND varlen shapes in both
# directions, cost ONE logical fused dispatch per window at mesh_size=8
# with every staged buffer donated, pad non-divisible batches on the host
# without the padding reaching the wire, and the chunk-index
# all_gather/psum must agree with the host-side sizes. Writes and
# re-validates artifacts/multichip_report.json.
multichip-demo:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" $(PYTHON) tools/multichip_demo.py --out artifacts/multichip_report.json

# Hot-tier gate (decrypt once, serve many): a seeded Zipfian replay over a
# warm encrypted store runs through the device hot-window cache tier. Every
# replay read served hot must cost ZERO GCM device dispatches
# (ops.gcm.device_dispatches cross-checked per request), the hot-tier hit
# rate over the replay must be >= 90%, every byte must equal the cold path's,
# the retained device buffer must never be a donated operand
# (is_deleted() stays False), device-side ranged slices must match the
# pinned host mirror, and hot replay throughput must be >= 5x the cold
# (decrypting) path in the same run. Writes and re-validates
# artifacts/hot_report.json.
hot-demo:
	$(PYTHON) tools/hot_demo.py --out artifacts/hot_report.json

# Load + SLO chaos gate (ROADMAP item 4, ISSUE 14): a seeded closed-loop
# Zipfian produce/fetch workload over a 3-instance fleet and a 2-replica
# store, while a storage replica AND a fleet instance are killed mid-run.
# Judged by the observability plane itself, not hardcoded thresholds: every
# survivor's GET /slo must report all specs ok with real histogram samples
# and both burn-rate windows engaged (fetch p99 within the deadline budget,
# bounded shed rate, bounded error rate), the fleet-wide telemetry scrape
# must prove the replica kill was absorbed (replica-failovers-total >= 1)
# and the cache tier held, every fetched byte must match the source across
# both kills, GET /debug/requests must hold flight records with tier
# evidence, and — LockWitness armed — zero lock-order and zero guarded-by
# violations. ISSUE 15 added the ROADMAP-item-4 remainders: an OVERLOAD
# burst that saturates one survivor's admission window (the shed-rate SLO
# must bite — >0 sheds, the engine reports the burn — then ordinary
# traffic refills the budget back to all-ok), and a SCALED CAPACITY PROBE:
# 1024 concurrent consumer-replay streams through the full decrypt chain
# with cross-request GCM batching on vs off (byte parity, mean batch
# occupancy > 1, launches-per-window strictly below the unbatched control,
# p99 within SLO by the PR-14 engine, flight records carrying the shared-
# launch evidence). ISSUE 16 put the integrity daemons INSIDE the chaos
# window: every instance runs the scrubber + anti-entropy repairer on
# ~1s periods through both kills (each survivor must show verification
# progress strictly after the replica kill, zero corrupt chunks, SLO
# verdicts still all-ok), and the capacity probe re-runs with
# background-work-class scrub verification racing the same device queue —
# the work-class scheduler must keep the fetch SLO verdict ok while scrub
# throughput stays > 0 (fetch p99 with/without active scrub is recorded).
# ISSUE 18 added the predictive-readahead A/B: a cold massed sequential
# replay (concurrent consumers each replaying a chain of encrypted
# segments front to back, NO warm pass) with the ReadaheadManager tier on
# vs the identical chain without it — readahead must win BOTH replay p99
# and total GCM launches (speculative windows merge foreground windows
# into fewer ranged GETs + batched decrypts), hold a cold hit rate >= 90%,
# keep wasted speculative bytes within readahead.misprediction.max.ratio
# by the readahead-misprediction SLO spec's own verdict, continue across
# every segment boundary, and leave attributable readahead.window flight
# records.
# Writes artifacts/load_report.json + artifacts/BENCH_LOAD.json and
# re-validates both.
load-demo:
	TSTPU_LOCK_WITNESS=1 $(PYTHON) tools/load_demo.py --out artifacts/load_report.json --bench-out artifacts/BENCH_LOAD.json

docs:
	$(PYTHON) -m tieredstorage_tpu.docs.configs_docs > docs/configs.rst
	$(PYTHON) -m tieredstorage_tpu.docs.metrics_docs > docs/metrics.rst

docker:
	docker build -t tieredstorage-tpu -f docker/Dockerfile .

# Project-invariant static analysis (tieredstorage_tpu/analysis/): lock-order
# DAG + blocking-under-lock, guarded-by data-race inference (races),
# device-dispatch discipline on the fused window path (device-dispatch),
# Deadline discipline, bounded concurrency, monotonic clock, swallowed
# exceptions, config/metrics doc drift. Exits non-zero on any unsuppressed
# finding or stale suppression (tools/analysis_suppressions.txt is a
# burn-down list, not a grandfather clause). The JSON artifact is uploaded
# by CI next to the demo reports. Incremental developer mode for a small
# diff (sub-second, content-hash parse cache under artifacts/):
#   python -m tieredstorage_tpu.analysis --paths <changed files...>
analyze:
	$(PYTHON) -m tieredstorage_tpu.analysis --json artifacts/analysis_report.json

lint: analyze
	$(PYTHON) -m compileall -q tieredstorage_tpu tests tools

# Mutation testing (counterpart of the reference's pitest gate,
# /root/reference/build.gradle:24): flips operators in core pure-logic
# modules and requires the owning suites to notice.
mutation:
	$(PYTHON) tools/mutation_test.py --budget 190

clean:
	find . -name __pycache__ -type d -exec rm -rf {} +
	rm -f native/*.so
