# Test/bench entry points (reference analog: tests.mk / Makefile).
# The driver and CI call pytest directly; these targets document the
# supported modes.

PY ?= python

.PHONY: test test-slow test-deadlock test-race test-e2e bench bench-all bench-micro native metrics-lint lint lockcheck jitcheck determcheck hotpathcheck envcheck trustcheck determinism-smoke test-jitguard wire-smoke flight-smoke mesh-smoke health-smoke pipeline-smoke chaos-smoke ingest-smoke light-smoke fleet-smoke attr-smoke wan-smoke byz-smoke churn-smoke perf-gate perf-ledger

# default gate: soak-tier tests (@pytest.mark.slow — the 10k-sig mesh
# torture, chunk-variant compile matrix, 150-key rotation build,
# randomized-manifest e2e, interpret-mode pallas trace) are skipped;
# target <15 min single-core (reference analog: tests.mk:66-87 CI
# package splits). The r4 default gate had grown to 48 min.
# All six lints gate the default flow — metrics-lint runs lockcheck,
# jitcheck, determcheck, hotpathcheck, envcheck AND trustcheck too, so one
# prerequisite covers them (and all run inside tier-1 via
# tests/test_metrics.py + tests/test_lockcheck.py +
# tests/test_jitcheck.py + tests/test_determcheck.py +
# tests/test_hotpathcheck.py + tests/test_envcheck.py +
# tests/test_trustcheck.py).
test: metrics-lint determinism-smoke flight-smoke mesh-smoke health-smoke pipeline-smoke chaos-smoke ingest-smoke light-smoke fleet-smoke attr-smoke wan-smoke byz-smoke churn-smoke perf-gate
	$(PY) -m pytest tests/ -x -q

# everything, including the soak tier (~1 h single-core)
test-slow:
	CMT_TPU_SLOW_TESTS=1 $(PY) -m pytest tests/ -x -q

# go-deadlock build-tag analog (tests.mk:61): every core mutex gets a
# watchdog that dumps stacks and raises instead of hanging.
# Scoped to the concurrency-bearing planes: the watchdog multiplies
# the cost of every lock acquisition, which makes the (lock-free)
# device-kernel/crypto math suites hours-slow for zero signal.
test-deadlock:
	CMT_TPU_DEADLOCK=1 CMT_TPU_DEADLOCK_TIMEOUT=60 \
		$(PY) -m pytest tests/ -x -q \
		--ignore=tests/test_ops_field.py \
		--ignore=tests/test_ops_kernel.py \
		--ignore=tests/test_parallel.py \
		--ignore=tests/test_bls.py \
		--ignore=tests/test_crypto.py \
		--ignore=tests/test_crypto_openssl.py \
		--ignore=tests/test_abci_wire_compat.py \
		--ignore=tests/test_fuzz.py \
		--ignore=tests/test_fuzz_guided.py

# subprocess perturbation/misbehavior harness only (test/e2e analog)
test-e2e:
	$(PY) -m pytest tests/test_e2e_perturb.py tests/test_light_proxy.py -q

# containerized e2e: manifest-driven namespace containers (docker.go
# analog without a daemon) — real per-node network stacks + partitions
test-e2e-nsnet:
	$(PY) -m pytest tests/test_e2e_nsnet.py -q

# QA macro campaign: saturation sweep + latency CDF + RSS envelope +
# per-component profile (CometBFT-QA-v1.md methodology at localnet
# scale); writes docs/qa/data/
qa:
	$(PY) tools/qa_campaign.py
	$(PY) tools/qa_campaign.py --profile --rates 400

bench:
	$(PY) bench.py

bench-all:
	$(PY) bench_all.py

bench-micro:
	$(PY) tools/bench_micro.py

# go test -race analog: the tier-1 concurrency suites under both the
# lock-order graph (every cmtsync acquire feeds a global acquisition-
# order graph; cycles raise LockOrderError with both stacks) and race
# mode (unguarded cross-thread writes to _GUARDED_BY fields raise
# RaceError).  Scoped to the lock-bearing planes for the same reason
# test-deadlock is.
test-race:
	CMT_TPU_LOCKGRAPH=1 CMT_TPU_RACE=1 \
		$(PY) -m pytest tests/test_lockcheck.py tests/test_sync_tools.py \
		tests/test_metrics.py tests/test_reactors.py -q

# every registered metric field must be updated by some subsystem,
# and every update site must name a registered field (inverse check);
# ALSO runs lockcheck so one command gates both lints
# (also enforced in the tier-1 flow via tests/test_metrics.py)
metrics-lint:
	$(PY) tools/metrics_lint.py

# static guarded-by lint + lock-seam check (docs/concurrency.md):
# guarded fields accessed under their lock, annotations name real
# locks, no raw threading.Lock() in core packages
lockcheck:
	$(PY) tools/lockcheck.py

# static device-path lint (docs/device_contracts.md): jax.jit only
# through registered memoized seams keyed on the shape ladder, no jit
# closures over mutable module globals, audited host-sync waivers,
# kernel shape/dtype contracts declared and well-formed
jitcheck:
	$(PY) tools/jitcheck.py

# static replay-determinism lint (docs/determinism.md): nothing
# reachable from the registered transition roots reads the wall clock,
# randomness, the environment, or iterates a set — the state machine
# stays a pure function of (block, prior state); audited
# '# deterministic:' waivers
determcheck:
	$(PY) tools/determcheck.py

# static critical-path blocking lint (docs/determinism.md sibling):
# nothing reachable from the consensus step handlers / WAL / block
# persistence sleeps, spawns, or waits unbounded without a
# '# blocking ok: <stage>' waiver billing it to a critpath stage
hotpathcheck:
	$(PY) tools/hotpathcheck.py

# env-knob registry lint: every CMT_TPU_* read goes through a
# fail-loudly validated reader (cometbft_tpu/utils/env.py) or carries
# an audited '# env ok:' waiver, is documented in the
# docs/observability.md env table, and every documented knob is
# still read (inverse)
envcheck:
	$(PY) tools/envcheck.py

# wire-ingress taint lint (docs/trust_boundary.md): network-derived
# values reaching a consensus-state sink must pass a registered
# validator or carry an audited '# trusted: <validator>' waiver;
# wire-length allocations need a dominating cap or '# bounded: <cap>'
trustcheck:
	$(PY) tools/trustcheck.py

# all six lints in one process, each file's AST parsed once
# (tools/lint_all.py); `make test` runs the same set via metrics-lint
lint:
	$(PY) tools/lint_all.py

# replay-determinism smoke (ISSUE 18 acceptance): a live node with
# CMT_TPU_DETERMINISM=1 commits >= 5 heights writing per-height
# transition digests into the WAL, replays them digest-clean on
# restart (wal_replay + handshake + startup surfaces), and a seeded
# store tamper is caught as a DivergenceError naming the first
# diverging field.  Tier-1 runs these too; `make test` gates on this
# target alongside the other smokes
determinism-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_determcheck.py \
		-k "Smoke" -q

# go test -race analog for the DEVICE plane: the jit/contract suite
# under CMT_TPU_JITGUARD=1 — a post-warmup retrace raises RetraceError
# with both compile-site stacks; an implicit host<->device transfer in
# the sealed verify window raises at the offending line
test-jitguard:
	CMT_TPU_JITGUARD=1 $(PY) -m pytest tests/test_jitcheck.py -q

# wire-plane telemetry smoke: the loopback MConnection pair + RPC
# dispatch + event-bus assertions, standalone (tier-1 runs them too)
wire-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_metrics.py -k wire -q

# replication-plane smoke: boots a node stub, commits heights, scrapes
# /metrics + /debug/flight, and asserts the blocksync/statesync/proxy/
# WAL families and the flight ring are live (tier-1 runs these too;
# `make test` gates on this target alongside the three lints)
flight-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_metrics.py \
		-k "flight or replication" -q

# forced-8-host-device mesh equivalence: the sharded KEYED tier must
# bit-match the single-device keyed path (padded-tail + partial-key-set
# cases included) with zero steady-state retraces, and the
# keyed-by-default promotion must route warm small batches to the
# keyed tier (conftest forces the 8-device virtual CPU mesh; tier-1
# runs these too — `make test` gates on this target alongside the
# three lints)
mesh-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_parallel.py \
		-k "ShardedKeyed or KeyedWarm or KeyPoolMesh" -q

# device-health smoke: boot the prober against the host tier and
# assert the healthy gauge + a probe histogram sample land, plus the
# /debug/perf + /debug index round trips (tier-1 runs these too;
# `make test` gates on this target alongside the three lints)
health-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_health.py \
		-k "HealthSmoke" -q

# verify-queue smoke: queue round trip on the host tier, the
# deterministic double-buffer overlap proof (buffer N+1's host prep
# completes during buffer N's gated launch, overlap ratio > 0), and
# the bench --pipelined round trip with ledger rows (tier-1 runs the
# full tests/test_verify_queue.py suite too; `make test` gates on
# this target alongside the three lints)
pipeline-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_verify_queue.py \
		-k "RoundTrip or Overlap or PipelinedBench" -q

# chaos smoke: the dispatch-ladder liveness proof (docs/
# dispatch_ladder.md) — a single-validator node under CMT_TPU_CHAOS=1
# with a device-loss-then-recovery plan must commit >= 20 consecutive
# heights while the ladder demotes tier by tier to the host floor and
# re-promotes (a demotion + a promotion + liveness, asserted in one
# drive); tier-1 runs the full tests/test_dispatch.py suite too, and
# `make test` gates on this target alongside the other smokes
chaos-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_dispatch.py \
		-k "ChaosLivenessNode" -q

# ingest smoke: the device-batched CheckTx liveness proof (ISSUE 10)
# — a single-validator node under closed-loop admission saturation
# (signed txs through the VerifyQueue ingest lane, small mempool cap)
# must commit strictly-increasing heights while admission SHEDS
# (nonzero MempoolFullError/duplicate counters on /metrics): degrade
# by load shed, never by consensus stall.  Tier-1 runs the full
# tests/test_ingest.py suite too; `make test` gates on this target
# alongside the other smokes
ingest-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_ingest.py \
		-k "IngestSmoke" -q

# light smoke: the serving-plane liveness proof (ISSUE 13) — a
# single-validator node serving a sustained 10k-client light-sync
# fleet (light/serve.py through the VerifyQueue light_client lane)
# must commit strictly-increasing heights with zero loader errors and
# a measurable header-cache hit rate: serving load stays preempted
# below consensus, so header batches never park a live vote.  Tier-1
# runs the full tests/test_light_serve.py suite too; `make test`
# gates on this target alongside the other smokes
light-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_light_serve.py \
		-k "LightSmoke" -q

# fleet smoke: the cross-node SLO proof (ISSUE 15) — a 4-node
# SUBPROCESS localnet (one node mixed-version: CMT_TPU_TRACE_CTX=0)
# under sustained load must commit >= +3 strictly-increasing heights,
# produce ONE stitched cross-node Chrome trace containing a complete
# proposal -> gossip-hop -> quorum -> commit height tree with hops
# from >= 2 distinct origin nodes, serve /debug/fleet, and append the
# perfdiff-gated height_latency_p95_4node + localnet_sustained_4node
# rows to the perf ledger (CMT_TPU_FLEET_LEDGER=1 targets the ledger
# CMT_TPU_PERF_LEDGER names; the bare tier-1 run writes a scratch copy
# so test runs never dirty the tree).  Tier-1 runs the full
# tests/test_fleet.py suite too; `make test` gates on this target
# alongside the other smokes
fleet-smoke:
	JAX_PLATFORMS=cpu CMT_TPU_FLEET_LEDGER=1 $(PY) -m pytest \
		tests/test_fleet.py -k "FleetSmoke" -q

# scenario fleet (ISSUE 20): the hostile-condition drives, each
# landing its perfdiff-gated ledger row (CMT_TPU_FLEET_LEDGER=1 so
# the real ledger gets the point; bare tier-1 runs write a scratch
# copy).  Tier-1 itself keeps only the lite 4-node wan drive; these
# targets run the full 8-node matrix under the slow tier.
wan-smoke:
	JAX_PLATFORMS=cpu CMT_TPU_SLOW_TESTS=1 CMT_TPU_FLEET_LEDGER=1 \
		$(PY) -m pytest tests/test_scenarios.py -k "wan_8node" -q

byz-smoke:
	JAX_PLATFORMS=cpu CMT_TPU_SLOW_TESTS=1 CMT_TPU_FLEET_LEDGER=1 \
		$(PY) -m pytest tests/test_scenarios.py \
		-k "Byzantine" -q

churn-smoke:
	JAX_PLATFORMS=cpu CMT_TPU_SLOW_TESTS=1 CMT_TPU_FLEET_LEDGER=1 \
		$(PY) -m pytest tests/test_scenarios.py -k "Churn" -q

# attribution smoke: the critical-path proof (ISSUE 16) — a
# single-validator node under the always-on sampling profiler must
# commit >= +3 heights, serve non-empty SPAN-TAGGED folded stacks at
# /debug/profile, decompose every committed height into the stage
# taxonomy with residual < 20% of the wall, and a seeded 200 ms
# store/save_block slowdown must be NAMED dominant both by the
# `attribution_height_critical_stage` gauge and by perfdiff's
# stage explanation (`perfdiff --selftest` runs inside).  Tier-1 runs
# the full tests/test_critpath.py + tests/test_profiler.py suites
# too; `make test` gates on this target alongside the other smokes
attr-smoke:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_profiler.py \
		tests/test_critpath.py -k "AttrSmoke or SeededStoreSlowdown" -q

# perf regression gate: proves perfdiff's calibration on the seeded
# fixture pair (a 20% regression MUST fail, 3% noise MUST pass) —
# deterministic, so it gates `make test`.  Compare two real ledger
# points with `python tools/perfdiff.py OLD NEW`.
perf-gate:
	$(PY) tools/perfdiff.py --selftest

# back-fill/refresh the ledger CMT_TPU_PERF_LEDGER names from the
# result files bench.py / bench_all.py write at the repo root (they
# append new points themselves when the variable is set)
perf-ledger:
	$(PY) tools/perfledger.py --harvest

# build the in-tree C++ libraries through their loaders, so the
# artefacts carry the content key utils/native_build.py looks for
native:
	$(PY) -c "from cometbft_tpu.crypto import bls_native, ed25519_native; \
		print(ed25519_native._LIB.load() and ed25519_native._LIB.out); \
		print(bls_native._NATIVE.load() and bls_native._NATIVE.out)"

fuzz:
	python tools/fuzz.py --time $${FUZZ_TIME:-60}
