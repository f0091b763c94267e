# Test tiers (see ROADMAP.md "Tier-1 verify" and pytest.ini markers).
#
#   make verify       — the tier-1 gate: fast suite (-m 'not slow') under
#                       the hard timeout the CI driver enforces.
#   make verify-slow  — the compile-heavy tier (-m slow): the checkpoint
#                       round-trip, full DORA e2e, and every other test
#                       excluded from tier-1 to keep it under its timeout.
#   make verify-all   — both tiers.
#   make verify-load  — slow-path fleet loadtest smoke: 2 worker
#                       processes, a few thousand exchanges, CPU-only,
#                       < 60 s — fleet regressions fail fast outside the
#                       slow tier.
#   make verify-chaos — seeded chaos sweep: the chaos-marked tests
#                       (kill-at-every-fault-point, auditor self-tests,
#                       scenario suite) plus a double run of
#                       `bng chaos run --seed 7` compared byte-for-byte
#                       (the bit-determinism acceptance gate, covering
#                       the three zero-downtime transition scenarios AND
#                       the five FULL-SCALE storm scenarios — flash
#                       crowd at 100k subscribers; the engine-swap/CoA
#                       scenarios compile the fused pipeline once,
#                       ~30 s on CPU; ~90-120 s/run total). The long
#                       soak lives under @pytest.mark.slow.
#   make verify-perf  — SLO engine tests (`perf` marker,
#                       tests/test_slo.py, < 30 s). The record of the
#                       system's speed is the benchmark's
#                       (`benchmark/`, `PERF_LEDGER.jsonl`, `PERF.md`).
#                       A prerequisite of `verify` (whose tier-1 line
#                       deselects `perf`; a bare ROADMAP tier-1 run
#                       still includes it).
#   make verify-storm — storm-suite tests (tests/test_storms.py, `storm`
#                       marker, < 60 s): fast deterministic variants of
#                       all five storms (same code as `bng chaos run`,
#                       reduced --storm-scale), the generator
#                       byte-identity proof, planted-violation tests for
#                       the v6/NAT-accounting/QoS-mirror audits, expiry
#                       batching + lease jitter, exhaustion hygiene.
#                       A prerequisite of `verify` (whose tier-1 line
#                       deselects `storm` so the suite runs once; a
#                       bare ROADMAP tier-1 run still includes it).
#   make verify-ops   — zero-downtime transition tests (< 60 s): live
#                       fleet resize / rolling restart / blue-green
#                       engine swap + rollback, the checkpoint N->M
#                       worker matrix, the `bng ctl` wire and the
#                       autoscaler (tests/test_ops.py, `ops` marker).
#   make verify-telemetry — telemetry tests with tracing ARMED via
#                       BNG_TELEMETRY=1 (< 30 s): disarmed-overhead
#                       bound, histogram merge laws, flight-recorder
#                       wrap + every anomaly trigger, Chrome-trace
#                       schema. The engine-compiling DORA e2e
#                       (TestDoraTracingE2E) runs in tier-1; this
#                       target deselects it and stays fast.
#   make verify-static — bngcheck static analyzer (< 30 s, no jax):
#                       `bng check` must exit 0 against the checked-in
#                       baseline (bng_tpu/analysis/baseline.json), then
#                       the analyzer's own planted-violation +
#                       clean-corpus tests run. Includes the
#                       concurrency-ownership pass (BNG060-BNG064):
#                       thread-entry discovery, call-graph context
#                       classification, lock-set propagation — warm
#                       runs reuse the mtime-keyed extraction cache
#                       (.bngcheck_cache.json). Part of `verify`: a PR
#                       that violates a dataplane invariant fails here
#                       before the test suite even starts.
#   make verify-sharded — the ICI-sharded SERVING path (ISSUE 12):
#                       `sharded`-marked tests on the forced
#                       8-host-device CPU mesh (< 60 s): steered-ring
#                       missteer accounting (exact split from legit
#                       slow-path punts), sharded checkpoint N->M and
#                       N->1->N re-shard round-trips + reject paths,
#                       sharded blue/green swap + crash-at-flip, the
#                       composed `bng run --shards 2` DORA-and-renewal
#                       end-to-end. A prerequisite of `verify` (whose
#                       tier-1 line deselects `sharded`; a bare ROADMAP
#                       tier-1 run still includes them).
#   make verify-express — AOT express OFFER-path gate (ISSUE 13):
#                       ALL `express`-marked tests (slow included):
#                       the geometry byte-identity matrix vs
#                       `_dhcp_jit`, descriptor-parse semantics, express-
#                       reply identity vs the codec-built reply, AOT
#                       cache hit-without-retrace and loud-miss
#                       fallback (counter + flight dump + ring-meta
#                       program identity), and the SLO device-budget
#                       smoke. A prerequisite of `verify` (whose tier-1
#                       line deselects `express`).
#   make verify-hostpath — vectorized host serving path (ISSUE 14):
#                       scalar-vs-vector byte identity over the frame
#                       corpus (classify/steer/peek kernels, PyRing
#                       assemble/complete/pops, batched admission,
#                       fleet pre-pass, staging pools, batched express
#                       render) in <60s. A prerequisite of `verify`
#                       (whose tier-1 line deselects `hostpath`; the
#                       ROADMAP tier-1 command still includes them).
#   make verify-wire  — AF_XDP wire pump (ISSUE 15): batch-pump
#                       bit-identity vs the scalar oracle over the
#                       edge-case corpus (partial fill, full fill
#                       ring, TX stall, headroom offsets, forged RX
#                       lengths), the frame-accounting satellite pins,
#                       and the memory-rung four-scenario serving twin
#                       (DORA + NAT punt + QoS drop + PPPoE through
#                       the full kernel-rings->pump->engine loop) in
#                       <60s. The veth e2e (slow tier)
#                       self-skips without CAP_NET_ADMIN. A
#                       prerequisite of `verify` (whose tier-1 line
#                       deselects `wire`; the ROADMAP tier-1 command
#                       still includes them).
#   make verify-sanitize — hotpath-marked engine/scheduler tests under
#                       BNG_SANITIZE=1 (transfer_guard + debug_nans):
#                       the dynamic cross-check of the static transfer
#                       lint. Best-effort on XLA:CPU (d2h guard inert
#                       there — analysis/sanitize.py documents the
#                       asymmetry); compile-bound, so not in tier-1.
#                       Also arms the @owned_by ownership assertions
#                       and re-runs the race-marked interleaving tests
#                       (tests/test_concurrency.py): the PR-7 race
#                       schedules forced with barriers, cross-context
#                       mutations raising OwnershipViolation.

SHELL := /bin/bash
PY ?= python
TIER1_TIMEOUT ?= 870
PYTEST_FLAGS = -q --continue-on-collection-errors -p no:cacheprovider \
               -p no:xdist -p no:randomly

.PHONY: verify verify-slow verify-all verify-load verify-chaos \
        verify-telemetry verify-static verify-sanitize verify-ops \
        verify-storm verify-perf verify-sharded \
        verify-express verify-hostpath verify-wire verify-cluster \
        verify-edge verify-fabric verify-multibox

verify: verify-static verify-storm verify-perf \
        verify-sharded verify-express verify-hostpath verify-wire \
        verify-cluster verify-edge verify-fabric verify-multibox
	set -o pipefail; rm -f /tmp/_t1.log; \
	timeout -k 10 $(TIER1_TIMEOUT) env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/ $(PYTEST_FLAGS) \
	-m 'not slow and not storm and not perf and not sharded and not express and not hostpath and not wire and not cluster and not edge and not fabric and not multibox' \
	2>&1 | tee /tmp/_t1.log

verify-sharded:
	set -o pipefail; \
	timeout -k 10 90 env JAX_PLATFORMS=cpu \
	XLA_FLAGS="--xla_force_host_platform_device_count=8" \
	$(PY) -m pytest tests/test_sharded_serving.py $(PYTEST_FLAGS) \
	  -m 'sharded and not slow' \
	&& echo "verify-sharded OK"

verify-express:
	set -o pipefail; \
	timeout -k 10 240 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_express.py $(PYTEST_FLAGS) \
	  -m 'express' \
	&& echo "verify-express OK"

verify-hostpath:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_hostpath.py $(PYTEST_FLAGS) \
	  -m 'hostpath and not slow' \
	&& echo "verify-hostpath OK"

verify-wire:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_wire_pump.py $(PYTEST_FLAGS) \
	  -m 'wire and not slow' \
	&& echo "verify-wire OK"

verify-cluster:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_cluster.py $(PYTEST_FLAGS) \
	  -m 'cluster and not slow' \
	&& echo "verify-cluster OK"

verify-edge:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_edge.py tests/test_qinq_ztp.py \
	  $(PYTEST_FLAGS) -m 'edge and not slow' \
	&& echo "verify-edge OK"

verify-fabric:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_fabric.py $(PYTEST_FLAGS) \
	  -m 'fabric and not slow' \
	&& echo "verify-fabric OK"

verify-multibox:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_multibox.py $(PYTEST_FLAGS) \
	  -m 'multibox and not slow' \
	&& echo "verify-multibox OK"

verify-slow:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ $(PYTEST_FLAGS) -m slow

verify-all: verify verify-slow

verify-chaos:
	set -o pipefail; \
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_chaos.py $(PYTEST_FLAGS) -m 'chaos and not slow'
	set -o pipefail; \
	timeout -k 10 360 env JAX_PLATFORMS=cpu \
	$(PY) -m bng_tpu.cli chaos run --seed 7 > /tmp/_chaos_a.json \
	&& timeout -k 10 360 env JAX_PLATFORMS=cpu \
	$(PY) -m bng_tpu.cli chaos run --seed 7 > /tmp/_chaos_b.json \
	&& test -s /tmp/_chaos_a.json \
	&& cmp /tmp/_chaos_a.json /tmp/_chaos_b.json \
	&& echo "verify-chaos OK: report bit-deterministic (incl. the 4 \
	transition scenarios, 2 fabric scenarios + 5 full-scale storms)" \
	|| { echo "verify-chaos FAILED: scenario failure or same-seed \
	reports differ"; exit 1; }

verify-storm:
	set -o pipefail; \
	timeout -k 10 90 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_storms.py $(PYTEST_FLAGS) \
	  -m 'storm and not slow' \
	&& echo "verify-storm OK"

verify-perf:
	set -o pipefail; \
	timeout -k 10 30 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_slo.py \
	  $(PYTEST_FLAGS) -m 'perf and not slow' \
	&& echo "verify-perf OK"

verify-ops:
	set -o pipefail; \
	timeout -k 10 90 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_ops.py $(PYTEST_FLAGS) -m 'ops and not slow' \
	&& echo "verify-ops OK"

verify-telemetry:
	set -o pipefail; \
	timeout -k 10 30 env JAX_PLATFORMS=cpu BNG_TELEMETRY=1 \
	$(PY) -m pytest tests/test_telemetry.py $(PYTEST_FLAGS) \
	  -m 'telemetry and not slow' -k 'not TestDoraTracingE2E' \
	&& echo "verify-telemetry OK"

verify-static:
	set -o pipefail; \
	timeout -k 10 30 $(PY) -m bng_tpu.analysis \
	&& timeout -k 10 60 env JAX_PLATFORMS=cpu \
	$(PY) -m pytest tests/test_analysis.py $(PYTEST_FLAGS) \
	  -m 'analysis and not slow' \
	&& echo "verify-static OK"

verify-sanitize:
	set -o pipefail; \
	timeout -k 10 300 env JAX_PLATFORMS=cpu BNG_SANITIZE=1 \
	$(PY) -m pytest tests/test_sanitize.py tests/test_scheduler.py \
	  tests/test_dhcp_fastpath.py tests/test_concurrency.py $(PYTEST_FLAGS) \
	  -m 'hotpath or analysis or race' \
	&& echo "verify-sanitize OK"

verify-load:
	set -o pipefail; \
	timeout -k 10 60 env JAX_PLATFORMS=cpu $(PY) -m bng_tpu.cli loadtest \
	  --workers 2 --duration 2 --warmup 1 --macs 2000 --batch-size 256 \
	  --json \
	| $(PY) -c "import json,sys; r=json.load(sys.stdin); \
	assert r['responses'] >= 2000 and r['errors'] == 0, r; \
	assert r['fleet']['workers'] == 2, r['fleet']; \
	print('verify-load OK: %d req/s, %d responses, fleet admitted %d' \
	% (r['rps'], r['responses'], r['fleet']['admission']['admitted']))"
