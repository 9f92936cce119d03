# Developer entry points (the reference ships sbt + python/run-tests.sh,
# /root/reference/project/Build.scala:8-127, python/run-tests.sh:28-117).

# `verify` uses bash arrays/PIPESTATUS; make the whole file consistent
SHELL := /bin/bash

PY ?= python

.PHONY: test test-failfast test-fast test-attn test-chaos test-distjobs test-durability test-elastic test-fleet test-ha test-multihost test-obs test-obsfleet test-plan test-spec test-tenancy test-tiers test-tp test-tune soak verify dryrun install lint

install:
	$(PY) -m pip install -e . --no-build-isolation

# full suite on a virtual 8-device CPU mesh (conftest forces the backend).
# NO -x: merge CI must report EVERY failure, not stop at the first and
# hide the rest (use test-failfast for the edit loop)
test:
	$(PY) -m pytest tests/ -q

# stop at the first failure — the local edit-debug convenience
test-failfast:
	$(PY) -m pytest tests/ -x -q

# the edit-test loop tier: everything not marked slow, parallelized;
# target < 3 min (the slow marks carry the multi-process / training
# heavyweights — CI runs `test-fast` on PRs and `test` on merges).
# pytest-xdist is enabled by its -n flag alone (`-p xdist` is not how the
# plugin is selected and broke on installs that auto-load it).
test-fast:
	$(PY) -m pytest tests/ -q -m "not slow" -n 4

# the EXACT ROADMAP tier-1 command (what the driver measures after each
# PR) — run this before shipping so local numbers match CI's
verify:
	set -o pipefail; rm -f /tmp/_t1.log; timeout -k 10 870 env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log; rc=$${PIPESTATUS[0]}; echo DOTS_PASSED=$$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$$' /tmp/_t1.log | tr -cd . | wc -c); exit $$rc

# the paged-attention suite (ops ragged kernel vs the gather oracle,
# prefix cache, chunked prefill) — fast, CPU interpret mode, part of
# tier-1; run alone when iterating on the kernel or the cache
test-attn:
	$(PY) -m pytest tests/ -q -m attn

# the seeded fault-injection suite (utils/chaos.py + the serving
# supervisor under chaos) — fast, CPU-only, deterministic; part of
# tier-1, runnable alone when iterating on failure handling
test-chaos:
	$(PY) -m pytest tests/ -q -m chaos

# the durable batch-job suite (engine/jobs.py: journal, crash-resume,
# quarantine) — fast, CPU-only, deterministic; part of tier-1
test-durability:
	$(PY) -m pytest tests/ -q -m durability

# the distributed-job suite (engine/dist_jobs.py: multi-worker block
# leasing, heartbeats, dead-worker reclamation, write fencing) — incl.
# the real 3-subprocess kill -9 soak; CPU-only, deterministic, tier-1
test-distjobs:
	$(PY) -m pytest tests/ -q -m distjobs

# the serving-fleet suite (serve/fleet.py: replicated engines behind the
# health-gated router, failover + request replay) — the fast tests are
# tier-1; the multi-replica chaos soak is marked slow and runs here too
test-fleet:
	$(PY) -m pytest tests/ -q -m fleet

# the observability suite (tensorframes_tpu/obs: metrics registry
# semantics, distributed tracing end-to-end, flight recorder + debug
# bundles, /statusz, the docs<->code drift gate) — CPU-only,
# deterministic, tier-1
test-obs:
	$(PY) -m pytest tests/ -q -m obs

# the fleet-telemetry suite (obs/export.py + obs/aggregate.py +
# obs/drift.py + obs/requests.py: cross-process snapshot federation
# incl. the 2-subprocess kill -9 staleness drill, merged-quantile
# oracles, drift shift/recovery, per-request cost attribution) —
# CPU-only, deterministic, tier-1
test-obsfleet:
	$(PY) -m pytest tests/ -q -m obsfleet

# the logical-plan suite (engine/plan.py: lazy op recording, map
# fusion, column pruning, reduction hoisting — incl. the per-pass
# byte-identity matrix and the journaled fused-pipeline kill+resume)
# — fast, CPU-only, deterministic; part of tier-1
test-plan:
	$(PY) -m pytest tests/ -q -m plan

# the self-tuning suite (tensorframes_tpu/tune: store durability incl.
# the 2-subprocess concurrent-write + kill -9 drills, learned-ranker
# pruning, per-surface byte-identity vs TFT_TUNE=0, persistence
# round-trip) — fast, CPU-only, deterministic; part of tier-1
test-tune:
	$(PY) -m pytest tests/ -q -m tune

# the speculative-decoding suite (serve/engine.py draft + verify step
# programs, the draft KV page group, exact-match acceptance): the
# byte-identity matrix vs solo decode — greedy/seeded, chunked
# prefill, prefix cache, preemption, restart, chaos at serve.verify,
# fleet failover across different k — plus the adaptive-k controller.
# Fast, CPU-only, deterministic; part of tier-1
test-spec:
	$(PY) -m pytest tests/ -q -m spec

# the multi-tenant QoS suite (serve/tenancy.py: quotas + token-bucket
# rate limits, priority admission/preemption/eviction, SLO-actuated
# shedding/deprioritization, 429 + /admin/tenants, the 2-replica
# fairness soak with byte-identity vs solo) — fast, CPU-only,
# deterministic; part of tier-1
test-tenancy:
	$(PY) -m pytest tests/ -q -m tenancy

# the tensor-parallel serving suite (serve/tp.py: mesh-sharded step
# programs + sharded KV PagePool — the TP=1/2/4 byte-identity matrix,
# capacity scaling, hetero-TP fleet failover). Part of tier-1 (conftest
# provisions the simulated mesh); this target also sets the
# host-device-count env itself so it works OUTSIDE pytest's conftest,
# e.g. under a bare `python -m pytest tests/test_serve_tp.py::...`
test-tp:
	env JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" $(PY) -m pytest tests/ -q -m tp

# the elastic multi-host fleet suite (serve/membership.py: lease-based
# membership + epoch fencing, remote replicas over HTTP with failover
# byte-identity, /readyz + SIGTERM drain, rolling restart / hot weight
# swap with probe-gated re-admission) — the fast tests are tier-1; the
# 3-subprocess kill -9 + wedge acceptance soak is marked slow and runs
# here too
test-elastic:
	$(PY) -m pytest tests/ -q -m elastic

# the router high-availability suite (serve/router_ha.py: request WAL,
# resumable streams, fenced standby takeover, lease clock edges, local
# subprocess provisioner); the 2-router + 3-member kill -9 takeover
# acceptance soak is marked slow and runs here too
test-ha:
	$(PY) -m pytest tests/ -q -m ha

# the disaggregated-tier suite (serve/tiers.py + the fleet's tier-aware
# router: live KV-page migration byte-identity matrix — greedy/seeded ×
# TP degree × speculative × prefix-cache donors — first-token handoff,
# pool-pressure rebalance vs preemption, chaos at tier.handoff /
# fleet.migrate; incl. the slow-marked kill -9 mid-migration soak)
test-tiers:
	$(PY) -m pytest tests/ -q -m tiers

# every multi-process fault-tolerance soak in one command: the elastic
# membership, fleet failover, chaos, and router-HA suites INCLUDING
# their slow-marked subprocess drills — the pre-release confidence run
# (budget ~15 min; tier-1 stays the fast gate)
soak:
	$(PY) -m pytest tests/ -q -m "elastic or fleet or chaos or ha or tiers"

# just the real 2-process distributed suite
test-multihost:
	$(PY) -m pytest tests/test_multihost.py -q

# the CPU sim-mesh check (self-provisions 8 virtual CPU devices; the
# chip evidence is chip_smoke.py)
dryrun:
	$(PY) -c "import __graft_entry__ as g; g.dryrun_multichip(8); print('DRYRUN OK')"

# compile-check every module (no external linter in this environment)
lint:
	$(PY) -m compileall -q tensorframes_tpu chipbench examples tests __graft_entry__.py chip_smoke.py
