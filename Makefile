# Convenience targets. `make chaos` is the headless resilience drill:
# it exits nonzero if any scenario's run fails to recover.

PYTHON ?= python
PYTEST_ARGS ?= -q -m 'not slow' -p no:cacheprovider

.PHONY: test test-all chaos chaos-fast chaos-replica-kill chaos-worker-kill chaos-outage chaos-shard-kill dataplane lint lint-json capacity capacity-smoke capacity-multi bench-proxy bench-routing drill-disagg drill-rl

test:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ $(PYTEST_ARGS)

test-all:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q -p no:cacheprovider

chaos:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.chaos --all

chaos-fast:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.chaos --scenario runner-flap

# Failure-isolation drills (docs/guides/multi-replica.md): control-plane
# replica SIGKILL with lease takeover, data-plane worker SIGKILL mid-SSE,
# and a full control-plane outage with degraded serving + epoch re-sync.
chaos-replica-kill:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.chaos --scenario replica-kill-takeover

chaos-worker-kill:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.chaos --scenario dataplane-worker-kill

chaos-outage:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.chaos --scenario dataplane-outage

# Sharded-FSM drill: SIGKILL one of four replicas mid-probe; survivors
# must absorb its shards within one lease TTL of expiry with zero
# pre-expiry steals, and every in-flight run still completes.
chaos-shard-kill:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.chaos --scenario shard-kill

# Standalone data-plane worker(s) against the local server DB.
dataplane:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.dataplane --workers $(or $(WORKERS),1)

# Static analysis (docs/guides/static-analysis.md) + bytecode compile.
# --gate runs the whole pipeline in one process (shared parsed ASTs):
# main tree against the committed baseline, the analyzer's own package
# with the baseline ignored, good fixture tree clean, and the seeded
# bad fixture tree tripping every checker (exit 1 expected there).
lint:
	$(PYTHON) -m dstack_tpu.analysis --gate --jobs 4
	$(PYTHON) -m compileall -q dstack_tpu

lint-json:
	$(PYTHON) -m dstack_tpu.analysis dstack_tpu/ --json

# Full control-plane capacity probe (500 concurrent runs, native runner,
# real socket). Results land in CAPACITY_r06.json; see
# docs/guides/control-plane-tuning.md for how to read them.
capacity:
	JAX_PLATFORMS=cpu $(PYTHON) capacity_probe.py --runs 500 --out CAPACITY_r06.json

# Multi-replica scaling sweep: 1/2/4 replicas (1 in-process + N-1 real
# subprocesses) sharing one file-backed DB with hash-sharded FSM
# ownership. Per-arm aggregate runs/min lands in CAPACITY_r11.json.
capacity-multi:
	JAX_PLATFORMS=cpu $(PYTHON) capacity_probe.py --runs 500 --replicas 1,2,4 --out CAPACITY_r11.json

# Proxy data-plane benchmark: pooled+streamed fast path vs the legacy
# per-request-client buffered proxy, plus the multi-worker scaling and
# route-staleness arms (real dataplane subprocesses). Results land in
# BENCH_proxy_r09.json; see docs/guides/proxy-tuning.md and
# docs/guides/multi-replica.md for how to read them.
bench-proxy:
	JAX_PLATFORMS=cpu $(PYTHON) bench_proxy.py --out BENCH_proxy_r09.json

bench-routing:
	JAX_PLATFORMS=cpu $(PYTHON) bench_routing.py --out BENCH_routing_r18.json

# Prefill/decode disaggregation drill: two real worker processes over a
# 2-way model mesh each, KV handoffs over a socket. Asserts token
# bit-exactness vs a unified engine, end-to-end trace continuity (one
# trace_id spanning both tiers, phases telescoping per tier), clean
# cancel mid-handoff, stale-epoch reject + client refresh, and zero
# KV-block residue.
drill-disagg:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.workloads.serving_disagg

# Podracer RL drill (docs/guides/rl.md): Sebulba-style actor gang
# (2 actor subprocesses) feeding an in-process learner, weight refresh
# over the framed-socket channel. Kills one actor mid-rollout, resolves
# it via elastic gang resize (accum-step rescale, zero learner
# restarts), then grows back to full width; asserts epoch convergence,
# the stage-marker timeline, and the RL /metrics series.
drill-rl:
	JAX_PLATFORMS=cpu $(PYTHON) -m dstack_tpu.workloads.rl_drill

# CI-sized variant: 40 runs in-process, asserts 0 failures + telemetry.
capacity-smoke:
	JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/server/test_capacity_smoke.py -q -m capacity -p no:cacheprovider
