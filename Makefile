# Every target runs on the CPU (JAX_PLATFORMS=cpu): tests never take a chip.
# The chip is reached through the chip tool, one process per chip:
# `python chip_smoke.py`, `python3 benchmark/run.py` (`make benchmark`).
.PHONY: test test-all verify benchmark chaos chaos-collective telemetry-smoke serve-smoke spec-smoke fleet-smoke adapters-smoke async-smoke autopilot-smoke lint lint-tests
test:
	python -m pytest tests/ -x -q

# the FULL pyramid including `slow` (multiprocess e2e, TCP, jax.distributed)
test-all:
	python -m pytest tests/ -x -q -m "slow or not slow"

# tier-1: the not-slow suite, once (ROADMAP.md "Tier-1 verify")
verify:
	JAX_PLATFORMS=cpu python -m pytest tests/ -q -m "not slow" \
		--continue-on-collection-errors -p no:cacheprovider

# how a cell of BENCHMARK.json is measured (benchmark/README.md; the driver
# runs every cell on the parent commit and on the change and writes
# PERF_LEDGER.jsonl)
benchmark:
	@echo "one cell, once, on the chip (a TPU v5e; no CPU fallback):"
	@echo "  chiprun -- python3 benchmark/run.py --workload <cell> --seed 7 --seconds 40 --trace 0"
	@python3 -c "import json; print('cells:', *(w['name'] for w in json.load(open('BENCHMARK.json'))['workloads']))"
	@echo "--trace 1 adds the breakdown; python3 benchmark/tools/span_table.py --workload <cell> splits it by span"

# telemetry smoke (ISSUE 4): the whole tracing/event/registry suite — the
# fast half (in-process 1-round run → merged Perfetto trace parses with
# server+client spans, KPI registry) also rides tier-1; the slow half adds
# the REAL multiprocess + TCP trace-propagation e2es
telemetry-smoke:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_telemetry.py -q -m "slow or not slow"

# photon-lint (ISSUE 6): the AST rule engine over the repo's invariants —
# registry-constant KPI/span/event names, None-guarded hook sites, no
# retrace hazards in jit'd code, scoped locks/owned threads, transport
# discipline. Fails on any unsuppressed finding (suppress inline with
# `# photon-lint: ignore[rule]`, or justify in analysis/baseline.json).
lint:
	python -m photon_tpu.analysis photon_tpu/

# the lint-marked pytest suite: seeded-violation fixtures per rule family,
# clean-tree gate, and the dynamic lock-order + retrace detectors. Rides
# tier-1 too (none of it is slow).
lint-tests:
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_analysis.py -q

# serving smoke (ISSUE 5 + 11 + 12): the whole serving-plane suite —
# mixed-step parity with the contiguous decoder, the ragged
# paged-attention kernel's epsilon tier, scheduler invariants incl.
# decode cadence under a 4x-budget chunked prompt, HTTP round-trips
# (blocking + chunked streaming) against a real round checkpoint, the
# content-addressed prefix cache (refcounts, chain hashes, cached-vs-cold
# per-step parity, LRU pressure) and the live checkpoint hot-swap
# (watcher state machine incl. the chaos corrupt-candidate skip,
# zero-dropped-across-swap e2e). All of it rides tier-1 too (none is
# slow). photon-lint preflight first: a
# rule regression (or a fresh violation in serve/) fails the smoke before
# any engine compile burns minutes
serve-smoke: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_serve.py tests/test_serve_prefix.py tests/test_hotswap.py \
		tests/test_ragged_attention.py -q -m "slow or not slow"

# speculative decoding (ISSUE 15): the draft-and-verify suite — the
# generalized grid's bitwise parity with K sequential single-token steps
# (incl. a mid-prefill batch-mate), greedy end-to-end bit-exactness
# through the batcher (prefix hits, recycled blocks, EOS mid-burst),
# rejection-sampling distribution pins, the n-gram drafter + accept-rate
# throttle, and the retrace sentinel over warm speculative bursts with
# the full-idle high-water reset. Rides tier-1 too (none is slow); lint
# preflight first like the other smoke targets.
spec-smoke: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_speculative.py -q -m "slow or not slow"

# fleet router (ISSUE 16): placement policy + control plane + failover
# suite (a mid-traffic replica kill drops zero requests on the survivors)
fleet-smoke: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_router.py -q -m "slow or not slow"

# per-cohort LoRA personalization plane (ISSUE 13): the train-side suite
# (config validation, LoRA payload algebra, fused multi-cohort reduction
# vs the per-cohort host oracle at off + pinned q8 bound, federated
# adapter rounds with frozen-base/cohort-degradation/checkpoint-resume
# pins) and the serve-side suite (adapter-pool refcounts, mixed-cohort
# bit-parity vs the contiguous base+adapter oracle incl. recycled pages,
# cohort over HTTP, retrace sentinel over cohort churn, and the
# train→checkpoint→hot-swap e2e with zero dropped requests). Both suites
# ride tier-1 too (none is slow); lint preflight first like the other
# smoke targets.
adapters-smoke: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_adapters.py tests/test_adapter_serve.py -q -m "slow or not slow"

# asynchronous federated rounds (ISSUE 18): the version-clock suite —
# zero-staleness bit-parity with the synchronous runner (all five server
# optimizers, fp32 + q8, fused plane + host path), staleness-discount
# weight math, the max-staleness reject / min-arrivals stall / liveness
# in-flight-drop ladder, deterministic chaos fit delays, the retrace
# sentinel over the event loop, and the SIGKILL+4x-skew chaos e2e with
# the hot-swap watcher consuming streamed versions mid-traffic. Lint
# preflight first like the other smoke targets.
async-smoke: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_async_round.py -q -m "slow or not slow"

# SLO autopilot (ISSUE 19): the feedback-controller suite — windowed
# reducer exact-value pins, runtime-knob loud rejects, breach/cooldown/
# saturation/relax state machine on an injected clock, the HBM
# alert-latch reclaim, per-replica restart cooldown, /statusz decision
# surfacing, and the seeded chaos-storm e2e through the real scheduler
# (zero queue rejects, the budget actuated). The fast half rides tier-1
# too; lint preflight first like the other smokes.
autopilot-smoke: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_autopilot.py -q -m "slow or not slow"

# the chaos-marked fault-injection + elasticity suite (incl. the slow
# SIGKILL/rejoin e2es): deterministic — every test pins
# ChaosConfig(seed=1234) and the injector streams are pure functions of
# (seed, node_id). Scoped to the files carrying chaos-marked tests so
# unrelated collection state can't mask a red suite.
chaos: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_chaos.py tests/test_membership.py tests/test_tcp_driver.py \
		tests/test_checkpoint.py tests/test_shm.py tests/test_router.py \
		-q -m chaos

# elastic collective rounds (ISSUE 8): stage-deadline units + the
# SIGKILL-mid-collective e2es (gang reconfiguration, quorum, host-fallback
# degradation, crash phases inside the collective), all running under BOTH
# dynamic detectors (lock-order recorder + retrace sentinel with absorbed
# reconfiguration compiles). Deterministic (ChaosConfig seed + injected
# clocks); the fast half rides tier-1 via the `chaos` marker. Lint
# preflight like the other smoke targets.
chaos-collective: lint
	JAX_PLATFORMS=cpu python -m pytest \
		tests/test_collective_elastic.py -q -m "slow or not slow"
