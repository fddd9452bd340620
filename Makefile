# Entry points. Tests and smokes run on the CPU (JAX_PLATFORMS=cpu, 8
# simulated devices for the mesh targets; nothing else to override). The
# chip is reached only through `chip-smoke`: one process, run where a TPU is
# visible (here: `chiprun -- python3 chip_smoke.py`). A chip belongs to one
# process at a time, so nothing that starts chip-owning children touches JAX.

CPU_ENV = env JAX_PLATFORMS=cpu
MESH_ENV = $(CPU_ENV) XLA_FLAGS=--xla_force_host_platform_device_count=8

.PHONY: test test-full test-fast test-telemetry test-collectives test-health test-attribution test-fleet test-resilience test-zero test-serving test-serve-cost test-tracing test-numerics test-elastic test-analysis test-memory test-opsplane lint dryrun telemetry-smoke serve-smoke chip-smoke

lint:            ## static analysis (ISSUE 15): invariant linter (jax-free), program auditor over the lowered step/serve programs, + generated-api drift check; CI runs this before pytest
	python scripts/stoke_lint.py
	$(CPU_ENV) python scripts/stoke_lint.py --programs
	$(CPU_ENV) python scripts/gen_api_md.py --check

test:            ## default tier (excludes @slow compile-heavy equivalence tests)
	$(MESH_ENV) python -m pytest tests/ -x -q

test-full:       ## FULL suite incl. @slow (what CI runs)
	$(MESH_ENV) python -m pytest tests/ -x -q -m ""

test-fast:       ## quick subset (status/facade/data), CPU mesh
	$(MESH_ENV) python -m pytest tests/test_status.py tests/test_facade.py tests/test_data.py -x -q

dryrun:          ## multi-chip sharding dry-run on 8 virtual devices
	$(MESH_ENV) python -c "import __graft_entry__; __graft_entry__.dryrun_multichip(8)"

test-telemetry:  ## observability-subsystem tests only (CPU, deterministic)
	$(MESH_ENV) python -m pytest tests/ -x -q -m telemetry

test-collectives: ## gradient-transport tests only (8-device host mesh)
	$(MESH_ENV) python -m pytest tests/ -x -q -m collectives

test-health:     ## health-monitor tests only (sentinels/detectors/watchdog/recorder)
	$(MESH_ENV) python -m pytest tests/ -x -q -m health

test-attribution: ## step-time attribution tests only (CostCards/MFU/goodput/auto-capture)
	$(MESH_ENV) python -m pytest tests/ -x -q -m attribution

test-fleet:      ## fleet-observability tests only (skew aggregation/stragglers/barrier attribution)
	$(MESH_ENV) python -m pytest tests/ -x -q -m fleet

test-resilience: ## pod-scale resilience tests only (preemption save/resume/quarantine/chaos/supervisor)
	$(MESH_ENV) python -m pytest tests/ -x -q -m resilience

test-zero:       ## ZeRO-parity quantized-collective tests only (sharded weight updates x int8 wire)
	$(MESH_ENV) python -m pytest tests/ -x -q -m zero

test-serving:    ## serving-stack tests only (paged KV decode parity/continuous batching/quantization)
	$(MESH_ENV) python -m pytest tests/ -x -q -m serving

test-serve-cost: ## serve roofline-observatory tests only (cost-card recombination/TPOT ceilings/drift gate)
	$(MESH_ENV) python -m pytest tests/ -x -q -m serve_cost

test-tracing:    ## structured-tracing tests only (span ring/nesting/Perfetto schema/request timelines/rank merge)
	$(MESH_ENV) python -m pytest tests/ -x -q -m tracing

test-numerics:   ## per-layer numerics tests only (module groups/provenance/quant attribution/diff tool)
	$(MESH_ENV) python -m pytest tests/ -x -q -m numerics

test-elastic:    ## elastic-resilience tests only (staged saves/elastic resume/rebalancing/kill_during_save)
	$(MESH_ENV) python -m pytest tests/ -x -q -m elastic

test-analysis:   ## static-analysis tests only (invariant linter rules/waivers/manifests + live program audit)
	$(MESH_ENV) python -m pytest tests/ -x -q -m analysis

test-memory:     ## HBM-capacity-observatory tests only (ledger recombination/OOM pre-flight/memory-drift gate)
	$(MESH_ENV) python -m pytest tests/ -x -q -m memory

test-opsplane:   ## live-ops-plane tests only (default-OFF contract/endpoint schemas/healthz flip/capture budget)
	$(MESH_ENV) python -m pytest tests/ -x -q -m opsplane

serve-smoke:     ## CPU-safe serve smoke: traced chunked-prefill + top-p request end-to-end
	$(MESH_ENV) python scripts/telemetry_smoke.py --serve-only

telemetry-smoke: ## one JSONL-emitting CPU train step through the full telemetry pipeline
	$(MESH_ENV) python scripts/telemetry_smoke.py

chip-smoke:      ## trainer + server + kernels at GPT-large width on the chip; needs a TPU (exits non-zero without one). Cache: JAX_COMPILATION_CACHE_DIR if set, else ./.jax_cache
	python3 chip_smoke.py
