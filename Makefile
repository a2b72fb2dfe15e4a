PY := python

.PHONY: test test-fast test-sharded bench-kernels bench-kernels-full example

# Tier-1 verify (ROADMAP): the full suite with the src layout on the path.
test:
	PYTHONPATH=src $(PY) -m pytest -x -q

test-fast: test-sharded
	PYTHONPATH=src $(PY) -m pytest -x -q tests/test_tiers.py tests/test_compaction.py tests/test_scheduler.py tests/test_multitier.py tests/test_hlo_analysis.py

# Multi-device lane: 8 virtual CPU devices (XLA_FLAGS must precede jax
# init, hence the separate pytest process) running the sharded-tier
# equivalence suite (SPMD trajectory identity, policy lowering, mesh
# construction, sharding-aware partition costs).
test-sharded:
	XLA_FLAGS="--xla_force_host_platform_device_count=8" PYTHONPATH=src $(PY) -m pytest -x -q tests/test_sharded_tiers.py

# Kernel-vs-jnp decode hot path sweep (flash_decode / fused exit decision /
# ssd_update / end-to-end TierExecutor step) in CI smoke mode: tiny shapes,
# kernels in interpret mode off-TPU, trajectory + 1-sync asserts inline.
bench-kernels:
	REPRO_BENCH_FAST=1 PYTHONPATH=src $(PY) benchmarks/kernel_micro.py

# Full sweep incl. the serving-scale jnp reference timings.
bench-kernels-full:
	PYTHONPATH=src $(PY) benchmarks/kernel_micro.py

example:
	PYTHONPATH=src $(PY) examples/serve_partitioned.py
