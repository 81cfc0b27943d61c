# Tier-1 verification and common dev entry points.
# `make test` is the exact command CI runs; a collection error (e.g. a test
# module importing a missing optional dep) fails it immediately.

PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-sharded lint bench bench-smoke chaos-smoke check-trajectory serve-example

test:
	PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q

# sharding suite under a forced 8-device CPU backend: mesh-sliced replicas,
# sharded KV pools, cross-slice spill/adopt (the flag must be set before
# jax first initializes, hence the dedicated target/CI job)
test-sharded:
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		PYTHONPATH=$(PYTHONPATH) python -m pytest -x -q tests/test_serving_sharding.py

# cascade-lint: lock discipline, host-sync discipline, donation/recompile
# hazards over the whole tree; exits nonzero on any unsuppressed finding
lint:
	PYTHONPATH=$(PYTHONPATH) python -m repro.analysis src/repro

bench:
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run $(if $(ONLY),--only $(ONLY))

# exactly what CI's bench-smoke job runs: the serving perf path end-to-end
# on tiny configs (unified tick, paged KV + prefix reuse, speculative
# decode, multi-model cascade + bounded admission, SLO-class overload with
# KV preemption vs the shed-only FIFO baseline, quantized-vs-bf16 KV pool)
bench-smoke:
	BENCH_SMOKE=1 PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
		--only serve_prefix_reuse,serve_mixed_tick,serve_speculative,serve_multi_model,serve_overload,serve_kv_quant
	XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		BENCH_SMOKE=1 PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
		--only serve_replica_scaling

# exactly what CI's chaos-smoke job runs: a seeded fault schedule (replica
# crash + KV migration, transient submit errors, slow ticks) over the
# serving path, asserting zero stranded requests and structured errors only
chaos-smoke:
	BENCH_SMOKE=1 PYTHONPATH=$(PYTHONPATH) python -m benchmarks.run \
		--only serve_chaos

# diff the freshly produced BENCH_serve.json against BASELINE (default: the
# last committed copy, via `git show`); fails on p99 regressions beyond the
# noise band
check-trajectory:
	git show HEAD:BENCH_serve.json > /tmp/BENCH_serve.baseline.json
	PYTHONPATH=$(PYTHONPATH) python -m benchmarks.check_trajectory \
		/tmp/BENCH_serve.baseline.json BENCH_serve.json $(if $(BAND),--band $(BAND))

serve-example:
	PYTHONPATH=$(PYTHONPATH) python examples/serve_cluster.py
