PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH),)

.PHONY: ci test test-sharded smoke examples-smoke bench tune tune-smoke \
	bench-batched-smoke bench-sharded-smoke bench-epilogue-smoke \
	trace-smoke serve-smoke lint analyze \
	traffic-baseline

# examples-smoke subsumes the quickstart smoke (runs it in full), so ci
# doesn't run it twice.
ci: test examples-smoke

# Style lint: ruff (E/F/W/I/UP per pyproject.toml) when installed, plus
# the repo-specific AST rules (RL001-RL006).  ruff is a dev dependency
# (requirements-dev.txt); a container without it still runs the RL leg.
lint:
	@if $(PY) -m ruff --version >/dev/null 2>&1; then \
	    $(PY) -m ruff check .; \
	else \
	    echo "ruff not installed; skipping style leg (pip install ruff)"; \
	fi
	$(PY) -m repro.analysis lint

# Static verification gate (CI-required): repo lint + plan-invariant
# linter over the mini suite + registry-driven kernel audit + the
# bytes-moved/coalescing traffic gate diffed against the committed
# baseline (artifacts/traffic_baseline.json).  Reports land in
# artifacts/ and are uploaded by CI.
analyze: lint
	mkdir -p artifacts
	$(PY) -m repro.analysis planlint --suite mini
	$(PY) -m repro.analysis audit --out artifacts/kernel_audit.txt
	$(PY) -m repro.analysis traffic --check \
	    --json artifacts/traffic_report.json

# Regenerate the static bytes-moved baseline after an *intentional*
# traffic change (new kernel, tiling change); commit the diff with the
# change that caused it.
traffic-baseline:
	$(PY) -m repro.analysis traffic --update

# Tier-1 verify (ROADMAP.md).  DeprecationWarnings are errors: first-party
# code and tests must use the v1 policy=/exec= spellings (the shim tests
# in tests/test_api.py exercise the legacy forms under pytest.warns).
test:
	$(PY) -m pytest -x -q -W error::DeprecationWarning

# Sharded SpMM tests on a forced 8-device CPU substrate (tests/conftest.py
# turns REPRO_FORCE_DEVICES into XLA_FLAGS before jax initializes).  The
# plain `make test` run covers the same tests via a subprocess wrapper;
# this target runs them directly, with the mesh visible to every test.
test-sharded:
	REPRO_FORCE_DEVICES=8 $(PY) -m pytest -x -q \
	    -W error::DeprecationWarning \
	    tests/test_distributed_spmm.py tests/test_shard_property.py

# Fast interpret-mode smoke of the public SpMM API
smoke:
	$(PY) examples/quickstart.py

# Every example end-to-end on CPU (Pallas interpret mode): quickstart in
# full, the rest via their CI-sized --smoke paths.  Wired into CI so the
# examples can never silently rot against the API.
examples-smoke:
	$(PY) examples/quickstart.py
	$(PY) examples/moe_spmm_demo.py --smoke
	$(PY) examples/serve_pruned.py --smoke
	$(PY) examples/train_tiny_lm.py --smoke

bench:
	$(PY) -m benchmarks.run

# Full empirical autotune over the paper corpus (see EXPERIMENTS.md)
tune:
	$(PY) -m repro.tune --suite paper --out tune.json

# CI smoke: autotune the 3-matrix mini suite + corpus bench, artifacts
# land in artifacts/ (TuneDB JSON + bench CSV)
tune-smoke:
	mkdir -p artifacts
	$(PY) -m repro.tune --suite mini --out artifacts/tune.json \
	    --warmup 1 --repeat 2
	REPRO_CORPUS_SUITE=mini $(PY) -m benchmarks.run corpus \
	    > artifacts/bench_corpus.csv
	cat artifacts/bench_corpus.csv

# CI smoke: tiny batch x k sweep through the Pallas kernels in interpret
# mode (real batched/K-tiled grid dataflow), CSV lands in artifacts/
bench-batched-smoke:
	mkdir -p artifacts
	REPRO_BENCH_BATCHED=smoke $(PY) -m benchmarks.run batched \
	    > artifacts/bench_batched.csv
	cat artifacts/bench_batched.csv

# CI smoke: fused epilogue vs separate elementwise tail through the Pallas
# kernels in interpret mode (real in-kernel epilogue flush), CSV lands in
# artifacts/
bench-epilogue-smoke:
	mkdir -p artifacts
	REPRO_BENCH_EPILOGUE=smoke $(PY) -m benchmarks.run epilogue \
	    > artifacts/bench_epilogue.csv
	cat artifacts/bench_epilogue.csv

# CI smoke: traced interpret-mode serve + train — Chrome trace-event JSON
# and metrics dumps land in artifacts/ and are schema-validated
# (repro.obs.validate); a malformed trace or an empty span set fails here
# instead of uploading a useless artifact.
trace-smoke:
	mkdir -p artifacts
	$(PY) -m repro.launch.serve --smoke --batch 2 --prompt-len 16 \
	    --prune-ffn 0.25 \
	    --trace-out artifacts/serve_trace.json \
	    --metrics-out artifacts/serve_metrics.json
	$(PY) -m repro.launch.train --smoke --steps 2 --global-batch 2 \
	    --seq-len 16 \
	    --trace-out artifacts/train_trace.json \
	    --metrics-out artifacts/train_metrics.json
	$(PY) -m repro.obs.validate \
	    --trace artifacts/serve_trace.json \
	    --require-cats plan,cache,dispatch,serve \
	    --metrics artifacts/serve_metrics.json \
	    --require-metrics plan_resolve_total,plan_cache_events_total,serve_latency_us
	$(PY) -m repro.obs.validate \
	    --trace artifacts/train_trace.json \
	    --metrics artifacts/train_metrics.json \
	    --require-metrics train_step_latency_us

# CI smoke: online serving under Poisson load — continuous batching vs
# one-at-a-time (the >= 1.5x smoke throughput gate lives inside the
# bench), plus a Pallas interpret-mode leg and the shed-accounting leg.
# Trace + metrics artifacts are schema-validated: the serve.* spans and
# the serving metric families must actually exist.
serve-smoke:
	mkdir -p artifacts
	REPRO_BENCH_SERVING=smoke \
	    REPRO_SERVING_TRACE_OUT=artifacts/serving_trace.json \
	    REPRO_SERVING_METRICS_OUT=artifacts/serving_metrics.json \
	    $(PY) -m benchmarks.run serving > artifacts/bench_serving.csv
	cat artifacts/bench_serving.csv
	$(PY) -m repro.obs.validate \
	    --trace artifacts/serving_trace.json \
	    --require-cats serve \
	    --metrics artifacts/serving_metrics.json \
	    --require-metrics serve_requests_total,serve_request_latency_us,serve_batch_occupancy,program_cache_events_total

# CI smoke: shard-count sweep + nnz-vs-row balance on a forced 8-device
# CPU mesh (bench_sharded forces the device count itself when run as a
# module), CSV lands in artifacts/
bench-sharded-smoke:
	mkdir -p artifacts
	$(PY) -m benchmarks.bench_sharded > artifacts/bench_sharded.csv
	cat artifacts/bench_sharded.csv
