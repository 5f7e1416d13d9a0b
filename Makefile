# Developer entry points. CI (.github/workflows/ci.yml) runs the same
# commands; keep the two in sync.

PYTHON ?= python
SANITIZER ?= address

.PHONY: lint test sanitize wire-docs flow-docs protocols build chaos loadgen \
	perf explore

# The unified gate (all passes + stale-suppression audit + wall-time
# budget), then the rpc_flow mutation gate: a seeded synchronous back-call
# cycle must be detected, or the pass has lost its teeth.
lint:
	$(PYTHON) -m ray_tpu.devtools.lint
	$(PYTHON) -m ray_tpu.devtools.rpc_flow --mutate back_call \
		--expect-violation
	$(PYTHON) -m ray_tpu.devtools.exc_flow --mutate swallow_cancel \
		--expect-violation

# Tier-1 as the driver runs it (/root/TESTS_LAST_RUN.json `commands`): six
# xdist workers, a file a worker, 1,470 s. The driver also sets
# ALLOW_MULTIPLE_LIBTPU_LOAD=1 in its own environment, so that the two files
# that compile for a described v5e may land on different workers; it is
# not set here, nor anywhere in the repository. (CI's tier-1 step runs the
# same tests in one process.)
test:
	timeout -k 10 1470 env JAX_PLATFORMS=cpu $(PYTHON) -m pytest tests/ -q \
		-m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
		-p xdist -n 6 --dist loadfile -p no:randomly

build:
	$(PYTHON) setup.py build_ext --inplace

# Rebuild the C++ extensions with -fsanitize=$(SANITIZER) and run the
# native-path tests under the instrumented .so files. ASan needs its
# runtime loaded before python, hence the LD_PRELOAD (gcc resolves the
# right libasan for the toolchain); UBSan links its runtime statically.
sanitize:
	RAY_TPU_SANITIZE=$(SANITIZER) $(PYTHON) setup.py build_ext --inplace
	@if [ "$(SANITIZER)" = "address" ]; then \
		env LD_PRELOAD=$$(gcc -print-file-name=libasan.so) \
			ASAN_OPTIONS=detect_leaks=0 JAX_PLATFORMS=cpu \
			$(PYTHON) -m pytest tests/test_store_core.py \
			tests/test_fastpath_native.py -q -p no:cacheprovider; \
	else \
		env JAX_PLATFORMS=cpu $(PYTHON) -m pytest \
			tests/test_store_core.py tests/test_fastpath_native.py \
			-q -p no:cacheprovider; \
	fi
	$(PYTHON) setup.py build_ext --inplace  # restore uninstrumented .so

wire-docs:
	$(PYTHON) -m ray_tpu.devtools.rpc_check --markdown > docs/wire_protocol.md

# Regenerate the cross-process blocking-graph inventory; CI fails if the
# checked-in copy is stale.
flow-docs:
	$(PYTHON) -m ray_tpu.devtools.rpc_flow --markdown > docs/rpc_flow.md

# Regenerate the FSM reference from the machine-readable spec; CI fails if
# the checked-in copy is stale.
protocols:
	$(PYTHON) -m ray_tpu.devtools.protocols --markdown > docs/protocols.md

# Deterministic fault injection (docs/chaos.md). SEEDS seeds per scenario;
# failing seeds land in chaos_corpus.jsonl for replay. The latency suite
# exercises the RPC resilience layer (docs/resilience.md) over fewer seeds.
# Serve load harness (docs/serving.md): closed-loop calibration plus a 5x
# open-loop overload phase against a local deployment; exits nonzero if an
# admitted request overruns its deadline.
loadgen:
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.loadgen --smoke

# Perf floors (CI perf-smoke job runs the same commands): the ray_perf
# microbenchmark suite — tasks/actors/put/get plus the streaming-ingest
# leg (ingest_rows_per_s) — and the serve loadgen smoke, gated together
# against benchmarks/perf_floors.json. Then the native-wire A/B: the
# lease bench runs with and without RAY_TPU_NATIVE_WIRE=0 and the gate
# asserts the _fastpath codec strictly wins (pack >= 1.2x) and the
# end-to-end lease rate doesn't regress with native enabled.
perf:
	timeout -k 10 900 env JAX_PLATFORMS=cpu \
		$(PYTHON) -m ray_tpu._private.ray_perf --json /tmp/perf.json
	timeout -k 10 300 env JAX_PLATFORMS=cpu \
		$(PYTHON) -m ray_tpu.loadgen --smoke --json /tmp/serve_load.json
	$(PYTHON) benchmarks/perf_gate.py /tmp/perf.json /tmp/serve_load.json
	timeout -k 10 600 env JAX_PLATFORMS=cpu \
		$(PYTHON) benchmarks/native_ab.py

# Exhaustive interleaving explorer (docs/static_analysis.md): enumerate
# the control-plane scenarios' schedule spaces under the virtual loop
# (lease + ha exhaust; resubscribe runs bounded-clean), prove the
# double-grant mutation is still caught and its committed trace still
# replays to the violation, then scan the WAL/replicated-store
# group-commit crash points. CI's explore-smoke job runs the same
# commands.
HA_EXPLORE_BUDGET ?= 40000
explore:
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--scenario lease_exactly_once --budget 5000 --check-determinism
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--scenario ha_promotion --budget $(HA_EXPLORE_BUDGET)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--scenario quorum_election --budget 4000 --check-determinism
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--scenario resubscribe_gap --budget 3000 --allow-bounded
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--scenario lease_exactly_once --mutate double_grant \
		--expect-violation
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--replay tests/schedules/lease_double_grant.json --expect-violation
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.devtools.explore \
		--crash-points

SEEDS ?= 20
LATENCY_SEEDS ?= 10
SCHED_SEEDS ?= 10
RECOVERY_SEEDS ?= 10
COLLECTIVE_SEEDS ?= 5
HA_SEEDS ?= 10
SPILL_SEEDS ?= 10
chaos:
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos --check-determinism \
		--suite full --seeds $(SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos --suite smoke \
		--seeds $(SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos --suite latency \
		--seeds $(LATENCY_SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos --suite sched \
		--seeds $(SCHED_SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos \
		--suite recovery_durable --seeds $(RECOVERY_SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos \
		--suite ha --seeds $(HA_SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos \
		--suite collective --seeds $(COLLECTIVE_SEEDS)
	env JAX_PLATFORMS=cpu $(PYTHON) -m ray_tpu.chaos \
		--suite spill --seeds $(SPILL_SEEDS)
