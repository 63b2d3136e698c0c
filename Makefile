# ray_tpu developer targets.

SHELL := /bin/bash
.SHELLFLAGS := -o pipefail -c

# Run the native-code test surfaces (shm store daemon, GCS daemon, C++
# raylet lane, direct-call transport, mutable channels, spilling) against
# ASan+UBSan-instrumented builds of every native component.  The
# sanitized binaries live in a separate cache namespace
# (ray_tpu/native/_build/*-asan*), so regular runs keep the -O2 builds.
# detect_leaks=0: CPython interns/arenas leak by design.
# log_path routes every report (including ones from daemon subprocesses
# whose stderr is redirected to session logs) into one greppable dir.
# Last clean pass: round 5 (49 tests, 0 reports) — see SANITIZE.md.
LIBASAN  := $(shell g++ -print-file-name=libasan.so)
LIBUBSAN := $(shell g++ -print-file-name=libubsan.so)
SANDIR   := /tmp/rtpu_san

sanitize:
	rm -rf $(SANDIR) && mkdir -p $(SANDIR)
	RTPU_SANITIZE=1 LD_PRELOAD="$(LIBASAN) $(LIBUBSAN)" \
	ASAN_OPTIONS=detect_leaks=0:log_path=$(SANDIR)/asan \
	UBSAN_OPTIONS=print_stacktrace=1:log_path=$(SANDIR)/ubsan \
	python -m pytest tests/test_store.py tests/test_store_dataplane.py \
	    tests/test_native_gcs.py \
	    tests/test_native_raylet.py tests/test_direct_calls.py \
	    tests/test_dag.py tests/test_spilling.py -q 2>&1 | tee $(SANDIR)/pytest.log
	@! grep -rq "runtime error\|AddressSanitizer" $(SANDIR) \
	    && echo "sanitize: clean (no ASan/UBSan reports)"

# Static analysis (`rtpu check`): cross-language drift between the C++
# daemons and their Python peers, lock-order / blocking-under-mutex
# analysis, hot-path purity lint, metrics naming lint, sharding-layout
# consistency (shard) and wire-protocol reachability (proto).
# Stdlib-only, no jax import, no cluster — a few seconds, so it fronts
# the default test flow and drift fails fast.
check:
	python -m ray_tpu._private.staticcheck

# Just the two layout/protocol passes — the tight loop while editing
# sharding rules or wire_constants (sub-second).
check-fast:
	python -m ray_tpu._private.staticcheck shard,proto

test: check
	python -m pytest tests/ -q

# Store daemon under ThreadSanitizer: rebuild shm_store with
# RTPU_SANITIZE=thread (its own cache namespace, like -asan) and drive
# the store dataplane + crash-recovery + KV-tier chaos tests against it
# — the striped-pull, restart, and KV seal/pull paths are the race-
# sensitive surfaces.  Only the standalone daemon binary is
# instrumented; no LD_PRELOAD needed.
TSANDIR := /tmp/rtpu_tsan

sanitize-store:
	rm -rf $(TSANDIR) && mkdir -p $(TSANDIR)
	RTPU_SANITIZE=thread \
	TSAN_OPTIONS=log_path=$(TSANDIR)/tsan:history_size=7 \
	python -m pytest tests/test_store_dataplane.py \
	    tests/test_store_recovery.py tests/test_kv_tier.py -q \
	    2>&1 | tee $(TSANDIR)/pytest.log
	@! grep -rq "WARNING: ThreadSanitizer" $(TSANDIR) \
	    && echo "sanitize-store: clean (no TSan reports)"

# Observability end-to-end: boot a cluster, run a traced nested
# workload, assert the trace assembles cluster-wide and the dashboard
# serves valid /metrics + /api/traces payloads.
obs-smoke:
	JAX_PLATFORMS=cpu python -m ray_tpu.scripts.obs_smoke

# Control-plane scale envelope: 1M queued plain tasks through the native
# raylet lane (queue-time spillback path active, shape-indexed backlog),
# plus the actor/PG/node scenarios.  A host measurement of host code: one
# JSON line a scenario on stdout, no file.  The pytest smoke
# (tests/test_scale_smoke.py) runs --quick; the big envelope is the
# @slow test.
bench-scale:
	JAX_PLATFORMS=cpu python -m ray_tpu._private.scale_bench

.PHONY: sanitize sanitize-store check check-fast test obs-smoke bench-scale
