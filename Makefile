# Tier-1 verification + common dev entry points.
# `repro` is importable either via `pip install -e .` (pyproject.toml) or via
# PYTHONPATH=src — the targets below use the latter so they work in the
# offline CI container without an install step.
#
# CI (.github/workflows/ci.yml) runs: test-fast + bench-smoke + check-bench
# on Python 3.12 (test-fast includes the golden-corpus format
# pin, tests/test_golden.py), test-multidevice + bench-sharded-smoke in a
# separate multidevice lane (8 forced host devices), test-serving +
# bench-kv-smoke in a serving lane (also 8 forced host devices, for the
# sharded eviction/restore tests), test-property as its own hypothesis
# lane, test-lossy + bench-lossy-smoke in a lossy lane (error-bounded
# frontend conformance), test-async as the crash-consistency/fault-
# injection lane for the async checkpoint writer (pytest-timeout +
# faulthandler so a deadlock fails with stacks instead of hanging), and
# `ruff check` / `ruff format --check` as a separate lint job.

PY ?= python

.PHONY: test test-fast test-multidevice test-property test-serving \
	test-lossy test-async check-bench lint \
	bench-pipeline bench-decode bench-ratio bench-sharded bench-kv \
	bench-lossy bench-sharded-smoke bench-decode-smoke bench-ratio-smoke \
	bench-kv-smoke bench-lossy-smoke bench-smoke bench

test:
	PYTHONPATH=src $(PY) -m pytest -x -q

# test_properties.py is excluded here: its strategies deliberately mint
# fresh jit traces per fuzzed geometry, which is the dedicated property
# lane's job (test-property below) — running it in the CI test lane
# would duplicate that wall-clock on every PR.  Likewise the stress-marked
# concurrency tests belong to the async lane (test-async below).  Plain
# `make test` still includes both.
test-fast:
	PYTHONPATH=src $(PY) -m pytest -x -q -m "not slow and not stress" \
		--ignore=tests/test_properties.py

# Property-based lane (requires hypothesis: pip install -e .[test]).  The
# ci-property profile (tests/conftest.py) derandomizes the example stream so
# failures reproduce; statistics go to stdout for the CI artifact.
test-property:
	PYTHONPATH=src HYPOTHESIS_PROFILE=ci-property $(PY) -m pytest -q \
		tests/test_properties.py --hypothesis-show-statistics

# Sharding/batch tests with the test process itself seeing 8 (forced host)
# devices: exercises the shard-mapped "sharded" compressor/decoder pair on
# a real mesh — the @multidevice tests that skip under plain tier-1.
test-multidevice:
	PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest -q tests/test_sharding.py -m "not slow"

# Serving lane: engine + paged-KV capacity-tier tests.  Runs with 8 forced
# host devices so the kv_mesh-sharded eviction/restore tests execute instead
# of skipping (single-device tests are unaffected by the flag).
test-serving:
	PYTHONPATH=src XLA_FLAGS=--xla_force_host_platform_device_count=8 \
		$(PY) -m pytest -q tests/test_serving.py tests/test_serving_paged.py

# Lossy lane: the error-bounded frontend (quantize -> bitshuffle -> inner
# lossless stage) end to end — registry pair, bound conformance on the
# adversarial corpora, golden lossy blobs, every consumer wiring (grad
# exchange, KV tier, checkpoint groups, sharded batches), plus the
# hypothesis bound property under the fixed-seed ci-property profile
# (skips cleanly where hypothesis isn't installed).
test-lossy:
	PYTHONPATH=src HYPOTHESIS_PROFILE=ci-property $(PY) -m pytest -q \
		tests/test_lossy.py tests/test_quant.py
	PYTHONPATH=src HYPOTHESIS_PROFILE=ci-property $(PY) -m pytest -q \
		tests/test_properties.py -k lossy

# Async-I/O lane: crash-consistency, fault-injection and concurrency-stress
# harness for the double-buffered background checkpoint writer
# (runtime/async_io.py + the runtime/fault.py FaultyFS seam).  Deadlocks
# must FAIL, not hang CI: pytest-timeout (pip install -e .[test]) bounds
# each test — its flags are auto-omitted where the plugin isn't installed
# (offline container) — and pytest's built-in faulthandler dumps every
# thread's stack as a last resort either way.
test-async:
	PYTHONPATH=src $(PY) -m pytest -q tests/test_async_io.py \
		-p faulthandler -o faulthandler_timeout=300 \
		$$($(PY) -c "import importlib.util as u; print('--timeout=300 --timeout-method=thread' if u.find_spec('pytest_timeout') else '')")

# Schema-validate the tracked BENCH_*.json perf records (catches a smoke run
# accidentally written to the repo root before it clobbers the trajectory)
# plus the core/autotune.py cache schema (a drift there would silently
# invalidate every persisted tuning entry).
check-bench:
	PYTHONPATH=src $(PY) -m pytest -q tests/test_benchmarks.py -k artifact_schema

# Mirrors the CI lint job (requires ruff: pip install -e .[lint]).  Format
# enforcement covers the kernel + sharding subsystems, the serving tier,
# the pipeline module and the autotuner; the rest of src/ converges module
# by module as PRs touch it.
lint:
	ruff check src tests benchmarks
	ruff format --check src/repro/kernels src/repro/sharding \
		src/repro/serving \
		src/repro/core/pipeline.py src/repro/core/autotune.py \
		src/repro/core/entropy.py src/repro/core/lossy.py \
		src/repro/core/bitshuffle.py src/repro/runtime/async_io.py

bench-pipeline:
	PYTHONPATH=src:. $(PY) benchmarks/fig9_throughput.py --backend fused-mono

bench-decode:
	PYTHONPATH=src:. $(PY) benchmarks/fig10_decode.py --decoders all

# Compression-ratio sweep over EVERY registered compressor backend (the
# fig8 headline: deflate-full's entropy stage vs the LZSS-only container).
# Writes the tracked BENCH_ratio.json at the repo root.
bench-ratio:
	PYTHONPATH=src:. $(PY) benchmarks/fig8_ratio.py --backends all

# Shard-mapped batch compression vs the single-device dispatch on a forced
# host mesh (the script sets XLA_FLAGS itself, before importing jax).
bench-sharded:
	PYTHONPATH=src:. $(PY) benchmarks/sharded_batch.py --devices 8

# Paged-KV capacity-tier sweep: decode throughput vs resident-block budget,
# with per-budget exactness asserted against the dense-cache engine.  Writes
# the tracked BENCH_kv.json at the repo root.
bench-kv:
	PYTHONPATH=src:. $(PY) benchmarks/kv_paging.py

# Tiny-size smoke of the paging sweep: real capacity pressure (budget 4 of
# an 8-block working set) but a dozen tokens, so it finishes in seconds.
# JSON to /tmp so the tracked BENCH_kv.json perf record isn't clobbered.
bench-kv-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/kv_paging.py \
		--batch 2 --max-len 32 --prompt-tokens 4 --new-tokens 12 \
		--block-tokens 8 --out-json /tmp/BENCH_kv.smoke.json

# Lossy ratio/throughput-vs-bound sweep; every row asserts reconstruction
# within its bound before the JSON is written.  Writes the tracked
# BENCH_lossy.json at the repo root.
bench-lossy:
	PYTHONPATH=src:. $(PY) benchmarks/fig_lossy.py

# Tiny-size smoke of the lossy sweep: the full bound axis (including the
# bit-exact eb=0 reference row) on a small slice, bound asserted per row.
# JSON to /tmp so the tracked BENCH_lossy.json perf record isn't clobbered.
bench-lossy-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/fig_lossy.py \
		--nbytes 16384 --sweep-nbytes 8192 \
		--out-json /tmp/BENCH_lossy.smoke.json

bench-sharded-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/sharded_batch.py --devices 8 \
		--buffers 8 --nbytes 8192 \
		--out-json /tmp/BENCH_sharded.smoke.json

# Tiny-size smoke of the fig10 decode sweep over EVERY registered decoder
# (the default --decoders all): exercises the generic registry enumeration
# plus the fused-mono single-launch path end to end in seconds.  JSON to
# /tmp so the tracked BENCH_decode.json perf record isn't clobbered.
bench-decode-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/fig10_decode.py \
		--nbytes 16384 --sweep-nbytes 8192 \
		--out-json /tmp/BENCH_decode.smoke.json

# Tiny-size smoke of the fig8 ratio sweep over EVERY registered backend:
# exercises the generic registry enumeration + the deflate-full entropy
# container end to end in seconds.  JSON to /tmp so the tracked
# BENCH_ratio.json perf record isn't clobbered.
bench-ratio-smoke:
	PYTHONPATH=src:. $(PY) benchmarks/fig8_ratio.py \
		--nbytes 16384 --sweep-nbytes 8192 \
		--out-json /tmp/BENCH_ratio.smoke.json

# Tiny-size smoke of all three fig sweeps: exercises the bench scripts end
# to end (compress + decode + ratio + JSON artifacts) in seconds, even in
# interpret mode.  The decode/ratio parts are their own targets so the CI
# steps and local runs share one definition.  JSONs go to /tmp so the
# tracked BENCH_*.json perf records aren't clobbered with meaningless smoke
# numbers.
bench-smoke: bench-decode-smoke bench-ratio-smoke
	PYTHONPATH=src:. $(PY) benchmarks/fig9_throughput.py \
		--nbytes 16384 --sweep-nbytes 8192 \
		--out-json /tmp/BENCH_pipeline.smoke.json

bench:
	PYTHONPATH=src:. $(PY) benchmarks/run.py
