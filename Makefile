PY ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: verify test ci test-multidevice dev-deps bench-table3 serve-smoke \
        tune-smoke bench-tune tile-smoke bench-tile obs-smoke bench-obs \
        zoo-smoke bench-zoo explain-smoke bench-explain examples-smoke \
        fleet-smoke bench-fleet

dev-deps:
	$(PY) -m pip install -r requirements-dev.txt

# Tier-1 verification (ROADMAP.md): install dev deps, run the full suite.
verify: dev-deps test

test:
	$(PY) -m pytest -x -q

# CI gate: the full suite except the multi-device subprocess tests.
# test_multidevice forces 8 host devices in subprocesses, which needs real
# cores; on throttled 2-core CI boxes it can exceed any sane wall budget, so
# it gates separately (make test-multidevice).
ci: dev-deps serve-smoke tune-smoke tile-smoke obs-smoke zoo-smoke \
    explain-smoke fleet-smoke examples-smoke
	$(PY) -m pytest -q --ignore=tests/test_multidevice.py

test-multidevice:
	$(PY) -m pytest -q tests/test_multidevice.py

bench-table3:
	$(PY) benchmarks/table3.py

# Serving acceptance (ISSUE 3): tiny-resolution serve_bench run asserting
# batched > sequential throughput, bit-exact served outputs, and a
# hazard-free cross-request pipeline schedule.  Benchmark JSON lands under
# the gitignored benchmarks/out/ (uploaded as a CI build artifact).
serve-smoke:
	$(PY) benchmarks/serve_bench.py --model vgg16 --img 32 --requests 16 \
	    --smoke --json serve_bench.json

# Autotuner acceptance (ISSUE 4): calibrate a device profile on a small op
# set, assert the fit deviation is within the accept band and that the
# profile-guided strategy is measured no slower end-to-end than the analytic
# one.  Writes benchmarks/out/tune_bench.json (CI build artifact).
tune-smoke:
	$(PY) benchmarks/tune_bench.py --model vgg16 --img 32 --smoke \
	    --json tune_bench.json

# Full tune benchmark: all three nets, saved profiles.
bench-tune:
	$(PY) benchmarks/tune_bench.py --save-profiles --json tune_bench.json

# Autotuned-tiling acceptance (ISSUE 5): search per-launch tile shapes on
# vgg16@32, assert tuned shapes are never measured-slower than the analytic
# Eq. 5/6 shapes, the e2e delta is within the gate, every searched strategy
# still lowers with 1.00 fused coverage, and the tuned program is bit-exact.
# Writes benchmarks/out/tile_bench.json (CI build artifact).
tile-smoke:
	$(PY) benchmarks/tile_bench.py --model vgg16 --img 32 --smoke \
	    --json tile_bench.json

# Full tiling benchmark: all three nets (the BENCH_tiling.json trajectory).
bench-tile:
	$(PY) benchmarks/tile_bench.py --json tile_bench.json

# Observability acceptance (ISSUE 6 + 8): serve vgg16@32 with the span
# tracer + sampling drift profiler on; assert the exported trace is valid
# Perfetto JSON carrying compile/serve/modeled tracks, the metrics snapshot
# is complete, the drift band is finite, and traced throughput is within 10%
# of untraced.  Then serve the same model through the full production plane
# (OpenMetrics endpoint scraped mid-run and strict-parsed, flight recorder,
# event log, per-tenant burn-rate trackers, drift gauges) within 5% of
# traced throughput, and induce one gold-SLO violation — asserting the
# burn-rate alert fires and a slo_violation flight dump lands on disk.
# Trace, bench JSON, forensic flight dumps, and the events JSONL all land
# in benchmarks/out/ (CI build artifacts).
obs-smoke:
	$(PY) benchmarks/obs_bench.py --model vgg16 --img 32 --requests 24 \
	    --smoke --trace obs_trace.json --json obs_bench.json

# Full observability benchmark: more requests, default knobs.
bench-obs:
	$(PY) benchmarks/obs_bench.py --json obs_bench.json

# Staged-pipeline / model-zoo acceptance (ISSUE 7): compile three nets into
# a content-addressed zoo, serve a skewed mixed stream co-resident vs
# swap-per-model, and assert cross-model bit-exactness, co-resident >
# swapped throughput, and that warm recompiles/zoo reopens build 0 stages
# (verified via the stage-cache metrics counters).
zoo-smoke:
	$(PY) benchmarks/zoo_bench.py --img 32 --requests 24 --smoke \
	    --json zoo_bench.json

# Full zoo benchmark: more traffic, default knobs.
bench-zoo:
	$(PY) benchmarks/zoo_bench.py --requests 96 --json zoo_bench.json

# Compile-provenance acceptance (ISSUE 9): compile vgg16@32, strict-parse
# and render the embedded CompileReport (fusion decisions with recorded
# not-chosen alternatives, tile leaderboard, DDR map), retune the tiles and
# assert the plan diff names exactly the changed units, scrape the
# /explain/<model> route mid-serve, and gate search-tracing overhead <= 5%.
# Writes benchmarks/out/explain_bench.json (CI build artifact).
explain-smoke:
	$(PY) benchmarks/explain_bench.py --model vgg16 --img 32 --smoke \
	    --json explain_bench.json

# Full explain benchmark: all three nets.
bench-explain:
	$(PY) benchmarks/explain_bench.py --model vgg16 --model resnet50 \
	    --model googlenet --json explain_bench.json

# Fault-tolerant fleet acceptance (ISSUE 10): serve googlenet@32 through a
# replicated Fleet on forced-host devices and gate the chaos harness —
# 2 replicas >= 1.7x one replica under a uniform injected launch cost,
# kill-a-replica mid-stream completes every request bit-exact (ZERO drops)
# with the eviction, retries, frozen flight dump, and post-heal re-admission
# all observable on the obs plane, and a tiny queue bound sheds load via
# AdmissionError instead of wedging.  Bench JSON + flight dumps land in
# benchmarks/out/ (CI build artifacts).
fleet-smoke:
	$(PY) benchmarks/fleet_bench.py --model googlenet --img 32 \
	    --requests 32 --replicas 2 --smoke --json fleet_bench.json

# Full fleet benchmark: more traffic, best-of-3 scaling trials.
bench-fleet:
	$(PY) benchmarks/fleet_bench.py --requests 64 --repeats 3 \
	    --json fleet_bench.json

# The README quickstarts must keep running: both examples at small
# resolution (documentation that executes is documentation that's true).
examples-smoke:
	$(PY) examples/quickstart.py
	$(PY) examples/serve_cnn.py --model vgg16 --img 32 --requests 4 \
	    --max-batch 2
