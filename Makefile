# Developer entry points.  The same gates CI and the git pre-commit
# hook run (.githooks/pre-commit; enable once per clone with
# `git config core.hooksPath .githooks`).

PY ?= python

.PHONY: lint test chaos fuzz bench

# ctlint: zero unbaselined findings, no stale/dead baseline entries
# (exit 1 = new findings, 2 = stale/rotten baseline)
lint:
	$(PY) tools/lint.py

# tier-1 test suite (the ROADMAP verify line, minus the timeout wrapper)
test:
	env JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow' \
		--continue-on-collection-errors -p no:cacheprovider

# chaos sweep with the ctlint preflight (a dirty tree aborts before
# any cluster boots)
chaos:
	$(PY) tools/chaos_run.py --lint --scenarios all --seeds 8

# coverage-guided trace-fuzz smoke: seed one fast scenario, spend a
# tiny mutant budget (the committed FUZZ artifact comes from the full
# campaign: tools/chaos_fuzz.py --seed 0 --budget 16 --out FUZZ_rNN.json)
fuzz:
	$(PY) tools/chaos_fuzz.py --scenarios osd_thrash --budget 2 \
		--settle-timeout 45

# one cell of the yardstick (needs the TPU: there is no CPU mode);
# BENCHMARK.json lists the cells, PERF.md says what each metric means
bench:
	python3 benchmarks/run.py --workload ec83_write --seed 0 --seconds 51 \
		--trace 0
