# Convenience targets; CI (.github/workflows/ci.yml) calls these verbatim.

PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: verify verify-lockdep lint analyze docs-check bench

# tier-1: the gate every PR must keep green.  JUNIT=<path> additionally
# writes a junit XML report; OBS_DUMP=<dir> dumps the suite's telemetry
# (metrics snapshot + span JSONL, see tests/conftest.py) — CI uploads
# both as artifacts.
JUNIT ?=
OBS_DUMP ?=
verify:
	$(if $(OBS_DUMP),REPRO_OBS_DUMP=$(OBS_DUMP) )python -m pytest -x -q \
		$(if $(JUNIT),--junitxml=$(JUNIT))

# static checks: ruff (config in ruff.toml) + the repo-native podlint
# pass (config in podlint.toml); CI runs this as a separate job
lint:
	ruff check src tests benchmarks tools
	python -m tools.podlint src tests benchmarks

# the full analysis gate: podlint + retrace_guard + lockdep self-tests,
# then the tree scan with a report file and the acquired-before graph
# artifact (CI uploads podlint-report.txt + lockgraph.json/.dot)
analyze:
	python -m pytest -q tests/test_podlint.py tests/test_retrace_guard.py \
		tests/test_lockdep.py
	python -m tools.podlint src tests benchmarks \
		--report podlint-report.txt --lock-graph lockgraph

# tier-1's concurrency-heavy suites under the runtime lock-order
# sanitizer: every lock built through repro.concurrency.make_lock
# records acquired-before edges and raises on the first inversion —
# a dynamic proof the static lockgraph is honest (DESIGN.md §14)
verify-lockdep:
	REPRO_LOCKDEP=1 python -m pytest -x -q tests/test_ingest.py \
		tests/test_pubsub.py tests/test_autoscale.py tests/test_obs.py

# docs front door: every relative link in README.md/docs/DESIGN.md
# resolves and every `make <target>` the docs mention exists here
docs-check:
	python -m tools.check_docs

# the paper's quality tables + the roofline table (bench/run.py is the
# TPU benchmark)
bench:
	python -m benchmarks.run
