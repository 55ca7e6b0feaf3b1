PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))
export PYTHONPATH

.PHONY: test test-fast lint-plane examples

# full tier-1 suite (includes the slow multidevice subprocess tests)
test:
	python -m pytest -q

# plane-invariant static analyzer (planelint): lock discipline, obs
# purity, env/schema hygiene over src/repro — see docs/ANALYSIS.md.
# Fails on any finding not pragma'd or baselined, and on stale baseline
# entries.
lint-plane:
	python -m repro.analysis src/repro

# fast lane: non-slow suite, planelint, the wire quorum and the examples
test-fast:
	bash scripts/ci.sh

# the typed-schema INC example apps (each self-asserts its results)
examples:
	python -m examples.quickstart
	python -m examples.mapreduce
	python -m examples.monitoring
	python -m examples.paxos
	python -m examples.train_telemetry
