# Developer entry points. The test suite itself runs the same gates
# (tests/test_graftlint.py, tests/test_sanitizers.py); these targets are
# the fast standalone forms.

PY ?= python

.PHONY: lint lint-fast lint-ci lint-baseline lint-update-baseline test \
	knobs signatures determinism sanitizers chaos smoke-rehearse

LINT_PATHS = deeplearning4j_tpu tools chip_smoke.py examples

# Whole-package interprocedural + flow-sensitive JAX hot-path and
# concurrency lint (rules G001-G018, docs/STATIC_ANALYSIS.md).
# Ratchet-aware: exit 1 on findings OR if any per-rule
# finding/suppression count grows past tools/graftlint/baseline.json —
# new code can't buy its way past a rule with fresh suppressions. Also
# enforced in tier-1 by tests/test_graftlint.py.
lint:
	$(PY) -m tools.graftlint $(LINT_PATHS) --ratchet

# CI form: the same ratcheted gate, PLUS the SARIF artifact (lint.sarif)
# CI uploads for PR annotations — one invocation, one shared
# parsed-AST/symbol/dataflow pass
lint-ci:
	$(PY) -m tools.graftlint $(LINT_PATHS) --ratchet --sarif-out lint.sarif

# pre-commit form: lint only git-changed .py files (intra-file rules).
# Prints a pointer that the interprocedural rules (the authoritative
# list is INTERPROCEDURAL_RULES in tools/graftlint/__main__.py) need
# the full cross-module graph + dataflow fixpoint — run `make lint`
# before merging.
lint-fast:
	$(PY) -m tools.graftlint $(LINT_PATHS) --changed

# rewrite the ratchet baseline after a REVIEWED change in findings or
# suppressions, and commit the result
lint-baseline lint-update-baseline:
	$(PY) -m tools.graftlint $(LINT_PATHS) --update-baseline

# fast test lane on the virtual 8-device CPU mesh
test:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/ -q -m 'not slow'

# chaos lane: the deterministic fault-injection suites (docs/ROBUSTNESS.md)
# — dead peers, round deadlines, prefetch worker crashes, NaN steps, torn
# checkpoint writes, corrupt-restore fallback, exact resume — run under the
# TSAN-lite lock-order validator (testing/lockwatch.py), the runtime
# resource-leak watcher (testing/leakwatch.py), the runtime compile
# watcher (testing/compilewatch.py), AND the runtime RNG-key watcher
# (testing/rngwatch.py): any ABBA inversion fails the lane with both
# stacks, any thread/socket/file/tempdir a test leaves live fails it
# with the leak's creation site, any steady-state or G025-flagged
# compile fails it with the dispatch site that paid it, and any key
# consumed twice fails it with both consumption stacks
chaos:
	JAX_PLATFORMS=cpu DL4J_TPU_LOCKWATCH=1 DL4J_TPU_LEAKWATCH=1 \
		DL4J_TPU_COMPILEWATCH=1 DL4J_TPU_RNGWATCH=1 \
		$(PY) -m pytest \
		tests/test_faults.py tests/test_checkpoint_resume.py \
		tests/test_lockwatch.py tests/test_leaklint.py \
		tests/test_siglint.py tests/test_detlint.py \
		tests/test_serving.py tests/test_serving_resilience.py \
		tests/test_elastic.py -q

# CPU rehearsal of chip_smoke.py — every phase (tier-1 runs only the two
# LM phases) at tiny size with Pallas in interpret mode, then the 4-device
# data-parallel phase on virtual CPU devices. Run before sending
# `python chip_smoke.py` to the chip; its last line names the CPU.
smoke-rehearse:
	$(PY) chip_smoke.py --rehearse
	$(PY) chip_smoke.py --rehearse --chips 4

# regenerate the env-knob table from the typed registry
# (deeplearning4j_tpu/config.py); tests/test_graftlint.py keeps it in sync
knobs:
	$(PY) -m deeplearning4j_tpu.config > docs/CONFIG.md

# regenerate the static compile-signature inventory (graftlint v6
# siglint, docs/STATIC_ANALYSIS.md): per model class, per program
# family — cardinality verdict, bounding ladders, cache attr, and every
# dispatch/store site
signatures:
	$(PY) -m tools.graftlint $(LINT_PATHS) --sig-report > docs/SIGNATURES.md

# regenerate the static RNG-key lineage inventory (graftlint v7 detlint,
# docs/STATIC_ANALYSIS.md): per model class — key creation, rebind, and
# consumption sites plus the carried key attributes the blessed
# split-rebind idiom threads through
determinism:
	$(PY) -m tools.graftlint $(LINT_PATHS) --det-report > docs/DETERMINISM.md

# native ASAN/TSAN lanes (the C++ twin of `make lint` — see
# docs/STATIC_ANALYSIS.md for how the two layers relate)
sanitizers:
	tests/run_sanitizers.sh
