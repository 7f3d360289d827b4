"""Test bootstrap: force a virtual 8-device CPU platform before JAX import.

Mirrors the reference's strategy of testing distributed semantics without a real
cluster (Spark `local[N]` in BaseSparkTest.java:90): an 8-device host-CPU mesh
stands in for a v5e-8 slice so sharding/collective paths compile and execute.
"""

import os

# NOTE: assignment, not setdefault — a machine with a chip defaults to it,
# and tests must run on the virtual CPU mesh. Both variables are read when
# jax first initialises, so they are set before anything imports it.
os.environ["JAX_PLATFORMS"] = os.environ.get("DL4J_TPU_TEST_PLATFORM", "cpu")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# TSAN-lite lock-order validation (DL4J_TPU_LOCKWATCH=1, the `make chaos`
# lane): install as early as possible so every lock constructed from here
# on (coordinator/storage/metric instances, queues, conditions) is watched;
# module-level locks the package import itself creates stay raw — a
# documented lockwatch scope limit. The autouse session fixture at the
# bottom fails the run on any recorded inversion.
from deeplearning4j_tpu.testing import lockwatch  # noqa: E402

if lockwatch.enabled():
    lockwatch.install()

# Runtime resource-leak watcher (DL4J_TPU_LEAKWATCH=1, also the chaos
# lane): wraps Thread/socket/open/TemporaryDirectory constructors keyed by
# creation site — the same identity as graftlint's G022-G024 static
# inventory. The autouse per-test fixture below snapshots before each test
# and fails any test that leaves a watched resource live; the session
# fixture fails the run even if a test swallowed the per-test error.
from deeplearning4j_tpu.testing import leakwatch  # noqa: E402

if leakwatch.enabled():
    leakwatch.install()

# Runtime compile watcher (DL4J_TPU_COMPILEWATCH=1, also the chaos lane):
# records the in-repo stack of every XLA backend compile and attributes it
# to siglint's static dispatch inventory (graftlint G025-G027's dynamic
# twin). Installing early catches the first warm-up compiles too. The
# autouse per-test fixture below fails any test that compiles inside a
# declared steady() region or from a G025-flagged site; the session
# fixture fails the run even if a test swallowed the per-test error.
from deeplearning4j_tpu.testing import compilewatch  # noqa: E402

if compilewatch.enabled():
    compilewatch.install()

# Runtime RNG-key watcher (DL4J_TPU_RNGWATCH=1, also the chaos lane):
# wraps the jax.random producer/consumer seams keyed by creation site —
# the same identity as detlint's G028-G030 static lineage inventory
# (graftlint v7's dynamic twin). Any concrete key consumed twice fails
# the test with both consumption stacks; the session fixture fails the
# run even if a test swallowed the per-test error.
from deeplearning4j_tpu.testing import rngwatch  # noqa: E402

if rngwatch.enabled():
    rngwatch.install()

# creation-site substrings the leak gates ignore: process-lifetime
# resources tests legitimately share across the session
_LEAKWATCH_ALLOW = (
    # the native-library build lock is held for the whole session
    "nativelib.py",
)

# build the native library once up front (serialized by a file lock) so tests
# exercise the native paths; request paths themselves never compile
from deeplearning4j_tpu import nativelib  # noqa: E402

nativelib.ensure_built()


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: wall-clock-heavy end-to-end test; runs only with "
        "DL4J_TPU_SLOW=1 (the slow lane)")


def pytest_collection_modifyitems(config, items):
    if os.environ.get("DL4J_TPU_SLOW") == "1":
        return
    if "slow" in (config.option.markexpr or ""):
        return   # explicit `pytest -m slow` selects the lane by itself
    skip = pytest.mark.skip(
        reason="slow lane: set DL4J_TPU_SLOW=1 or use `pytest -m slow`")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.RandomState(12345)


@pytest.fixture(scope="session", autouse=True)
def _lockwatch_gate():
    """Under DL4J_TPU_LOCKWATCH=1 (the chaos lane) the whole session runs
    watched, and ANY recorded lock-order inversion fails the run with the
    two-stack report."""
    yield
    if lockwatch.installed():
        lockwatch.assert_clean()


@pytest.fixture(autouse=True)
def _leakwatch_per_test():
    """Under DL4J_TPU_LEAKWATCH=1 every test gets its own leak gate:
    every watched resource (thread/socket/file/temp dir from in-repo
    code) created during the test must be released by its end."""
    if not leakwatch.installed():
        yield
        return
    snap = leakwatch.snapshot()
    yield
    leakwatch.assert_clean(since=snap, allow=_LEAKWATCH_ALLOW)


@pytest.fixture(scope="session", autouse=True)
def _leakwatch_gate():
    """Session twin of the per-test gate: a leak a test swallowed (the
    per-test AssertionError caught by test code, an xfail wrapper) still
    fails the chaos lane — assert_clean records every violation before
    raising."""
    yield
    if leakwatch.installed() and leakwatch.violations():
        raise AssertionError(
            "leakwatch: resource-leak violations were recorded during "
            f"this session: {leakwatch.violations()}")


@pytest.fixture(autouse=True)
def _compilewatch_per_test():
    """Under DL4J_TPU_COMPILEWATCH=1 every test gets its own compile
    gate: no compile may land inside a steady() region or at a site the
    static pass flagged G025."""
    if not compilewatch.installed():
        yield
        return
    snap = compilewatch.snapshot()
    yield
    compilewatch.assert_clean(since=snap)


@pytest.fixture(scope="session", autouse=True)
def _compilewatch_gate():
    """Session twin: a stray-compile violation a test swallowed still
    fails the chaos lane."""
    yield
    if compilewatch.installed() and compilewatch.violations():
        raise AssertionError(
            "compilewatch: stray-compile violations were recorded during "
            f"this session: {compilewatch.violations()}")


@pytest.fixture(autouse=True)
def _rngwatch_per_test():
    """Under DL4J_TPU_RNGWATCH=1 every test gets its own key-reuse
    gate: no concrete PRNG key consumed during the test may be
    consumed twice without an interposed split/fold_in rebind."""
    if not rngwatch.installed():
        yield
        return
    snap = rngwatch.snapshot()
    yield
    rngwatch.assert_clean(since=snap)


@pytest.fixture(scope="session", autouse=True)
def _rngwatch_gate():
    """Session twin: a key-reuse violation a test swallowed still fails
    the chaos lane — violations are recorded at consume time."""
    yield
    if rngwatch.installed() and rngwatch.violations():
        raise AssertionError(
            "rngwatch: key-reuse violations were recorded during this "
            f"session:\n{rngwatch.report()}")
