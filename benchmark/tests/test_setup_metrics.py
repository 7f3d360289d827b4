"""The four readers under ``setup_s`` (layer ``build``): on the rehearsal twin
``gpt2-tiny-train`` through the harness, on a log with an entry planted after
the window opened, and on a program that keeps no log."""

import importlib
import os
import time

import jax
import pytest

os.environ.setdefault("DL4J_TPU_PALLAS_INTERPRET", "1")

from benchmark import run  # noqa: E402
from deeplearning4j_tpu import obs  # noqa: E402

SEED = 3_600_000_011   # past 2**31, as the driver's are
TWIN = "gpt2-tiny-train"
READERS = ("setup_trace_lower_s", "setup_compile_s", "setup_cold_compiles",
           "step_build_s")


def _read(metric, ctx):
    return importlib.import_module(
        f"benchmark.layer_metrics.{metric}").read(ctx)


def _ctx(window_start):
    spans = run.Spans()
    spans.window_start = window_start
    return {"spans": spans}


def test_the_twin_reads_all_four_and_they_fit_inside_its_setup():
    cell = run.Cell(TWIN, rehearse=True)
    assert set(READERS) <= {m["name"] for m in cell.per_layer}
    t0 = time.perf_counter()
    out = run.measure(cell, SEED, 0.3, False, jax.local_devices()[:1])
    assert out["correct"] is True, out["compared"]
    read = out["run"]["per_layer_untraced"]
    assert all(read[m] is not None for m in READERS)
    # the process's set-up is older than this test's: its own part is the
    # seconds from the call of measure to the window
    own_setup = out["run"]["setup_s"] - (t0 - run._T0)
    new = [e for e in obs.compiles() if e["end"] >= t0]
    before = [e for e in new
              if e["end"] < t0 + own_setup]
    parts = sum(e["trace_seconds"] + e["lower_seconds"]
                + e["backend_seconds"] for e in before)
    assert 0 < parts < own_setup
    assert 0 < read["step_build_s"] < own_setup
    (step,) = [e for e in before if e["owner"] == "lm.step"]
    assert read["step_build_s"] >= step["trace_seconds"] \
        + step["lower_seconds"] + step["backend_seconds"]
    # the readers sum the whole process's log up to the window: no less
    # than this run's own programs
    assert read["setup_trace_lower_s"] + read["setup_compile_s"] >= parts
    assert read["setup_trace_lower_s"] + read["setup_compile_s"] \
        < out["run"]["setup_s"]
    assert read["step_build_s"] < out["run"]["setup_s"]
    assert read["setup_cold_compiles"] == 0   # benchmark/tests: cache off
    assert out["run"]["compiles_in_window"] == 0


def test_an_entry_that_closed_after_the_window_opened_is_left_out(
        monkeypatch):
    entry = {"fun_name": "jit(f)", "owner": None, "trace_seconds": 1.0,
             "lower_seconds": 2.0, "backend_seconds": 4.0,
             "cache_asked": True, "cache_served": False,
             "cache_written": True, "retrieval_seconds": 0.0}
    log = [dict(entry, end=10.0), dict(entry, end=11.0, cache_served=True),
           dict(entry, end=20.5, trace_seconds=100.0, lower_seconds=100.0,
                backend_seconds=100.0)]
    monkeypatch.setattr(obs, "compiles", lambda: log)
    ctx = _ctx(window_start=20.0)
    assert _read("setup_trace_lower_s", ctx) == 6.0
    assert _read("setup_compile_s", ctx) == 8.0
    assert _read("setup_cold_compiles", ctx) == 1
    late = _ctx(window_start=30.0)
    assert _read("setup_trace_lower_s", late) == 206.0
    assert _read("setup_compile_s", late) == 108.0
    assert _read("setup_cold_compiles", late) == 2


@pytest.mark.parametrize("metric", READERS)
def test_a_program_without_the_log_reads_none(metric, monkeypatch):
    """The parent's side of the PR that brought them."""
    monkeypatch.delattr(obs, "compiles")
    monkeypatch.delitem(obs.metrics._REGISTRY, "lm.step.build_seconds",
                        raising=False)
    assert _read(metric, _ctx(window_start=time.perf_counter())) is None
