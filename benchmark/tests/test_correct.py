"""``correct`` has to come out false when the timed path is broken, and when the
control (the reference one precision below the stated one) stands in the
program's place. At the rehearsal twins' size, on the CPU; the same readings at
the cells' own size are made on the chip when limits are set (PERF.md).

Each test skips the harness's look for a chip and drives the rest of a run
(``run.measure``): build, first steps through the window's call, a short
window, the reference, the comparison.
"""

import os

import jax
import pytest

os.environ.setdefault("DL4J_TPU_PALLAS_INTERPRET", "1")

from benchmark import check, run  # noqa: E402

SEED = 2_500_000_011   # past 2**31, as the driver's are


def _measure(cell_name):
    cell = run.Cell(cell_name, rehearse=True)
    return run.measure(cell, SEED, 0.3, False, jax.local_devices()[:1])


def _half(a):
    return a[:max(1, a.shape[0] // 2)]


def _lm_state_unchanged(monkeypatch):
    from deeplearning4j_tpu.models import transformer as t
    real = t._adamw_apply
    monkeypatch.setattr(t, "_adamw_apply", lambda c, p, *a, **k:
                        (p, real(c, p, *a, **k)[1]))


def _lm_half_batch(monkeypatch):
    from deeplearning4j_tpu.models.transformer import TransformerLM
    real = TransformerLM._loss
    monkeypatch.setattr(
        TransformerLM, "_loss", lambda self, params, tokens, targets, mask,
        rng=None: real(self, params, _half(tokens), _half(targets), None, rng))


def _graph_state_unchanged(monkeypatch):
    from deeplearning4j_tpu.ops import updaters
    real = updaters.compute_updates

    def frozen(conf, grads, state, iteration, params=None):
        upd, new_state = real(conf, grads, state, iteration, params=params)
        return {k: 0.0 * u for k, u in upd.items()}, new_state
    monkeypatch.setattr(updaters, "compute_updates", frozen)


def _graph_half_batch(monkeypatch):
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    real = ComputationGraph._loss_fn

    def half(self, params_map, states_map, inputs, labels, *rest, **kw):
        return real(self, params_map, states_map, [_half(x) for x in inputs],
                    [_half(y) for y in labels], *rest, **kw)
    monkeypatch.setattr(ComputationGraph, "_loss_fn", half)


CELLS = {
    "gpt2-tiny-train": {"state_unchanged": _lm_state_unchanged,
                        "half_batch": _lm_half_batch},
    "resnet-tiny-fit": {"state_unchanged": _graph_state_unchanged,
                        "half_batch": _graph_half_batch},
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    out = _measure(cell)
    assert out["correct"] is True, out["compared"]
    assert list(out)[-1] == "compared"
    assert all(v["limit"] is not None for v in out["compared"].values())
    assert out["device"]["platform"] == jax.local_devices()[0].platform


@pytest.mark.parametrize("cell,fault", [(c, f) for c in sorted(CELLS)
                                        for f in sorted(CELLS[c])])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch):
    CELLS[cell][fault](monkeypatch)
    out = _measure(cell)
    assert out["correct"] is False, out["compared"]
    failed = [k for k, v in out["compared"].items()
              if not v["value"] <= v["limit"]]
    assert failed, out["compared"]


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("seed", [SEED, SEED + 1, SEED + 2])
def test_control_one_precision_down_is_not_correct(cell, seed):
    """The reference in fp8 (the stated precision is bfloat16) in the program's
    place, on the same weights and batches."""
    c = run.Cell(cell, rehearse=True)
    import importlib
    driver = importlib.import_module(f"benchmark.drivers.{c.traffic['driver']}")
    job = driver.Job(c.config, c.traffic, seed, run.Spans())
    batches = job.check_batches()
    job.free()
    correct, compared = check.verify_training(
        c.config, seed, None, batches, c.limits, precision="fp8")
    assert correct is False, compared
