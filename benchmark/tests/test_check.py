"""The arithmetic of the comparison, on readings made up by hand."""

import math

import pytest

from benchmark import check


def _readings(grad, delta, loss=(2.0, 2.0, 2.0)):
    return {"loss": list(loss), "grad_norm": dict(grad),
            "delta_norm": dict(delta)}


REF = _readings({"a": 1.0, "b": 2.0, "c": 4.0, "tiny": 1e-6},
                {"a": 0.1, "b": 0.1, "c": 0.1, "tiny": 0.1})


def test_loss_gaps_are_relative_per_step():
    prog = _readings(REF["grad_norm"], REF["delta_norm"], (2.0, 2.2, 1.0))
    vals, _ = check.numbers(prog, REF)
    assert vals["loss1_gap"] == 0.0
    assert vals["loss2_gap"] == pytest.approx(0.1)
    assert vals["loss3_gap"] == pytest.approx(0.5)


def test_a_leaf_is_measured_against_its_norm_or_the_median_leafs():
    # median of (1e-6, 1, 2, 4) is 1.5: "a" (norm 1) is measured against 1.5,
    # "c" against its own 4
    prog = _readings({"a": 1.3, "b": 2.0, "c": 4.4, "tiny": 1e-6},
                     REF["delta_norm"])
    vals, where = check.numbers(prog, REF)
    assert vals["grad_gap"] == pytest.approx(0.3 / 1.5)
    assert where["grad_gap"] == "a"
    assert vals["grad_median_gap"] == pytest.approx((0.0 + 0.1) / 2)


def test_change_leaves_out_leaves_whose_reference_gradient_is_negligible():
    # "tiny" has a gradient under 1e-3 of the median leaf's: its change is
    # round-off under Adam, and a wild reading there is not judged
    prog = _readings(REF["grad_norm"],
                     {"a": 0.1, "b": 0.11, "c": 0.1, "tiny": 5.0})
    vals, where = check.numbers(prog, REF)
    assert vals["delta_gap"] == pytest.approx(0.1)
    assert where["delta_gap"] == "b"


def test_unmoved_leaf_reads_one_and_a_missing_or_nan_leaf_reads_infinite():
    still = _readings(REF["grad_norm"], {k: 0.0 for k in REF["delta_norm"]})
    assert check.numbers(still, REF)[0]["delta_gap"] == pytest.approx(1.0)
    gone = _readings({"a": 1.0, "b": 2.0, "tiny": 1e-6}, REF["delta_norm"])
    assert check.numbers(gone, REF)[0]["grad_gap"] == math.inf
    nan = _readings({**REF["grad_norm"], "c": math.nan}, REF["delta_norm"])
    assert check.numbers(nan, REF)[0]["grad_gap"] == math.inf
    lost = _readings(REF["grad_norm"], REF["delta_norm"], (2.0, math.nan, 2.0))
    assert check.numbers(lost, REF)[0]["loss2_gap"] == math.inf


def test_decide_holds_only_the_numbers_the_cell_names():
    prog = _readings({"a": 1.3, "b": 2.0, "c": 4.4, "tiny": 1e-6},
                     REF["delta_norm"])
    ok, compared = check.decide(prog, REF, {"grad_gap": 0.25, "loss1_gap": 0})
    assert ok and list(compared) == ["grad_gap", "loss1_gap"]
    assert compared["grad_gap"] == {"value": pytest.approx(0.2),
                                    "limit": 0.25, "leaf": "a"}
    ok, _ = check.decide(prog, REF, {"grad_gap": 0.15})
    assert not ok
    with pytest.raises(KeyError):
        check.decide(prog, REF, {"no_such_number": 1.0})
    with pytest.raises(KeyError):
        check.decide(prog, REF, {})
