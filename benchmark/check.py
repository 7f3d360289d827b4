"""The comparison that decides ``correct`` for a training cell.

Two sets of readings of the first training steps -- the program's, taken from
the object the window then drives, and the plain reference's on the same
weights and batches -- become the numbers below, each held to a limit of its
own (the cell's ``limits``, set from chip readings as PERF.md records):

``loss1_gap`` ``loss2_gap`` ``loss3_gap``
    |program's loss - reference's| / |reference's|, per step.
``grad_gap``
    worst leaf of the first gradient as the optimizer got it.
``delta_gap``
    worst leaf of the parameters' change over the steps.
``grad_median_gap`` ``delta_median_gap``
    the median leaf's gap, steadier from seed to seed than the worst leaf's.

A cell's ``limits`` name the numbers it holds; the others are not computed
into the result.

A leaf's gap is the gap between the two NORMS (not the norm of a difference),
against the reference's norm of that leaf or of the median leaf, whichever is
larger: some gradients are all but zero. Leaves whose reference gradient is
under a thousandth of the median leaf's move, under Adam, by round-off alone:
they are left out of ``delta_gap`` by that rule, never by name.
"""

from __future__ import annotations

import math
import statistics

NEGLIGIBLE_GRADIENT = 1e-3   # of the median leaf's gradient norm


def _leaf_gaps(prog, ref, keep=None):
    """``(worst gap, its leaf, median gap)`` over the leaves."""
    names = [n for n in ref if keep is None or n in keep]
    if not names:
        return math.inf, None, math.inf
    floor = statistics.median(ref[n] for n in names)
    gaps = {}
    for n in names:
        if n not in prog or not math.isfinite(prog[n]):
            return math.inf, n, math.inf
        gaps[n] = abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
    at = max(gaps, key=gaps.get)
    return gaps[at], at, statistics.median(gaps.values())


def numbers(prog, ref):
    """The numbers compared, and for the worst-leaf ones where the worst was."""
    out, where = {}, {}
    steps = len(ref["loss"])
    for i in range(steps):
        p = prog["loss"][i] if i < len(prog["loss"]) else math.nan
        gap = abs(p - ref["loss"][i]) / abs(ref["loss"][i])
        out[f"loss{i + 1}_gap"] = gap if math.isfinite(gap) else math.inf
    out["grad_gap"], where["grad_gap"], out["grad_median_gap"] = _leaf_gaps(
        prog["grad_norm"], ref["grad_norm"])
    med = statistics.median(ref["grad_norm"].values())
    moved = {n for n, g in ref["grad_norm"].items()
             if g >= NEGLIGIBLE_GRADIENT * med}
    out["delta_gap"], where["delta_gap"], out["delta_median_gap"] = \
        _leaf_gaps(prog["delta_norm"], ref["delta_norm"], keep=moved)
    return out, where


def decide(prog, ref, limits):
    """``(correct, compared)``: ``compared`` maps each number the cell's
    limits name to ``{"value", "limit"}`` (and ``"leaf"`` for a worst-leaf
    number). A limit with no number is an error, and so is a cell with none."""
    vals, where = numbers(prog, ref)
    missing = sorted(set(limits) - set(vals))
    if missing or not limits:
        raise KeyError(f"limits name no number, or ones the comparison "
                       f"lacks: {missing}")
    compared, correct = {}, True
    for name, limit in limits.items():
        compared[name] = {"value": vals[name], "limit": limit}
        if where.get(name) is not None:
            compared[name]["leaf"] = where[name]
        if not vals[name] <= limit:
            correct = False
    return correct, compared


def verify_training(config, seed, program, batches, limits, precision="float32",
                    fault=None):
    """Run the configuration's plain reference over ``batches`` from the seed's
    weights and judge the program's readings by it. ``precision`` and ``fault``
    put the control or a planted fault in the REFERENCE's place of the
    program: then ``program`` is ignored and the control's readings are judged
    against the float32 reference."""
    import importlib
    reference = importlib.import_module(
        f"benchmark.references.{config['family']}")
    want = reference.first_steps(config, seed, batches)
    if precision != "float32" or fault is not None:
        program = reference.first_steps(config, seed, batches,
                                        precision=precision, fault=fault)
    return decide(program, want, limits)
