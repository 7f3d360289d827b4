"""Numerical vs analytic gradient checking — the correctness oracle.

Parity surface: ``gradientcheck/GradientCheckUtil.java:76 (MLN), :223 (CG)`` —
central-difference numeric gradients compared param-by-param against the
analytic (here: autodiff) gradients at double precision, with a relative-error
threshold and an absolute floor for tiny gradients.

Per SURVEY §7 hard-part 6, checks run in float64 on the CPU backend (TPUs are
poor at f64); tests set JAX_PLATFORMS=cpu and this module enables x64 locally
via the ``jax.enable_x64`` context.
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import enable_x64

from deeplearning4j_tpu.utils import flat_params


def check_gradients(net, x, y, fmask=None, lmask=None, *, epsilon=1e-6,
                    max_rel_error=1e-3, min_abs_error=1e-8, print_results=False,
                    subset=None, seed=0):
    """Gradient-check a MultiLayerNetwork (or compatible model).

    Returns (passed: bool, max_observed_rel_error: float, n_failures: int).
    ``subset``: optionally check only this many randomly chosen params
    (GradientCheckUtil checks all; subset speeds up big layers).
    """
    with enable_x64(True):
        layers = net.layers
        params64 = [jax.tree.map(lambda a: jnp.asarray(a, jnp.float64), p)
                    for p in net.params_list]
        states64 = [jax.tree.map(lambda a: jnp.asarray(a, jnp.float64), s)
                    for s in net.states_list]
        x64 = jnp.asarray(x, jnp.float64)
        y64 = jnp.asarray(y, jnp.float64)
        fm = None if fmask is None else jnp.asarray(fmask, jnp.float64)
        lm = None if lmask is None else jnp.asarray(lmask, jnp.float64)

        def loss_from_vector(vec):
            plist = flat_params.vector_to_params(layers, vec)
            score, _ = net._loss_fn(plist, states64, x64, y64, fm, lm, None,
                                    train=False)
            return score

        vec0 = flat_params.params_to_vector(layers, params64)
        return _central_difference(
            loss_from_vector, vec0, epsilon=epsilon, max_rel_error=max_rel_error,
            min_abs_error=min_abs_error, print_results=print_results,
            subset=subset, seed=seed)


def _central_difference(loss_from_vector, vec0, *, epsilon, max_rel_error,
                        min_abs_error, print_results, subset, seed):
    """Shared central-difference loop (the body of GradientCheckUtil.checkGradients)."""
    analytic = np.asarray(jax.grad(loss_from_vector)(vec0))
    vec0 = np.asarray(vec0)
    n = vec0.shape[0]
    idxs = range(n)
    if subset is not None and subset < n:
        rng = np.random.RandomState(seed)
        idxs = rng.choice(n, subset, replace=False)
    loss_jit = jax.jit(loss_from_vector)
    max_rel = 0.0
    failures = 0
    for i in idxs:
        vp = vec0.copy()
        vp[i] += epsilon
        vm = vec0.copy()
        vm[i] -= epsilon
        numeric = (float(loss_jit(jnp.asarray(vp))) - float(loss_jit(jnp.asarray(vm)))) / (2 * epsilon)
        a = float(analytic[i])
        denom = abs(a) + abs(numeric)
        rel = 0.0 if denom == 0 else abs(a - numeric) / denom
        if rel > max_rel_error and abs(a - numeric) > min_abs_error:
            failures += 1
            if print_results:
                print(f"param {i}: analytic={a:.8g} numeric={numeric:.8g} rel={rel:.3g}")
        max_rel = max(max_rel, rel if abs(a - numeric) > min_abs_error else 0.0)
    return failures == 0, max_rel, failures


def check_gradients_graph(graph, mds, *, epsilon=1e-6, max_rel_error=1e-3,
                          min_abs_error=1e-8, print_results=False, subset=None,
                          seed=0):
    """Gradient-check a ComputationGraph (GradientCheckUtil.java:223 CG entry).

    ``mds``: a MultiDataSet (or DataSet, auto-converted)."""
    from deeplearning4j_tpu.models.computation_graph import _as_multi
    mds = _as_multi(mds)
    with enable_x64(True):
        layers = graph.layers
        names = graph.layer_names
        params64 = {n: jax.tree.map(lambda a: jnp.asarray(a, jnp.float64),
                                    graph.params_map[n]) for n in names}
        states64 = {n: jax.tree.map(lambda a: jnp.asarray(a, jnp.float64), s)
                    for n, s in graph.states_map.items()}
        inputs = [jnp.asarray(f, jnp.float64) for f in mds.features]
        labels = [jnp.asarray(l, jnp.float64) for l in mds.labels]
        fmasks = None if mds.features_masks is None else [
            None if m is None else jnp.asarray(m, jnp.float64)
            for m in mds.features_masks]
        lmasks = None if mds.labels_masks is None else [
            None if m is None else jnp.asarray(m, jnp.float64)
            for m in mds.labels_masks]

        def loss_from_vector(vec):
            plist = flat_params.vector_to_params(layers, vec)
            pmap = dict(zip(names, plist))
            score, _ = graph._loss_fn(pmap, states64, inputs, labels, fmasks,
                                      lmasks, None, train=False)
            return score

        vec0 = flat_params.params_to_vector(
            layers, [params64[n] for n in names])
        return _central_difference(
            loss_from_vector, vec0, epsilon=epsilon, max_rel_error=max_rel_error,
            min_abs_error=min_abs_error, print_results=print_results,
            subset=subset, seed=seed)
