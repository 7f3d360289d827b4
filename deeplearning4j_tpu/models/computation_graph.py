"""ComputationGraph: the DAG model.

Parity surface: ``nn/graph/ComputationGraph.java`` — init (:270), topological
forward over vertices, multi-input/multi-output fit over MultiDataSetIterator
(:751) and DataSetIterator (:674), flattened params (:311-345), score,
computeGradientAndScore, evaluation.

Like MultiLayerNetwork, the whole train step (forward over the DAG, summed
output-layer losses + l1/l2, autodiff backward, per-layer updater rules, param
update) is ONE jitted XLA program. Params/states/updater state are dicts keyed
by vertex name — a pytree XLA shards and donates naturally.
"""

from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.datasets.dataset import (
    DataSet, DataSetIterator, MultiDataSet, MultiDataSetIterator,
    StackedMultiDataSet,
)
from deeplearning4j_tpu.nn.conf.computation_graph import (
    ComputationGraphConfiguration, LayerVertex,
)
from deeplearning4j_tpu.nn.layers.core import BaseOutputLayer, LossLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, GravesBidirectionalLSTM
from deeplearning4j_tpu.ops import updaters as updaters_mod
from deeplearning4j_tpu.utils import flat_params


def _as_multi(data) -> MultiDataSet:
    if isinstance(data, MultiDataSet):
        return data
    if isinstance(data, DataSet):
        return MultiDataSet(
            [data.features], [data.labels],
            None if data.features_mask is None else [data.features_mask],
            None if data.labels_mask is None else [data.labels_mask])
    raise ValueError(f"Cannot convert {type(data)} to MultiDataSet")


from deeplearning4j_tpu.models._device_state import (_OBS_GROUP_SECONDS,
                                                       _OBS_GROUPS,
                                                       _OBS_OUTPUT_SECONDS,
                                                       _OBS_STEP_SECONDS,
                                                       _OBS_STEPS,
                                                       DeviceStateMixin,
                                                       fuse_unroll, maybe_remat,
                                                       nanguard_enabled,
                                                       step_all_finite)
from deeplearning4j_tpu.testing import faults


class ComputationGraph(DeviceStateMixin):
    def __init__(self, conf: ComputationGraphConfiguration):
        obs.compilation.install()
        self.conf = conf
        self.topological_order = conf.topological_order
        self.layer_names = conf.layer_names()
        self.layers = conf.layer_confs()  # topological order — flattening order
        self.params_map = None   # name -> {param: array} for layer vertices
        self.states_map = None
        self.updater_states = None
        self.iteration = 0
        self.epoch_count = 0
        self.listeners = []
        self._score = None
        self._rng = None
        self._iter_dev = None
        self._iter_dev_py = None
        self._jit_train = {}
        self._jit_output = {}
        self._last_gradients = None
        self._pretrained = False
        self._rnn_carries = None


    # ------------------------------------------------------------------
    def init(self, params=None):
        key = jax.random.PRNGKey(self.conf.seed)
        keys = jax.random.split(key, len(self.layer_names) + 1)
        self._rng = keys[0]
        self.params_map = {}
        self.states_map = {}
        self.updater_states = {}
        for name, k in zip(self.layer_names, keys[1:]):
            layer = self.conf.vertices[name].layer
            self.params_map[name] = layer.init_params(k)
            self.states_map[name] = layer.init_state()
            self.updater_states[name] = updaters_mod.init_state(
                layer.updater_config(self.conf.max_iterations), self.params_map[name])
        if params is not None:
            self.set_params(params)
        return self

    # ---- flattened parameter API --------------------------------------
    def num_params(self):
        return flat_params.n_params(self.layers)

    def params(self):
        plist = [self.params_map[n] for n in self.layer_names]
        return np.asarray(flat_params.params_to_vector(self.layers, plist))

    def set_params(self, vec):
        plist = flat_params.vector_to_params(self.layers, jnp.asarray(vec))
        for n, p in zip(self.layer_names, plist):
            self.params_map[n] = p

    def get_layer_params(self, name):
        # copies, not views (train step donates the underlying buffers)
        return {k: jnp.copy(v) for k, v in self.params_map[name].items()}

    def set_listeners(self, listeners):
        self.listeners = list(listeners) if isinstance(listeners, (list, tuple)) else [listeners]

    # ------------------------------------------------------------------
    # forward over the DAG
    # ------------------------------------------------------------------
    def _forward_graph(self, params_map, states_map, inputs, *, train, rngs, fmasks,
                       carries=None):
        """Walk vertices in topological order.

        Returns (acts: dict name->activation incl. inputs, preouts: dict for
        output layers, new_states, masks: dict, new_carries: dict|None).

        ``carries`` (dict vertex-name → (h, c) or None) switches LSTM vertices
        into carried-state mode: the scan starts from the given carry and the
        final carry is returned — the substrate for tBPTT segments and
        rnnTimeStep on the DAG model (ComputationGraph.java:711,770,828)."""
        acts = dict(zip(self.conf.network_inputs, inputs))
        masks = {n: None for n in self.conf.network_inputs}
        if fmasks is not None:
            for n, m in zip(self.conf.network_inputs, fmasks):
                masks[n] = m
        preouts = {}
        new_states = {}
        new_carries = None if carries is None else dict(carries)
        out_set = set(self.conf.network_outputs)
        for name in self.topological_order:
            v = self.conf.vertices[name]
            in_names = self.conf.vertex_inputs[name]
            xs = [acts[i] for i in in_names]
            ms = [masks[i] for i in in_names]
            if isinstance(v, LayerVertex):
                layer = v.layer
                x, m = xs[0], ms[0]
                if v.preprocessor is not None:
                    x = v.preprocessor.pre_process(x, m)
                    m = v.preprocessor.feed_forward_mask(m)
                rng_i = None if rngs is None else rngs[name]
                # the layer's class is its scope in a profiler trace: no vertex
                # name, so that the copies of one op sum into one row
                with jax.named_scope(type(layer).__name__):
                    if name in out_set and isinstance(layer, BaseOutputLayer):
                        x_in = layer.apply_dropout(x, train=train, rng=rng_i)
                        pre = layer.pre_output(params_map[name], x_in)
                        preouts[name] = pre
                        acts[name] = layer.activation_fn()(pre)
                        new_states[name] = states_map[name]
                    elif name in out_set and isinstance(layer, LossLayer):
                        preouts[name] = x
                        acts[name], s = layer.forward(params_map[name], x, states_map[name],
                                                      train=train, rng=rng_i, mask=m)
                        new_states[name] = s
                    elif (carries is not None and isinstance(layer, LSTM)
                          and not isinstance(layer, GravesBidirectionalLSTM)):
                        x_in = layer.apply_dropout(x, train=train, rng=rng_i)
                        carry = new_carries.get(name)
                        if carry is None:
                            carry = layer.initial_carry(x_in.shape[0], x_in.dtype)
                        h0, c0 = carry
                        out, (hf, cf) = layer._scan(params_map[name], x_in, h0, c0, m)
                        new_carries[name] = (hf, cf)
                        acts[name] = out
                        new_states[name] = states_map[name]
                    else:
                        acts[name], s = maybe_remat(
                            layer, train, getattr(self.conf, "remat", False))(
                            params_map[name], x, states_map[name], m, rng_i)
                        new_states[name] = s
                masks[name] = layer.feed_forward_mask(m)
            else:
                # parameter-free vertex; rnn vertices may consult named inputs
                from deeplearning4j_tpu.nn.conf.graph import (
                    DuplicateToTimeSeriesVertex, LastTimeStepVertex,
                )
                if isinstance(v, LastTimeStepVertex) and v.mask_input_name is not None:
                    ms = [masks.get(v.mask_input_name)]
                if (isinstance(v, DuplicateToTimeSeriesVertex)
                        and v.ts_input_name is not None and len(xs) == 1):
                    # reference wiring: one wired input, time length taken from
                    # the named network input (DuplicateToTimeSeriesVertex.java)
                    xs = xs + [acts[v.ts_input_name]]
                    ms = ms + [masks.get(v.ts_input_name)]
                with jax.named_scope(type(v).__name__):
                    acts[name] = v.forward(xs, ms)
                masks[name] = v.feed_forward_mask(ms)
        return acts, preouts, new_states, masks, new_carries

    def _embedding_fed_inputs(self):
        """Network-input names consumed by an EmbeddingLayer vertex (their
        arrays carry indices, not values — exempt from compute-dtype casts)."""
        if getattr(self, "_emb_inputs", None) is None:
            from deeplearning4j_tpu.nn.layers import EmbeddingLayer
            fed = set()
            for name, ins in self.conf.vertex_inputs.items():
                v = self.conf.vertices.get(name)
                if (isinstance(v, LayerVertex)
                        and isinstance(v.layer, EmbeddingLayer)):
                    fed.update(i for i in ins
                               if i in self.conf.network_inputs)
            self._emb_inputs = fed
        return self._emb_inputs

    def _output_layer(self, name):
        layer = self.conf.vertices[name].layer
        if not isinstance(layer, (BaseOutputLayer, LossLayer)):
            raise ValueError(f"Network output {name!r} is not an output/loss layer")
        return layer

    def _split_rngs(self, rng):
        keys = jax.random.split(rng, len(self.layer_names))
        return dict(zip(self.layer_names, keys))

    def _loss_fn(self, params_map, states_map, inputs, labels, fmasks, lmasks, rngs,
                 train=True, carries=None, ew=None):
        master_params = params_map
        cd = self._compute_dtype()
        if cd is not None:   # mixed precision: bf16 forward, f32 loss
            params_map = self._cast_floats(params_map, cd)
            # embedding INDEX inputs must stay exact (bf16 rounds ids >256)
            skip = self._embedding_fed_inputs()
            inputs = [x if n in skip else x.astype(cd)
                      for n, x in zip(self.conf.network_inputs, inputs)]
            if carries is not None:
                carries = self._cast_floats(carries, cd)
        acts, preouts, new_states, _, new_carries = self._forward_graph(
            params_map, states_map, inputs, train=train, rngs=rngs, fmasks=fmasks,
            carries=carries)
        if cd is not None:
            preouts = {k: v.astype(jnp.float32) for k, v in preouts.items()}
        score = 0.0
        if ew is None:
            denom = inputs[0].shape[0]
        else:
            # shape-bucketed batch: zero-weight (padded) rows drop out of
            # every output's loss; average over REAL examples (clamped so
            # all-pad dummy scan steps stay finite)
            denom = jnp.maximum(jnp.sum(ew), 1.0)
        for i, name in enumerate(self.conf.network_outputs):
            layer = self._output_layer(name)
            if ew is None:
                lm = None if lmasks is None else lmasks[i]
                score = score + layer.compute_score(labels[i], preouts[name], mask=lm,
                                                    average=True)
            else:
                score = score + layer.compute_score(labels[i], preouts[name],
                                                    mask=ew, average=False) / denom
        for name in self.layer_names:
            layer = self.conf.vertices[name].layer
            p = master_params[name]   # regularization over f32 masters
            if p:
                score = score + updaters_mod.l1_l2_score(
                    p, l1=layer.l1 or 0.0, l2=layer.l2 or 0.0,
                    l1_bias=layer.l1_bias or 0.0, l2_bias=layer.l2_bias or 0.0) / denom
        return score, (new_states, new_carries)

    # ------------------------------------------------------------------
    # jitted train step
    # ------------------------------------------------------------------
    def _build_train_step(self, tbptt=False, guard=False):
        updater_confs = {
            n: self.conf.vertices[n].layer.updater_config(self.conf.max_iterations)
            for n in self.layer_names}
        # GSPMD sharding plan (parallel/sharding_core.py): captured at
        # build time; _cache_signature folds _plan_key() into the jit
        # cache key, so one compiled program sees one fixed plan
        plan = self._shard_plan

        def step(params_map, states_map, upd_states, rng, iteration, inputs, labels,
                 fmasks, lmasks, ew, carries, skipped):
            # ``ew`` ([batch] example weights, or None): the per-batch
            # shape-bucketing contract — zero-weight padded rows drop out
            # of loss and gradient, as in the fused scan body
            rng2, sub = jax.random.split(rng)
            rngs = self._split_rngs(sub)
            # ZeRO level 3: gather the 1/N param/state shards just-in-time
            # for the forward; the gradient constraint below (not the
            # gather's transpose) places the backward's reduction
            fwd_p = params_map if plan is None else plan.gather_params(params_map)
            fwd_s = states_map if plan is None else plan.gather_states(states_map)
            (score, (new_states, new_carries)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    fwd_p, fwd_s, inputs, labels, fmasks, lmasks, rngs,
                    True, carries, ew)
            if plan is not None:
                # ZeRO level >= 2 reduce-scatter point
                grads = plan.constrain_grads(grads)
            new_params = {}
            new_upd = {}
            for n in self.layer_names:
                p, g, s = params_map[n], grads[n], upd_states[n]
                if not p:
                    new_params[n] = p
                    new_upd[n] = s
                    continue
                with jax.named_scope("updater"):
                    upd, s2 = updaters_mod.compute_updates(
                        updater_confs[n], g, s, iteration, params=p)
                    new_params[n] = {k: p[k] - upd[k] for k in p}
                    new_upd[n] = s2
            if tbptt:
                # detach the carry between segments (truncation semantics,
                # ComputationGraph doTruncatedBPTT)
                new_carries = jax.tree.map(jax.lax.stop_gradient, new_carries)
            it2 = iteration + 1
            if guard:
                # non-finite step: select-revert the whole carry so the
                # step never happened, and count it (device-only, no sync)
                ok = step_all_finite(score, grads)
                sel = lambda nw, old: jnp.where(ok, nw, old)
                new_params = jax.tree.map(sel, new_params, params_map)
                new_states = jax.tree.map(sel, new_states, states_map)
                new_upd = jax.tree.map(sel, new_upd, upd_states)
                if tbptt:
                    new_carries = jax.tree.map(sel, new_carries, carries)
                rng2 = jnp.where(ok, rng2, rng)
                it2 = jnp.where(ok, it2, iteration)
                skipped = skipped + jnp.where(ok, 0, 1).astype(skipped.dtype)
            if plan is not None:
                # pin the RETURNED state to its at-rest placement, LAST
                # (after the guard select) so output shardings equal the
                # placement fit() commits — 0 in-fit compiles
                new_params = plan.constrain_params(new_params)
                new_states = plan.constrain_states(new_states)
                new_upd = plan.constrain_updater(new_upd)
            return (new_params, new_states, new_upd, rng2, it2, skipped,
                    score, grads, new_carries)

        # donate param/state/updater/rng/iteration buffers (in-place HBM
        # update); the trailing skipped counter is NOT donated (the deferred
        # guard policy reads it after dispatch)
        return jax.jit(step, donate_argnums=(0, 1, 2, 3, 4))

    def _fused_signature(self, xs, ys, guard):
        return ("fused",
                tuple((x.shape, str(x.dtype)) for x in xs),
                tuple(y.shape for y in ys), guard, self._plan_key())

    def _cache_signature(self, kind, inputs, labels, fmasks, lmasks):
        return (kind,
                tuple((x.shape, str(x.dtype)) for x in inputs),
                None if labels is None else tuple(y.shape for y in labels),
                fmasks is None, lmasks is None, self._plan_key())

    def fit_batch(self, mds: MultiDataSet, ew=None):
        """One update (or one tBPTT segment sweep) on one multi-minibatch.

        Returns the score as a DEVICE scalar (``float()`` it, or read
        ``score_``): keeping it on device keeps the dispatch loop async.
        ``ew`` ([batch] example weights): the per-batch shape-bucketing
        contract (see MultiLayerNetwork.fit_batch) — plain maskless SGD
        only."""
        inputs = [jnp.asarray(f) for f in mds.features]
        labels = [jnp.asarray(l) for l in mds.labels]
        if faults.fire("nan-step") is not None:
            # chaos harness: poison this step's first float input with NaN
            inputs = [jnp.full(x.shape, jnp.nan, x.dtype)
                      if i == 0 and jnp.issubdtype(x.dtype, jnp.floating)
                      else x for i, x in enumerate(inputs)]
        fmasks = None if mds.features_masks is None else [
            None if m is None else jnp.asarray(m) for m in mds.features_masks]
        lmasks = None if mds.labels_masks is None else [
            None if m is None else jnp.asarray(m) for m in mds.labels_masks]
        tbptt = (self.conf.backprop_type == "tbptt"
                 and any(x.ndim == 3 for x in inputs))
        self._check_solver_supported(tbptt)
        if ew is not None:
            if lmasks is not None or \
                    self.conf.optimization_algo != "stochastic_gradient_descent":
                raise ValueError(
                    "example weights (ew) apply only to the maskless SGD "
                    "path (tBPTT included) — the same gate as fused shape "
                    "bucketing")
            ew = jnp.asarray(ew)
        if tbptt:
            return self._fit_tbptt(inputs, labels, fmasks, lmasks, ew)
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            return self._fit_batch_solver(inputs, labels, fmasks, lmasks)
        return self._fit_one(inputs, labels, fmasks, lmasks, tbptt=False,
                             carries=None, ew=ew)[0]

    # ------------------------------------------------------------------
    # fused multi-step training (lax.scan over a stacked super-batch) —
    # the DAG twin of MultiLayerNetwork._build_fused_train_step
    # ------------------------------------------------------------------
    def _tbptt_window_plan(self, xs):
        """Host-side tBPTT window plan ``(seg, n_full, rem)`` for a stacked
        multi-input group, or None for standard backprop — the DAG twin of
        MultiLayerNetwork._tbptt_window_plan (temporal streams are the
        rank-4 [K, B, T, F] leaves, mirroring the unfused rank-3 check).
        Derived from conf + the shapes ``_fused_signature`` keys on, so
        shape-derived window control flow stays beside the blessed
        signature (the G017 contract)."""
        if self.conf.backprop_type != "tbptt":
            return None
        ts = [x.shape[2] for x in xs if x.ndim == 4]
        if not ts:
            return None
        if len(set(ts)) > 1:
            # the scan-of-scans reshapes every temporal stream by ONE
            # window plan; the host loop's clamping slice has no fused
            # equivalent — refuse with the escape hatch rather than fail
            # at trace time with a bare reshape error
            raise ValueError(
                "fused tBPTT needs all temporal inputs to share one "
                f"sequence length, got {sorted(set(ts))}; set "
                "DL4J_TPU_FUSE_TBPTT=0 to train mixed-length multi-input "
                "graphs through the host window loop")
        seg = int(self.conf.tbptt_fwd_length)   # graftlint: disable=G001 -- host config int (tbptt_fwd_length), never a device value
        t = ts[0]
        return (seg, t // seg, t % seg)

    def _build_fused_train_step(self, guard, window_plan=None):
        updater_confs = {
            n: self.conf.vertices[n].layer.updater_config(self.conf.max_iterations)
            for n in self.layer_names}
        # GSPMD sharding plan: constraints INSIDE the scan body, so XLA
        # overlaps the ZeRO collectives with each step's backward
        plan = self._shard_plan

        def body(carry, batch):
            (params_map, states_map, upd_states, rng, iteration, skipped,
             last_grads) = carry
            inputs, labels, ew = batch
            real = jnp.any(ew > 0)
            rng2, sub = jax.random.split(rng)
            rngs = self._split_rngs(sub)
            fwd_p = params_map if plan is None else plan.gather_params(params_map)
            fwd_s = states_map if plan is None else plan.gather_states(states_map)
            (score, (new_states, _)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    fwd_p, fwd_s, inputs, labels, None, None, rngs,
                    True, None, ew)
            if plan is not None:
                grads = plan.constrain_grads(grads)
            new_params = {}
            new_upd = {}
            for n in self.layer_names:
                p, g, s = params_map[n], grads[n], upd_states[n]
                if not p:
                    new_params[n] = p
                    new_upd[n] = s
                    continue
                with jax.named_scope("updater"):
                    upd, s2 = updaters_mod.compute_updates(
                        updater_confs[n], g, s, iteration, params=p)
                    new_params[n] = {k: p[k] - upd[k] for k in p}
                    new_upd[n] = s2
            keep = real
            if guard:
                ok = step_all_finite(score, grads)
                keep = jnp.logical_and(real, ok)
                skipped = skipped + jnp.where(
                    jnp.logical_and(real, jnp.logical_not(ok)), 1, 0
                ).astype(skipped.dtype)
            sel = lambda nw, old: jnp.where(keep, nw, old)
            # grads stay un-guarded (padding steps still revert): a NaN
            # gradient is the diagnostic a listener wants to see
            selr = lambda nw, old: jnp.where(real, nw, old)
            new_params = jax.tree.map(sel, new_params, params_map)
            new_states = jax.tree.map(sel, new_states, states_map)
            new_upd = jax.tree.map(sel, new_upd, upd_states)
            if plan is not None:
                # at-rest placement pinned on the POST-select carry
                # (loop-invariant scan-carry sharding — 0 in-fit compiles)
                new_params = plan.constrain_params(new_params)
                new_states = plan.constrain_states(new_states)
                new_upd = plan.constrain_updater(new_upd)
            carry = (new_params, new_states, new_upd,
                     jnp.where(keep, rng2, rng),
                     jnp.where(keep, iteration + 1, iteration),
                     skipped,
                     jax.tree.map(selr, grads, last_grads))
            return carry, score

        if window_plan is not None:
            # scan-of-scans tBPTT (docs/FUSED_LOOP.md "Sequence
            # workloads"): the DAG twin of MultiLayerNetwork's tbptt_body —
            # window slicing of the temporal streams, carry threading
            # (detached between windows) and the per-window update all on
            # device; rank-2 static / rank-4 image inputs pass whole to
            # every window exactly as the host loop's slice_time does
            seg, n_full, rem = window_plan

            def win_update(wcarry, inputs_w, labels_w, ew):
                (params_map, states_map, upd_states, rng, iteration,
                 skipped, carries, last_grads, real) = wcarry
                rng2, sub = jax.random.split(rng)
                rngs = self._split_rngs(sub)
                fwd_p = (params_map if plan is None
                         else plan.gather_params(params_map))
                fwd_s = (states_map if plan is None
                         else plan.gather_states(states_map))
                (score, (new_states, new_carries)), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(
                        fwd_p, fwd_s, inputs_w, labels_w, None,
                        None, rngs, True, carries, ew)
                if plan is not None:
                    grads = plan.constrain_grads(grads)
                new_params = {}
                new_upd = {}
                for n in self.layer_names:
                    p, g, s = params_map[n], grads[n], upd_states[n]
                    if not p:
                        new_params[n] = p
                        new_upd[n] = s
                        continue
                    with jax.named_scope("updater"):
                        upd, s2 = updaters_mod.compute_updates(
                            updater_confs[n], g, s, iteration, params=p)
                        new_params[n] = {k: p[k] - upd[k] for k in p}
                        new_upd[n] = s2
                # truncation semantics: detach the carry between windows
                new_carries = jax.tree.map(jax.lax.stop_gradient, new_carries)
                keep = real
                if guard:
                    ok = step_all_finite(score, grads)
                    keep = jnp.logical_and(real, ok)
                    skipped = skipped + jnp.where(
                        jnp.logical_and(real, jnp.logical_not(ok)), 1, 0
                    ).astype(skipped.dtype)
                sel = lambda nw, old: jnp.where(keep, nw, old)
                selr = lambda nw, old: jnp.where(real, nw, old)
                new_params = jax.tree.map(sel, new_params, params_map)
                new_states = jax.tree.map(sel, new_states, states_map)
                new_upd = jax.tree.map(sel, new_upd, upd_states)
                if plan is not None:
                    # at-rest placement on the POST-select window carry
                    new_params = plan.constrain_params(new_params)
                    new_states = plan.constrain_states(new_states)
                    new_upd = plan.constrain_updater(new_upd)
                wcarry = (new_params, new_states, new_upd,
                          jnp.where(keep, rng2, rng),
                          jnp.where(keep, iteration + 1, iteration),
                          skipped,
                          jax.tree.map(sel, new_carries, carries),
                          jax.tree.map(selr, grads, last_grads),
                          real)
                return wcarry, score

            def tbptt_body(carry, batch):
                (params_map, states_map, upd_states, rng, iteration,
                 skipped, last_grads) = carry
                inputs, labels, ew = batch
                real = jnp.any(ew > 0)
                batch_n = inputs[0].shape[0]
                dtype = inputs[0].dtype
                carries = {n: self.conf.vertices[n].layer.initial_carry(
                               batch_n, dtype)
                           for n in self._lstm_vertex_names()}
                wcarry = (params_map, states_map, upd_states, rng,
                          iteration, skipped, carries, last_grads, real)
                temporal = lambda a: a is not None and a.ndim == 3
                scores = None
                if n_full:
                    def windows(a):
                        w = a[:, :n_full * seg].reshape(
                            (a.shape[0], n_full, seg) + a.shape[2:])
                        return jnp.swapaxes(w, 0, 1)   # [n_full, B, seg, ..]
                    xw = [windows(a) if temporal(a) else None for a in inputs]
                    yw = [windows(a) if temporal(a) else None for a in labels]

                    def win_body(wc, wxy):
                        wx, wy = wxy
                        inputs_w = [w if w is not None else a
                                    for w, a in zip(wx, inputs)]
                        labels_w = [w if w is not None else a
                                    for w, a in zip(wy, labels)]
                        return win_update(wc, inputs_w, labels_w, ew)

                    # NOT fuse_unroll: the window body already contains the
                    # LSTM time-step scan (a while loop on every backend),
                    # so unrolling the window axis buys no intra-op
                    # threading on XLA:CPU — it only multiplies compiled
                    # program size by the window count (the outer K scan
                    # is already unrolled there)
                    wcarry, scores = jax.lax.scan(
                        win_body, wcarry, (xw, yw))
                if rem:
                    inputs_t = [a[:, n_full * seg:] if temporal(a) else a
                                for a in inputs]
                    labels_t = [a[:, n_full * seg:] if temporal(a) else a
                                for a in labels]
                    wcarry, s_last = win_update(wcarry, inputs_t, labels_t,
                                                ew)
                    scores = (s_last[None] if scores is None
                              else jnp.concatenate([scores, s_last[None]]))
                (params_map, states_map, upd_states, rng, iteration,
                 skipped, _carries, last_grads, _real) = wcarry
                carry = (params_map, states_map, upd_states, rng,
                         iteration, skipped, last_grads)
                return carry, scores

        step_body = body if window_plan is None else tbptt_body

        def fused(params_map, states_map, upd_states, rng, iteration, xs, ys,
                  ews, skipped):
            g0 = {n: {k: jnp.zeros_like(v) for k, v in p.items()}
                  for n, p in params_map.items()}
            carry = (params_map, states_map, upd_states, rng, iteration,
                     skipped, g0)
            (p, s, u, r, i, sk, g), scores = jax.lax.scan(
                step_body, carry, (xs, ys, ews),
                unroll=fuse_unroll(ews.shape[0]))
            return p, s, u, r, i, sk, g, scores

        # trailing skipped counter NOT donated (deferred guard policy read)
        return jax.jit(fused, donate_argnums=(0, 1, 2, 3, 4))

    def fit_fused(self, stacked):
        """All K updates of a stacked group in one XLA dispatch; listeners
        replayed on the host afterwards (one ``iteration_done`` per REAL
        step, with that step's device score)."""
        from deeplearning4j_tpu.datasets.dataset import StackedDataSet
        if isinstance(stacked, StackedDataSet):
            stacked = StackedMultiDataSet([stacked.features], [stacked.labels],
                                          stacked.weights, stacked.n_steps)
        xs = [jnp.asarray(f) for f in stacked.features]
        ys = [jnp.asarray(l) for l in stacked.labels]
        ews = jnp.asarray(stacked.weights)
        spec = faults.fire("nan-step")
        if spec is not None:
            # chaos harness: poison ONE step of the group (param = step
            # index, default 0) in the first float input stream
            j = spec.param_int(0)
            xs = [x.at[j].set(jnp.nan)
                  if i == 0 and jnp.issubdtype(x.dtype, jnp.floating)
                  else x for i, x in enumerate(xs)]
        guard = nanguard_enabled()
        k = stacked.n_steps
        if self._fuse_autotune:
            from deeplearning4j_tpu.tuning import autotuner
            plan = autotuner.plan_fused(self, xs, ys, ews, k, guard)
        else:
            plan = [(xs, ys, ews, k)]
        for cxs, cys, cews, ck in plan:
            score = self._fused_dispatch(cxs, cys, cews, ck, guard)
        return score

    def _fused_dispatch(self, xs, ys, ews, k, guard):
        """One [K, B, ...] scan dispatch plus its host bookkeeping — the
        DAG twin of MultiLayerNetwork._fused_dispatch (tBPTT groups count
        windows-per-batch updates per real step, like the host loop)."""
        t0 = time.perf_counter()
        plan = self._tbptt_window_plan(xs)
        # every window is one parameter update (n_windows == 1 untruncated)
        n_w = 1 if plan is None else (plan[1] + (1 if plan[2] else 0))
        ku = k * n_w
        with obs.span("fit.dispatch_group", steps=ku):
            sig = self._fused_signature(xs, ys, guard)
            if sig not in self._jit_train:
                self._jit_train[sig] = self._build_fused_train_step(guard,
                                                                    plan)
            (self.params_map, self.states_map, self.updater_states,
             self._rng, self._iter_dev, skipped, self._last_gradients,
             scores) = self._jit_train[sig](
                self.params_map, self.states_map, self.updater_states,
                self._rng, self._device_iteration(), xs, ys, ews,
                self._nan_skipped_arg())
            if guard:
                self._nanguard_record(skipped)
        dt = time.perf_counter() - t0
        # scores: [K] standard, [K, n_windows] tBPTT — flatten to the
        # per-update stream (padding steps trail the real ones); flatten
        # even for n_windows == 1, where a raw scores[i] would hand
        # listeners/score_ a shape-(1,) array instead of a scalar
        if plan is not None:
            scores = scores.reshape((-1,))
        _OBS_GROUP_SECONDS.record(dt)
        _OBS_GROUPS.inc()
        _OBS_STEPS.inc(ku)
        it0 = self.iteration
        self.iteration = it0 + ku
        self._iter_dev_py = self.iteration
        self._last_batch_size = int(xs[0].shape[1])
        if self.listeners:
            for i in range(ku):
                self.iteration = it0 + i + 1
                self._score = scores[i]
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration)
            self.iteration = it0 + ku
        self._score = scores[ku - 1]
        return self._score

    def _fused_probe_dispatch(self, xs, ys, ews, guard):
        """One ZERO-WEIGHT fused dispatch for the autotuner: identity
        steps, donated buffers rebound, score fetch as the timing
        barrier — the DAG twin of MultiLayerNetwork._fused_probe_dispatch.
        Returns wall seconds."""
        sig = self._fused_signature(xs, ys, guard)
        if sig not in self._jit_train:
            self._jit_train[sig] = self._build_fused_train_step(
                guard, self._tbptt_window_plan(xs))
        t0 = time.perf_counter()
        (self.params_map, self.states_map, self.updater_states, self._rng,
         self._iter_dev, _skipped, _grads, scores) = self._jit_train[sig](
            self.params_map, self.states_map, self.updater_states,
            self._rng, self._device_iteration(), xs, ys, ews,
            self._nan_skipped_arg())
        float(scores.reshape((-1,))[-1])  # graftlint: disable=G001 -- bounded first-compile probe timing barrier (autotuner), never in the steady-state loop
        return time.perf_counter() - t0

    def _fit_batch_solver(self, inputs, labels, fmasks, lmasks):
        """Line-search solver path on the DAG model (Solver.java:48 role):
        ``conf.iterations`` whole-batch solver steps over the flat parameter
        vector in one jitted program. States stay fixed during line searches
        and refresh once at the final parameters (see MultiLayerNetwork)."""
        from deeplearning4j_tpu.utils import flat_params

        self._rng, sub = jax.random.split(self._rng)
        rngs = self._split_rngs(sub)
        names = self.layer_names
        sig_extra = self._cache_signature("solver", inputs, labels, fmasks, lmasks)

        def make_vg():
            def vg(vec, states_map, inputs, labels, fmasks, lmasks, rngs):
                def loss(v):
                    plist = flat_params.vector_to_params(self.layers, v)
                    pmap = dict(zip(names, plist))
                    s, _ = self._loss_fn(pmap, states_map, inputs, labels,
                                         fmasks, lmasks, rngs, True, None)
                    return s
                return jax.value_and_grad(loss)(vec)
            return vg

        x0 = flat_params.params_to_vector(
            self.layers, [self.params_map[n] for n in names])
        vec, score = self._solver_run(
            sig_extra, make_vg, x0,
            (self.states_map, inputs, labels, fmasks, lmasks, rngs))
        for n, p in zip(names, flat_params.vector_to_params(self.layers, vec)):
            self.params_map[n] = p

        self.states_map = self._refresh_states_after_solver(
            sig_extra, self.params_map, self.states_map,
            (inputs, labels, fmasks, lmasks, rngs))
        self._post_solver_bookkeeping(score, int(inputs[0].shape[0]))
        return score

    def _fit_one(self, inputs, labels, fmasks, lmasks, *, tbptt, carries,
                 ew=None):
        guard = nanguard_enabled()
        t0 = time.perf_counter()
        with obs.span("fit.step"):
            sig = self._cache_signature("train", inputs, labels, fmasks,
                                        lmasks) + (tbptt, guard, ew is None)
            if sig not in self._jit_train:
                self._jit_train[sig] = self._build_train_step(tbptt, guard)
            (self.params_map, self.states_map, self.updater_states,
             self._rng, self._iter_dev, skipped, score, grads,
             new_carries) = self._jit_train[sig](
                self.params_map, self.states_map, self.updater_states,
                self._rng, self._device_iteration(), inputs, labels, fmasks,
                lmasks, ew, carries, self._nan_skipped_arg())
            if guard:
                self._nanguard_record(skipped)
        dt = time.perf_counter() - t0
        _OBS_STEP_SECONDS.record(dt)
        _OBS_STEPS.inc()
        self.score_ = score  # device array; synced lazily on read
        self._last_gradients = grads
        self._last_batch_size = int(inputs[0].shape[0])
        self.iteration += 1
        self._iter_dev_py = self.iteration
        if self.listeners:
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration)
        return score, new_carries

    # ------------------------------------------------------------------
    # truncated BPTT on the DAG (ComputationGraph.java:711 doTruncatedBPTT)
    # ------------------------------------------------------------------
    def _lstm_vertex_names(self):
        return [n for n in self.layer_names
                if isinstance(self.conf.vertices[n].layer, LSTM)
                and not isinstance(self.conf.vertices[n].layer,
                                   GravesBidirectionalLSTM)]

    def _fit_tbptt(self, inputs, labels, fmasks, lmasks, ew=None):
        """Segmented training sweep over the time axis; LSTM carries flow
        (detached) between segments so context crosses segment boundaries
        exactly as the reference's stateful tBPTT does. This is the HOST
        window loop — fused runs take the scan-of-scans path in
        ``_build_fused_train_step``; ``ew`` (shape-bucketing example
        weights) rides into every window's loss."""
        t = max(x.shape[1] for x in inputs if x.ndim == 3)
        seg = self.conf.tbptt_fwd_length

        def slice_time(arrs, start):
            # only rank-3 NTC arrays are temporal; rank-2 (static features) and
            # rank-4 (NHWC images) inputs of a mixed-input DAG pass through
            # whole to every segment
            if arrs is None:
                return None
            return [a[:, start:start + seg] if a is not None and a.ndim == 3
                    else a for a in arrs]

        batch = inputs[0].shape[0]
        dtype = inputs[0].dtype
        carries = {n: self.conf.vertices[n].layer.initial_carry(batch, dtype)
                   for n in self._lstm_vertex_names()}
        last_score = None
        for start in range(0, t, seg):
            xs = slice_time(inputs, start)
            ys = slice_time(labels, start)
            fm = None if fmasks is None else [
                None if m is None else m[:, start:start + seg] for m in fmasks]
            lm = None if lmasks is None else [
                None if m is None else m[:, start:start + seg] for m in lmasks]
            last_score, carries = self._fit_one(xs, ys, fm, lm, tbptt=True,
                                                carries=carries, ew=ew)
        self.score_ = last_score
        return last_score

    # ------------------------------------------------------------------
    # stateful rnn inference (ComputationGraph.rnnTimeStep:770)
    # ------------------------------------------------------------------
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, *inputs):
        """Stateful stepping inference over the DAG; accepts [batch, size]
        single steps or [batch, t, size] chunks, carries LSTM state across
        calls (reference rnnTimeStep)."""
        inputs = [jnp.asarray(x) for x in inputs]
        single = inputs[0].ndim == 2
        if single:
            inputs = [x[:, None, :] for x in inputs]
        if getattr(self, "_rnn_carries", None) is None:
            batch = inputs[0].shape[0]
            dtype = inputs[0].dtype
            self._rnn_carries = {
                n: self.conf.vertices[n].layer.initial_carry(batch, dtype)
                for n in self._lstm_vertex_names()}
        acts, _, _, _, self._rnn_carries = self._forward_graph(
            self.params_map, self.states_map, inputs, train=False, rngs=None,
            fmasks=None, carries=self._rnn_carries)
        outs = [np.asarray(acts[n]) for n in self.conf.network_outputs]
        if single:
            outs = [o[:, 0] if o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------------------
    # unsupervised layer-wise pretraining (ComputationGraph.pretrain:529-534)
    # ------------------------------------------------------------------
    def pretrain(self, iterator, epochs=1):
        """Greedy pretraining of every pretrain-capable layer vertex in
        topological order."""
        if self.params_map is None:
            self.init()
        for name in self.topological_order:
            v = self.conf.vertices[name]
            if isinstance(v, LayerVertex) and v.layer.is_pretrain_layer():
                self.pretrain_vertex(name, iterator, epochs=epochs)
        return self

    def _forward_until(self, params_map, states_map, inputs, upto_name):
        """Activations of ``upto_name``'s (preprocessed) layer input, computing
        only its ancestors; used by pretraining."""
        acts = dict(zip(self.conf.network_inputs, inputs))
        for name in self.topological_order:
            if name == upto_name:
                break
            v = self.conf.vertices[name]
            xs = [acts[i] for i in self.conf.vertex_inputs[name]]
            if isinstance(v, LayerVertex):
                x = xs[0]
                if v.preprocessor is not None:
                    x = v.preprocessor.pre_process(x, None)
                acts[name], _ = v.layer.forward(params_map[name], x, states_map[name],
                                                train=False, rng=None, mask=None)
            else:
                acts[name] = v.forward(xs, None)
        v = self.conf.vertices[upto_name]
        x = acts[self.conf.vertex_inputs[upto_name][0]]
        if v.preprocessor is not None:
            x = v.preprocessor.pre_process(x, None)
        return x

    def pretrain_vertex(self, name, iterator, epochs=1):
        self._check_solver_supported(pretrain=True)
        layer = self.conf.vertices[name].layer
        if not layer.is_pretrain_layer():
            return self
        conf_u = layer.updater_config(self.conf.max_iterations)

        # donate only the vertex's updater state (argument 2): it is
        # replaced wholesale per call; the other vertices' params/
        # states buffers are reused
        @functools.partial(jax.jit, donate_argnums=(2,))
        def pre_step(params_map, states_map, upd, rng, iteration, inputs):
            h = jax.lax.stop_gradient(
                self._forward_until(params_map, states_map, inputs, name))
            grads, score = layer.pretrain_grads(params_map[name], h, rng)
            u, upd2 = updaters_mod.compute_updates(conf_u, grads, upd, iteration, params=params_map[name])
            new_p = {k: params_map[name][k] - u[k] for k in params_map[name]}
            return new_p, upd2, score

        if isinstance(data := iterator, (DataSet, MultiDataSet)):
            iterator = [data]
        for _ in range(epochs):
            for ds in iterator:
                mds = _as_multi(ds)
                inputs = [jnp.asarray(f) for f in mds.features]
                self._rng, sub = jax.random.split(self._rng)
                new_p, new_upd, score = pre_step(
                    self.params_map, self.states_map, self.updater_states[name],
                    sub, self.iteration, inputs)
                self.params_map = dict(self.params_map)
                self.params_map[name] = new_p
                self.updater_states = dict(self.updater_states)
                self.updater_states[name] = new_upd
                # device array, synced lazily on read (fit_batch's contract)
                self.score_ = score
                self.iteration += 1
        return self

    # ------------------------------------------------------------------
    # public training API (fit(DataSetIterator):674 / fit(MultiDataSetIterator):751)
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, *, epochs=1, checkpoint_every=None,
            checkpoint_dir=None, resume_from=None):
        """Train on a (Multi)DataSet or iterator. The checkpoint/resume
        contract matches MultiLayerNetwork.fit: ``checkpoint_every=N``
        commits TrainingCheckpoints into ``checkpoint_dir`` at dispatch
        boundaries, ``resume_from=dir`` restores the newest verified one
        and fast-forwards the stream to its cursor — the resumed run is
        bitwise the uninterrupted one."""
        if self.params_map is None:
            self.init()
        if self.conf.pretrain and not self._pretrained:
            self.pretrain(data if labels is None else DataSet(data, labels))
            self._pretrained = True
        if labels is not None:
            data = DataSet(data, labels)
        every, ck_dir, keep = self._resolve_ckpt_args(
            checkpoint_every, checkpoint_dir, resume_from)
        if isinstance(data, (DataSet, MultiDataSet)):
            if every or resume_from:
                raise ValueError(
                    "checkpoint_every/resume_from need a data ITERATOR "
                    "(the checkpoint cursor is a stream position); wrap "
                    "the DataSet in an iterator to use them")
            for _ in range(self.conf.iterations):
                self.fit_batch(_as_multi(data))
            self._nanguard_flush()
            return self
        if isinstance(data, (DataSetIterator, MultiDataSetIterator)) or hasattr(data, "__iter__"):
            # async prefetch wrap for BOTH iterator kinds
            # (ComputationGraph.java:674/751 wraps in Async(Multi)DataSetIterator)
            from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
            from deeplearning4j_tpu.datasets.dataset import StackedDataSet
            wrapped = None
            use_ew = False
            # never let a fit that wraps nothing (caller-provided async
            # iterator, raw iterable) report the PREVIOUS fit's telemetry
            self._last_fuse_stats = None
            if (isinstance(data, (DataSetIterator, MultiDataSetIterator))
                    and not isinstance(data, AsyncDataSetIterator)):
                from deeplearning4j_tpu.datasets.async_iterator import (
                    default_stage)
                from deeplearning4j_tpu.tuning import autotuner
                fuse, k_resolver, bucket_pad, self._fuse_autotune = \
                    autotuner.fuse_wrap_config(self)
                use_ew = bucket_pad
                data = wrapped = AsyncDataSetIterator(
                    data, queue_size=4, stage=default_stage(), fuse=fuse,
                    k_resolver=k_resolver, bucket_pad=bucket_pad)
            start_epoch = skip = 0
            if resume_from is not None:
                cursor = self._resume_fit_checkpoint(resume_from)
                if cursor:
                    start_epoch = min(int(cursor.get("epoch", 0)), epochs)
                    skip = int(cursor.get("batch", 0))
            last_ck = self.iteration
            try:
                for ep in range(start_epoch, epochs):
                    # cursor fast-forward, first resumed epoch only (see
                    # MultiLayerNetwork.fit — the worker-thread skip keeps
                    # the fused grouping the uninterrupted continuation)
                    to_skip, skip = (skip, 0) if ep == start_epoch else (0, 0)
                    batches = to_skip
                    if to_skip and wrapped is not None:
                        wrapped.skip_next(to_skip)
                        to_skip = 0
                    for ds in data:
                        if to_skip:
                            n = getattr(ds, "n_steps", 1)
                            if n > to_skip:
                                raise ValueError(
                                    "resume cursor does not align with "
                                    "this iterator's grouping; resume "
                                    "with the same iterator configuration "
                                    "the checkpoint was written under")
                            to_skip -= n
                            continue
                        if isinstance(ds, (StackedDataSet, StackedMultiDataSet)):
                            self.fit_fused(ds)
                            batches += ds.n_steps
                        else:
                            mds = _as_multi(ds)
                            ew = getattr(ds, "example_weights", None)
                            if (ew is None and use_ew
                                    and mds.features_masks is None
                                    and mds.labels_masks is None):
                                # bucketized run: every maskless batch uses
                                # the ew program so a row-padded ragged
                                # trailer shares one train signature
                                ew = np.ones(
                                    int(mds.features[0].shape[0]),
                                    np.float32)
                            for _ in range(self.conf.iterations):
                                self.fit_batch(mds, ew=ew)
                            batches += 1
                        if every and self.iteration - last_ck >= every:
                            self._save_fit_checkpoint(ck_dir, ep, batches,
                                                      keep)
                            last_ck = self.iteration
                    for lst in self.listeners:
                        if hasattr(lst, "on_epoch_end"):
                            lst.on_epoch_end(self)
                    self.epoch_count += 1
                # deferred guard policy: the LAST dispatch's counter must
                # not ride past the fit boundary unchecked
                self._nanguard_flush()
            finally:
                self._fuse_autotune = False
                if wrapped is not None:
                    wrapped.shutdown()
                    # grouping telemetry for this fit (rebucket flushes /
                    # padding waste) — same surface as MLN.fit
                    self._last_fuse_stats = wrapped.fuse_stats()
                for lst in self.listeners:
                    close = getattr(lst, "close", None)
                    if callable(close):
                        close(self)
            return self
        raise ValueError(f"Cannot fit on {type(data)}")

    # ------------------------------------------------------------------
    # inference / scoring
    # ------------------------------------------------------------------
    def _build_output_fn(self):
        def run(params_map, states_map, inputs, fmasks):
            acts, _, _, _, _ = self._forward_graph(
                params_map, states_map, inputs, train=False, rngs=None, fmasks=fmasks)
            return [acts[n] for n in self.conf.network_outputs]
        return jax.jit(run)

    def output(self, *inputs, fmasks=None):
        """Outputs for the given inputs; single array if one network output."""
        inputs = [jnp.asarray(x) for x in inputs]
        fmasks = None if fmasks is None else [
            None if m is None else jnp.asarray(m) for m in fmasks]
        sig = self._cache_signature("out", inputs, None, fmasks, None)
        if sig not in self._jit_output:
            self._jit_output[sig] = self._build_output_fn()
        with _OBS_OUTPUT_SECONDS.time():
            # graftlint: disable=G001 -- output()'s contract IS the eval seam: it returns host numpy once per request, after the whole program ran
            outs = [np.asarray(o) for o in
                    self._jit_output[sig](self.params_map, self.states_map, inputs, fmasks)]
        return outs[0] if len(outs) == 1 else outs

    def feed_forward(self, *inputs, train=False):
        """All vertex activations by name (reference feedForward)."""
        inputs = [jnp.asarray(x) for x in inputs]
        acts, _, _, _, _ = self._forward_graph(
            self.params_map, self.states_map, inputs, train=train, rngs=None,
            fmasks=None)
        # graftlint: disable=G001 -- feed_forward returns HOST arrays by API contract (diagnostic surface, not the step loop)
        return {k: np.asarray(v) for k, v in acts.items()}

    def score(self, data, train=False):
        mds = _as_multi(data)
        inputs = [jnp.asarray(f) for f in mds.features]
        labels = [jnp.asarray(l) for l in mds.labels]
        fmasks = None if mds.features_masks is None else [
            None if m is None else jnp.asarray(m) for m in mds.features_masks]
        lmasks = None if mds.labels_masks is None else [
            None if m is None else jnp.asarray(m) for m in mds.labels_masks]
        s, _ = self._loss_fn(self.params_map, self.states_map, inputs, labels,
                             fmasks, lmasks, None, train=train)
        return float(s)

    def compute_gradient_and_score(self, data):
        mds = _as_multi(data)
        inputs = [jnp.asarray(f) for f in mds.features]
        labels = [jnp.asarray(l) for l in mds.labels]
        fmasks = None if mds.features_masks is None else [
            None if m is None else jnp.asarray(m) for m in mds.features_masks]
        lmasks = None if mds.labels_masks is None else [
            None if m is None else jnp.asarray(m) for m in mds.labels_masks]
        (score, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self.params_map, self.states_map, inputs, labels, fmasks, lmasks,
            None, False)
        self._last_gradients = grads
        self.score_ = float(score)
        return grads, self.score_

    def gradient(self):
        return self._last_gradients

    def gradient_vector(self):
        if self._last_gradients is None:
            return None
        glist = [self._last_gradients[n] for n in self.layer_names]
        return np.asarray(flat_params.params_to_vector(self.layers, glist))

    # ------------------------------------------------------------------
    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        if len(self.conf.network_outputs) != 1:
            raise ValueError("evaluate() requires a single network output")
        ev = Evaluation()
        for ds in iterator:
            mds = _as_multi(ds)
            out = self.output(*mds.features)
            lm = None if mds.labels_masks is None else mds.labels_masks[0]
            ev.eval(mds.labels[0], out, mask=lm)
        return ev

    def clone(self):
        net = ComputationGraph(self.conf)
        net.init()
        net.params_map = jax.tree.map(jnp.copy, self.params_map)
        net.states_map = jax.tree.map(jnp.copy, self.states_map)
        net.updater_states = jax.tree.map(jnp.copy, self.updater_states)
        net.iteration = self.iteration
        return net

    def summary(self):
        lines = ["name                 type                        n_params   inputs"]
        for n in self.topological_order:
            v = self.conf.vertices[n]
            if isinstance(v, LayerVertex):
                lines.append(f"{n:<20s} {type(v.layer).__name__:<27s} "
                             f"{v.layer.n_params():<10d} {self.conf.vertex_inputs[n]}")
            else:
                lines.append(f"{n:<20s} {type(v).__name__:<27s} {0:<10d} "
                             f"{self.conf.vertex_inputs[n]}")
        lines.append(f"total params: {self.num_params()}")
        return "\n".join(lines)
