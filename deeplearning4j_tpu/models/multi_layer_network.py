"""MultiLayerNetwork: the sequential model.

Parity surface: ``nn/multilayer/MultiLayerNetwork.java`` — init/param flattening
(:382, :470), fit over DataSetIterator (:917), feedForward (:703), backprop
(:1003), tBPTT (:1080, :1149), rnnTimeStep, output (:1459), score,
computeGradientAndScore (:1745), listeners, masking.

TPU-first inversion (SURVEY §7 design stance): instead of mutable layers writing
into one flattened buffer with hand-written backprop, the whole train step —
forward, loss (+l1/l2), autodiff backward, gradient normalization, updater rule,
parameter subtraction — is ONE jitted XLA program per input signature. The
flattened ``params()``/``set_params()`` view, per-layer gradients, and
listener hooks remain available as the same observable API the reference exposes.
"""

from __future__ import annotations

import functools
import time

import numpy as np
import jax
import jax.numpy as jnp

from deeplearning4j_tpu import obs

from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator, DataSet, DataSetIterator
from deeplearning4j_tpu.nn.conf.multi_layer import MultiLayerConfiguration
from deeplearning4j_tpu.nn.layers.core import BaseOutputLayer, LossLayer
from deeplearning4j_tpu.nn.layers.recurrent import LSTM, GravesBidirectionalLSTM
from deeplearning4j_tpu.ops import updaters as updaters_mod
from deeplearning4j_tpu.utils import flat_params


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


class MultiLayerNetwork(DeviceStateMixin):
    def __init__(self, conf: MultiLayerConfiguration):
        obs.compilation.install()
        self.conf = conf
        self.layers = conf.layers
        self.params_list = None
        self.states_list = None
        self.updater_states = None
        self.iteration = 0
        self.epoch_count = 0
        self.listeners = []
        self._score = None
        self._rng = None
        self._iter_dev = None       # device-resident iteration counter
        self._iter_dev_py = None    # python iteration the device counter mirrors
        self._jit_train = {}
        self._jit_output = {}
        self._rnn_carries = None
        self._last_gradients = None
        self._last_batch_size = None


    # ------------------------------------------------------------------
    # init & parameter API
    # ------------------------------------------------------------------
    def init(self, params=None):
        """Initialise parameters/updater state (MultiLayerNetwork.init:382)."""
        key = jax.random.PRNGKey(self.conf.seed)
        self._rng = key
        keys = jax.random.split(key, len(self.layers) + 1)
        self._rng = keys[0]
        self.params_list = [l.init_params(k) for l, k in zip(self.layers, keys[1:])]
        self.states_list = [l.init_state() for l in self.layers]
        self.updater_states = [
            updaters_mod.init_state(l.updater_config(self.conf.max_iterations), p)
            for l, p in zip(self.layers, self.params_list)]
        if params is not None:
            self.set_params(params)
        return self

    def num_params(self):
        return flat_params.n_params(self.layers)

    def params(self):
        """Flattened parameter vector (reference params())."""
        return np.asarray(flat_params.params_to_vector(self.layers, self.params_list))

    def set_params(self, vec):
        self.params_list = flat_params.vector_to_params(self.layers, jnp.asarray(vec))

    def get_layer_params(self, i):
        # copies, not views: the train step donates the underlying buffers, so
        # a view held across the next fit_batch would be a deleted array
        return {k: jnp.copy(v) for k, v in self.params_list[i].items()}

    def set_listeners(self, listeners):
        self.listeners = list(listeners) if isinstance(listeners, (list, tuple)) else [listeners]

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def _forward_layers(self, params_list, states_list, x, *, train, rngs, fmask,
                        carries=None):
        """Walk preprocessors + layers; return (acts, preout, new_states, out_mask,
        new_carries). ``acts`` includes the input as element 0 (feedForward parity)."""
        acts = [x]
        new_states = []
        new_carries = [None] * len(self.layers) if carries is None else list(carries)
        mask = fmask
        n = len(self.layers)
        preout = None
        for i, layer in enumerate(self.layers):
            pre = self.conf.input_preprocessors.get(i)
            if pre is not None:
                x = pre.pre_process(x, mask)
                mask = pre.feed_forward_mask(mask)
            rng_i = None if rngs is None else rngs[i]
            is_last = i == n - 1
            # the layer's class is its scope in a profiler trace: no index, so
            # that the copies of one op sum into one row (PERF.md section 3)
            with jax.named_scope(type(layer).__name__):
                if is_last and isinstance(layer, (BaseOutputLayer,)):
                    x_in = layer.apply_dropout(x, train=train, rng=rng_i)
                    preout = layer.pre_output(params_list[i], x_in)
                    x = layer.activation_fn()(preout)
                    new_states.append(states_list[i])
                elif is_last and isinstance(layer, LossLayer):
                    preout = x
                    x, s = layer.forward(params_list[i], x, states_list[i],
                                         train=train, rng=rng_i, mask=mask)
                    new_states.append(s)
                elif (carries is not None and isinstance(layer, LSTM)
                      and not isinstance(layer, GravesBidirectionalLSTM)):
                    x_in = layer.apply_dropout(x, train=train, rng=rng_i)
                    carry = new_carries[i]
                    if carry is None:
                        carry = layer.initial_carry(x_in.shape[0], x_in.dtype)
                    h0, c0 = carry
                    out, (hf, cf) = layer._scan(params_list[i], x_in, h0, c0, mask)
                    new_carries[i] = (hf, cf)
                    x = out
                    new_states.append(states_list[i])
                else:
                    x, s = maybe_remat(
                        layer, train, getattr(self.conf, "remat", False))(
                        params_list[i], x, states_list[i], mask, rng_i)
                    new_states.append(s)
            mask = layer.feed_forward_mask(mask)
            acts.append(x)
        return acts, preout, new_states, mask, new_carries

    def _output_layer(self):
        last = self.layers[-1]
        if not isinstance(last, (BaseOutputLayer, LossLayer)):
            raise ValueError("Last layer is not an output/loss layer; no loss defined")
        return last

    def _split_rngs(self, rng):
        return list(jax.random.split(rng, len(self.layers)))

    def _loss_fn(self, params_list, states_list, x, y, fmask, lmask, rngs, train=True,
                 carries=None, ew=None):
        master_params = params_list
        cd = self._compute_dtype()
        if cd is not None:   # mixed precision: bf16 forward, f32 loss
            from deeplearning4j_tpu.nn.layers import EmbeddingLayer
            params_list = self._cast_floats(params_list, cd)
            # embedding INDEX inputs must stay exact (bf16 rounds ids >256)
            if not isinstance(self.layers[0], EmbeddingLayer):
                x = x.astype(cd)
            if carries is not None:
                carries = self._cast_floats(carries, cd)
        acts, preout, new_states, _, new_carries = self._forward_layers(
            params_list, states_list, x, train=train, rngs=rngs, fmask=fmask,
            carries=carries)
        if cd is not None:
            preout = preout.astype(jnp.float32)
        out_layer = self._output_layer()
        if ew is None:
            score = out_layer.compute_score(y, preout, mask=lmask, average=True)
            denom = x.shape[0]
        else:
            # shape-bucketed batch: ``ew`` [batch] zeroes padded rows out of
            # the loss; average over REAL examples (max(.,1) keeps all-pad
            # dummy steps finite — their update is select-discarded anyway)
            denom = jnp.maximum(jnp.sum(ew), 1.0)
            score = out_layer.compute_score(y, preout, mask=ew,
                                            average=False) / denom
        for layer, p in zip(self.layers, master_params):
            if p:
                score = score + updaters_mod.l1_l2_score(
                    p, l1=layer.l1 or 0.0, l2=layer.l2 or 0.0,
                    l1_bias=layer.l1_bias or 0.0, l2_bias=layer.l2_bias or 0.0) / denom
        return score, (new_states, new_carries)

    # ------------------------------------------------------------------
    # jitted train step
    # ------------------------------------------------------------------
    def _build_train_step(self, tbptt, guard):
        updater_confs = [l.updater_config(self.conf.max_iterations) for l in self.layers]
        # GSPMD sharding plan (parallel/sharding_core.py): captured at
        # build time; the dispatch site keys _plan_key() into the blessed
        # _train_signature, so one compiled program sees one fixed plan
        plan = self._shard_plan

        def step(params_list, states_list, upd_states, rng, iteration, x, y, fmask, lmask,
                 ew, carries, skipped):
            # rng split + iteration increment live INSIDE the compiled step so
            # the host loop dispatches exactly one XLA program per minibatch.
            # ``ew`` ([batch] example weights, or None) is the shape-bucketing
            # contract of the per-batch path: zero-weight padded rows drop out
            # of loss and gradient, exactly as in the fused scan body.
            rng2, sub = jax.random.split(rng)
            rngs = self._split_rngs(sub)
            # ZeRO level 3: carried params/states are 1/N shards —
            # all-gathered just-in-time for the forward (no-op below
            # level 3). The gather sits OUTSIDE the differentiated fn so
            # the explicit gradient constraint below, not the gather's
            # transpose, decides where the backward's reduction lands.
            fwd_p = params_list if plan is None else plan.gather_params(params_list)
            fwd_s = states_list if plan is None else plan.gather_states(states_list)
            (score, (new_states, new_carries)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    fwd_p, fwd_s, x, y, fmask, lmask, rngs, True,
                    carries, ew)
            if plan is not None:
                # ZeRO level >= 2 reduce-scatter point: the updater math
                # below runs on 1/N-sized gradient shards
                grads = plan.constrain_grads(grads)
            new_params = []
            new_upd = []
            for conf_u, p, g, s in zip(updater_confs, params_list, grads, upd_states):
                if not p:
                    new_params.append(p)
                    new_upd.append(s)
                    continue
                with jax.named_scope("updater"):
                    upd, s2 = updaters_mod.compute_updates(
                        conf_u, g, s, iteration, params=p)
                    new_params.append({k: p[k] - upd[k] for k in p})
                    new_upd.append(s2)
            if tbptt:
                new_carries = jax.tree.map(jax.lax.stop_gradient, new_carries)
            it2 = iteration + 1
            if guard:
                # non-finite step: select-revert the WHOLE carry (params,
                # states, updater, rng, iteration) so the step never
                # happened, and count it. Device-only — no host sync.
                ok = step_all_finite(score, grads)
                sel = lambda n, o: jnp.where(ok, n, o)
                new_params = jax.tree.map(sel, new_params, params_list)
                new_states = jax.tree.map(sel, new_states, states_list)
                new_upd = jax.tree.map(sel, new_upd, upd_states)
                if tbptt:
                    new_carries = jax.tree.map(sel, new_carries, carries)
                rng2 = jnp.where(ok, rng2, rng)
                it2 = jnp.where(ok, it2, iteration)
                skipped = skipped + jnp.where(ok, 0, 1).astype(skipped.dtype)
            if plan is not None:
                # pin the RETURNED state to its at-rest placement (level
                # <= 2: all-gather of the sharded delta onto the
                # replicated params; level 3: shards stay shards between
                # steps). Applied LAST — after the guard select — so the
                # program's output shardings equal the rest placement and
                # every later dispatch is a cache hit (0 in-fit compiles).
                new_params = plan.constrain_params(new_params)
                new_states = plan.constrain_states(new_states)
                new_upd = plan.constrain_updater(new_upd)
            return (new_params, new_states, new_upd, rng2, it2, skipped,
                    score, grads, new_carries)

        # donate params/updater/rng/iteration buffers: XLA updates in place
        # instead of allocating fresh HBM + copying every step (the skipped
        # counter is NOT donated: the deferred guard policy reads it later)
        return jax.jit(step, donate_argnums=(0, 1, 2, 3, 4))

    def _train_signature(self, x, y, fmask, lmask, tbptt, guard, ew=None):
        return ("train", x.shape, str(x.dtype), None if y is None else y.shape,
                fmask is None, lmask is None, ew is None, tbptt, guard,
                self._plan_key())

    def _fused_signature(self, xs, ys, guard):
        return ("fused", xs.shape, str(xs.dtype), ys.shape, guard,
                self._plan_key())

    def _output_signature(self, x, fmask):
        return ("out", x.shape, str(x.dtype), fmask is None)

    def fit_batch(self, x, y, fmask=None, lmask=None, ew=None):
        """One parameter update on one minibatch (the inner step of fit:951-971).

        Returns the minibatch score as a DEVICE scalar (use ``float()`` or read
        ``net.score_`` to fetch it); keeping it on device lets the host loop
        run ahead of the TPU instead of syncing every step.

        ``ew`` ([batch] example weights) is the shape-bucketing contract:
        a row-padded ragged batch carries zeros over its padding tail so it
        trains identically to the raw ragged batch while compiling against
        the bucket's one signature. ``fit()`` pairs it with ew=ones full
        batches so a whole bucketized run holds ONE train signature."""
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        if faults.fire("nan-step") is not None:
            # chaos harness: poison this step's float inputs with NaN so the
            # loss/gradients go non-finite and the guard must catch it
            if jnp.issubdtype(x.dtype, jnp.floating):
                x = jnp.full(x.shape, jnp.nan, x.dtype)
            else:
                y = jnp.full(y.shape, jnp.nan, y.dtype)
        fmask = None if fmask is None else jnp.asarray(fmask)
        lmask = None if lmask is None else jnp.asarray(lmask)
        tbptt = self.conf.backprop_type == "tbptt" and x.ndim == 3
        self._check_solver_supported(tbptt)
        if ew is not None:
            if lmask is not None or \
                    self.conf.optimization_algo != "stochastic_gradient_descent":
                raise ValueError(
                    "example weights (ew) apply only to the maskless SGD "
                    "path (tBPTT included) — the same gate as fused shape "
                    "bucketing")
            ew = jnp.asarray(ew)
        if tbptt:
            return self._fit_tbptt(x, y, fmask, lmask, ew)
        if self.conf.optimization_algo != "stochastic_gradient_descent":
            return self._fit_batch_solver(x, y, fmask, lmask)
        guard = nanguard_enabled()
        t0 = time.perf_counter()
        with obs.span("fit.step"):
            sig = self._train_signature(x, y, fmask, lmask, False, guard, ew)
            if sig not in self._jit_train:
                self._jit_train[sig] = self._build_train_step(False, guard)
            (self.params_list, self.states_list, self.updater_states,
             self._rng, self._iter_dev, skipped, score, grads, _) = \
                self._jit_train[sig](
                    self.params_list, self.states_list, self.updater_states,
                    self._rng, self._device_iteration(), x, y, fmask, lmask,
                    ew, None, self._nan_skipped_arg())
            if guard:
                self._nanguard_record(skipped)
        dt = time.perf_counter() - t0
        _OBS_STEP_SECONDS.record(dt)
        _OBS_STEPS.inc()
        self.score_ = score  # device array; synced lazily on read
        self._last_gradients = grads
        self._last_batch_size = int(x.shape[0])
        self.iteration += 1
        self._iter_dev_py = self.iteration
        if self.listeners:
            for lst in self.listeners:
                lst.iteration_done(self, self.iteration)
        return score

    # ------------------------------------------------------------------
    # fused multi-step training (lax.scan over a stacked super-batch)
    # ------------------------------------------------------------------
    def _tbptt_window_plan(self, xs):
        """Host-side tBPTT window plan ``(seg, n_full, rem)`` for a stacked
        [K, B, T, F] group, or None when this model/group trains standard
        backprop. Derived ONLY from conf + the group's shapes — the same
        quantities ``_fused_signature`` already keys the jit cache on — so
        every cached fused program sees one fixed plan: the shape-derived
        window count steers trace-time control flow strictly beside the
        blessed signature, never per-dispatch (the G017 contract)."""
        if self.conf.backprop_type != "tbptt" or xs.ndim != 4:
            return None
        seg = int(self.conf.tbptt_fwd_length)   # graftlint: disable=G001 -- host config int (tbptt_fwd_length), never a device value
        t = xs.shape[2]
        return (seg, t // seg, t % seg)

    def _build_fused_train_step(self, guard, window_plan=None):
        """K parameter updates inside ONE jitted program: scan over the
        stacked [K, B, ...] leaves with carry (params, states, updater
        states, rng, iteration, skipped counter, last grads). Zero-weight
        (padding) steps are identity updates — the whole carry, rng split
        and iteration counter included, is select-reverted — so one
        compiled signature serves every group, ragged trailers included,
        with updates bit-matching the sequential ``fit_batch`` loop. With
        ``guard``, a REAL step whose loss/grads are non-finite is reverted
        the same way and bumps the in-carry skipped counter — still zero
        host syncs inside the scan.

        With ``window_plan`` (tBPTT models; ``(seg, full windows, trailing
        remainder)`` host ints the dispatch site derives from the SAME
        shapes ``_fused_signature`` keys on), each scanned step is itself
        a scan over that batch's tBPTT windows: window slicing, LSTM-carry
        threading (detached between windows) and the per-window update all
        run on device, so a tBPTT group costs ONE dispatch exactly like a
        standard group, with per-window updates matching the host window
        loop to 1 ulp (bitwise across fused grouping contracts — see
        docs/FUSED_LOOP.md "Sequence workloads"). Scores come back
        [K, n_windows]."""
        updater_confs = [l.updater_config(self.conf.max_iterations) for l in self.layers]
        # GSPMD sharding plan: the with_sharding_constraint placements
        # below sit INSIDE the scan body, so XLA overlaps the ZeRO
        # reduce-scatter/all-gather collectives with each step's backward
        # instead of serializing a monolithic all-reduce per group
        plan = self._shard_plan

        def body(carry, batch):
            (params_list, states_list, upd_states, rng, iteration, skipped,
             last_grads) = carry
            x, y, ew = batch
            real = jnp.any(ew > 0)
            rng2, sub = jax.random.split(rng)
            rngs = self._split_rngs(sub)
            fwd_p = params_list if plan is None else plan.gather_params(params_list)
            fwd_s = states_list if plan is None else plan.gather_states(states_list)
            (score, (new_states, _)), grads = jax.value_and_grad(
                self._loss_fn, has_aux=True)(
                    fwd_p, fwd_s, x, y, None, None, rngs, True,
                    None, ew)
            if plan is not None:
                grads = plan.constrain_grads(grads)
            new_params = []
            new_upd = []
            for conf_u, p, g, s in zip(updater_confs, params_list, grads, upd_states):
                if not p:
                    new_params.append(p)
                    new_upd.append(s)
                    continue
                with jax.named_scope("updater"):
                    upd, s2 = updaters_mod.compute_updates(
                        conf_u, g, s, iteration, params=p)
                    new_params.append({k: p[k] - upd[k] for k in p})
                    new_upd.append(s2)
            keep = real
            if guard:
                ok = step_all_finite(score, grads)
                keep = jnp.logical_and(real, ok)
                skipped = skipped + jnp.where(
                    jnp.logical_and(real, jnp.logical_not(ok)), 1, 0
                ).astype(skipped.dtype)
            sel = lambda n, o: jnp.where(keep, n, o)
            # grads stay un-guarded (padding steps still revert): a NaN
            # gradient is the diagnostic a listener wants to see
            selr = lambda n, o: jnp.where(real, n, o)
            new_params = jax.tree.map(sel, new_params, params_list)
            new_states = jax.tree.map(sel, new_states, states_list)
            new_upd = jax.tree.map(sel, new_upd, upd_states)
            if plan is not None:
                # at-rest placement pinned on the POST-select carry, so
                # the scan carry's sharding is loop-invariant and equals
                # the placement fit() commits — later dispatches are
                # cache hits (0 in-fit compiles)
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
            seg, n_full, rem = window_plan

            def win_update(wcarry, xw, yw, ew):
                # one tBPTT window update — the fused twin of
                # _build_train_step's step with tbptt=True (same rng split,
                # updater math, carry detach and guard select-revert), plus
                # the padding-step revert of the fused contract
                (params_list, states_list, upd_states, rng, iteration,
                 skipped, carries, last_grads, real) = wcarry
                rng2, sub = jax.random.split(rng)
                rngs = self._split_rngs(sub)
                fwd_p = (params_list if plan is None
                         else plan.gather_params(params_list))
                fwd_s = (states_list if plan is None
                         else plan.gather_states(states_list))
                (score, (new_states, new_carries)), grads = jax.value_and_grad(
                    self._loss_fn, has_aux=True)(
                        fwd_p, fwd_s, xw, yw, None, None, rngs,
                        True, carries, ew)
                if plan is not None:
                    grads = plan.constrain_grads(grads)
                new_params = []
                new_upd = []
                for conf_u, p, g, s in zip(updater_confs, params_list, grads,
                                           upd_states):
                    if not p:
                        new_params.append(p)
                        new_upd.append(s)
                        continue
                    with jax.named_scope("updater"):
                        upd, s2 = updaters_mod.compute_updates(
                            conf_u, g, s, iteration, params=p)
                        new_params.append({k: p[k] - upd[k] for k in p})
                        new_upd.append(s2)
                # truncation semantics: detach the carry between windows
                new_carries = jax.tree.map(jax.lax.stop_gradient, new_carries)
                keep = real
                if guard:
                    ok = step_all_finite(score, grads)
                    keep = jnp.logical_and(real, ok)
                    skipped = skipped + jnp.where(
                        jnp.logical_and(real, jnp.logical_not(ok)), 1, 0
                    ).astype(skipped.dtype)
                sel = lambda n, o: jnp.where(keep, n, o)
                selr = lambda n, o: jnp.where(real, n, o)
                new_params = jax.tree.map(sel, new_params, params_list)
                new_states = jax.tree.map(sel, new_states, states_list)
                new_upd = jax.tree.map(sel, new_upd, upd_states)
                if plan is not None:
                    # at-rest placement on the POST-select window carry
                    # (loop-invariant sharding — the 0-in-fit-compiles
                    # contract)
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
                # scan-of-scans: the inner scan walks this batch's FULL
                # tBPTT windows (reshaped off the time axis); a ragged
                # trailing window is one extra traced update with its real
                # (shorter) length — the same per-window shapes, order and
                # math as the host loop
                (params_list, states_list, upd_states, rng, iteration,
                 skipped, last_grads) = carry
                x, y, ew = batch
                real = jnp.any(ew > 0)
                carries = [l.initial_carry(x.shape[0], x.dtype)
                           if (isinstance(l, LSTM)
                               and not isinstance(l, GravesBidirectionalLSTM))
                           else None
                           for l in self.layers]
                wcarry = (params_list, states_list, upd_states, rng,
                          iteration, skipped, carries, last_grads, real)
                slice_y = y.ndim == 3   # per-timestep labels window-slice
                scores = None
                if n_full:
                    def windows(a):
                        w = a[:, :n_full * seg].reshape(
                            (a.shape[0], n_full, seg) + a.shape[2:])
                        return jnp.swapaxes(w, 0, 1)   # [n_full, B, seg, ..]
                    xw = windows(x)
                    yw = windows(y) if slice_y else None

                    def win_body(wc, wxy):
                        wx, wy = wxy
                        return win_update(wc, wx, wy if slice_y else y, ew)

                    # NOT fuse_unroll: the window body already contains the
                    # LSTM time-step scan (a while loop on every backend),
                    # so unrolling the window axis buys no intra-op
                    # threading on XLA:CPU — it only multiplies compiled
                    # program size by the window count (the outer K scan
                    # is already unrolled there)
                    wcarry, scores = jax.lax.scan(
                        win_body, wcarry, (xw, yw))
                if rem:
                    xt = x[:, n_full * seg:]
                    yt = y[:, n_full * seg:] if slice_y else y
                    wcarry, s_last = win_update(wcarry, xt, yt, ew)
                    scores = (s_last[None] if scores is None
                              else jnp.concatenate([scores, s_last[None]]))
                (params_list, states_list, upd_states, rng, iteration,
                 skipped, _carries, last_grads, _real) = wcarry
                carry = (params_list, states_list, upd_states, rng,
                         iteration, skipped, last_grads)
                return carry, scores

        step_body = body if window_plan is None else tbptt_body

        def fused(params_list, states_list, upd_states, rng, iteration, xs,
                  ys, ews, skipped):
            g0 = [{k: jnp.zeros_like(v) for k, v in p.items()}
                  for p in params_list]
            carry = (params_list, states_list, upd_states, rng, iteration,
                     skipped, g0)
            (p, s, u, r, i, sk, g), scores = jax.lax.scan(
                step_body, carry, (xs, ys, ews),
                unroll=fuse_unroll(xs.shape[0]))
            return p, s, u, r, i, sk, g, scores

        # the skipped counter (trailing arg) is NOT donated: the deferred
        # guard policy reads the previous group's counter after dispatch
        return jax.jit(fused, donate_argnums=(0, 1, 2, 3, 4))

    def fit_fused(self, stacked):
        """All K updates of a ``StackedDataSet`` in one XLA dispatch.

        Listener/score semantics match K sequential ``fit_batch`` calls: the
        per-step score vector comes back from the scan and listeners are
        replayed on the host afterwards, one ``iteration_done`` per REAL
        step, with ``score_``/``iteration`` set to that step's values.

        With the fusion autotuner armed (``fit()`` under
        ``DL4J_TPU_FUSE_AUTOTUNE=1``), the first full-size group of an
        undecided bucket is probed and in-flight probe-size groups are
        re-chunked to the decided K (tuning/autotuner.py); otherwise the
        group dispatches whole."""
        xs = jnp.asarray(stacked.features)
        ys = jnp.asarray(stacked.labels)
        ews = jnp.asarray(stacked.weights)
        spec = faults.fire("nan-step")
        if spec is not None:
            # chaos harness: poison ONE step of the group (param = step
            # index, default 0) — the guard must revert exactly that step
            xs = xs.at[spec.param_int(0)].set(jnp.nan)
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
        """One [K, B, ...] scan dispatch plus its host bookkeeping: guard
        record, obs metrics/span, listener replay for the ``k`` REAL
        steps (times the windows-per-batch for tBPTT groups — every
        window is one parameter update, exactly as in the host loop)."""
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
            (self.params_list, self.states_list, self.updater_states,
             self._rng, self._iter_dev, skipped, self._last_gradients,
             scores) = self._jit_train[sig](
                self.params_list, self.states_list, self.updater_states,
                self._rng, self._device_iteration(), xs, ys, ews,
                self._nan_skipped_arg())
            if guard:
                self._nanguard_record(skipped)
        dt = time.perf_counter() - t0
        # scores: [K] standard, [K, n_windows] tBPTT — flatten to the
        # per-update stream (padding steps trail, so the first ku entries
        # are exactly the real updates); flatten even for n_windows == 1,
        # where scores is still rank-2 and a raw scores[i] would hand
        # listeners/score_ a shape-(1,) array instead of a scalar
        if plan is not None:
            scores = scores.reshape((-1,))
        _OBS_GROUP_SECONDS.record(dt)
        _OBS_GROUPS.inc()
        _OBS_STEPS.inc(ku)
        it0 = self.iteration
        self.iteration = it0 + ku
        self._iter_dev_py = self.iteration
        self._last_batch_size = int(xs.shape[1])
        if self.listeners:
            # host-side replay AFTER the fused block (per-step scores are
            # device scalars, synced only if a listener reads them)
            for i in range(ku):
                self.iteration = it0 + i + 1
                self._score = scores[i]
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration)
            self.iteration = it0 + ku
        self._score = scores[ku - 1]
        return self._score

    def _fused_probe_dispatch(self, xs, ys, ews, guard):
        """One ZERO-WEIGHT fused dispatch for the autotuner (tuning/
        autotuner.py): every step select-reverts — the padding-step
        mechanism — so params/updater/rng/iteration come back bit-equal
        and the rebind below only swaps buffers (the donated carry must
        be rebound, never discarded). The score fetch is the timing
        barrier. Returns wall seconds; the compiled program lands under
        the blessed signature (the tuner evicts losers)."""
        sig = self._fused_signature(xs, ys, guard)
        if sig not in self._jit_train:
            self._jit_train[sig] = self._build_fused_train_step(
                guard, self._tbptt_window_plan(xs))
        t0 = time.perf_counter()
        (self.params_list, self.states_list, self.updater_states, self._rng,
         self._iter_dev, _skipped, _grads, scores) = self._jit_train[sig](
            self.params_list, self.states_list, self.updater_states,
            self._rng, self._device_iteration(), xs, ys, ews,
            self._nan_skipped_arg())
        float(scores.reshape((-1,))[-1])  # graftlint: disable=G001 -- bounded first-compile probe timing barrier (autotuner), never in the steady-state loop
        return time.perf_counter() - t0

    def _fit_batch_solver(self, x, y, fmask, lmask):
        """Line-search solver path (Solver.java:48 → ConjugateGradient/LBFGS/
        LineGradientDescent): run ``conf.iterations`` whole-batch solver
        iterations on the flat parameter vector in ONE jitted program.

        Layer states stay fixed during the line searches (a consistent loss
        is what makes Armijo probes meaningful) and are refreshed by one
        forward pass at the final parameters."""
        self._rng, sub = jax.random.split(self._rng)
        rngs = self._split_rngs(sub)  # fixed across probes: consistent loss
        sig_extra = self._solver_signature(x, y, fmask, lmask)

        def make_vg():
            def vg(vec, states, x, y, fmask, lmask, rngs):
                def loss(v):
                    plist = flat_params.vector_to_params(self.layers, v)
                    s, _ = self._loss_fn(plist, states, x, y, fmask, lmask,
                                         rngs, True, None)
                    return s
                return jax.value_and_grad(loss)(vec)
            return vg

        x0 = flat_params.params_to_vector(self.layers, self.params_list)
        vec, score = self._solver_run(
            sig_extra, make_vg, x0, (self.states_list, x, y, fmask, lmask, rngs))
        self.params_list = flat_params.vector_to_params(self.layers, vec)

        self.states_list = self._refresh_states_after_solver(
            sig_extra, self.params_list, self.states_list,
            (x, y, fmask, lmask, rngs))
        self._post_solver_bookkeeping(score, int(x.shape[0]))
        return score

    def _fit_tbptt(self, x, y, fmask, lmask, ew=None):
        """Truncated BPTT (doTruncatedBPTT, MultiLayerNetwork.java:1080).

        The HOST window loop: one jitted dispatch per window. Fused runs
        (``fuse_allowed`` + ``DL4J_TPU_FUSE_TBPTT``) route stacked groups
        through the scan-of-scans in ``_build_fused_train_step`` instead;
        masked batches and the ``DL4J_TPU_FUSE_TBPTT=0`` escape hatch land
        here. ``ew`` ([batch] example weights, shape-bucketing contract)
        rides into every window's loss."""
        t = x.shape[1]
        seg = self.conf.tbptt_fwd_length
        carries = [None] * len(self.layers)
        carries_init = False
        last_score = None
        guard = nanguard_enabled()
        for start in range(0, t, seg):
            xs = x[:, start:start + seg]
            ys = y[:, start:start + seg] if y.ndim == 3 else y
            fm = None if fmask is None else fmask[:, start:start + seg]
            lm = None if lmask is None else lmask[:, start:start + seg]
            t0 = time.perf_counter()
            with obs.span("fit.step"):
                sig = self._train_signature(xs, ys, fm, lm, True, guard, ew)
                if sig not in self._jit_train:
                    self._jit_train[sig] = self._build_train_step(True, guard)
                # materialise initial carries so the jit signature is stable
                if not carries_init:
                    carries = [l.initial_carry(xs.shape[0], xs.dtype)
                               if (isinstance(l, LSTM) and not isinstance(l, GravesBidirectionalLSTM))
                               else None
                               for l in self.layers]
                    carries_init = True
                (self.params_list, self.states_list, self.updater_states,
                 self._rng, self._iter_dev, skipped, score, grads,
                 carries) = self._jit_train[sig](
                    self.params_list, self.states_list, self.updater_states,
                    self._rng, self._device_iteration(), xs, ys, fm, lm, ew,
                    carries, self._nan_skipped_arg())
                if guard:
                    self._nanguard_record(skipped)
            dt = time.perf_counter() - t0
            _OBS_STEP_SECONDS.record(dt)
            _OBS_STEPS.inc()
            last_score = score
            self._last_gradients = grads
            self._last_batch_size = int(xs.shape[0])
            self.iteration += 1
            self._iter_dev_py = self.iteration
            if self.listeners:
                for lst in self.listeners:
                    lst.iteration_done(self, self.iteration)
        self.score_ = last_score
        return last_score

    # ------------------------------------------------------------------
    # unsupervised layer-wise pretraining (fit:932 → pretrainLayer:178)
    # ------------------------------------------------------------------
    def pretrain(self, iterator, epochs=1):
        """Greedy layer-wise pretraining of all pretrain layers in order."""
        if self.params_list is None:
            self.init()
        for i, layer in enumerate(self.layers):
            if layer.is_pretrain_layer():
                self.pretrain_layer(i, iterator, epochs=epochs)
        return self

    def pretrain_layer(self, i, iterator, epochs=1):
        """Pretrain layer ``i`` on activations from the layers below it
        (MultiLayerNetwork.pretrainLayer). Input is fed through layers [0, i)
        in inference mode, then the layer's own unsupervised update runs."""
        self._check_solver_supported(pretrain=True)
        layer = self.layers[i]
        if not layer.is_pretrain_layer():
            return self
        conf_u = layer.updater_config(self.conf.max_iterations)

        # donate only the layer's updater state (argument 2): it is
        # replaced wholesale after every call, while params_list/
        # states_list keep the OTHER layers' live buffers and must
        # survive
        @functools.partial(jax.jit, donate_argnums=(2,))
        def pre_step(params_list, states_list, upd_i, rng, iteration, x):
            # forward through layers below (stop_gradient: frozen)
            h = x
            for j in range(i):
                pre = self.conf.input_preprocessors.get(j)
                if pre is not None:
                    h = pre.pre_process(h, None)
                h, _ = self.layers[j].forward(params_list[j], h, states_list[j],
                                              train=False, rng=None, mask=None)
            pre = self.conf.input_preprocessors.get(i)
            if pre is not None:
                h = pre.pre_process(h, None)
            h = jax.lax.stop_gradient(h)
            grads, score = layer.pretrain_grads(params_list[i], h, rng)
            upd, upd2 = updaters_mod.compute_updates(conf_u, grads, upd_i, iteration, params=params_list[i])
            new_p = {k: params_list[i][k] - upd[k] for k in params_list[i]}
            return new_p, upd2, score

        if isinstance(iterator, DataSet):
            iterator = ArrayDataSetIterator(iterator.features,
                                            iterator.labels if iterator.labels is not None
                                            else iterator.features,
                                            batch_size=iterator.num_examples())
        for _ in range(epochs):
            for ds in iterator:
                x = jnp.asarray(ds.features)
                self._rng, sub = jax.random.split(self._rng)
                new_p, new_upd, score = pre_step(
                    self.params_list, self.states_list, self.updater_states[i],
                    sub, self.iteration, x)
                self.params_list = list(self.params_list)
                self.params_list[i] = new_p
                self.updater_states = list(self.updater_states)
                self.updater_states[i] = new_upd
                # device array, synced lazily on read (fit_batch's contract):
                # a float() here would stall the host loop every pretrain batch
                self.score_ = score
                self.iteration += 1
        return self

    # ------------------------------------------------------------------
    # public training API
    # ------------------------------------------------------------------
    def fit(self, data, labels=None, *, epochs=1, checkpoint_every=None,
            checkpoint_dir=None, resume_from=None):
        """fit(DataSetIterator) / fit(DataSet) / fit(X, y) (MultiLayerNetwork.fit:917).

        ``checkpoint_every=N`` (default ``DL4J_TPU_CKPT_EVERY``) commits a
        crash-consistent TrainingCheckpoint into ``checkpoint_dir`` every
        >=N parameter updates, at dispatch-group boundaries; ``resume_from=
        dir`` restores the newest verified checkpoint (params, updater
        state, rng, counters, NaN-guard state) and fast-forwards the data
        stream to its cursor, making the resumed run bitwise equal to the
        uninterrupted one. Passing only ``resume_from`` with
        ``checkpoint_every`` is the whole crash-restart contract: a fresh
        directory starts from scratch. Iterator fits only."""
        if self.params_list is None:
            self.init()
        if self.conf.pretrain and not getattr(self, "_pretrained", False):
            # pretrain_layer handles DataSet (incl. labels=None) directly
            self.pretrain(data if labels is None else DataSet(data, labels))
            self._pretrained = True
        if labels is not None:
            data = DataSet(data, labels)
        every, ck_dir, keep = self._resolve_ckpt_args(
            checkpoint_every, checkpoint_dir, resume_from)
        if isinstance(data, DataSet):
            if every or resume_from:
                raise ValueError(
                    "checkpoint_every/resume_from need a data ITERATOR "
                    "(the checkpoint cursor is a stream position); wrap "
                    "the DataSet in an iterator to use them")
            for _ in range(self.conf.iterations):
                self.fit_batch(data.features, data.labels, data.features_mask,
                               data.labels_mask)
            self._nanguard_flush()
            return self
        if isinstance(data, DataSetIterator) or hasattr(data, "__iter__"):
            # async prefetch wrap, as the reference does unconditionally at
            # MultiLayerNetwork.java:920 — host-side batch prep (+normalizer)
            # overlaps device compute
            from deeplearning4j_tpu.datasets.async_iterator import AsyncDataSetIterator
            from deeplearning4j_tpu.datasets.dataset import StackedDataSet
            wrapped = None
            use_ew = False
            # never let a fit that wraps nothing (caller-provided async
            # iterator, raw iterable) report the PREVIOUS fit's telemetry
            self._last_fuse_stats = None
            if isinstance(data, DataSetIterator) and not isinstance(data, AsyncDataSetIterator):
                # super-batch host->HBM transfers (link-latency
                # amortization); DL4J_TPU_TRANSFER_STAGE tunes/disables.
                # DL4J_TPU_FUSE_STEPS>1 additionally runs each staged group
                # as ONE lax.scan program (fit_fused) — gated by
                # fuse_allowed (plain SGD single-update path, no
                # batch-statistics layers); with DL4J_TPU_FUSE_AUTOTUNE the
                # tuner picks per-bucket K (tuning/autotuner.py) and
                # bucket_pad row-pads ragged per-batch trailers so even an
                # unfused run holds one train signature (ew contract)
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
                    # the cursor applies only to the first resumed epoch;
                    # our own wrapper fast-forwards in the worker thread
                    # (before grouping), anything else is drained below
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
                        if isinstance(ds, StackedDataSet):
                            self.fit_fused(ds)
                            batches += ds.n_steps
                        else:
                            ew = getattr(ds, "example_weights", None)
                            if (ew is None and use_ew
                                    and ds.features_mask is None
                                    and ds.labels_mask is None):
                                # bucketized run: EVERY maskless batch
                                # dispatches through the ew program, so a
                                # row-padded ragged trailer shares the
                                # full batches' one train signature
                                ew = np.ones(int(ds.features.shape[0]),
                                             np.float32)
                            for _ in range(self.conf.iterations):
                                self.fit_batch(ds.features, ds.labels,
                                               ds.features_mask,
                                               ds.labels_mask, ew=ew)
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
                    # padding waste) — read by bench.py fused and by the
                    # ROADMAP fused-loop-grouping investigation
                    self._last_fuse_stats = wrapped.fuse_stats()
                # finalize window-based listeners (ProfilerListener): the
                # jax trace is process-global; a run shorter than the
                # capture window must not leave it stuck
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
        def run(params_list, states_list, x, fmask):
            acts, preout, _, _, _ = self._forward_layers(
                params_list, states_list, x, train=False, rngs=None, fmask=fmask)
            return acts[-1]
        return jax.jit(run)

    def output(self, x, train=False, fmask=None):
        """Inference output (MultiLayerNetwork.output:1459)."""
        x = jnp.asarray(x)
        fmask = None if fmask is None else jnp.asarray(fmask)
        sig = self._output_signature(x, fmask)
        if sig not in self._jit_output:
            self._jit_output[sig] = self._build_output_fn()
        with _OBS_OUTPUT_SECONDS.time():
            # graftlint: disable=G001 -- output()'s contract IS the eval seam: it returns host numpy once per request, after the whole program ran
            return np.asarray(self._jit_output[sig](self.params_list, self.states_list, x, fmask))

    def feed_forward(self, x, train=False):
        """All layer activations, input first (feedForwardToLayer:703)."""
        x = jnp.asarray(x)
        rngs = None
        if train:
            self._rng, sub = jax.random.split(self._rng)
            rngs = self._split_rngs(sub)
        acts, _, _, _, _ = self._forward_layers(
            self.params_list, self.states_list, x, train=train, rngs=rngs, fmask=None)
        # graftlint: disable=G001 -- feed_forward returns HOST arrays by API contract (diagnostic surface, not the step loop)
        return [np.asarray(a) for a in acts]

    def score(self, dataset: DataSet, train=False):
        """Loss on a dataset without updating params (reference score(DataSet))."""
        x = jnp.asarray(dataset.features)
        y = jnp.asarray(dataset.labels)
        fm = None if dataset.features_mask is None else jnp.asarray(dataset.features_mask)
        lm = None if dataset.labels_mask is None else jnp.asarray(dataset.labels_mask)
        score, _ = self._loss_fn(self.params_list, self.states_list, x, y, fm, lm,
                                 None, train=False)
        return float(score)

    def compute_gradient_and_score(self, x, y, fmask=None, lmask=None):
        """Per-layer gradients + score WITHOUT updating params
        (computeGradientAndScore:1745 — the gradient-check entry point)."""
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        fm = None if fmask is None else jnp.asarray(fmask)
        lm = None if lmask is None else jnp.asarray(lmask)
        (score, _), grads = jax.value_and_grad(self._loss_fn, has_aux=True)(
            self.params_list, self.states_list, x, y, fm, lm, None, False, None)
        self._last_gradients = grads
        self.score_ = float(score)
        return grads, self.score_

    def gradient(self):
        """Most recent per-layer gradients (reference Model.gradient())."""
        return self._last_gradients

    def gradient_vector(self):
        if self._last_gradients is None:
            return None
        return np.asarray(flat_params.params_to_vector(self.layers, self._last_gradients))

    # ------------------------------------------------------------------
    # rnn stateful inference
    # ------------------------------------------------------------------
    def rnn_clear_previous_state(self):
        self._rnn_carries = None

    def rnn_time_step(self, x):
        """Stateful stepping inference (reference rnnTimeStep)."""
        x = jnp.asarray(x)
        single = x.ndim == 2
        if single:
            x = x[:, None, :]
        if self._rnn_carries is None:
            self._rnn_carries = [
                l.initial_carry(x.shape[0], x.dtype)
                if (isinstance(l, LSTM) and not isinstance(l, GravesBidirectionalLSTM))
                else None
                for l in self.layers]
        acts, preout, _, _, self._rnn_carries = self._forward_layers(
            self.params_list, self.states_list, x, train=False, rngs=None,
            fmask=None, carries=self._rnn_carries)
        out = np.asarray(acts[-1])
        return out[:, 0] if single and out.ndim == 3 else out

    # ------------------------------------------------------------------
    # evaluation / misc
    # ------------------------------------------------------------------
    def evaluate(self, iterator):
        from deeplearning4j_tpu.eval.evaluation import Evaluation
        ev = Evaluation()
        for ds in iterator:
            out = self.output(ds.features)
            ev.eval(ds.labels, out, mask=ds.labels_mask)
        return ev

    def clone(self):
        net = MultiLayerNetwork(self.conf)
        net.init()
        # real copies, not aliases: the donor's next fit_batch donates (and so
        # invalidates) its param/state buffers
        net.params_list = jax.tree.map(jnp.copy, self.params_list)
        net.states_list = jax.tree.map(jnp.copy, self.states_list)
        net.updater_states = jax.tree.map(jnp.copy, self.updater_states)
        net.iteration = self.iteration
        return net

    def summary(self):
        lines = ["idx  type                        n_params   shapes"]
        for i, l in enumerate(self.layers):
            lines.append(f"{i:<4d} {type(l).__name__:<27s} {l.n_params():<10d} "
                         f"{ {k: v for k, v in l.param_shapes().items()} }")
        lines.append(f"total params: {self.num_params()}")
        return "\n".join(lines)
