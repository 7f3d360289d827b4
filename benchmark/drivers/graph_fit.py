"""Driver ``graph_fit``: a ``ComputationGraph`` trained through ONE
``fit(iterator)`` call per window, one chip. The iterator cycles a pool of
distinct host float32 batches drawn from the seed and stops once the clock has
passed ``--seconds``, so ``AsyncDataSetIterator``'s staging and the
host-to-device transfer run inside the window.

Traffic parameters: ``batch`` rows per step, ``pool`` distinct batches.

The weights are the benchmark's own (``references/resnet_v1.init_weights``, one
jitted call from the seed) laid into ``params_map``; the program's ``init()``
is never called. The first three steps go through ``fit(iterator)`` too, and a
listener -- the program's own hook -- reads what the comparison needs of them;
it is taken off before the window.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check
from benchmark.references import resnet_v1 as ref
from benchmark.work import resnet_v1 as work

CHECKED_STEPS = 3

verify = check.verify_training


class _Readings:
    """IterationListener: each step's loss, the first gradient's leaf norms
    from the updater state after step 1 (Nesterov's ``v`` after one step IS the
    gradient), the parameters' change after step 3."""

    def __init__(self, job):
        self.job = job
        self.losses, self.grad, self.delta = [], None, None
        self._leaf_norms = jax.jit(ref.leaf_norms)
        self._change = jax.jit(lambda p, start: ref.leaf_norms(jax.tree.map(
            lambda a, b: a - b, p, start)))

    def iteration_done(self, model, iteration):
        if iteration > CHECKED_STEPS:
            return
        self.losses.append(model.score_)
        own = lambda m: {n: m[n] for n in self.job.weighted}
        if iteration == 1:
            first = {n: model.updater_states[n]["v"]
                     for n in self.job.weighted}
            self.grad = self._leaf_norms(first)
        if iteration == CHECKED_STEPS:
            self.delta = self._change(
                own(model.params_map),
                ref.init_weights(self.job.config, self.job.seed))


class Job:
    def __init__(self, config, traffic, seed, spans):
        from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                         DataSetIterator)
        from deeplearning4j_tpu.models.computation_graph import \
            ComputationGraph
        from deeplearning4j_tpu.models.zoo import resnet50
        self.config, self.traffic, self.seed, self.spans = (
            config, traffic, seed, spans)
        if config["stem_width"] != 64 or config["bottleneck_expansion"] != 4:
            raise ValueError("zoo.resnet50 builds stem 64, expansion 4 only")
        upd = config["assumed"]["updater"]
        conf = resnet50(n_classes=config["n_classes"],
                        height=config["height"], width=config["width"],
                        channels=config["channels"],
                        seed=seed % (2 ** 31 - 1),
                        learning_rate=upd["learning_rate"],
                        stages=tuple(config["stages"]))
        conf.compute_dtype = config["assumed"]["compute_dtype"]
        net = self.net = ComputationGraph(conf)

        # what init() would build, with the benchmark's weights in it
        weights = ref.init_weights(config, seed)
        self.weighted = sorted(weights)
        expect = sorted(n for n in net.layer_names
                        if net.conf.vertices[n].layer.param_shapes())
        if expect != self.weighted:
            raise ValueError("the reference's layers are not the graph's: "
                             f"{set(expect) ^ set(self.weighted)}")
        net._rng = jax.random.PRNGKey(seed % (2 ** 31 - 1))
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        vel = zeros(weights)
        net.params_map, net.states_map, net.updater_states = {}, {}, {}
        for name in net.layer_names:
            layer = net.conf.vertices[name].layer
            net.params_map[name] = weights.get(name, {})
            net.states_map[name] = layer.init_state()
            net.updater_states[name] = {"v": vel.get(name, {})}

        self.batch = traffic["batch"]
        rng = np.random.default_rng([seed, 2])
        shape = (self.batch, config["height"], config["width"],
                 config["channels"])
        eye = np.eye(config["n_classes"], dtype=np.float32)
        self.pool = [
            (rng.standard_normal(shape, dtype=np.float32),
             eye[rng.integers(0, config["n_classes"], self.batch)])
            for _ in range(traffic["pool"])]

        job = self

        class Feed(DataSetIterator):
            """Cycles the pool: ``n`` batches, or until ``deadline``."""

            def __init__(self, start, n=None, deadline=None):
                self.i, self.n, self.deadline = start, n, deadline

            def reset(self):
                pass

            def batch_size(self):
                return job.batch

            def __next__(self):
                if self.n is not None and self.n <= 0:
                    raise StopIteration
                if self.deadline is not None and \
                        time.perf_counter() >= self.deadline:
                    raise StopIteration
                with job.spans.span("iterator_next"):
                    x, y = job.pool[self.i % len(job.pool)]
                    self.i += 1
                    if self.n is not None:
                        self.n -= 1
                    return DataSet(x, y)

        self._feed = Feed

    def check_batches(self):
        return self.pool[:CHECKED_STEPS]

    def _fit(self, feed):
        """THE call the window makes."""
        with self.spans.span("fit"):
            self.net.fit(feed)

    def first_steps(self):
        readings = _Readings(self)
        self.net.set_listeners([readings])
        self._fit(self._feed(0, n=CHECKED_STEPS))
        self.net.set_listeners([])
        self.steps_done = CHECKED_STEPS
        get = lambda d: {k: float(v) for k, v in jax.device_get(d).items()}
        return {"loss": [float(x) for x in readings.losses],
                "grad_norm": get(readings.grad),
                "delta_norm": get(readings.delta)}

    def warm(self):
        """A second, longer fit: a full staged group and its on-device slices
        (the first steps' three batches may be fewer than one group), then a
        trailing single batch, as the window's own end will have."""
        n = self.traffic.get("warm_batches", 9)
        self._fit(self._feed(self.steps_done, n=n))
        self.steps_done += n
        jax.block_until_ready(self.net.params_map)

    def window(self, seconds):
        from deeplearning4j_tpu import obs
        before = self._counters(obs)
        it0 = self.net.iteration
        t0 = time.perf_counter()
        self._fit(self._feed(self.steps_done, deadline=t0 + seconds))
        with self.spans.span("drain"):
            jax.block_until_ready((self.net.score_, self.net.params_map))
        elapsed = time.perf_counter() - t0
        steps = self.net.iteration - it0
        after = self._counters(obs)
        self._window_counters = {k: after[k] - before[k] for k in after}
        finite = bool(np.isfinite(float(self.net.score_)))
        return {"steps": steps, "seconds": elapsed, "attempted": steps,
                "failed": 0 if finite else steps,
                "end_to_end": {"train_step_ms": 1e3 * elapsed / steps}}

    @staticmethod
    def _counters(obs):
        snap = obs.metrics_snapshot()["histograms"]
        pick = lambda name, key: float((snap.get(name) or {}).get(key, 0.0))
        return {"input_wait_s": pick("prefetch.consumer_wait_seconds", "sum"),
                "input_waits": pick("prefetch.consumer_wait_seconds", "count"),
                "step_dispatch_s": pick("train.step_seconds", "sum")}

    def counters(self):
        return self._window_counters

    def dispatch_seconds(self):
        """``train.step_seconds``: host seconds inside ``_fit_one``."""
        return self._window_counters["step_dispatch_s"]

    def work(self):
        return {"step_flops": work.train_step_flops(self.config, self.batch),
                "conv": {"flops": work.conv_train_flops(self.config,
                                                        self.batch)},
                "images_per_step": self.batch}

    def free(self):
        self.net.params_map = self.net.states_map = None
        self.net.updater_states = None
        self.net._jit_train = {}
        self.net._last_gradients = None
        self.net = None
