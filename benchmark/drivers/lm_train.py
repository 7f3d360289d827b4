"""Driver ``lm_train``: a decoder LM trained through ``TransformerLM.fit_batch``
on host ``int32`` token batches, one chip.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``rows`` sequences of
``seq_len`` tokens per step (the batch is ``rows`` x ``seq_len + 1``: inputs and
shifted targets), ``pool`` distinct batches drawn from the seed and cycled.

The weights are the benchmark's own (``references/transformer.init_weights``,
one jitted call from the seed), re-laid into the program's tree; the program's
``init()`` is never called. The object that takes the first steps is the object
the window drives.
"""

from __future__ import annotations

import collections
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check
from benchmark.references import transformer as ref
from benchmark.work import transformer as work

CHECKED_STEPS = 3
RUN_AHEAD = 4   # steps the host may be ahead of the device inside the window


def _to_program(stacked, n_layer):
    """The reference's stacked tree as ``TransformerLM``'s ``params``."""
    out = {k: v for k, v in stacked.items() if k != "blocks"}
    for i in range(n_layer):
        out[f"b{i}"] = {k: v[i] for k, v in stacked["blocks"].items()}
    return out


def _norms(tree, scale=1.0):
    """Per-leaf L2 norms of a program tree under the reference's leaf names
    (the fused ``qkv`` leaves read as their q, k and v thirds)."""
    norm = lambda a: scale * jnp.sqrt(jnp.sum(jnp.square(a)))
    flat = {}
    for k, v in tree.items():
        if not isinstance(v, dict):
            flat[k] = norm(v)
            continue
        for kk, a in v.items():
            if kk in ref.FUSED_QKV:
                for part, third in zip("qkv", jnp.split(a, 3, axis=-1)):
                    flat[f"{k}.{kk}.{part}"] = norm(third)
            else:
                flat[f"{k}.{kk}"] = norm(a)
    return flat


verify = check.verify_training


class Job:
    def __init__(self, config, traffic, seed, spans):
        from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                           TransformerLM)
        self.config, self.traffic, self.seed, self.spans = (
            config, traffic, seed, spans)
        a = config["assumed"]
        o = a["optimizer"]
        self.rows, self.seq = traffic["rows"], traffic["seq_len"]
        if self.seq > config["n_positions"]:
            raise ValueError("seq_len exceeds the configuration's n_positions")
        self.lm = TransformerLM(TransformerConfig(
            vocab_size=config["vocab_size"], max_len=config["n_positions"],
            d_model=config["n_embd"], n_heads=config["n_head"],
            n_layers=config["n_layer"], d_ff=work.d_ff(config),
            compute_dtype=a["compute_dtype"], block_size=a["block_size"],
            remat=a["remat"], learning_rate=o["learning_rate"],
            beta1=o["beta1"], beta2=o["beta2"], eps=o["eps"],
            weight_decay=o["weight_decay"], seed=seed % (2 ** 31 - 1)))
        L = config["n_layer"]
        self.lm.params = jax.jit(lambda s: _to_program(s, L))(
            ref.init_weights(config, seed))
        zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
        self.lm.opt_state = {"m": zeros(self.lm.params),
                             "v": zeros(self.lm.params)}
        rng = np.random.default_rng([seed, 2])
        self.pool = [rng.integers(0, config["vocab_size"],
                                  (self.rows, self.seq + 1), dtype=np.int32)
                     for _ in range(traffic["pool"])]

    def check_batches(self):
        return self.pool[:CHECKED_STEPS]

    def _call(self, i):
        """THE call the window makes, for step ``i`` of this process."""
        with self.spans.span("fit_batch"):
            return self.lm.fit_batch(self.pool[i % len(self.pool)])

    def first_steps(self):
        """Steps 1..3 through the window's own call; what the comparison reads
        of them, fetched once the three are dispatched."""
        b1 = self.config["assumed"]["optimizer"]["beta1"]
        grad_of_m = jax.jit(lambda m: _norms(m, 1.0 / (1.0 - b1)))
        losses, grad_norm = [], None
        for i in range(CHECKED_STEPS):
            losses.append(self._call(i))
            if i == 0:   # Adam's first moment after one step is (1 - b1) g
                grad_norm = grad_of_m(self.lm.opt_state["m"])
        # one program: the seed's weights are remade leaf by leaf beside the
        # subtraction, not held whole a second time
        L = self.config["n_layer"]
        change = jax.jit(lambda p, start: _norms(jax.tree.map(
            lambda a, b: a - b, p, _to_program(start, L))))
        delta = change(self.lm.params,
                       ref.init_weights(self.config, self.seed))
        self.steps_done = CHECKED_STEPS
        get = lambda d: {k: float(v) for k, v in jax.device_get(d).items()}
        return {"loss": [float(x) for x in losses],
                "grad_norm": get(grad_norm), "delta_norm": get(delta)}

    def warm(self):
        """Nothing beyond the first steps: one shape, one program."""

    def window(self, seconds):
        ahead = collections.deque()
        t0 = time.perf_counter()
        steps, loss = 0, None
        while time.perf_counter() - t0 < seconds:
            loss = self._call(self.steps_done + steps)
            steps += 1
            ahead.append(loss)
            if len(ahead) > RUN_AHEAD:
                with self.spans.span("wait_device"):
                    ahead.popleft().block_until_ready()
        with self.spans.span("drain"):
            jax.block_until_ready((loss, self.lm.params))
        seconds = time.perf_counter() - t0
        finite = bool(np.isfinite(float(loss)))
        return {"steps": steps, "seconds": seconds, "attempted": steps,
                "failed": 0 if finite else steps,
                "end_to_end": {"train_step_ms": 1e3 * seconds / steps}}

    def work(self):
        fa, ba, fb, bb = work.attention_work(self.config, self.rows, self.seq)
        return {"step_flops": work.train_step_flops(self.config, self.rows,
                                                    self.seq),
                "flash_fwd": {"flops": fa, "bytes": ba},
                "flash_bwd": {"flops": fb, "bytes": bb},
                "tokens_per_step": self.rows * self.seq}

    def counters(self):
        """No counter of the program is read in this kind of job."""
        return {}

    def dispatch_seconds(self):
        """Host seconds inside the window's ``fit_batch`` calls."""
        return self.spans.total("fit_batch", since=self.spans.window_start)

    def free(self):
        self.lm.params = self.lm.opt_state = None
        self.lm._step = None
        self.lm = None
