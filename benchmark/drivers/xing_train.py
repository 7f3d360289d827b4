"""Driver ``xing_train``: a ``xing``-family decoder (``glm_train``'s family with
manifold-constrained hyper-connections around every sublayer, latent attention
whose q / k and v have widths of their own under YaRN's softmax scale, and this
chip's share of the heads) trained through ``TransformerLM.fit_batch`` on host
``int32`` token batches, one chip. The closed loop, the call and the spans ARE
``lm_train.Job``'s, the window with the expert layers' counters around it
``laguna_train.Job``'s, the first steps with the routers' bias readings
``glm_train.Job``'s; the hyper-connections' counters are read beside the
expert layers', before and after the window.

Traffic parameters (``benchmark/traffic/<mix>.json``): ``rows`` sequences of
``seq_len`` tokens per step (the batch is ``rows`` x ``seq_len + 1``; the
module's target at position i is the main target of i + 1), ``pool`` distinct
batches drawn from the seed and cycled, ids uniform over the ``vocab_size``
rows held.

The weights are the benchmark's own (``references/xing.init_weights``, one
jitted call from the seed), re-laid into the program's tree; the program's
``init()`` is never called, and its routers' biases start at 0 as the
reference's do.
"""

from __future__ import annotations

import math
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import check
from benchmark.drivers import glm_train, laguna_train
from benchmark.references import xing as ref
from benchmark.work import xing as work
# a program without hyper-connections or latent attention whose v has a width
# of its own cannot run this family: fail here, at the import, before a
# weight is made
from deeplearning4j_tpu.models.transformer import (Experts, HyperConnections,
                                                   LayerSpec, Latent, Rope,
                                                   TransformerConfig,
                                                   TransformerLM)

CHECKED_STEPS = laguna_train.CHECKED_STEPS

LEAVES = glm_train.LEAVES
MTP, TOP = glm_train.MTP, glm_train.TOP
# the program's fused maps of a sublayer -> the reference's sublayer
HC = {"hc1": "attn_hc", "hc2": "mlp_hc"}


def program_config(config, seq_len, seed, **experts):
    """The configuration file as a ``TransformerConfig``. ``experts``
    overrides fields of ``Experts``."""
    a = config["assumed"]
    o = a["optimizer"]
    rs = config["rope_scaling"]
    if config["num_nextn_predict_layers"] != 1:
        raise ValueError("the program builds one multi-token-prediction "
                         "module")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise NotImplementedError(
            f"n_group {config['n_group']}: group-limited routing is not "
            "implemented")
    if rs.get("type") != "yarn" \
            or config["mhc_h_res_clamp_min"] != -config["mhc_h_res_clamp_max"]:
        raise NotImplementedError("the program reads YaRN rope scaling and a "
                                  "clamp of H_res symmetric about 0")
    factor = float(rs["factor"])
    rope = Rope(base=float(config["rope_theta"]), yarn_factor=factor,
                yarn_original_len=rs["original_max_position_embeddings"],
                yarn_beta_fast=float(rs["beta_fast"]),
                yarn_beta_slow=float(rs["beta_slow"]),
                attention_factor=ref.mscale(factor, rs["mscale"])
                / ref.mscale(factor, rs["mscale_all_dim"]))
    return TransformerConfig(
        vocab_size=config["vocab_size"], max_len=seq_len,
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        head_dim=config["v_head_dim"], n_layers=len(config["layers_held"]),
        d_ff=config["intermediate_size"], pos_embed="rope",
        rope_base=float(config["rope_theta"]), rope_layout="interleaved",
        norm="rmsnorm", norm_eps=config["rms_norm_eps"],
        bias=config["attention_bias"], ffn="swiglu",
        tie_embeddings=config["tie_word_embeddings"],
        latent=Latent(q_rank=config["q_lora_rank"],
                      kv_rank=config["kv_lora_rank"],
                      d_nope=config["qk_nope_head_dim"],
                      d_rope=config["qk_rope_head_dim"],
                      d_v=config["v_head_dim"],
                      scale=ref.softmax_scale(config) * math.sqrt(
                          config["qk_nope_head_dim"]
                          + config["qk_rope_head_dim"])),
        layers=tuple(LayerSpec(ffn="experts" if r else "dense", rope=rope)
                     for r in ref.routed(config)),
        experts=Experts(**{**dict(
            n_experts=config["n_routed_experts"],
            top_k=config["num_experts_per_tok"],
            d_expert=config["moe_intermediate_size"], held=ref.held(config),
            scale=config["routed_scaling_factor"],
            d_shared=config["moe_intermediate_size"]
            * config["n_shared_experts"],
            row_buffer=a["expert_row_buffer"],
            bias_rate=a["bias_update_speed"]),
            **experts}),
        mtp=LayerSpec(ffn="experts", rope=rope),
        mtp_weight=a["mtp_loss_weight"],
        hyper=HyperConnections(streams=config["hc_mult"],
                               sinkhorn_iters=config["hc_sinkhorn_iters"],
                               eps=config["hc_eps"],
                               clamp=float(config["mhc_h_res_clamp_max"])),
        compute_dtype=a["compute_dtype"], block_size=a["block_size"],
        remat=a["remat"], learning_rate=o["learning_rate"], beta1=o["beta1"],
        beta2=o["beta2"], eps=o["eps"], weight_decay=o["weight_decay"],
        seed=seed % (2 ** 31 - 1))


def _layer(lp):
    """A reference layer as the program's block: the three phi of a sublayer
    side by side, its three b end to end."""
    bp = glm_train._layer(lp)
    for mine, sub in HC.items():
        bp[mine] = jnp.concatenate([lp[f"{sub}_phi_{k}"]
                                    for k in ("pre", "post", "res")], axis=1)
        bp[mine + "_alpha"] = lp[f"{sub}_alpha"]
        bp[mine + "_b"] = jnp.concatenate([lp[f"{sub}_b_{k}"]
                                           for k in ("pre", "post", "res")])
    return bp


def _to_program(tree):
    """The reference's tree as ``TransformerLM``'s ``params``."""
    out = {mine: tree[theirs] for mine, theirs in TOP.items()}
    for i, lp in enumerate(tree["layers"]):
        out[f"b{i}"] = _layer(lp)
    mp = tree["mtp"]
    out["mtp"] = {mine: mp[theirs] for mine, theirs in MTP.items()}
    out["mtp"]["block"] = _layer(mp["layer"])
    return out


def _block_leaves(prefix, bp):
    for mine, a in bp.items():
        own = mine[:-2] if mine.endswith("_b") else mine
        if own in HC:     # [n(n + 2)] or [n C, n(n + 2)]: n, n and n² wide
            n = math.isqrt(a.shape[-1] + 1) - 1
            for k, part in zip(("pre", "post", "res"),
                               jnp.split(a, [n, 2 * n], axis=-1)):
                yield f"{prefix}{HC[own]}_{'b' if own != mine else 'phi'}_{k}", \
                    part
        elif mine.endswith("_alpha"):
            yield f"{prefix}{HC[mine[:-6]]}_alpha", a
        else:
            yield f"{prefix}{LEAVES[mine]}", a


def leaves(tree):
    """``(name, array)`` of every leaf of a program tree under the reference's
    leaf names (``b1.q_a_proj``, ``b1.attn_hc_phi_res``, ``mtp.eh_proj``,
    ``mtp.layer.router``), the fused maps read as their three parts."""
    for k, bp in tree.items():
        if k in TOP:
            yield TOP[k], bp
        elif k == "mtp":
            for mine, a in bp.items():
                if mine == "block":
                    yield from _block_leaves("mtp.layer.", a)
                else:
                    yield f"mtp.{MTP[mine]}", a
        else:
            yield from _block_leaves(f"{k}.", bp)


def _norms(tree, scale=1.0):
    """Per-leaf L2 norms of a program tree under the reference's names."""
    return {name: scale * jnp.sqrt(jnp.sum(jnp.square(a)))
            for name, a in leaves(tree)}


verify = check.verify_training


class Job(laguna_train.Job):
    def __init__(self, config, traffic, seed, spans):
        self.config, self.traffic, self.seed, self.spans = (
            config, traffic, seed, spans)
        self.rows, self.seq = traffic["rows"], traffic["seq_len"]
        if self.seq > config["max_position_embeddings"]:
            raise ValueError("seq_len exceeds max_position_embeddings")
        self.lm = TransformerLM(program_config(config, self.seq, seed))
        self.lm.params = jax.jit(_to_program)(ref.init_weights(config, seed))
        self.lm._init_opt_state()
        rng = np.random.default_rng([seed, 2])
        self.pool = [rng.integers(0, config["vocab_size"],
                                  (self.rows, self.seq + 1), dtype=np.int32)
                     for _ in range(traffic["pool"])]
        self.window_counters = {}

    def first_steps(self):
        """Steps 1..3 through the window's own call; what the comparison reads
        of them (``glm_train.Job.first_steps``' readings), fetched once the
        three are dispatched."""
        b1 = self.config["assumed"]["optimizer"]["beta1"]
        grad_of_m = jax.jit(lambda m: _norms(m, 1.0 / (1.0 - b1)))
        read_bias = jax.jit(lambda bias, load, before: (
            ref.bias_reading(self.config, jax.tree.map(
                lambda a, b: a - b, bias, before), load),
            jax.tree.map(lambda a: a + 0, bias)))
        before = jax.tree.map(jnp.zeros_like, self.lm.opt_state["expert_bias"])
        losses, grad_norm, readings = [], None, []
        for i in range(CHECKED_STEPS):
            losses.append(self._call(i))
            opt = self.lm.opt_state
            if i == 0:   # Adam's first moment after one step is (1 - b1) g
                grad_norm = grad_of_m(opt["m"])
            read, before = read_bias(opt["expert_bias"], opt["expert_load"],
                                     before)
            readings.append(read)
        # one program: the seed's weights are an argument
        change = jax.jit(lambda p, start: _norms(jax.tree.map(
            lambda a, b: a - b, p, _to_program(start))))
        delta = change(self.lm.params, ref.init_weights(self.config,
                                                        self.seed))
        self.steps_done = CHECKED_STEPS
        get = lambda d: {k: float(v) for k, v in jax.device_get(d).items()}
        readings = [get(r) for r in readings]
        return {"loss": [float(x) for x in losses],
                "grad_norm": {**get(grad_norm), **readings[0]},
                "delta_norm": {**get(delta), **ref.summed(readings)}}

    def window(self, seconds):
        """``laguna_train``'s window; the hyper-connections' counters are
        read before its clock starts and after it stopped, beside the expert
        layers', and printed for the reader of a log."""
        before = self.lm.hc_counters()
        out = super().window(seconds)
        after = self.lm.hc_counters()
        hc = {k: after[k] - before[k] for k in after}
        self.window_counters.update(hc)
        if hc.get("hc.steps"):
            print(f"xing_train: H_res's worst row or column sum error after "
                  f"Sinkhorn, mean over the window's steps: "
                  f"{hc['hc.sinkhorn_err'] / hc['hc.steps'] * 1e-6:.3g}",
                  file=sys.stderr, flush=True)
        return out

    def work(self):
        c, rows, seq = self.config, self.rows, self.seq
        return {"step_flops": work.train_step_flops(c, rows, seq),
                "attn_latent": work.attention_work(c, rows, seq),
                "experts": work.experts_work(c, rows, seq),
                "hc": work.hc_work(c, rows, seq),
                "tokens_per_step": rows * seq}
