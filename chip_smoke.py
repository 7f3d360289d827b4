#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main paths once, through the entry points a user calls, at the
full published width of the models (depth and step counts are what is cut),
on ONE TPU chip, in ONE process:

  train_lenet     zoo.lenet_mnist, MultiLayerNetwork.fit(iterator): the rolled
                  K-step fit_fused scan behind AsyncDataSetIterator grouping
  train_resnet50  zoo.resnet50 (224x224x3, 1000 classes, bf16, batch 128),
                  ComputationGraph.fit(iterator) through AsyncDataSetIterator
                  staging (BatchNormalization keeps it off the fused scan:
                  models/_device_state.fuse_allowed)
  train_lm        TransformerLM at GPT-2-small widths, block_size=512: the
                  Pallas flash-attention kernels inside the donated step
  train_mixed_lm  TransformerLM with a per-layer list in the Laguna-XS.2 cut's
                  pattern (full + dense, then window, window, window, full
                  with experts; heads of 128, 48 / 64 query heads over 8
                  key/value heads, window 512, 8 of 16 experts held, remat)
                  at a small width: three steps, and the loss against dense
                  attention and a looped expert sum on the same weights
  train_looped_lm TransformerLM with a looped stack in Ouro's pattern (two
                  layers run four times over the same weights, sandwich
                  RMSNorms, 16 heads of 128, an exit gate and a loss over the
                  four exits, remat) at a small width: three steps, one flash
                  kernel of each kind an APPLICATION, the loss against dense
                  attention without remat, and greedy ``generate`` through
                  the 4 x 2 KV caches against ``output``'s last exit
  serve_lm        the GPT-2-small model behind ContinuousLM + ServingIngress, a few
                  /v1/generate requests over HTTP admitted mid-decode

Output: one JSON line per phase (checks, compile seconds and run seconds kept
apart, persistent-cache hits, peak device bytes), then as the LAST line
``{"ok": ..., "device": {"platform", "kind", "count"}}`` with the device as
JAX reports it. Exit code 0 only when every check of every phase passed.

Without a TPU the script exits non-zero before running anything. ``--rehearse``
is the explicit CPU lane (tiny sizes, Pallas in interpret mode): its last line
names the CPU, so it can never be read as a chip result.

``--chips 4`` runs ONLY the data-parallel phase (``TransformerLM.shard`` at
ZeRO levels 0 and 3 on a 4-device mesh against the same steps on one device).

Weights and data come from ``--seed``; no network, no git, no child process.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
import traceback
import urllib.request

PHASES = ("train_lenet", "train_resnet50", "train_lm", "train_mixed_lm",
          "train_looped_lm", "serve_lm")

# TransformerLM widths: GPT-2 small (Radford et al. 2019: 50257 BPE tokens,
# 1024 positions, d_model 768, 12 heads, 12 layers, d_ff 3072).
_GPT2_SMALL = dict(vocab_size=50257, max_len=1024, d_model=768, n_heads=12,
                   n_layers=12, d_ff=3072, block_size=512)
_TINY_LM = dict(vocab_size=512, max_len=64, d_model=32, n_heads=4,
                n_layers=2, d_ff=64, block_size=32)

# the mixed model: the kernels' shapes are the published ones (head 128, groups
# of 6 and 8 query heads, window and block 512, rows of 4 blocks), the widths
# around them small; the rehearsal's are what the interpreter does in seconds
_MIXED_LM = dict(vocab_size=4096, seq=2048, d_model=256, d_ff=512, head_dim=128,
                 n_kv_heads=8, heads_full=48, heads_window=64, window=512,
                 block_size=512, n_experts=16, held=8, top_k=2, d_expert=128)
_TINY_MIXED_LM = dict(vocab_size=96, seq=32, d_model=64, d_ff=128, head_dim=16,
                      n_kv_heads=2, heads_full=4, heads_window=6, window=8,
                      block_size=8, n_experts=16, held=8, top_k=2, d_expert=32)

# the looped model: Ouro's kernel shapes (16 ungrouped heads of 128, blocks of
# 512, rows of 4 blocks) and its four runs, the widths around them small
_LOOPED_LM = dict(vocab_size=4096, max_len=2048, d_model=256, n_heads=16,
                  head_dim=128, n_layers=2, d_ff=512, block_size=512, loops=4)
_TINY_LOOPED_LM = dict(vocab_size=96, max_len=32, d_model=64, n_heads=4,
                       head_dim=16, n_layers=2, d_ff=128, block_size=8,
                       loops=3)

# relative bar for "the same numbers up to bf16": 8 mantissa bits leave
# ~0.4% per rounding; the repo's cross-backend parity gate uses the same 2e-2
_BF16_REL = 2e-2


_LM_STEPS = 4      # fit_batch steps of train_lm (the first one compiles)
_DP_STEPS = 3      # steps of each --chips 4 run
_CMP_ROWS = 2      # batch rows whose logits are compared with dense attention


def _sizes(rehearse):
    """Real sizes, or the tiny CPU rehearsal of the same control flow."""
    if rehearse:
        return dict(
            lenet=dict(batch=16),
            resnet=dict(conf=dict(n_classes=10, height=32, width=32,
                                  stages=(1, 1, 1, 1)),
                        batch=4, batches_per_fit=4),
            lm=dict(conf=_TINY_LM, batch=2),
            mixed=dict(conf=_TINY_MIXED_LM, batch=2),
            looped=dict(conf=_TINY_LOOPED_LM, batch=2),
            # (prompt length, n_new): the first streams and stays decoding
            # while the others are admitted
            serve=dict(conf=_TINY_LM,
                       requests=((5, 40), (9, 8), (20, 12), (3, 5))),
            dp=dict(conf=_TINY_LM, batch=8))
    return dict(
        lenet=dict(batch=128),
        resnet=dict(conf=dict(n_classes=1000, height=224, width=224),
                    batch=128, batches_per_fit=9),
        lm=dict(conf=_GPT2_SMALL, batch=8),
        mixed=dict(conf=_MIXED_LM, batch=2),
        looped=dict(conf=_LOOPED_LM, batch=2),
        serve=dict(conf=_GPT2_SMALL,
                   requests=((5, 160), (37, 16), (130, 40), (300, 8))),
        dp=dict(conf=_GPT2_SMALL, batch=8))


def _fit_twice(net, make_iterator):
    """``net.fit`` over a fresh iterator twice: the first fit compiles, the
    second is the steady window. Returns every step's loss (the models
    replay fused groups step by step, so one entry per real update), the
    second fit's wall seconds and its compile count."""
    from deeplearning4j_tpu.optimize.listeners import \
        CollectScoresIterationListener
    from tools.compile_counter import CompileCounter
    rec = CollectScoresIterationListener()
    net.set_listeners(rec)
    net.fit(make_iterator())
    with CompileCounter() as steady:
        t0 = time.perf_counter()
        net.fit(make_iterator())
        float(net.score_)
        run_s = time.perf_counter() - t0
    return [score for _, score in rec.scores], run_s, steady.count


def _lm_steps(lm, toks, steps):
    """``steps`` fit_batch calls on one batch: (losses, seconds of all but
    the first, compiling, call)."""
    losses = [float(lm.fit_batch(toks))]
    t0 = time.perf_counter()
    losses += [float(lm.fit_batch(toks)) for _ in range(steps - 1)]
    return losses, time.perf_counter() - t0


def _falls(losses):
    """Finite everywhere and lower at the end than at the start."""
    import numpy as np
    return bool(np.all(np.isfinite(losses)) and losses[-1] < losses[0])


def _lm(conf, seed):
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    return TransformerLM(TransformerConfig(
        compute_dtype="bfloat16", seed=seed, **conf)).init()


# ---------------------------------------------------------------------------
# phases: each returns {"checks": {name: bool}, "run_s": float, ...facts}
# ---------------------------------------------------------------------------

def phase_train_lenet(sz, seed, rehearse):
    import numpy as np
    from deeplearning4j_tpu.datasets.async_iterator import default_fuse
    from deeplearning4j_tpu.datasets.dataset import ArrayDataSetIterator
    from deeplearning4j_tpu.models._device_state import fuse_unroll
    from deeplearning4j_tpu.models.multi_layer_network import \
        MultiLayerNetwork
    from deeplearning4j_tpu.models.zoo import lenet_mnist

    b, k = sz["batch"], default_fuse()
    rng = np.random.default_rng(seed)
    x = rng.random((b, 28, 28, 1), dtype=np.float32)
    y = np.eye(10, dtype=np.float32)[rng.integers(0, 10, b)]
    # the same batch 2K times: two full fused groups per fit
    xs, ys = np.tile(x, (2 * k, 1, 1, 1)), np.tile(y, (2 * k, 1))
    net = MultiLayerNetwork(lenet_mnist()).init()
    losses, run_s, steady_compiles = _fit_twice(
        net, lambda: ArrayDataSetIterator(xs, ys, batch_size=b))
    stats = net._last_fuse_stats or {}
    return {
        "checks": {
            "loss_falls": _falls(losses),
            "fused_groups_ran": stats.get("fused_groups") == 2,
            # docs/SIGNATURES.md + docs/FUSED_LOOP.md: a homogeneous
            # stream holds ONE fused train signature
            "one_train_signature": len(net._jit_train) == 1,
            "no_compile_after_first_fit": steady_compiles == 0,
            "scan_rolled": rehearse or fuse_unroll(k) == 1,
        },
        "run_s": run_s, "steps": len(losses), "fuse_steps": k,
        "scan_unroll": fuse_unroll(k),
        "loss_first_last": [losses[0], losses[-1]],
    }


def phase_train_resnet50(sz, seed, rehearse):
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import (DataSet,
                                                     ListDataSetIterator)
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    from deeplearning4j_tpu.models.zoo import resnet50

    conf = resnet50(**sz["conf"])
    conf.compute_dtype = "bfloat16"
    net = ComputationGraph(conf).init()
    h, w, n_cls = (sz["conf"][k] for k in ("height", "width", "n_classes"))
    b, n = sz["batch"], sz["batches_per_fit"]
    rng = np.random.default_rng(seed)
    batch = DataSet(
        rng.standard_normal((b, h, w, 3), dtype=np.float32),
        np.eye(n_cls, dtype=np.float32)[rng.integers(0, n_cls, b)])
    # host numpy batches: fit() wraps them in AsyncDataSetIterator, which
    # stages equal-shape batches to the device in super-batch groups
    losses, run_s, steady_compiles = _fit_twice(
        net, lambda: ListDataSetIterator([batch] * n))
    return {
        "checks": {
            "loss_falls": _falls(losses),
            "one_train_signature": len(net._jit_train) == 1,
            "no_compile_after_first_fit": steady_compiles == 0,
        },
        "run_s": run_s, "steps": len(losses), "batch": b,
        "images_hw": [h, w], "classes": n_cls,
        "loss_first_last": [losses[0], losses[-1]],
    }


def _flash_kernels_in_step(text, n_layers, facts, others=0):
    """Whether the lowered step ``text`` holds the forward, dQ and dK/dV
    flash kernels once a layer each and, beside ``others``, no other Mosaic
    call; the counts go into ``facts``."""
    names = ("_flash_kernel", "_flash_dq_kernel", "_flash_dkv_kernel")
    found = {n: text.count(f'kernel_name = "{n}"') for n in names}
    facts["tpu_custom_calls"] = text.count("tpu_custom_call")
    facts["kernels_in_step"] = found
    return (all(v == n_layers for v in found.values())
            and facts["tpu_custom_calls"] == 3 * n_layers + others)


def phase_train_lm(sz, seed, rehearse):
    import dataclasses

    import numpy as np
    from deeplearning4j_tpu.models.transformer import TransformerLM

    lm = _lm(sz["conf"], seed)
    c = lm.conf
    rng = np.random.default_rng(seed)
    # max_len + 1 tokens: inputs and shifted targets are both max_len wide
    toks = rng.integers(0, c.vocab_size, (sz["batch"], c.max_len + 1))
    losses, run_s = _lm_steps(lm, toks, _LM_STEPS)

    checks = {"loss_falls": _falls(losses)}
    facts = {}
    if not rehearse:   # interpret mode lowers to plain HLO, not Mosaic
        text = lm._step.lower(
            lm.params, lm.opt_state, lm.iteration, lm._rng,
            toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32),
            None).as_text()
        checks["flash_kernels_in_step"] = _flash_kernels_in_step(
            text, c.n_layers, facts)

    # the kernel route against dense attention on the same weights
    rows = toks[:_CMP_ROWS, :c.max_len]
    dense = TransformerLM(dataclasses.replace(c, block_size=None))
    dense.params = lm.params
    got, want = lm.output(rows), dense.output(rows)
    rel = float(np.abs(got - want).max() / max(1.0, np.abs(want).max()))
    checks["logits_finite"] = bool(np.isfinite(got).all())
    checks["logits_match_dense"] = rel <= _BF16_REL
    return {"checks": checks, "run_s": run_s, "steps": len(losses),
            "tokens_per_step": int(toks[:, 1:].size), "losses": losses,
            "logits_rel_err_vs_dense": rel, **facts}


def _mixed_config(m, seed):
    """The per-layer list of the Laguna-XS.2 cut at the sizes ``m``."""
    from deeplearning4j_tpu.models.transformer import (Experts, LayerSpec,
                                                       Rope,
                                                       TransformerConfig)
    full = Rope(base=500000.0, share=0.5, yarn_factor=64.0,
                yarn_original_len=m["seq"] // 2, yarn_beta_fast=64.0)
    F = LayerSpec(None, m["heads_full"], full, "experts")
    W = LayerSpec(m["window"], m["heads_window"], Rope(), "experts")
    return TransformerConfig(
        vocab_size=m["vocab_size"], max_len=m["seq"], d_model=m["d_model"],
        n_heads=m["heads_full"], n_kv_heads=m["n_kv_heads"],
        head_dim=m["head_dim"], n_layers=5, d_ff=m["d_ff"],
        block_size=m["block_size"], pos_embed="rope", rope_layout="half",
        norm="rmsnorm", norm_eps=1e-6, bias=False, ffn="swiglu",
        tie_embeddings=False, attn_gate=True, remat=True,
        layers=(LayerSpec(None, m["heads_full"], full, "dense"), W, W, W, F),
        experts=Experts(n_experts=m["n_experts"], top_k=m["top_k"],
                        d_expert=m["d_expert"], held=(0, m["held"]),
                        scale=2.5, d_shared=m["d_expert"]),
        compute_dtype="bfloat16", seed=seed)


def _plain_mixed_loss(c, params, tokens):
    """The mixed model's loss with dense masked attention and the held
    experts one after another, each on every token under a weight that is 0
    where the token did not choose it: no kernel, no grouped product."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.models import expert_layer
    from deeplearning4j_tpu.models.transformer import (_block_apply,
                                                       _forward_tokens)
    c = dataclasses.replace(c, block_size=None, remat=False)
    ex = c.experts
    first, count = ex.held_range

    def looped(bp, h):
        flat = h.reshape(-1, h.shape[-1])
        w, chosen = expert_layer.route(ex, flat, bp["router"])
        y = expert_layer.glu(flat, bp["sh_gate"], bp["sh_up"],
                                bp["sh_down"])
        for e in range(count):
            w_e = jnp.where(chosen == first + e, w, 0.0).sum(-1)
            y = y + w_e[:, None].astype(h.dtype) * expert_layer.glu(
                flat, bp["W_gate"][e], bp["W_up"][e], bp["W_down"][e])
        return y.reshape(h.shape)

    def apply(i, bp, x):
        spec = c.layer_spec(i)
        return _block_apply(c, bp, x, spec=spec,
                            ffn=looped if spec.ffn == "experts" else None)

    @jax.jit
    def loss(params, tokens):
        logp = jax.nn.log_softmax(
            _forward_tokens(c, params, tokens[:, :-1], apply), axis=-1)
        return -jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean()

    return float(loss(params, jnp.asarray(tokens, jnp.int32)))


def phase_train_mixed_lm(sz, seed, rehearse):
    import numpy as np
    from deeplearning4j_tpu.models.transformer import TransformerLM

    m = sz["conf"]
    lm = TransformerLM(_mixed_config(m, seed)).init()
    c = lm.conf
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c.vocab_size, (sz["batch"], m["seq"] + 1))
    steps = 3
    losses, run_s = _lm_steps(lm, toks, steps)
    counters = lm.moe_counters()
    # eight of sixteen experts, two a token: one assignment a token on average
    balanced = steps * 4 * toks[:, 1:].size
    checks = {"loss_falls": _falls(losses),
              "no_row_over_buffer": counters["moe.rows_over_buffer"] == 0,
              "rows_near_balance":
                  0.5 * balanced < counters["moe.local_rows"] < 2 * balanced}
    facts = {}
    if not rehearse:   # interpret mode lowers to plain HLO, not Mosaic
        text = lm._step.lower(
            lm.params, lm.opt_state, lm.iteration, lm._rng,
            toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32),
            None).as_text()
        # a sparse layer's three grouped products are Pallas calls: forward,
        # forward again in the rematerialised block, input gradient (one
        # kernel, nine calls) and weight gradient (three); none is left to
        # the compiler's ragged_dot
        sparse = sum(spec.ffn == "experts" for spec in c.layers)
        facts["grouped_products"] = {
            n: text.count(f'kernel_name = "{n}"')
            for n in ("_gmm_kernel", "_tgmm_kernel")}
        facts["ragged_dots"] = text.count("ragged_dot")
        # ... and the rows around them move in Pallas calls too: the gather
        # (forward, again in the rematerialised block, and as the combine's
        # backward), the combine (forward, and as the gather's backward) and
        # the pass that lays the source of each out as words
        facts["row_movement"] = {
            n: text.count(f'kernel_name = "{n}"')
            for n in ("_gather_kernel", "_combine_kernel", "_words_kernel")}
        # remat or not, one of each a layer: a rematerialised block keeps the
        # forward kernel's output and logsumexp and does not run it again
        checks["flash_kernels_in_step"] = _flash_kernels_in_step(
            text, c.n_layers, facts, others=(12 + 10) * sparse)
        checks["grouped_products_in_step"] = facts["ragged_dots"] == 0 \
            and facts["grouped_products"] == {"_gmm_kernel": 9 * sparse,
                                              "_tgmm_kernel": 3 * sparse}
        checks["rows_moved_in_step"] = facts["row_movement"] == {
            "_gather_kernel": 3 * sparse, "_combine_kernel": 2 * sparse,
            "_words_kernel": 5 * sparse}
    got = lm.eval_loss(toks)
    want = _plain_mixed_loss(c, lm.params, toks)
    rel = abs(got - want) / abs(want)
    checks["loss_matches_plain"] = rel <= _BF16_REL
    return {"checks": checks, "run_s": run_s, "steps": steps,
            "tokens_per_step": int(toks[:, 1:].size), "losses": losses,
            "loss_rel_err_vs_plain": rel, "loss_kernels": got,
            "loss_plain": want, "counters": counters, **facts}


def phase_train_looped_lm(sz, seed, rehearse):
    import dataclasses

    import numpy as np
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    lm = TransformerLM(TransformerConfig(
        pos_embed="rope", rope_base=1e6, rope_layout="half", norm="rmsnorm",
        norm_eps=1e-6, bias=False, ffn="swiglu", tie_embeddings=False,
        post_norm=True, exit_gate=True, exit_entropy=0.1, remat=True,
        compute_dtype="bfloat16", seed=seed, **sz["conf"])).init()
    c = lm.conf
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c.vocab_size, (sz["batch"], c.max_len + 1))
    steps = 3
    losses, run_s = _lm_steps(lm, toks, steps)
    counters = lm.exit_counters()
    tokens = steps * toks[:, 1:].size
    checks = {"loss_falls": _falls(losses),
              "tokens_counted": counters["exit.tokens"] == tokens,
              # at the gate's initial balance the last exits hold an eighth
              "every_exit_carries_loss":
                  min(counters["exit.mass"]) > 0.02 * tokens,
              "exits_sum_to_the_tokens":
                  abs(sum(counters["exit.mass"]) - tokens) <= c.loops * steps}
    facts = {}
    if not rehearse:   # interpret mode lowers to plain HLO, not Mosaic
        text = lm._step.lower(
            lm.params, lm.opt_state, lm.iteration, lm._rng,
            toks[:, :-1].astype(np.int32), toks[:, 1:].astype(np.int32),
            None).as_text()
        # one of each an application, remat or not
        checks["flash_kernels_in_step"] = _flash_kernels_in_step(
            text, c.applications, facts)
    # the training loss against dense attention, nothing rematerialised
    plain = TransformerLM(dataclasses.replace(c, block_size=None,
                                              remat=False))
    plain.params = lm.params
    loss = lambda m: float(m._loss(m.params, toks[:, :-1], toks[:, 1:], None))
    got, want = loss(lm), loss(plain)
    rel = abs(got - want) / abs(want)
    checks["loss_matches_plain"] = rel <= _BF16_REL
    # all the runs a token through the loops x n_layers caches: greedy rows
    # pick the last exit's argmax wherever it is clear of bfloat16's rounding
    prompt, new = 8, 8
    rows = lm.generate(toks[:, :prompt], new, temperature=0.0)
    logits = lm.output(rows[:, :-1])[:, prompt - 1:]
    best = np.sort(logits, axis=-1)
    clear = best[..., -1] - best[..., -2] > _BF16_REL * np.abs(best[..., -1])
    checks["generate_reads_the_last_exit"] = bool(
        np.array_equal(rows[:, :prompt], toks[:, :prompt])
        and np.all((rows[:, prompt:] == logits.argmax(-1)) | ~clear))
    return {"checks": checks, "run_s": run_s, "steps": steps,
            "tokens_per_step": int(toks[:, 1:].size), "losses": losses,
            "loss_rel_err_vs_plain": rel, "applications": c.applications,
            "greedy_tokens_clear_of_rounding": int(clear.sum()),
            "counters": counters, **facts}


def _post(port, body, first_chunk=None):
    """POST /v1/generate and return the full token row. With ``first_chunk``
    (an Event) the request streams NDJSON: the event fires on the first
    decoded chunk, and the streamed spans must add up to the row's tail."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/generate",
        data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=300) as resp:
        if first_chunk is None:
            return json.loads(resp.read())["tokens"]
        streamed, final = [], {}
        for line in resp:
            msg = json.loads(line)
            if "done" in msg:
                final = msg
                break
            streamed += msg["tokens"]
            first_chunk.set()
    if not final.get("done"):
        raise RuntimeError(f"stream ended without a result: {final}")
    if final["tokens"][len(body["prompt"]):] != streamed:
        raise RuntimeError("streamed spans differ from the final row")
    return final["tokens"]


def phase_serve_lm(sz, seed, rehearse):
    import numpy as np
    from deeplearning4j_tpu.config import env_int
    from deeplearning4j_tpu.serving import ContinuousLM
    from deeplearning4j_tpu.serving.decode import kv_ladder, prefill_ladder
    from deeplearning4j_tpu.serving.ingress import ServingIngress
    from tools.compile_counter import CompileCounter

    lm = _lm(sz["conf"], seed)
    c = lm.conf
    rng = np.random.default_rng(seed)
    reqs = [(rng.integers(0, c.vocab_size, p).tolist(), n)
            for p, n in sz["requests"]]
    server = ContinuousLM(lm)
    slots = server.warm_start()   # compiles the whole rung inventory
    ingress = ServingIngress(server).start()
    answers, errors = {}, []

    def client(i, first_chunk=None):
        prompt, n_new = reqs[i]
        body = {"prompt": prompt, "n_new": n_new, "temperature": 0.0}
        if first_chunk is not None:
            body["stream"] = True
        try:
            answers[i] = _post(ingress.port, body, first_chunk)
        except Exception as e:   # noqa: BLE001 -- recorded, fails the phase
            errors.append(f"request {i}: {e!r}")
            if first_chunk is not None:
                first_chunk.set()

    try:
        with CompileCounter() as steady:
            t0 = time.perf_counter()
            decoding = threading.Event()
            threads = [threading.Thread(target=client, args=(0, decoding),
                                        daemon=True)]
            threads[0].start()
            decoding.wait(timeout=300)
            # request 0 has streamed its first chunk and still has most of
            # its n_new to go: these are admitted into a decoding pool
            others_sent_mid_decode = 0 not in answers
            for i in range(1, len(reqs)):
                threads.append(threading.Thread(target=client, args=(i,),
                                                daemon=True))
                threads[-1].start()
            for t in threads:
                t.join(timeout=600)
            if any(t.is_alive() for t in threads):
                errors.append("a client thread did not finish in 600 s")
            run_s = time.perf_counter() - t0
    finally:
        ingress.drain(timeout=30.0)
        server.stop()

    resolved = not errors and all(
        len(answers.get(i, ())) == len(p) + n
        and answers[i][:len(p)] == p
        for i, (p, n) in enumerate(reqs))
    # one greedy answer against the model's own generate(): equal, or
    # first differing at a bf16 near-tie of the two candidates' logits
    prompt, n_new = reqs[1]
    want = np.asarray(lm.generate(np.asarray([prompt]), n_new,
                                  temperature=0.0))[0].tolist()
    got = answers.get(1, [])
    exact = got == want
    tie_gap = None
    if not exact and len(got) == len(want):
        i = next(j for j, (a, b) in enumerate(zip(got, want)) if a != b)
        logits = lm.output(np.asarray([want[:i]]))[0, -1]
        tie_gap = float(abs(logits[got[i]] - logits[want[i]])
                        / max(1.0, np.abs(logits).max()))
    budget = len(kv_ladder(c.max_len, env_int("DL4J_TPU_SERVE_CHUNK"))) \
        + len(prefill_ladder(c.max_len)) + 1
    return {
        "checks": {
            "every_request_resolved": resolved,
            "admitted_mid_decode": others_sent_mid_decode,
            "greedy_equals_generate": exact or (
                tie_gap is not None and tie_gap <= _BF16_REL),
            "no_compile_after_warm_start": steady.count == 0,
            "signatures_within_ladder_budget":
                len(lm._jit_decode) <= budget,
        },
        "run_s": run_s, "requests": [[len(p), n] for p, n in reqs],
        "slots": slots, "decode_signatures": len(lm._jit_decode),
        "ladder_budget": budget, "greedy_exact": exact,
        "greedy_tie_gap": tie_gap, "errors": errors,
    }


def phase_dp4(sz, seed, rehearse):
    """``--chips 4`` only: three steps at one global batch on one device,
    then the same seed ``shard()``-ed over the 4-device mesh at ZeRO
    levels 0 and 3 — same losses, state really spread over four devices."""
    import jax
    import numpy as np
    from deeplearning4j_tpu.parallel.sharding_core import build_mesh

    c_kw = sz["conf"]
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, c_kw["vocab_size"],
                        (sz["batch"], c_kw["max_len"] + 1))
    mesh = build_mesh(devices=jax.devices()[:4])

    def run(level):
        lm = _lm(c_kw, seed)
        if level is not None:
            lm.shard(mesh, level=level)
        return (lm, *_lm_steps(lm, toks, _DP_STEPS))

    def devices_of(tree):
        """(fewest, most) devices any leaf of ``tree`` lives on."""
        n = [len({s.device for s in a.addressable_shards})
             for a in jax.tree.leaves(tree)]
        return min(n), max(n)

    def sharded_fraction(tree):
        """Share of the tree's bytes whose per-device shard is 1/4."""
        leaves = jax.tree.leaves(tree)
        part = sum(a.nbytes for a in leaves
                   if a.addressable_shards[0].data.size * 4 == a.size)
        return part / sum(a.nbytes for a in leaves)

    lm, ref, run_s = run(None)
    del lm   # free device 0 before the mesh runs
    gc.collect()
    checks, facts = {"loss_falls_1dev": _falls(ref)}, {"losses_1dev": ref}
    for level in (0, 3):
        lm, losses, dt = run(level)
        run_s += dt
        rel = max(abs(a - b) / max(1.0, abs(b)) for a, b in zip(losses, ref))
        p_dev, o_dev = devices_of(lm.params), devices_of(lm.opt_state)
        p_frac = sharded_fraction(lm.params)
        batch = jax.device_put(toks[:, :-1], lm._data_sharding)
        b_dev = devices_of(batch)
        facts[f"level{level}"] = {
            "losses": losses, "loss_rel_err_vs_1dev": rel,
            "param_devices_min_max": p_dev, "opt_devices_min_max": o_dev,
            "batch_devices_min_max": b_dev,
            "param_bytes_sharded_fraction": p_frac}
        checks[f"level{level}_matches_1dev"] = rel <= _BF16_REL
        # every leaf of params, optimizer state and batch is on all four
        # devices (replicated or sharded), none parked on the first
        checks[f"level{level}_state_on_4_devices"] = (
            p_dev[0] == 4 and o_dev[0] == 4 and b_dev[0] == 4)
        # level 0 replicates the params whole; level 3 keeps 1/4 shards
        checks[f"level{level}_param_placement"] = (
            p_frac == 0.0 if level == 0 else p_frac > 0.9)
        batch_rows = batch.addressable_shards[0].data.shape[0]
        checks[f"level{level}_batch_split"] = batch_rows * 4 == sz["batch"]
        del lm, batch
        gc.collect()
    return {"checks": checks, "run_s": run_s, **facts}


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

def _run_phase(name, fn, sz, seed, rehearse, device):
    """Run one phase under the compile and cache counters; a raised
    exception is a failed phase, never a skipped one."""
    from tools.compile_counter import CompileCacheCounter, CompileCounter
    t0 = time.perf_counter()
    try:
        with CompileCounter() as cc, CompileCacheCounter() as cache:
            out = fn(sz, seed, rehearse)
        ok = all(out["checks"].values())
    except Exception:   # noqa: BLE001 -- reported on the phase's line
        out = {"checks": {}, "error": traceback.format_exc()[-2000:]}
        ok = False
    stats = device.memory_stats() or {}
    line = {"phase": name, "ok": ok, **out,
            "wall_s": time.perf_counter() - t0,
            "compile_s": cc.seconds, "compiles": cc.count,
            "cache_hits": cache.hits, "cache_misses": cache.misses,
            "device_kind": device.device_kind,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
    print(json.dumps(line), flush=True)
    gc.collect()   # the phase's models are unreachable: free their HBM
    return ok


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the data-parallel phase on a "
                         "4-device mesh")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of: " + ", ".join(PHASES))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal: tiny sizes, Pallas interpret mode; "
                         "the last line then names the CPU")
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = sorted(set(phases) - set(PHASES))
    if unknown:
        ap.error(f"unknown phase(s) {unknown}; known: {list(PHASES)}")

    if args.rehearse:   # before jax initialises
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["DL4J_TPU_PALLAS_INTERPRET"] = "1"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.chips}")
    # decisions stay in memory: a smoke run neither adopts a slot/ladder
    # decision an earlier run persisted nor leaves one behind
    os.environ["DL4J_TPU_TUNE_CACHE_DIR"] = ""

    import jax
    import deeplearning4j_tpu  # noqa: F401 -- places the compile cache
    from deeplearning4j_tpu import nativelib

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: no TPU — JAX reports platform {dev.platform!r} "
              f"({dev.device_kind}). Nothing was run. For the tiny CPU "
              "rehearsal pass --rehearse.", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX reports "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    native = nativelib.ensure_built()
    print(json.dumps({
        "phase": "setup", "ok": native,
        "nativelib_available": nativelib.available(),
        "jax": jax.__version__, "rehearse": args.rehearse,
        "seed": args.seed, "device_kind": dev.device_kind,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }), flush=True)

    sz = _sizes(args.rehearse)
    if args.chips == 4:
        plan = [("dp4", phase_dp4, sz["dp"])]
    else:
        table = {"train_lenet": (phase_train_lenet, sz["lenet"]),
                 "train_resnet50": (phase_train_resnet50, sz["resnet"]),
                 "train_lm": (phase_train_lm, sz["lm"]),
                 "train_mixed_lm": (phase_train_mixed_lm, sz["mixed"]),
                 "train_looped_lm": (phase_train_looped_lm, sz["looped"]),
                 "serve_lm": (phase_serve_lm, sz["serve"])}
        plan = [(p, *table[p]) for p in PHASES if p in phases]
    ok = native
    for name, fn, phase_sz in plan:
        ok = _run_phase(name, fn, phase_sz, args.seed, args.rehearse,
                        dev) and ok
    print(json.dumps({"ok": ok, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
