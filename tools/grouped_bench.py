"""The held experts' grouped products alone, at the two expert cells' shapes,
on the chip: ``chiprun -- python tools/grouped_bench.py [--cells ...]``.

For each shape ``[rows, k] x [groups, k, n]`` (bfloat16; group sizes that sum
to about a quarter or a half of the buffer and are crowded as the cells'
``moe_peak_load`` says) one JSON line a product with the host-clock ms of a
call of the forward, of the input gradient and of the weight gradient:

* ``ragged_dot.tail_in_last_group``: ``jax.lax.ragged_dot`` as the layer
  called it until PR 35, the empty rows zero and in the last expert's group;
* ``ragged_dot.tail_in_no_group``: the same call, group sizes summing to the
  rows that hold an assignment;
* ``pallas.plan``: ``pallas_kernels.grouped_matmul`` at the row tile and the
  blocks its own plan gives (``grouped_row_tile``, ``grouped_plan``);
* ``pallas.<row tile>.<...>``: the same kernels at the plans of ``--plans``, a
  Python list of ``(row tile, (k, n block) of the forward, (k, n block) of
  the input gradient, (k, n block) of the weight gradient's output)``, one
  list a product (``gate_up``, ``down``).

``--move`` times the rows' movement around the products instead, at the same
shapes and at a routing drawn evenly over all experts (a quarter and a half
of the buffer live): ``pallas_kernels.gather_rows`` and ``combine_rows`` and
the backward of each against XLA's ``take`` and float32 ``.at[].add`` over
the whole buffer on the same indices, one JSON line each with the host-clock
ms of a call and the ns a live row; the ``words`` lines time the pass that
lays a source out as whole words a row (over the tokens for the gather, over
the row tiles the walk visits for the combine), which each kernel call
includes, and ``dispatch`` the index half that both forms share.

``--check`` poisons the operands' rows past the groups with NaN and compares
result and gradients on the rows inside the groups with a float32 loop over
the experts (``--move``: with ``src[token]`` and ``.at[token].add`` in
float32). Without a TPU it exits non-zero: a CPU timing is no device number
(PERF.md)."""

from __future__ import annotations

import argparse
import ast
import json
import sys
import time

import _bootstrap  # noqa: F401  (puts the repo root on sys.path)
import jax
import jax.numpy as jnp
import numpy as np

# (rows of the buffer, rows that hold an assignment, d_model, d_expert, held)
CELLS = {
    "smallthinker": (98304, 24000, 2560, 768, 16),
    "laguna": (32768, 16400, 2048, 512, 32),
}
# the layer of each cell, for --move: (tokens, experts, top_k, row_buffer)
LAYERS = {
    "smallthinker": (16384, 64, 6, 4.0),
    "laguna": (16384, 256, 8, 2.0),
}


def crowded_sizes(total, groups, peak, seed):
    """``groups`` int32 sizes that sum to ``total``, the fullest ``peak``
    times the even one, one group empty."""
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.2, 1.0, groups)
    w[rng.integers(groups)] = 0.0
    hot = int(np.argmax(w))
    rest = np.delete(np.arange(groups), hot)
    share = peak / groups
    w[rest] *= (1.0 - share) / w[rest].sum()
    w[hot] = share
    sizes = np.floor(w * total).astype(np.int32)
    sizes[hot] += total - sizes.sum()
    return sizes


def timed(fn, *args, reps=20):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / reps, out


def three(product):
    """The jitted forward, input gradient and weight gradient of ``product
    (a, w, sizes)``, each a call of its own."""
    fwd = jax.jit(product)
    da = jax.jit(lambda a, w, s, g: jax.vjp(lambda a: product(a, w, s), a)[1](g)[0])
    dw = jax.jit(lambda a, w, s, g: jax.vjp(lambda w: product(a, w, s), w)[1](g)[0])
    return fwd, da, dw


def explicit(pk, plan, groups):
    """The three kernels at a plan given by hand, each a call of its own."""
    tile, fwd_blocks, da_blocks, dw_blocks = plan
    fwd = jax.jit(lambda a, w, table: pk._gmm(
        a, w, table, row_tile=tile, transposed=False, blocks=fwd_blocks,
        interpret=pk._interpret_mode()))
    da = jax.jit(lambda a, w, table, g: pk._gmm(
        g, w, table, row_tile=tile, transposed=True, blocks=da_blocks,
        interpret=pk._interpret_mode()))
    dw = jax.jit(lambda a, w, table, g: pk._tgmm(
        a, g, table, row_tile=tile, groups=groups, blocks=dw_blocks,
        interpret=pk._interpret_mode()))
    return fwd, da, dw


def reference(a, w, g, sizes):
    """Forward, input gradient and weight gradient by a float32 loop over
    the experts, on the rows inside the groups (numpy, host)."""
    a, w, g = (np.asarray(x, np.float32) for x in (a, w, g))
    out, da, dw = np.zeros((a.shape[0], w.shape[2]), np.float32), \
        np.zeros(a.shape, np.float32), np.zeros(w.shape, np.float32)
    lo = 0
    for e, size in enumerate(sizes):
        hi = lo + int(size)
        out[lo:hi] = a[lo:hi] @ w[e]
        da[lo:hi] = g[lo:hi] @ w[e].T
        dw[e] = a[lo:hi].T @ g[lo:hi]
        lo = hi
    return out, da, dw


def gap(x, ref, rows=None):
    x = np.asarray(x, np.float32)
    if rows is not None:
        x, ref = x[:rows], ref[:rows]
    return {"finite": bool(np.isfinite(x).all()),
            "rel": float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-30))}


def move(args, platform):
    """The ``--move`` lines: the two kernels and their backward against
    XLA's whole-buffer forms, on the indices ``expert_layer.dispatch``
    makes."""
    from deeplearning4j_tpu.models import expert_layer
    expert_layer.pallas_supported = lambda: True     # the walk, on any backend
    for cell in args.cells.split(","):
        move_cell(args, cell, platform)
    return 0


def four(how, gather, combine, flat, out, w, dy):
    """A form's gather and combine and the backward of each, jitted, with
    their operands."""
    return [
        (f"{how}.gather", jax.jit(gather), (flat,)),
        (f"{how}.gather.bwd",
         jax.jit(lambda flat, g: jax.vjp(gather, flat)[1](g)), (flat, out)),
        (f"{how}.combine", jax.jit(combine), (out, w)),
        (f"{how}.combine.bwd",
         jax.jit(lambda out, w, g: jax.vjp(combine, out, w)[1](g)),
         (out, w, dy))]


def move_cell(args, cell, platform):
    from deeplearning4j_tpu.models import expert_layer
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    rows, _, d, de, held = CELLS[cell]
    tokens, n_experts, top_k, buffer = LAYERS[cell]
    tokens, d = tokens // args.shrink, d // args.shrink
    ex = expert_layer.Experts(n_experts, top_k, de, held=(0, held),
                              row_buffer=buffer)
    keys = jax.random.split(jax.random.PRNGKey(37), 5)
    chosen = jax.lax.top_k(
        jax.random.normal(keys[0], (tokens, n_experts)), top_k)[1]
    dispatch = jax.jit(lambda c: expert_layer.dispatch(ex, c, tokens))
    order, pos, valid, _, stats, table = dispatch(chosen)
    rows, live = order.shape[0], int(stats["local_rows"])
    tile = expert_layer._row_tile(ex, tokens)
    token = order // top_k
    flat = jax.random.normal(keys[1], (tokens, d), jnp.bfloat16)
    out = jax.random.normal(keys[2], (rows, d), jnp.bfloat16)
    w = jax.random.uniform(keys[3], (tokens, top_k), jnp.float32)
    dy = jax.random.normal(keys[4], (tokens, d), jnp.bfloat16)
    nan = jnp.asarray(jnp.nan, jnp.bfloat16)
    out_nan = jnp.where(valid[:, None], out, nan)

    def xla_gather(flat):
        return jnp.where(valid[:, None], flat[token], 0)

    def xla_combine(out, w):
        w_rows = jnp.where(valid, w.reshape(-1)[order], 0.0)
        weighted = jnp.where(valid[:, None], out.astype(jnp.float32)
                             * w_rows[:, None], 0.0)
        return jnp.zeros(flat.shape, jnp.float32).at[token].add(
            weighted).astype(flat.dtype)

    def own_gather(flat):
        return pk.gather_rows(flat, order, pos, table, tile)[0]

    def own_combine(out, w):
        return pk.combine_rows(out, w, order, pos, table, tile)

    words = jax.jit(lambda x, *walk: pk._row_words(
        (x,), *walk, row_tile=tile, interpret=pk._interpret_mode()))
    calls = [("words.tokens", words, (flat,)),
             ("words.live_tiles", words, (out, table)),
             ("dispatch", dispatch, (chosen,))] \
        + four("xla", xla_gather, xla_combine, flat, out, w, dy) \
        + four("pallas", own_gather, own_combine, flat, out, w, dy)
    results = {}
    for name, fn, xs in calls:
        line = {"cell": cell, "how": name, "rows": rows, "live_rows": live,
                "tokens": tokens, "top_k": top_k, "d": d, "row_tile": tile,
                "platform": platform}
        try:
            ms, _ = timed(fn, *xs)
            if platform == "tpu":
                line["ms"] = ms
                line["ns_a_live_row"] = 1e6 * ms / live
            if args.check and name.split(".")[0] in ("xla", "pallas"):
                poisoned = tuple(out_nan if x is out else x for x in xs) \
                    if name.startswith("pallas") else xs
                results[name] = fn(*poisoned)
        except Exception as e:  # noqa: BLE001
            line["error"] = repr(e)[-600:]
        print(json.dumps(line), flush=True)
    if not args.check:
        return
    f32 = lambda x: np.asarray(x, np.float32)
    inside = slice(0, live)
    pairs = {
        "gather": (f32(results["pallas.gather"])[inside],
                   f32(results["xla.gather"])[inside]),
        "gather.bwd": (f32(results["pallas.gather.bwd"][0]),
                       f32(results["xla.gather.bwd"][0])),
        "combine": (f32(results["pallas.combine"]),
                    f32(results["xla.combine"])),
        "combine.bwd.out": (f32(results["pallas.combine.bwd"][0])[inside],
                            f32(results["xla.combine.bwd"][0])[inside]),
        "combine.bwd.w": (f32(results["pallas.combine.bwd"][1]),
                          f32(results["xla.combine.bwd"][1]))}
    print(json.dumps({"cell": cell, "how": "poisoned_tail",
                      "platform": platform,
                      **{k: gap(a, b) for k, (a, b) in pairs.items()}}),
          flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cells", default="smallthinker,laguna")
    ap.add_argument("--move", action="store_true",
                    help="time the rows' movement, not the products")
    ap.add_argument("--plans", default="{}",
                    help="{'<cell>.<product>': [(tile, fwd, da, dw)]}")
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearse the control flow; prints no ms")
    ap.add_argument("--shrink", type=int, default=1)
    args = ap.parse_args()
    platform = jax.devices()[0].platform
    if platform != "tpu" and not args.allow_cpu:
        print("no TPU: a CPU timing is no device number", file=sys.stderr)
        return 1
    if args.move:
        return move(args, platform)
    from deeplearning4j_tpu.ops import pallas_kernels as pk
    own = getattr(pk, "grouped_matmul", None)
    plans = ast.literal_eval(args.plans)
    for cell in args.cells.split(","):
        rows, live, d, de, groups = CELLS[cell]
        rows, live, d, de = (x // args.shrink for x in (rows, live, d, de))
        sizes_np = crowded_sizes(live, groups, 3.6 if cell == "smallthinker" else 2.1, 35)
        sizes = jnp.asarray(sizes_np)
        with_tail = sizes.at[-1].add(rows - live)
        for label, k, n in (("gate_up", d, de), ("down", de, d)):
            keys = jax.random.split(jax.random.PRNGKey(k), 3)
            a = jax.random.normal(keys[0], (rows, k), jnp.bfloat16)
            w = jax.random.normal(keys[1], (groups, k, n), jnp.bfloat16) * 0.02
            g = jax.random.normal(keys[2], (rows, n), jnp.bfloat16)
            inside = (jnp.arange(rows) < live)[:, None]
            a0, g0 = jnp.where(inside, a, 0), jnp.where(inside, g, 0)
            nan = jnp.asarray(jnp.nan, jnp.bfloat16)
            a_nan, g_nan = jnp.where(inside, a, nan), jnp.where(inside, g, nan)
            flop = 2.0 * live * k * n
            products = [
                ("ragged_dot.tail_in_last_group", jax.lax.ragged_dot, with_tail, a0, g0),
                ("ragged_dot.tail_in_no_group", jax.lax.ragged_dot, sizes, a0, g0)]
            if own is not None:
                # the walk's table is made once, outside the timed calls,
                # as the layer makes it once for its eight products
                tile = pk.grouped_row_tile(live // groups)
                products.append((
                    "pallas.plan",
                    lambda a, w, table, t=tile: own(a, w, table, t),
                    pk.group_tiles(sizes, rows, tile), a0, g0))
                for plan in plans.get(f"{cell}.{label}", ()):
                    products.append((
                        "pallas." + ".".join(map(str, plan)).replace(" ", ""),
                        plan, pk.group_tiles(sizes, rows, plan[0]), a0, g0))
            ref = reference(a0, w, g0, sizes_np) if args.check else None
            for name, product, s, a_in, g_in in products:
                line = {"cell": cell, "product": label, "shape": [rows, k, n],
                        "groups": groups, "live_rows": live, "how": name,
                        "platform": platform}
                if name.startswith("pallas"):
                    line["grid_rows"] = int(s[-1])
                try:
                    fwd, da, dw = explicit(pk, product, groups) \
                        if isinstance(product, tuple) else three(product)
                    ms = {}
                    for tag, fn, xs in (("fwd", fwd, (a_in, w, s)),
                                        ("da", da, (a_in, w, s, g_in)),
                                        ("dw", dw, (a_in, w, s, g_in))):
                        ms[tag], _ = timed(fn, *xs)
                    if platform == "tpu":
                        line["ms"] = ms
                        line["tflops_on_live_rows"] = {
                            t: flop / v / 1e9 for t, v in ms.items()}
                    if args.check and not name.endswith("last_group"):
                        outs = (fwd(a_nan, w, s), da(a_nan, w, s, g_nan),
                                dw(a_nan, w, s, g_nan))
                        line["poisoned_tail"] = {
                            "fwd": gap(outs[0], ref[0], live),
                            "da": gap(outs[1], ref[1], live),
                            "dw": gap(outs[2], ref[2])}
                except Exception as e:  # noqa: BLE001
                    line["error"] = repr(e)[-600:]
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
