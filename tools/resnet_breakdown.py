"""ResNet-50 time-sink breakdown.

Ablation-based profiling: times the full bf16 train step, then variants
with one suspected cost source removed, and reports each component's
share of the step plus the implied MFU. This names the top time sinks
with measured numbers where no trace reduction exists yet (ROADMAP S0);
pair with ProfilerListener traces.

Variants:
- full          : resnet50 bf16 train step (the bench configuration)
- fwd_only      : output() only — isolates backward+optimizer share
- no_bn         : BatchNormalization dropped from every block (conv+relu
                  residual net of identical conv shapes) — isolates BN
- fp32          : compute_dtype float32 — isolates bf16 speedup
- conv_gemm_roof: a single fused dummy matmul with the same FLOP count —
                  the practical MXU roof for this chip via XLA

Usage: python tools/resnet_breakdown.py [batch ...] (default 128 256)
One TPU process; never run concurrently with bench.py.
"""

import json
import sys
import time

import numpy as np

import _bootstrap  # noqa: F401  (repo root onto sys.path)


def _net(conf):
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph
    g = ComputationGraph(conf)
    g.init()
    return g

def build(batch, *, bn=True, dtype="bfloat16"):
    from deeplearning4j_tpu.models.zoo import resnet50
    conf = resnet50(n_classes=1000)
    if not bn:
        # drop BN vertices: rewire each BN's consumers to its input
        drop = {name for name, v in conf.vertices.items()
                if type(v).__name__ == "LayerVertex"
                and type(getattr(v, "layer", None)).__name__
                == "BatchNormalization"}
        if not drop:   # fall back: name-based (zoo names bn layers "*_bn")
            drop = {n for n in conf.vertices if n.endswith("_bn")}
        remap = {}
        for name in drop:
            [inp] = conf.vertex_inputs[name]
            remap[name] = inp
        def resolve(n):
            while n in remap:
                n = remap[n]
            return n
        for name in list(conf.vertex_inputs):
            if name in drop:
                continue
            conf.vertex_inputs[name] = [resolve(i)
                                        for i in conf.vertex_inputs[name]]
        for name in drop:
            del conf.vertices[name]
            del conf.vertex_inputs[name]
        conf.network_outputs = [resolve(o) for o in conf.network_outputs]
        conf.topological_order = conf._topological_sort()   # rebuilt DAG
    conf.compute_dtype = dtype
    return _net(conf)


def timed(fn, sync, warm=3, meas=10):
    for _ in range(warm):
        fn()
    sync()
    t0 = time.perf_counter()
    for _ in range(meas):
        fn()
    sync()
    return (time.perf_counter() - t0) / meas


def main():
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.datasets.dataset import MultiDataSet

    batches = [int(a) for a in sys.argv[1:]] or [128, 256]
    from deeplearning4j_tpu.hw import peak_bf16_flops
    platform = jax.devices()[0].platform
    peak = peak_bf16_flops() if platform == "tpu" else None
    FLOPS_PER_IMG_TRAIN = 3 * 3.86e9               # fwd 3.86 GF x3 for train

    out = {"platform": platform, "batches": {}}
    rng = np.random.default_rng(0)
    for batch in batches:
        x = jnp.asarray(rng.normal(size=(batch, 224, 224, 3)).astype(np.float32))
        y = jnp.asarray(np.eye(1000, dtype=np.float32)[
            rng.integers(0, 1000, batch)])
        mds = MultiDataSet([x], [y])
        rep = {}

        g = build(batch, bn=True, dtype="bfloat16")
        rep["full_s"] = timed(lambda: g.fit_batch(mds), lambda: float(g.score_))
        rep["img_per_s"] = batch / rep["full_s"]
        if peak:
            rep["mfu"] = batch * FLOPS_PER_IMG_TRAIN / rep["full_s"] / peak

        rep["fwd_only_s"] = timed(
            lambda: g.output(*mds.features),
            lambda: float(jnp.ravel(g.output(*mds.features))[0]),
            warm=2, meas=6)

        g32 = build(batch, bn=True, dtype="float32")
        rep["fp32_s"] = timed(lambda: g32.fit_batch(mds),
                              lambda: float(g32.score_), warm=2, meas=5)
        del g32

        gnb = build(batch, bn=False, dtype="bfloat16")
        rep["no_bn_s"] = timed(lambda: gnb.fit_batch(mds),
                               lambda: float(gnb.score_), warm=2, meas=5)
        del gnb

        # MXU roof: one dense matmul with the train-step FLOP count
        n = int(np.sqrt(batch * FLOPS_PER_IMG_TRAIN / 2.0) ** (1 / 1.5))
        a = jnp.asarray(rng.normal(size=(n, n)).astype(jnp.bfloat16))
        # graftlint: disable=G002 -- profiling tool: one deliberate compile per batch config, used immediately
        mm = jax.jit(lambda a: a @ a)
        roof_flops = 2 * n ** 3
        rep["roof_s_per_eqflops"] = timed(
            lambda: mm(a), lambda: float(jnp.sum(mm(a)[0, 0])), warm=2,
            meas=5) * (batch * FLOPS_PER_IMG_TRAIN / roof_flops)
        if peak:
            rep["roof_mfu"] = batch * FLOPS_PER_IMG_TRAIN / \
                rep["roof_s_per_eqflops"] / peak

        rep["bn_share"] = 1 - rep["no_bn_s"] / rep["full_s"]
        rep["bwd_opt_share"] = 1 - rep["fwd_only_s"] / rep["full_s"]
        rep["bf16_speedup"] = rep["fp32_s"] / rep["full_s"]
        out["batches"][batch] = {k: round(v, 5) for k, v in rep.items()}
        print(json.dumps({str(batch): out["batches"][batch]}), flush=True)

    print(json.dumps(out))


if __name__ == "__main__":
    main()
