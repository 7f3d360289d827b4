"""Worker process for the 2-process jax.distributed parity test.

Each process owns 2 virtual CPU devices (a stand-in host), joins the
multi-controller runtime, and drives the SAME ParallelWrapper code over a
4-device global mesh, feeding only its local half of every batch — the
per-host sharded-input contract of SURVEY §5.8. Run by
tests/test_multihost.py; not a test itself.
"""

import json
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import jax  # noqa: E402


def main():
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    outfile = sys.argv[4]

    from deeplearning4j_tpu.parallel import multihost
    multihost.initialize(f"127.0.0.1:{port}", num_processes=nproc,
                         process_id=pid, local_devices=2)

    import numpy as np
    from deeplearning4j_tpu import NeuralNetConfiguration
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
    from deeplearning4j_tpu.nn.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.parallel.parallel_wrapper import (
        ParallelWrapper, data_parallel_mesh)

    assert len(jax.devices()) == 2 * nproc, jax.devices()
    assert multihost.process_count() == nproc

    rng = np.random.RandomState(0)
    X = rng.randn(16, 8).astype(np.float32)
    W = rng.randn(8, 3).astype(np.float32)
    Y = np.eye(3, dtype=np.float32)[np.argmax(X @ W, axis=1)]

    conf = (NeuralNetConfiguration.Builder()
            .seed(7).updater("sgd").learning_rate(0.1)
            .list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss="negativeloglikelihood"))
            .build())
    net = MultiLayerNetwork(conf)
    net.init()

    mesh = data_parallel_mesh(jax.devices())     # spans both processes
    wrapper = ParallelWrapper(net, mesh=mesh)

    # per-host sharded input: this process loads ONLY its half
    lo, hi = pid * 8, (pid + 1) * 8
    local = DataSet(X[lo:hi], Y[lo:hi])
    for _ in range(5):
        wrapper.fit(local)

    checksum = float(sum(float(np.asarray(p).sum())
                         for lp in net.params_list for p in lp.values()))
    out = {"process": pid, "checksum": checksum,
           "score": float(net.score_),
           "global_devices": len(jax.devices())}
    with open(outfile, "w") as f:
        json.dump(out, f)
    print("OK", json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
