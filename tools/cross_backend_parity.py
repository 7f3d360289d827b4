"""Cross-backend parity: the same jitted computation on the TPU backend
vs host CPU must agree within tolerance.

The reference's strongest correctness gates are equivalence tests —
cuDNN-helper vs builtin outputs (``TestConvolution.java:118``) and
Spark-vs-single-machine params (``TestCompareParameterAveragingSparkVs
SingleMachine.java:44``). This tool applies the same pattern one level
down, across PJRT backends: logical results must not depend on which
backend compiled the program.

Each leg runs in a SUBPROCESS per backend (a jax process is pinned to one
backend once initialized). This parent never imports jax — a process that
has touched jax holds the chip — and runs the legs strictly one after
another, the TPU leg first so a machine without a chip fails before the
CPU leg is paid for.

Usage:  python tools/cross_backend_parity.py          # TPU vs CPU
        python tools/cross_backend_parity.py --self   # CPU vs CPU (smoke)
Exits 0 on parity, 1 on mismatch, 2 when there is no TPU (JAX reports
another platform, or the leg timed out), 3 when the TPU leg crashed on
the TPU (a TPU-side regression).
"""

import json
import os
import subprocess
import sys

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:        # repo root holds bench.py and the package
    sys.path.insert(0, _ROOT)

_PAYLOAD = r"""
import json, sys
import numpy as np
platform = sys.argv[1]
import jax, jax.numpy as jnp
if jax.default_backend() != platform:
    # a "tpu" leg that quietly ran on the CPU would make the gate vacuous
    print("PARITY_WRONG_BACKEND:" + jax.default_backend())
    sys.exit(0)

from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork
from deeplearning4j_tpu.models.zoo import lenet_mnist, char_rnn

out = {}
rng = np.random.RandomState(0)

# 1) LeNet forward + one SGD step: logits and post-step score
net = MultiLayerNetwork(lenet_mnist()).init()
x = rng.rand(8, 28, 28, 1).astype(np.float32)
y = np.eye(10, dtype=np.float32)[rng.randint(0, 10, 8)]
out["lenet_logits"] = np.asarray(net.output(x)).tolist()
net.fit_batch(jnp.asarray(x), jnp.asarray(y))
out["lenet_score"] = float(net.score_)

# 2) LSTM char-rnn forward (scan path)
net2 = MultiLayerNetwork(char_rnn(vocab_size=16, tbptt_length=8)).init()
ids = rng.randint(0, 16, (2, 12))
xs = np.eye(16, dtype=np.float32)[ids]
out["lstm_out"] = np.asarray(net2.output(xs)).reshape(-1)[:64].tolist()

# 3) TransformerLM: logits + one AdamW step (attention, LN, tied embeds)
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
lm = TransformerLM(TransformerConfig(vocab_size=24, max_len=16, d_model=16,
                                     n_heads=2, n_layers=1, d_ff=32,
                                     seed=0)).init()
toks = rng.randint(0, 24, (2, 10))
out["lm_logits"] = np.asarray(lm.output(toks)).reshape(-1)[:64].tolist()
out["lm_loss"] = float(lm.fit_batch(toks))

# 4) ViT: probabilities + one step (patchify reshape path + mean pool)
from deeplearning4j_tpu.models.vit import ViT, ViTConfig
vit = ViT(ViTConfig(image_size=8, n_channels=1, patch_size=2, n_classes=10,
                    d_model=32, n_heads=2, n_layers=1, d_ff=64,
                    seed=0)).init()
imgs = rng.rand(4, 8, 8, 1).astype(np.float32)
labels = rng.randint(0, 10, 4)
out["vit_probs"] = np.asarray(vit.output(imgs)).reshape(-1).tolist()
out["vit_loss"] = float(vit.fit_batch(imgs, labels))

# 5) MoE LM: switch-routed logits + one step. Cross-backend float noise
# (~1e-6) could flip an argmax route on a near-tied gate, so the payload
# (a) exports the routing so a flip FAILS on 'moe_routing' (diagnosed as
# a flip, not a numerics regression) and (b) asserts the seed gives
# comfortable gate margins in the first place.
from deeplearning4j_tpu.models import moe_transformer as _MT
from deeplearning4j_tpu.models.moe_transformer import (MoETransformerConfig,
                                                       MoETransformerLM)
moe = MoETransformerLM(MoETransformerConfig(
    vocab_size=24, max_len=16, d_model=16, n_heads=2, n_layers=2, d_ff=32,
    n_experts=2, moe_every=2, seed=0)).init()
_route = {"margin": float("inf"), "eid": []}
_orig_ffn = _MT.moe_ffn_dense
def _spy(bp, h, E):
    # accumulate across MoE layers: min margin, concatenated routing
    gl = (h @ bp["gate"]).astype(jnp.float32).reshape(-1, E)
    top2 = jnp.sort(gl, axis=-1)[:, -2:]
    _route["margin"] = min(_route["margin"],
                           float(jnp.min(top2[:, 1] - top2[:, 0])))
    _route["eid"] += np.asarray(jnp.argmax(gl, axis=-1)).tolist()
    return _orig_ffn(bp, h, E)
_MT.moe_ffn_dense = _spy
try:
    out["moe_logits"] = np.asarray(moe.output(toks)).reshape(-1)[:64].tolist()
finally:
    _MT.moe_ffn_dense = _orig_ffn
assert _route["margin"] > 1e-3, (
    f"gate margin {_route['margin']:.2e} too small for cross-backend "
    "argmax stability — pick a different seed for this check")
out["moe_routing"] = _route["eid"]
out["moe_loss"] = float(moe.fit_batch(toks))

print("PARITY_JSON:" + json.dumps(out))
"""


class WrongBackend(Exception):
    """The leg's process came up on another platform than asked for."""


def run_backend(platform, timeout=600):
    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    else:
        env.pop("JAX_PLATFORMS", None)   # let the real backend register
    r = subprocess.run(
        [sys.executable, "-c", _PAYLOAD, platform],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_ROOT)
    for line in r.stdout.splitlines():
        if line.startswith("PARITY_WRONG_BACKEND:"):
            raise WrongBackend(line.split(":", 1)[1])
        if line.startswith("PARITY_JSON:"):
            return json.loads(line[len("PARITY_JSON:"):])
    raise RuntimeError(
        f"{platform} run produced no parity payload (rc={r.returncode}): "
        f"{r.stderr[-500:]}")


def main():
    if "--self" in sys.argv:
        name = "cpu(2nd run)"
        other = run_backend("cpu")
    else:
        name = "tpu"
        try:
            other = run_backend("tpu")
        except WrongBackend as e:
            print(f"no TPU: the tpu leg came up on {e}; cannot check "
                  "cross-backend parity")
            return 2
        except subprocess.TimeoutExpired as e:
            print(f"TPU leg timed out: {e}")
            return 2
        except RuntimeError as e:
            # on the TPU but the leg CRASHED — a real TPU-side regression,
            # distinct from both mismatch (1) and no TPU (2)
            print(f"TPU leg crashed: {e}")
            return 3
    ref = run_backend("cpu")
    worst = 0.0
    for key in ref:
        a = np.asarray(ref[key], dtype=float)
        b = np.asarray(other[key], dtype=float)
        err = float(abs(a - b).max() / max(1.0, abs(a).max()))
        worst = max(worst, err)
        status = "OK" if err < 2e-2 else "MISMATCH"
        print(f"{key}: cpu vs {name} max rel err {err:.2e} [{status}]")
    if worst >= 2e-2:   # bf16-tolerant bar; logical divergence is >> this
        print("FAIL: backends disagree beyond tolerance")
        return 1
    print("parity OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
