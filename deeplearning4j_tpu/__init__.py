"""deeplearning4j_tpu — a TPU-native deep-learning framework.

A from-scratch rebuild of the capability surface of Deeplearning4j
(reference: Chiurie/deeplearning4j v0.7.3) designed for TPU hardware:

- functional layer zoo compiled by XLA (autodiff replaces the reference's
  hand-written ``backpropGradient`` chains, ``deeplearning4j-nn/.../nn/api/Layer.java:217``)
- sequential (:class:`MultiLayerNetwork`) and DAG (:class:`ComputationGraph`)
  models mirroring ``MultiLayerNetwork.java`` / ``ComputationGraph.java``
- fluent, JSON/YAML-serializable configuration
  (``nn/conf/NeuralNetConfiguration.java:485``)
- SGD-family updaters with schedules, clipping and gradient normalization
  (``nn/updater/LayerUpdater.java:137-275``)
- data-parallel training over a ``jax.sharding.Mesh`` with ICI allreduce in
  place of ``ParallelWrapper`` parameter averaging
  (``parallelism/ParallelWrapper.java:170-216``)
"""

__version__ = "0.1.0"


def _init_compile_cache():
    """Persistent XLA compilation cache — restarted runs and servers skip
    cold-start compiles. Its place is decided from outside: where
    ``JAX_COMPILATION_CACHE_DIR`` is set (or the caller configured a
    directory already) JAX has read it itself and no directory is set
    here. Otherwise it is the fixed ``<checkout>/.jax_cache`` — the path
    is part of the cache key's neighbourhood, so never a temporary name."""
    import os as _os

    import jax as _jax

    if _jax.config.jax_compilation_cache_dir is None:
        _jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(_os.path.dirname(_os.path.dirname(
                _os.path.abspath(__file__))), ".jax_cache"))
    # cache even fast/small compiles: the default 1s/min-size thresholds
    # would skip most of this framework's per-signature programs
    _jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


_init_compile_cache()

from deeplearning4j_tpu.nn.conf import (  # noqa: F401
    NeuralNetConfiguration,
    MultiLayerConfiguration,
)
from deeplearning4j_tpu.models.multi_layer_network import MultiLayerNetwork  # noqa: F401

from deeplearning4j_tpu.nn.conf import ComputationGraphConfiguration  # noqa: F401
from deeplearning4j_tpu.models.computation_graph import ComputationGraph  # noqa: F401
from deeplearning4j_tpu.models.transformer import (  # noqa: F401
    TransformerConfig, TransformerLM)
from deeplearning4j_tpu.models.vit import ViT, ViTConfig  # noqa: F401
from deeplearning4j_tpu.models.moe_transformer import (  # noqa: F401
    MoETransformerConfig, MoETransformerLM)
from deeplearning4j_tpu.parallel.tp_transformer import (  # noqa: F401
    TPTransformerLM)
from deeplearning4j_tpu.parallel.pp_transformer import (  # noqa: F401
    PPTransformerLM)
from deeplearning4j_tpu.parallel.sp_transformer import (  # noqa: F401
    SPTransformerLM)
