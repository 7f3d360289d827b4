"""Multi-host (multi-process) distributed runtime.

SURVEY §5.8: the reference scales across hosts with a Spark driver +
parameter-averaging workers (``ParameterAveragingTrainingMaster.java:650``)
or an Aeron parameter server. TPU-natively the same role is played by
JAX's multi-controller runtime: every host runs the SAME program,
``jax.distributed`` wires the coordination service, the mesh spans all
hosts' devices, and XLA routes collectives over ICI within a slice and
DCN across slices. Each host feeds only its local shard of every batch
(``make_array_from_process_local_data``) — the per-host sharded-input
contract of the Spark ingest path, without a driver in the data plane.

On CPU (tests / this environment) cross-process collectives use XLA's
Gloo backend — the same code path shape as multi-host TPU, minus the
fabric. ``tests/test_multihost.py`` proves 2-process parity against
single-process training.
"""

from __future__ import annotations

import jax
import numpy as np

_INITIALIZED = False


def initialize(coordinator_address: str, num_processes: int, process_id: int,
               *, local_devices: int | None = None):
    """Join the multi-controller runtime (idempotent per process).

    On the CPU backend this selects the Gloo collectives implementation
    (required for cross-process psum/all_gather); on TPU the plugin's
    fabric is used as-is. ``local_devices`` forces the per-process CPU
    device count (tests use 2×N virtual devices).
    """
    global _INITIALIZED
    if _INITIALIZED:
        return
    if local_devices is not None:
        jax.config.update("jax_num_cpu_devices", int(local_devices))
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    _INITIALIZED = True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def is_multiprocess(mesh) -> bool:
    """True when the mesh spans devices owned by more than one process."""
    return len({d.process_index for d in mesh.devices.flat}) > 1


def global_put(arr, sharding, *, per_host_shard: bool):
    """Place a host array onto a (possibly multi-process) sharding.

    ``per_host_shard=True``: ``arr`` is THIS host's shard of the batch
    dimension (per-host sharded input — each host loads different data);
    the global array is their concatenation.
    ``per_host_shard=False``: ``arr`` is the full (replicated) value and
    must be identical on every process.
    Single-process meshes degrade to a plain ``device_put``.
    """
    if arr is None:
        return None
    arr = np.asarray(arr)  # graftlint: disable=G001 -- ingest seam: host batch normalized BEFORE placement, no device value syncs
    mesh = sharding.mesh
    if not is_multiprocess(mesh):
        return jax.device_put(arr, sharding)
    if per_host_shard:
        return jax.make_array_from_process_local_data(sharding, arr)
    # replicated: every process owns a full copy; local shard == full value
    return jax.make_array_from_process_local_data(sharding, arr,
                                                  global_shape=arr.shape)
