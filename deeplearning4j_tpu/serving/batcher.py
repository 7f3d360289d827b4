"""Request queue + batcher: output() inference through fixed signatures.

Callers submit SINGLE examples; the batch loop groups same-shape
requests into the ``DL4J_TPU_SERVE_BUCKETS`` batch-size ladder, pads a
partial batch to the smallest bucket that fits (the ``async_iterator``
row-padding machinery — copies of the last real row, discarded on the
way out), and dispatches ONE ``model.output()`` per batch through the
blessed signature-keyed jit caches. Steady state therefore runs a
FIXED compiled-signature set: (number of buckets) x (number of distinct
row shapes), pinned by :meth:`InferenceServer.signatures` and
``tools/compile_counter.py`` in ``bench.py serve``.

Queue/lifecycle semantics (capacity backpressure, typed drain, the
single owner-thread contract) live in ``serving/_base.py`` — shared
with the continuous decoder. Fault sites (``DL4J_TPU_FAULT_SPEC``,
docs/ROBUSTNESS.md): ``queue-overflow`` forces a submit to see a full
queue, ``slow-request`` sleeps the batch loop before dispatching batch
N, ``client-disconnect`` cancels a request's future right before its
result lands (the loop must discard and move on, never wedge).
"""

from __future__ import annotations

import time
from collections import deque
from concurrent.futures import Future

import numpy as np

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.config import env_float
from deeplearning4j_tpu.serving._base import (_DISCONNECTS, _OCCUPANCY,
                                              _QUEUE_DEPTH, _REQ_SECONDS,
                                              ServingFrontEnd, int_ladder,
                                              resolve_deadline)
from deeplearning4j_tpu.testing import faults

__all__ = ["InferenceServer", "serve_buckets"]

_BATCHES = obs.counter("serve.batches_total",
                       "Batches the serving batcher dispatched")
_PADDED_ROWS = obs.counter(
    "serve.padded_rows_total",
    "Padding rows dispatched to fill partial batches up to their bucket")
_DISPATCH_SECONDS = obs.histogram(
    "serve.dispatch_seconds",
    "Device dispatch + result fetch time of one served batch")


def serve_buckets():
    """The batch-size bucket ladder from ``DL4J_TPU_SERVE_BUCKETS``
    (``int_ladder`` semantics: sorted, deduplicated, warn-and-fall-back
    on malformed values)."""
    return int_ladder("DL4J_TPU_SERVE_BUCKETS", (8,))


def _infer_signature(model, x):
    """The blessed inference-cache key for this model family: MLN's
    ``_output_signature``, ComputationGraph's ``_cache_signature("out",
    ...)``, or — for models without a jitted output cache
    (TransformerLM logits) — the same-shaped tuple, so the served
    signature set is pinned uniformly across families."""
    if hasattr(model, "_output_signature"):
        return model._output_signature(x, None)
    if hasattr(model, "_cache_signature"):
        return model._cache_signature("out", [x], None, None, None)
    return ("out", tuple(x.shape), str(x.dtype))


class _Request:
    __slots__ = ("x", "key", "future", "t0", "deadline")

    def __init__(self, x, deadline=None):
        self.x = x
        self.key = (x.shape, str(x.dtype))
        self.future = Future()
        self.t0 = time.monotonic()
        self.deadline = deadline   # absolute monotonic, None = none


class InferenceServer(ServingFrontEnd):
    """Thread-safe batching front end over a ``model.output()`` surface.

    ``model`` is any in-tree model exposing ``output(x)`` row-aligned
    with ``x`` (MultiLayerNetwork, single-input ComputationGraph,
    TransformerLM logits). Construct, optionally :meth:`warm_start`,
    then :meth:`submit`/:meth:`infer` from any thread; :meth:`stop`
    drains."""

    _thread_name = "dl4j-serve-batcher"

    def __init__(self, model, buckets=None, *, queue_cap=None, wait_s=None):
        super().__init__(queue_cap=queue_cap)
        self.model = model
        self._buckets = tuple(sorted(int(b) for b in buckets)) if buckets \
            else serve_buckets()
        self._wait = wait_s if wait_s is not None \
            else env_float("DL4J_TPU_SERVE_WAIT", minimum=0.0)
        self._sigs = set()        # blessed signatures served so far

    def _loop(self):
        self._batch_loop()

    # ---- warm start / introspection ------------------------------------
    def warm_start(self, row_shapes, dtype=None):
        """Pre-compile the blessed output signatures for every
        (bucket, row shape) pair by dispatching zeros through
        ``model.output`` — a server RESTART replays these compiles from
        the persistent XLA cache (``JAX_COMPILATION_CACHE_DIR``, else
        ``<checkout>/.jax_cache``) and cold-start is ~free
        (docs/SERVING.md). ``dtype``
        defaults per model family — int32 token rows for the LM family
        (marked by the blessed ``_gen_signature`` builder), float32
        features otherwise — so the warmed signatures are the ones real
        submits will hit. Returns the pinned signature list."""
        if dtype is None:
            dtype = "int32" if hasattr(self.model, "_gen_signature") \
                else "float32"
        for shape in row_shapes:
            for b in self._buckets:
                x = np.zeros((b,) + tuple(shape), dtype)
                with obs.building(f"serve.warm.out.b{b}"):
                    self.model.output(x)
                sig = _infer_signature(self.model, x)
                with self._lock:
                    self._sigs.add(sig)
        return self.signatures()

    def signatures(self):
        """The (sorted, repr'd) blessed signature set this server has
        dispatched through — ``bench.py serve`` asserts it is FIXED
        after warmup."""
        with self._lock:
            return sorted(repr(s) for s in self._sigs)

    # ---- client surface ------------------------------------------------
    def submit(self, x, *, deadline_s=None):
        """Enqueue ONE example (feature array WITHOUT the batch dim);
        returns a ``concurrent.futures.Future`` resolving to that
        example's output row. ``deadline_s`` is this request's deadline
        budget (seconds; default ``DL4J_TPU_SERVE_DEADLINE_S``): a
        request still queued past it is swept with
        ``ServeDeadlineError`` BEFORE dispatch, never batched. Raises
        ``ServeQueueFullError`` when the queue is at capacity
        (backpressure) and ``ServeStoppedError`` after ``stop()`` or
        during a drain."""
        return self._enqueue(_Request(np.asarray(x),
                                      resolve_deadline(deadline_s)))

    def infer(self, x, timeout=60.0):
        """Synchronous ``submit``: the output row, or the typed error."""
        return self.submit(x).result(timeout)

    # ---- batch loop (single owner thread) ------------------------------
    def _take_batch(self):
        """Pop up to max-bucket same-shape requests, lingering up to
        ``DL4J_TPU_SERVE_WAIT`` for the bucket to fill. Returns a list
        (empty = stop)."""
        b_max = self._buckets[-1]
        with self._lock:
            while not self._pending and not self._stopping:
                self._more.wait(0.05)       # bounded: stop() must land
            if not self._pending:
                return []
            key = self._pending[0].key
            deadline = time.monotonic() + self._wait
            while not self._stopping:
                n = sum(1 for r in self._pending if r.key == key)
                left = deadline - time.monotonic()
                if n >= b_max or left <= 0:
                    break
                self._more.wait(min(left, 0.05))
            batch, rest = [], deque()
            while self._pending:
                r = self._pending.popleft()
                if r.key == key and len(batch) < b_max:
                    batch.append(r)
                else:
                    rest.append(r)
            self._pending = rest
            _QUEUE_DEPTH.set(len(self._pending))
            return batch

    def _batch_loop(self):
        while True:
            batch = self._take_batch()
            if not batch:
                return
            # pre-dispatch deadline sweep: an expired request is failed
            # typed here and NEVER batched (zero device work)
            batch = self._sweep_expired(batch)
            if not batch:
                continue
            if self._replica_fault():
                return   # kill-replica: hard crash, no cleanup
            try:
                self._dispatch_batch(batch)
            except Exception as exc:
                # the loop survives a bad batch: its callers get the
                # typed/raw error, later requests still serve
                for r in batch:
                    if not r.future.done():
                        r.future.set_exception(exc)

    def _dispatch_batch(self, batch):
        spec = faults.fire("slow-request")
        if spec is not None:
            time.sleep(spec.param_float(0.05))
        n = len(batch)
        b = next((b for b in self._buckets if b >= n), self._buckets[-1])
        x = np.stack([r.x for r in batch])
        if n < b:
            x = _pad_batch_rows(x, b)
            _PADDED_ROWS.inc(b - n)
        _OCCUPANCY.record(n / b)
        with _DISPATCH_SECONDS.time():
            # output() returns host numpy — the ONE documented sync per
            # dispatched batch (the eval-seam contract on output itself)
            y = self.model.output(x)
        with self._lock:
            self._sigs.add(_infer_signature(self.model, x))
        _BATCHES.inc()
        now = time.monotonic()
        for i, r in enumerate(batch):
            if faults.fire("client-disconnect") is not None:
                r.future.cancel()
            if r.future.cancelled():
                _DISCONNECTS.inc()
                continue
            r.future.set_result(y[i])
            _REQ_SECONDS.record(now - r.t0)


def _pad_batch_rows(x, b):
    """Row-pad a stacked request batch up to its bucket size through the
    ``async_iterator`` machinery (``_pad_rows``: copies of the last real
    row — finite under batch statistics, discarded on the way out)."""
    from deeplearning4j_tpu.datasets.async_iterator import \
        AsyncDataSetIterator
    from deeplearning4j_tpu.datasets.dataset import DataSet
    ds = DataSet(x, np.zeros((x.shape[0], 1), np.float32))
    bucket = ("ds", (b,) + x.shape[1:], (b, 1))
    padded = AsyncDataSetIterator._pad_rows(ds, bucket)
    if padded is None:   # shape drifted from the bucket: impossible via
        return x         # _take_batch's same-key grouping; belt-and-braces
    return padded[0].features   # host numpy out of _pad_rows
