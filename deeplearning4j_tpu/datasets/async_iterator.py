"""Async prefetching iterator: background thread + bounded queue + device put.

Parity surface: ``datasets/iterator/AsyncDataSetIterator.java:36`` (IteratorRunnable
→ blocking queue :256; device-affinity pinning :75-76) and
``MultipleEpochsIterator``. The device-pinning role is played by
``jax.device_put`` with an optional sharding, overlapping host→HBM transfer with
compute — the TPU analog of MagicQueue's per-device buckets.
"""

from __future__ import annotations

import queue
import threading
import time
import warnings

import jax

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.config import env_int
from deeplearning4j_tpu.errors import PrefetchWorkerDiedError
from deeplearning4j_tpu.datasets.dataset import (DataSet, DataSetIterator,
                                                 MultiDataSet, StackedDataSet,
                                                 StackedMultiDataSet)
from deeplearning4j_tpu.testing import faults

_SENTINEL = object()

# process-wide prefetch observability (docs/OBSERVABILITY.md). The fuse
# counters are the PR-3 grouping telemetry migrated onto the registry:
# each per-instance increment ALSO lands here, so snapshots/Prometheus/
# bench see the cumulative process view while ``fuse_stats()`` keeps its
# per-iterator (and therefore per-fit — fit() wraps a fresh iterator)
# semantics.
_OBS_REBUCKETS = obs.counter(
    "prefetch.rebucket_flushes_total",
    "Mid-stream shape-change flushes of a fused bucket (each pads its "
    "short group up to K with zero-weight steps)")
_OBS_FUSED_GROUPS = obs.counter(
    "prefetch.fused_groups_total", "StackedDataSet groups emitted")
_OBS_PADDED_STEPS = obs.counter(
    "prefetch.padded_steps_total",
    "Zero-weight dummy steps added to pad short fused groups")
_OBS_PARTIAL_BATCHES = obs.counter(
    "prefetch.partial_flush_batches_total",
    "Batches adaptive grouping emitted under the per-batch contract "
    "instead of inside a padded fused group (lone mid-stream flushes and "
    "fully-degraded K=1 buckets)")
_OBS_PAD_SAVED = obs.counter(
    "fuse.padding_steps_saved_total",
    "Zero-weight padding steps adaptive grouping avoided relative to the "
    "always-pad-to-K contract (per-bucket K + trailing-group-only padding)")
_OBS_QUEUE_DEPTH = obs.gauge(
    "prefetch.queue_depth",
    "Prefetch queue occupancy (groups) after the worker's latest enqueue")
_OBS_CONSUMER_WAIT = obs.histogram(
    "prefetch.consumer_wait_seconds",
    "Time the training loop blocked waiting for the prefetch queue")

# consumer-side liveness poll: how long one bounded queue.get waits before
# re-checking that the worker thread is still alive (not a knob — it trades
# only fault-detection latency, never throughput: a live worker's batch is
# returned the moment it is enqueued)
_LIVENESS_POLL_S = 0.2


class _WorkerKilled(Exception):
    """Injected hard crash (``kill-worker`` fault point): the worker exits
    WITHOUT emitting its sentinel, which is exactly what a segfaulting or
    OOM-killed thread looks like to the consumer."""


class _Staged(object):
    """Host-side batch group awaiting device staging.

    The worker thread only ever groups/concatenates NUMPY arrays; the
    device transfer happens on the CONSUMER thread when the group is
    dequeued (__next__): one thread issues every device op, in program
    order, and JAX's async dispatch means a consumer-thread device_put
    still overlaps the actual transfer with queued compute. Whether a
    worker-thread transfer would be faster on the chip is not measured.
    """

    __slots__ = ("single", "concat")

    def __init__(self, single=None, concat=None):
        # exactly one of the two is set: a lone batch passes through as-is;
        # a multi-batch group keeps ONLY its host concatenation (keeping the
        # per-batch originals too would double queued host memory)
        self.single = single
        self.concat = concat


def default_stage():
    """Super-batch staging factor for model fit() paths. >1 amortizes
    per-transfer dispatch cost across K batches (its size on a local
    PCIe link is not measured); set
    DL4J_TPU_TRANSFER_STAGE=1 to disable (low-latency local links / tight
    device memory: staged prefetch holds up to 2K device-resident
    batches). Read at call time so setting the env var after import
    works; bad values fall back to 8 with a warning."""
    return env_int("DL4J_TPU_TRANSFER_STAGE", minimum=1)


def default_fuse():
    """Fused-scan step count for model fit() paths. >1 makes fit() run K
    parameter updates inside ONE jitted ``lax.scan`` program per emitted
    ``StackedDataSet`` (eliminating K-1 host dispatches); set
    DL4J_TPU_FUSE_STEPS=1 to disable (e.g. per-step listeners that must
    observe host state between updates — see docs/FUSED_LOOP.md). Read at
    call time; bad values fall back to 8 with a warning."""
    return env_int("DL4J_TPU_FUSE_STEPS", minimum=1)


class AsyncDataSetIterator(DataSetIterator):
    def __init__(self, base, queue_size=2, sharding=None, stage=1, fuse=1,
                 fuse_sharding=None, k_resolver=None, bucket_pad=False):
        """``stage`` > 1 enables SUPER-BATCH staging: the worker thread
        concatenates up to ``stage`` consecutive equal-shape mask-free
        batches on the host, moves them to the device in ONE transfer, and
        enqueues on-device slices. Where the per-transfer round trip
        dominates small-batch host→HBM cost, staging amortizes it
        ``stage``-fold. Batches with masks or
        shape changes (tail batch) fall back to per-batch transfer.

        Staging targets the single-device path: with an explicit
        ``sharding`` the super-batch's slices would carry a different
        layout than ``device_put(batch, sharding)`` (each slice landing on
        one device of the sharded super-batch), so ``stage`` is forced to
        1 there. Without ``sharding`` AND without staging, batches pass
        through as host arrays (legacy contract — ParallelWrapper shards
        them itself).

        ``fuse`` > 1 supersedes ``stage``: the worker groups up to ``fuse``
        consecutive batches of ONE bucket shape (ragged batches are padded
        up to the bucket's batch size with zero-weight rows; short trailing
        groups are padded up to ``fuse`` steps with zero-weight copies of
        the last batch) and emits each group as a single ``StackedDataSet``
        [K, B, ...] — the input of the models' fused ``lax.scan`` train
        loop. Exactly one device shape per run ⇒ exactly one compiled train
        signature, ragged trailing batch included. ``fuse_sharding`` (a
        NamedSharding whose spec covers the [K, B] leading axes, e.g.
        P(None, "data")) places stacked groups on a mesh for the
        data-parallel fused path; batches that cannot stack (masks, shape
        changes mid-bucket) fall back to the legacy single-batch contract.

        ``k_resolver`` (optional) maps a bucket shape key (``_shapes_of``)
        to that bucket's fused-group step count — the fusion autotuner's
        hook (tuning/autotuner.py): while a bucket is undecided it returns
        the probe group size, afterwards the tuned K. Called from the
        WORKER thread, so it must never touch jax. ``bucket_pad`` enables
        row-padding of ragged batches to the bucket's batch size in the
        PER-BATCH (fuse==1) path too, attaching the zero-weight tail as
        ``example_weights`` — the models' fit() pairs it with ew=ones full
        batches so unfused runs also hold one train signature."""
        self.base = base
        self.sharding = sharding
        self.fuse = max(1, int(fuse))
        self.fuse_sharding = fuse_sharding
        self._k_resolver = k_resolver
        self._bucket_pad = bool(bucket_pad)
        self.stage = 1 if sharding is not None else max(1, int(stage))
        # staging multiplies the device-resident footprint, so cap it in
        # BYTES, not batches: one super-batch transfer stays under
        # stage_bytes (the effective group size shrinks for large batches)
        # and the worker keeps at most ~2*stage_bytes of device-resident
        # batches queued (enforced in _worker.emit). Relief valves:
        # DL4J_TPU_TRANSFER_STAGE=1 (disable) or
        # DL4J_TPU_TRANSFER_STAGE_BYTES (cap, default 256 MiB).
        self.stage_bytes = env_int("DL4J_TPU_TRANSFER_STAGE_BYTES", minimum=1)
        # a whole group travels as ONE queue item (_Staged), so the queue
        # only needs room for a couple of items; the byte budget in
        # _worker.emit is what actually bounds queued host memory
        self.queue_size = max(queue_size, 2)
        self._device_stage = sharding is not None or self.stage > 1
        # fused groups are ALWAYS device-staged (fuse_sharding when given,
        # plain device_put otherwise): the fused scan consumes device
        # arrays. Non-stacked stragglers keep the single-batch contract
        # above (host pass-through unless sharding/stage say otherwise).
        self._queue = None
        self._thread = None
        self._stop = None
        self._error = None
        self._ready = None   # consumer-side buffer of device-staged batches
        # fused-loop grouping telemetry, cumulative over the iterator's
        # lifetime (reset() does NOT zero them: an epoch loop re-resets,
        # and the interesting number is per-fit). A mid-stream rebucket
        # pads every short group up to K with zero-weight dummy steps, so
        # a shape-thrashing stream can waste up to K-1 train steps per
        # real batch — this counter is the measurement the ROADMAP
        # "fused-loop grouping" item wants before any grouping change.
        # Plain int increments from the worker thread (GIL-atomic enough
        # for telemetry; a stale read costs a count, not correctness).
        self.rebucket_flushes = 0    # mid-stream shape-change flushes
        self.fused_groups = 0        # StackedDataSet groups emitted
        self.padded_steps = 0        # zero-weight dummy steps added
        # adaptive-grouping telemetry + state (DL4J_TPU_FUSE_ADAPT, default
        # on): batches a mid-stream flush emitted per-batch instead of
        # inside a padded group, and the padding steps that avoided vs the
        # always-pad contract. Worker-thread owned, like the counters above.
        self.partial_flush_batches = 0
        self.padded_steps_saved = 0
        # per-bucket adaptation, CUMULATIVE across resets (an epoch loop
        # re-resets; a bucket that thrashed in epoch 1 stays degraded
        # until full-group evidence recovers it):
        # _bucket_k[key] = adaptive K ceiling (halved toward 1 while
        # rebucket flushes outnumber naturally-full groups, doubled back
        # toward base while fulls outweigh flushes — see _maybe_recover),
        # _bucket_stats[key] = [mid-stream flushes, full groups],
        # _bucket_streak[key] = consecutive per-batch (K=1) emissions of
        # a degraded bucket, the recovery evidence and the honest
        # always-pad savings counterfactual (settled at bucket switches)
        self._bucket_k = {}
        self._bucket_stats = {}
        self._bucket_streak = {}
        self._bucket_cf = {}   # always-pad counterfactual K (byte-capped)
        # one-shot resume cursor (fit(resume_from=...)): the NEXT run's
        # worker discards this many base batches before grouping, so the
        # emitted stream is exactly the uninterrupted run's continuation
        self._skip_next = 0

    # ---- worker-side device staging ----------------------------------

    def _put(self, x):
        return x if x is None else (
            jax.device_put(x, self.sharding) if self.sharding is not None
            else jax.device_put(x))

    def _stageable(self, ds):
        import numpy as np
        if isinstance(ds, MultiDataSet):
            # device-resident arrays are already staged (see DataSet case)
            return (ds.features_masks is None and ds.labels_masks is None
                    and all(isinstance(a, np.ndarray)
                            for a in ds.features + ds.labels))
        return (isinstance(ds, DataSet) and ds.features is not None
                and ds.labels is not None and ds.features_mask is None
                and ds.labels_mask is None
                and getattr(ds.features, "shape", None) is not None
                # device-resident arrays are already staged: concatenating
                # would force a device->host round trip (the exact thing
                # DataSet keeps jax arrays resident to avoid)
                and isinstance(ds.features, np.ndarray)
                and isinstance(ds.labels, np.ndarray))

    @staticmethod
    def _nbytes(ds):
        try:
            if isinstance(ds, MultiDataSet):
                return sum(a.nbytes for a in ds.features) + sum(
                    a.nbytes for a in ds.labels)
            return ds.features.nbytes + ds.labels.nbytes
        except (AttributeError, TypeError):
            return 0    # masked/odd batches: exempt from the byte budget

    def _bucket_base_k(self, key):
        """Bucket group size before adaptation and byte caps: the tuner's
        decision (or its probe group size while the bucket is undecided)
        when a ``k_resolver`` is wired, else the fleet-wide fuse count.
        Worker-thread code: the resolver must never touch jax."""
        if self._k_resolver is not None:
            return max(1, int(self._k_resolver(key)))
        return self.fuse

    def _always_pad_k(self, key):
        """The byte-capped, un-degraded group size the FUSE_ADAPT=0
        contract would have padded this bucket's flush to — the honest
        counterfactual for ``padded_steps_saved`` (claiming the raw base K
        would over-count on byte-capped streams, where always-pad never
        builds base-K groups either). Recorded by _group_target at every
        group open, so it is always current for the bucket being flushed
        or settled."""
        return self._bucket_cf.get(key) or self._bucket_base_k(key)

    def _group_target(self, ds, key=None):
        """How many batches like ``ds`` one super-batch may hold: the
        configured stage (or the bucket's fused-step count when fusion is
        on — per-bucket: tuner decision, degraded adaptive ceiling), shrunk
        so the combined transfer stays under ``stage_bytes`` (always at
        least 1). Snapshotted when a group OPENS, so every group pads/fills
        against one deterministic K even if a tuner decision lands
        mid-group."""
        per = max(1, self._nbytes(ds))
        if self.fuse > 1:
            key = self._shapes_of(ds) if key is None else key
            group_n = self._bucket_base_k(key)
            # the always-pad counterfactual the savings telemetry measures
            # against: base K under the SAME byte cap, WITHOUT the adaptive
            # degradation — exactly what FUSE_ADAPT=0 would have padded to
            self._bucket_cf[key] = max(1, min(group_n,
                                              self.stage_bytes // per))
            cap = self._bucket_k.get(key)
            if cap is not None:
                group_n = min(group_n, cap)
        else:
            group_n = self.stage
        return max(1, min(group_n, self.stage_bytes // per))

    def _degrade_bucket(self, key):
        """Adaptation bookkeeping for one mid-stream rebucket flush of
        ``key``'s bucket: while flushes outnumber naturally-full groups
        the bucket's K halves toward 1 (at 1 the bucket emits under the
        per-batch contract and stops paying padding entirely)."""
        st = self._bucket_stats.setdefault(key, [0, 0])
        st[0] += 1
        if st[0] > st[1]:
            cur = self._bucket_k.get(key) or self._bucket_base_k(key)
            if cur > 1:
                self._bucket_k[key] = max(1, cur // 2)

    def _maybe_recover(self, key):
        """The mirror of _degrade_bucket: once full-group evidence (real
        full groups, or K=1 streaks worth a full group) outweighs the
        bucket's mid-stream flushes, its K doubles back toward base —
        degradation is adaptive, not a one-way ratchet, so a transient
        thrash phase cannot disable fusion for the rest of a long run."""
        cap = self._bucket_k.get(key)
        if cap is None:
            return
        st = self._bucket_stats.setdefault(key, [0, 0])
        if st[1] > st[0]:
            if cap * 2 >= self._bucket_base_k(key):
                self._bucket_k.pop(key, None)    # fully recovered
            else:
                self._bucket_k[key] = cap * 2
            # leaving (or shrinking) the per-batch regime: the pending
            # streak remainder is dropped, never claimed as savings
            self._bucket_streak.pop(key, None)

    def _settle_streak(self, key):
        """Account a terminated K=1 streak against the always-pad
        counterfactual: ``s`` consecutive same-bucket batches would have
        formed s//base full (unpadded) groups plus one flush padded with
        base-(s%base) dummy steps — only that remainder counts as saved.
        A long homogeneous run at degraded K therefore claims ~nothing
        (and recovery ends it anyway); a thrashing stream claims base-1
        per lone batch, exactly the waste PR-3 measured."""
        s = self._bucket_streak.pop(key, 0)
        r = s % self._always_pad_k(key) if s else 0
        if r:
            saved = self._always_pad_k(key) - r
            # graftlint: disable=G015 -- GIL-atomic int telemetry, same contract as fused_groups
            self.padded_steps_saved += saved
            _OBS_PAD_SAVED.inc(saved)

    @staticmethod
    def _shapes_of(ds):
        """Grouping key: every array's shape must match for a super-batch."""
        if isinstance(ds, MultiDataSet):
            return ("mds", tuple(a.shape for a in ds.features),
                    tuple(a.shape for a in ds.labels))
        return ("ds", ds.features.shape, ds.labels.shape)

    def _emit_single(self, ds):
        if self._device_stage and isinstance(ds, DataSet):
            out = DataSet(self._put(ds.features), self._put(ds.labels),
                          ds.features_mask, ds.labels_mask)
        elif self._device_stage and isinstance(ds, MultiDataSet):
            out = MultiDataSet([self._put(f) for f in ds.features],
                               [self._put(l) for l in ds.labels],
                               ds.features_masks, ds.labels_masks)
        else:
            return ds
        w = getattr(ds, "example_weights", None)
        if w is not None:   # row-padded ragged batch: zero-weight tail rides
            out.example_weights = self._put(w)
        return out

    # ---- fused-group (stacked super-batch) helpers --------------------

    @staticmethod
    def _pad_rows(ds, bucket):
        """Worker-side shape bucketing: pad a ragged (smaller-batch) batch
        up to the bucket's batch size with copies of its last example and a
        zero example-weight tail, so it compiles against the SAME signature
        as every full batch. Returns (padded_ds, weights[B]) or None when
        ``ds`` differs from the bucket in more than the batch dim. Copies
        of real rows (not zeros) keep batch statistics (BatchNorm) finite;
        the zero weight removes them from loss and gradient."""
        import numpy as np

        def pad_to(a, bn):
            n = a.shape[0]
            return np.concatenate([a, np.repeat(a[-1:], bn - n, axis=0)])

        if isinstance(ds, MultiDataSet):
            _, fshapes, lshapes = bucket
            bn = fshapes[0][0]
            n = ds.features[0].shape[0]
            if n >= bn:
                return None
            ok = all(a.shape == (n,) + ref[1:]
                     for a, ref in zip(ds.features, fshapes)) and \
                 all(a.shape == (n,) + ref[1:]
                     for a, ref in zip(ds.labels, lshapes)) and \
                 len(ds.features) == len(fshapes) and len(ds.labels) == len(lshapes)
            if not ok:
                return None
            w = np.zeros(bn, np.float32)
            w[:n] = 1.0
            return (MultiDataSet([pad_to(a, bn) for a in ds.features],
                                 [pad_to(a, bn) for a in ds.labels]), w)
        _, fshape, lshape = bucket
        bn = fshape[0]
        n = ds.features.shape[0]
        if (n >= bn or ds.features.shape[1:] != fshape[1:]
                or ds.labels.shape != (n,) + lshape[1:]):
            return None
        w = np.zeros(bn, np.float32)
        w[:n] = 1.0
        return (DataSet(pad_to(ds.features, bn), pad_to(ds.labels, bn)), w)

    @staticmethod
    def _host_stack(group, k_target):
        """Worker-side: stack a fused group to [K, B, ...] numpy arrays,
        padding short trailing groups up to ``k_target`` steps with
        zero-weight copies of the last batch (the scan body turns a
        zero-weight step into an identity update). ``group`` is a list of
        (ds, weights[B]|None); returns the _Staged payload."""
        import numpy as np

        first = group[0][0]
        bn = (first.features[0].shape[0] if isinstance(first, MultiDataSet)
              else first.features.shape[0])
        ws = [np.ones(bn, np.float32) if w is None else w for _, w in group]
        n_real = len(group)
        pad_steps = k_target - n_real
        if isinstance(first, MultiDataSet):
            mds = [d for d, _ in group] + [group[-1][0]] * pad_steps
            xs = [np.stack([d.features[i] for d in mds])
                  for i in range(len(first.features))]
            ys = [np.stack([d.labels[i] for d in mds])
                  for i in range(len(first.labels))]
        else:
            dss = [d for d, _ in group] + [group[-1][0]] * pad_steps
            xs = np.stack([np.asarray(d.features) for d in dss])
            ys = np.stack([np.asarray(d.labels) for d in dss])
        w = np.stack(ws + [np.zeros(bn, np.float32)] * pad_steps)
        kind = "fmds" if isinstance(first, MultiDataSet) else "fds"
        return (kind, xs, ys, w, n_real)

    @staticmethod
    def _host_concat(group):
        """Worker-side: one numpy concatenation per array stream. Pure
        host work (no jax) so it runs on the prefetch thread."""
        import numpy as np
        if isinstance(group[0], MultiDataSet):
            nf, nl = len(group[0].features), len(group[0].labels)
            xs = [np.concatenate([d.features[i] for d in group])
                  for i in range(nf)]
            ys = [np.concatenate([d.labels[i] for d in group])
                  for i in range(nl)]
            sizes = [d.num_examples() for d in group]
            return ("mds", xs, ys, sizes)
        xs = np.concatenate([np.asarray(d.features) for d in group])
        ys = np.concatenate([np.asarray(d.labels) for d in group])
        sizes = [d.features.shape[0] for d in group]
        return ("ds", xs, ys, sizes)

    def _stage_group(self, staged):
        """Consumer-side: ONE device transfer per array stream for the
        whole group, then on-device slices. The only method that touches
        jax for staged batches — it must run on the consumer thread (see
        class docstring of _Staged)."""
        if staged.single is not None:
            return [self._emit_single(staged.single)]
        if staged.concat[0] in ("fds", "fmds"):
            # fused stacked group: one transfer per stream, one emitted item
            kind, xs, ys, w, n_real = staged.concat
            putf = (lambda a: jax.device_put(a, self.fuse_sharding)) \
                if self.fuse_sharding is not None else jax.device_put
            if kind == "fmds":
                return [StackedMultiDataSet([putf(x) for x in xs],
                                            [putf(y) for y in ys],
                                            putf(w), n_real)]
            return [StackedDataSet(putf(xs), putf(ys), putf(w), n_real)]
        kind, xs, ys, sizes = staged.concat
        if kind == "mds":
            dxs = [self._put(x) for x in xs]
            dys = [self._put(y) for y in ys]
            out, pos = [], 0
            for n in sizes:
                out.append(MultiDataSet([x[pos:pos + n] for x in dxs],
                                        [y[pos:pos + n] for y in dys]))
                pos += n
            return out
        dxs, dys = self._put(xs), self._put(ys)
        out, pos = [], 0
        for n in sizes:
            out.append(DataSet(dxs[pos:pos + n], dys[pos:pos + n]))
            pos += n
        return out

    def skip_next(self, n):
        """Arm a one-shot fast-forward: the next run (``__iter__``/
        ``reset``) discards the first ``n`` base batches in the worker
        thread, BEFORE bucketing/grouping — the checkpoint cursor's
        fast-forward path (docs/ROBUSTNESS.md §4). Consumed by one reset."""
        self._skip_next = max(0, int(n))

    def _worker(self, q, stop, errbox, skip=0):
        # q/stop/errbox are captured per-run: after a reset() this thread can
        # only ever fill its own (abandoned) queue and error slot, never the
        # replacement's; stop is checked at every iteration boundary so a
        # zombie worker detaches from the shared base promptly.
        #
        # This thread NEVER touches jax: it groups and enqueues host
        # (numpy) batches only. Device transfers happen on the consumer
        # thread when a _Staged group is dequeued — every device op is
        # issued from one thread, and async dispatch gives the
        # consumer-thread transfer the same compute overlap.
        def emit(items, nbytes=0):
            for item in items:
                while not stop.is_set():
                    # byte budget: queued host batches may total at most
                    # ~2*stage_bytes, independent of queue_size in items
                    # (queue_size alone would let 2*stage large batches
                    # pile up; the consumer device-stages one group at a
                    # time, so this also bounds the device footprint)
                    if nbytes and q.qsize() > 0 and \
                            (q.qsize() + 1) * nbytes > 2 * self.stage_bytes:
                        stop.wait(0.05)
                        continue
                    try:
                        q.put(item, timeout=0.1)
                        _OBS_QUEUE_DEPTH.set(q.qsize())
                        break
                    except queue.Full:
                        continue

        def flush(group, full=False):
            nb = (sum(self._nbytes(d) for d in group)
                  if self._device_stage else 0)
            if len(group) > 1 and full:
                with obs.span("prefetch.host_concat", batches=len(group)):
                    concat = self._host_concat(group)
                emit([_Staged(concat=concat)], nb)
                return
            # PARTIAL stage groups (trailing batches, shape-change flushes)
            # go per-batch: a partial concat would mint a novel super-batch
            # shape whose consumer-side dynamic_slice programs XLA compiles
            # fresh every time the partial size changes (the pre-existing
            # "unfused=2 in-fit compiles" bench line) — only FULL groups
            # share the one super-batch slicing signature per bucket
            for d in group:
                emit([_Staged(single=d)],
                     self._nbytes(d) if self._device_stage else 0)

        def emit_weighted_single(d, w):
            # per-batch contract for fused-mode singles: a row-padded
            # ragged batch carries its zero-weight tail as example_weights
            # (the models' ew per-batch path keeps one train signature)
            if w is not None:
                d.example_weights = w
            emit([_Staged(single=d)] if self._device_stage else [d],
                 self._nbytes(d) if self._device_stage else 0)

        def flush_fused(group, k_target):
            # group: list of (ds, weights|None), all bucket-shaped; pads the
            # step dim up to ``k_target`` so every group emitted at that K
            # compiles against one scan signature
            if not group:
                return
            k = max(k_target, len(group))
            # graftlint: disable=G015 -- GIL-atomic int telemetry: fuse_stats reads after fit joins the worker; a mid-run stale read costs a count, never correctness
            self.fused_groups += 1
            # graftlint: disable=G015 -- GIL-atomic int telemetry, same contract as fused_groups above
            self.padded_steps += k - len(group)
            _OBS_FUSED_GROUPS.inc()
            _OBS_PADDED_STEPS.inc(k - len(group))
            nb = sum(self._nbytes(d) for d, _ in group)
            with obs.span("prefetch.stack_group", steps=len(group), k=k):
                staged = _Staged(concat=self._host_stack(group, k))
            emit([staged], nb)

        def flush_partial(group, k_target, bucket_key):
            # mid-stream flush under the ADAPTIVE contract: instead of
            # paying k_target-len(group) zero-weight padding steps, emit
            # the partial group at the next power-of-2 step count (a
            # handful of scan signatures per bucket, each compiled once)
            # or — for a lone batch — under the per-batch contract.
            # Padding steps are select-reverted identities either way, so
            # the trained params stay bit-identical to always-pad (the
            # trailing-parity test proves it). ``padded_steps_saved``
            # measures against the UN-degraded (but byte-capped) base K —
            # the steps the always-pad contract would actually have paid.
            if not group:
                return
            n = len(group)
            base_k = self._always_pad_k(bucket_key)
            if n == 1:
                d, w = group[0]
                # graftlint: disable=G015 -- GIL-atomic int telemetry, same contract as fused_groups above
                self.partial_flush_batches += 1
                _OBS_PARTIAL_BATCHES.inc()
                saved = max(0, base_k - 1)
                emit_weighted_single(d, w)
            else:
                k = min(1 << (n - 1).bit_length(), k_target)  # pow2 >= n
                saved = max(0, base_k - k)
                flush_fused(group, k)
            self.padded_steps_saved += saved
            _OBS_PAD_SAVED.inc(saved)

        def emit_k1(entry, key):
            # steady-state per-batch contract (K degraded to 1): emit on
            # arrival. Savings are NOT claimed here — consecutive
            # same-bucket batches accrue as a STREAK settled at the next
            # bucket switch / stream end (_settle_streak), where the
            # always-pad counterfactual is known. A streak worth a full
            # base-K group counts as full-group evidence, feeding
            # RECOVERY (_maybe_recover) so K climbs back once the stream
            # stops thrashing. Tuner- or byte-cap-driven K=1 (no
            # degradation entry) claims no streaks and no savings.
            d, w = entry
            self.partial_flush_batches += 1
            _OBS_PARTIAL_BATCHES.inc()
            emit_weighted_single(d, w)
            if key in self._bucket_k:
                s = self._bucket_streak.get(key, 0) + 1
                if s >= self._always_pad_k(key):
                    self._bucket_stats.setdefault(key, [0, 0])[1] += 1
                    s = 0
                    self._bucket_streak[key] = s
                    self._maybe_recover(key)
                else:
                    self._bucket_streak[key] = s

        try:
            it = iter(self.base)
            # transient-error budget for flaky base iterators (network-backed
            # record readers): retry the pull instead of failing the epoch.
            # Read once per run — the worker is a host thread, but a
            # per-batch env read would still be wasted work.
            retries = env_int("DL4J_TPU_ITER_RETRIES", minimum=0)
            # adaptive grouping contract (read once per run, like retries):
            # trailing-group-only padding + per-bucket K degradation
            from deeplearning4j_tpu.config import env_flag
            adapt = env_flag("DL4J_TPU_FUSE_ADAPT")
            attempts = 0
            last_exc = None
            n_pulled = 0
            group = []    # stageable batches awaiting a combined transfer
            fgroup = []   # (ds, weights) pairs awaiting a fused stack
            bucket = None  # shapes key the current fused bucket compiles for
            ftarget = 1   # the open fused group's K, snapshotted at open
            ubucket = None  # bucket_pad shapes key for the fuse==1 path
            while not stop.is_set():
                try:
                    if faults.fire("iter-raise") is not None:
                        raise RuntimeError(
                            "fault injected: base iterator failure at "
                            f"pull {n_pulled}")
                    with obs.span("prefetch.pull"):
                        ds = next(it)
                except StopIteration:
                    if attempts:
                        # a generator-backed base CLOSES when it raises, so
                        # the retry's pull reports a clean end-of-stream;
                        # treating that as the end would silently truncate
                        # the epoch — surface the original failure instead
                        # (retries only help re-pullable iterators)
                        raise last_exc
                    break
                except Exception as exc:
                    if attempts >= retries:
                        raise
                    attempts += 1
                    last_exc = exc
                    warnings.warn(
                        f"prefetch base iterator raised {exc!r}; "
                        f"retry {attempts}/{retries}", RuntimeWarning)
                    continue
                attempts = 0
                n_pulled += 1
                if skip > 0:
                    # resume fast-forward: this batch was already consumed
                    # by the run the checkpoint captured — discard it
                    # un-grouped (before pp/bucketing) so the rest of the
                    # stream buckets exactly as its continuation would.
                    # Discarded pulls sit INSIDE the retry budget above: a
                    # flaky base iterator that survives normal training
                    # survives the fast-forward too.
                    skip -= 1
                    continue
                if faults.fire("kill-worker") is not None:
                    raise _WorkerKilled
                spec = faults.fire("slow-batch")
                if spec is not None:
                    time.sleep(spec.param_float(0.1))
                # pre-processor runs here, in the background thread and BEFORE
                # device staging (DL4J applies preProcessor in
                # IteratorRunnable) — normalization overlaps compute and never
                # forces a device→host round trip
                ds = self._run_pp(ds)
                nb = self._nbytes(ds) if self._device_stage else 0
                if self.fuse > 1 and self._stageable(ds):
                    shp = self._shapes_of(ds)
                    if bucket is None:
                        bucket = shp
                    entry = None
                    if shp == bucket:
                        entry = (ds, None)
                    else:
                        entry = self._pad_rows(ds, bucket)
                        if entry is None:
                            # genuinely new shape: flush and rebucket. A
                            # shape change landing exactly on a group
                            # boundary (empty fgroup) costs nothing and is
                            # not counted as a flush.
                            if fgroup:
                                # graftlint: disable=G015 -- GIL-atomic int telemetry, same contract as fused_groups below
                                self.rebucket_flushes += 1
                                _OBS_REBUCKETS.inc()
                                if adapt:
                                    self._degrade_bucket(bucket)
                                    flush_partial(fgroup, ftarget, bucket)
                                else:
                                    flush_fused(fgroup, ftarget)
                            # the outgoing bucket's K=1 streak (if any)
                            # ends here: settle its savings remainder
                            self._settle_streak(bucket)
                            fgroup = []
                            bucket = shp
                            entry = (ds, None)
                    if not fgroup:
                        # K snapshot at group open: deterministic padding/
                        # fill even if a tuner decision lands mid-group
                        ftarget = self._group_target(ds, bucket)
                    if adapt and ftarget <= 1:
                        # fully-degraded (or tuner-chosen K=1) bucket: the
                        # per-batch contract, no stacking, no padding ever
                        emit_k1(entry, bucket)
                        continue
                    fgroup.append(entry)
                    if len(fgroup) >= ftarget:
                        flush_fused(fgroup, ftarget)
                        self._bucket_stats.setdefault(bucket, [0, 0])[1] += 1
                        self._maybe_recover(bucket)
                        fgroup = []
                elif self.fuse > 1:
                    # unstackable (masks / non-numpy): keep order — flush the
                    # pending group, then the single via the legacy contract
                    # (adaptive: emit the partial unpadded; not a rebucket).
                    # A K=1 streak is interrupted exactly as a group is.
                    if adapt:
                        flush_partial(fgroup, ftarget, bucket)
                    else:
                        flush_fused(fgroup, ftarget)
                    self._settle_streak(bucket)
                    fgroup = []
                    emit([_Staged(single=ds)] if self._device_stage else [ds],
                         nb)
                elif (padded := (
                        self._pad_rows(ds, ubucket)
                        if (self._bucket_pad and ubucket is not None
                            and self._stageable(ds)
                            and self._shapes_of(ds) != ubucket)
                        else None)) is not None:
                    # fuse==1 bucket padding: a ragged batch is row-padded
                    # up to the bucket's batch size with a zero example-
                    # weight tail, so the per-batch path holds ONE train
                    # signature too (the models pair it with ew=ones full
                    # batches). Pending stage group flushes first (order).
                    if group:
                        flush(group)
                        group = []
                    emit_weighted_single(*padded)
                elif self.stage > 1 and self._stageable(ds) and (
                        not group
                        or self._shapes_of(ds) == self._shapes_of(group[0])):
                    if self._bucket_pad:
                        ubucket = self._shapes_of(ds)
                    group.append(ds)
                    if len(group) >= self._group_target(ds):
                        flush(group, full=True)
                        group = []
                else:
                    if group:
                        flush(group)
                        group = []
                    if self._bucket_pad and self._stageable(ds):
                        ubucket = self._shapes_of(ds)
                    emit([_Staged(single=ds)] if self._device_stage else [ds],
                         nb)
            if not stop.is_set():
                if group:
                    flush(group)
                # TRAILING group of the stream: K-padding here is what keeps
                # the one-signature invariant on homogeneous streams, so it
                # stays even under adaptive grouping
                flush_fused(fgroup, ftarget)
                # settle every open K=1 streak against the always-pad
                # counterfactual (its trailing group would have padded)
                for key in list(self._bucket_streak):
                    self._settle_streak(key)
        except _WorkerKilled:
            # simulated hard crash (chaos testing): NO sentinel and NO error
            # box — the consumer's liveness check must catch this unaided
            return
        except Exception as e:  # surfaced on next()
            errbox.append(e)
            emit([_SENTINEL])
        else:
            # the sentinel must not be dropped (consumer would block forever),
            # but must also not block a shutdown (emit re-checks stop)
            emit([_SENTINEL])

    def _apply_pp(self, item):
        # already applied in _worker; the automatic __next__ wrapper must not
        # re-apply on the consumer thread
        return item

    @staticmethod
    def _pp_copy(item):
        # this iterator wraps BOTH batch kinds (the reference splits them
        # into Async(Multi)DataSetIterator); dispatch to the canonical
        # per-kind copy so the copy contract lives in one place
        from deeplearning4j_tpu.datasets.dataset import MultiDataSetIterator
        if isinstance(item, MultiDataSet):
            return MultiDataSetIterator._pp_copy(item)
        return DataSetIterator._pp_copy(item)

    def fuse_stats(self):
        """Fused-loop grouping telemetry: how the stream actually
        bucketed. ``rebucket_flushes`` > 0 means the stream changed shape
        mid-run; under adaptive grouping (DL4J_TPU_FUSE_ADAPT, default on)
        each such flush emits its partial group at the next power-of-2 —
        per-batch when lone (``partial_flush_batches``) — instead of
        padding to K, and ``padded_steps_saved`` counts the zero-weight
        steps that avoided. Models record this per fit as
        ``_last_fuse_stats`` and ``bench.py fused`` reports it. Every
        increment is mirrored onto the process-wide obs registry
        (``prefetch.*_total`` / ``fuse.padding_steps_saved_total``) —
        this view stays per-iterator."""
        return {"rebucket_flushes": self.rebucket_flushes,
                "fused_groups": self.fused_groups,
                "padded_steps": self.padded_steps,
                "partial_flush_batches": self.partial_flush_batches,
                "padded_steps_saved": self.padded_steps_saved}

    def shutdown(self):
        """Stop the prefetch thread and detach from the base iterator, so a
        failed/abandoned epoch doesn't leave a worker racing the next one."""
        if self._stop is not None:
            self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            if self._thread.is_alive():
                # blocked inside base.__next__; remember it so the next run
                # waits it out rather than racing it on the shared base
                self._lingering = self._thread
        self._queue = None
        self._thread = None
        self._stop = None
        self._ready = None

    def reset(self):
        self.shutdown()
        lingering = getattr(self, "_lingering", None)
        if lingering is not None:
            # must be fully dead before a new worker touches the base iterator
            lingering.join()
            self._lingering = None
        self._queue = queue.Queue(maxsize=self.queue_size)
        self._ready = []   # device-staged batches awaiting consumption
        self._error = []   # per-run error box shared with this run's worker only
        self._stop = threading.Event()
        skip, self._skip_next = self._skip_next, 0   # one-shot cursor
        self._thread = threading.Thread(
            target=self._worker,
            args=(self._queue, self._stop, self._error, skip),
            daemon=True)
        self._thread.start()

    def __iter__(self):
        self.reset()
        return self

    def _get_checked(self):
        """Bounded ``queue.get`` + worker-liveness check: a worker that died
        WITHOUT its sentinel (hard crash) raises a clear error instead of
        wedging the consumer forever. A live worker blocked on a slow base
        iterator is legitimate — only death breaks the wait."""
        q, thread = self._queue, self._thread
        t0 = time.perf_counter()

        def got(item):
            _OBS_CONSUMER_WAIT.record(time.perf_counter() - t0)
            return item

        with obs.span("prefetch.wait"):
            while True:
                try:
                    return got(q.get(timeout=_LIVENESS_POLL_S))
                except queue.Empty:
                    pass
                if thread is not None and thread.is_alive():
                    continue
                # dead worker: drain the race where the sentinel/batch landed
                # between the get timeout and the liveness check
                try:
                    return got(q.get_nowait())
                except queue.Empty:
                    if self._error:
                        raise self._error[0]
                    name = "<unstarted>" if thread is None else thread.name
                    raise PrefetchWorkerDiedError(
                        f"prefetch worker thread {name!r} died without "
                        "emitting its end-of-stream sentinel (hard crash?); "
                        "the stream is broken — reset() the iterator to "
                        "restart it")

    def __next__(self):
        if self._queue is None:
            self.reset()
        if self._ready:
            return self._ready.pop(0)
        item = self._get_checked()
        if item is _SENTINEL:
            if self._error:
                raise self._error[0]
            raise StopIteration
        if isinstance(item, _Staged):
            # device transfer happens HERE, on the consumer thread
            with obs.span("prefetch.device_put"):
                self._ready = self._stage_group(item)
            return self._ready.pop(0)
        return item

    def batch_size(self):
        return self.base.batch_size()


class MultipleEpochsIterator(DataSetIterator):
    """Repeat a base iterator N epochs (MultipleEpochsIterator.java)."""

    def __init__(self, epochs, base):
        self.epochs = epochs
        self.base = base
        self._epoch = 0
        self._inner = None

    def reset(self):
        self._epoch = 0
        self._inner = None

    def batch_size(self):
        return self.base.batch_size()

    def __next__(self):
        if self._inner is None:
            self._inner = iter(self.base)
        while True:
            try:
                return next(self._inner)
            except StopIteration:
                self._epoch += 1
                if self._epoch >= self.epochs:
                    raise
                self._inner = iter(self.base)
