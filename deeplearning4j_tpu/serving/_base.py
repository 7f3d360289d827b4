"""Shared queue + lifecycle core of the two serving front ends.

One bounded request deque, one lock, one owner-thread contract — the
batcher (`batcher.py`) and the continuous decoder (`decode.py`) differ
only in what their loop does with a popped request, so the
capacity/backpressure/typed-drain semantics live HERE once: a queue
fairness or deadline change cannot silently diverge between the two.

Thread contract: ``_enqueue`` is called from any client thread; the
subclass ``_loop`` body runs on ONE daemon thread spawned UNDER the
lock by the same critical section that checked ``_stopping`` — a
concurrent ``stop()`` can therefore never be resurrected by a racing
submit (the spawn and the stop flag are serialized on one lock).
``stop()`` drains the queue typed, then joins; subclass state owned by
the loop thread is only touched through ``_after_stop(joined)``, which
reports whether the join actually landed.
"""

from __future__ import annotations

import threading
import time
import warnings
from collections import deque

from deeplearning4j_tpu import obs
from deeplearning4j_tpu.config import env_float, env_int
from deeplearning4j_tpu.errors import (ServeDeadlineError,
                                       ServeQueueFullError,
                                       ServeStoppedError)
from deeplearning4j_tpu.testing import faults

__all__ = ["ServingFrontEnd", "int_ladder", "resolve_deadline"]


def int_ladder(knob, default):
    """Parse a comma-separated-int ladder knob (sorted, deduplicated,
    each at least 1); malformed values warn and fall back to ``default``
    — the registry's uniform contract. Shared by the batcher's bucket
    ladder and the decoder's slot ladder so the two parses cannot
    drift."""
    from deeplearning4j_tpu.config import env_str
    raw = env_str(knob)
    try:
        # graftlint: disable=G001 -- env knob parse: host config ints
        vs = sorted({max(1, int(p)) for p in raw.split(",") if p.strip()})
    except ValueError:
        warnings.warn(f"{knob}={raw!r} is not a comma-separated int "
                      f"list; using {default}")
        vs = []
    return tuple(vs) if vs else default

_QUEUE_DEPTH = obs.gauge(
    "serve.queue_depth",
    "Requests waiting in the serving queue (batcher + continuous decoder)")
_REQUESTS = obs.counter("serve.requests_total",
                        "Requests accepted by the serving tier")
_REJECTED = obs.counter(
    "serve.rejected_total",
    "Requests refused with ServeQueueFullError (backpressure)")

OCCUPANCY_BUCKETS = (0.0625, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75,
                     0.875, 1.0)

_REQ_SECONDS = obs.histogram(
    "serve.request_seconds",
    "End-to-end request latency: submit() to result (p50/p99 on /metrics)")
_OCCUPANCY = obs.histogram(
    "serve.batch_occupancy",
    "Real-request fraction of each dispatched batch / decode chunk "
    "(1.0 = no padding rows, no idle KV slots)", buckets=OCCUPANCY_BUCKETS)
_DISCONNECTS = obs.counter(
    "serve.disconnects_total",
    "Requests whose caller disappeared (cancelled future) mid-flight")
_DEADLINE_EXPIRED = obs.counter(
    "serve.deadline_expired_total",
    "Requests swept with ServeDeadlineError before dispatch: their "
    "deadline expired while they were still queued, so they never "
    "reached the device")


def resolve_deadline(deadline_s):
    """Absolute monotonic deadline for a submit: an explicit per-request
    budget (seconds) wins; else the ``DL4J_TPU_SERVE_DEADLINE_S``
    default (0 = no deadline → ``None``)."""
    if deadline_s is None:
        deadline_s = env_float("DL4J_TPU_SERVE_DEADLINE_S", minimum=0.0)
        if not deadline_s:
            return None
    # graftlint: disable=G001 -- parses the caller's host deadline budget (python/env float at the submit seam), never a device value
    return time.monotonic() + float(deadline_s)


class ServingFrontEnd:
    """Bounded request queue + single owner-thread lifecycle."""

    _thread_name = "dl4j-serve"

    def __init__(self, queue_cap=None):
        obs.compilation.install()
        self._lock = threading.Lock()
        self._more = threading.Condition(self._lock)
        self._pending = deque()
        self._cap = queue_cap if queue_cap is not None \
            else env_int("DL4J_TPU_SERVE_QUEUE", minimum=1)
        self._stopping = False
        self._draining = False
        self._died = False    # hard crash (kill-replica): no resurrection
        self._thread = None
        # accepted-but-unresolved request count: incremented by _enqueue,
        # decremented by a future done-callback — covering EVERY
        # resolution path (completion, typed drain, disconnect cancel,
        # deadline sweep) without per-site bookkeeping. drain() and the
        # router's load() read it.
        self._open = 0
        # set by ReplicaRouter for the kill-replica / slow-replica fault
        # qualifiers and the failover logs; None outside a router
        self.replica_id = None

    # ---- subclass surface ----------------------------------------------
    def _loop(self):
        """The owner-thread body (dispatch loop)."""
        raise NotImplementedError

    def _after_stop(self, joined):
        """Called by ``stop()`` after the join attempt; ``joined`` is
        False when the loop thread outlived the timeout — loop-owned
        state must then be left alone."""

    # ---- queue ---------------------------------------------------------
    def _enqueue(self, r):
        """Admit request ``r`` (an object with ``future`` and
        ``deadline`` attrs) under the capacity/stopping/draining
        contract and make sure the loop thread runs. Returns
        ``r.future``."""
        overflow = faults.fire("queue-overflow") is not None
        with self._lock:
            if self._stopping or self._draining or self._died:
                raise ServeStoppedError(
                    "serving front end is draining" if self._draining
                    else "serving loop died (replica crash)" if self._died
                    else "serving front end is stopped")
            if overflow or len(self._pending) >= self._cap:
                _REJECTED.inc()
                raise ServeQueueFullError(
                    f"serving queue at capacity ({self._cap}); retry "
                    f"later (DL4J_TPU_SERVE_QUEUE)")
            self._pending.append(r)
            self._open += 1
            _REQUESTS.inc()
            _QUEUE_DEPTH.set(len(self._pending))
            self._more.notify()
            self._ensure_thread_locked()
        # registered OUTSIDE the lock: an already-resolved future runs
        # its callback synchronously, and _dec_open takes the same lock
        r.future.add_done_callback(self._dec_open)
        return r.future

    def _dec_open(self, _future):
        with self._lock:
            self._open -= 1

    def _pop_pending(self):
        with self._lock:
            if not self._pending:
                return None
            r = self._pending.popleft()
            _QUEUE_DEPTH.set(len(self._pending))
            return r

    def _sweep_expired(self, reqs):
        """The pre-dispatch deadline sweep: fail every request in
        ``reqs`` whose deadline has already expired (typed, with the
        non-positive time left in the message) and return only the live
        ones — an expired request is NEVER batched or admitted, so it
        costs zero device work. The ``expire-deadline`` fault site
        forces a sweep check to see an expired request. Runs OUTSIDE
        the queue lock (resolving a future fires done-callbacks that
        take it)."""
        now = time.monotonic()
        live = []
        for r in reqs:
            dl = r.deadline
            if faults.fire("expire-deadline") is not None:
                dl = now
            if dl is not None and now >= dl:
                _DEADLINE_EXPIRED.inc()
                if not r.future.done():
                    r.future.set_exception(ServeDeadlineError(
                        f"request deadline expired before dispatch "
                        f"(time left {dl - now:.4f}s <= 0); swept from "
                        f"the queue, no device work done"))
            else:
                live.append(r)
        return live

    # ---- router surface -------------------------------------------------
    def load(self):
        """Balancing signal for the ReplicaRouter: requests accepted
        (queued + admitted + dispatching) whose futures have not
        resolved yet."""
        with self._lock:
            return self._open

    def healthy(self):
        """Heartbeat liveness: accepting work (not stopped/draining) and
        the loop thread — if one was ever spawned — still alive. A
        scheduler that hard-crashed mid-loop reports False while its
        queue may still hold work: the router's failover trigger."""
        with self._lock:
            if self._stopping or self._draining or self._died:
                return False
            return self._thread is None or self._thread.is_alive()

    def evict_pending(self):
        """Atomically remove and return every not-yet-dispatched queued
        request (failover: the router re-dispatches a dead replica's
        pending work to survivors; the dead scheduler can no longer pop
        them)."""
        with self._lock:
            out = list(self._pending)
            self._pending.clear()
            _QUEUE_DEPTH.set(0)
            return out

    def _replica_fault(self):
        """The ``kill-replica`` / ``slow-replica`` chaos sites, fired
        once per dispatch with this replica's id as qualifier. Returns
        True when this replica must die NOW — the loop exits without
        failing its futures (a hard crash; recovery is the router's
        failover, not the dying thread's cleanup)."""
        if faults.fire("kill-replica", qual=self.replica_id) is not None:
            with self._lock:
                # a dead replica stays dead: a racing submit must NOT
                # respawn the loop thread over half-mutated state
                self._died = True
            return True
        spec = faults.fire("slow-replica", qual=self.replica_id)
        if spec is not None:
            time.sleep(spec.param_float(0.5))
        return False

    # ---- lifecycle -----------------------------------------------------
    def _ensure_thread_locked(self):
        # caller holds the lock; _stopping was checked in the SAME
        # critical section, so a racing stop() cannot be resurrected
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, name=self._thread_name, daemon=True)
            self._thread.start()

    def start(self):
        """Explicitly (re)start the loop thread — the only call that
        clears a previous ``stop()`` or ``drain()``."""
        with self._lock:
            self._stopping = False
            self._draining = False
            self._died = False
            self._ensure_thread_locked()
        return self

    def drain(self, timeout=30.0):
        """Graceful drain: from the first moment, NEW submits fail typed
        (``ServeStoppedError`` — ingress answers 503) while every
        already-accepted request, queued or admitted, runs to
        completion; then the loop thread is stopped and joined.
        Returns True when all accepted work finished inside ``timeout``
        (``stop()`` then had nothing to drop typed)."""
        with self._lock:
            self._draining = True
            self._more.notify_all()
        deadline = time.monotonic() + timeout
        drained = False
        while time.monotonic() < deadline:
            with self._lock:
                drained = self._open == 0
            if drained:
                break
            time.sleep(0.005)
        self.stop(timeout=max(1.0, deadline - time.monotonic()))
        return drained

    def stop(self, timeout=10.0):
        """Drain: queued requests fail typed immediately; the loop exits
        at its next boundary and joins; loop-owned state is failed over
        via ``_after_stop`` only when the join actually landed."""
        with self._lock:
            self._stopping = True
            dropped = list(self._pending)
            self._pending.clear()
            _QUEUE_DEPTH.set(0)
            self._more.notify_all()
            t = self._thread
        for r in dropped:
            if not r.future.done():
                r.future.set_exception(
                    ServeStoppedError("serving stopped before this "
                                      "request was dispatched"))
        joined = True
        if t is not None:
            t.join(timeout)
            joined = not t.is_alive()
        if not joined:
            warnings.warn(
                f"{self._thread_name}: loop thread still running "
                f"{timeout}s after stop(); in-flight state left to it")
        self._after_stop(joined)
        return self
