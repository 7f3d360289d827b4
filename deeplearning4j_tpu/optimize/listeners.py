"""Training listeners.

Parity surface: ``optimize/api/IterationListener.java`` / ``TrainingListener.java``
and ``optimize/listeners/*`` — ScoreIterationListener, PerformanceListener
(samples/sec, ``PerformanceListener.java:86``), CollectScoresIterationListener.
"""

from __future__ import annotations

import time


class IterationListener:
    def iteration_done(self, model, iteration):
        pass

    def on_epoch_end(self, model):
        pass


class ScoreIterationListener(IterationListener):
    """Print score every ``frequency`` iterations (ScoreIterationListener)."""

    def __init__(self, frequency=10, log_fn=print):
        self.frequency = max(1, frequency)
        self.log_fn = log_fn

    def iteration_done(self, model, iteration):
        if iteration % self.frequency == 0:
            self.log_fn(f"Score at iteration {iteration} is {model.score_}")


class PerformanceListener(IterationListener):
    """Throughput per iteration: samples/sec, batches/sec (PerformanceListener.java:57-87)."""

    def __init__(self, frequency=1, report_samples=True, log_fn=print):
        self.frequency = max(1, frequency)
        self.report_samples = report_samples
        self.log_fn = log_fn
        self._last_time = None
        self._last_iter = None
        self.last_samples_per_sec = None
        self.last_batches_per_sec = None

    def iteration_done(self, model, iteration):
        now = time.perf_counter()
        if self._last_time is not None and iteration % self.frequency == 0:
            dt = now - self._last_time
            iters = iteration - self._last_iter
            if dt > 0:
                self.last_batches_per_sec = iters / dt
                batch = getattr(model, "_last_batch_size", None)
                msg = f"iteration {iteration}: {self.last_batches_per_sec:.1f} batches/sec"
                if batch:
                    self.last_samples_per_sec = iters * batch / dt
                    msg += f", {self.last_samples_per_sec:.1f} samples/sec"
                self.log_fn(msg)
        self._last_time = now
        self._last_iter = iteration


class ProfilerListener(IterationListener):
    """XLA/PJRT profiler capture (SURVEY §5.1: the reference instruments
    every Spark phase + per-iteration timings; the TPU-native equivalent is a
    ``jax.profiler`` trace over a window of training iterations).

    Captures iterations [start_iteration, start_iteration + num_iterations)
    into ``log_dir``: one ``<log_dir>/plugins/profile/<time>/*.xplane.pb``
    (TensorBoard- and Perfetto-loadable) that holds, on one clock, the device
    ops under the names of the program's ``jax.named_scope``s and the host's
    ``obs.span``s (``dl4j:<name>``) on the line of their thread.
    ``python3 -m benchmark.scope_reduce <that file>`` prints device time by
    scope, the spans by thread and the device's idle gaps by span.

    The trace is taken without the Python tracer and at host tracer level 1,
    which keeps the annotations: the defaults make the file ten times the
    size and ``stop_trace`` minutes long (PERF.md section 6, PR 25).

    >>> net.set_listeners([ProfilerListener("/tmp/prof", start_iteration=10)])
    """

    def __init__(self, log_dir, start_iteration=5, num_iterations=10,
                 log_fn=print):
        self.log_dir = str(log_dir)
        self.start_iteration = start_iteration
        self.num_iterations = max(1, num_iterations)
        self.log_fn = log_fn
        self._active = False
        self.captured = False
        self.trace_dir = None

    def _sync(self, model):
        """Flush queued device work so the trace brackets real execution."""
        import jax
        # the device iteration counter is written by EVERY jitted step
        # (including tBPTT segments, where score_ lags the segment loop)
        for attr in ("_iter_dev", "_score", "params_list", "params_map"):
            out = getattr(model, attr, None)
            if out is not None and not isinstance(out, float):
                # graftlint: disable=G001 -- profiler window boundary: the sync IS the listener's job
                jax.block_until_ready(out)
                return

    def iteration_done(self, model, iteration):
        import jax
        if (not self._active and not self.captured
                and iteration >= self.start_iteration):
            self._sync(model)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=options)
            self._active = True
            self._stop_at = iteration + self.num_iterations
            return
        if self._active and iteration >= self._stop_at:
            self._finish(model, iteration)

    @staticmethod
    def _stop_trace_safely():
        """Stop the process-global jax trace, tolerating double-stop and
        stop-without-start: jax raises (RuntimeError on current releases,
        historically other types) when no trace is running, and a listener
        being torn down must treat that as "already stopped", never
        propagate it. Returns whether a running trace was actually
        stopped."""
        import jax
        try:
            jax.profiler.stop_trace()
            return True
        except Exception:
            # no-trace-running detection: jax's raise type is not stable
            # across versions, and close()/__del__ must be no-ops then
            return False

    def _finish(self, model, iteration):
        # flip _active FIRST: if the stop itself raises/no-ops (trace
        # already stopped elsewhere), a later close()/__del__ must not
        # try again — double-stop is a no-op by contract. The stop runs
        # in a finally so a _sync failure (device error mid-run) cannot
        # strand the process-global trace with _active already cleared.
        self._active = False
        try:
            if model is not None:
                self._sync(model)
        finally:
            stopped = self._stop_trace_safely()
        if not stopped:
            # a trace WAS started in this window, so a failed stop here is
            # either an external stop (benign) or a real export failure
            # (disk full): it must not raise, but it must not be silent
            self.log_fn(f"profiler capture to {self.log_dir} was NOT "
                        "finalized: jax.profiler.stop_trace() failed or the "
                        "trace was already stopped externally")
            return
        self.captured = True
        self.trace_dir = self.log_dir
        self.log_fn(f"profiler trace captured to {self.log_dir} "
                    f"(iterations {self.start_iteration}..{iteration})")

    def close(self, model=None):
        """Finalize a capture that training ended mid-window — the jax trace
        is process-global, so leaving it running blocks any later capture.
        Call after fit() when the run may be shorter than the window (a
        window spanning epochs completes on its own; epoch boundaries do
        NOT truncate it). Idempotent: double close and close-without-start
        are no-ops."""
        if self._active:
            self._finish(model, self._stop_at)

    def __del__(self):
        if getattr(self, "_active", False):
            self._active = False
            self._stop_trace_safely()


class CollectScoresIterationListener(IterationListener):
    """Accumulate (iteration, score) pairs (CollectScoresIterationListener)."""

    def __init__(self, frequency=1):
        self.frequency = max(1, frequency)
        self.scores = []

    def iteration_done(self, model, iteration):
        if iteration % self.frequency == 0:
            self.scores.append((iteration, model.score_))


class TimeIterationListener(IterationListener):
    """ETA logging (reference TimeIterationListener)."""

    def __init__(self, total_iterations, log_fn=print, frequency=50):
        self.total = total_iterations
        self.start = time.perf_counter()
        self.log_fn = log_fn
        self.frequency = max(1, frequency)

    def iteration_done(self, model, iteration):
        if iteration % self.frequency == 0 and iteration > 0:
            elapsed = time.perf_counter() - self.start
            remaining = elapsed / iteration * (self.total - iteration)
            self.log_fn(f"iteration {iteration}/{self.total}, ETA {remaining:.0f}s")
