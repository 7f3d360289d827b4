#!/usr/bin/env python3
"""One process, one cell: load, warm the cell's own shapes, measure for
``--seconds``, check the outputs against the plain reference, print one line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one kind of job
or one per-layer metric is a file of its own, found by name (README.md):

    BENCHMARK.json                      cells, metrics, which metric in which cell
    benchmark/configs/<config>.json     sizes as run (+ ``family``)
    benchmark/traffic/<mix>.json        ``driver`` + the mix's parameters
    benchmark/workloads/<cell>.json     the cell's ``limits`` for ``correct``
    benchmark/drivers/<driver>.py       builds the job, runs the window
    benchmark/references/<family>.py    the plain float32 reference
    benchmark/layer_metrics/<name>.py   one per-layer metric's reader

A real cell refuses to run without a TPU. ``--rehearse`` is the explicit lane
for the tiny twins under ``benchmark/rehearsal/``: it runs wherever JAX runs
(here: the CPU) and its last line names that device, so it can never be read as
a chip result. It is never an entry of BENCHMARK.json.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()   # set-up is counted from here

import argparse
import importlib
import json
import os
import shutil
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACE_START_S = 2.0    # into the window, past the first steps' ramp
TRACE_LENGTH_S = 2.0   # traces are large (170 MB for 3 s of ResNet-50 steps)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


class Cell:
    """A cell's files, resolved by name."""

    def __init__(self, name, rehearse):
        self.name = name
        bench = load_json(ROOT, "BENCHMARK.json")
        base = os.path.join(HERE, "rehearsal") if rehearse else HERE
        own = load_json(base, "workloads", name + ".json")
        if rehearse:
            entry = own
            metrics_of = entry["twin_of"]
            self.chips = 1
            config_file = os.path.join(base, "configs",
                                       entry["config"] + ".json")
        else:
            found = [w for w in bench["workloads"] if w["name"] == name]
            if not found:
                raise SystemExit(f"no cell {name!r} in BENCHMARK.json")
            entry, metrics_of = found[0], name
            self.chips = entry["chips"]
            for key in ("config", "traffic"):
                if own.get(key, entry[key]) != entry[key]:
                    raise SystemExit(
                        f"benchmark/workloads/{name}.json names another {key} "
                        "than BENCHMARK.json")
            conf = [c for c in bench["configs"]
                    if c["name"] == entry["config"]][0]
            config_file = os.path.join(ROOT, conf["file"])
        self.config = load_json(config_file)
        self.traffic = load_json(base, "traffic", entry["traffic"] + ".json")
        self.limits = own["limits"]

        def listed(metric):
            return "workloads" not in metric or metrics_of in metric["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if listed(m)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in bench["per_layer"]
                          if listed(m) and m["moves"] in e2e]


class Spans:
    """The benchmark's own host spans: kept in memory on the host clock, and
    written into the profiler's trace as ``TraceAnnotation``s so that an idle
    gap of the device can be laid to what the host was doing."""

    def __init__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation
        self.events = []          # (name, start, seconds)
        self.window_start = None
        self._lock = threading.Lock()

    def span(self, name):
        return _Span(self, name)

    def total(self, name, since=None):
        with self._lock:
            return sum(d for n, t, d in self.events
                       if n == name and (since is None or t >= since))


class _Span:
    def __init__(self, spans, name):
        self.spans, self.name = spans, name
        self.ann = spans._annotation("bench:" + name)

    def __enter__(self):
        self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self.t0
        self.ann.__exit__(*exc)
        with self.spans._lock:
            self.spans.events.append((self.name, self.t0, dt))
        return False


class TraceWindow:
    """Traces ``TRACE_LENGTH_S`` seconds of the window from a thread of its own,
    so that a window which is one call of the program (``fit(iterator)``) is
    traced like one that is a loop here."""

    def __init__(self, directory, start_s, length_s):
        self.directory, self.start_s, self.length_s = (directory, start_s,
                                                       length_s)
        self.error = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="bench-trace")

    def _run(self):
        import jax
        try:
            if self._stop.wait(self.start_s):
                return
            # device ops and TraceAnnotations (level 1) only: the Python tracer
            # and the host's level-2 events (half a million futex waits a
            # thread) make a trace ten times the size and stopping it minutes
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(self.directory, profiler_options=options)
            self._stop.wait(self.length_s)
            jax.profiler.stop_trace()
        except Exception as e:   # noqa: BLE001 -- reported by the main thread
            self.error = e

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=180)
        if self._thread.is_alive():
            raise RuntimeError("the trace thread did not stop in 180 s")
        if self.error is not None and exc[0] is None:
            raise self.error
        return False


def device_facts(jax, devices):
    """The device as JAX reports it. ``memory_peak_bytes``: the allocator's
    ``peak_bytes_in_use`` counts live arrays only; what the loaded programs
    hold for their temporaries is ``peak_bytes_reserved``, carved out of the
    same HBM (free = limit - in use - reserved, to the byte; PERF.md section 6).
    The peak on a chip is their sum; the fullest chip's is reported."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def setup_cache(jax):
    """The persistent compile cache: where ``JAX_COMPILATION_CACHE_DIR`` says,
    else the fixed ``<checkout>/.jax_cache`` (the program, imported later, then
    finds a directory set and keeps it)."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def per_layer_values(cell, ctx):
    """Each of the cell's per-layer metrics from its own reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    values = {}
    for m in cell.per_layer:
        reader = importlib.import_module(
            f"benchmark.layer_metrics.{m['name']}")
        v = reader.read(ctx)
        if v is not None:
            values[m["name"]] = v
    return values


def measure(cell, seed, seconds, trace, devices, keep_trace=None):
    """Everything after the look for a chip: build, first steps, warm, window,
    the reference and the comparison. Returns the result line as a dict."""
    import jax

    from benchmark import compile_count, trace_reduce

    spans = Spans()
    driver = importlib.import_module(
        f"benchmark.drivers.{cell.traffic['driver']}")
    with compile_count.CompileCounter() as setup_compiles:
        job = driver.Job(cell.config, cell.traffic, seed, spans)
        program = job.first_steps()
        job.warm()
    setup_s = time.perf_counter() - _T0

    trace_dir = os.path.join(ROOT, ".bench_trace", f"{cell.name}.{os.getpid()}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    spans.window_start = time.perf_counter()
    try:
        with compile_count.CompileCounter() as in_window:
            if trace:
                with TraceWindow(trace_dir, min(TRACE_START_S, seconds / 4),
                                 min(TRACE_LENGTH_S, seconds / 2)):
                    window = job.window(seconds)
            else:
                window = job.window(seconds)
        device = device_facts(jax, devices)
        memory_stats = devices[0].memory_stats() or {}
        ctx = {"trace": None, "spans": spans, "work": job.work(),
               "steps": window["steps"],
               "counters": job.counters(),
               "window_seconds": window["seconds"], "chips": cell.chips,
               "dispatch_seconds": job.dispatch_seconds(),
               "compiles_in_window": in_window.count,
               "peaks": load_json(HERE, "peaks.json").get(device["kind"])}
        batches = job.check_batches()
        job.free()
        del job

        result = {"attempted": window["attempted"],
                  "failed": window["failed"]}
        if trace:
            ctx["trace"] = reduced = trace_reduce.reduce_dir(trace_dir)
            if reduced is not None:
                device["busy_s"] = reduced["busy_s"]
                device["window_s"] = reduced["window_s"]
                result["breakdown"] = {"device_ops": reduced["top_ops"],
                                       "idle_gaps": reduced["idle_gaps"]}
                if keep_trace:
                    os.makedirs(os.path.dirname(keep_trace) or ".",
                                exist_ok=True)
                    shutil.copy(reduced["path"], keep_trace)
            values = per_layer_values(cell, ctx)
        else:
            values = dict(window["end_to_end"], setup_s=setup_s)
            values = {m["name"]: values[m["name"]] for m in cell.end_to_end}
            # what the readers find without a trace, for the reader of a log
            host_side = per_layer_values(cell, ctx)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in values.items()}
    result["device"] = device

    # the plain reference: after the window closed, the peak was read and the
    # program's state was freed; its time is in no metric
    t_ref = time.perf_counter()
    correct, compared = driver.verify(cell.config, seed, program, batches,
                                      cell.limits)
    # for the reader of a log, not for the driver
    result["run"] = {"steps": window["steps"], "seconds": window["seconds"],
                     "setup_s": setup_s,
                     "reference_s": time.perf_counter() - t_ref,
                     "compiles_in_window": in_window.count,
                     "compiles_in_setup": setup_compiles.count,
                     "compile_s_in_setup": setup_compiles.seconds,
                     "memory_stats": {k: v for k, v in memory_stats.items()
                                      if "bytes" in k}}
    if not trace:
        result["run"]["per_layer_untraced"] = host_side
    return {"correct": bool(correct and result["failed"] == 0), **result,
            "compared": compared}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the tiny twin under benchmark/rehearsal/ on "
                         "whatever device JAX has (the CPU lane)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb to this path")
    args = ap.parse_args(argv)

    cell = Cell(args.workload, args.rehearse)
    import jax
    setup_cache(jax)
    devices = jax.local_devices()
    if not args.rehearse:
        if devices[0].platform != "tpu" or len(devices) < cell.chips:
            print(f"benchmark: cell {cell.name!r} needs {cell.chips} TPU "
                  f"chip(s); JAX has {len(devices)} x {devices[0].platform}. "
                  "No CPU fallback (the rehearsal lane is --rehearse).",
                  file=sys.stderr)
            return 3
    devices = devices[:cell.chips]
    out = measure(cell, args.seed, args.seconds, bool(args.trace), devices,
                  args.keep_trace)
    lines = [f"{k} = {v['value']!r} (limit {v['limit']!r})"
             + (f" at {v['leaf']}" if "leaf" in v else "")
             for k, v in out["compared"].items()]
    print("compared:\n  " + "\n  ".join(lines), file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
