"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer metrics
read. Nothing but JAX reads the file (``jax.profiler.ProfileData``).

What a trace of a TPU run holds (one look at a real one, PERF.md section 6):
one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event
per operation the chip ran (start and duration on the profiler's clock), and
one host plane, ``/host:CPU``, with a line per thread, on which
``jax.profiler.TraceAnnotation`` spans appear under their own names. The
benchmark's spans are named ``bench:<name>`` so they cannot be mistaken.

``reduce_events`` is the pure part, tested on hand-counted intervals:

busy_s      union of the device-op intervals, averaged over the chips
window_s    the traced window: first start to last end over device ops and
            the benchmark's host spans
ops         seconds per op name, summed over the chips
idle_gaps   every gap in a chip's busy union, laid to the innermost benchmark
            span open on the host at the gap's middle, summed per span name
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
NO_SPAN = "no_benchmark_span"
TOP = 10


def union(intervals):
    """Merged, sorted ``[start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def reduce_events(device_ops, host_spans):
    """``device_ops``: ``{chip: [(name, start_ns, dur_ns), ...]}``;
    ``host_spans``: ``[(name, start_ns, dur_ns), ...]``. Returns the reduction
    described in the module docstring, seconds throughout."""
    chips = [c for c, evs in device_ops.items() if evs]
    if not chips:
        return None
    starts = [s for c in chips for _, s, _ in device_ops[c]]
    ends = [s + d for c in chips for _, s, d in device_ops[c]]
    starts += [s for _, s, _ in host_spans]
    ends += [s + d for _, s, d in host_spans]
    w0, w1 = min(starts), max(ends)
    busy_ns, ops, counts, gaps = 0, {}, {}, {}
    for c in chips:
        merged = union([(s, s + d) for _, s, d in device_ops[c]])
        busy_ns += sum(e - s for s, e in merged)
        for name, _, d in device_ops[c]:
            ops[name] = ops.get(name, 0) + d
            counts[name] = counts.get(name, 0) + 1
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = (g0 + g1) / 2
            # the innermost (shortest) benchmark span open at the gap's middle
            open_then = [(d, name) for name, s, d in host_spans
                         if s <= mid < s + d]
            best = min(open_then)[1] if open_then else NO_SPAN
            gaps[best] = gaps.get(best, 0) + (g1 - g0)
    n = len(chips)
    return {
        "busy_s": busy_ns / n / 1e9,
        "window_s": (w1 - w0) / 1e9,
        "chips": n,
        "ops": {k: v / 1e9 for k, v in ops.items()},
        "op_counts": counts,
        "idle_gaps_by_span": {k: v / n / 1e9 for k, v in gaps.items()},
    }


def load(path):
    """``(device_ops, host_spans)`` of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_ops, host_spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE):
            tail = plane.name[len(DEVICE_PLANE):]
            if not tail.isdigit():
                continue   # e.g. a sparse-core plane of the same chip
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_ops[int(tail)] = [
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans.append((e.name[len(SPAN_PREFIX):],
                                           float(e.start_ns),
                                           float(e.duration_ns)))
    return device_ops, host_spans


def label(name):
    """A device event is named by its whole HLO instruction,
    ``%fusion.12 = bf16[8,1024]{1,0:T(8,128)} fusion(...), kind=...``. Its
    label keeps what tells ops apart and drops what tells instances apart:
    ``fusion fusion bf16[8,1024]`` (name stem, opcode, result type without
    layouts), so the 24 unrolled layers' copies of one op sum into one row."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:160]
    stem = re.sub(r"[.\d]+$", "", lhs.lstrip("%"))
    depth, end = 0, len(rhs)
    for i, ch in enumerate(rhs):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            end = i
            break
    result = re.sub(r"\{[^}]*\}", "", rhs[:end])
    opcode = rhs[end + 1:].split("(")[0]
    return f"{stem} {opcode} {result}"[:160]


def _top(d, key=lambda k: k):
    summed = {}
    for k, v in d.items():
        summed[key(k)] = summed.get(key(k), 0) + v
    return [[k, v] for k, v in
            sorted(summed.items(), key=lambda kv: -kv[1])[:TOP]]


def reduce_file(path):
    out = reduce_events(*load(path))
    if out is None:
        return None
    out["path"] = path
    out["top_ops"] = _top(out["ops"], label)
    out["idle_gaps"] = _top(out["idle_gaps_by_span"])
    return out


def reduce_dir(directory):
    """The reduction of the one trace under a profiler output directory, or
    None where no chip's operations are in it (a CPU run)."""
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return reduce_file(found[-1]) if found else None


def kernel_time(reduced, match):
    """``(seconds, runs)`` of the ops whose event name ``match`` accepts:
    their summed device seconds, and how many times the matched set ran as a
    whole -- executions over distinct instructions, since every unrolled layer
    has an instruction of its own that runs once a step."""
    names = [k for k in reduced["ops"] if match(k)]
    if not names:
        return 0.0, 0.0
    seconds = sum(reduced["ops"][k] for k in names)
    runs = sum(reduced["op_counts"][k] for k in names) / len(names)
    return seconds, runs


def roofline_share(reduced, match, work, peaks):
    """Least time the chip could take for ``work`` (``flops`` and/or ``bytes``
    of ONE run of the matched set: the larger of FLOPs over peak FLOP/s and
    bytes over peak bytes/s), times the runs traced, over the device seconds
    the matched ops took; in percent. None where nothing matched."""
    if reduced is None or not peaks:
        return None
    seconds, runs = kernel_time(reduced, match)
    if not seconds:
        return None
    least = max(work.get("flops", 0) / peaks["bf16_flops_per_s"],
                work.get("bytes", 0) / peaks["hbm_bytes_per_s"])
    return 100.0 * least * runs / seconds
