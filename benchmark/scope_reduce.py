"""From a profiler trace (``.xplane.pb``) to device time by the program's
``jax.named_scope``s and host time by the program's ``obs.span``s: one file, one
clock. Beside ``trace_reduce.py`` (busy/idle, seconds per op, gaps by the
benchmark's own spans), which this file does not replace.

    python3 -m benchmark.scope_reduce <file.xplane.pb>

prints the three tables an operator wants of a trace taken with
``ProfilerListener``, ``jax.profiler.trace`` or ``benchmark/run.py --trace 1
--keep-trace``: device time a step by scope, the program's spans by thread, and
the device's idle gaps by the span open on the host.

What the file holds beyond what ``trace_reduce`` reads (one look at a real one,
``testdata/gpt2-tiny-v5e.xplane.pb.gz``):

* Every device event's *metadata* carries the JAX name stack of its HLO
  instruction as the stat ``tf_op``: ``jit(step)/transpose(jvp(block.ln1))/mul``.
  ``jax.profiler.ProfileData`` shows an event's own stats only, so the metadata
  is read here from the wire format of ``XSpace`` (planes[].event_metadata[]
  .stats[], planes[].stat_metadata[]); nothing but the standard library and
  JAX is imported. A fusion carries the stack of the instruction it was built
  around (its root, as a rule).
* ``obs.span(name)`` of the program is a ``TraceAnnotation`` named
  ``dl4j:<name>`` on the line of its thread in the plane ``/host:CPU``.

``reduce_events`` is the pure part, tested on hand-counted events:

instructions  per HLO instruction: its name stack, how often it ran and the
              mean device seconds of a run, over all chips. Sums of the means
              are device seconds PER EXECUTION of the program on a chip: a
              step cut by the edge of the trace costs nothing
spans         per (thread, span name): count and seconds
idle_gaps     every gap in a chip's busy union, laid to the innermost
              ``dl4j:`` span open on ANY host thread at the gap's middle
"""

from __future__ import annotations

import functools
import re
import sys

from benchmark import trace_reduce

SPAN_PREFIX = "dl4j:"
NO_SPAN = "no_program_span"
NAME_STACK_STAT = "tf_op"
KERNEL = 'custom_call_target="tpu_custom_call"'

# The scopes ``TransformerLM``'s step enters (``models/transformer.SCOPES``; a
# tier-1 test holds the two lists together). A copy, because the benchmark
# also runs on a program that has no such list.
LM_SCOPES = ("embed", "block", "ln1", "qkv", "attn", "proj", "ln2", "mlp",
             "final_ln", "logits_loss", "grad_clip", "optimizer")


# --- the wire format of XSpace, as far as the name stacks need it ------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """``(field number, value)`` of one message: an int for a varint, a
    ``(start, end)`` pair into ``buf`` for a length-delimited field; fixed-width
    fields are skipped."""
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield number, (i, i + n)
            i += n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """The ``value`` message of one ``map<int64, Message>`` entry."""
    for number, value in _fields(buf, *span):
        if number == 2:
            return value
    return None


def name_stacks(path):
    """``{plane name: {event name: name stack}}`` of every plane of the file
    whose event metadata carries the stat ``tf_op``."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for number, plane in _fields(buf, 0, len(buf)):
        if number != 1:                       # XSpace.planes
            continue
        plane_name, events, stat_names = "", [], {}
        for number, value in _fields(buf, *plane):
            if number == 2:                   # XPlane.name
                plane_name = _text(buf, value)
            elif number == 4:                 # XPlane.event_metadata
                events.append(_map_value(buf, value))
            elif number == 5:                 # XPlane.stat_metadata
                meta = dict(_fields(buf, *_map_value(buf, value)))
                if 2 in meta:                 # XStatMetadata.id, .name
                    stat_names[meta.get(1, 0)] = _text(buf, meta[2])
        wanted = {k for k, v in stat_names.items() if v == NAME_STACK_STAT}
        stacks = {}
        for event in events if wanted else ():
            name, stack = None, None
            for number, value in _fields(buf, *event):
                if number == 2:               # XEventMetadata.name
                    name = _text(buf, value)
                elif number == 5:             # XEventMetadata.stats
                    stat = dict(_fields(buf, *value))
                    # XStat.metadata_id, .str_value
                    if stat.get(1) in wanted and 5 in stat:
                        stack = _text(buf, stat[5])
            if name is not None and stack:
                # the stat is "<name stack>:<op type>", the type often empty
                stacks.setdefault(name, stack.rpartition(":")[0] or stack)
        if stacks:
            out[plane_name] = stacks
    return out


# --- events, through JAX -----------------------------------------------------

def load(path):
    """``(device_ops, host_spans)`` of one ``.xplane.pb``: ``device_ops`` as
    ``trace_reduce.load`` gives them, ``{chip: [(name, start_ns, dur_ns)]}``;
    ``host_spans`` the program's, ``[(thread, name, start_ns, dur_ns)]``, the
    ``#key=value#`` suffix an annotation's arguments make stripped."""
    from jax.profiler import ProfileData
    device_ops, _ = trace_reduce.load(path)
    host_spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        seen = {}
        for line in plane.lines:
            # a line is a thread, "<name>/<tid>" on the chip's host; where the
            # profiler gives the bare name, a second thread of it is "<name>#2"
            seen[line.name] = seen.get(line.name, 0) + 1
            thread = line.name if seen[line.name] == 1 \
                else f"{line.name}#{seen[line.name]}"
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    name = e.name[len(SPAN_PREFIX):].split("#", 1)[0]
                    host_spans.append((thread, name, float(e.start_ns),
                                       float(e.duration_ns)))
    return device_ops, host_spans


# --- the reduction -----------------------------------------------------------

def tokens(stack):
    """A name stack's tokens: ``jit(step)/transpose(jvp(block.ln1))/mul`` ->
    ``{jit, step, transpose, jvp, block, ln1, mul}``."""
    return {t for t in re.split(r"[/().]", stack) if t}


def scope_path(stack):
    """The scopes of a name stack as one path, for the table: the function
    names of ``jit(...)`` dropped, the transforms unwrapped, the primitive (the
    last component) left out: ``jit(step)/jvp(block.mlp)/jit(gelu)/tanh`` ->
    ``block.mlp``."""
    head = stack.rpartition("/")[0]
    head = re.sub(r"p?jit\([^()]*\)", "", head)
    head = re.sub(r"[\w.<>-]+\(|\)", "", head)
    return "/".join(p for p in head.split("/") if p)


def reduce_events(device_ops, stacks, host_spans):
    """``device_ops``: ``{chip: [(name, start_ns, dur_ns)]}``; ``stacks``:
    ``{name: name stack}``; ``host_spans``: ``[(thread, name, start_ns,
    dur_ns)]``. Seconds throughout; see the module docstring. Busy union, sums
    per instruction and gap attribution are ``trace_reduce``'s, handed the
    program's spans in the benchmark's place."""
    base = trace_reduce.reduce_events(
        device_ops, [(name, s, d) for _, name, s, d in host_spans])
    if base is None:
        return None
    instructions = {
        name: {"stack": stacks.get(name, ""), "count": base["op_counts"][name],
               "mean_s": seconds / base["op_counts"][name]}
        for name, seconds in base["ops"].items()}
    gaps = {NO_SPAN if name == trace_reduce.NO_SPAN else name: seconds
            for name, seconds in base["idle_gaps_by_span"].items()}
    spans = {}
    for thread, name, _, d in host_spans:
        row = spans.setdefault((thread, name), {"count": 0, "seconds": 0.0})
        row["count"] += 1
        row["seconds"] += d / 1e9
    return {"chips": base["chips"], "instructions": instructions,
            "spans": spans, "idle_gaps_by_span": gaps}


@functools.lru_cache(maxsize=2)
def reduce_file(path):
    """The reduction of one ``.xplane.pb`` (kept: every reader of one traced
    run asks for the same file), or None where no chip's operations are in
    it."""
    device_ops, host_spans = load(path)
    stacks = {}
    for plane, found in name_stacks(path).items():
        if plane.startswith(trace_reduce.DEVICE_PLANE):
            # the chips of one program run the same instructions
            stacks.update(found)
    return reduce_events(device_ops, stacks, host_spans)


def of(ctx):
    """The reduction of the trace a per-layer reader was handed, or None."""
    trace = ctx.get("trace")
    return reduce_file(trace["path"]) if trace else None


# --- what the per-layer metrics read -----------------------------------------

def is_kernel(name):
    """A Pallas kernel: the trace names an event by its HLO instruction, and
    Mosaic's is this custom call."""
    return KERNEL in name


def device_ms(reduced, scopes=None, kernels=None):
    """Device milliseconds per execution of the program in the instructions
    whose name stack holds one of ``scopes`` as a token (every instruction
    where ``scopes`` is None); ``kernels`` True or False keeps only the Pallas
    kernels or only the rest. None where nothing matched."""
    if reduced is None:
        return None
    want = None if scopes is None else set(scopes)
    found = [row["mean_s"] for name, row in reduced["instructions"].items()
             if (want is None or want & tokens(row["stack"]))
             and (kernels is None or kernels == is_kernel(name))]
    return 1e3 * sum(found) if found else None


def unscoped_share(reduced, vocabulary=LM_SCOPES):
    """Percent of the device time of one execution whose name stack holds no
    scope of ``vocabulary``."""
    total = device_ms(reduced)
    if not total:
        return None
    return 100.0 * (1.0 - (device_ms(reduced, vocabulary) or 0.0) / total)


def span_ms_per(reduced, name, per):
    """Host milliseconds inside the spans ``name``, over all threads, for each
    span ``per`` in the trace. None where either is missing."""
    if reduced is None:
        return None
    seconds = sum(v["seconds"] for (_, n), v in reduced["spans"].items()
                  if n == name)
    times = sum(v["count"] for (_, n), v in reduced["spans"].items()
                if n == per)
    return 1e3 * seconds / times if seconds and times else None


def by_path(reduced):
    """``{row: device ms per execution}``, every instruction in exactly one
    row: its scope path, ``<bwd>`` where the stack holds ``transpose``,
    ``<kernel result-type>`` for a Pallas kernel. The rows sum to
    ``device_ms(reduced)``."""
    rows = {}
    for name, row in reduced["instructions"].items():
        key = scope_path(row["stack"]) or "(no scope)"
        if "transpose" in tokens(row["stack"]):
            key += " <bwd>"
        if is_kernel(name):   # told apart by what they return: dQ from dK, dV
            result = trace_reduce.label(name).partition(" custom-call ")[2]
            key += f" <kernel {result}>"
        rows[key] = rows.get(key, 0.0) + 1e3 * row["mean_s"]
    return rows


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    reduced = reduce_file(argv[0])
    if reduced is None:
        print("no device operations in this trace", file=sys.stderr)
        return 1
    total = device_ms(reduced)
    print(f"device ms per execution by scope ({reduced['chips']} chip(s), "
          f"{total:.3f} ms in all)")
    for key, ms in sorted(by_path(reduced).items(), key=lambda kv: -kv[1]):
        print(f"  {ms:10.3f}  {100 * ms / total:5.1f} %  {key}")
    print("program spans (thread, span: count, ms in all, ms each)")
    for (thread, name), v in sorted(reduced["spans"].items()):
        print(f"  {thread}  {name}: {v['count']}  {1e3 * v['seconds']:.3f}  "
              f"{1e3 * v['seconds'] / v['count']:.3f}")
    print("device idle ms by the innermost program span open on the host")
    for name, s in sorted(reduced["idle_gaps_by_span"].items(),
                          key=lambda kv: -kv[1]):
        print(f"  {1e3 * s:10.3f}  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
