"""The reduction from a trace to device time by ``jax.named_scope`` and host
time by ``obs.span``: the wire-format reader and the sums on events counted by
hand (a synthetic trace written as an XSpace text proto), and on the two
traces recorded on the chip (``benchmark/testdata``): the rehearsal twin
before the program entered any scope (PR 25) and with the scopes (PR 26)."""

import gzip
import importlib
import os

import pytest

from benchmark import scope_reduce, trace_reduce

MS = 1e-6   # nanoseconds -> milliseconds
TESTDATA = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata")
NEW_READERS = ("h2d_ms", "step_call_ms", "optimizer_ms", "logits_loss_ms",
               "layernorm_ms", "attn_layout_ms", "unscoped_share")

# chip 0, XLA Ops (ns): A [1000,3000) [5000,7000) [9000,14000): 3 runs, mean
#   3000; K (a Pallas kernel) [3000,4000): 1 run; N (no name stack) [7000,7500)
#   busy union [1000,4000) [5000,7500) [9000,14000) -> gaps [0,1000)
#   [4000,5000) [7500,9000) [14000,20000)
# chip 1: A [2000,3000): 1 run, mean 1000
# host: thread main: dl4j:fit.step [0,20000) and, with arguments,
#   dl4j:lm.h2d#steps=4# [4200,4800); thread worker: dl4j:prefetch.pull
#   [7600,8900) twice over (a second span [15000,16000)); bench:outer ignored
KERNEL_NAME = ('%jvp_block.attn_.2 = bf16[4] custom-call(bf16[4] %x), '
               'custom_call_target=\\"tpu_custom_call\\"')
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 4000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 6000000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 8000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1 name: "A"
    stats { metadata_id: 7 str_value: "jit(step)/transpose(jvp(block.ln1))/mul:" }
    stats { metadata_id: 8 uint64_value: 12 } } }
  event_metadata { key: 2 value { id: 2 name: "%s"
    stats { metadata_id: 7 str_value: "jit(step)/jvp(block.attn)/pallas_call:" } } }
  event_metadata { key: 3 value { id: 3 name: "N" } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
  stat_metadata { key: 8 value { id: 8 name: "flops" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 2000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "A"
    stats { metadata_id: 7 str_value: "jit(step)/transpose(jvp(block.ln1))/mul:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } } }
planes { id: 4 name: "/host:CPU"
  lines { id: 7 name: "main/7" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 2 offset_ps: 4200000 duration_ps: 600000 }
    events { metadata_id: 4 offset_ps: 0 duration_ps: 20000000 } }
  lines { id: 8 name: "worker/8" timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 7600000 duration_ps: 1300000 }
    events { metadata_id: 3 offset_ps: 15000000 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "dl4j:fit.step" } }
  event_metadata { key: 2 value { id: 2 name: "dl4j:lm.h2d#steps=4#" } }
  event_metadata { key: 3 value { id: 3 name: "dl4j:prefetch.pull" } }
  event_metadata { key: 4 value { id: 4 name: "bench:outer" } } }
""" % KERNEL_NAME
KERNEL = KERNEL_NAME.replace('\\"', '"')


@pytest.fixture(scope="module")
def synthetic_path(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return str(path)


@pytest.fixture(scope="module")
def synthetic(synthetic_path):
    return scope_reduce.reduce_file(synthetic_path)


def test_name_stacks_are_read_from_the_event_metadata(synthetic_path):
    """The stat ``tf_op`` among an event metadata's stats; its ``:<op type>``
    tail is dropped; planes without the stat are left out."""
    assert scope_reduce.name_stacks(synthetic_path) == {
        "/device:TPU:0": {
            "A": "jit(step)/transpose(jvp(block.ln1))/mul",
            KERNEL: "jit(step)/jvp(block.attn)/pallas_call"},
        "/device:TPU:1": {"A": "jit(step)/transpose(jvp(block.ln1))/mul"}}


def test_tokens_and_scope_path():
    stack = "jit(step)/transpose(jvp(block.ln1))/mul"
    assert scope_reduce.tokens(stack) == {"jit", "step", "transpose", "jvp",
                                          "block", "ln1", "mul"}
    assert scope_reduce.scope_path(stack) == "block.ln1"
    assert scope_reduce.scope_path(
        "jit(step)/jvp(logits_loss)/jit(log_softmax)/reduce_max") == \
        "logits_loss"
    assert scope_reduce.scope_path("jit(step)/jvp()/pallas_call") == ""
    assert scope_reduce.scope_path(
        "jit(step)/jvp(ConvolutionLayer)/conv_general_dilated") == \
        "ConvolutionLayer"
    assert scope_reduce.scope_path("jit(step)/optimizer/add") == "optimizer"


def test_device_time_is_per_execution_of_an_instruction(synthetic):
    rows = synthetic["instructions"]
    assert synthetic["chips"] == 2
    # A ran 3 times on chip 0 (2000, 2000, 5000) and once on chip 1 (1000)
    assert rows["A"]["count"] == 4
    assert rows["A"]["mean_s"] == pytest.approx(10000 / 4 * 1e-9)
    assert rows[KERNEL]["mean_s"] == pytest.approx(1000e-9)
    assert rows["N"]["stack"] == ""
    total = (2500 + 1000 + 500) * MS
    assert scope_reduce.device_ms(synthetic) == pytest.approx(total)
    assert scope_reduce.device_ms(synthetic, {"ln1"}) == pytest.approx(
        2500 * MS)
    assert scope_reduce.device_ms(synthetic, {"attn"}) == pytest.approx(
        1000 * MS)
    assert scope_reduce.device_ms(synthetic, {"attn"}, kernels=False) is None
    assert scope_reduce.device_ms(synthetic, {"block"}, kernels=True) == \
        pytest.approx(1000 * MS)
    assert scope_reduce.device_ms(synthetic, {"final_ln"}) is None
    assert scope_reduce.device_ms(None, {"ln1"}) is None
    # N has no stack: 500 of 4000
    assert scope_reduce.unscoped_share(synthetic) == pytest.approx(12.5)
    assert sum(scope_reduce.by_path(synthetic).values()) == pytest.approx(
        total)
    assert set(scope_reduce.by_path(synthetic)) == {
        "block.ln1 <bwd>", "block.attn <kernel bf16[4]>", "(no scope)"}


def test_spans_are_kept_by_thread_with_their_arguments_stripped(synthetic):
    assert synthetic["spans"] == {
        ("main/7", "fit.step"): {"count": 1,
                                 "seconds": pytest.approx(20000e-9)},
        ("main/7", "lm.h2d"): {"count": 1, "seconds": pytest.approx(600e-9)},
        ("worker/8", "prefetch.pull"): {"count": 2,
                                        "seconds": pytest.approx(2300e-9)}}
    assert scope_reduce.span_ms_per(synthetic, "prefetch.pull",
                                    "lm.h2d") == pytest.approx(2300 * MS)
    assert scope_reduce.span_ms_per(synthetic, "lm.h2d",
                                    "prefetch.pull") == pytest.approx(300 * MS)
    assert scope_reduce.span_ms_per(synthetic, "lm.step_call",
                                    "lm.step_call") is None
    assert scope_reduce.span_ms_per(None, "lm.h2d", "lm.h2d") is None


def test_gaps_go_to_the_innermost_span_open_on_any_thread(synthetic):
    # chip 0: [0,1000) fit.step; [4000,5000): middle 4500 lies in lm.h2d;
    # [7500,9000): middle 8250 lies in the WORKER's prefetch.pull;
    # [14000,20000): middle 17000, fit.step -> fit.step 7000, lm.h2d 1000,
    # prefetch.pull 1500. chip 1: [0,2000) and [3000,20000) (middle 11500):
    # fit.step 19000. Mean of the two chips.
    gaps = synthetic["idle_gaps_by_span"]
    assert gaps == pytest.approx({"fit.step": (7000 + 19000) / 2 * 1e-9,
                                  "lm.h2d": 1000 / 2 * 1e-9,
                                  "prefetch.pull": 1500 / 2 * 1e-9})


def test_a_gap_under_no_program_span_is_named_so():
    got = scope_reduce.reduce_events(
        {0: [("A", 0, 10), ("A", 30, 10)]}, {}, [])
    assert got["idle_gaps_by_span"] == pytest.approx(
        {scope_reduce.NO_SPAN: 20e-9})
    assert scope_reduce.reduce_events({}, {}, [("t", "fit", 0, 10)]) is None


def test_the_operators_tool_prints_three_tables(synthetic_path, capsys):
    assert scope_reduce.main([synthetic_path]) == 0
    out = capsys.readouterr().out
    assert "device ms per execution by scope (2 chip(s)" in out
    assert "block.ln1 <bwd>" in out and "(no scope)" in out
    assert "main/7  lm.h2d: 1" in out and "worker/8  prefetch.pull: 2" in out
    assert "device idle ms by the innermost program span" in out
    assert scope_reduce.main([]) == 2


# --- the traces recorded on the chip -----------------------------------------

def unpacked(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name
    with gzip.open(os.path.join(TESTDATA, name + ".gz")) as f:
        path.write_bytes(f.read())
    return str(path)


def ctx_of(path):
    """What ``run.py`` hands a per-layer reader of a traced run, as far as the
    new readers and the flash readers look."""
    return {"trace": trace_reduce.reduce_file(path)}


@pytest.fixture(scope="module")
def unscoped(tmp_path_factory):
    # PR 25's trace: see test_trace_reduce.py for how it was recorded
    return unpacked(tmp_path_factory, "gpt2-tiny-v5e.xplane.pb")


def test_unscoped_trace_has_name_stacks_and_no_scope(unscoped):
    reduced = scope_reduce.reduce_file(unscoped)
    fwd = [row for name, row in reduced["instructions"].items()
           if name.startswith("%jvp__.2 = ")]
    assert [row["stack"] for row in fwd] == ["jit(step)/jvp()/pallas_call"]
    assert fwd[0]["count"] == 34
    assert scope_reduce.unscoped_share(reduced) == 100.0
    assert not reduced["spans"]
    assert set(reduced["idle_gaps_by_span"]) == {scope_reduce.NO_SPAN}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_on_a_program_without_scopes_or_spans(unscoped, metric):
    """The parent of PR 26 enters no scope and writes no span: a reader finds
    nothing and says so; only the guard reads, and reads 100."""
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    assert reader.read({"trace": None}) is None
    expected = 100.0 if metric == "unscoped_share" else None
    assert reader.read(ctx_of(unscoped)) == expected


# benchmark/testdata/gpt2-tiny-v5e-scoped.xplane.pb.gz: the same twin, command
# and seed as PR 25's trace (test_trace_reduce.py), on one TPU v5e with PR 26's
# program: 20,358 device events in 39 executions of the step, 37 `dl4j:lm.h2d`
# and 37 `dl4j:lm.step_call` spans on the one thread that trains. A later PR
# that moves a scope or a span fails here before a metric turns silent there.

@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    return unpacked(tmp_path_factory, "gpt2-tiny-v5e-scoped.xplane.pb")


def test_scoped_trace_still_feeds_the_accepted_flash_readers(scoped):
    from benchmark.layer_metrics import flash_bwd_roofline, flash_fwd_roofline
    reduced = trace_reduce.reduce_file(scoped)
    fwd = [k for k in reduced["ops"] if flash_fwd_roofline.is_flash_fwd(k)]
    bwd = [k for k in reduced["ops"] if flash_bwd_roofline.is_flash_bwd(k)]
    assert (len(fwd), len(bwd)) == (2, 4)       # 2 layers: fwd; dQ and dK, dV
    assert all(k.startswith("%jvp_block.attn_.") for k in fwd)
    assert all(k.startswith("%transpose_jvp_block.attn__.") for k in bwd)
    assert trace_reduce.kernel_time(
        reduced, flash_fwd_roofline.is_flash_fwd) == (
            pytest.approx(864.691e-6), 39.0)
    assert trace_reduce.kernel_time(
        reduced, flash_bwd_roofline.is_flash_bwd) == (
            pytest.approx(1098.718e-6), 39.0)
    # the ledger's breakdown: one row for both layers' copies of a kernel
    assert reduced["top_ops"][0][0] == \
        "jvp_block.attn_ custom-call (bf16[8,64,8], f32[8,64,128])"
    stacks = scope_reduce.reduce_file(scoped)["instructions"]
    assert {stacks[k]["stack"] for k in fwd} == {
        "jit(step)/jvp(block.attn)/pallas_call"}
    assert {stacks[k]["stack"] for k in bwd} == {
        "jit(step)/transpose(jvp(block.attn))/pallas_call"}


@pytest.mark.parametrize("metric", NEW_READERS)
def test_every_new_reader_reads_a_number_on_the_scoped_trace(scoped, metric):
    reader = importlib.import_module(f"benchmark.layer_metrics.{metric}")
    value = reader.read(ctx_of(scoped))
    assert isinstance(value, float) and value > 0
    if metric == "unscoped_share":
        # a toy step is mostly compiler-made copies of its 38 small leaves
        assert value == pytest.approx(17.254, abs=1e-3)


def test_scoped_trace_scope_sums_equal_the_busy_time(scoped):
    reduced = scope_reduce.reduce_file(scoped)
    busy = trace_reduce.reduce_file(scoped)["busy_s"]
    rows = scope_reduce.by_path(reduced)
    total = scope_reduce.device_ms(reduced)
    assert sum(rows.values()) == pytest.approx(total)
    # mean x count of every instruction is the busy time to the nanosecond
    # (no two ops overlap on the one core) ...
    assert sum(r["mean_s"] * r["count"]
               for r in reduced["instructions"].values()) == \
        pytest.approx(busy, rel=1e-9)
    # ... and the per-execution sum is that over the 39 executions, to the
    # half percent that three instructions of the twice-run slicing program
    # (78 runs each) make
    counts = sorted(r["count"] for r in reduced["instructions"].values())
    assert (counts[0], counts[-1], counts.count(39)) == (39, 78, 516)
    assert total * 1e-3 * 39 == pytest.approx(busy, rel=5e-3)
    # every scope of the vocabulary the twin's step enters has a row
    seen = set().union(*(scope_reduce.tokens(r["stack"])
                         for r in reduced["instructions"].values()))
    assert set(scope_reduce.LM_SCOPES) - seen == {"grad_clip"}
    vocabulary = scope_reduce.device_ms(reduced, scope_reduce.LM_SCOPES)
    assert vocabulary / total == pytest.approx(1 - 0.17254, abs=1e-4)


def test_scoped_trace_spans_and_gaps(scoped):
    reduced = scope_reduce.reduce_file(scoped)
    assert {k: v["count"] for k, v in reduced["spans"].items()} == {
        ("python3", "lm.h2d"): 37, ("python3", "lm.step_call"): 37}
    h2d = scope_reduce.span_ms_per(reduced, "lm.h2d", "lm.step_call")
    call = scope_reduce.span_ms_per(reduced, "lm.step_call", "lm.step_call")
    assert (h2d, call) == (pytest.approx(1.7601, abs=1e-4),
                           pytest.approx(0.8660, abs=1e-4))
    # a toy model leaves the chip idle while the host slices the batch
    gaps = reduced["idle_gaps_by_span"]
    assert max(gaps, key=gaps.get) == "lm.h2d"
    traced = trace_reduce.reduce_file(scoped)
    assert sum(gaps.values()) == pytest.approx(
        traced["window_s"] - traced["busy_s"], rel=1e-6)
