"""The reduction from a trace to busy/idle, per-op sums and gap attribution:
on intervals counted by hand (a synthetic trace written as an XSpace text
proto), and on a trace recorded on the chip (``benchmark/testdata``)."""

import os

import pytest

from benchmark import trace_reduce

NS = 1e-9

# chip 0: ops at [1000,3000) A, [2500,4000) B (overlaps A), [6000,7000) A
#         -> busy union [1000,4000) + [6000,7000) = 4000 ns
# chip 1: one op [2000,4000) C -> busy 2000 ns
# host:   bench:outer [0,10000), bench:inner [4200,5800), other (ignored)
# window: 0 .. 10000 ns
SYNTHETIC = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 1500000 }
    events { metadata_id: 1 offset_ps: 5000000 duration_ps: 1000000 } }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 6000000 } }
  event_metadata { key: 1 value { id: 1 name: "A" } }
  event_metadata { key: 2 value { id: 2 name: "B" } }
  event_metadata { key: 3 value { id: 3 name: "jit_step" } } }
planes { id: 2 name: "/device:TPU:1"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 2000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 } }
  event_metadata { key: 1 value { id: 1 name: "C" } } }
planes { id: 3 name: "/device:TPU:0 SparseCore 0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "ignored" } } }
planes { id: 4 name: "/host:CPU"
  lines { id: 7 name: "main" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 4200000 duration_ps: 1600000 }
    events { metadata_id: 3 offset_ps: 100000 duration_ps: 9000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench:outer" } }
  event_metadata { key: 2 value { id: 2 name: "bench:inner" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } } }
"""


@pytest.fixture(scope="module")
def synthetic(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "synthetic.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(SYNTHETIC))
    return trace_reduce.reduce_file(str(path))


def test_union_merges_overlaps_and_touching():
    assert trace_reduce.union([(5, 7), (1, 3), (2, 4), (4, 5)]) == [[1, 7]]
    assert trace_reduce.union([(1, 2), (3, 4)]) == [[1, 2], [3, 4]]


def test_busy_is_the_union_averaged_over_chips(synthetic):
    assert synthetic["chips"] == 2
    assert synthetic["busy_s"] == pytest.approx((4000 + 2000) / 2 * NS)
    assert synthetic["window_s"] == pytest.approx(10000 * NS)


def test_op_sums_count_overlapping_time_per_op(synthetic):
    assert synthetic["ops"] == pytest.approx(
        {"A": 3000 * NS, "B": 1500 * NS, "C": 2000 * NS})
    assert synthetic["op_counts"] == {"A": 2, "B": 1, "C": 1}
    assert synthetic["top_ops"][0] == ["A", pytest.approx(3000 * NS)]
    assert trace_reduce.kernel_time(synthetic, lambda n: n in "AB") == (
        pytest.approx(4500 * NS), 1.5)
    assert "ignored" not in synthetic["ops"]      # the sparse-core plane
    assert "jit_step" not in synthetic["ops"]     # the modules line


def test_gaps_go_to_the_innermost_span_open_at_their_middle(synthetic):
    # chip 0 gaps: [0,1000) outer; [4000,6000): middle 5000 is inside inner;
    # [7000,10000) outer -> outer 4000, inner 2000
    # chip 1 gaps: [0,2000) outer and [4000,10000) (middle 7000) outer: 8000
    # mean of the two chips
    gaps = synthetic["idle_gaps_by_span"]
    assert gaps == pytest.approx({"outer": (4000 + 8000) / 2 * NS,
                                  "inner": 2000 / 2 * NS})
    total = sum(gaps.values())
    assert total == pytest.approx(synthetic["window_s"] - synthetic["busy_s"])


def test_inner_span_wins_a_gap_it_covers():
    ops = {0: [("A", 0, 1000), ("A", 3000, 1000)]}
    spans = [("outer", 0, 4000), ("inner", 1900, 200)]
    got = trace_reduce.reduce_events(ops, spans)
    assert got["idle_gaps_by_span"] == pytest.approx({"inner": 2000 * NS})


def test_gap_with_no_span_is_named_so():
    got = trace_reduce.reduce_events({0: [("A", 0, 10), ("A", 30, 10)]}, [])
    assert got["idle_gaps_by_span"] == pytest.approx(
        {trace_reduce.NO_SPAN: 20 * NS})


def test_no_device_plane_reduces_to_nothing():
    assert trace_reduce.reduce_events({}, [("fit", 0, 10)]) is None


def test_label_sums_instances_of_one_op():
    a = ("%fusion.12 = (bf16[8,1024]{1,0:T(8,128)(2,1)S(1)}, f32[8]{0}) "
         "fusion(f32[8]{0} %x), kind=kLoop, calls=%fused_computation.3")
    b = a.replace("%fusion.12", "%fusion.7").replace("%x", "%y")
    assert trace_reduce.label(a) == "fusion fusion (bf16[8,1024], f32[8])"
    assert trace_reduce.label(a) == trace_reduce.label(b)
    assert trace_reduce.label("plain") == "plain"


def test_roofline_share_by_hand():
    reduced = {"ops": {"k.1": 2e-3, "k.2": 2e-3, "x": 1.0},
               "op_counts": {"k.1": 10, "k.2": 10, "x": 1}}
    peaks = {"bf16_flops_per_s": 100e12, "hbm_bytes_per_s": 1e12}
    # one run of the set needs 1e9 FLOP (10 us) or 2e7 B (20 us): 20 us binds;
    # 10 runs -> 200 us least, 4 ms taken -> 5 %
    work = {"flops": 1e9, "bytes": 2e7}
    got = trace_reduce.roofline_share(reduced, lambda n: n.startswith("k"),
                                      work, peaks)
    assert got == pytest.approx(5.0)
    assert trace_reduce.roofline_share(reduced, lambda n: False, work,
                                       peaks) is None
    assert trace_reduce.roofline_share(None, lambda n: True, work,
                                       peaks) is None


# --- a trace recorded on the chip -------------------------------------------
# benchmark/testdata/gpt2-tiny-v5e.xplane.pb.gz: 0.1 s of the rehearsal twin
# gpt2-tiny-train (2 layers, d 32, 2 x 64 tokens) on one TPU v5e, PR 25:
#   python3 benchmark/run.py --rehearse --workload gpt2-tiny-train --seed 305 \
#       --seconds 0.2 --trace 1 --keep-trace tiny.xplane.pb
# Counted by hand from a dump of its planes: 17,754 events on `XLA Ops` of
# /device:TPU:0, the first starting at 44,270,936 ns and the last ending at
# 143,912,079 ns; 33 `bench:fit_batch` and 34 `bench:wait_device` spans, all
# inside that; 34 steps, each running the forward flash kernel once per layer
# (2 instructions) and the two backward kernels once per layer (4).

RECORDED = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "testdata", "gpt2-tiny-v5e.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import gzip
    path = tmp_path_factory.mktemp("trace") / "recorded.xplane.pb"
    with gzip.open(RECORDED) as f:
        path.write_bytes(f.read())
    return trace_reduce.load(str(path)), trace_reduce.reduce_file(str(path))


def test_recorded_trace_planes_and_spans(recorded):
    (device_ops, host_spans), reduced = recorded
    assert {c: len(v) for c, v in device_ops.items()} == {0: 17754}
    names = [n for n, _, _ in host_spans]
    assert (names.count("fit_batch"), names.count("wait_device")) == (33, 34)
    assert reduced["chips"] == 1
    assert reduced["window_s"] == pytest.approx((143912079 - 44270936) * NS)


def test_recorded_trace_busy_matches_an_independent_sweep(recorded):
    (device_ops, _), reduced = recorded
    busy, end = 0.0, -1.0
    for s, d in sorted((s, d) for _, s, d in device_ops[0]):
        busy += max(0.0, s + d - max(s, end))
        end = max(end, s + d)
    assert reduced["busy_s"] == pytest.approx(busy * NS)
    assert reduced["busy_s"] == pytest.approx(2.924438e-3)
    idle = sum(reduced["idle_gaps_by_span"].values())
    assert idle + reduced["busy_s"] == pytest.approx(reduced["window_s"])
    # a toy model leaves the chip idle while the host dispatches
    assert reduced["idle_gaps"][0][0] == "fit_batch"


def test_recorded_trace_flash_kernels_are_found_by_the_metrics_rules(recorded):
    from benchmark.layer_metrics import flash_bwd_roofline, flash_fwd_roofline
    _, reduced = recorded
    fwd = [k for k in reduced["ops"] if flash_fwd_roofline.is_flash_fwd(k)]
    bwd = [k for k in reduced["ops"] if flash_bwd_roofline.is_flash_bwd(k)]
    assert (len(fwd), len(bwd)) == (2, 4)
    assert trace_reduce.kernel_time(
        reduced, flash_fwd_roofline.is_flash_fwd) == (
            pytest.approx(753.83e-6), 34.0)
    assert trace_reduce.kernel_time(
        reduced, flash_bwd_roofline.is_flash_bwd) == (
            pytest.approx(957.86e-6), 34.0)
    assert reduced["top_ops"][0][0] == \
        "jvp__ custom-call (bf16[8,64,8], f32[8,64,128])"
