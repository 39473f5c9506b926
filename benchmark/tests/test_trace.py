"""The trace reducer: busy union, idle over the WINDOW, self times under
control-flow events, kernel time by name, slabs from the event's own text —
on hand-made events and on a small piece of a trace recorded on the chip
(``data/recorded_trace.json``: 1,500 consecutive ``XLA Ops`` events of the
tune cell's traced call, names cut to 400 characters)."""

import json
import os

from benchmark.harness import roofline, trace
from benchmark.harness.peaks import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
GN = ('%fused_group_norm.5 = bf16[2,32768,320]{2,1,0:T(8,128)(2,1)} '
      'custom-call(bf16[2,32768,320]{2,1,0} %x, f32[320]{0} %s), '
      'custom_call_target="tpu_custom_call"')
FA = ('%fused_frame_attention.3 = bf16[2,8,8,4096,40]{4,3,2,1,0} '
      'custom-call(bf16[2,8,8,4096,40]{4,3,2,1,0} %q), '
      'custom_call_target="tpu_custom_call"')


def test_names():
    assert trace.short_name(GN) == "fused_group_norm.5"
    assert trace.kernel_of(GN, {}) == "fused_group_norm"
    assert trace.kernel_of("%fusion.1 = f32[2] fusion(f32[2] %a)", {}) is None
    assert trace.output_arrays(GN) == [("bf16", 2 * 32768 * 320)]
    assert trace.op_family("%convolution.12 = bf16[8,64,64,320]{3,2,1,0} "
                           "convolution(bf16[8,64,64,4] %a)") == "convolution"
    assert trace.op_family("%select_bitcast_fusion = f32[2] fusion(f32[2] "
                           "%a)") == "select_bitcast_fusion"
    tup = ("%k.1 = (bf16[4,8]{1,0}, f32[4]{0}) custom-call(bf16[4,8]{1,0} "
           "%a)")
    assert trace.output_arrays(tup) == [("bf16", 32), ("f32", 4)]


def test_union_self_times_and_idle_over_the_window():
    ns = 1_000_000_000
    events = {"/device:TPU:0": [
        ("%while.1 = (s32[]) while((s32[]) %t)", 0, 6 * ns, {}),
        (FA, 0, 2 * ns, {}),
        (GN, 2 * ns, 1 * ns, {}),
        ("%fusion.7 = f32[2]{0} fusion(f32[2]{0} %a)", 4 * ns, 2 * ns, {}),
        ("%fusion.8 = f32[2]{0} fusion(f32[2]{0} %a)", 8 * ns, 1 * ns, {}),
    ]}
    r = trace.reduce_events(events, window_s=10.0)
    # busy is the union of the LEAF events: 0-3, 4-6, 8-9 (the while event
    # covers 3-4 too, where nothing ran)
    assert abs(r["busy_s"] - 6.0) < 1e-9
    assert r["window_s"] == 10.0           # idle is over the window: 40 %
    assert r["kernel_s"] == {"fused_frame_attention": 2.0,
                             "fused_group_norm": 1.0}
    assert r["kernel_calls"] == {"fused_frame_attention": 1,
                                 "fused_group_norm": 1}
    fam = dict(r["breakdown"]["device_ops"])
    assert fam["fusion"] == 3.0 and abs(fam["while"] - 1.0) < 1e-9
    assert trace.op_family("%fusion.9 = f32[2]{0} fusion(f32[2]{0} %a), "
                           "kind=kOutput, calls=%c") == "fusion.Output"
    gaps = r["breakdown"]["idle_gaps"]
    assert gaps[0] == ["after fusion.7 before fusion.8", 2.0]
    assert gaps[1] == ["after fused_group_norm.5 before fusion.7", 1.0]
    assert r["kernel_slabs"]["fused_group_norm"] == [
        ["bf16", 2 * 32768 * 320, 1, 1.0]]


def test_readers_return_nothing_when_there_is_nothing_to_read():
    import sys
    sys.path.insert(0, os.path.dirname(HERE))
    import run as bench_run

    ctx = {"trace": {"kernel_s": {}, "kernel_slabs": {}, "busy_s": 1.0,
                     "window_s": 2.0},
           "window": {"kind": "tune"}, "config": {},
           "device": {"kind": "TPU v5 lite", "count": 1}}
    assert bench_run.find_reader("frame_attn_roofline.edit").read(ctx) is None
    assert bench_run.find_reader("device_idle.tune").read(ctx) == 50.0
    assert bench_run.find_reader("device_idle.tune").read(
        dict(ctx, trace=None)) is None


def test_roofline_counts_at_the_four_sd15_sites():
    peaks = peaks_for("TPU v5 lite")
    want = {  # (resolution, channels): (operations, bytes), 8 frames, bf16
        (64, 320): (4 * 8 * 4096 * 4096 * 320, 2 * (2 * 8 + 2) * 4096 * 320),
        (32, 640): (4 * 8 * 1024 * 1024 * 640, 2 * (2 * 8 + 2) * 1024 * 640),
        (16, 1280): (4 * 8 * 256 * 256 * 1280, 2 * (2 * 8 + 2) * 256 * 1280),
        (8, 1280): (4 * 8 * 64 * 64 * 1280, 2 * (2 * 8 + 2) * 64 * 1280),
    }
    for (r, c), (ops, nbytes) in want.items():
        assert roofline.frame_attention_site(8, r, c) == (ops, nbytes)
    # 64x64: 1.72e11 operations = 0.87 ms at 197 TFLOP/s, compute-bound
    t, which = roofline.least_seconds(*want[(64, 320)], peaks)
    assert which == "compute" and abs(t - 0.872e-3) < 0.005e-3
    # 8x8: bytes bind
    assert roofline.least_seconds(*want[(8, 1280)], peaks)[1] == "memory"


def test_recorded_trace_piece():
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    events = {"/device:TPU:0": [(n, s, d, {}) for n, s, d in rec["events"]]}
    span_s = (max(s + d for _, s, d in rec["events"])
              - min(s for _, s, _ in rec["events"])) / 1e9
    r = trace.reduce_events(events, window_s=span_s)
    assert r["events"] == len(rec["events"])
    assert 0 < r["busy_s"] <= span_s
    assert abs(r["busy_s"] - rec["expect"]["busy_s"]) < 1e-9
    for k, v in rec["expect"]["kernel_s"].items():
        assert abs(r["kernel_s"][k] - v) < 1e-9
    # self times add up to the busy time where nothing overlaps
    assert sum(r["family_s"].values()) <= span_s + 1e-9
