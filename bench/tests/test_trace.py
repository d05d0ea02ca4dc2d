"""The trace reduction against numbers worked out by hand."""
import json
import os

import pytest

from bench.lib import trace as TR

KINDS = TR.load_kinds(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "kernels.json"))


# Event names as a TPU trace gives them: the HLO instruction text.
CC = ' custom-call(f32[8,8,128]{2,1,0} %x), custom_call_target="tpu_custom_call"'
BIN = "%launch_fn.2 = f32[64,256,128]{2,1,0:T(8,128)}" + CC + "\t"
FLAT = ("%launch_fn.3 = (f32[64,4,1,1,128]{4,3,2,1,0}, f32[64,1,128]{2,1,0}, "
        "s32[64,1,128]{2,1,0})" + CC + "\t")
STENCIL = ("%shard_map.39 = (f32[16,4,1,128]{3,2,1,0}, f32[16,1,128]{2,1,0}, "
           "s32[16,1,128]{2,1,0})" + CC + "\t")


def synthetic():
    # Device 0 (ns):  A [0,100)  B [50,150)  C [200,250)  D [400,500)
    #   busy = [0,150) + [200,250) + [400,500) = 150 + 50 + 100 = 300
    #   gaps: [250,400) = 150, [150,200) = 50
    # Device 1:       E [0,300)  busy 300
    # Host: a thread-long wrapper over everything; a launch over most of
    #   the long gap; a scatter over all of the short one.
    return TR.Trace(
        {0: [("fusion.1\t", 0.0, 100.0),
             (BIN, 50.0, 100.0),
             (FLAT, 200.0, 50.0),
             ("copy.4\t", 400.0, 100.0)],
         1: [(STENCIL, 0.0, 300.0)]},
        [("thread", 0.0, 1000.0),
         ("PjitFunction(launch_fn)", 260.0, 100.0),
         ("scatter", 140.0, 70.0)])


def test_busy_union_by_hand():
    t = synthetic()
    assert TR.busy_ns(t.devices[0]) == 300.0
    assert TR.busy_ns(t.devices[1]) == 300.0
    assert TR.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3], [5, 8]]


def test_kernel_time_per_kind_by_hand():
    t = synthetic()
    assert TR.kernel_ns(t, "bin", KINDS) == 100.0
    assert TR.kernel_ns(t, "flat", KINDS) == 50.0
    assert TR.kernel_ns(t, "stencil", KINDS) == 300.0
    assert TR.kernel_ns(t, "labels", KINDS) == 0.0


def test_summary_and_idle_gaps_by_hand():
    s = TR.summarize(synthetic(), 1000e-9, KINDS)
    assert s["busy_s"] == pytest.approx(300e-9)      # (300 + 300) / 2
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(0.7)
    gaps = s["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["PjitFunction(launch_fn)", "scatter"]
    assert [g[1] for g in gaps] == pytest.approx([150e-9, 50e-9])
    ops = dict(s["breakdown"]["device_ops"])
    assert ops[STENCIL[:-1]] == pytest.approx(150e-9)  # 300 ns / 2 chips


@pytest.mark.parametrize("name,kind", [
    # The histogram route's label gather at buckets 64 and 1, as a TPU
    # v5 lite trace names them.
    ("%fusion = s32[2513728]{0:T(1024)S(1)} fusion(s32[64,256]{1,0:T(8,128)"
     "S(1)} %reduce.1, s32[2513728]{0:T(1024)S(1)} %reshape.69), "
     "kind=kCustom, calls=%fused_computation", "gather"),
    ("%fusion = s32[39277]{0:T(1024)S(1)} fusion(s32[256]{0:T(256)S(1)} "
     "%bitcast.14, s32[39936]{0:T(1024)S(1)} %pad_clamp_fusion), "
     "kind=kCustom, calls=%fused_computation", "gather"),
    # Fusions around it and the spatial route's labelling are no kernel.
    ("%fusion.4 = s32[64,39277]{1,0:T(8,128)} fusion(s32[64,39277]{1,0:"
     "T(8,128)S(1)} %reshape.72, u8[64,39277]{1,0:T(8,128)(4,1)S(1)} "
     "%copy-done), kind=kLoop, calls=%fused_computation.7", None),
    ("%convert_reduce_fusion = s32[64,217,181]{1,0,2:T(8,128)S(1)} fusion("
     "f32[64,4,217,181]{2,0,3,1:T(8,128)S(1)} %get-tuple-element.15), "
     "kind=kLoop, calls=%fused_computation.3", None),
    (BIN, "bin"), (FLAT, "flat"), (STENCIL, "stencil"),
])
def test_kind_of_recorded_names(name, kind):
    assert TR.kind_of(name, KINDS) == kind


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_trace.json")


def brute_busy(ops):
    """Busy time by walking every event boundary (independent of the
    interval merge under test)."""
    edges = sorted({x for o in ops for x in (o[1], o[1] + o[2])})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        if any(o[1] <= mid < o[1] + o[2] for o in ops):
            busy += b - a
    return busy


def test_recorded_trace():
    with open(RECORDED) as f:
        rec = json.load(f)
    t = TR.Trace.from_json(rec["trace"])
    want = rec["by_hand"]
    ops = t.devices[min(t.devices)]
    assert TR.busy_ns(ops) == pytest.approx(want["busy_ns"])
    assert brute_busy(ops) == pytest.approx(want["busy_ns"])
    for kind, ns in want["kernel_ns"].items():
        assert TR.kernel_ns(t, kind, KINDS) == pytest.approx(ns)
    s = TR.summarize(t, want["window_ns"] * 1e-9, KINDS)
    assert 1 - s["busy_s"] / s["window_s"] == pytest.approx(
        want["idle_share"])
    assert s["breakdown"]["idle_gaps"][0][1] == pytest.approx(
        want["longest_gap_ns"] * 1e-9)
