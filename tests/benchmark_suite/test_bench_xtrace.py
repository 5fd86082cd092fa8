"""Trace reduction: on a hand-made event list, and on the first real v5e
trace this repo brought back (PR 23's profiler probe: three calls of a
matmul chain plus flash attention forward and backward, 81 KB)."""

import os

import pytest

from benchmarks.lib import xtrace

REAL = os.path.join(os.path.dirname(__file__), "data", "v5e_probe.xplane.pb")

# two "steps" of one program; ops in ns: a [0,40) b [30,60) | gap | c [80,100)
MODULES = [("jit_step(1)", 0.0, 60.0), ("jit_step(1)", 80.0, 20.0)]
OPS = [("%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f.1", 0.0, 40.0),
       ("%fusion.2 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f.2", 30.0, 30.0),
       ('%jvp__.3 = bf16[4,8,2]{2,1,0} custom-call(bf16[4,8,2]{2,1,0} %a), '
        'custom_call_target="tpu_custom_call"', 80.0, 20.0)]
HOST = [("train", 0.0, 75.0), ("bench.tick", 62.0, 10.0),
        ("train", 75.0, 30.0)]


def test_interval_union_counts_overlap_once():
    assert xtrace.merge([(30, 60), (0, 40), (80, 100), (90, 95)]) == [
        (0, 60), (80, 100)]
    assert xtrace.busy_ns(OPS, (0.0, 100.0)) == 80.0
    # clipped to a window that cuts the first and the last op
    assert xtrace.busy_ns(OPS, (10.0, 90.0)) == 60.0


def test_idle_share_of_the_steady_window():
    window = xtrace.steady_window(MODULES)
    assert window == (0.0, 100.0)
    idle = 1.0 - xtrace.busy_ns(OPS, window) / (window[1] - window[0])
    assert idle == pytest.approx(0.2)


def test_gaps_are_labelled_by_the_shortest_covering_host_span():
    found = xtrace.gaps(OPS, (0.0, 100.0))
    assert found == [(60.0, 80.0)]
    # the gap's middle (70) lies in "train" [0,75) and "bench.tick" [62,72)
    assert xtrace.label_gap(found[0], HOST) == "bench.tick"
    assert xtrace.label_gap((200.0, 210.0), HOST) == "(no host span)"


def test_op_kind_drops_the_number_and_names_a_custom_calls_target():
    assert xtrace.op_kind(OPS[0][0]) == "fusion[Loop]"
    assert xtrace.op_kind("%add_fusion.7 = f32[8]{0} fusion(%p), kind=kLoop") \
        == "add_fusion"
    assert xtrace.op_kind(OPS[2][0]) == "jvp__[tpu_custom_call]"
    assert xtrace.op_kind("%copy-done = bf16[2]{0} copy-done(%x)") == \
        "copy-done"


def test_device_summary_of_a_hand_made_trace():
    # a scan's ``while`` spans its body: counted in busy time by the union,
    # left out of the ranking so the body is not counted twice
    scan = [("%while.1 = (f32[8]{0}) while(%t), body=%b", 0.0, 60.0)]
    trace = xtrace.Trace(ops={"/device:TPU:0": OPS + scan},
                         modules={"/device:TPU:0": MODULES}, host=HOST)
    s = xtrace.device_summary(trace)
    assert s["busy_s"] == pytest.approx(80e-9)
    assert s["window_s"] == pytest.approx(100e-9)
    assert s["breakdown"]["device_ops"][0] == ["fusion[Loop]",
                                               pytest.approx(70e-9)]
    assert s["breakdown"]["idle_gaps"] == [["bench.tick",
                                            pytest.approx(20e-9)]]
    assert xtrace.module_durations(trace) == pytest.approx([60e-9, 20e-9])
    assert xtrace.op_seconds(trace, r"tpu_custom_call") == (
        pytest.approx(20e-9), 1)


def test_a_trace_with_no_device_plane_gives_nothing():
    assert xtrace.device_summary(
        xtrace.Trace(ops={}, modules={}, host=HOST)) is None


@pytest.fixture(scope="module")
def real():
    return xtrace.load(REAL)


def test_real_v5e_trace_planes_and_programs(real):
    assert list(real.ops) == ["/device:TPU:0"]
    assert len(real.modules["/device:TPU:0"]) == 3
    assert all(n.startswith("jit_step(") for n, _, _ in
               real.modules["/device:TPU:0"])
    # the probe's own annotations, written by its Python thread
    names = {n for n, _, _ in real.host}
    assert {"train", "probe.tick"} <= names
    assert not any(n.startswith("$") for n in names)


def test_real_v5e_trace_busy_idle_and_breakdown(real):
    s = xtrace.device_summary(real)
    # three calls of 3.106 ms each inside an 11.94 ms slice
    assert xtrace.module_durations(real) == pytest.approx(
        [0.003105, 0.003106, 0.003106], abs=2e-6)
    assert s["window_s"] == pytest.approx(0.011941, abs=1e-6)
    assert s["busy_s"] == pytest.approx(0.009317, abs=1e-6)
    assert 0.0 < 1.0 - s["busy_s"] / s["window_s"] < 0.25
    kinds = [k for k, _ in s["breakdown"]["device_ops"]]
    assert kinds[:2] == ["transpose_jvp___[tpu_custom_call]",
                         "jvp__[tpu_custom_call]"]
    assert len(s["breakdown"]["device_ops"]) <= 10
    assert s["breakdown"]["idle_gaps"][0][0] == "probe.tick"


def test_real_v5e_trace_finds_the_flash_kernels_by_shape(real):
    # forward + dq + dkv of b8 h16 t1024 d64, three calls each
    seconds, count = xtrace.op_seconds(
        real, r'custom-call\(bf16\[128,1024,64\].*'
              r'custom_call_target="tpu_custom_call"')
    assert count == 9
    assert seconds / 3 == pytest.approx(0.002318, abs=5e-6)
