"""The two readers that find what they read by the names the program wrote:
``span_idle`` (device idle time under a ``dtf.*`` host phase) and
``kernel_roofline`` (a ``dtf_*`` Pallas kernel against its roofline), on
hand-made traces and on the recorded v5e trace."""

import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import peaks as peak_table, xtrace
from benchmarks.lib.flops import flash_attention_cost, roofline_least_seconds
from benchmarks.readers import flash_roofline, kernel_roofline, span_idle

REAL = os.path.join(os.path.dirname(__file__), "data", "v5e_probe.xplane.pb")
TPU = "/device:TPU:0"
TICK = r"^dtf\.serve\.tick$"


def op(start, end, name="%fusion.1 = f32[8]{0} fusion(%p), kind=kLoop"):
    return (name, float(start), float(end - start))


def span(name, start, end):
    return (name, float(start), float(end - start))


# The device runs [0,10) [30,40) [70,100): idle [10,30) and [40,70), 50 ns.
# Two ticks; tick A prefills and decodes, tick B only decodes; [50,55) is
# the benchmark's clients between them.
MODULES = [("jit_prefill_fn(1)", 0.0, 10.0), ("jit_decode_fn(2)", 30.0, 10.0),
           ("jit_decode_fn(2)", 70.0, 30.0)]
OPS = [op(0, 10), op(30, 40), op(70, 100)]
HOST = [
    span("bench.tick", 4, 51),
    span("dtf.serve.tick", 5, 50),
    span("dtf.serve.prefill_chunk", 11, 21),
    span("dtf.engine.prefill.dispatch", 12, 20),
    span("PjitFunction(prefill_fn)", 14, 19),
    span("dtf.serve.decode", 24, 45),
    span("dtf.engine.decode.dispatch", 25, 35),
    span("dtf.engine.decode.readback", 35, 45),
    span("dtf.serve.tick", 55, 95),
    span("dtf.serve.decode", 56, 90),
    span("dtf.engine.decode.dispatch", 56, 66),
    span("dtf.engine.decode.readback", 66, 90),
]
TRACE = xtrace.Trace(ops={TPU: OPS}, modules={TPU: MODULES}, host=HOST)


def idle(trace=TRACE, **args):
    return span_idle.read({"trace": trace}, per=TICK, scale=1e9, **args)


def test_overlap_of_two_sorted_interval_lists():
    a = [(0.0, 10.0), (20.0, 30.0), (40.0, 50.0)]
    b = [(5.0, 25.0), (28.0, 45.0), (60.0, 70.0)]
    # [5,10) + [20,25) + [28,30) + [40,45)
    assert span_idle.overlap_ns(a, b) == 17.0
    assert span_idle.overlap_ns(b, a) == 17.0
    assert span_idle.overlap_ns(a, []) == 0.0
    assert span_idle.overlap_ns([(0.0, 100.0)], a) == 30.0


@pytest.mark.parametrize("args,idle_ns", [
    # [25,30) of tick A's dispatch, all of tick B's [56,66)
    ({"inside": r"^dtf\.engine\.decode\.dispatch$"}, 15.0),
    # [12,20) lies wholly in the first idle interval
    ({"inside": r"^dtf\.engine\.prefill\.dispatch$"}, 8.0),
    # the second idle interval [40,70) straddles tick A's readback, its
    # delivery loop, the clients and tick B's dispatch and readback: the
    # readback spans get [40,45) and [66,70) of it and no more
    ({"inside": r"^dtf\.engine\.(decode|prefill)\.readback$"}, 9.0),
    # a tick less its engine spans: [10,12) [20,25) [45,50) and [55,56)
    ({"inside": TICK, "outside": r"^dtf\.engine\."}, 13.0),
    # without the subtraction, all the idle time inside ticks
    ({"inside": TICK}, 45.0),
    # a span nested in another is counted once (a union, not a sum)
    ({"inside": r"^dtf\.(serve\.decode|engine\.decode\.)"}, 25.0),
    # `outside` that covers everything leaves nothing
    ({"inside": TICK, "outside": r"^bench\.tick$|^dtf\.serve\.tick$"}, 0.0),
])
def test_idle_under_a_phase_per_tick(args, idle_ns):
    assert idle(**args) == pytest.approx(idle_ns / 2)      # two ticks


def test_the_phases_account_for_the_idle_inside_ticks():
    """The four metrics' patterns partition a tick: their sum is the idle
    time inside ticks, and what is left of the window's idle time lies
    between ticks."""
    m = json.load(open(os.path.join(bench_run.ROOT, "BENCHMARK.json")))
    names = [e["name"] for e in m["per_layer"]
             if e["name"].startswith("idle_ms_per_tick.")]
    assert len(names) == 4
    total = 0.0
    for name in names:
        spec = json.load(open(os.path.join(
            bench_run.ROOT, "benchmarks", "metrics", name + ".json")))
        assert spec["reader"] == "span_idle"
        total += span_idle.read({"trace": TRACE},
                                **{**spec["args"], "scale": 1e9})
    window = xtrace.steady_window(MODULES)
    all_idle = (window[1] - window[0]) - xtrace.busy_ns(OPS, window)
    assert all_idle == 50.0
    assert total * 2 == pytest.approx(45.0)     # [50,55) is the clients'


def test_partial_ticks_at_the_windows_edges():
    """The steady window opens at the first program's start and closes at
    the last one's end, both inside a tick. The tick that began before the
    window is not counted, but the idle time of its part inside is; the
    tick that outlasts the window is counted and cut."""
    modules = [("jit_decode_fn(2)", 10.0, 10.0),
               ("jit_decode_fn(2)", 40.0, 10.0),
               ("jit_decode_fn(2)", 80.0, 10.0)]          # window [10,90)
    ops = [op(10, 20), op(40, 50), op(80, 90)]            # idle [20,40) [50,80)
    host = [span("dtf.serve.tick", 0, 30),                # began before
            span("dtf.engine.decode.readback", 12, 28),
            span("dtf.serve.tick", 32, 60),
            span("dtf.engine.decode.readback", 45, 58),
            span("dtf.serve.tick", 62, 120),              # outlasts it
            span("dtf.engine.decode.readback", 85, 118),
            span("dtf.serve.tick", 125, 140)]             # after the window
    trace = xtrace.Trace(ops={TPU: ops}, modules={TPU: modules}, host=host)
    # two ticks start in [10,90); idle in ticks: [20,30) [32,40) [50,60)
    # [62,80) = 46; in readbacks: [20,28) + [50,58) = 16 (the last
    # readback's idle part lies past the window)
    assert idle(trace, inside=TICK) == pytest.approx(46.0 / 2)
    assert idle(trace, inside=r"readback$") == pytest.approx(16.0 / 2)


def test_span_idle_returns_nothing_where_there_is_nothing_to_read():
    args = {"inside": TICK, "per": TICK}
    assert span_idle.read({"trace": None}, **args) is None
    assert span_idle.read({}, **args) is None
    # a CPU's trace: host spans, no device plane
    assert span_idle.read({"trace": xtrace.Trace(ops={}, modules={},
                                                 host=HOST)}, **args) is None
    # the parent commit's program: a device plane, no dtf.serve.tick span
    old = xtrace.Trace(ops={TPU: OPS}, modules={TPU: MODULES},
                       host=[span("bench.tick", 4, 51),
                             span("dtf.serve.decode", 24, 45)])
    assert span_idle.read({"trace": old}, **args) is None
    # ticks, but none of them starts inside the window
    late = xtrace.Trace(ops={TPU: OPS}, modules={TPU: MODULES},
                        host=[span("dtf.serve.tick", 200, 300)])
    assert span_idle.read({"trace": late}, **args) is None
    # a phase that never ran reads zero, not nothing: the tick was there
    assert span_idle.read({"trace": TRACE}, inside=r"^dtf\.engine\.pages\.",
                          per=TICK) == 0.0


# ----------------------------------------------------- kernel_roofline

V5E = peak_table.peaks_for("TPU v5 lite")
SHAPE = {"seq_len": 1024, "grad_accum": 1, "device_micro_batch": 8,
         "attention": {"heads": 16, "d_head": 64, "causal": True,
                       "calls_per_micro_batch": 2}}


def least(backward: bool) -> float:
    flops, nbytes = flash_attention_cost(
        batch=8, heads=16, t_q=1024, t_k=1024, d_head=64, causal=True,
        backward=backward)
    return roofline_least_seconds(flops, nbytes, V5E)[0]


def kernel(name, start, ns):
    return (f"%{name} = bf16[128,1024,64]{{2,1,0}} custom-call(bf16[128,1024,"
            f'64]{{2,1,0}} %bitcast.1), custom_call_target="tpu_custom_call"',
            float(start), float(ns))


def named_trace():
    """Two steps of two layers: each layer runs the forward, dq and dk/dv
    kernels, under a module scope (``%dtf_flash_fwd.N``) in the first
    layer and bare under ``jax.grad`` (``%jvp_dtf_flash_fwd_.N``) in the
    second; an instruction that reads a kernel's output names it as an
    operand and must not be counted."""
    ops, modules, t = [], [], 0.0
    for _ in range(2):
        modules.append(("jit_train_step(7)", t, 8.0e6))
        for fwd, dq, dkv in (
                ("dtf_flash_fwd.3", "dtf_flash_dq.5", "dtf_flash_dkv.4"),
                ("jvp_dtf_flash_fwd_.1", "transpose_jvp_dtf_flash_dq__.2",
                 "transpose_jvp_dtf_flash_dkv__.2")):
            ops += [kernel(fwd, t, 8.0e5), kernel(dq, t + 1.0e6, 6.0e5),
                    kernel(dkv, t + 2.0e6, 9.0e5),
                    ("%fusion.9 = bf16[128,1024,64]{2,1,0} fusion(%"
                     + fwd + "), kind=kLoop", t + 3.0e6, 5.0e5)]
            t += 4.0e6
    return xtrace.Trace(ops={TPU: ops}, modules={TPU: modules}, host=[])


FWD = {"kernel": r"^%?\w*dtf_flash_fwd", "backward": False}
BWD = {"kernel": r"^%?\w*dtf_flash_(dq|dkv)", "backward": True}


def test_kernel_roofline_finds_kernels_by_name_and_splits_the_halves():
    obs = {"trace": named_trace(), "values": SHAPE, "peaks": V5E}
    fwd = kernel_roofline.read(obs, **FWD)
    bwd = kernel_roofline.read(obs, **BWD)
    # per step: two forward calls of 0.8 ms, two backwards of 0.6 + 0.9 ms
    assert fwd == pytest.approx(100 * least(False) * 2 / 1.6e-3)
    assert bwd == pytest.approx(100 * least(True) * 2 / 3.0e-3)
    # weighted by their least times the halves give the whole back: what
    # flash_attn_roofline reads in the same trace, by operand shape
    whole = flash_roofline.read(obs)
    both = least(False) + least(True)
    assert both / (least(False) / fwd + least(True) / bwd) == \
        pytest.approx(whole, rel=1e-9)
    assert 1.0 < fwd < 105.0 and 1.0 < bwd < 105.0


def test_the_manifests_kernel_patterns_are_the_ones_tested():
    for name, want in (("flash_fwd_roofline", FWD),
                       ("flash_bwd_roofline", BWD)):
        spec = json.load(open(os.path.join(
            bench_run.ROOT, "benchmarks", "metrics", name + ".json")))
        assert spec == {"reader": "kernel_roofline", "args": want}


def test_kernel_roofline_returns_nothing_where_there_is_nothing_to_read():
    obs = {"trace": named_trace(), "values": SHAPE, "peaks": V5E}
    assert kernel_roofline.read({**obs, "trace": None}, **FWD) is None
    assert kernel_roofline.read({**obs, "peaks": None}, **FWD) is None
    # a name no kernel of the trace carries (the dense path), or the
    # parent commit's unnamed kernels
    assert kernel_roofline.read(obs, kernel=r"^%?\w*dtf_flash_fused",
                                backward=False) is None
    unnamed = xtrace.Trace(
        ops={TPU: [kernel("attention.1", 0.0, 8.0e5)]},
        modules={TPU: [("jit_wrapped(7)", 0.0, 1.0e6)]}, host=[])
    assert kernel_roofline.read({**obs, "trace": unnamed}, **FWD) is None
    assert kernel_roofline.read(
        {**obs, "trace": xtrace.Trace(ops={}, modules={}, host=[])},
        **FWD) is None


# ------------------------------------------------- the recorded v5e trace


@pytest.fixture(scope="module")
def real():
    return xtrace.load(REAL)


def test_real_v5e_trace_idle_under_the_probes_own_spans(real):
    """PR 23's probe wrote ``probe.tick`` round each call (there was no
    ``dtf.*`` vocabulary yet); the reader takes its names as arguments."""
    s = xtrace.device_summary(real)
    idle_s = s["window_s"] - s["busy_s"]
    in_ticks = span_idle.read({"trace": real}, inside=r"^probe\.tick$",
                              per=r"^probe\.tick$")
    # the window opens at the first program's start, which by the trace's
    # clock is 0.7 ms BEFORE the host span that enqueued it (the skew
    # between the host's and the device's timestamps): all three spans
    # start inside it, and nearly all the idle time lies under them
    assert 0.0 < in_ticks * 3 <= idle_s
    assert in_ticks * 3 == pytest.approx(idle_s, rel=0.001)
    enqueue = span_idle.read({"trace": real}, inside=r"^PjitFunction\(step\)$",
                             per=r"^probe\.tick$")
    rest = span_idle.read({"trace": real}, inside=r"^probe\.tick$",
                          outside=r"^PjitFunction\(step\)$",
                          per=r"^probe\.tick$")
    assert enqueue > 0.0 and rest > 0.0
    assert enqueue + rest == pytest.approx(in_ticks, rel=1e-9)


def test_real_v5e_trace_splits_the_flash_kernels_by_name(real):
    obs = {"trace": real, "peaks": V5E,
           "values": {**SHAPE, "attention": {
               **SHAPE["attention"], "calls_per_micro_batch": 1}}}
    fwd = kernel_roofline.read(obs, kernel=r"^%jvp__\.", backward=False)
    bwd = kernel_roofline.read(obs, kernel=r"^%transpose_jvp___\.",
                               backward=True)
    # 0.855 ms forward, 0.813 + 0.650 ms backward (PERF.md section 6)
    assert fwd == pytest.approx(100 * least(False) / 0.855e-3, rel=2e-3)
    assert bwd == pytest.approx(100 * least(True) / 1.463e-3, rel=2e-3)
    both = least(False) + least(True)
    assert both / (least(False) / fwd + least(True) / bwd) == \
        pytest.approx(flash_roofline.read(obs), rel=1e-9)
