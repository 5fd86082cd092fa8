"""Device-time attribution (ISSUE 8): the XPlane parser, category buckets,
the per-collective ``file:line`` provenance join, comm/compute overlap
efficiency, the device-MFU cross-check and chrome-trace export.

Anchored on the committed synthetic fixture
(``tests/data/xplane_synthetic.pb``, built by tests/xplane_fixture.py):
a hand-laid two-device timeline whose bucketing/overlap/provenance
numbers are exact — 0.5 of the ppermute ring hidden under the Pallas
kernel, the all-reduce fully exposed, device busy fraction 0.8.
"""

import json
import os
import sys

import pytest

from dtf_tpu.analysis.provenance import (instruction_sites,
                                         profile_site_map)
from dtf_tpu.telemetry import profile as profile_mod
from dtf_tpu.telemetry import xplane
from dtf_tpu.telemetry.trace import TraceCollector
from dtf_tpu.telemetry.xplane import OpEvent, TraceData

from tests.xplane_fixture import FIXTURE_PATH, HLO_TEXT, build_bytes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fixture_trace() -> TraceData:
    space = xplane.load_xspace(FIXTURE_PATH)
    assert space is not None, "tensorflow xplane bindings missing"
    return xplane.extract(space, path=FIXTURE_PATH)


def _fixture_report(**kw) -> dict:
    return profile_mod.analyze(_fixture_trace(),
                               site_map=profile_site_map(HLO_TEXT), **kw)


# --------------------------------------------------------------------------
# the committed fixture: determinism + byte-stable parse
# --------------------------------------------------------------------------

def test_fixture_bytes_match_committed_file():
    """The builder reproduces the committed proto byte-for-byte — the
    fixture cannot silently drift from the code that documents it."""
    with open(FIXTURE_PATH, "rb") as f:
        committed = f.read()
    assert build_bytes() == committed
    assert build_bytes() == build_bytes()      # deterministic serialization


def test_fixture_parse_is_byte_stable_across_runs():
    """Same fixture in → byte-identical report JSON out, twice (sets,
    dict order, float rounding — none may leak nondeterminism)."""
    a = json.dumps(_fixture_report(), sort_keys=True)
    b = json.dumps(_fixture_report(), sort_keys=True)
    assert a == b


# --------------------------------------------------------------------------
# bucketing + provenance join + overlap on the exact fixture numbers
# --------------------------------------------------------------------------

def test_fixture_extract_shape():
    tr = _fixture_trace()
    assert len(tr.op_events) == 16         # 4 ops x 2 steps x 2 devices
    assert len(tr.step_windows) == 2
    assert tr.device_planes == ["/device:TPU:0", "/device:TPU:1"]
    assert [w.step for w in tr.step_windows] == [0, 1]


def test_fixture_buckets():
    rep = _fixture_report()
    b = rep["buckets"]
    assert set(b) == {"matmul", "pallas", "all-reduce",
                      "collective-permute"}
    assert b["matmul"]["time_ms"] == pytest.approx(0.012)
    assert b["pallas"]["count"] == 4
    # fractions over total device time: 12/36, 8/36 x3
    assert b["matmul"]["frac"] == pytest.approx(1 / 3, abs=1e-3)
    assert rep["device_time_ms"] == pytest.approx(0.036)


def test_fixture_provenance_join_names_the_source_line():
    """Every collective's device time lands on the file:line that issued
    it — the PR 7 provenance machinery joined through instruction names."""
    rows = {r["kind"]: r for r in _fixture_report()["collectives"]}
    assert rows["collective-permute"]["loc"] == \
        "dtf_tpu/ops/collective_matmul.py:120"
    assert rows["all-reduce"]["loc"] == "dtf_tpu/core/train.py:396"
    assert rows["collective-permute"]["hlo_ops"] == \
        ["collective-permute.2"]


def test_fixture_overlap_efficiency():
    """The ring is half-hidden under the Pallas kernel; the all-reduce has
    nothing concurrent — the two ends of the latency-hiding scale."""
    ov = _fixture_report()["overlap"]
    assert ov["collective-permute"]["hidden_frac"] == pytest.approx(0.5)
    assert ov["all-reduce"]["hidden_frac"] == 0.0
    assert ov["collective-permute"]["exposed_ms"] == pytest.approx(0.004)


def test_fixture_step_timing_and_device_mfu():
    rep = _fixture_report(model_flops_per_step=1e6, peak_flops=1e12,
                          n_devices=2)
    st = rep["steps"]
    assert st["n"] == 2
    assert st["step_wall_ms_mean"] == pytest.approx(0.01)
    assert st["device_busy_frac"] == pytest.approx(0.8)
    # 1e6 flops / (1e-5 s * 1e12 flop/s * 2 devices)
    assert rep["mfu_device"] == pytest.approx(0.05)


def test_unattributed_collective_without_site_map():
    rep = profile_mod.analyze(_fixture_trace())    # no HLO text supplied
    assert all(r["loc"] == "<unattributed>" for r in rep["collectives"])
    assert rep["buckets"]     # bucketing must not depend on the join


# --------------------------------------------------------------------------
# categorize + interval machinery
# --------------------------------------------------------------------------

def test_categorize():
    c = profile_mod.categorize
    assert c("dot.3") == "matmul"
    assert c("convolution.1") == "matmul"
    assert c("loop_add_fusion.2") == "fusion"
    assert c("dot_reduce_fusion") == "matmul"   # dot-rooted fusion = MXU
    assert c("all-reduce.17") == "all-reduce"
    assert c("all-gather-start.2") == "all-gather"
    assert c("reduce-scatter.1") == "reduce-scatter"
    assert c("collective-permute-done") == "collective-permute"
    assert c("custom-call.4", "") == "other"
    assert c("tpu_custom_call.flash_fwd") == "pallas"
    assert c("copy.2") == "data"
    assert c("rng-bit-generator") == "other"
    # the backend's hlo_category stat wins when informative
    assert c("fusion.9", "convolution") == "matmul"


def test_interval_union_and_cover():
    u = profile_mod._union([(5, 9), (0, 3), (2, 4), (9, 9)])
    assert u == [(0, 4), (5, 9)]
    assert profile_mod._covered((1, 6), u) == 4      # [1,4) + [5,6)
    assert profile_mod._covered((10, 12), u) == 0
    assert profile_mod._total(u) == 8


def test_base_op_name():
    f = profile_mod.base_op_name
    assert f("all-reduce.12") == "all-reduce"
    assert f("all-gather-start.2") == "all-gather"
    assert f("dot") == "dot"


# --------------------------------------------------------------------------
# instruction_sites — the shared source-anchoring helper
# --------------------------------------------------------------------------

def test_instruction_sites_from_hlo_text():
    sites = instruction_sites(HLO_TEXT)
    assert sites["all-reduce.1"]["loc"] == "dtf_tpu/core/train.py:396"
    assert sites["all-reduce.1"]["op"] == "all-reduce"
    assert sites["all-reduce.1"]["bytes"] == 64 * 64 * 4
    assert sites["collective-permute.2"]["op"] == "collective-permute"
    assert "dot.1" not in sites          # collectives only


def test_profile_site_map_merges_programs():
    other = ('  %all-gather.9 = f32[8]{0} all-gather(f32[1]{0} %x), '
             'metadata={op_name="x" source_file="/q/dtf_tpu/core/comms.py"'
             ' source_line=7}\n')
    m = profile_site_map([HLO_TEXT, other])
    assert m["all-gather.9"]["loc"] == "dtf_tpu/core/comms.py:7"
    assert "all-reduce.1" in m


# --------------------------------------------------------------------------
# tolerant degradation — no TF / no trace / no per-op events
# --------------------------------------------------------------------------

def test_load_trace_missing_dir_degrades(tmp_path):
    trace, reason = xplane.load_trace(str(tmp_path / "nope"))
    assert trace is None and reason


def test_parse_logdir_degrades_to_reason(tmp_path):
    rep = profile_mod.parse_logdir(str(tmp_path))
    assert rep["n_op_events"] == 0
    assert "degraded" in rep


def test_analyze_empty_trace_degrades():
    rep = profile_mod.analyze(TraceData())
    assert "degraded" in rep
    assert rep["buckets"] == {}
    assert rep["collectives"] == []


def test_trace_without_step_windows_still_buckets():
    """No StepTraceAnnotation (a bare start/stop_trace window): every op
    event passes the window filter and buckets normally; the steps/mfu
    section is simply absent."""
    tr = _fixture_trace()
    bare = TraceData(op_events=tr.op_events)
    rep = profile_mod.analyze(bare)
    assert rep["buckets"]["matmul"]["count"] == 4
    assert "steps" not in rep and "mfu_device" not in rep


def test_events_outside_step_windows_are_excluded():
    """Stale pre-window events (buffered warmup work shows up in real CPU
    traces) must not pollute the per-step buckets."""
    tr = _fixture_trace()
    stale = OpEvent(name="dot.99", plane="/device:TPU:0", line="XLA Ops",
                    start_ps=500 * 1_000_000, dur_ps=1_000_000)
    polluted = TraceData(op_events=tr.op_events + [stale],
                         step_windows=tr.step_windows)
    rep = profile_mod.analyze(polluted)
    assert rep["buckets"]["matmul"]["count"] == 4    # stale dot excluded


def test_find_trace_dir_picks_newest_session(tmp_path):
    for ts in ("2026_01_01", "2026_02_02"):
        d = tmp_path / "plugins" / "profile" / ts
        d.mkdir(parents=True)
        (d / "host.xplane.pb").write_bytes(build_bytes())
    assert xplane.find_trace_dir(str(tmp_path)).endswith("2026_02_02")
    trace, reason = xplane.load_trace(str(tmp_path))
    assert trace is not None and len(trace.step_windows) == 2


# --------------------------------------------------------------------------
# chrome-trace export
# --------------------------------------------------------------------------

def test_export_chrome_trace_device_and_requests(tmp_path):
    tr = _fixture_trace()
    tc = TraceCollector(clock=iter([0.0, 0.001, 0.002, 0.004]).__next__)
    tc.complete("request", cat="request", tid=7, t0_us=0.0, t1_us=900.0,
                args={"rid": 7})
    path = str(tmp_path / "trace.json")
    doc = profile_mod.export_chrome_trace(
        path, trace=tr, request_events=tc.events, meta={"source": "test"})
    with open(path) as f:
        loaded = json.load(f)
    assert loaded == doc
    evs = loaded["traceEvents"]
    # 16 device ops + 2 step windows + 1 request lifecycle
    assert len(evs) == 19
    pids = {e["pid"] for e in evs}
    assert {"/device:TPU:0", "/device:TPU:1", "steps", "serve"} <= pids
    req = [e for e in evs if e["pid"] == "serve"]
    assert req[0]["tid"] == 7 and req[0]["dur"] == 900.0
    cats = {e["cat"] for e in evs if e["pid"].startswith("/device")}
    assert "collective-permute" in cats and "matmul" in cats


# --------------------------------------------------------------------------
# TraceCollector mechanics
# --------------------------------------------------------------------------

def test_trace_collector_bounded_and_ordered():
    clk = iter(x * 0.001 for x in range(100))
    tc = TraceCollector(keep=4, clock=clk.__next__)
    for i in range(6):
        tc.instant(f"e{i}", cat="t", tid=i)
    assert len(tc) == 4
    assert tc.dropped == 2
    names = [e["name"] for e in tc.events]
    assert names == ["e2", "e3", "e4", "e5"]     # oldest evicted first


def test_trace_collector_span_records_duration():
    clk = iter([0.0, 0.010, 0.025])              # t0, span start, span end
    tc = TraceCollector(clock=clk.__next__)
    with tc.span("work", cat="t", tid="a", args={"k": 1}):
        pass
    (ev,) = tc.events
    assert ev["ph"] == "X" and ev["ts"] == pytest.approx(10_000.0)
    assert ev["dur"] == pytest.approx(15_000.0)
    assert ev["args"] == {"k": 1}


# --------------------------------------------------------------------------
# ProfilerHook hands its trace dir to the parser
# --------------------------------------------------------------------------

def _session_logdir(tmp_path) -> str:
    d = tmp_path / "profile" / "plugins" / "profile" / "0001"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(build_bytes())
    return str(tmp_path / "profile")


def test_profiler_hook_analyze_writes_device_profile(tmp_path):
    from dtf_tpu.hooks import ProfilerHook
    from dtf_tpu.telemetry import Telemetry

    logdir = _session_logdir(tmp_path)
    tel = Telemetry(watchdog=False, n_devices=2, peak_flops=1e12)
    hook = ProfilerHook(logdir, start_step=None,
                        hlo_text_fn=lambda: HLO_TEXT, telemetry=tel,
                        flops_per_step=1e6)
    hook._analyze_window()
    assert hook.last_profile["buckets"]["matmul"]["count"] == 4
    rows = {r["kind"]: r["loc"] for r in hook.last_profile["collectives"]}
    assert rows["all-reduce"] == "dtf_tpu/core/train.py:396"
    with open(os.path.join(logdir, "device_profile.json")) as f:
        on_disk = json.load(f)
    assert on_disk["overlap"]["collective-permute"]["hidden_frac"] == 0.5
    # the telemetry RunReport carries the compact summary
    rep = tel.report()
    assert rep["device_profile"]["steps"]["device_busy_frac"] == 0.8
    assert "mfu_device" in rep["device_profile"]


def test_profiler_hook_analyze_degrades_without_trace(tmp_path):
    from dtf_tpu.hooks import ProfilerHook

    hook = ProfilerHook(str(tmp_path / "empty"), start_step=None)
    hook._analyze_window()
    assert "degraded" in hook.last_profile


def test_profiler_hook_analyze_never_raises(tmp_path):
    from dtf_tpu.hooks import ProfilerHook

    hook = ProfilerHook(_session_logdir(tmp_path), start_step=None,
                        hlo_text_fn=lambda: (_ for _ in ()).throw(
                            RuntimeError("boom")))
    hook._analyze_window()                      # must not raise
    assert "degraded" in hook.last_profile


# --------------------------------------------------------------------------
# the report CLI: one JSON line over the fixture
# --------------------------------------------------------------------------

def test_report_cli_one_json_line(tmp_path, cpu_sim_subprocess_env):
    import subprocess

    logdir = _session_logdir(tmp_path)
    hlo = tmp_path / "step.hlo.txt"
    hlo.write_text(HLO_TEXT)
    chrome = tmp_path / "chrome.json"
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.telemetry", "report",
         f"--logdir={logdir}", f"--hlo={hlo}", f"--chrome={chrome}",
         "--flops=1e6", "--peak=1e12", "--n-devices=2"],
        cwd=ROOT, env=cpu_sim_subprocess_env, capture_output=True,
        text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = proc.stdout.strip().splitlines()[-1]
    rep = json.loads(line)
    assert rep["telemetry"] == "device_profile"
    assert rep["mfu_device"] == pytest.approx(0.05)
    assert rep["collectives"][0]["loc"].startswith("dtf_tpu/")
    assert json.load(open(chrome))["traceEvents"]


@pytest.mark.slow
def test_profiler_hook_gpt_window_round_trip_on_cpu_sim(tmp_path):
    """ISSUE 8 acceptance, hook edition: a ProfilerHook window inside a
    real Trainer.fit over the GPT train step captures, closes, and parses
    into buckets + provenance rows — with the train-step compile fence
    still pinned at 1 (the twin-step HLO lowering must not retrace the
    live program)."""
    import subprocess

    from _dtf_env import cpu_sim_env
    from dtf_tpu.telemetry.xplane import CPU_OP_TRACE_FLAG

    logdir = str(tmp_path / "profile")
    env = cpu_sim_env(8, os.environ)
    env["XLA_FLAGS"] += " " + CPU_OP_TRACE_FLAG
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "_profile_worker.py"),
         logdir],
        env=env, capture_output=True, text=True, timeout=900, cwd=ROOT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = next(ln for ln in reversed(proc.stdout.strip().splitlines())
                if ln.startswith("PROFILE_WORKER "))
    out = json.loads(line[len("PROFILE_WORKER "):])
    assert out["trace_counts"] == {"train_step": 1}
    prof = out["profile"]
    # boundary-straddling step annotations are dropped by the profiler;
    # the interior ones must round-trip
    assert prof["n_steps"] >= 2 and prof["buckets"]
    assert any(r["loc"].startswith("dtf_tpu/") for r in prof["collectives"])
    assert out["run_report_has_device_profile"]
    with open(os.path.join(logdir, "device_profile.json")) as f:
        assert json.load(f)["buckets"]
