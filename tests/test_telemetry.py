"""Run-wide telemetry (ISSUE 5): step-phase spans, MFU/goodput accounting,
the training compile fence, and the crash flight recorder.

The two contracts that anchor this file:

- **the training recompile fence** — ``Trainer.trace_counts`` pinned at 1
  per program across a multi-step fit (the training twin of
  tests/test_serve.py's fence), with the jax.monitoring compile-event
  cross-check;
- **zero added blocking readbacks** — telemetry-ON fit performs exactly
  the same O(1) host casts as telemetry-OFF (the PR 3 counter-instrumented
  idiom): observability must not re-serialize the sync-free loop.
"""

import json
import os
import signal

import jax
import numpy as np
import optax
import pytest

from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import shard_batch
from dtf_tpu.hooks import Hook, LoggingHook, ProfilerHook, StopAtStepHook
from dtf_tpu.loop import Trainer
from dtf_tpu.metrics import MetricWriter, quantile
from dtf_tpu.telemetry import Telemetry, merge_artifact
from dtf_tpu.telemetry.flight import FlightRecorder, StallWatchdog

from tests.test_train import linear_init, linear_loss, make_batch


def build(mesh, telemetry=None):
    tx = optax.adam(0.05)
    state, shardings = tr.create_train_state(
        linear_init, tx, jax.random.PRNGKey(0), mesh)
    step = tr.make_train_step(linear_loss, tx, mesh, shardings,
                              telemetry=telemetry)
    return state, step


def batches(n):
    return (make_batch(seed=i) for i in range(n))


# --------------------------------------------------------------------------
# pillar 3: the training compile fence
# --------------------------------------------------------------------------

def test_trainer_trace_counts_pinned_steady_state(mesh8):
    """The training twin of test_serve's recompile fence: one trace for the
    step program across a multi-step fit — and, where jax.monitoring
    observes compiles at all, ZERO new backend compiles after the warm
    lap (steady-state churn through fresh host batches must not re-lower
    anything)."""
    tel = Telemetry(watchdog=False)
    state, step = build(mesh8, telemetry=tel)
    trainer = Trainer(step, mesh8, telemetry=tel)

    # warm lap: the one legitimate trace + compile
    state = trainer.fit(state, batches(100), max_steps=2)
    assert trainer.trace_counts == {"train_step": 1}
    traces0, compiles0 = tel.fence.snapshot()

    state = trainer.fit(state, batches(100), max_steps=10)
    assert int(state.step) == 10
    assert trainer.trace_counts == {"train_step": 1}, (
        f"steady-state retrace: {trainer.trace_counts}")
    traces1, compiles1 = tel.fence.snapshot()
    assert traces1 == traces0
    if compiles0:   # listener demonstrably observes compiles → assert flat
        assert compiles1 == compiles0, (
            f"{compiles1 - compiles0} backend compiles during steady state")


def test_trainer_without_telemetry_has_empty_trace_counts(mesh8):
    state, step = build(mesh8)
    assert Trainer(step, mesh8).trace_counts == {}


@pytest.mark.parametrize("name", ["train_step", "eval_step"])
def test_count_traces_names_the_wrapper_after_the_program(name):
    """``jax.jit`` names a program after the function it is given, and the
    name is what every trace, xprof view and ``XLA Modules`` event shows: a
    fenced step must not be called ``wrapped``."""
    tel = Telemetry(watchdog=False)
    counted = tel.count_traces(name, lambda x: x + 1)
    assert counted.__name__ == name and counted.__qualname__ == name
    assert jax.jit(counted).lower(1.0).as_text().startswith(
        f"module @jit_{name} ")
    assert tel.trace_counts == {name: 1}


def test_fenced_train_step_lowers_as_jit_train_step(mesh8):
    """The program the trainer runs under telemetry is ``jit_train_step``
    (the benchmark's ``step_device_ms_p50`` and a person reading a trace
    both find it by that name), and naming it did not add a trace."""
    tel = Telemetry(watchdog=False)
    state, step = build(mesh8, telemetry=tel)
    lowered = step.lower(state, shard_batch(make_batch(), mesh8))
    assert lowered.as_text().startswith("module @jit_train_step ")
    assert "HloModule jit_train_step" in lowered.compile().as_text()
    assert tel.trace_counts == {"train_step": 1}


def test_serve_programs_lower_as_jit_decode_fn_and_jit_prefill_fn():
    """The names the ledger's serve traces carry and the benchmark's
    ``decode_device_ms_p50`` / ``prefill_chunk_device_ms_p50`` match:
    pinned here, so that renaming the engine's step bodies fails a test
    and not a metric."""
    import jax.numpy as jnp

    from dtf_tpu.models import gpt
    from dtf_tpu.serve.engine import program_table

    programs, _ = program_table(gpt.GPTConfig.tiny(dtype=jnp.float32),
                                n_slots=2, max_len=16, prefill_chunk=4)
    assert programs["decode"].lower().as_text().startswith(
        "module @jit_decode_fn ")
    assert programs["prefill"].lower().as_text().startswith(
        "module @jit_prefill_fn ")


# --------------------------------------------------------------------------
# the sync-free invariant: telemetry adds zero blocking readbacks
# --------------------------------------------------------------------------

class _CastCounter:
    """Scalar whose int()/float() casts are recorded — the PR 3 idiom: on
    a real device array those casts are blocking readbacks."""

    def __init__(self, v, casts):
        self.v = v
        self.casts = casts

    def __int__(self):
        self.casts.append("int")
        return self.v

    def __float__(self):
        self.casts.append("float")
        return float(self.v)


def _fake_fit(n, telemetry, hooks=()):
    casts = []

    class FakeState:
        def __init__(self, v):
            self.step = _CastCounter(v, casts)

    def fake_step(state, batch):
        return FakeState(state.step.v + 1), {"loss": _CastCounter(1, casts)}

    t = Trainer(fake_step, mesh=None, place_batch=lambda b: b,
                prefetch=2, hooks=list(hooks), telemetry=telemetry)
    out = t.fit(FakeState(0), iter(range(1000)), max_steps=n)
    return len(casts), out


def test_telemetry_on_adds_zero_blocking_readbacks():
    """Telemetry-on fit casts exactly as often as telemetry-off — O(1) per
    fit (the resume sync), never O(steps), and it never touches metrics."""
    off3, _ = _fake_fit(3, None)
    off30, _ = _fake_fit(30, None)
    tel = Telemetry(watchdog=False)
    on3, _ = _fake_fit(3, tel)
    on30, out = _fake_fit(30, Telemetry(watchdog=False))
    assert out.step.v == 30
    assert off3 == off30 == on3 == on30, (off3, off30, on3, on30)
    assert on30 <= 2
    # and the phases were genuinely recorded while staying readback-free
    roll = tel.spans.rollup()
    for phase in ("data_wait", "dispatch", "hooks", "step"):
        assert roll[phase]["count"] == 3, (phase, roll[phase])


# --------------------------------------------------------------------------
# pillar 1: step-phase spans + rollups
# --------------------------------------------------------------------------

def test_run_report_phases_mfu_goodput(mesh8, tmp_path):
    """One RunReport with per-phase p50/p99, throughput + MFU from the
    declared per-step work, and goodput buckets that include the hook
    attribution (logging bucket from LoggingHook wall time)."""
    tel = Telemetry(out_dir=str(tmp_path / "tel"), watchdog=False,
                    peak_flops=1e12)
    tel.set_throughput_model(tokens_per_step=64,
                             model_flops_per_step=1e9)
    state, step = build(mesh8, telemetry=tel)
    writer = MetricWriter(also_log=False)
    trainer = Trainer(
        step, mesh8,
        hooks=[LoggingHook(writer, 2, tokens_per_step=64,
                           model_flops_per_step=1e9, telemetry=tel),
               StopAtStepHook(6)],
        telemetry=tel)
    trainer.fit(state, batches(100))
    report = tel.finish()
    json.dumps(report)                       # must be one serializable line
    assert report["steps"] == 6 and report["last_step"] == 6
    for phase in ("data_wait", "h2d", "dispatch", "hooks", "step"):
        roll = report["phases"][phase]
        assert {"count", "total_s", "mean_s", "p50_s", "p99_s"} <= set(roll)
        assert roll["p99_s"] >= roll["p50_s"] >= 0.0
    assert report["tokens_per_sec"] > 0
    assert 0.0 <= report["mfu"] < 1.0
    g = report["goodput_buckets"]
    assert 0.0 <= g["goodput"] <= 1.0
    assert "logging_s" in g and g["total_s"] > 0
    # the flight ring saw every step, and LoggingHook fed it scalars
    assert report["flight"]["records"] == 6
    assert report["last_scalars"]["step"] == 6
    assert "mfu" in report["last_scalars"]


def test_goodput_bucket_attribution(mesh8):
    """Hook wall time lands in the hook's declared bucket."""
    import time

    class SlowEvalish(Hook):
        telemetry_bucket = "eval"

        def after_step(self, step, state, metrics):
            time.sleep(0.005)

    tel = Telemetry(watchdog=False)
    state, step = build(mesh8, telemetry=tel)
    Trainer(step, mesh8, hooks=[SlowEvalish(), StopAtStepHook(4)],
            telemetry=tel).fit(state, batches(100))
    assert tel.goodput.buckets["eval"] >= 4 * 0.005
    rep = tel.finish()
    assert rep["goodput_buckets"]["eval_s"] >= 0.02


def test_mfu_divides_by_device_count_and_throughput_name():
    """model_flops_per_step covers the global batch, so MFU's denominator
    is the MESH's peak (per-chip × n_devices) — an 8-chip run must not
    report 8× the truth. Non-token launchers relabel the rate key."""
    def run(n_devices):
        t = [0.0]
        tel = Telemetry(watchdog=False, n_devices=n_devices,
                        peak_flops=1e12, clock=lambda: t[0])
        tel.set_throughput_model(tokens_per_step=64,
                                 model_flops_per_step=1e9,
                                 throughput_name="examples_per_sec")
        tel.open_wall()
        t[0] += 1.0
        tel.note_step(1, {"step_s": 1.0})
        tel.close_wall()
        return tel.report()

    r1, r8 = run(1), run(8)
    assert r1["mfu"] == pytest.approx(1e9 / 1e12)
    assert r8["mfu"] == pytest.approx(1e9 / 8e12)
    assert r8["n_devices"] == 8
    assert r8["examples_per_sec"] == pytest.approx(64.0)
    assert "tokens_per_sec" not in r8


def test_cpu_run_reports_no_mfu():
    """The peak comes from the running device's row of DEVICE_PEAKS; the
    CPU has none, so neither the RunReport nor LoggingHook names an mfu
    (a utilization against a chip the run never touched)."""
    t = [0.0]
    tel = Telemetry(watchdog=False, clock=lambda: t[0])
    assert tel.peak_flops is None
    tel.set_throughput_model(tokens_per_step=64, model_flops_per_step=1e9)
    tel.open_wall()
    t[0] += 1.0
    tel.note_step(1, {"step_s": 1.0})
    tel.close_wall()
    rep = tel.report()
    assert rep["tokens_per_sec"] > 0 and "mfu" not in rep
    hook = LoggingHook(MetricWriter(also_log=False), 1,
                       model_flops_per_step=1e9)
    assert hook.peak_flops is None


def test_device_peaks_table_is_keyed_by_device_kind():
    from dtf_tpu.telemetry import accounting

    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    assert accounting.device_peaks(Dev("cpu", "cpu")) is None
    v5e = accounting.device_peaks(Dev("tpu", "TPU v5 lite"))
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="TPU v99"):
        accounting.device_peaks(Dev("tpu", "TPU v99"))


def test_logging_hook_peak_derived_from_telemetry_mesh():
    """With no explicit peak_flops, LoggingHook's MFU denominator comes
    from the telemetry object's per-chip peak × device count."""
    tel = Telemetry(watchdog=False, n_devices=4, peak_flops=1e12)
    hook = LoggingHook(MetricWriter(also_log=False), 1,
                       model_flops_per_step=1e9, telemetry=tel)
    assert hook.peak_flops == pytest.approx(4e12)


def test_wall_window_covers_out_of_loop_overheads():
    """Restore (before start) and end hooks (after stop) account into
    goodput buckets; the wall window must cover them — open_wall/close_wall
    around fit — or report() subtracts out-of-window seconds from
    in-window wall and a long restore reports goodput 0 on a healthy run."""
    t = [0.0]
    tel = Telemetry(watchdog=False, clock=lambda: t[0])
    tel.open_wall()                            # fit entry
    t[0] += 300.0
    tel.account("restore", 300.0)              # pre-start restore
    tel.start()
    t[0] += 200.0
    tel.note_step(1, {"step_s": 200.0})
    tel.stop()
    t[0] += 50.0
    tel.account("checkpoint", 50.0)            # end hooks' final save
    tel.close_wall()
    g = tel.report()["goodput_buckets"]
    assert g["total_s"] == pytest.approx(550.0)
    assert g["productive_s"] == pytest.approx(200.0)
    assert g["goodput"] == pytest.approx(200.0 / 550.0, abs=1e-3)


# --------------------------------------------------------------------------
# pillar 4: flight recorder + stall watchdog + SIGTERM
# --------------------------------------------------------------------------

def _postmortems(path):
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_flight_recorder_dumps_postmortem_on_crash(tmp_path):
    """A crash mid-run leaves a JSON postmortem holding the last steps'
    records (the acceptance-criteria injection test)."""
    tel = Telemetry(out_dir=str(tmp_path), watchdog=False, keep_steps=8)

    class _Step:
        def __init__(self, v):
            self.v = v

        def __int__(self):
            return self.v

    class FakeState:
        def __init__(self, v):
            self.step = _Step(v)

    def fake_step(state, batch):
        if state.step.v + 1 == 4:
            raise RuntimeError("injected device loss")
        return FakeState(state.step.v + 1), {}

    t = Trainer(fake_step, mesh=None, place_batch=lambda b: b,
                telemetry=tel)
    state0 = FakeState(0)
    with pytest.raises(RuntimeError, match="injected"):
        t.fit(state0, iter(range(100)))

    posts = _postmortems(tmp_path / "postmortem.json")
    assert len(posts) == 1
    post = posts[0]
    assert post["reason"] == "crash"
    assert "injected device loss" in post["error"]
    assert [r["step"] for r in post["records"]] == [1, 2, 3]
    assert all("step_s" in r and "dispatch_s" in r for r in post["records"])


def test_stall_watchdog_adaptive_threshold(tmp_path):
    """No step within max(min_stall, factor x median step time) → ONE
    stall dump; a completing step re-arms the trigger. Driven through an
    injected clock — no sleeps, no thread."""
    now = [0.0]
    fl = FlightRecorder(str(tmp_path / "post.json"), keep=8,
                        clock=lambda: now[0], wall=lambda: now[0])
    wd = StallWatchdog(fl, factor=3.0, min_stall_s=2.0)
    for i in range(4):
        now[0] += 1.0
        fl.record_step(i + 1, {"step_s": 1.0})
    assert wd.threshold_s() == 3.0            # factor x median(1.0) vs 2.0
    now[0] += 2.9
    assert not wd.check()
    now[0] += 0.2                              # 3.1s since the last step
    assert wd.check()
    assert not wd.check()                      # once per episode
    posts = _postmortems(tmp_path / "post.json")
    assert len(posts) == 1 and posts[0]["reason"] == "stall"
    assert posts[0]["stalled_for_s"] >= 3.0
    now[0] += 1.0
    fl.record_step(5, {"step_s": 1.0})         # a step completes: re-armed
    now[0] += 10.0
    assert wd.check()


def test_sigterm_dump_chains_previous_handler(tmp_path):
    """Telemetry's SIGTERM hook dumps the postmortem AND forwards to the
    previously-installed handler (PreemptionHook keeps its checkpoint)."""
    seen = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: seen.append(s))
    try:
        tel = Telemetry(out_dir=str(tmp_path), watchdog=False)
        tel.start()
        tel.flight.record_step(1, {"step_s": 0.1})
        signal.raise_signal(signal.SIGTERM)
        tel.stop()
        assert seen == [signal.SIGTERM]        # chained handler ran
        posts = _postmortems(tmp_path / "postmortem.json")
        assert [p["reason"] for p in posts] == ["sigterm"]
        # stop() restored the chained handler, not ours
        assert signal.getsignal(signal.SIGTERM) is not tel._on_sigterm
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_sigterm_dump_reentrant_under_record_lock(tmp_path):
    """A SIGTERM can land while the main thread is INSIDE record_step's
    critical section (it runs every step); the handler's dump() then
    re-acquires the recorder lock on the same thread. The lock must be
    reentrant or the handler deadlocks and the process becomes immune to
    SIGTERM — the exact hang the flight recorder exists to diagnose."""
    fr = FlightRecorder(str(tmp_path / "pm.json"))
    fr.record_step(1, {"step_s": 0.1})
    with fr._lock:                 # simulate the mid-record_step signal
        post = fr.dump("sigterm")
    assert post["reason"] == "sigterm" and fr.dumps == 1


# --------------------------------------------------------------------------
# satellite: LoggingHook tokens/sec + MFU
# --------------------------------------------------------------------------

class CaptureWriter:
    def __init__(self):
        self.seen = {}

    def write_scalars(self, step, scalars):
        self.seen[step] = scalars

    def flush(self):
        pass


def test_logging_hook_reports_tokens_and_mfu(mesh8):
    state, step = build(mesh8)
    w = CaptureWriter()
    Trainer(step, mesh8,
            hooks=[LoggingHook(w, 2, tokens_per_step=64,
                               model_flops_per_step=1e12, peak_flops=2e12),
                   StopAtStepHook(4)]).fit(state, batches(100))
    assert w.seen, "no scalars captured"
    for s, scalars in w.seen.items():
        sps = scalars["steps_per_sec"]
        np.testing.assert_allclose(scalars["tokens_per_sec"], sps * 64,
                                   rtol=1e-6)
        np.testing.assert_allclose(scalars["mfu"], sps * 0.5, rtol=1e-6)


def test_logging_hook_default_scalars_unchanged(mesh8):
    """Without the new kwargs the scalar set is exactly the historical
    one — no tokens_per_sec/mfu keys appear."""
    state, step = build(mesh8)
    w = CaptureWriter()
    Trainer(step, mesh8, hooks=[LoggingHook(w, 2), StopAtStepHook(4)]).fit(
        state, batches(100))
    for scalars in w.seen.values():
        assert "tokens_per_sec" not in scalars and "mfu" not in scalars


# --------------------------------------------------------------------------
# satellite: ProfilerHook on-demand triggers
# --------------------------------------------------------------------------

def test_profiler_hook_trigger_file(mesh8, tmp_path):
    """`touch <trigger>` opens a num_steps window at the next boundary and
    is CONSUMED (one touch = one window); no scheduled start needed."""
    state, step = build(mesh8)
    logdir, trig = tmp_path / "prof", tmp_path / "profile.trigger"
    trig.touch()
    hook = ProfilerHook(str(logdir), start_step=None, num_steps=2,
                        trigger_file=str(trig), check_every=1)
    Trainer(step, mesh8, hooks=[hook, StopAtStepHook(6)]).fit(
        state, batches(100))
    assert list(logdir.rglob("*.xplane.pb")), "no XPlane trace written"
    assert not trig.exists(), "trigger file must be consumed"


def test_profiler_hook_scheduled_survives_on_demand_overlap(mesh8, tmp_path):
    """An on-demand window open ACROSS the scheduled start marks the
    scheduled request satisfied (those steps were profiled) instead of
    deferring it forever; a trigger window that CLOSES before the start
    leaves the scheduled window to fire normally."""
    state, step = build(mesh8)

    # trigger consumed at step 0 opens a 4-step window covering the
    # scheduled start at 3 — run must end with no window left dangling
    logdir, trig = tmp_path / "prof_overlap", tmp_path / "t1"
    trig.touch()
    hook = ProfilerHook(str(logdir), start_step=3, num_steps=4,
                        trigger_file=str(trig), check_every=1)
    Trainer(step, mesh8, hooks=[hook, StopAtStepHook(10)]).fit(
        state, batches(100))
    assert hook._sched_done and not hook._active
    assert list(logdir.rglob("*.xplane.pb"))

    # no overlap: trigger window [0,2] closes, scheduled fires at 6
    state, step = build(mesh8)
    logdir2, trig2 = tmp_path / "prof_seq", tmp_path / "t2"
    trig2.touch()
    opened = []
    hook = ProfilerHook(str(logdir2), start_step=6, num_steps=2,
                        trigger_file=str(trig2), check_every=1)
    orig = hook.before_step

    def spy(s, _orig=orig, _h=hook):
        was = _h._active
        _orig(s)
        if _h._active and not was:
            opened.append(s)
    hook.before_step = spy
    Trainer(step, mesh8, hooks=[hook, StopAtStepHook(10)]).fit(
        state, batches(100))
    assert opened == [0, 6], f"windows opened at {opened}"


def test_profiler_hook_signal_trigger(mesh8, tmp_path):
    """SIGUSR1 mid-run opens a window without any pre-chosen step."""
    state, step = build(mesh8)
    logdir = tmp_path / "prof_sig"

    class Kick(Hook):
        def before_step(self, s):
            if s == 2:
                signal.raise_signal(signal.SIGUSR1)

    hook = ProfilerHook(str(logdir), start_step=None, num_steps=2,
                        trigger_signal=signal.SIGUSR1)
    prev = signal.getsignal(signal.SIGUSR1)
    Trainer(step, mesh8, hooks=[Kick(), hook, StopAtStepHook(6)]).fit(
        state, batches(100))
    assert list(logdir.rglob("*.xplane.pb")), "no XPlane trace written"
    assert signal.getsignal(signal.SIGUSR1) == prev   # restored at end()


# --------------------------------------------------------------------------
# serve scheduler spans
# --------------------------------------------------------------------------

class _StubEngine:
    """Just enough DecodeEngine surface for the Scheduler: fixed 2 slots,
    instant prefill/decode, greedy token stream."""

    n_slots = 2
    max_len = 32
    prefill_chunk = 4

    def n_chunks(self, prompt_len):
        return -(-prompt_len // self.prefill_chunk)

    def prefill_chunk_into(self, slot, prompt, chunk_i, **kw):
        if chunk_i == self.n_chunks(len(prompt)) - 1:
            return 7, False
        return None

    def decode(self):
        return (np.full((self.n_slots,), 7, np.int64),
                np.ones((self.n_slots,), bool))     # done immediately


def test_scheduler_records_serve_spans():
    from dtf_tpu.serve.scheduler import Request, Scheduler

    tel = Telemetry(watchdog=False)
    sched = Scheduler(_StubEngine(), None, telemetry=tel)
    for i in range(3):
        sched.submit(Request(prompt=[1, 2, 3, 4, 5], max_new=2))
    sched.run_until_idle()
    roll = tel.spans.rollup()
    assert roll["serve_prefill_chunk"]["count"] >= 3 * 2  # 2 chunks each
    assert roll["serve_decode"]["count"] >= 1
    stats = sched.stats()
    assert "serve_decode_p50_s" in stats
    assert "serve_prefill_chunk_p99_s" in stats


def test_scheduler_stats_unchanged_without_telemetry():
    from dtf_tpu.serve.scheduler import Request, Scheduler

    sched = Scheduler(_StubEngine(), None)
    sched.submit(Request(prompt=[1, 2, 3], max_new=2))
    sched.run_until_idle()
    stats = sched.stats()
    assert not any(k.startswith("serve_prefill_chunk_") for k in stats)


# --------------------------------------------------------------------------
# srclint: the hot-path readback fence
# --------------------------------------------------------------------------

def test_srclint_fences_hotpath_readbacks(tmp_path):
    from dtf_tpu.analysis import srclint

    pkg = tmp_path / "dtf_tpu"
    pkg.mkdir()
    bad = pkg / "loop.py"
    bad.write_text(
        "class Trainer:\n"
        "    def fit(self, state, batches):\n"
        "        step = int(state.step)\n"          # pre-loop: legal
        "        for batch in batches:\n"
        "            state, m = self.train_step(state, batch)\n"
        "            step = int(state.step)\n"      # hot path: fenced
        "            x = float(m['loss'])\n"        # fenced
        "            y = m['loss'].item()\n"        # fenced
        "        return state\n")
    probs = srclint.lint_file(str(bad))
    assert len([p for p in probs if "hot loop" in p]) == 3, probs
    assert not any(":3:" in p for p in probs)       # pre-loop int() legal

    ok = pkg / "loop_ok.py"    # not named loop.py → rule does not apply
    ok.write_text(bad.read_text())
    os.rename(ok, pkg / "other.py")
    assert not [p for p in srclint.lint_file(str(pkg / "other.py"))
                if "hot loop" in p]

    marked = pkg / "loop.py"
    marked.write_text(
        "class Trainer:\n"
        "    def fit(self, state, batches):\n"
        "        for batch in batches:\n"
        "            state, m = self.train_step(state, batch)\n"
        "            x = float(m['loss'])  # blocking-ok: backpressure\n"
        "        return state\n")
    assert not [p for p in srclint.lint_file(str(marked))
                if "hot loop" in p]


def test_srclint_real_loop_is_clean():
    from dtf_tpu.analysis import srclint

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert srclint.lint_file(os.path.join(root, "dtf_tpu", "loop.py")) == []


# --------------------------------------------------------------------------
# report plumbing
# --------------------------------------------------------------------------

def test_merge_artifact_bounded_and_resilient(tmp_path):
    path = str(tmp_path / "TELEMETRY.json")
    for i in range(25):
        merge_artifact(path, {"telemetry": "run_report", "steps": i},
                       keep_runs=20, meta={"ts": i})
    data = json.load(open(path))
    assert len(data["runs"]) == 20
    assert data["runs"][-1]["steps"] == 24 and data["runs"][0]["steps"] == 5
    # malformed file → replaced, not crashed on
    with open(path, "w") as f:
        f.write("{not json")
    data = merge_artifact(path, {"steps": 99}, meta={})
    assert [r["steps"] for r in data["runs"]] == [99]


def test_quantile_convention():
    assert quantile([], 0.5) is None
    assert quantile([3.0], 0.99) == 3.0
    xs = list(range(100))
    assert quantile(xs, 0.5) == 50
    assert quantile(xs, 0.99) == 98
