"""Session hooks — successor of tf.train.SessionRunHook and the chief's hook set.

Reference capability replaced (SURVEY.md §3.4): ``MonitoredTrainingSession``
installs ``CheckpointSaverHook``, ``SummarySaverHook``, ``StopAtStepHook``,
``LoggingTensorHook`` on the chief. The same lifecycle — begin / before-step /
after-step / end — is kept so reference users find the familiar shape, but
hooks run on host Python around an async dispatched step, so they cost
nothing on the device timeline unless they block on results.
"""

from __future__ import annotations

import json
import logging
import os
import signal
import time
from typing import Any, Mapping

import jax

from dtf_tpu._hostio import atomic_replace
from dtf_tpu.checkpoint import Checkpointer
from dtf_tpu.metrics import MetricWriter

PyTree = Any

log = logging.getLogger("dtf_tpu")


class StopTraining(Exception):
    """Raised by a hook to end the loop (the ``should_stop()`` successor)."""


class Hook:
    #: goodput bucket the trainer attributes this hook's wall time to when
    #: telemetry is on (dtf_tpu/telemetry/accounting.GOODPUT_BUCKETS)
    telemetry_bucket = "hooks"

    def begin(self, state: PyTree) -> None: ...

    def before_step(self, step: int) -> None: ...

    def after_step(self, step: int, state: PyTree,
                   metrics: Mapping[str, jax.Array]) -> None: ...

    def end(self, state: PyTree) -> None: ...


class StopAtStepHook(Hook):
    """``tf.train.StopAtStepHook`` equivalent (last_step semantics)."""

    def __init__(self, last_step: int):
        self.last_step = last_step

    def before_step(self, step):
        # A resumed state may already be at/past last_step; stop before
        # running an extra step (MonitoredSession checks should_stop()
        # before run(), not only after).
        if step >= self.last_step:
            raise StopTraining

    def after_step(self, step, state, metrics):
        if step >= self.last_step:
            raise StopTraining


class LoggingHook(Hook):
    """Step/loss/throughput logging — ``LoggingTensorHook`` + ``print`` path.

    Materializing ``metrics`` blocks on the async step, so this is also the
    loop's backpressure point; every_n trades log freshness for overlap.

    Throughput accounting (docs/OBSERVABILITY.md): when the launcher passes
    ``tokens_per_step`` the log line gains ``tokens_per_sec``, and with
    ``model_flops_per_step`` (the analytic 6N·tokens rule or an AOT
    ``cost_analysis()`` count) it gains ``mfu`` vs. ``peak_flops`` — both
    pure host arithmetic on the steps/sec it already computes. Defaults
    keep the historical scalars exactly. ``telemetry`` (optional) receives
    the materialized scalars so the crash flight recorder can report the
    last known loss without ever blocking on a device value itself.
    """

    telemetry_bucket = "logging"

    def __init__(self, writer: MetricWriter, every_n: int = 10,
                 lr_schedule=None, *, tokens_per_step=None,
                 model_flops_per_step=None, peak_flops=None,
                 throughput_name: str = "tokens_per_sec",
                 telemetry=None):
        #: optional optax schedule (or plain float) to surface the current
        #: learning rate next to the loss — the schedule position equals
        #: the global step (one optimizer update per step; grad-accum
        #: applies the accumulated mean gradient in that single update)
        self.writer = writer
        self.every_n = every_n
        self.lr_schedule = lr_schedule
        self.tokens_per_step = tokens_per_step
        self.model_flops_per_step = model_flops_per_step
        self.throughput_name = throughput_name
        if peak_flops is None:
            # model_flops_per_step covers the whole global batch, so the
            # MFU denominator is the MESH's peak, not one chip's. The
            # per-chip peak is the running device's published one; on the
            # CPU there is none and no mfu is logged.
            if telemetry is not None:
                chip, n = telemetry.peak_flops, telemetry.n_devices
            else:
                from dtf_tpu.telemetry.accounting import device_peak_flops

                chip, n = device_peak_flops(), jax.device_count()
            peak_flops = chip * n if chip else None
        self.peak_flops = peak_flops
        self.telemetry = telemetry
        self._t0 = None
        self._last_logged = None

    def begin(self, state):
        self._t0 = time.perf_counter()
        self._last_logged = int(state.step)

    def after_step(self, step, state, metrics):
        if step % self.every_n:
            return
        now = time.perf_counter()
        steps_done = step - self._last_logged
        sps = steps_done / max(now - self._t0, 1e-9)
        self._t0, self._last_logged = now, step
        scalars = {k: float(v) for k, v in metrics.items()}
        scalars["steps_per_sec"] = sps
        if self.tokens_per_step:
            scalars[self.throughput_name] = sps * self.tokens_per_step
        if self.model_flops_per_step and self.peak_flops:
            scalars["mfu"] = (sps * self.model_flops_per_step
                              / self.peak_flops)
        if self.lr_schedule is not None:
            lr = self.lr_schedule
            scalars["lr"] = float(lr(step) if callable(lr) else lr)
        if self.telemetry is not None:
            self.telemetry.note_scalars(step, scalars)
        self.writer.write_scalars(step, scalars)

    def end(self, state):
        self.writer.flush()


class CheckpointHook(Hook):
    """``CheckpointSaverHook`` equivalent: periodic async sharded saves,
    final save + barrier at end. Orbax dedupes by save_interval_steps."""

    telemetry_bucket = "checkpoint"

    def __init__(self, ckpt: Checkpointer, every_n: int = 100):
        self.ckpt = ckpt
        self.every_n = every_n

    def after_step(self, step, state, metrics):
        if step % self.every_n == 0:
            self.ckpt.save(step, state)

    def end(self, state):
        self.ckpt.save(int(state.step), state, force=True)
        self.ckpt.wait()


class PublishHook(Hook):
    """Weight publishing for the train→serve hot-swap loop (ISSUE 14):
    every ``every_n`` steps the current params subtree is published as
    the next monotone version into the publish dir
    (:class:`dtf_tpu.publish.ParamPublisher` — atomic manifest, content
    digest; a crash mid-publish leaves the previous version intact).

    Rides next to :class:`CheckpointHook`, not instead of it: a publish
    is weights-only for serving replicas, the checkpoint stays the full
    resume state. ``publisher=None`` is the non-chief fake-host idiom
    (PreemptionHook's ``ckpt=None``): the hook is inert. The final
    params are published at ``end()`` unless the last periodic publish
    already covered that step. A publish failure WARNs and keeps
    training — serving staleness must never take the trainer down."""

    telemetry_bucket = "checkpoint"

    def __init__(self, publisher, every_n: int = 100):
        if every_n < 1:
            raise ValueError(f"every_n={every_n} must be >= 1")
        self.publisher = publisher
        self.every_n = every_n
        self._last_published_step: int | None = None

    @staticmethod
    def _params_of(state):
        params = getattr(state, "params", None)
        if params is None and isinstance(state, dict):
            params = state.get("params")
        if params is None:
            raise ValueError(
                "PublishHook needs a state with a params subtree "
                "(TrainState attribute or dict key)")
        return params

    def _publish(self, step, state) -> None:
        from dtf_tpu.fault.inject import InjectedCrash

        try:
            self.publisher.publish(step, self._params_of(state))
            self._last_published_step = step
        except InjectedCrash:
            # the crash_in_publish chaos verb: the host DIES mid-publish
            # (that is the scenario) — swallowing it here would turn the
            # atomicity proof into a no-op, and end() must not re-publish
            # from fit's finally (a SIGKILL'd host runs no end hooks;
            # this in-process twin has to match it)
            self.publisher = None
            raise
        except Exception as e:  # noqa: BLE001 — a failed publish leaves
            # the previous version serving; training continues
            log.warning(
                "publish at step %d failed (%s: %.200s); the previous "
                "published version keeps serving", step,
                type(e).__name__, e)

    def after_step(self, step, state, metrics):
        if self.publisher is not None and step % self.every_n == 0:
            self._publish(step, state)

    def end(self, state):
        if self.publisher is None:
            return
        step = getattr(state, "step", None)
        if step is None and isinstance(state, dict):
            step = state.get("step")      # dict states publish too —
            #                               _params_of supports them
        step = int(step) if step is not None else None
        if step is not None and step != self._last_published_step:
            self._publish(step, state)


class PreemptionHook(Hook):
    """Graceful-preemption checkpointing: SIGTERM → save → clean stop.

    Cloud TPU / GKE evictions deliver SIGTERM with a grace window before the
    SIGKILL; the reference era's ``_RecoverableSession`` only covered the
    crash side. The handler just sets a flag (async-signal-safe); the loop
    notices at the next step boundary, force-saves the exact current step,
    blocks until the write is durable, and raises :class:`StopTraining` —
    the relaunch then resumes with zero lost steps (vs. up to
    ``checkpoint_every - 1`` lost on a plain kill; that crash path is
    exercised by tests/test_fault_injection.py).

    Multi-host: the save is a COLLECTIVE Orbax write, and the signal lands
    at different instants on different hosts — acting on the local flag
    alone would have hosts calling save() at different steps and
    deadlocking. So under ``jax.process_count() > 1`` the flag is
    OR-allgathered at each step boundary: collectives match in program
    order, so every host evaluates the k-th sync at the same step and they
    all agree to save that step (the cluster manager signals every host of
    an evicted slice, so the OR converges within one step).

    Must be constructed and ``begin()``-run in the main thread (CPython's
    ``signal.signal`` requirement). Restores the previous handlers at
    ``end()`` so short-lived Trainers don't leak handler state.
    """

    # NOT "checkpoint": this hook's steady-state cost is the periodic
    # flag-sync allgather, a backpressure readback absorbing host
    # run-ahead (accounting.BACKPRESSURE_BUCKETS) — charging it as
    # overhead would invert multi-host goodput
    telemetry_bucket = "preempt_sync"

    def __init__(self, ckpt: Checkpointer | None, signals=(signal.SIGTERM,),
                 check_every: int = 8, *, on_preempt=None,
                 save_retries: int = 2, save_backoff_s: float = 0.25):
        #: multi-host flag-sync cadence: the OR-allgather is a device
        #: collective whose result the host blocks on, so syncing every
        #: step would forfeit async-dispatch run-ahead; every ``check_every``
        #: steps bounds the reaction delay (grace windows are ~30 s, steps
        #: are ms–s) while amortizing the barrier. Single-host runs react
        #: at the very next step regardless.
        #:
        #: ``ckpt=None``: stop cleanly on SIGTERM without saving — the
        #: non-chief fake-host processes of a CPU-sim cluster (the chief
        #: owns the shared checkpoint dir; docs/RESILIENCE.md).
        #: ``on_preempt(step)``: controller notification, called AFTER the
        #: save is durable (the last link of the SIGTERM chain: flight
        #: dump → checkpoint → notify); errors are swallowed — a broken
        #: notifier must not undo a clean preemption exit.
        #: ``save_retries``/``save_backoff_s``: Checkpointer.save_durable
        #: knobs — a transient save failure inside the grace window
        #: retries, then falls back to the previous checkpoint cleanly.
        self.ckpt = ckpt
        self.signals = tuple(signals)
        self.check_every = max(1, check_every)
        self.on_preempt = on_preempt
        self.save_retries = save_retries
        self.save_backoff_s = save_backoff_s
        self.preempted = False
        self._prev: dict = {}
        self._multiprocess = False

    def begin(self, state):
        self._multiprocess = jax.process_count() > 1
        for s in self.signals:
            self._prev[s] = signal.signal(s, self._on_signal)

    def _on_signal(self, signum, frame):
        self.preempted = True

    def after_step(self, step, state, metrics):
        flag = self.preempted
        if self._multiprocess:
            if step % self.check_every:
                # between sync points even a locally-set flag must wait:
                # acting alone would desync the collective order
                return
            import numpy as np
            from jax.experimental import multihost_utils

            flag = bool(multihost_utils.process_allgather(
                np.asarray([self.preempted])).any())
        if flag:
            saved = True
            if self.ckpt is not None:
                saved = self.ckpt.save_durable(
                    step, state, retries=self.save_retries,
                    backoff_s=self.save_backoff_s)
            if saved and self.on_preempt is not None:
                # notify ONLY after the save is durable: the marker means
                # "step N is the resume point" — a failed save must not
                # advertise a step that only exists on the older
                # checkpoint (save_durable already logged the failure).
                try:
                    self.on_preempt(step)
                except Exception:  # noqa: BLE001 — see __init__ docstring
                    pass
            raise StopTraining

    def end(self, state):
        for s, prev in self._prev.items():
            signal.signal(s, prev)
        self._prev.clear()


class EvalHook(Hook):
    """Periodic evaluation — the reference-era validation-while-training
    pattern (an eval pass between ``mon_sess.run`` steps), as a hook.

    ``eval_step(state, batch) -> metrics`` is a compiled step from
    :func:`dtf_tpu.core.train.make_eval_step`; ``batches()`` returns an
    iterable of host batches for one eval sweep (metrics are averaged);
    ``place_batch`` maps them onto the mesh.
    """

    telemetry_bucket = "eval"

    def __init__(self, eval_step, batches, writer: MetricWriter,
                 every_n: int = 100, *, place_batch=None):
        self.eval_step = eval_step
        self.batches = batches
        self.writer = writer
        self.every_n = every_n
        self.place_batch = place_batch or (lambda b: b)
        self._last_eval_step = None

    def _run(self, step, state):
        totals, n = {}, 0
        for batch in self.batches():
            metrics = self.eval_step(state, self.place_batch(batch))
            for k, v in metrics.items():
                totals[k] = totals.get(k, 0.0) + float(v)
            n += 1
        if n:
            self.writer.write_scalars(step,
                                      {k: v / n for k, v in totals.items()})
        self._last_eval_step = step

    def after_step(self, step, state, metrics):
        if step % self.every_n == 0:
            self._run(step, state)

    def end(self, state):
        # after_step may already have evaluated at the final step; a second
        # sweep would write duplicate scalars and double end-of-run cost.
        if self._last_eval_step != int(state.step):
            self._run(int(state.step), state)


class ProfilerHook(Hook):
    """``tf.profiler``/Timeline equivalent: capture an XPlane trace window.

    Two trigger modes, composable in one hook:

    - **scheduled** (the original): a window of ``num_steps`` opening at
      ``start_step``; ``start_step=None`` disables it.
    - **on-demand** (live-run profiling without a restart): send
      ``trigger_signal`` (e.g. ``SIGUSR1``) to the process, or ``touch``
      ``trigger_file`` — checked at step boundaries every ``check_every``
      steps (an ``os.path.exists`` per check, nothing per step) and
      CONSUMED (unlinked) when it fires, so one touch = one window. The
      next window opens at the following step boundary and runs
      ``num_steps``. Repeatable: touch/kill again after a window closes.

    The signal handler only sets a flag (async-signal-safe, the
    PreemptionHook discipline) and chains nothing — profiling is
    process-local. Construct + ``begin()`` in the main thread when using
    ``trigger_signal`` (CPython's ``signal.signal`` rule); previous
    handlers are restored at ``end()``.

    **Device-time attribution** (``analyze=True``, the default): when a
    window closes, the hook hands its trace dir to the XPlane parser
    (:mod:`dtf_tpu.telemetry.profile`) and writes the per-category
    device-time buckets / overlap efficiency / per-collective provenance
    report to ``<logdir>/device_profile.json`` (also kept as
    ``self.last_profile`` and fed to ``telemetry`` for the RunReport).
    ``hlo_text_fn`` — optional ``() -> str | list[str]`` returning the
    profiled program's OPTIMIZED HLO text, called lazily at parse time —
    enables the ``file:line`` provenance join. The stock launchers do
    NOT pass it (lowering a twin step just for provenance costs a full
    compile); their windows bucket without attribution, and the join
    runs where the HLO is already in hand: ``python -m dtf_tpu.telemetry
    report --hlo=...`` over the same trace dir. The parse runs on the host
    after the window closed: it adds zero work to traced steps and
    degrades to a reason dict when the proto bindings or per-op events
    are absent.
    """

    telemetry_bucket = "profile"

    def __init__(self, logdir: str, start_step: int | None = 10,
                 num_steps: int = 5, *, trigger_file: str | None = None,
                 trigger_signal: int | None = None, check_every: int = 16,
                 analyze: bool = True, hlo_text_fn=None, telemetry=None,
                 flops_per_step=None):
        self.logdir = logdir
        self.start = start_step
        self.num_steps = num_steps
        self.stop = (start_step + num_steps
                     if start_step is not None else None)
        self.trigger_file = trigger_file
        self.trigger_signal = trigger_signal
        self.check_every = max(1, check_every)
        self.analyze = analyze
        self.hlo_text_fn = hlo_text_fn
        self.telemetry = telemetry
        self.flops_per_step = flops_per_step
        self.last_profile: dict | None = None
        self._active = False
        self._signaled = False
        self._sched_done = start_step is None
        self._prev_handler = None

    def begin(self, state):
        if self.trigger_signal is not None:
            try:
                self._prev_handler = signal.signal(
                    self.trigger_signal, self._on_signal)
            except ValueError:
                # not the main thread: file trigger still works, the
                # signal trigger is simply unavailable here
                self._prev_handler = None

    def _on_signal(self, signum, frame):
        self._signaled = True

    def _triggered(self, step) -> bool:
        if self._signaled:
            self._signaled = False
            return True
        if self.trigger_file and step % self.check_every == 0:
            if os.path.exists(self.trigger_file):
                try:
                    os.unlink(self.trigger_file)   # consume: one touch,
                except OSError:                    # one window
                    pass
                return True
        return False

    def before_step(self, step):
        # non-chief processes must not even POLL the triggers: _triggered
        # consumes the (logdir-shared) trigger file, so a non-chief
        # polling first would eat the chief's window
        if jax.process_index() != 0:
            return
        # `>=` + once-flag, not `==`: an on-demand window open ACROSS the
        # scheduled start must not swallow the scheduled window forever
        # (it covers those steps, so the request is satisfied), and a
        # resume past start_step must not wait for a step that never comes
        sched_due = not self._sched_done and step >= self.start
        if self._active:
            if sched_due:
                self._sched_done = True
            return
        if sched_due or self._triggered(step):
            self._sched_done = self._sched_done or sched_due
            jax.profiler.start_trace(self.logdir)
            self._active = True
            self.stop = step + self.num_steps

    def after_step(self, step, state, metrics):
        if self._active and self.stop is not None and step >= self.stop:
            jax.profiler.stop_trace()
            self._active = False
            self._analyze_window()

    def _analyze_window(self) -> None:
        """Parse the just-closed window's XPlane dump (see class docstring).
        Never raises: a parse failure becomes a ``degraded`` reason in the
        report — profiling must not be able to crash the training run."""
        if not self.analyze:
            return
        try:
            from dtf_tpu.telemetry import profile as profile_mod

            site_map = None
            if self.hlo_text_fn is not None:
                from dtf_tpu.analysis.provenance import profile_site_map

                site_map = profile_site_map(self.hlo_text_fn())
            kw = {}
            if self.flops_per_step and self.telemetry is not None:
                kw = {"model_flops_per_step": self.flops_per_step,
                      "peak_flops": self.telemetry.peak_flops,
                      "n_devices": self.telemetry.n_devices}
            report = profile_mod.parse_logdir(
                self.logdir, site_map=site_map, **kw)
            path = os.path.join(self.logdir, "device_profile.json")
            # atomic: other processes read this file while windows
            # keep closing
            atomic_replace(path, json.dumps(report, indent=1))
        except Exception as e:  # noqa: BLE001 — see docstring
            report = {"degraded": f"profile parse failed: "
                                  f"{type(e).__name__}: {e}"}
        self.last_profile = report
        if self.telemetry is not None:
            self.telemetry.note_device_profile(report)

    def end(self, state):
        if self._active:
            jax.profiler.stop_trace()
            self._active = False
            self._analyze_window()
        if self._prev_handler is not None:
            signal.signal(self.trigger_signal, self._prev_handler)
            self._prev_handler = None
