"""Run-wide telemetry façade — one object wired through trainer, hooks,
launchers and the serve scheduler (docs/OBSERVABILITY.md).

Composes the four pillars:

- :class:`~dtf_tpu.telemetry.spans.SpanRecorder` — step-phase spans,
- :class:`~dtf_tpu.telemetry.accounting.GoodputTracker` + the MFU helpers,
- :class:`~dtf_tpu.telemetry.fence.CompileFence` — the compile fence,
- :class:`~dtf_tpu.telemetry.flight.FlightRecorder` +
  :class:`~dtf_tpu.telemetry.flight.StallWatchdog` — the flight recorder,

and emits ONE RunReport dict at the end (the bench.py one-JSON-line
idiom): per-phase p50/p99, tokens/sec, MFU, goodput buckets, trace/compile
counts. ``merge_artifact`` folds reports into a ``TELEMETRY.json`` under
the run's log directory (bounded list, newest last).

Lifecycle: the Trainer calls ``start()``/``stop()`` around ``fit`` (signal
hook + watchdog live only inside that window); the launcher calls
``report()`` once after training and prints it. All hot-path entry points
(``note_step``, ``account``) are pure host arithmetic — the no-added-
readbacks contract is regression-tested.
"""

from __future__ import annotations

import json
import os
import signal
import threading
import time
from typing import Mapping, Optional

from dtf_tpu._hostio import atomic_replace
from dtf_tpu.telemetry.accounting import (GoodputTracker,
                                          device_peak_flops)
from dtf_tpu.telemetry.fence import CompileFence, program_compile
from dtf_tpu.telemetry.flight import FlightRecorder, StallWatchdog
from dtf_tpu.telemetry.spans import SpanRecorder, step_annotation


class Telemetry:
    """Per-run telemetry state (see module docstring).

    ``out_dir`` is where the flight recorder writes ``postmortem.json``
    (None = in-memory only). ``watchdog=False`` disables the stall thread
    (unit tests drive ``StallWatchdog.check`` directly).
    """

    def __init__(self, out_dir: Optional[str] = None, *,
                 keep_steps: int = 64, stall_factor: float = 10.0,
                 min_stall_s: float = 60.0, watchdog: bool = True,
                 peak_flops: Optional[float] = None,
                 n_devices: int = 1, clock=time.monotonic, wall=time.time):
        self.out_dir = out_dir
        self.spans = SpanRecorder()
        self.fence = CompileFence()
        self.goodput = GoodputTracker()
        self.flight = FlightRecorder(
            os.path.join(out_dir, "postmortem.json") if out_dir else None,
            keep=keep_steps,
            # liveness for the elastic run controller (dtf_tpu/fault):
            # written by the watchdog thread, so it exists exactly when
            # the stall detector runs — the two signals the host-lost vs
            # run-wedged verdict needs come from one place
            heartbeat_path=(os.path.join(out_dir, "heartbeat.json")
                            if out_dir else None),
            clock=clock, wall=wall)
        self.watchdog = StallWatchdog(
            self.flight, factor=stall_factor, min_stall_s=min_stall_s) \
            if watchdog else None
        #: per-CHIP peak × the mesh's device count is the MFU denominator:
        #: model_flops_per_step covers the whole global batch, so quoting
        #: it against one chip's peak would overstate MFU by n_devices.
        #: Left unset it is the running device's published peak — None on
        #: the CPU, where no mfu is reported at all.
        self.peak_flops = (peak_flops if peak_flops is not None
                           else device_peak_flops())
        self.n_devices = max(int(n_devices), 1)
        self.tokens_per_step: Optional[float] = None
        self.model_flops_per_step: Optional[float] = None
        self.throughput_name = "tokens_per_sec"
        self.clock = clock
        #: per-request trace events (serving tier) — None unless a caller
        #: attaches a TraceCollector; recording stays host-clock-only
        self.tracer = None
        #: last ProfilerHook device-profile report (note_device_profile)
        self.device_profile: Optional[dict] = None
        self._t_start: Optional[float] = None
        self._t_stop: Optional[float] = None
        self._steps = 0
        self._last_step: Optional[int] = None
        self._prev_sigterm = None
        self._active = False
        self._compile_accounted = 0.0   # compile_s already in the bucket
        #: per program key, the compiles already filed (file_compiles)
        self._filed: dict[str, int] = {}

    # -------------------------------------------------------- configuration

    def set_throughput_model(self, *, tokens_per_step: Optional[float] = None,
                             model_flops_per_step: Optional[float] = None,
                             throughput_name: Optional[str] = None) -> None:
        """Declare per-step work so the report (and LoggingHook) can turn
        steps/sec into tokens/sec and MFU. Optional: absent, the report
        simply omits those fields. ``throughput_name`` relabels the rate
        key for non-token launchers (``examples_per_sec`` for ResNet/
        WideDeep) so merged report rows stay comparable."""
        if tokens_per_step is not None:
            self.tokens_per_step = float(tokens_per_step)
        if model_flops_per_step is not None:
            self.model_flops_per_step = float(model_flops_per_step)
        if throughput_name is not None:
            self.throughput_name = throughput_name

    # ------------------------------------------------------- compile fence

    def count_traces(self, name: str, fn):
        return self.fence.count_traces(name, fn)

    @property
    def trace_counts(self) -> dict:
        return dict(self.fence.trace_counts)

    def file_compiles(self, keys) -> None:
        """File the latest compile of each program named in ``keys`` (the
        names their traced functions have; ``Program.trace_name``) that
        compiled since this object last filed it: one sample each of
        ``setup_trace``, ``setup_lower``, ``setup_compile`` (seconds) and
        ``setup_cache_hit`` (1.0 where the persistent cache served it) in
        the SpanRecorder. Host dict reads of what the compile fence's
        listener recorded; nothing touches a device."""
        for key in dict.fromkeys(keys):
            found = program_compile(key)
            if found is None or found[0] <= self._filed.get(key, 0):
                continue
            self._filed[key], rec = found
            self.spans.add("setup_trace", rec.trace_s)
            self.spans.add("setup_lower", rec.lower_s)
            self.spans.add("setup_compile", rec.compile_s)
            self.spans.add("setup_cache_hit", rec.cache_hit)

    # ----------------------------------------------------------- lifecycle

    def start(self) -> None:
        """Open the run window: fence listeners, watchdog thread, SIGTERM
        postmortem hook (chained AFTER any already-installed handler, e.g.
        PreemptionHook's — ours dumps, then theirs checkpoints). Files the
        compile of every fenced program that compiled before it
        (:meth:`file_compiles`: a train step warmed up ahead of ``fit``)."""
        if self._active:
            return
        self._active = True
        self.file_compiles(self.fence.trace_counts)
        if self._t_start is None:
            self._t_start = self.clock()
        self._t_stop = None
        self.fence.start()
        if self.watchdog is not None:
            self.watchdog.start()
        if threading.current_thread() is threading.main_thread():
            try:
                self._prev_sigterm = signal.signal(
                    signal.SIGTERM, self._on_sigterm)
            except (ValueError, OSError):    # non-main ctx despite check
                self._prev_sigterm = None

    def _on_sigterm(self, signum, frame):
        self.flight.dump("sigterm")
        prev = self._prev_sigterm
        if callable(prev):
            prev(signum, frame)
            return
        # non-callable previous disposition (SIG_DFL/SIG_IGN): restore it
        # and re-deliver — a telemetry hook must never make the process
        # immune to SIGTERM (SIG_DFL then terminates as it should have;
        # SIG_IGN keeps ignoring, the operator's prior choice).
        try:
            signal.signal(signum,
                          prev if prev is not None else signal.SIG_DFL)
            self._prev_sigterm = None
            os.kill(os.getpid(), signum)
        except (ValueError, OSError):
            pass

    def open_wall(self) -> None:
        """Pin the run's wall-clock start NOW (idempotent). The Trainer
        calls this at ``fit`` entry, BEFORE restore and hook ``begin`` —
        seconds accounted into goodput buckets there must fall inside the
        wall window or ``report()`` would subtract out-of-window overhead
        from in-window wall and understate goodput."""
        if self._t_start is None:
            self._t_start = self.clock()

    def close_wall(self) -> None:
        """Extend the wall-clock end to NOW — the Trainer's ``finally``
        calls this after the end hooks (final checkpoint save + barrier),
        which run after ``stop()`` for the LIFO signal-handler teardown
        yet still account into the checkpoint bucket."""
        self._t_stop = self.clock()

    def stop(self) -> None:
        if not self._active:
            return
        self._active = False
        self._t_stop = self.clock()
        if self.watchdog is not None:
            self.watchdog.stop()
        self.fence.stop()
        if self._prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, self._prev_sigterm)
            except (ValueError, OSError):
                pass
            self._prev_sigterm = None

    # ------------------------------------------------------------ hot path

    def note_step(self, step: int, durations: Mapping[str, float]) -> None:
        """One completed loop iteration: host floats only (the zero-added-
        readbacks contract). Feeds the phase spans AND the flight ring."""
        for name, v in durations.items():
            self.spans.add(name.removesuffix("_s"), v)
        self.flight.record_step(step, durations)
        self._steps += 1
        self._last_step = step

    def account(self, bucket: str, seconds: float) -> None:
        self.goodput.account(bucket, seconds)

    def note_scalars(self, step: int, scalars: Mapping[str, float]) -> None:
        self.flight.note_scalars(step, scalars)

    def dump_postmortem(self, reason: str,
                        extra: Optional[Mapping] = None) -> dict:
        return self.flight.dump(reason, extra)

    def add_postmortem_provider(self, name: str, fn) -> None:
        """Register a flight-recorder context provider (host facts only —
        see :meth:`FlightRecorder.add_provider`); the serve tier hangs its
        in-flight request ids + slot ages here."""
        self.flight.add_provider(name, fn)

    def note_device_profile(self, report: Mapping) -> None:
        """Record a ProfilerHook window's parsed device profile; a compact
        summary rides the RunReport (full detail stays in the hook's
        ``device_profile.json``)."""
        self.device_profile = dict(report)

    # -------------------------------------------------------------- report

    def wall_s(self) -> float:
        if self._t_start is None:
            return 0.0
        end = self._t_stop if self._t_stop is not None else self.clock()
        return end - self._t_start

    def report(self, extra: Optional[Mapping] = None) -> dict:
        """The RunReport dict — emit with ``json.dumps`` as one line.
        Safe to call more than once (a mid-run progress line + finish)."""
        wall = self.wall_s()
        # compile seconds observed by jax.monitoring feed the goodput
        # bucket here (not incrementally: the listener thread must stay
        # allocation-free). Account only the DELTA since the last report —
        # a repeat call must neither double-count nor freeze the bucket
        # at its first-report value.
        compile_s = self.fence.compile_s
        delta = compile_s - self._compile_accounted
        if delta > 0:
            self.goodput.account("compile", delta)
            self._compile_accounted += delta
        out = {
            "telemetry": "run_report",
            "steps": self._steps,
            "last_step": self._last_step,
            "wall_s": round(wall, 3),
            "phases": self.spans.rollup(),
            "trace_counts": self.trace_counts,
            "compile_events": self.fence.compile_events,
            "compile_s": round(compile_s, 3),
            "monitoring_available": self.fence.monitoring_available,
            "goodput_buckets": self.goodput.report(wall),
            "flight": {"records": len(self.flight.records),
                       "dumps": self.flight.dumps},
        }
        if wall > 0 and self._steps:
            sps = self._steps / wall
            out["steps_per_sec"] = round(sps, 4)
            if self.tokens_per_step:
                out[self.throughput_name] = round(
                    sps * self.tokens_per_step, 1)
            if self.model_flops_per_step:
                out["model_flops_per_step"] = self.model_flops_per_step
                out["n_devices"] = self.n_devices
                if self.peak_flops:
                    out["mfu"] = round(
                        sps * self.model_flops_per_step
                        / (self.peak_flops * self.n_devices), 8)
        if self.flight.last_scalars:
            out["last_scalars"] = dict(self.flight.last_scalars)
        if self.device_profile is not None:
            dp = self.device_profile
            out["device_profile"] = {
                k: dp[k] for k in ("buckets", "overlap", "steps",
                                   "mfu_device", "device_time_ms",
                                   "degraded") if k in dp}
        if extra:
            out.update(extra)
        return out

    def finish(self, extra: Optional[Mapping] = None) -> dict:
        """stop() + report() — the launcher's one call after fit."""
        self.stop()
        return self.report(extra)

    # ------------------------------------------------------------- helpers

    @staticmethod
    def step_annotation(step: int):
        return step_annotation(step)


def merge_artifact(path: str, report: Mapping, *, keep_runs: int = 20,
                   meta: Optional[Mapping] = None) -> dict:
    """Fold one RunReport into the ``TELEMETRY.json`` at ``path``.

    ``{"runs": [...]}`` with the newest LAST, bounded at ``keep_runs``
    (round timestamps ride in ``meta``); a malformed existing file is
    replaced, never crashed on — the artifact writer must not be able to
    fail the run it is reporting on.
    """
    data: dict = {"runs": []}
    try:
        with open(path) as f:
            prev = json.load(f)
        if isinstance(prev, dict) and isinstance(prev.get("runs"), list):
            data = prev
    except (OSError, ValueError):
        pass
    entry = dict(report)
    if meta:
        entry.update(meta)
    data["runs"] = (data["runs"] + [entry])[-keep_runs:]
    # atomic: sibling tooling (bench fences, the sentinel's pathspec
    # commits) reads the artifact while runs append to it
    atomic_replace(path, json.dumps(data, indent=1))
    return data
