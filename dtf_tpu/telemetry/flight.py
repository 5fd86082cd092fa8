"""Crash flight recorder + stall watchdog — the postmortem half of telemetry.

A run that hangs dies with nothing on disk: the host loop blocks inside
a device call and the run's last N steps of context evaporate with the
process. The flight recorder keeps those N
steps in a host-side ring — step number, wall timestamp, per-phase
durations, host RSS, the last hook-materialized scalars — and dumps them
as ONE JSON line (the bench.py contract) on crash, stall, or SIGTERM, plus
nothing at all in the steady state.

Deliberate constraint: the dump path touches NO device API. A postmortem
fires exactly when the backend is wedged; a ``device.memory_stats()`` call
from the watchdog thread would hang the postmortem the same way the step
hung the loop.
Host RSS + host timings are what we can always have.

The stall watchdog is a daemon thread: if no step completes within
``max(min_stall_s, factor × p99 recent step time)``, it dumps a
``stall`` postmortem (once per stall episode — a completing step re-arms
it). It detects the hang; it does not try to recover it (relaunch is the
cluster manager's job, resume is the checkpointer's).
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Mapping, Optional

from dtf_tpu._hostio import append_line, atomic_replace
from dtf_tpu.metrics import quantile


def _rss_mb() -> Optional[float]:
    try:
        import resource

        # linux ru_maxrss is KB
        return round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0, 1)
    except Exception:
        return None


class FlightRecorder:
    """Ring buffer of the last ``keep`` step records + postmortem dumps.

    ``path`` is the postmortem file; each dump appends one JSON line (a
    stall dump followed by a crash dump both survive). ``clock``/``wall``
    are injectable for deterministic tests.
    """

    def __init__(self, path: Optional[str] = None, *, keep: int = 64,
                 heartbeat_path: Optional[str] = None,
                 clock=time.monotonic, wall=time.time):
        self.path = path
        self.keep = keep
        #: liveness file for the elastic run controller (dtf_tpu/fault):
        #: written atomically by the stall watchdog's poll thread — NOT by
        #: the hot path — with the last completed step and the stalled
        #: flag. None = no heartbeat (the default for bare recorders).
        self.heartbeat_path = heartbeat_path
        self.clock = clock
        self.wall = wall
        self.records: collections.deque = collections.deque(maxlen=keep)
        self.last_scalars: dict = {}
        self.last_step_t: Optional[float] = None   # clock() domain
        self.dumps = 0
        #: postmortem context providers (add_provider): host-fact callables
        #: merged into every dump under "context" — the serve tier
        #: registers its in-flight request ids + per-slot ages here. The
        #: NO-device-API constraint extends to providers: they run while
        #: the backend may be wedged, so host state only.
        self._providers: dict[str, object] = {}
        # REENTRANT: the SIGTERM postmortem handler runs dump() on the
        # main thread between bytecodes — if the signal lands inside
        # record_step's critical section (every step), a plain Lock would
        # self-deadlock the handler against its own thread and make the
        # process immune to SIGTERM. RLock lets the same-thread dump
        # proceed (the in-flight record is in a consistent-enough state:
        # deque.append is atomic under the GIL).
        self._lock = threading.RLock()

    # ------------------------------------------------------------ recording

    def record_step(self, step: int, durations: Mapping[str, float]) -> None:
        """One completed loop iteration — host facts only (a device value
        here would be a blocking readback in the hot path)."""
        rec = {"step": step, "t": round(self.wall(), 3)}
        rec.update({k: round(v, 6) for k, v in durations.items()})
        rss = _rss_mb()
        if rss is not None:
            rec["rss_mb"] = rss
        with self._lock:
            self.records.append(rec)
            self.last_step_t = self.clock()

    def note_scalars(self, step: int, scalars: Mapping[str, float]) -> None:
        """Last metrics a hook chose to materialize (LoggingHook feeds this
        at its own cadence) — the loss the postmortem can report without
        the recorder ever blocking on a device value itself."""
        with self._lock:
            self.last_scalars = {"step": int(step),
                                 **{k: float(v) for k, v in scalars.items()}}

    def step_durations_s(self) -> list:
        """Recent whole-iteration durations (for the stall threshold)."""
        with self._lock:
            return [r["step_s"] for r in self.records if "step_s" in r]

    def add_provider(self, name: str, fn) -> None:
        """Register a postmortem context provider: ``fn() -> dict`` of
        HOST facts (no device API — it runs against a possibly-wedged
        backend), merged into every dump under ``context[name]``. A
        provider that raises is reported as its error string instead of
        masking the postmortem (dump() never raises). Re-registering a
        name replaces it; ``fn=None`` removes it."""
        with self._lock:
            if fn is None:
                self._providers.pop(name, None)
            else:
                self._providers[name] = fn

    # ------------------------------------------------------------ heartbeat

    def write_heartbeat(self, *, stalled: bool = False,
                        extra: Optional[Mapping] = None) -> None:
        """One atomic liveness record (tmp + rename so the controller can
        never read a torn write). Host facts only, never raises — it runs
        on the watchdog thread against a possibly-wedged backend. A wedged
        loop keeps heartbeating (the thread is alive) with ``stalled:
        true`` and a frozen ``step`` — exactly the signature the
        controller's run-wedged verdict keys on; a SIGKILL'd host simply
        stops writing. ``extra`` merges caller facts into the record —
        the serve tier's :class:`dtf_tpu.serve.client.Heartbeat` stamps
        its fleet panel (completed/queue/quarantines) here so a serving
        process exposes the same liveness surface as a trainer."""
        path = self.heartbeat_path
        if not path:
            return
        with self._lock:
            step = self.records[-1]["step"] if self.records else None
        rec = {"t": round(self.wall(), 3), "pid": os.getpid(),
               "step": step, "stalled": bool(stalled)}
        if extra:
            rec.update(extra)
        try:
            atomic_replace(path, json.dumps(rec))
        except OSError:
            pass

    # ----------------------------------------------------------------- dump

    def dump(self, reason: str, extra: Optional[Mapping] = None) -> dict:
        """Append one postmortem JSON line; returns the record. Never
        raises — the dump path runs inside except/signal/watchdog contexts
        where a secondary failure would mask the primary one."""
        with self._lock:
            post = {
                "telemetry": "postmortem",
                "reason": reason,
                "t": round(self.wall(), 3),
                "pid": os.getpid(),
                "n_records": len(self.records),
                "records": list(self.records),
                "last_scalars": dict(self.last_scalars),
            }
            rss = _rss_mb()
            if rss is not None:
                post["rss_mb"] = rss
            if self._providers:
                ctx = {}
                for name, fn in self._providers.items():
                    try:
                        ctx[name] = fn()
                    except Exception as e:  # noqa: BLE001 — a provider
                        # failure must not mask the primary postmortem
                        ctx[name] = {"provider_error": repr(e)[:200]}
                post["context"] = ctx
            if extra:
                post.update(extra)
            self.dumps += 1
        if self.path:
            try:
                append_line(self.path, json.dumps(post))
            except OSError:
                pass
        return post


class StallWatchdog:
    """Daemon thread: dump a ``stall`` postmortem when no step completes
    inside the adaptive threshold (see module docstring).

    ``check(now)`` holds all the logic and is called directly by tests;
    the thread just polls it. One dump per stall episode: a new step
    completion re-arms the trigger.
    """

    def __init__(self, flight: FlightRecorder, *, factor: float = 10.0,
                 min_stall_s: float = 60.0, poll_s: float = 1.0,
                 on_stall=None):
        self.flight = flight
        self.factor = factor
        self.min_stall_s = min_stall_s
        self.poll_s = poll_s
        self.on_stall = on_stall     # extra callback (tests, launchers)
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._fired_at: Optional[float] = None   # last_step_t when dumped

    def threshold_s(self) -> float:
        # p99 of recent iteration times, not the median: in the sync-free
        # loop most iterations are ms-scale dispatches while the periodic
        # readback/eval/checkpoint iterations run seconds-to-minutes — a
        # median-based bar would flag every such legitimate pause as a
        # stall. The first long pause of a run is only covered by
        # min_stall_s: set it above the longest expected hook pause.
        slow = quantile(self.flight.step_durations_s(), 0.99)
        return max(self.min_stall_s,
                   self.factor * slow if slow is not None else 0.0)

    def check(self, now: Optional[float] = None) -> bool:
        """True when a stall postmortem was dumped by THIS call."""
        last = self.flight.last_step_t
        if last is None:           # nothing completed yet: startup/compile
            return False
        if self._fired_at == last:
            return False           # already reported this episode
        now = self.flight.clock() if now is None else now
        waited = now - last
        thresh = self.threshold_s()
        if waited < thresh:
            return False
        self._fired_at = last
        post = self.flight.dump("stall", {
            "stalled_for_s": round(waited, 3),
            "stall_threshold_s": round(thresh, 3)})
        if self.on_stall is not None:
            try:
                self.on_stall(post)
            except Exception:
                pass
        return True

    # ------------------------------------------------------------ lifecycle

    def stalled_now(self) -> bool:
        """True while the current stall episode is unresolved (fired and
        no step has completed since)."""
        return (self._fired_at is not None
                and self._fired_at == self.flight.last_step_t)

    def start(self) -> None:
        if self._thread is not None:
            return
        self._stop.clear()
        # first heartbeat BEFORE the first poll interval: the controller's
        # startup-timeout clock stops the moment liveness appears, and
        # compile time shouldn't eat into it
        self.flight.write_heartbeat(stalled=False)

        def run():
            while not self._stop.wait(self.poll_s):
                self.check()
                # liveness every poll: a wedged loop keeps heartbeating
                # with stalled=true (this thread is alive even when the
                # main thread is stuck inside a device call); only a dead
                # process goes silent
                self.flight.write_heartbeat(stalled=self.stalled_now())

        self._thread = threading.Thread(
            target=run, name="dtf-stall-watchdog", daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
