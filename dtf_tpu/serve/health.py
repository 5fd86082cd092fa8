"""Replica health — quarantine, probation, and serve-side fault injection.

PR 11 made *training* survive a wedged or lost host; this module is the
same discipline for the PR 6 serve fleet. One wedged replica must not
stall the Router's pump loop, and "the fleet never stops serving" has to
be a tested property, so every replica carries a tiny state machine:

    healthy ──slow ticks──▶ degraded ──more/worse──▶ quarantined
       ▲                        │                         │
       └──── clean tick ────────┘      probation delay    │
       ▲                                                  ▼
       └──── N clean ticks/probes ◀──────────────── probation

- **healthy / degraded** — routable. Degraded replicas (one or more slow
  ticks) lose admission priority but keep serving.
- **quarantined** — NOT routable: the Router's ``_pick`` skips it, its
  in-flight requests are requeued onto survivors, and its ticks stop, so
  a wedged engine is never called again and the pump loop stays fast.
- **probation** — after ``probation_delay_s`` a quarantined replica is
  re-admitted on trial (lowest routing priority; an idle probation
  replica is exercised via ``DecodeEngine.probe`` instead of waiting for
  traffic). ``probation_ticks`` clean ticks promote it back to healthy;
  one slow tick or fault re-quarantines with the delay doubled
  (exponential backoff, capped — the run-controller relaunch idiom).

Slow is the PR 11 stall bar: a tick is slow when its wall time exceeds
``max(min_slow_s, slow_factor × p99 of recent HEALTHY ticks)`` — the p99
baseline deliberately excludes slow ticks so a wedge cannot raise its own
bar. A single tick past ``wedge_s`` skips degraded and quarantines
outright. All host clock arithmetic (injectable ``clock`` for
deterministic tests); zero device readbacks, and the tracker never calls
into an engine itself — a wedged backend cannot hang its own watchdog.

The bottom half is the serve edition of :mod:`dtf_tpu.fault.inject`:
:func:`install_serve_fault` arms a ``DTF_FAULT_INJECT`` serve verb
(``wedge_replica@tick:replica=k`` / ``slow_decode@tick`` /
``poison_request@n``) on a live Router/Scheduler by wrapping engine
methods — the chaos tests and the degraded-fleet bench row drive the REAL
pump through it, the way PR 11's verbs ride the real trainers.

jax-free at module level (the telemetry/tune/fault convention): health is
pure host bookkeeping. docs/RESILIENCE.md walks the serving section.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import logging
import os
import time
from typing import Optional

from dtf_tpu.fault.inject import (InjectedCrash, InjectedPoison,
                                  ServeFaultPlan, corrupt_publish_version)
from dtf_tpu.metrics import quantile

log = logging.getLogger("dtf_tpu")

HEALTHY = "healthy"
DEGRADED = "degraded"
QUARANTINED = "quarantined"
PROBATION = "probation"

#: routing priority per state (``Router._pick`` sorts on this first);
#: quarantined is absent on purpose — it is never a candidate.
_RANK = {HEALTHY: 0, DEGRADED: 1, PROBATION: 2}


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Thresholds of the replica state machine (module docstring).

    Defaults are deliberately conservative for the CPU sim: a legitimate
    prefill-heavy tick on a sim replica can run hundreds of ms, while a
    real wedge is *forever* — ``min_slow_s`` only needs to sit well under
    the caller's patience, not near the median tick.
    """

    slow_factor: float = 20.0      # × p99 of recent healthy ticks
    min_slow_s: float = 5.0        # floor under the adaptive bar
    wedge_s: float = 20.0          # one tick this slow → quarantine now
    degrade_after: int = 1         # consecutive slow ticks → degraded
    quarantine_after: int = 3      # consecutive slow ticks → quarantined
    probation_delay_s: float = 10.0
    probation_backoff: float = 2.0   # delay multiplier per failed probation
    probation_delay_max_s: float = 300.0
    probation_ticks: int = 3       # clean ticks/probes to re-admit fully
    keep: int = 64                 # healthy-tick baseline window

    def __post_init__(self):
        if not 1 <= self.degrade_after <= self.quarantine_after:
            raise ValueError(
                f"need 1 <= degrade_after ({self.degrade_after}) <= "
                f"quarantine_after ({self.quarantine_after})")
        if self.probation_ticks < 1:
            raise ValueError(
                f"probation_ticks={self.probation_ticks} must be >= 1")
        if self.min_slow_s <= 0 or self.wedge_s < self.min_slow_s:
            raise ValueError(
                f"need 0 < min_slow_s ({self.min_slow_s}) <= wedge_s "
                f"({self.wedge_s})")
        if self.probation_backoff < 1.0:
            raise ValueError(
                f"probation_backoff={self.probation_backoff} must be >= 1 "
                "(a shrinking delay would hammer a dead replica)")


@dataclasses.dataclass
class _Replica:
    state: str = HEALTHY
    strikes: int = 0               # consecutive slow ticks
    ok_probation: int = 0          # clean ticks inside this probation
    since: float = 0.0             # clock() of the last transition
    delay_s: float = 0.0           # current quarantine→probation delay
    last_cause: str = ""
    durations: collections.deque = dataclasses.field(
        default_factory=collections.deque)


class HealthTracker:
    """Per-replica state machines + fleet counters (module docstring).

    The Router owns one and feeds it ``note_tick(i, wall_s)`` after every
    replica tick and ``note_fault(i, err)`` on an engine exception; it
    reads back ``routable``/``rank`` for admission and ``state``/
    ``counters`` for stats and postmortems.
    """

    def __init__(self, n_replicas: int, cfg: Optional[HealthConfig] = None,
                 *, clock=time.monotonic, events=None):
        if n_replicas < 1:
            raise ValueError(f"n_replicas={n_replicas} must be >= 1")
        self.cfg = cfg or HealthConfig()
        self.clock = clock
        #: optional fleet EventLog (dtf_tpu/telemetry/events.py) — every
        #: transition verdict lands on the run timeline too.
        self.events = events
        self._r = [
            _Replica(delay_s=self.cfg.probation_delay_s,
                     durations=collections.deque(maxlen=self.cfg.keep))
            for _ in range(n_replicas)]
        self.counters = {"quarantines": 0, "slow_ticks": 0, "faults": 0,
                         "probations": 0, "readmits": 0}
        #: bounded transition log (newest last) — the serve postmortem
        #: names every verdict with its cause, controller-style.
        self.transitions: collections.deque = collections.deque(maxlen=100)

    # ------------------------------------------------------------- verdicts

    def threshold_s(self, i: int) -> float:
        """The slow bar for replica ``i`` — the PR 11 stall idiom over the
        replica's recent HEALTHY tick durations."""
        slow = quantile(list(self._r[i].durations), 0.99)
        return max(self.cfg.min_slow_s,
                   self.cfg.slow_factor * slow if slow is not None else 0.0)

    def note_tick(self, i: int, dur_s: float) -> Optional[str]:
        """One completed replica tick of ``dur_s`` wall seconds. Returns
        the new state on a transition (the Router requeues on
        ``QUARANTINED``), None when nothing changed."""
        h = self._r[i]
        cfg = self.cfg
        thresh = self.threshold_s(i)
        if dur_s < thresh:
            h.durations.append(dur_s)
            h.strikes = 0
            if h.state == PROBATION:
                h.ok_probation += 1
                if h.ok_probation >= cfg.probation_ticks:
                    self.counters["readmits"] += 1
                    h.delay_s = cfg.probation_delay_s       # reset backoff
                    return self._transit(
                        i, HEALTHY,
                        f"probation passed ({cfg.probation_ticks} clean)")
            elif h.state == DEGRADED:
                return self._transit(i, HEALTHY, "recovered")
            return None
        self.counters["slow_ticks"] += 1
        h.strikes += 1
        if dur_s >= cfg.wedge_s:
            cause = f"tick {dur_s:.3f}s >= wedge bar {cfg.wedge_s:.3f}s"
        else:
            cause = (f"tick {dur_s:.3f}s >= threshold {thresh:.3f}s "
                     f"(strike {h.strikes})")
        if (h.state == PROBATION or dur_s >= cfg.wedge_s
                or h.strikes >= cfg.quarantine_after):
            return self._quarantine(i, cause)
        if h.strikes >= cfg.degrade_after and h.state == HEALTHY:
            return self._transit(i, DEGRADED, cause)
        return None

    def note_fault(self, i: int, err: BaseException) -> str:
        """An engine exception with no single owning request (the decode
        path) — quarantine on the spot."""
        self.counters["faults"] += 1
        if self._r[i].state == QUARANTINED:
            return QUARANTINED
        return self._quarantine(i, f"engine fault: {repr(err)[:120]}")

    def quarantine(self, i: int, cause: str) -> str:
        """Forced quarantine (operator/test API — the Router's
        :meth:`~dtf_tpu.serve.router.Router.quarantine` rides this)."""
        if self._r[i].state == QUARANTINED:
            return QUARANTINED
        return self._quarantine(i, cause)

    def _quarantine(self, i: int, cause: str) -> str:
        h = self._r[i]
        if h.state == PROBATION:
            # a failed probation doubles the next wait — the controller's
            # relaunch backoff, serving edition
            h.delay_s = min(h.delay_s * self.cfg.probation_backoff,
                            self.cfg.probation_delay_max_s)
        h.strikes = 0
        h.ok_probation = 0
        self.counters["quarantines"] += 1
        return self._transit(i, QUARANTINED, cause)

    def _transit(self, i: int, state: str, cause: str) -> str:
        h = self._r[i]
        old, h.state = h.state, state
        h.since = self.clock()
        h.last_cause = cause
        self.transitions.append({"replica": i, "from": old, "to": state,
                                 "cause": cause, "t": round(h.since, 3)})
        if self.events is not None:
            # "at" = the tracker's own (injectable) clock: episode
            # durations on the timeline are deltas in THIS domain, while
            # the sink's wall "t" keeps the record ordered against the
            # other subsystems' events
            self.events.emit("health_transition", replica=i, state_from=old,
                             state_to=state, cause=cause,
                             at=round(h.since, 6))
        log.warning("serve replica %d: %s -> %s (%s)", i, old, state, cause)
        return state

    # ------------------------------------------------------------- admission

    def routable(self, i: int) -> bool:
        """May the Router send replica ``i`` work / tick it? Flips a
        quarantined replica whose delay elapsed into PROBATION lazily —
        the tracker needs no thread of its own."""
        h = self._r[i]
        if h.state != QUARANTINED:
            return True
        if self.clock() - h.since >= h.delay_s:
            h.ok_probation = 0
            self.counters["probations"] += 1
            self._transit(i, PROBATION,
                          f"probation after {h.delay_s:.1f}s quarantine")
            return True
        return False

    def rank(self, i: int) -> int:
        """Routing priority (0 best) — degraded after healthy, probation
        last, so trial traffic only lands when the fleet has no better
        home for it."""
        return _RANK.get(self._r[i].state, 3)

    def state(self, i: int) -> str:
        return self._r[i].state

    def states(self) -> list[str]:
        return [h.state for h in self._r]

    def quarantined_eta_s(self) -> Optional[float]:
        """Seconds until the NEXT quarantined replica reaches probation —
        the honest retry-after hint for a fully-quarantined fleet. None
        when nothing is quarantined."""
        now = self.clock()
        etas = [max(0.0, h.delay_s - (now - h.since))
                for h in self._r if h.state == QUARANTINED]
        return min(etas) if etas else None


# ---------------------------------------------------------------------------
# Serve-side fault injection (the chaos half).
# ---------------------------------------------------------------------------

class ServeFaultState:
    """What an installed plan has done so far (tests assert on it)."""

    def __init__(self, plan: ServeFaultPlan):
        self.plan = plan
        self.fired = False
        self.poison_prompt: Optional[tuple] = None


def install_serve_fault(plan: ServeFaultPlan, pump, *, sleep=time.sleep,
                        wedge_s: Optional[float] = None,
                        slow_s: Optional[float] = None,
                        watcher=None, emit=None) -> ServeFaultState:
    """Arm a serve fault on a live Router or Scheduler (``pump``).

    - ``wedge_replica@N[:replica=k]`` — from the target engine's N-th
      decode call on (``decode_begin`` on an engine that
      ``can_run_ahead``, else ``decode``: the call that dispatches every
      step of the tick and every probe), every decode sleeps ``wedge_s``
      (env ``DTF_FAULT_WEDGE_S``, default 0.75): alive but useless, exactly the
      signature the health watchdog must quarantine on.
    - ``slow_decode@N[:replica=k]`` — same shape, shorter ``slow_s``
      sleeps (env ``DTF_FAULT_SLOW_S``, default 0.2): degrades without
      wedging, the tail-latency chaos case.
    - ``poison_request@N`` — the N-th ``submit`` (0-based) is marked; any
      prefill chunk of that request raises :class:`InjectedPoison`
      wherever it lands, even after a requeue. The scheduler must isolate
      it (terminal ``error`` status) without taking the replica down.
    - ``poison_draft@N`` — the N-th submit is marked; while that request
      is RUNNING on a replica, the replica's ``draft_propose`` raises
      :class:`InjectedPoison`. The engine must fall back to plain decode
      (verify with null proposals — ``draft_fallbacks`` counts) instead
      of erroring the request or the replica: speculation is an
      optimization, never a correctness dependency.
    - ``wedge_in_swap@N[:replica=k]`` — the targeted replica's N-th
      ``swap_params`` call (0-based) sleeps ``wedge_s`` then raises
      mid-rolling-swap. The Router must roll the partial fleet back onto
      ONE version (docs/RESILIENCE.md §9); fires once.
    - ``corrupt_publish@N`` — needs ``watcher`` (a
      :class:`dtf_tpu.publish.PublishWatcher`): the N-th NEW published
      version the watcher observes (0-based) is damaged on disk before
      it loads. The digest check must skip it with a WARN and the fleet
      keeps serving its current version.
    - ``corrupt_log_record@N`` — the serve-log sink's N-th record written
      (0-based) gets a damaged CRC: a mounting
      :class:`~dtf_tpu.data.stream.servelog.ServeLogSource` must skip it
      with one WARN, exactly the bit-rot branch. No-op without a sink.
    - ``crash_in_log_rotate@N`` — the sink's N-th rotation (0-based)
      raises after the shard is durable but BEFORE its manifest commit:
      the next sink over the directory must ADOPT the orphan shard —
      committed records are never lost. No-op without a sink.
    - ``crash_in_event_rotate@N`` — the same crash seam on the pump's
      fleet :class:`~dtf_tpu.telemetry.events.EventLog` (``pump.events``):
      the next event log over the directory must adopt the orphan event
      shard and the timeline must still close every episode. No-op
      without an event log.

    Ticks are counted in the TARGET's own call domain (decode calls /
    submits) so plans stay deterministic under Poisson timing. ``sleep``
    is injectable — fast tests pass a fake clock's ``advance``. Each
    firing prints one JSON line first (the FaultHook contract: a failed
    recovery assertion must still show where the fault landed).
    """
    scheds = getattr(pump, "schedulers", None) or [pump]
    state = ServeFaultState(plan)
    _emit = emit or (lambda line: print(line, flush=True))

    def note(what: str, **kw) -> None:
        try:
            _emit(json.dumps({
                "fault_inject": what, "kind": plan.kind, "tick": plan.tick,
                "replica": plan.replica, "pid": os.getpid(), **kw}))
        except Exception:   # noqa: BLE001 — reporting must not alter the
            pass            # scenario under test

    if plan.kind == "poison_request":
        orig_submit = pump.submit
        count = [0]

        def submit(req, **kw):
            if count[0] == plan.tick and state.poison_prompt is None:
                state.poison_prompt = tuple(int(t) for t in req.prompt)
                note("poison_armed", submit_index=count[0])
            count[0] += 1
            return orig_submit(req, **kw)

        pump.submit = submit
        for s in scheds:
            eng = s.engine
            orig = eng.prefill_chunk_into

            def prefill(slot, prompt, chunk_i, *, _orig=orig, **kw):
                if (state.poison_prompt is not None
                        and tuple(int(t) for t in prompt)
                        == state.poison_prompt):
                    if not state.fired:
                        state.fired = True
                        note("firing")
                    raise InjectedPoison(
                        f"injected poison request (submit #{plan.tick})")
                return _orig(slot, prompt, chunk_i, **kw)

            eng.prefill_chunk_into = prefill
        return state

    if plan.kind == "poison_draft":
        orig_submit = pump.submit
        count = [0]

        def submit(req, **kw):
            if count[0] == plan.tick and state.poison_prompt is None:
                state.poison_prompt = tuple(int(t) for t in req.prompt)
                note("poison_armed", submit_index=count[0])
            count[0] += 1
            return orig_submit(req, **kw)

        pump.submit = submit
        for s in scheds:
            eng = s.engine
            if not getattr(eng, "spec_k", 0):
                continue            # non-speculative engine (fakes, or a
                                    # disagg prefill replica): no draft
                                    # runs there — the plan no-ops
            orig = eng.draft_propose

            def draft(*, _orig=orig, _s=s, **kw):
                if state.poison_prompt is not None and any(
                        tuple(int(t) for t in r.req.prompt)
                        == state.poison_prompt
                        for r in _s._running.values()):
                    if not state.fired:
                        state.fired = True
                        note("firing")
                    raise InjectedPoison(
                        f"injected draft poison (submit #{plan.tick})")
                return _orig(**kw)

            eng.draft_propose = draft
        return state

    if plan.kind == "wedge_in_swap":
        delay = (wedge_s if wedge_s is not None
                 else float(os.environ.get("DTF_FAULT_WEDGE_S", "0.75")))
        for k, s in enumerate(scheds):
            if plan.replica is not None and plan.replica != k:
                continue
            eng = s.engine
            orig = getattr(eng, "swap_params", None)
            if orig is None:
                continue        # fakes without a swap surface: no-op
            calls = [0]

            def swap(*a, _orig=orig, _calls=calls, _k=k, **kw):
                idx = _calls[0]
                _calls[0] += 1
                if idx == plan.tick and not state.fired:
                    state.fired = True
                    note("firing", on_replica=_k, delay_s=delay)
                    sleep(delay)
                    raise InjectedCrash(
                        f"injected wedge_in_swap on replica {_k} "
                        f"(swap call #{idx})")
                return _orig(*a, **kw)

            eng.swap_params = swap
        return state

    if plan.kind == "corrupt_publish":
        if watcher is None:
            return state        # nothing to arm without a publish watcher
        orig_load = watcher.load_new
        seen: list = []

        def load_new(*, _orig=orig_load):
            m = watcher.poll()
            if m is not None:
                v = int(m["version"])
                if v not in seen:
                    seen.append(v)
                    if len(seen) - 1 == plan.tick and not state.fired:
                        state.fired = True
                        note("firing", version=v)
                        try:
                            corrupt_publish_version(watcher.directory, v)
                        except FileNotFoundError:
                            pass   # raced a prune; nothing to corrupt
            return _orig()

        watcher.load_new = load_new
        return state

    if plan.kind in ("corrupt_log_record", "crash_in_log_rotate"):
        # the serve-log sink seams (ISSUE 19): a Router's replicas SHARE
        # one sink — arm each DISTINCT sink once, counting in its own
        # record/rotation domain (deterministic under Poisson timing)
        seen_sinks: set = set()
        for s in scheds:
            sink = getattr(s, "_log_sink", None)
            if sink is None or id(sink) in seen_sinks:
                continue
            seen_sinks.add(id(sink))

            def mark(what: str) -> None:
                state.fired = True
                note(what)

            if plan.kind == "corrupt_log_record":
                sink.arm_corrupt(plan.tick, note=mark)
            else:
                sink.arm_crash_rotate(plan.tick, note=mark)
        return state

    if plan.kind == "crash_in_event_rotate":
        # the fleet event log's crash seam (ISSUE 20) — same shape as the
        # sink verbs, armed on the pump-shared EventLog
        events = getattr(pump, "events", None)
        if events is not None:
            def mark_ev(what: str) -> None:
                state.fired = True
                note(what)

            events.arm_crash_rotate(plan.tick, note=mark_ev)
        return state

    delay = (wedge_s if wedge_s is not None
             else float(os.environ.get("DTF_FAULT_WEDGE_S", "0.75"))) \
        if plan.kind == "wedge_replica" else \
        (slow_s if slow_s is not None
         else float(os.environ.get("DTF_FAULT_SLOW_S", "0.2")))
    for k, s in enumerate(scheds):
        if plan.replica is not None and plan.replica != k:
            continue
        eng = s.engine
        name = ("decode_begin" if getattr(eng, "can_run_ahead", False)
                else "decode")
        orig = getattr(eng, name)
        calls = [0]

        def decode(*, _orig=orig, _calls=calls, _k=k, **kw):
            _calls[0] += 1
            if _calls[0] > plan.tick:
                if not state.fired:
                    state.fired = True
                    note("firing", on_replica=_k, delay_s=delay)
                sleep(delay)
            return _orig(**kw)

        setattr(eng, name, decode)
    return state


__all__ = ["DEGRADED", "HEALTHY", "HealthConfig", "HealthTracker",
           "PROBATION", "QUARANTINED", "ServeFaultState",
           "install_serve_fault"]
