"""In-process serving client + seeded Poisson load generator.

``ServeClient`` is the submit/poll surface a caller (or the reference-style
launcher ``scripts/serve_gpt.py``) talks to — it owns a
:class:`~dtf_tpu.serve.scheduler.Scheduler` and pumps it. ``PoissonLoadGen``
produces a reproducible open-loop arrival process (exponential
inter-arrivals, seeded prompt/length sampling) for benching (the
benchmark's serve cells drive a closed loop of their own:
``benchmarks/lib/loadgen.py``).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import sys
import time
from typing import Iterator, Optional, Sequence

import numpy as np

from dtf_tpu.serve.scheduler import (FAILED_STATUSES, Request,
                                     RequestFailed, Scheduler)

log = logging.getLogger("dtf_tpu")


def replay(scheduler: Scheduler, arrivals, *,
           clock=time.perf_counter, sleep=time.sleep,
           on_tick=None) -> float:
    """Open-loop arrival replay: submit each ``(t_arrival, Request)`` when
    its wall-clock moment comes, tick the scheduler whenever work is
    pending, and drain. Returns the makespan in seconds. THE one pump loop
    — serve_gpt.py and the bench A/B both drive it, so admission timing
    cannot drift between them. Returns request ids in submit order via
    ``scheduler`` (callers poll). ``on_tick`` (optional, zero-arg) fires
    after every scheduler tick — the :class:`Heartbeat` hook point."""
    arrivals = list(arrivals)
    t0 = clock()
    i = 0
    while i < len(arrivals) or scheduler.pending:
        now = clock() - t0
        while i < len(arrivals) and arrivals[i][0] <= now:
            scheduler.submit(arrivals[i][1])
            i += 1
        if scheduler.pending:
            scheduler.tick()
            if on_tick is not None:
                on_tick()
        elif i < len(arrivals):
            sleep(min(arrivals[i][0] - now, 0.05))
    return clock() - t0


#: heartbeat snapshot keys, in emit order — the operator's at-a-glance
#: panel (everything else stays in the final stats() line).
_HEARTBEAT_KEYS = ("serve_completed", "serve_queue_depth",
                   "serve_occupancy", "serve_ttft_p50_s",
                   "serve_ttft_p99_s", "serve_ttft_slo_ok_frac",
                   "serve_shed", "serve_timeouts",
                   "router_completed", "router_queue_depth",
                   "router_occupancy", "router_ttft_p50_s",
                   "router_ttft_p99_s", "router_ttft_slo_ok_frac",
                   "router_shed", "router_timeouts", "router_requeued",
                   "router_quarantines", "router_version",
                   "router_swaps", "router_swap_rollbacks",
                   "router_swap_in_progress")


class Heartbeat:
    """Periodic one-line JSON liveness snapshots of a running server.

    Call :meth:`maybe_emit` after every scheduler/router tick (``replay``'s
    ``on_tick``, or the explicit pump loop): every ``every_ticks`` ticks it
    emits one ``{"serve_heartbeat": ...}`` JSON line via ``emit`` (default:
    stderr — stdout's LAST line stays the launcher's one metrics line) with
    the scheduler/router ``stats()`` panel: per-replica occupancy, TTFT
    p50/p99, and the SLO compliance fraction. When ``slo_floor > 0`` and
    the ok-fraction drops below it, a WARNING logs once per excursion
    EPISODE — per key: the fleet aggregate and each ``replica{i}`` panel
    dedup independently, re-armed when that key's compliance recovers (a
    sustained breach, or one breach seen through several replicas, must
    not spam one warning per tick); every excursion is COUNTED and the
    worst ok-fraction retained, so :meth:`stats` can stamp both into the
    launcher's final JSON line (a run that breached and recovered is not
    allowed to look clean). With an ``events`` log attached, each episode
    lands on the run timeline as paired ``slo_excursion`` enter/exit
    records carrying their entry/exit ticks. With a ``flight`` recorder attached, each
    emit also writes the atomic liveness heartbeat file with a ``serve``
    summary — the PR 11 run-controller surface, serving edition. Host
    arithmetic only; stats() is already readback-free.
    """

    def __init__(self, sched, *, every_ticks: int, slo_floor: float = 0.0,
                 emit=None, clock=time.monotonic, flight=None, events=None):
        if every_ticks < 1:
            raise ValueError(f"every_ticks={every_ticks} must be >= 1")
        self.sched = sched
        self.every_ticks = every_ticks
        self.slo_floor = slo_floor
        self.emit = emit or (lambda line: print(line, file=sys.stderr))
        self.clock = clock
        self.flight = flight
        #: optional fleet EventLog (ISSUE 20): excursion entry/exit edges
        #: land on the run timeline with their ticks
        self.events = events
        self._t0 = clock()
        self._ticks = 0
        self.emitted = 0
        self.excursions = 0
        self.replica_excursions = 0
        self.worst_ok_frac: float | None = None
        #: open excursion episodes, keyed "fleet" / "replica{i}" — entry
        #: is the ONE moment that WARNs and emits (a sustained breach, or
        #: the same breach seen through several replicas' panels, must
        #: not spam); exit closes the episode on the event plane.
        self._episodes: dict = {}

    def snapshot(self) -> dict:
        stats = self.sched.stats()
        snap = {"serve_heartbeat": self.emitted,
                "t_s": round(self.clock() - self._t0, 3)}
        for k in _HEARTBEAT_KEYS:
            if k in stats:
                snap[k] = (round(v, 6) if isinstance(v := stats[k], float)
                           else v)
        # the per-replica SLO panel (Router stats) rides along verbatim
        for k, v in stats.items():
            if k.startswith("replica"):
                snap[k] = round(v, 6) if isinstance(v, float) else v
        return snap

    def _slo_ok_frac(self, snap) -> float | None:
        for k in ("router_ttft_slo_ok_frac", "serve_ttft_slo_ok_frac"):
            if k in snap:
                return snap[k]
        return None

    def maybe_emit(self) -> dict | None:
        self._ticks += 1
        if self._ticks % self.every_ticks:
            return None
        snap = self.snapshot()
        self.emitted += 1
        self.emit(json.dumps(snap))
        ok = self._slo_ok_frac(snap)
        if ok is not None:
            self.worst_ok_frac = (ok if self.worst_ok_frac is None
                                  else min(self.worst_ok_frac, ok))
        if self.slo_floor > 0.0:
            fracs = {}
            if ok is not None:
                fracs["fleet"] = ok
            suffix = "_serve_ttft_slo_ok_frac"
            for k, v in snap.items():
                if k.startswith("replica") and k.endswith(suffix):
                    fracs[k[:-len(suffix)]] = v
            for key, frac in fracs.items():
                ep = self._episodes.get(key)
                if frac < self.slo_floor and ep is None:
                    self._episodes[key] = {"tick": self._ticks,
                                           "ok": frac}
                    if key == "fleet":
                        self.excursions += 1
                        log.warning(
                            "TTFT SLO compliance %.3f below the %.3f "
                            "floor (p99 %.4fs; excursion %d)", frac,
                            self.slo_floor,
                            snap.get("router_ttft_p99_s",
                                     snap.get("serve_ttft_p99_s", 0.0)),
                            self.excursions)
                    else:
                        self.replica_excursions += 1
                        log.warning(
                            "%s TTFT SLO compliance %.3f below the %.3f "
                            "floor (one WARN per replica episode)",
                            key, frac, self.slo_floor)
                    if self.events is not None:
                        self.events.emit(
                            "slo_excursion", edge="enter", key=key,
                            ok_frac=round(frac, 6), tick=self._ticks)
                elif frac >= self.slo_floor and ep is not None:
                    del self._episodes[key]
                    if self.events is not None:
                        self.events.emit(
                            "slo_excursion", edge="exit", key=key,
                            ok_frac=round(frac, 6), tick=self._ticks,
                            entered_tick=ep["tick"],
                            ticks=self._ticks - ep["tick"])
        if self.flight is not None:
            # the run-controller liveness surface: the heartbeat file a
            # chief-side watcher polls, with the serve panel riding along
            serve = {k: snap[k] for k in
                     ("serve_completed", "serve_queue_depth",
                      "router_completed", "router_queue_depth",
                      "router_quarantines", "router_version",
                      "router_swaps", "router_swap_rollbacks")
                     if k in snap}
            # per-replica ACTIVE param versions: the flight-recorder
            # serve panel's skew view (ISSUE 14 satellite)
            versions = {k: snap[k] for k in snap
                        if k.startswith("replica") and k.endswith("_version")}
            if versions:
                serve["replica_versions"] = versions
            self.flight.write_heartbeat(extra={"serve": serve})
        return snap

    def stats(self) -> dict:
        """SLO-excursion aggregates for the launcher's final JSON line:
        how often compliance dipped below the floor and how bad the worst
        dip was (a breach-and-recover run must not look clean)."""
        out = {"heartbeats": float(self.emitted),
               "slo_excursions": float(self.excursions),
               "replica_slo_excursions": float(self.replica_excursions)}
        if self.worst_ok_frac is not None:
            out["worst_ttft_slo_ok_frac"] = round(self.worst_ok_frac, 6)
        return out


class ServeClient:
    """Submit/poll API over an engine. ``submit`` returns a request id;
    ``result`` blocks (pumping the scheduler) until that request is done."""

    def __init__(self, engine, writer=None, **scheduler_kw):
        self.scheduler = Scheduler(engine, writer, **scheduler_kw)

    def submit(self, prompt: Sequence[int], *, max_new: int = 32,
               temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
               eos_id: Optional[int] = None, pad_id: int = 0,
               seed: int = 0) -> int:
        return self.scheduler.submit(Request(
            prompt=list(prompt), max_new=max_new, temperature=temperature,
            top_k=top_k, top_p=top_p, eos_id=eos_id, pad_id=pad_id,
            seed=seed))

    def poll(self, rid: int) -> dict:
        return self.scheduler.poll(rid)

    def step(self) -> None:
        self.scheduler.tick()

    def result(self, rid: int, max_ticks: int = 100000) -> list[int]:
        """Generated tokens of ``rid`` (pumps the scheduler until done).
        A shed/timed-out/errored request raises :class:`RequestFailed`
        IMMEDIATELY — terminal statuses must not spin ``max_ticks`` to
        exhaustion on a request that will never finish."""
        for _ in range(max_ticks):
            st = self.poll(rid)
            if st["status"] == "done":
                return st["tokens"]
            if st["status"] in FAILED_STATUSES:
                raise RequestFailed(rid, st)
            self.scheduler.tick()
        raise RuntimeError(f"request {rid} not done after {max_ticks} ticks")

    def drain(self) -> None:
        self.scheduler.run_until_idle()

    def stats(self) -> dict:
        return self.scheduler.stats()


@dataclasses.dataclass(frozen=True)
class PoissonLoadGen:
    """Seeded open-loop load: ``arrivals()`` yields ``(t_arrival, Request)``
    with Exp(rate) inter-arrival gaps, prompts of uniform random length in
    ``[prompt_min, prompt_max]`` over ``vocab_size`` tokens, and ``max_new``
    uniform in ``[new_min, new_max]`` — the mixed-length churn continuous
    batching exists for. Deterministic per seed (benches commit rows)."""

    rate: float                       # requests per second
    n_requests: int
    vocab_size: int
    prompt_min: int = 4
    prompt_max: int = 64
    new_min: int = 8
    new_max: int = 64
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    seed: int = 0

    def __post_init__(self):
        # fail at construction, not mid-replay inside numpy
        if self.rate <= 0:
            raise ValueError(f"rate={self.rate} must be > 0")
        if not 1 <= self.prompt_min <= self.prompt_max:
            raise ValueError(
                f"need 1 <= prompt_min ({self.prompt_min}) <= prompt_max "
                f"({self.prompt_max})")
        if not 1 <= self.new_min <= self.new_max:
            raise ValueError(
                f"need 1 <= new_min ({self.new_min}) <= new_max "
                f"({self.new_max})")

    def arrivals(self) -> Iterator[tuple[float, Request]]:
        rng = np.random.default_rng(self.seed)
        t = 0.0
        for i in range(self.n_requests):
            t += float(rng.exponential(1.0 / self.rate))
            n_p = int(rng.integers(self.prompt_min, self.prompt_max + 1))
            prompt = rng.integers(0, self.vocab_size, n_p).tolist()
            yield t, Request(
                prompt=prompt,
                max_new=int(rng.integers(self.new_min, self.new_max + 1)),
                temperature=self.temperature, top_k=self.top_k,
                top_p=self.top_p, eos_id=self.eos_id, seed=self.seed + i)
