"""Chief-side elastic run controller — the relaunch policy in one place.

At pod scale, preemption and host failure are the steady state (MLPerf on
TPU-v3 pods, pjit on TPUv4 — PAPERS.md). The pieces below the controller
already exist: every host runs a flight recorder whose stall watchdog writes
a liveness heartbeat (PR 5/this PR, ``telemetry/flight.py``), PreemptionHook
turns SIGTERM into a durable save + clean exit, and Orbax restore reshards
onto whatever mesh the relaunch brings up (``fault/elastic.py``). What was
missing is the process that *owns the decision*: watch N host processes,
tell **host-lost** from **run-wedged**, and relaunch accordingly.

The two verdicts and their policies (docs/RESILIENCE.md):

- **host-lost** — a host process died (SIGKILL'd by the cluster manager,
  OOM, hardware). Survivors cannot make progress (collectives block), so:
  stop the survivors (SIGTERM first — their dump chain writes postmortems
  and a final checkpoint), then relaunch on the largest valid smaller host
  count, under bounded exponential backoff and a max-restarts budget.
- **run-wedged** — every host process is alive but no step completes: a
  host's stall watchdog flagged its heartbeat ``stalled``, or heartbeats
  went stale, or a launch never produced one. Nothing is gone, something
  is stuck (a hung backend, a deadlocked collective): dump postmortems
  everywhere (the SIGTERM chain does — flight dump first, then the
  checkpoint), kill, relaunch at the SAME size.

Every transition is emitted as one JSON line (the bench.py idiom) and
appended to ``<logdir>/controller.jsonl``; ``finish()`` stamps the run's
restart count and per-restart MTTR into TELEMETRY.json.

Module-level jax-free (srclint-fenced): the controller must run in a clean
process that cannot hang on a wedged backend — it observes hosts through
the filesystem and the process table only.
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Callable, Mapping, Optional, Sequence

from dtf_tpu._hostio import append_line


def read_heartbeat(path: str) -> Optional[dict]:
    """The host's last liveness record, or None. Never raises — a torn
    write (the host died mid-rename) reads as 'no heartbeat', which the
    staleness rules already handle."""
    try:
        with open(path) as f:
            data = json.load(f)
        return data if isinstance(data, dict) else None
    except (OSError, ValueError):
        return None


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    """The retry/timeout/backoff policy knobs."""

    max_restarts: int = 3
    backoff_base_s: float = 1.0       # exponential: base * 2**restart
    backoff_max_s: float = 60.0
    #: heartbeat older than this on a live process = wedged
    wedge_timeout_s: float = 120.0
    #: a launch that never produced a heartbeat within this = wedged
    startup_timeout_s: float = 600.0
    #: SIGTERM → SIGKILL grace when stopping hosts (the dump/save window)
    grace_s: float = 15.0
    poll_s: float = 0.5
    min_hosts: int = 1


@dataclasses.dataclass(frozen=True)
class HostObservation:
    """One host's state at one poll — everything classify() looks at."""

    host: int
    alive: bool
    returncode: Optional[int]
    #: seconds since the heartbeat's own wall stamp; None = no heartbeat
    heartbeat_age_s: Optional[float]
    last_step: Optional[int] = None
    #: the host's own stall watchdog fired (heartbeat ``stalled`` flag)
    stalled: bool = False


@dataclasses.dataclass(frozen=True)
class Decision:
    """One policy verdict: what happened and what to do about it."""

    kind: str                 # running | done | host_lost | wedged
    reason: str = ""
    dead_hosts: tuple = ()
    wedged_hosts: tuple = ()


class ControllerPolicy:
    """The pure state machine — classify observations, size the relaunch.

    Separated from :class:`RunController` so every branch is unit-testable
    with hand-built observations (tier-1 fast), while the controller owns
    only process plumbing.
    """

    def classify(self, obs: Sequence[HostObservation], *,
                 config: ControllerConfig,
                 since_launch_s: float) -> Decision:
        dead = tuple(o.host for o in obs
                     if not o.alive and o.returncode != 0)
        if dead:
            return Decision(
                "host_lost", dead_hosts=dead,
                reason=f"host(s) {list(dead)} exited "
                       f"{[o.returncode for o in obs if o.host in dead]}")
        if all(not o.alive for o in obs):        # every rc == 0
            return Decision("done", reason="all hosts exited 0")
        # some/all alive, none failed: wedge checks apply to live hosts
        wedged = []
        for o in obs:
            if not o.alive:
                continue
            if o.stalled:
                wedged.append((o.host, "stall watchdog fired"))
            elif (o.heartbeat_age_s is not None
                  and o.heartbeat_age_s > config.wedge_timeout_s):
                wedged.append(
                    (o.host,
                     f"heartbeat stale {o.heartbeat_age_s:.0f}s"))
            elif (o.heartbeat_age_s is None
                  and since_launch_s > config.startup_timeout_s):
                wedged.append(
                    (o.host,
                     f"no heartbeat {since_launch_s:.0f}s after launch"))
        if wedged:
            return Decision(
                "wedged", wedged_hosts=tuple(h for h, _ in wedged),
                reason="; ".join(f"host {h}: {why}" for h, why in wedged))
        return Decision("running")

    def shrink(self, n_hosts: int, n_dead: int, *,
               config: ControllerConfig,
               valid: Optional[Callable[[int], bool]] = None
               ) -> Optional[int]:
        """Largest valid survivor count, or None (no valid shrink left).

        ``valid`` encodes mesh divisibility (the ``analysis fit
        --hosts/--lost`` pre-pricing feeds the same predicate): the data
        axis must split evenly across the survivors or the relaunch would
        die in ``make_mesh`` instead of training.
        """
        valid = valid or (lambda n: True)
        for n in range(n_hosts - max(n_dead, 1), config.min_hosts - 1, -1):
            if n >= config.min_hosts and valid(n):
                return n
        return None

    def backoff_s(self, restarts: int, config: ControllerConfig) -> float:
        return min(config.backoff_base_s * (2 ** restarts),
                   config.backoff_max_s)


class RunController:
    """Supervise N host processes through failures to completion.

    ``launch(n_hosts, attempt) -> list[proc]`` starts one OS process per
    host and returns handles exposing ``poll() -> rc|None``, ``pid``,
    ``terminate()``, ``kill()`` (``subprocess.Popen`` as-is; tests pass
    fakes). ``heartbeat_path(host) -> path`` locates each host's liveness
    file (default: ``<logdir>/telemetry/p<host>/heartbeat.json``, the
    multi-process telemetry layout; single-host runs fall back to the
    unsuffixed dir). ``valid_hosts(n) -> bool`` gates shrink sizes on mesh
    divisibility. ``clock``/``wall``/``sleep`` are injectable so the whole
    supervision loop unit-tests in milliseconds.
    """

    def __init__(self, launch: Callable[[int, int], list], n_hosts: int,
                 logdir: str, config: ControllerConfig = ControllerConfig(),
                 *, policy: Optional[ControllerPolicy] = None,
                 heartbeat_path: Optional[Callable[[int], str]] = None,
                 valid_hosts: Optional[Callable[[int], bool]] = None,
                 emit: Callable[[str], None] = None,
                 clock=time.monotonic, wall=time.time, sleep=time.sleep,
                 event_log=None):
        if n_hosts < 1:
            raise ValueError(f"n_hosts must be >= 1, got {n_hosts}")
        self.launch = launch
        self.n_hosts = n_hosts
        self.logdir = logdir
        self.config = config
        self.policy = policy or ControllerPolicy()
        self.heartbeat_path = heartbeat_path or self._default_hb_path
        self.valid_hosts = valid_hosts
        self._emit_fn = emit or (lambda line: print(line, flush=True))
        self.clock = clock
        self.wall = wall
        self.sleep = sleep
        self.events: list[dict] = []
        #: optional fleet EventLog (ISSUE 20): every verdict the
        #: controller emits is mirrored onto the run timeline with the
        #: controller's OWN wall stamp (MTTR ground truth).
        self.event_log = event_log
        self.mttr_s: list[float] = []
        self.restarts = 0
        self.causes: list[str] = []

    # ------------------------------------------------------------- plumbing

    def _default_hb_path(self, host: int) -> str:
        """Multi-process telemetry writes per-host ``p<i>/heartbeat.json``;
        single-process writes the unsuffixed file — after an elastic
        shrink to one host the same controller must follow along, so
        prefer whichever exists (stamp filtering discards a stale
        ``p<i>`` file left by the bigger fleet)."""
        base = os.path.join(self.logdir, "telemetry")
        suffixed = os.path.join(base, f"p{host}", "heartbeat.json")
        plain = os.path.join(base, "heartbeat.json")
        if host == 0 and os.path.exists(plain):
            if not os.path.exists(suffixed):
                return plain
            # both exist (a shrink crossed the naming boundary): the one
            # beating NOW is the one with the newer stamp
            ts = (read_heartbeat(suffixed) or {}).get("t", 0)
            tp = (read_heartbeat(plain) or {}).get("t", 0)
            return suffixed if ts >= tp else plain
        return suffixed if self.n_hosts > 1 else plain

    def _emit(self, event: Mapping) -> dict:
        rec = {"controller": "event", "t": round(self.wall(), 3), **event}
        self.events.append(rec)
        line = json.dumps(rec)
        try:
            self._emit_fn(line)
        except Exception:   # noqa: BLE001 — an emit sink must not kill
            pass            # the supervision loop
        try:
            append_line(os.path.join(self.logdir, "controller.jsonl"),
                        line)
        except OSError:
            pass
        if self.event_log is not None:
            fields = {k: v for k, v in rec.items()
                      if k not in ("controller", "t", "state", "hosts")}
            # "hosts" is the bulky per-host observation dump — it stays
            # in controller.jsonl; the timeline carries the verdict
            state = event.get("state", rec.get("controller", "event"))
            self.event_log.emit(f"controller_{state}", t=rec["t"],
                                **fields)
        return rec

    def _observe(self, procs: Sequence,
                 launched_wall: float) -> list[HostObservation]:
        """Poll liveness + heartbeats. A heartbeat stamped BEFORE this
        attempt's launch is a previous incarnation's last word (possibly
        ``stalled: true`` from the wedge that caused the relaunch) and is
        treated as absent — the startup-timeout rule governs until the new
        processes write their own."""
        now_wall = self.wall()
        obs = []
        for host, p in enumerate(procs):
            rc = p.poll()
            hb = read_heartbeat(self.heartbeat_path(host))
            age = None
            step = None
            stalled = False
            if hb is not None:
                try:
                    t = float(hb.get("t", 0.0))
                except (TypeError, ValueError):
                    t = None
                if t is not None and t >= launched_wall:
                    age = max(now_wall - t, 0.0)
                    step = hb.get("step")
                    stalled = bool(hb.get("stalled"))
            obs.append(HostObservation(
                host=host, alive=rc is None, returncode=rc,
                heartbeat_age_s=age, last_step=step, stalled=stalled))
        return obs

    def _stop_procs(self, procs: Sequence, *, reason: str) -> None:
        """SIGTERM every live host (their chain dumps a postmortem, then
        PreemptionHook checkpoints), wait the grace window, SIGKILL the
        rest. A wedged host by definition may ignore the SIGTERM — the
        grace bound is what keeps the controller from joining it."""
        live = [p for p in procs if p.poll() is None]
        for p in live:
            try:
                p.terminate()
            except (OSError, ProcessLookupError):
                pass
        deadline = self.clock() + self.config.grace_s
        while self.clock() < deadline:
            if all(p.poll() is not None for p in live):
                break
            self.sleep(min(self.config.poll_s, 0.2))
        killed = []
        for p in live:
            if p.poll() is None:
                killed.append(getattr(p, "pid", None))
                try:
                    p.kill()
                except (OSError, ProcessLookupError):
                    pass
        if killed:
            self._emit({"state": "killed", "reason": reason,
                        "pids": killed})

    @staticmethod
    def _fresh(o: HostObservation, config: ControllerConfig) -> bool:
        return (o.alive and o.heartbeat_age_s is not None
                and o.heartbeat_age_s <= config.wedge_timeout_s
                and not o.stalled)

    # ------------------------------------------------------------ main loop

    def run(self) -> dict:
        """Supervise to completion; returns the summary dict (also the
        last emitted event). Raises nothing on policy failures — a
        ``final: failed`` summary with the cause IS the loud failure."""
        cfg = self.config
        n = self.n_hosts
        pending_mttr: Optional[float] = None
        while True:
            self._emit({"state": "launching", "n_hosts": n,
                        "restarts": self.restarts})
            # wall stamp BEFORE launch: a heartbeat written during the
            # launch callback (or by a worker that starts instantly) must
            # count as THIS attempt's, while anything older is a previous
            # incarnation's last word
            launched = self.clock()
            launched_wall = self.wall()
            procs = list(self.launch(n, self.restarts))
            recovered_logged = pending_mttr is None
            while True:
                obs = self._observe(procs, launched_wall)
                if not recovered_logged and any(
                        self._fresh(o, cfg) for o in obs):
                    mttr = self.wall() - pending_mttr
                    self.mttr_s.append(round(mttr, 3))
                    pending_mttr = None
                    recovered_logged = True
                    self._emit({"state": "recovered",
                                "mttr_s": round(mttr, 3), "n_hosts": n})
                d = self.policy.classify(
                    obs, config=cfg,
                    since_launch_s=self.clock() - launched)
                if d.kind == "running":
                    self.sleep(cfg.poll_s)
                    continue
                if d.kind == "done":
                    self._emit({"state": "done", "reason": d.reason,
                                "n_hosts": n})
                    return self._summary("done", n)
                # ---- failure detected --------------------------------
                t_detect = self.wall()
                self.causes.append(d.kind)
                self._emit({
                    "state": d.kind, "reason": d.reason, "n_hosts": n,
                    "dead_hosts": list(d.dead_hosts),
                    "wedged_hosts": list(d.wedged_hosts),
                    "hosts": [dataclasses.asdict(o) for o in obs]})
                self._stop_procs(procs, reason=d.kind)
                if self.restarts >= cfg.max_restarts:
                    self._emit({"state": "failed",
                                "reason": f"max_restarts={cfg.max_restarts}"
                                          f" exhausted after {d.kind}"})
                    return self._summary("failed", n, cause=d.kind)
                if d.kind == "host_lost":
                    n_next = self.policy.shrink(
                        n, len(d.dead_hosts), config=cfg,
                        valid=self.valid_hosts)
                    if n_next is None:
                        self._emit({"state": "failed",
                                    "reason": "no valid survivor host "
                                              f"count below {n}"})
                        return self._summary("failed", n, cause=d.kind)
                else:
                    n_next = n
                backoff = self.policy.backoff_s(self.restarts, cfg)
                self.restarts += 1
                self._emit({"state": "relaunching", "cause": d.kind,
                            "n_hosts": n_next, "backoff_s": backoff,
                            "restarts": self.restarts})
                self.sleep(backoff)
                pending_mttr = t_detect
                n = n_next
                break       # relaunch

    def _summary(self, final: str, n_hosts: int, *,
                 cause: Optional[str] = None) -> dict:
        out = {
            "controller": "summary",
            "final": final,
            "n_hosts_initial": self.n_hosts,
            "n_hosts_final": n_hosts,
            "restarts": self.restarts,
            "causes": list(self.causes),
            "mttr_s": list(self.mttr_s),
        }
        if self.mttr_s:
            out["mttr_mean_s"] = round(sum(self.mttr_s)
                                       / len(self.mttr_s), 3)
        if cause:
            out["cause"] = cause
        self._emit(out)
        return out

    def finish(self, summary: Mapping,
               telemetry_artifact: Optional[str] = None,
               meta: Optional[Mapping] = None) -> Optional[dict]:
        """Stamp the run's MTTR/restart fields into TELEMETRY.json
        (``telemetry.run.merge_artifact`` — jax-free, same bounded-runs
        layout the RunReports use). Always emits the terminal ``run_end``
        event FIRST (ISSUE 20 satellite): the timeline must close every
        episode even when the artifact merge is skipped."""
        if self.event_log is not None:
            self.event_log.emit(
                "run_end", final=summary.get("final", "unknown"),
                restarts=int(summary.get("restarts", self.restarts)),
                causes=list(summary.get("causes", self.causes)),
                mttr_s=list(summary.get("mttr_s", self.mttr_s)),
                t=round(self.wall(), 3))
            self.event_log.flush()   # commit: the timeline reads it now
        if not telemetry_artifact:
            return None
        from dtf_tpu.telemetry.run import merge_artifact

        entry = {"telemetry": "controller", **summary}
        return merge_artifact(telemetry_artifact, entry, meta=meta)
