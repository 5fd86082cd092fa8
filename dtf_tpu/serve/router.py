"""Multi-replica router — the serving tier above :class:`DecodeEngine`.

One engine is one KV-cache pool on one device set; the ROADMAP's
millions-of-users north star needs N of them behind one front door. A
:class:`Router` owns N ``(DecodeEngine, Scheduler)`` replicas that SHARE
one restored param tree (weights are read-only at serve time — N replicas
cost N KV caches, not N param copies) while keeping fully independent KV
state, and admits each request to the replica with the **least slot
occupancy**, breaking ties by **queue depth** (then replica index, for
determinism). Every replica keeps the engine's fixed-shape discipline:
``trace_counts`` stays ``{prefill: 1, decode: 1}`` per replica and the
``gpt_serve`` comms fence covers each replica's decode graph identically.

Observability is the PR 5 span surface, serving edition:

- ``router_wait`` — queue time between submit and a replica accepting the
  request into a slot (recorded by the scheduler at admission; host
  clocks only, zero added device readbacks);
- per-replica TTFT/occupancy/SLO rollups in :meth:`Router.stats`
  (``replica{i}_*`` keys) next to the fleet aggregates — ``ttft_slo_s``
  sets the TTFT objective each replica reports compliance against.

The router is drop-in for the scheduler in the pump loop: it exposes the
same ``submit/tick/pending`` surface, so :func:`dtf_tpu.serve.client.replay`
drives a fleet exactly like a single scheduler (the bench A/B rides this).

Resilience (ISSUE 12): with more than one replica the router runs a
per-replica health state machine (:mod:`dtf_tpu.serve.health`) by
default — every replica tick is wall-timed on the router's clock, a
wedged or repeatedly-slow replica is **quarantined** (``_pick`` skips it,
its ticks stop, its in-flight requests are requeued onto survivors in
submit order), and after a probation delay it is re-admitted on trial
(idle probation replicas are exercised via ``DecodeEngine.probe``).
Requeue is a full deterministic replay — the survivor re-prefills the
prompt (cached stems land in one page gather where the survivor's prefix
pool has them) and regenerates the token stream, bitwise identical to a
fault-free run of the same request. When NO replica is routable the
router sheds at the front door with a ``retry_after_s`` derived from the
earliest probation ETA. docs/RESILIENCE.md "Serving" walks the states
and the chaos matrix that pins the behavior.

Weight hot-swap (ISSUE 14): :meth:`Router.start_swap` rolls a new param
version across the fleet with ZERO downtime — one replica at a time is
drained through the same requeue path, swapped in place
(``DecodeEngine.swap_params``: no recompiles, ``trace_counts`` pinned),
probed and re-admitted; the first swapped replica serves a
:class:`SwapConfig`-sized CANARY window under the health watchdog and a
TTFT-SLO gate, and a breach (or any swap-step failure — the
``wedge_in_swap`` chaos verb) rolls every swapped replica back onto the
previous version fleet-wide. ``maybe_swap_published`` drives it from a
:class:`dtf_tpu.publish.PublishWatcher`. Completed records stamp the
param version that decoded them; docs/RESILIENCE.md §9 walks the
contracts.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import logging
import time
from typing import Optional, Sequence

from dtf_tpu.metrics import quantile as _quantile
from dtf_tpu.serve import health as health_lib
from dtf_tpu.serve.engine import DecodeEngine
from dtf_tpu.serve.scheduler import (FAILED_STATUSES, Request,
                                     RequestFailed, Scheduler)
from dtf_tpu.telemetry.spans import SpanRecorder

log = logging.getLogger("dtf_tpu")

#: per-replica stat keys surfaced as ``replica{i}_<key>`` (the SLO panel);
#: everything else stays per-scheduler to keep the JSON line bounded.
_REPLICA_KEYS = ("serve_completed", "serve_occupancy_mean",
                 "serve_ttft_p50_s", "serve_ttft_p99_s",
                 "serve_queue_peak", "serve_ttft_slo_ok_frac",
                 "serve_shed", "serve_timeouts", "serve_requeued_in")


@dataclasses.dataclass(frozen=True)
class SwapConfig:
    """Knobs of the rolling weight swap (ISSUE 14, module docstring).

    The FIRST swapped replica is the **canary**: for ``canary_ticks``
    router ticks it serves live traffic on the new version alone, and a
    breach inside that window — the canary's health state leaving
    HEALTHY (the watchdog's slow/wedge/fault verdicts), or, with a TTFT
    SLO configured, its post-swap ok-fraction dropping under
    ``slo_floor`` over at least ``slo_min_samples`` completions —
    triggers an automatic FLEET-WIDE rollback to the previous version.
    Only after a clean window does the swap roll across the rest of the
    fleet, one replica per tick."""

    canary_ticks: int = 8
    slo_floor: float = 0.0          # 0 = health-gate only
    slo_min_samples: int = 1

    def __post_init__(self):
        if self.canary_ticks < 1:
            raise ValueError(
                f"canary_ticks={self.canary_ticks} must be >= 1 (a swap "
                "with no canary window cannot be health-gated)")
        if not 0.0 <= self.slo_floor <= 1.0:
            raise ValueError(f"slo_floor={self.slo_floor} must be in "
                             "[0, 1]")
        if self.slo_min_samples < 1:
            raise ValueError(
                f"slo_min_samples={self.slo_min_samples} must be >= 1")


class Router:
    """Least-occupancy admission over N engine replicas (module docstring).

    Build from live engines (params already shared by construction — pass
    the same tree to each) or via :meth:`build`. ``ttft_slo_s``/``clock``/
    scheduler knobs apply to every replica's scheduler uniformly.
    """

    #: router ticks between periodic ``cp_profile`` events on the event
    #: plane (the tick profiler's durable rollup; stats() is the live one).
    CP_PROFILE_EVERY = 256

    def __init__(self, engines: Sequence[DecodeEngine], writer=None, *,
                 telemetry=None, ttft_slo_s: float = 0.0,
                 clock=time.monotonic, health=None,
                 prefill_replicas: int = 0, log_sink=None, events=None,
                 **scheduler_kw):
        if not engines:
            raise ValueError("Router needs at least one engine replica")
        # prefill/decode DISAGGREGATION: the FIRST ``prefill_replicas``
        # engines are dedicated prefill replicas — requests whose prompt
        # has >= 1 uncached full page route there first, their KV pages
        # land in the SHARED page store (the transport), and the request
        # is then handed off to a decode replica whose admission gathers
        # the pinned chain instead of re-running the transformer. A burst
        # of long prompts therefore saturates prefill replicas, not the
        # fleet's decode ticks.
        self._prefill_replicas = prefill_replicas
        if prefill_replicas:
            if not 0 < prefill_replicas < len(engines):
                raise ValueError(
                    f"prefill_replicas={prefill_replicas} must leave at "
                    f"least one decode replica (have {len(engines)})")
            stores = {id(getattr(e, "page_store", None)) for e in engines}
            if any(getattr(e, "page_store", None) is None
                   for e in engines) or len(stores) != 1:
                raise ValueError(
                    "prefill/decode disaggregation needs every replica "
                    "to mount ONE shared page store (the KV transport) — "
                    "build via Router.build(prefill_replicas=..., "
                    "prefix_pages=...)")
        self._roles = ["prefill" if i < prefill_replicas else "decode"
                       for i in range(len(engines))]
        self.telemetry = telemetry
        self.clock = clock
        #: ONE serve-log sink shared by the fleet (ISSUE 19): the pump is
        #: one thread, records carry their replica id, and a single shard
        #: sequence keeps the mounted stream source's addressing global.
        self.log_sink = log_sink
        #: ONE fleet EventLog (ISSUE 20, dtf_tpu/telemetry/events.py):
        #: requeue drains, swap lifecycle and health transitions land on
        #: the run timeline, each stamped with the router tick.
        self.events = events
        #: the CONTROL-PLANE TICK PROFILER (ISSUE 20): per-tick phase
        #: attribution on the PR 5 span machinery, timed on the router's
        #: own injectable clock — host arithmetic only, zero added device
        #: readbacks (counter-proven in tests/test_events.py).
        self._cp = SpanRecorder(clock=clock)
        self.schedulers = [
            Scheduler(e, writer, telemetry=telemetry,
                      ttft_slo_s=ttft_slo_s, clock=clock,
                      postmortem_name=None, log_sink=log_sink,
                      replica_index=i, **scheduler_kw)
            for i, e in enumerate(engines)]
        # replica health: ON by default for a real fleet (>1 replica —
        # quarantine needs survivors to requeue onto); pass a
        # HealthConfig to tune thresholds or force it for a single
        # replica, False to disable outright.
        if health is False:
            self.health: Optional[health_lib.HealthTracker] = None
        elif isinstance(health, health_lib.HealthTracker):
            self.health = health
            if events is not None and health.events is None:
                health.events = events   # one timeline for the fleet
        elif isinstance(health, health_lib.HealthConfig):
            self.health = health_lib.HealthTracker(
                len(engines), health, clock=clock, events=events)
        elif health is None and len(engines) == 1:
            self.health = None
        else:    # None with a fleet, or True
            self.health = health_lib.HealthTracker(len(engines), clock=clock,
                                                   events=events)
        if telemetry is not None:
            # ONE aggregate postmortem provider for the fleet (each
            # replica's provider would collide on the name): in-flight
            # request ids + slot ages per replica, host facts only.
            telemetry.add_postmortem_provider(
                "serve_router", self.postmortem_state)
        self.ttft_slo_s = ttft_slo_s
        self._where: dict[int, tuple[int, int]] = {}
        #: front-door sheds (no routable replica): terminal records the
        #: schedulers never saw, bounded like their completed retention.
        self._router_shed: dict[int, dict] = {}
        self._shed_cap = int(scheduler_kw.get("completed_cap", 100_000))
        self._shed_router = 0
        self._requeued = 0
        #: in-flight prefill-phase handoffs: fleet rid -> (the ORIGINAL
        #: request, its submit moment). While present, the rid points at
        #: a max_new=1 prefill JOB on a prefill replica; on the job's
        #: terminal status the original request is submitted to a decode
        #: replica with the original submit_t (TTFT and deadlines honest
        #: across the handoff) and hits the pages the job just saved.
        self._handoff: dict[int, tuple[Request, float]] = {}
        self._handoffs = 0
        self._next_id = 0
        # ---- rolling weight swap (ISSUE 14) -------------------------
        #: the fleet's COMMITTED param version (what a fully-converged
        #: fleet serves); per-replica truth lives on each engine.
        self._version = 0
        #: in-progress swap state machine (None = steady state)
        self._swap: Optional[dict] = None
        #: replica currently being drained+swapped (never routable)
        self._swapping: Optional[int] = None
        #: replicas stuck on weights the fleet REJECTED (their reverse
        #: swap failed during a rollback): version -> repair payload.
        #: Such a replica is never routable — probation would otherwise
        #: re-admit it serving a rolled-back version — until
        #: :meth:`_retry_version_repair` aligns it with the fleet.
        self._version_repair: dict[int, tuple] = {}
        #: health-less fleets have no quarantine backoff to pace repair
        #: retries: (next_allowed_tick, delay_ticks) per pending repair
        self._repair_backoff: dict[int, tuple[int, int]] = {}
        self._ticks = 0
        self._swaps = 0
        self._swap_rollbacks = 0
        self._last_swap: Optional[dict] = None
        #: version-skew tripwire: WARN once when the fleet spans more
        #: than one version OUTSIDE an in-progress swap, re-armed when
        #: the fleet converges again (ISSUE 14 satellite)
        self._skew_warned = False

    @classmethod
    def build(cls, cfg, params, *, n_replicas: int, n_slots: int,
              max_len: int, prefill_chunk: int = 16, mesh=None,
              kv_page_size: int = 0, prefix_pages: int = 0,
              page_save_after: int = 2, draft_cfg=None, draft_params=None,
              spec_k: int = 0, prefill_replicas: int = 0,
              **router_kw) -> "Router":
        """N replicas over ONE param tree. Each replica gets its own KV
        state (and page pool, when enabled) and its own AOT programs; the
        params device arrays are shared. ``draft_cfg``/``draft_params``/
        ``spec_k`` arm speculative decoding on the DECODE replicas (a
        dedicated prefill replica never decodes, so it skips the draft
        programs). ``prefill_replicas=N`` disaggregates: the first N
        replicas are prefill-role, ALL replicas mount one shared page
        store (the KV transport; saves become eager — ``save_after`` is
        forced to 1, a transport that waits for a second sighting would
        hand off nothing), and the router routes by request phase."""
        if n_replicas < 1:
            raise ValueError(f"n_replicas={n_replicas} must be >= 1")
        if prefill_replicas and not prefix_pages:
            raise ValueError(
                "prefill_replicas needs prefix_pages > 0: the page pool "
                "IS the prefill→decode KV transport")
        if prefill_replicas and not 0 < prefill_replicas < n_replicas:
            # fail BEFORE compiling N engines (the ctor re-checks)
            raise ValueError(
                f"prefill_replicas={prefill_replicas} must leave at "
                f"least one decode replica (have {n_replicas})")
        if prefill_replicas:
            page_save_after = 1
        engines, store = [], None
        for r in range(n_replicas):
            pre = r < prefill_replicas
            engines.append(DecodeEngine(
                cfg, params, n_slots=n_slots, max_len=max_len,
                prefill_chunk=prefill_chunk, mesh=mesh,
                kv_page_size=kv_page_size, prefix_pages=prefix_pages,
                page_save_after=page_save_after, shared_pages=store,
                draft_cfg=None if pre else draft_cfg,
                draft_params=None if pre else draft_params,
                spec_k=0 if pre else spec_k))
            if prefill_replicas and store is None:
                store = engines[0].page_store
        return cls(engines, prefill_replicas=prefill_replicas, **router_kw)

    # ------------------------------------------------------------ admission

    def _emit(self, kind: str, /, **fields) -> None:
        """One fleet event, stamped with the router tick (the pump's own
        causal counter — the timeline can line events up with the tick
        profiler even when the wall clock is injected)."""
        if self.events is not None:
            self.events.emit(kind, tick=self._ticks, **fields)

    def _routable(self, i: int) -> bool:
        if i == self._swapping:     # mid-drain/swap: not a candidate
            return False
        if i in self._version_repair:
            # holding weights the fleet rolled back from: traffic (and
            # probation probes) must wait for the version repair
            return False
        return self.health is None or self.health.routable(i)

    def _pick(self, phase: str = "decode") -> Optional[int]:
        """Least occupancy over ROUTABLE replicas (health rank first:
        healthy before degraded before probation); queue depth breaks the
        tie (every replica saturated → the shortest line), replica index
        breaks that (deterministic tests). With disaggregation on, only
        replicas of the request's PHASE role are candidates — unless that
        role has no routable member, in which case the whole routable
        fleet serves it (a quarantined prefill tier degrades to full
        prefill on decode replicas; it never stops the fleet). None when
        nothing at all is routable — the caller sheds at the front
        door."""
        # cp_pick attributes EVERY admission decision (submit, handoff
        # promotion, requeue) — it may nest inside cp_page_ops; the
        # phases are attributions, not a partition
        t0 = self.clock()
        try:
            cands = [i for i in range(len(self.schedulers))
                     if self._routable(i)]
            if not cands:
                return None
            if self._prefill_replicas:
                role = [i for i in cands if self._roles[i] == phase]
                cands = role or cands
            rank = (self.health.rank if self.health is not None
                    else (lambda i: 0))
            return min(cands,
                       key=lambda i: (rank(i), self.schedulers[i].occupancy,
                                      self.schedulers[i].queue_depth, i))
        finally:
            self._cp.add("cp_pick", self.clock() - t0)

    def _wants_prefill_replica(self, req: Request) -> bool:
        """Phase classification: a request is PREFILL-HEAVY when at least
        one full page of its prompt is not already in the shared store —
        the work a dedicated prefill replica exists to absorb. Cached
        stems and sub-page prompts go straight to decode replicas (their
        admission is one page gather + a tail chunk)."""
        if not self._prefill_replicas:
            return False
        # pages are EPOCH-keyed (ISSUE 14): while ROUTABLE replicas'
        # versions diverge (a rolling swap in flight), a prefill job
        # would save pages at one version that the decode admission
        # gathers at another — a guaranteed miss that burns prefill-tier
        # work AND a promote hop. Route straight to decode (full prefill
        # there: the same tokens, one fewer hop) until they converge —
        # the window is bounded by the roll. Non-routable replicas
        # (quarantined / awaiting version repair) carry no traffic, so
        # their stray version must not disable disaggregation.
        versions = {getattr(s.engine, "param_version", 0)
                    for i, s in enumerate(self.schedulers)
                    if self._routable(i)}
        if len(versions) > 1:
            return False
        eng = self.schedulers[0].engine
        prompt = tuple(int(t) for t in req.prompt)
        full = max(0, (len(prompt) - 1) // eng.page_size)
        if full < 1:
            return False
        have, _ = eng._prefix.longest(
            prompt, cap=full, epoch=getattr(eng, "param_version", 0))
        return have < full

    def _shed_at_door(self, rid: int) -> None:
        eta = (self.health.quarantined_eta_s()
               if self.health is not None else None)
        self._router_shed[rid] = {
            "status": "shed", "tokens": [],
            "retry_after_s": round(eta if eta is not None else 1.0, 3)}
        self._where.pop(rid, None)
        self._shed_router += 1
        while len(self._router_shed) > self._shed_cap:
            self._router_shed.pop(next(iter(self._router_shed)))

    def submit(self, req: Request) -> int:
        # the fleet-global rid IS the request's trace id: every span the
        # replica scheduler and engine record for it carries this one id,
        # so a request renders end-to-end across the tiers in Perfetto.
        # Increment only after the replica ACCEPTED — a rejected submit
        # (over-long prompt) must not consume a fleet id.
        rid = self._next_id
        if self._wants_prefill_replica(req):
            i = self._pick("prefill")
            if i is None:
                self._next_id += 1
                self._shed_at_door(rid)
                return rid
            # the PREFILL JOB: same prompt/sampling/deadlines, one token —
            # its whole value is the page-save side effect. The original
            # request rides self._handoff until the job is terminal.
            t0 = self.clock()
            job = dataclasses.replace(req, max_new=1)
            local = self.schedulers[i].submit(job, trace_id=rid,
                                              submit_t=t0)
            self._next_id += 1
            self._where[rid] = (i, local)
            self._handoff[rid] = (req, t0)
            self._handoffs += 1
            return rid
        i = self._pick()
        if i is None:
            # nothing routable: shed at the front door with the earliest
            # probation ETA as the honest retry hint
            self._next_id += 1
            self._shed_at_door(rid)
            return rid
        local = self.schedulers[i].submit(req, trace_id=rid)
        self._next_id += 1
        self._where[rid] = (i, local)
        return rid

    def _promote_handoffs(self) -> None:
        """Move every finished prefill job's ORIGINAL request onto a
        decode replica. ``done`` promotes (the pages are saved; the
        decode admission gathers them) and so does ``shed`` (the prefill
        queue was full — the decode tier may still have room, where the
        request prefills from scratch). A ``timeout``/``error`` job is
        ADOPTED as the request's own verdict instead: the deadline was
        measured from the original submit and a poisoned prefill raises
        wherever it lands, so a decode-side replay could only repeat the
        same outcome while double-counting it in fleet stats — poll()
        keeps reading the job's terminal record through ``_where``."""
        if not self._handoff:
            return
        for rid in list(self._handoff):
            i, local = self._where[rid]
            st = self.schedulers[i].poll(local)
            if st["status"] not in ("done",) + FAILED_STATUSES:
                continue
            req, t0 = self._handoff.pop(rid)
            if st["status"] in ("timeout", "error"):
                continue               # adopted verdict; record retained
            self.schedulers[i].release(local)   # drop a DONE job's record
            j = self._pick()
            if j is None:
                self._shed_at_door(rid)
                continue
            local2 = self.schedulers[j].submit(req, trace_id=rid,
                                               submit_t=t0)
            self._where[rid] = (j, local2)

    def replica_of(self, rid: int) -> int:
        """Which replica holds request ``rid`` (admission audit)."""
        return self._where[rid][0]

    def postmortem_state(self) -> dict:
        """Fleet postmortem context: per-replica in-flight request ids,
        slot ages and health verdicts (host facts only — the
        flight-recorder dump contract)."""
        out = {f"replica{i}": s.postmortem_state()
               for i, s in enumerate(self.schedulers)}
        out["router"] = {"shed_at_door": self._shed_router,
                         "requeued": self._requeued,
                         "version": self._version,
                         "replica_versions": [
                             getattr(s.engine, "param_version", None)
                             for s in self.schedulers],
                         "swaps": self._swaps,
                         "swap_rollbacks": self._swap_rollbacks,
                         "swap_in_progress": self._swap is not None,
                         "version_repair_pending": sorted(
                             self._version_repair)}
        if self._last_swap is not None:
            out["router"]["last_swap"] = dict(self._last_swap)
        if self.health is not None:
            out["router"]["health"] = self.health.states()
            out["router"]["health_counters"] = dict(self.health.counters)
            out["router"]["health_transitions"] = \
                list(self.health.transitions)[-10:]
        return out

    # ------------------------------------------------------ quarantine drain

    def quarantine(self, i: int, cause: str = "forced") -> None:
        """Quarantine replica ``i`` now and requeue its in-flight
        requests onto survivors (operator/test API; the health watchdog
        reaches the same path through :meth:`tick`'s verdicts)."""
        if self.health is None:
            raise RuntimeError(
                "Router health is disabled (single replica without an "
                "explicit HealthConfig) — nothing to quarantine with")
        self.health.quarantine(i, cause)
        self._requeue_from(i)

    def _requeue_from(self, i: int) -> None:
        """Drain replica ``i`` (quarantined, or mid-swap): every
        in-flight request is re-submitted to a survivor in submit order
        with its ORIGINAL fleet rid, trace id and submit time — the
        survivor re-prefills (cached stems in one page gather where its
        prefix pool has them) and regenerates the deterministic token
        stream, so completed tokens are bitwise identical to a
        fault-free run. With no routable survivor the request sheds at
        the front door."""
        moved = shed = 0
        for rec in self.schedulers[i].evict_for_requeue():
            rid = rec.trace_id     # the fleet-global id (we threaded it)
            # a drained prefill JOB stays in its phase: re-route it to a
            # surviving prefill replica (or, via _pick's role fallback,
            # anywhere routable when the whole prefill tier is down)
            phase = "prefill" if rid in self._handoff else "decode"
            j = self._pick(phase)  # never i: quarantined is not routable
            if j is None:
                self._handoff.pop(rid, None)
                self._shed_at_door(rid)
                shed += 1
                continue
            local = self.schedulers[j].submit(
                rec.req, trace_id=rid, submit_t=rec.submit_t, requeued=True)
            self._where[rid] = (j, local)
            self._requeued += 1
            moved += 1
        if moved or shed:
            self._emit("requeue_drain", replica=i, requeued=moved,
                       shed=shed)

    def _probe(self, i: int) -> None:
        """Exercise an idle probation replica with one timed decode probe
        so re-admission does not have to wait for (and gamble) live
        traffic. Engines without a ``probe`` (fakes) skip — their
        probation resolves through routed requests instead."""
        probe = getattr(self.schedulers[i].engine, "probe", None)
        if probe is None:
            return
        t0 = self.clock()
        try:
            probe()
        except Exception as e:  # noqa: BLE001 — a probe failure is the
            # quarantine signal working; nothing to requeue (idle replica)
            self.health.note_fault(i, e)
            return
        self.health.note_tick(i, self.clock() - t0)

    # ------------------------------------------------------ rolling weight swap

    def stamp_version(self, version: int) -> None:
        """Stamp the param version the fleet was BUILT with (serving a
        published version from startup) onto every replica — no swap, no
        drain; call before traffic so record stamps, page epochs and the
        skew tripwire carry the real version."""
        for s in self.schedulers:
            setter = getattr(s.engine, "set_param_version", None)
            if setter is not None:
                setter(version)
        self._version = int(version)

    @property
    def swap_in_progress(self) -> bool:
        return self._swap is not None

    @property
    def version(self) -> int:
        """The fleet's committed param version (per-replica truth is in
        ``stats()``'s ``replica{i}_version`` panel)."""
        return self._version

    def start_swap(self, params, *, version: Optional[int] = None,
                   draft_params=None,
                   config: Optional[SwapConfig] = None) -> int:
        """Begin a ROLLING swap of the fleet onto ``params`` (module
        docstring): one replica per tick is drained via the quarantine
        requeue path (its in-flight requests replay on survivors — the
        fleet never stops serving), swapped with zero recompiles
        (``DecodeEngine.swap_params``), probed, and re-admitted. The
        first swapped replica is the health-gated CANARY
        (:class:`SwapConfig`); a breach inside its window rolls every
        already-swapped replica back to the previous version fleet-wide.

        The swap advances inside :meth:`tick` (one step per tick, so
        live traffic interleaves); with no traffic pending, pump
        :meth:`finish_swap`. ``version`` must be monotone (default:
        committed + 1); ``draft_params`` rides the same transaction on
        spec engines. Returns the target version."""
        if self._swap is not None:
            raise RuntimeError(
                f"a rolling swap to version {self._swap['version']} is "
                "already in progress")
        n = len(self.schedulers)
        if n < 2:
            raise ValueError(
                "a rolling swap needs >= 2 replicas (one drains while "
                "the others serve); a single engine swaps via "
                "DecodeEngine.swap_params after draining")
        version = self._version + 1 if version is None else int(version)
        if version <= self._version:
            raise ValueError(
                f"swap version {version} is not monotone (fleet is at "
                f"{self._version}) — published versions only move "
                "forward")
        cfg = config or SwapConfig()
        rank = (self.health.rank if self.health is not None
                else (lambda i: 0))
        # healthiest replica first: the canary must start from a clean
        # health state or the gate would trip on pre-existing trouble
        order = sorted(range(n), key=lambda i: (rank(i), i))
        self._swap = {
            "version": version, "params": params, "draft": draft_params,
            "cfg": cfg, "order": order, "canary": order[0],
            "canary_swapped": False, "ticks_left": cfg.canary_ticks,
            "ttft_mark": 0, "done": [],
            "prev_params": [s.engine._params for s in self.schedulers],
            "prev_draft": [getattr(s.engine, "_draft_params", None)
                           if getattr(s.engine, "spec_k", 0) else None
                           for s in self.schedulers],
            "prev_version": [getattr(s.engine, "param_version", 0)
                             for s in self.schedulers],
            "watcher": None,
        }
        self._emit("swap_start", version=version, canary=order[0],
                   canary_ticks=cfg.canary_ticks,
                   draft=draft_params is not None)
        log.info("rolling swap to param version %d started (canary "
                 "replica %d, %d-tick window)", version, order[0],
                 cfg.canary_ticks)
        return version

    def maybe_swap_draft(self, watcher, *,
                         config: Optional[SwapConfig] = None
                         ) -> Optional[int]:
        """Poll a :class:`dtf_tpu.publish.PublishWatcher` mounted on a
        DRAFT publish directory (``train_gpt --distill_draft``'s output)
        and roll a **draft-only** swap when it hands over a new version:
        the fleet's base params ride the transaction UNCHANGED and only
        ``draft_params`` flips, so emitted tokens are byte-identical by
        construction (the verifier owns the rng chain) and acceptance is
        the only thing that moves. The fleet version still advances by
        one (monotone — records stamp which draft served them, and the
        prefix-page epoch rolls with it); the watcher is credited with
        ITS version number, which need not match the fleet's."""
        if self._swap is not None:
            return None
        got = watcher.load_new()
        if got is None:
            return None
        dversion, step, draft_params = got
        # every replica shares ONE base tree by construction — replica
        # 0's live params ARE the fleet's params
        base = self.schedulers[0].engine._params
        v = self.start_swap(base, version=self._version + 1,
                            draft_params=draft_params, config=config)
        self._swap["watcher"] = watcher
        self._swap["watcher_version"] = dversion
        self._swap["step"] = step
        log.info("draft-only rolling swap started: draft publish version "
                 "%d rides fleet version %d (base params unchanged)",
                 dversion, v)
        return v

    def maybe_swap_published(self, watcher, *,
                             config: Optional[SwapConfig] = None,
                             draft_factory=None) -> Optional[int]:
        """Poll a :class:`dtf_tpu.publish.PublishWatcher` and start a
        rolling swap when it hands over a NEW verified version (corrupt
        publishes were already skipped with a WARN inside the watcher —
        the fleet keeps serving). ``draft_factory(params) ->
        draft_params`` rebuilds the draft from the new weights (the
        ``--draft_layers`` early-exit case). No-op while a swap is in
        progress. Returns the version a swap was started for, else
        None."""
        if self._swap is not None:
            return None
        got = watcher.load_new()
        if got is None:
            return None
        version, step, params = got
        if version <= self._version:
            watcher.note_applied(version)
            return None
        draft = draft_factory(params) if draft_factory is not None else None
        v = self.start_swap(params, version=version, draft_params=draft,
                            config=config)
        self._swap["watcher"] = watcher
        self._swap["step"] = step
        return v

    def finish_swap(self, max_ticks: int = 100000) -> None:
        """Pump ticks until the in-progress swap commits or rolls back
        (ticks with no traffic still advance the swap machine)."""
        for _ in range(max_ticks):
            if self._swap is None:
                return
            self.tick()
        raise RuntimeError(f"swap still in progress after {max_ticks} "
                           "ticks")

    def _swap_span(self):
        if self.telemetry is None:
            return contextlib.nullcontext()
        return self.telemetry.spans.span("serve_swap")

    def _swap_replica(self, i: int, params, draft, version: int, *,
                      probe: bool = True, mark=None) -> None:
        """Drain replica ``i`` onto the rest of the fleet, swap its
        weights, probe, re-admit — the per-replica step of the rolling
        swap. In-flight requests requeue with their ORIGINAL rid/
        submit_t (the PR 12 path), so a request spanning the swap
        boundary replays WHOLE on exactly one version. ``mark`` (the
        forward-swap callers' bookkeeping) runs the moment
        ``swap_params`` returns — BEFORE the probe — so a replica whose
        probe then raises is already recorded as swapped and a rollback
        includes it (its weights DID flip)."""
        self._swapping = i
        try:
            with self._swap_span():
                self._requeue_from(i)
                self.schedulers[i].engine.swap_params(
                    params, draft_params=draft, version=version)
        finally:
            self._swapping = None
        # ANY successful swap supersedes a pending version repair: the
        # replica now holds the weights this swap installed — a later
        # repair retry would revert it to the STALE rolled-back payload
        # and split the fleet permanently
        self._version_repair.pop(i, None)
        self._repair_backoff.pop(i, None)
        if mark is not None:
            mark()
        quarantined = (self.health is not None
                       and self.health.state(i)
                       == health_lib.QUARANTINED)
        if probe and not quarantined:
            # the same compiled decode, timed and fed to the watchdog: a
            # replica that comes back wedged is caught BEFORE live
            # traffic lands on it (and, for the canary, trips the gate)
            fn = getattr(self.schedulers[i].engine, "probe", None)
            if fn is not None:
                t0 = self.clock()
                fn()        # an exception here = swap failure (caller
                #             rolls the fleet back)
                if (self.health is not None
                        and self.health.note_tick(i, self.clock() - t0)
                        == health_lib.QUARANTINED):
                    self._requeue_from(i)   # nothing in flight; no-op

    def _advance_swap(self) -> None:
        """One step of the rolling-swap state machine, run at the end of
        every tick. Any exception inside a replica's swap step (the
        ``wedge_in_swap`` chaos verb, a failed probe, a bad tree) rolls
        the partial fleet back onto ONE version instead of propagating —
        a swap can fail, the fleet cannot."""
        sw = self._swap
        if sw is None:
            return
        try:
            if not sw["canary_swapped"]:
                i = sw["canary"]

                def mark_canary():
                    sw["canary_swapped"] = True
                    sw["ttft_mark"] = self.schedulers[i].ttft_count
                    self._emit("swap_canary", version=sw["version"],
                               replica=i,
                               canary_ticks=sw["cfg"].canary_ticks)

                self._swap_replica(i, sw["params"], sw["draft"],
                                   sw["version"], mark=mark_canary)
                return
            if sw["ticks_left"] > 0:
                cause = self._canary_breach()
                if cause is not None:
                    self._rollback_swap(f"canary breach: {cause}")
                    return
                sw["ticks_left"] -= 1
                return
            nxt = next((i for i in sw["order"]
                        if i != sw["canary"] and i not in sw["done"]),
                       None)
            if nxt is None:
                self._commit_swap()
                return
            self._swap_replica(nxt, sw["params"], sw["draft"],
                               sw["version"],
                               mark=lambda: sw["done"].append(nxt))
        except Exception as e:  # noqa: BLE001 — swap-step failures roll
            # back; only the rollback itself may quarantine a replica
            self._rollback_swap(
                f"swap step failed: {type(e).__name__}: {e}")

    def _canary_breach(self) -> Optional[str]:
        """The canary gate (SwapConfig docstring): health verdict first,
        then the post-swap TTFT SLO floor. None = clean so far."""
        sw = self._swap
        i = sw["canary"]
        if (self.health is not None
                and self.health.state(i) != health_lib.HEALTHY):
            return f"canary replica {i} health {self.health.state(i)}"
        cfg = sw["cfg"]
        if self.ttft_slo_s > 0.0 and cfg.slo_floor > 0.0:
            # samples SINCE the canary swap, measured against the
            # monotone counter (the deque is maxlen-bounded: an index
            # mark into it goes stale once it wraps — a long-running
            # server would otherwise never see a canary sample again).
            # REQUEUED requests are excluded: their TTFT includes time
            # lost on some OTHER replica's failure (original submit_t —
            # the PR 12 contract), and a gate counting them would blame
            # the new weights for an unrelated fault and blacklist a
            # perfectly good version.
            sched = self.schedulers[i]
            new = sched.ttft_count - sw["ttft_mark"]
            d, rq = sched._ttfts, sched._ttft_requeued
            lo = max(0, len(d) - min(new, len(d)))
            samples = [t for t, requeued in zip(
                itertools.islice(d, lo, None),
                itertools.islice(rq, lo, None)) if not requeued]
            if len(samples) >= cfg.slo_min_samples:
                ok = sum(1 for t in samples
                         if t <= self.ttft_slo_s) / len(samples)
                if ok < cfg.slo_floor:
                    return (f"canary TTFT SLO ok-frac {ok:.3f} < floor "
                            f"{cfg.slo_floor} over {len(samples)} "
                            "completions")
        return None

    def _rollback_swap(self, cause: str) -> None:
        """Fleet-wide rollback: every already-swapped replica (canary
        included) drains and takes its PREVIOUS weights back, so the
        fleet converges on one version. A replica that cannot even swap
        back is quarantined out of traffic — the fleet keeps serving."""
        sw = self._swap
        self._swap = None
        swapped = ([sw["canary"]] if sw["canary_swapped"] else []) \
            + sw["done"]
        log.warning(
            "rolling swap to param version %d ROLLED BACK after %d "
            "replica(s): %s", sw["version"], len(swapped), cause)
        for i in reversed(swapped):
            try:
                self._swap_replica(i, sw["prev_params"][i],
                                   sw["prev_draft"][i],
                                   sw["prev_version"][i], probe=False)
            except Exception as e:  # noqa: BLE001 — a replica wedged in
                # BOTH directions leaves traffic via quarantine, not by
                # failing the rollback of the rest of the fleet; the
                # REPAIR record keeps it unroutable (probation must not
                # re-admit a replica serving the rejected version) until
                # _retry_version_repair re-aligns its weights
                log.warning("replica %d failed to roll back (%r)", i, e)
                self._version_repair[i] = (sw["prev_params"][i],
                                           sw["prev_draft"][i],
                                           sw["prev_version"][i])
                if self.health is not None:
                    self.health.quarantine(i, f"rollback failed: {e!r}")
                    self._requeue_from(i)
        self._swap_rollbacks += 1
        self._last_swap = {"version": sw["version"],
                           "outcome": "rolled_back", "cause": cause}
        self._emit("swap_rollback", version=sw["version"], cause=cause,
                   swapped=len(swapped))
        if sw["watcher"] is not None:
            # a rolled-back version must not immediately re-swap on the
            # next poll: only a NEWER republish may try again (a draft
            # watcher is credited in ITS version numbering)
            sw["watcher"].skipped.add(sw.get("watcher_version",
                                            sw["version"]))
        self._invalidate_stale_pages()

    def _commit_swap(self) -> None:
        sw = self._swap
        self._swap = None
        self._version = sw["version"]
        self._swaps += 1
        self._last_swap = {"version": sw["version"], "outcome": "done"}
        if sw["watcher"] is not None:
            sw["watcher"].note_applied(sw.get("watcher_version",
                                              sw["version"]))
        self._invalidate_stale_pages()
        self._emit("swap_commit", version=sw["version"],
                   draft=sw["draft"] is not None)
        log.info("rolling swap complete: fleet serving param version %d",
                 sw["version"])

    def _retry_version_repair(self, i: int) -> bool:
        """Re-align a replica stuck on rolled-back weights (its reverse
        swap failed) with the fleet's committed version — attempted at
        every tick the health machine would otherwise let it back in,
        BEFORE any probe or traffic. True once aligned."""
        params, draft, version = self._version_repair[i]
        try:
            self._swap_replica(i, params, draft, version, probe=False)
        except Exception as e:  # noqa: BLE001 — still broken: stays
            # unroutable (the repair record); quarantine backoff paces
            # the next try (health), or the tick backoff (health-less)
            log.warning("replica %d version repair failed (%r)", i, e)
            if self.health is not None:
                self.health.quarantine(i, f"version repair failed: {e!r}")
            else:
                _, delay = self._repair_backoff.get(i, (0, 1))
                self._repair_backoff[i] = (self._ticks + delay,
                                           min(delay * 2, 1024))
            return False
        # the record was popped by _swap_replica on success
        log.info("replica %d re-aligned to param version %d after a "
                 "failed rollback", i, version)
        return True

    def _invalidate_stale_pages(self) -> None:
        """Reclaim prefix pages of other param versions once the fleet
        converged (lookups already epoch-gate them — this is the eager
        half of invalidation; pages.py docstring). One pass per DISTINCT
        store: a shared disaggregation pool must not be walked once per
        mounting replica."""
        seen: set[int] = set()
        for s in self.schedulers:
            store = getattr(s.engine, "page_store", None)
            if store is None or id(store) in seen:
                continue
            seen.add(id(store))
            freed = store.index.invalidate_stale(self._version)
            if freed:
                log.info("freed %d stale-version prefix page(s)", freed)

    def _skew_check(self) -> None:
        """The version-skew tripwire (ISSUE 14 satellite): WARN once when
        replicas serve more than one param version OUTSIDE an in-progress
        rolling swap; re-armed when the fleet converges."""
        vs = {getattr(s.engine, "param_version", None)
              for s in self.schedulers}
        vs.discard(None)
        if len(vs) > 1 and self._swap is None:
            if not self._skew_warned:
                self._skew_warned = True
                log.warning(
                    "fleet spans param versions %s outside a rolling "
                    "swap — replicas are serving DIFFERENT weights "
                    "(skew tripwire; re-armed on convergence)",
                    sorted(vs))
        elif len(vs) <= 1:
            self._skew_warned = False

    # ----------------------------------------------------------- pump surface

    @property
    def pending(self) -> int:
        return sum(s.pending for s in self.schedulers)

    def tick(self) -> None:
        """One scheduling round on every ROUTABLE replica with work —
        replicas are independent KV state, so their ticks never contend
        for slots. With health on, each tick is wall-timed and fed to the
        watchdog; a quarantine verdict (slow/wedged/faulted) immediately
        drains that replica onto survivors, so the pump loop never calls
        into a wedged engine again."""
        self._ticks += 1
        # the control-plane tick profiler (ISSUE 20): cp_engine_tick sums
        # the replica s.tick() calls (for the health branch, the SAME
        # wall-time samples the watchdog judges); cp_health_sweep is the
        # replica loop's remainder (routable checks, verdicts, probes);
        # cp_page_ops = handoff promotion; cp_bookkeeping = swap machine +
        # skew tripwire. Host clock arithmetic only.
        cp = self._cp
        h = self.health
        t_loop0 = self.clock()
        engine_s = 0.0
        if h is None:
            for i, s in enumerate(self.schedulers):
                if i in self._version_repair:
                    # paced by the tick backoff: a still-broken engine
                    # must not re-validate + re-place the whole param
                    # tree (and WARN) on every tick of a busy pump
                    if self._ticks >= self._repair_backoff.get(i, (0, 1))[0]:
                        self._retry_version_repair(i)
                    continue
                if s.pending:
                    t0 = self.clock()
                    s.tick()
                    engine_s += self.clock() - t0
        else:
            for i, s in enumerate(self.schedulers):
                if i in self._version_repair:
                    # stuck on a rolled-back version: the repair must land
                    # before the health machine may re-admit it (routable()
                    # flips quarantine→probation lazily — let it, but no
                    # probe/traffic this tick either way)
                    if h.routable(i):
                        self._retry_version_repair(i)
                    continue
                if not h.routable(i):
                    continue
                if not s.pending:
                    if h.state(i) == health_lib.PROBATION:
                        self._probe(i)
                    continue
                t0 = self.clock()
                try:
                    s.tick()
                except Exception as e:  # noqa: BLE001 — a decode-path
                    # engine failure has no single owning request:
                    # quarantine the replica and replay its in-flight
                    # work on survivors
                    engine_s += self.clock() - t0
                    h.note_fault(i, e)
                    self._requeue_from(i)
                    continue
                dur = self.clock() - t0
                engine_s += dur
                if h.note_tick(i, dur) == health_lib.QUARANTINED:
                    self._requeue_from(i)
        t_loop1 = self.clock()
        cp.add("cp_engine_tick", engine_s)
        cp.add("cp_health_sweep", max(0.0, (t_loop1 - t_loop0) - engine_s))
        t0 = self.clock()
        self._promote_handoffs()
        t1 = self.clock()
        cp.add("cp_page_ops", t1 - t0)
        self._advance_swap()
        self._skew_check()
        cp.add("cp_bookkeeping", self.clock() - t1)
        if self.events is not None and self._ticks % self.CP_PROFILE_EVERY == 0:
            self._emit("cp_profile", **{
                f"{name}_total_s": round(cp.total(name), 6)
                for name in ("cp_pick", "cp_engine_tick", "cp_health_sweep",
                             "cp_page_ops", "cp_bookkeeping")})

    def run_until_idle(self, max_ticks: int = 100000, *,
                       on_tick=None) -> None:
        for _ in range(max_ticks):
            if not self.pending:
                return
            self.tick()
            if on_tick is not None:
                on_tick()
        raise RuntimeError(f"requests still pending after {max_ticks} ticks")

    def poll(self, rid: int) -> dict:
        shed = self._router_shed.get(rid)
        if shed is not None:
            return dict(shed)
        if rid in self._handoff:
            # prefill phase of a disaggregated request: the job's local
            # statuses (and its one sampled token) are plumbing — the
            # caller sees a request that is still prefilling
            return {"status": "prefill", "tokens": []}
        i, local = self._where[rid]
        return self.schedulers[i].poll(local)

    def result(self, rid: int, max_ticks: int = 100000) -> list[int]:
        for _ in range(max_ticks):
            st = self.poll(rid)
            if st["status"] == "done":
                return st["tokens"]
            if st["status"] in FAILED_STATUSES:
                # shed/timeout/error are TERMINAL: raise now instead of
                # pumping max_ticks on a request that will never finish
                raise RequestFailed(rid, st)
            self.tick()
        raise RuntimeError(f"request {rid} not done after {max_ticks} ticks")

    def release(self, rid: int) -> None:
        if self._router_shed.pop(rid, None) is not None:
            return
        i, local = self._where.pop(rid)
        self.schedulers[i].release(local)

    def drain(self) -> None:
        self.run_until_idle()

    # --------------------------------------------------------------- metrics

    def trace_counts(self) -> list[dict]:
        """Per-replica program trace counters (page fences merged in) —
        the steady-state recompile pin, fleet edition."""
        return [{**s.engine.trace_counts,
                 **{f"page_{k}": v
                    for k, v in s.engine.page_trace_counts.items()}}
                for s in self.schedulers]

    def accept_by_version(self) -> dict:
        """Fleet-summed per-version speculative acceptance counts,
        ``{version: (proposed, accepted)}`` (ISSUE 19) — the raw ints
        behind ``router_spec_accept_rate_v{N}``."""
        fleet: dict = {}
        for s in self.schedulers:
            for v, (prop, acc) in s.accept_by_version().items():
                cur = fleet.get(v, (0, 0))
                fleet[v] = (cur[0] + prop, cur[1] + acc)
        return dict(sorted(fleet.items()))

    def stats(self, brief: bool = False) -> dict:
        """Fleet aggregates + the ``replica{i}_*`` SLO panel."""
        n = len(self.schedulers)
        out = {
            "router_replicas": float(n),
            "router_completed": float(sum(s._completed
                                          for s in self.schedulers)),
            "router_queue_depth": float(sum(s.queue_depth
                                            for s in self.schedulers)),
            "router_occupancy": (sum(s.occupancy for s in self.schedulers)
                                 / n),
        }
        if brief:
            return out
        # the hot-swap panel (ISSUE 14): committed + per-replica active
        # param versions (the skew tripwire's raw data — _skew_check
        # WARNs on divergence outside a swap), swap/rollback counters
        self._skew_check()
        out["router_version"] = float(self._version)
        out["router_swaps"] = float(self._swaps)
        out["router_swap_rollbacks"] = float(self._swap_rollbacks)
        out["router_swap_in_progress"] = float(self._swap is not None)
        for i, s in enumerate(self.schedulers):
            v = getattr(s.engine, "param_version", None)
            if v is not None:
                out[f"replica{i}_version"] = float(v)
        out["router_shed"] = float(self._shed_router
                                   + sum(s._shed for s in self.schedulers))
        out["router_timeouts"] = float(sum(s._timeouts
                                           for s in self.schedulers))
        out["router_request_errors"] = float(
            sum(s._request_errors for s in self.schedulers))
        out["router_requeued"] = float(self._requeued)
        if self._prefill_replicas:
            out["router_prefill_replicas"] = float(self._prefill_replicas)
            out["router_handoffs"] = float(self._handoffs)
            for i, role in enumerate(self._roles):
                out[f"replica{i}_role"] = role
        if self.health is not None:
            hc = self.health.counters
            out["router_quarantines"] = float(hc["quarantines"])
            out["router_probation_readmits"] = float(hc["readmits"])
            out["router_replica_faults"] = float(hc["faults"])
            for i in range(n):
                out[f"replica{i}_health"] = self.health.state(i)
        # fleet TTFT: with disaggregation on, prefill-role schedulers'
        # samples are JOB latencies (plumbing), not user-visible first
        # tokens — the decode replicas record the real TTFT (measured
        # from the ORIGINAL submit via the threaded submit_t)
        ttfts = [t for i, s in enumerate(self.schedulers)
                 if not (self._prefill_replicas
                         and self._roles[i] == "prefill")
                 for t in s._ttfts]
        out["router_ttft_p50_s"] = _quantile(ttfts, 0.5)
        out["router_ttft_p99_s"] = _quantile(ttfts, 0.99)
        if self.ttft_slo_s > 0.0:
            out["router_ttft_slo_ok_frac"] = (
                sum(1 for t in ttfts if t <= self.ttft_slo_s) / len(ttfts)
                if ttfts else 1.0)
        # the flywheel panel (ISSUE 19): fleet per-version acceptance —
        # a distilled draft's swap shows up as rate_v{new} > rate_v{old}
        for v, (prop, acc) in self.accept_by_version().items():
            if prop:
                out[f"router_spec_accept_rate_v{v}"] = acc / prop
        if self.log_sink is not None:
            out["router_log_sink_records"] = float(
                self.log_sink.stats()["records"])
        # fleet-summed engine counters (prefill chunks, page hits, ...)
        counters: dict = {}
        for s in self.schedulers:
            for k, v in getattr(s.engine, "counters", {}).items():
                counters[k] = counters.get(k, 0) + v
        out.update({f"router_{k}": float(v) for k, v in counters.items()})
        # the control-plane tick profiler panel (ISSUE 20): where the
        # pump's host time goes, per phase — the live view of what the
        # cp_profile events make durable
        out["router_ticks"] = float(self._ticks)
        for name, roll in self._cp.rollup().items():
            out[f"{name}_total_s"] = roll["total_s"]
            out[f"{name}_mean_s"] = roll["mean_s"]
            out[f"{name}_p99_s"] = roll["p99_s"]
        if self.events is not None:
            out["router_events"] = float(self.events.stats()["events"])
        for i, s in enumerate(self.schedulers):
            st = s.stats()
            for k in _REPLICA_KEYS:
                if k in st:
                    out[f"replica{i}_{k}"] = st[k]
        if self.telemetry is not None:
            rollup = self.telemetry.spans.rollup()
            roll = rollup.get("router_wait")
            if roll is not None:
                out["router_wait_p50_s"] = roll["p50_s"]
                out["router_wait_p99_s"] = roll["p99_s"]
            swap_roll = rollup.get("serve_swap")
            if swap_roll is not None:
                out["serve_swap_p50_s"] = swap_roll["p50_s"]
                out["serve_swap_p99_s"] = swap_roll["p99_s"]
        return out


def poisson_replay(router, arrivals, *, clock=time.perf_counter,
                   sleep=time.sleep) -> float:
    """:func:`dtf_tpu.serve.client.replay` works unchanged on a Router
    (same submit/tick/pending surface) — re-exported here so fleet benches
    read naturally."""
    from dtf_tpu.serve.client import replay

    return replay(router, arrivals, clock=clock, sleep=sleep)


__all__ = ["Router", "SwapConfig", "poisson_replay"]
