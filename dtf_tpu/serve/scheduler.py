"""Request scheduling over a :class:`~dtf_tpu.serve.engine.DecodeEngine`.

FIFO admission with prefill/decode interleave: each :meth:`Scheduler.tick`
runs at most ``prefill_chunks_per_tick`` prompt chunks (admitting queued
requests into free slots as chunk budget allows — a long prompt spreads its
prefill over several ticks instead of stalling everyone's decode), then one
``decode_all`` step for every occupied slot. Slots are evicted on EOS, on
``max_new``, or when the slot's ``max_len`` budget fills; the freed slot is
immediately reusable next tick — the continuous-batching loop.

Observability rides :class:`dtf_tpu.metrics.MetricWriter` (the training
stack's writer): queue depth and slot occupancy per logging interval, plus
per-request TTFT and per-token latency on completion. ``stats()`` returns
the same aggregates for benches (``scripts/serve_gpt.py`` prints them as
its one JSON line). With a :class:`dtf_tpu.telemetry.Telemetry` attached
the engine calls are additionally recorded as ``serve_prefill_chunk`` /
``serve_page_load`` / ``serve_page_save`` / ``serve_decode`` phase spans
(host wall time per compiled-program call — the training loop's
data_wait/dispatch decomposition, serving edition) plus ``router_wait``
(queue time between submit and a slot accepting the request — the
admission latency the Router SLO panel watches), and ``stats()`` gains
their p50/p99. All of it is host clock arithmetic: zero added device
readbacks (counter-instrumented test, PR 5 idiom). The same gate also
writes each tick into the profiler's timeline as one
``jax.profiler.TraceAnnotation`` span, ``dtf.serve.tick`` (the engine adds
its own inside it under ``annotate_traces``; docs/OBSERVABILITY.md section
7 has the table), so that a trace reader can say how much of a tick's idle
device time lies outside every engine call. Without telemetry a tick
constructs no annotation.

With an engine built with ``prefix_pages > 0`` admission consults the
prefix page cache: the pinned page chain lands in ONE batched gather on
the same ``prefill_chunks_per_tick`` budget as prompt chunks (one budget
unit replacing ``n_cached/prefill_chunk`` chunks of transformer work),
the live chunks continue at ``start = n_cached``, new full pages scatter
back in one dispatch after the last chunk, and the pin is released on
slot evict — the refcount contract of :mod:`dtf_tpu.serve.pages`.

Resilience (ISSUE 12, docs/RESILIENCE.md "Serving"): requests can end in
a terminal status other than ``done`` —

- ``shed`` — bounded-queue admission control (``max_queue``): an
  over-full queue rejects at submit with a ``retry_after_s`` hint
  instead of growing host memory and tail latency without bound;
- ``timeout`` — per-request deadlines (``Request.ttft_deadline_s`` /
  ``deadline_s``, measured from submit on the scheduler clock) evict at
  the next tick, whether the request is still queued, mid-prefill, or
  decoding;
- ``error`` — an engine exception during ADMISSION is attributed to the
  admitting request and isolates to it (the ``poison_request`` chaos
  verb); decode-path exceptions have no single owner and propagate to
  the Router's health machinery, which quarantines the replica and
  requeues its in-flight requests (:meth:`Scheduler.evict_for_requeue`,
  status ``requeued`` on the vacated replica).

``poll`` reports the terminal status (+hint/cause fields);
:class:`RequestFailed` is what ``result()`` raises immediately instead of
spinning ``max_ticks`` on a request that will never finish.
"""

from __future__ import annotations

import collections
import dataclasses
import logging
import time
import weakref
from typing import Optional, Sequence

from dtf_tpu.metrics import quantile as _quantile
from dtf_tpu.telemetry.spans import trace_annotation

log = logging.getLogger("dtf_tpu")

#: terminal statuses that are NOT success — ``result()`` raises
#: :class:`RequestFailed` on sight instead of pumping to tick exhaustion.
FAILED_STATUSES = ("shed", "timeout", "error")


class RequestFailed(RuntimeError):
    """A request ended in a terminal non-success status (``shed`` /
    ``timeout`` / ``error``). Carries the ``poll()`` payload so callers
    can honor ``retry_after_s`` without a second lookup."""

    def __init__(self, rid: int, info: dict):
        self.rid = rid
        self.status = info.get("status", "?")
        self.info = dict(info)
        hint = ""
        if "retry_after_s" in info:
            hint = f" (retry after {info['retry_after_s']}s)"
        elif info.get("timeout_kind"):
            hint = f" ({info['timeout_kind']} deadline)"
        elif info.get("error"):
            hint = f" ({info['error']})"
        super().__init__(f"request {rid} terminally {self.status}{hint}")


@dataclasses.dataclass(frozen=True)
class Request:
    """One decode request. Sampling fields mirror ``gpt.generate``;
    the deadline fields are client promises measured from submit on the
    scheduler's clock (0 = none): ``ttft_deadline_s`` bounds the wait for
    the FIRST token, ``deadline_s`` the whole request."""

    prompt: Sequence[int]
    max_new: int = 32
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    eos_id: Optional[int] = None
    pad_id: int = 0
    seed: int = 0
    ttft_deadline_s: float = 0.0
    deadline_s: float = 0.0


@dataclasses.dataclass
class _Rec:
    rid: int
    req: Request
    #: queued | prefill | running | done | shed | timeout | error | requeued
    status: str = "queued"
    slot: int = -1
    chunks_done: int = 0
    tokens: list = dataclasses.field(default_factory=list)
    submit_t: float = 0.0
    #: None until the first token lands — NOT 0.0: an injectable test
    #: clock legitimately stamps first tokens at t == 0.0, and a falsy
    #: check would re-arm the TTFT deadline on an actively-decoding row
    first_token_t: Optional[float] = None
    #: TTFT in scheduler TICKS (submit_tick → first_token_tick): the
    #: per-replica clock. On the single-process CPU sim every replica's
    #: wall time shares one thread, so wall TTFT charges a replica for
    #: the whole fleet's work; tick counts are what a real parallel
    #: fleet's wall clock would see (the disaggregation benches/tests
    #: compare on these).
    submit_tick: int = 0
    first_token_tick: Optional[int] = None
    finish_t: float = 0.0
    #: pinned prefix-page chain (engine.prefix_match) — pages loaded so
    #: far, released on slot evict (the refcount contract).
    handle: object = None
    pages_loaded: int = 0
    #: end-to-end trace id (router-assigned global rid when behind one;
    #: the local rid otherwise) — tags every span/trace event this
    #: request touches, through scheduler and engine alike.
    trace_id: int = -1
    #: submit moment on the TraceCollector's clock (chrome ts domain)
    submit_us: float = 0.0
    retry_after_s: float = 0.0        # shed hint (poll surfaces it)
    timeout_kind: str = ""            # "ttft" | "total" on timeout
    error: str = ""                   # admission-failure cause on error
    requeued: bool = False            # re-admitted off a quarantined replica
    #: the param VERSION whose weights decoded this request (ISSUE 14),
    #: stamped at completion from the engine. Exactly ONE version per
    #: request by construction: a rolling swap DRAINS a replica before
    #: swapping it, so a request spanning the boundary replays whole on
    #: one version (tokens cleared on requeue).
    version: Optional[int] = None
    #: per-request speculative accounting (ISSUE 19): proposals the draft
    #: made for this request and how many the verifier accepted — host
    #: ints mirrored off the scheduler's fleet counters, recorded into
    #: the serve-log sink so draft distillation can weigh its examples.
    #: Cleared on requeue with the tokens (the replay regenerates both).
    proposed: int = 0
    accepted: int = 0


class Scheduler:
    """FIFO continuous-batching scheduler (see module docstring).

    ``prefill_chunks_per_tick`` bounds how much prefill work may delay the
    next decode step (0 = admit greedily, whole queue's worth per tick).
    ``clock`` is injectable for deterministic tests.
    """

    def __init__(self, engine, writer=None, *, log_every: int = 0,
                 prefill_chunks_per_tick: int = 4, clock=time.monotonic,
                 completed_cap: int = 100_000, telemetry=None,
                 ttft_slo_s: float = 0.0, max_queue: int = 0,
                 shed_retry_after_s: float = 0.25,
                 postmortem_name: Optional[str] = "serve_scheduler",
                 log_sink=None, replica_index: int = 0):
        self.engine = engine
        self.writer = writer
        #: serve-log sink (ISSUE 19): every terminal ``done`` request is
        #: recorded as future training data — host facts only, zero added
        #: device readbacks (the token ints already crossed in tick()).
        #: A Router threads ONE shared sink here with per-replica indices.
        self._log_sink = log_sink
        self.replica_index = int(replica_index)
        self.log_every = log_every
        self.telemetry = telemetry
        if telemetry is not None and postmortem_name:
            # the serve postmortem: a crash/stall/SIGTERM dump names the
            # in-flight request ids + per-slot ages (host facts only —
            # the dump path must not touch a wedged backend). The Router
            # registers ONE aggregate provider instead (postmortem_name
            # None for its replica schedulers).
            # held WEAKLY: the telemetry object outlives a scheduler its
            # owner has dropped, and a strong bound method would keep the
            # engine — its whole KV cache on the device — alive with it
            # (3.6 GB in the latent-cache cell's traced run, PERF.md PR 31)
            state = weakref.WeakMethod(self.postmortem_state)
            telemetry.add_postmortem_provider(
                postmortem_name, lambda: (state() or dict)())
        #: TTFT service-level objective (0 = untracked): ``stats()`` then
        #: reports the fraction of completed first tokens inside it — the
        #: per-replica SLO rollup the router surfaces (docs/SERVING.md).
        self.ttft_slo_s = ttft_slo_s
        if prefill_chunks_per_tick < 0:
            # a negative budget would be truthy in tick()'s `or 10**9`
            # fallback yet fail `> 0` — admission silently off, replay()
            # spinning forever on a non-empty queue
            raise ValueError(
                f"prefill_chunks_per_tick={prefill_chunks_per_tick} must "
                "be >= 0 (0 = admit greedily)")
        self.prefill_chunks_per_tick = prefill_chunks_per_tick
        self.clock = clock
        if max_queue < 0:
            raise ValueError(f"max_queue={max_queue} must be >= 0 "
                             "(0 = unbounded)")
        #: bounded-queue admission control: with ``max_queue > 0`` a
        #: submit against a full queue is SHED (terminal status + a
        #: retry_after_s hint) instead of queueing forever — overload
        #: sheds load, it does not grow tail latency without bound.
        self.max_queue = max_queue
        self.shed_retry_after_s = shed_retry_after_s
        #: completed records (and latency samples) retained for poll();
        #: beyond the cap the OLDEST finished request is forgotten — a
        #: long-running server must not grow host memory per request.
        #: poll() of a forgotten id raises KeyError; callers that need a
        #: result must collect it within cap completions (or raise the cap).
        self.completed_cap = completed_cap
        self._free = list(range(engine.n_slots))
        self._queue: collections.deque[_Rec] = collections.deque()
        self._admitting: Optional[_Rec] = None
        self._running: dict[int, _Rec] = {}
        self._recs: dict[int, _Rec] = {}
        self._done_order: collections.deque[int] = collections.deque()
        self._next_id = 0
        self._tick = 0
        self._ttfts: collections.deque[float] = collections.deque(
            maxlen=completed_cap)
        #: MONOTONE count of TTFT samples ever recorded (the deque is
        #: maxlen-bounded, so ``len(_ttfts)`` stops moving once full —
        #: windowed consumers like the Router's canary SLO gate measure
        #: "samples since a mark" against this counter instead), plus a
        #: lockstep flag deque marking samples of REQUEUED requests:
        #: their TTFT honestly includes time lost on a dead replica, so
        #: the canary gate must not blame the new weights for them.
        self._ttft_count = 0
        self._ttft_requeued: collections.deque[bool] = collections.deque(
            maxlen=completed_cap)
        self._tok_lats: collections.deque[float] = collections.deque(
            maxlen=completed_cap)
        self._completed = 0
        self._occupancy_sum = 0.0
        self._queue_peak = 0
        # resilience counters (host ints — the stats()/postmortem panel)
        self._shed = 0
        self._timeouts = 0
        self._timeouts_ttft = 0
        self._request_errors = 0
        self._requeued_out = 0
        self._requeued_in = 0
        # speculative-decode acceptance over RUNNING slots only (the
        # engine's own counters also see stale still-active rows)
        self._spec_proposed = 0
        self._spec_accepted = 0
        #: acceptance bucketed by the engine's param version at proposal
        #: time (ISSUE 19): {version: [proposed, accepted]} — the
        #: per-version panel that shows a distilled draft's acceptance
        #: climbing across a draft-only swap.
        self._accept_by_version: dict[int, list] = {}
        # deadline sweeps only run once a deadlined request has been seen
        self._any_deadlines = False

    # ----------------------------------------------------------- submit/poll

    def submit(self, req: Request, *, trace_id: Optional[int] = None,
               submit_t: Optional[float] = None,
               requeued: bool = False) -> int:
        """Accept a request; returns the local rid. ``trace_id`` threads an
        end-to-end id through every span this request touches (the Router
        passes its fleet-global rid; standalone, the local rid is the id).
        ``submit_t``/``requeued`` are the Router's requeue path: a request
        re-admitted off a quarantined replica keeps its ORIGINAL submit
        moment, so its TTFT and deadlines honestly include the lost time."""
        if not 1 <= len(req.prompt) <= self.engine.max_len - 1:
            raise ValueError(
                f"prompt length {len(req.prompt)} must be in "
                f"[1, {self.engine.max_len - 1}]")
        if req.max_new < 1:
            raise ValueError(f"max_new={req.max_new} must be >= 1")
        rid = self._next_id
        self._next_id += 1
        rec = _Rec(rid, req, requeued=requeued,
                   submit_t=self.clock() if submit_t is None else submit_t,
                   submit_tick=self._tick,
                   trace_id=rid if trace_id is None else trace_id)
        tracer = self._tracer()
        if tracer is not None:
            rec.submit_us = tracer.now_us()
        self._recs[rid] = rec
        if requeued:
            self._requeued_in += 1
        if req.ttft_deadline_s > 0 or req.deadline_s > 0:
            self._any_deadlines = True
        if self.max_queue and len(self._queue) >= self.max_queue:
            # admission control: shed NOW with an honest hint instead of
            # joining a line that already guarantees a deadline miss
            rec.status = "shed"
            rec.retry_after_s = round(
                self.shed_retry_after_s
                * (1 + len(self._queue) / self.max_queue), 6)
            self._shed += 1
            self._remember_done(rec)
            return rid
        self._queue.append(rec)
        self._queue_peak = max(self._queue_peak, len(self._queue))
        return rid

    def poll(self, rid: int) -> dict:
        rec = self._recs[rid]
        out = {"status": rec.status, "tokens": list(rec.tokens)}
        if rec.version is not None:
            out["version"] = rec.version
        if rec.status == "shed":
            out["retry_after_s"] = rec.retry_after_s
        elif rec.status == "timeout":
            out["timeout_kind"] = rec.timeout_kind
        elif rec.status == "error":
            out["error"] = rec.error
        return out

    @property
    def pending(self) -> int:
        """Requests not yet finished (queued + prefilling + running)."""
        return (len(self._queue) + (self._admitting is not None)
                + len(self._running))

    # ------------------------------------------------------------------ tick

    def tick(self) -> None:
        """One scheduling round: deadline sweep, bounded prefill, then one
        decode step. With telemetry the round is a ``dtf.serve.tick`` span
        on the profiler's clock (``jax.profiler.TraceAnnotation``, reached
        through telemetry/spans.py so this module stays jax-free at
        import); without, the one attribute test is all it costs."""
        if self.telemetry is None:
            self._round()
            return
        with trace_annotation("dtf.serve.tick"):
            self._round()

    def _round(self) -> None:
        self._tick += 1
        if self._any_deadlines:
            self._sweep_deadlines()
        budget = self.prefill_chunks_per_tick or 10 ** 9
        while budget > 0:
            if self._admitting is None:
                if not (self._queue and self._free):
                    break
                rec = self._queue.popleft()
                rec.slot = self._free.pop(0)
                rec.status = "prefill"
                self._admitting = rec
                # queue time before a replica accepts — the router_wait
                # span (host clocks only: zero added device readbacks)
                if self.telemetry is not None:
                    self.telemetry.spans.add(
                        "router_wait", self.clock() - rec.submit_t)
                    tracer = self._tracer()
                    if tracer is not None:
                        tracer.complete(
                            "queue_wait", cat="request", tid=rec.trace_id,
                            t0_us=rec.submit_us, t1_us=tracer.now_us(),
                            args={"slot": rec.slot})
                # prefix-page lookup at admission (None with the cache
                # off): the pinned chain loads below, on the same budget
                pm = getattr(self.engine, "prefix_match", None)
                if pm is not None:
                    rec.handle = pm(rec.req.prompt)
            rec = self._admitting
            r = rec.req
            try:
                if rec.handle is not None and not rec.pages_loaded:
                    # the whole pinned chain lands in ONE compiled gather —
                    # n_tokens/chunk prefill chunks of work for one budget
                    # unit (it still spends budget so admission cannot
                    # starve decode, and the load deactivates the slot
                    # first)
                    self._timed("serve_page_load", self.engine.load_prefix,
                                rec.slot, rec.handle, tid=rec.trace_id)
                    rec.pages_loaded = len(rec.handle.entries)
                    budget -= 1
                    continue
                start = rec.handle.n_tokens if rec.handle is not None else 0
                # the trace id reaches the ENGINE (XPlane annotation) only
                # when it opted in — simple engines need not know about ids
                ekw = ({"trace_id": rec.trace_id}
                       if getattr(self.engine, "annotate_traces", False)
                       else {})
                out = self._timed(
                    "serve_prefill_chunk", self.engine.prefill_chunk_into,
                    rec.slot, r.prompt, rec.chunks_done, start=start,
                    temperature=r.temperature, top_k=r.top_k, top_p=r.top_p,
                    eos_id=r.eos_id, pad_id=r.pad_id, seed=r.seed,
                    tid=rec.trace_id,
                    targs={"slot": rec.slot, "chunk": rec.chunks_done},
                    **ekw)
            except Exception as e:  # noqa: BLE001 — an ADMISSION failure
                # has exactly one owner: fail that request terminally and
                # keep the replica serving (poison_request isolation).
                # Decode-path exceptions below have no single owner and
                # propagate to the Router's health machinery instead.
                self._fail(rec, e)
                budget -= 1
                continue
            rec.chunks_done += 1
            budget -= 1
            if out is not None:                      # last chunk: tok0
                tok, done = out
                save = getattr(self.engine, "save_prefix_pages", None)
                if save is not None:
                    try:
                        self._timed("serve_page_save", save, rec.slot,
                                    r.prompt, tid=rec.trace_id)
                    except Exception as e:  # noqa: BLE001 — same owner
                        self._fail(rec, e)
                        continue
                rec.first_token_t = self.clock()
                rec.first_token_tick = self._tick
                rec.tokens.append(tok)
                self._admitting = None
                self._ttfts.append(rec.first_token_t - rec.submit_t)
                self._ttft_requeued.append(rec.requeued)
                self._ttft_count += 1
                if done or self._budget_spent(rec):
                    self._finish(rec)
                else:
                    rec.status = "running"
                    self._running[rec.slot] = rec

        if self._running:
            if self.telemetry is None \
                    and not getattr(self.engine, "annotate_traces", False):
                # hottest loop, telemetry off: no per-token id-list /
                # targs allocation for data nothing would consume
                out = self.engine.decode()
            else:
                active = [r.trace_id for r in self._running.values()]
                ekw = ({"trace_ids": active}
                       if getattr(self.engine, "annotate_traces", False)
                       else {})
                out = self._timed(
                    "serve_decode", self.engine.decode,
                    targs={"trace_ids": active}, **ekw)
            now = self.clock()
            spec_k = getattr(self.engine, "spec_k", 0)
            if spec_k:
                # SPECULATIVE tick: up to k+1 tokens per slot, delivered
                # in order until the row's eos or budget — exactly the
                # sequence n_emit plain ticks would have delivered.
                toks, dones, n_emit = out
                ver = int(getattr(self.engine, "param_version", 0) or 0)
                bucket = self._accept_by_version.setdefault(ver, [0, 0])
                for slot, rec in list(self._running.items()):
                    n = int(n_emit[slot])
                    self._spec_proposed += spec_k
                    self._spec_accepted += n - 1
                    rec.proposed += spec_k
                    rec.accepted += n - 1
                    bucket[0] += spec_k
                    bucket[1] += n - 1
                    for j in range(n):
                        rec.tokens.append(int(toks[slot, j]))
                        if bool(dones[slot, j]) or self._budget_spent(rec):
                            rec.finish_t = now
                            self._finish(rec)
                            break
            else:
                toks, dones = out
                for slot, rec in list(self._running.items()):
                    rec.tokens.append(int(toks[slot]))
                    if bool(dones[slot]) or self._budget_spent(rec):
                        rec.finish_t = now
                        self._finish(rec)
        self._occupancy_sum += self._occupancy()

        if (self.writer is not None and self.log_every
                and self._tick % self.log_every == 0):
            self.writer.write_scalars(self._tick, self.stats(brief=True))

    def run_until_idle(self, max_ticks: int = 100000, *,
                       on_tick=None) -> None:
        """Drain the queue. ``on_tick`` (zero-arg, optional) fires after
        every tick — the heartbeat hook point, shared with replay()."""
        for _ in range(max_ticks):
            if not self.pending:
                return
            self.tick()
            if on_tick is not None:
                on_tick()
        raise RuntimeError(f"requests still pending after {max_ticks} ticks")

    # ------------------------------------------------------------- internals

    def _note_samples(self) -> None:
        """What the engine call left for a telemetry object
        (``DecodeEngine.take_samples``: a routed-expert model's picks,
        experts touched, fullest expert and cache positions; whether a
        decode step's sampler stayed greedy), into the SpanRecorder as
        ``serve_<name>``. The channel is named for seconds, but a sample is
        a number: ``mean_s`` and ``total_s`` of these are plain means and
        sums (docs/OBSERVABILITY.md section 7)."""
        take = getattr(self.engine, "take_samples", None)
        if take is not None:
            for name, value in take().items():
                self.telemetry.spans.add(f"serve_{name}", value)

    def _tracer(self):
        """The run's per-request TraceCollector, if one is attached to the
        telemetry object (host-clock chrome events; None = no recording)."""
        return getattr(self.telemetry, "tracer", None)

    def _timed(self, name, fn, *args, tid=None, targs=None, **kwargs):
        """Engine call under a telemetry phase span (no-op without one);
        with a TraceCollector attached, additionally one chrome event
        tagged ``tid`` (the request trace id; the shared "engine" track
        for decode steps serving many requests at once). All host
        perf_counter arithmetic — zero added device readbacks."""
        if self.telemetry is None:
            return fn(*args, **kwargs)
        tracer = self._tracer()
        t0 = None if tracer is None else tracer.now_us()
        try:
            with self.telemetry.spans.span(name):
                return fn(*args, **kwargs)
        finally:
            self._note_samples()
            if tracer is not None:
                tracer.complete(name, cat="engine",
                                tid="engine" if tid is None else tid,
                                t0_us=t0, t1_us=tracer.now_us(), args=targs)

    def _budget_spent(self, rec: _Rec) -> bool:
        return (len(rec.tokens) >= rec.req.max_new
                or len(rec.req.prompt) + len(rec.tokens) >= self.engine.max_len)

    def _occupancy(self) -> float:
        return 1.0 - len(self._free) / self.engine.n_slots

    # -------------------------------------------------- router admission

    @property
    def occupancy(self) -> float:
        """Occupied-slot fraction (prefilling slots included) — the
        router's primary admission signal."""
        return self._occupancy()

    @property
    def queue_depth(self) -> int:
        """Requests accepted but not yet in a slot — the router's
        admission tiebreak."""
        return len(self._queue) + (self._admitting is not None)

    @property
    def ttft_count(self) -> int:
        """Monotone TTFT-sample count (see ``_ttft_count``)."""
        return self._ttft_count

    def _finish(self, rec: _Rec) -> None:
        rec.finish_t = rec.finish_t or self.clock()
        if len(rec.tokens) > 1:
            self._tok_lats.append((rec.finish_t - rec.first_token_t)
                                  / (len(rec.tokens) - 1))
        self._completed += 1
        self._retire(rec, "done")

    def _retire(self, rec: _Rec, status: str,
                now: Optional[float] = None) -> None:
        """Shared terminal bookkeeping for done/shed/timeout/error: stamp
        the status, emit the lifecycle trace slice, release the prefix
        pin, free the slot (if the request held one) and enter the
        bounded retention window."""
        rec.status = status
        rec.finish_t = rec.finish_t or (self.clock() if now is None else now)
        if status == "done":
            # the version-stamp contract (ISSUE 14): every completed
            # record names the param version that decoded it — the
            # engine's CURRENT version is the whole request's version
            # because a swap drains in-flight work first (see _Rec)
            rec.version = getattr(self.engine, "param_version", None)
            if self._log_sink is not None:
                # the flywheel's write point (ISSUE 19): every fact here
                # is a host int/float the scheduler already holds
                self._log_sink.record({
                    "rid": rec.trace_id if rec.trace_id >= 0 else rec.rid,
                    "replica": self.replica_index,
                    "version": rec.version,
                    "status": status,
                    "prompt": [int(t) for t in rec.req.prompt],
                    "tokens": list(rec.tokens),
                    "ttft_s": round(rec.first_token_t - rec.submit_t, 6)
                    if rec.first_token_t is not None else None,
                    "latency_s": round(rec.finish_t - rec.submit_t, 6),
                    "proposed": rec.proposed,
                    "accepted": rec.accepted,
                })
        tracer = self._tracer()
        if tracer is not None:
            # the request's whole lifecycle as ONE slice on its own track
            # — renders submit → terminal in Perfetto with the engine-call
            # slices (tagged with the same trace id) nested visually
            args = {"rid": rec.rid, "status": status,
                    "prompt_len": len(rec.req.prompt),
                    "tokens": len(rec.tokens)}
            if rec.first_token_t is not None:
                args["ttft_s"] = round(rec.first_token_t - rec.submit_t, 6)
            tracer.complete("request", cat="request", tid=rec.trace_id,
                            t0_us=rec.submit_us, t1_us=tracer.now_us(),
                            args=args)
        if rec.handle is not None:       # refcount release on slot evict
            self.engine.release_prefix(rec.handle)
            rec.handle = None
        if rec.slot >= 0:
            self._running.pop(rec.slot, None)
            self._free.append(rec.slot)
            self._free.sort()
            rec.slot = -1
        self._remember_done(rec)

    def _remember_done(self, rec: _Rec) -> None:
        self._done_order.append(rec.rid)
        while len(self._done_order) > self.completed_cap:
            self._recs.pop(self._done_order.popleft(), None)

    def _fail(self, rec: _Rec, e: BaseException) -> None:
        """An admission-path engine failure owned by ``rec``: fail it
        terminally (status ``error``) and keep serving — the chaos
        contract that one poisoned request cannot take the replica with
        it. The device slot needs no cleanup: a half-prefilled slot is
        stale state the next admission fully resets (PR 4 contract)."""
        self._request_errors += 1
        rec.error = repr(e)[:200]
        log.warning("request %d failed in admission: %s",
                    rec.rid, rec.error)
        if self._admitting is rec:
            self._admitting = None
        self._retire(rec, "error")

    def _timeout(self, rec: _Rec, kind: str, now: float) -> None:
        self._timeouts += 1
        if kind == "ttft":
            self._timeouts_ttft += 1
        rec.timeout_kind = kind
        self._retire(rec, "timeout", now)

    def _deadline_kind(self, rec: _Rec, now: float) -> Optional[str]:
        r = rec.req
        waited = now - rec.submit_t
        if (r.ttft_deadline_s > 0 and rec.first_token_t is None
                and waited >= r.ttft_deadline_s):
            return "ttft"
        if r.deadline_s > 0 and waited >= r.deadline_s:
            return "total"
        return None

    def _sweep_deadlines(self) -> None:
        """Evict every request past its deadline — queued, mid-prefill or
        decoding alike (the freed slot is reusable this same tick). An
        abandoned mid-prefill slot leaves only stale device state the
        next admission resets."""
        now = self.clock()
        for rec in [rec for rec in self._queue
                    if self._deadline_kind(rec, now)]:
            self._queue.remove(rec)
            self._timeout(rec, self._deadline_kind(rec, now), now)
        rec = self._admitting
        if rec is not None:
            kind = self._deadline_kind(rec, now)
            if kind:
                self._admitting = None
                self._timeout(rec, kind, now)
        for rec in list(self._running.values()):
            kind = self._deadline_kind(rec, now)
            if kind:
                self._timeout(rec, kind, now)

    # ------------------------------------------------------ quarantine drain

    def evict_for_requeue(self) -> list:
        """Vacate every in-flight request (queued + admitting + running)
        for re-admission elsewhere — the Router's quarantine drain. The
        records are returned in SUBMIT order (deterministic re-routing),
        marked ``requeued`` here as tombstones; their prefix pins are
        released (host-side index work — safe against a wedged engine),
        tokens are cleared (survivors regenerate the full deterministic
        stream), and every slot is freed. The engine's device state needs
        no touch: stale slots are masked spectators until re-admission
        resets them."""
        recs = list(self._queue)
        if self._admitting is not None:
            recs.append(self._admitting)
        recs += list(self._running.values())
        recs.sort(key=lambda r: r.rid)
        self._queue.clear()
        self._admitting = None
        self._running.clear()
        self._free = list(range(self.engine.n_slots))
        for rec in recs:
            if rec.handle is not None:
                try:
                    self.engine.release_prefix(rec.handle)
                except Exception:  # noqa: BLE001 — draining a broken
                    pass           # replica must not fail the requeue
                rec.handle = None
            rec.pages_loaded = 0
            rec.slot = -1
            rec.tokens = []
            rec.proposed = 0
            rec.accepted = 0
            rec.status = "requeued"
            self._requeued_out += 1
        return recs

    def release(self, rid: int) -> None:
        """Drop a completed request's record (tokens included) — call after
        consuming the result to keep a long-running server's host memory
        flat without relying on the completed_cap backstop."""
        rec = self._recs.get(rid)
        if rec is not None and rec.status == "done":
            self._recs.pop(rid, None)

    # ----------------------------------------------------------- postmortem

    def postmortem_state(self) -> dict:
        """In-flight request ids + per-slot ages for the flight-recorder
        dump — pure host clocks and counters (the dump fires exactly when
        the backend may be wedged, so NO device API on this path)."""
        now = self.clock()
        in_flight, slot_ages = [], {}
        recs = list(self._queue)
        if self._admitting is not None:
            recs.append(self._admitting)
        recs += list(self._running.values())
        for rec in recs:
            in_flight.append({
                "rid": rec.rid, "trace_id": rec.trace_id,
                "status": rec.status, "slot": rec.slot,
                "age_s": round(now - rec.submit_t, 3),
                "tokens": len(rec.tokens)})
            if rec.slot >= 0:
                slot_ages[str(rec.slot)] = round(now - rec.submit_t, 3)
        return {"in_flight": in_flight,
                "queue_depth": len(self._queue),
                "occupancy": round(self._occupancy(), 4),
                "slot_ages_s": slot_ages,
                "completed": self._completed,
                "shed": self._shed,
                "timeouts": self._timeouts,
                "request_errors": self._request_errors,
                "requeued_out": self._requeued_out,
                "requeued_in": self._requeued_in}

    # --------------------------------------------------------------- metrics

    def accept_by_version(self) -> dict:
        """Per-param-version speculative acceptance counts,
        ``{version: (proposed, accepted)}`` — raw ints so a Router can
        fleet-sum them (the rate panel lives in :meth:`stats`)."""
        return {v: (b[0], b[1])
                for v, b in sorted(self._accept_by_version.items())}

    def stats(self, brief: bool = False) -> dict:
        """Aggregate serving metrics (floats, MetricWriter-compatible)."""
        out = {
            "serve_queue_depth": float(len(self._queue)
                                       + (self._admitting is not None)),
            "serve_occupancy": self._occupancy(),
            "serve_completed": float(self._completed),
        }
        if brief:
            if self._ttfts:
                out["serve_ttft_last_s"] = self._ttfts[-1]
            return out
        out.update({
            "serve_ticks": float(self._tick),
            "serve_shed": float(self._shed),
            "serve_timeouts": float(self._timeouts),
            "serve_timeouts_ttft": float(self._timeouts_ttft),
            "serve_request_errors": float(self._request_errors),
            "serve_requeued_out": float(self._requeued_out),
            "serve_requeued_in": float(self._requeued_in),
            "serve_queue_peak": float(self._queue_peak),
            "serve_occupancy_mean": (self._occupancy_sum / self._tick
                                     if self._tick else 0.0),
            "serve_ttft_p50_s": _quantile(self._ttfts, 0.5),
            "serve_ttft_p99_s": _quantile(self._ttfts, 0.99),
            "serve_tok_latency_p50_s": _quantile(self._tok_lats, 0.5),
            "serve_tok_latency_p99_s": _quantile(self._tok_lats, 0.99),
        })
        if self._spec_proposed:
            out["serve_spec_accept_rate"] = (self._spec_accepted
                                             / self._spec_proposed)
        for v, (prop, acc) in sorted(self._accept_by_version.items()):
            if prop:
                out[f"serve_spec_accept_rate_v{v}"] = acc / prop
        if self.ttft_slo_s > 0.0:
            out["serve_ttft_slo_ok_frac"] = (
                sum(1 for t in self._ttfts if t <= self.ttft_slo_s)
                / len(self._ttfts) if self._ttfts else 1.0)
        counters = getattr(self.engine, "counters", None)
        if counters is not None:
            out.update({f"serve_{k}": float(v) for k, v in counters.items()})
        prefix = getattr(self.engine, "prefix_stats", None)
        if prefix is not None:
            out.update({f"serve_prefix_{k}": float(v)
                        for k, v in prefix().items()})
        if self.telemetry is not None:
            for name, roll in self.telemetry.spans.rollup().items():
                if name.startswith("serve_") or name == "router_wait":
                    out[f"{name}_p50_s"] = roll["p50_s"]
                    out[f"{name}_p99_s"] = roll["p99_s"]
        return out
