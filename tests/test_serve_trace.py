"""Per-request serve traces (ISSUE 8): trace ids threaded router →
scheduler → engine spans, the chrome-trace request lifecycle export, the
serve heartbeat, the flight-recorder serve postmortem — and the proof
that ALL of it adds zero blocking device readbacks (the PR 3/5
counter-instrumented idiom)."""

import json
import logging

import pytest

from dtf_tpu.serve import Heartbeat, Request, Router, Scheduler, replay
from dtf_tpu.serve.router import poisson_replay  # noqa: F401  (API kept)
from dtf_tpu.telemetry import Telemetry, TraceCollector

MAX_LEN = 48


class _CastCounter:
    def __init__(self, v, casts):
        self.v = v
        self.casts = casts

    def __int__(self):
        self.casts.append("int")
        return int(self.v)

    def __bool__(self):
        self.casts.append("bool")
        return bool(self.v)


class _CountArr:
    def __init__(self, vals, casts):
        self.vals = vals
        self.casts = casts

    def __getitem__(self, i):
        return _CastCounter(self.vals[i], self.casts)


class _FakeEngine:
    """Host-only engine (the test_serve_router idiom): one chunk per
    prompt, pad-token decodes; outputs count their device casts."""

    n_slots = 2
    max_len = MAX_LEN
    prefill_chunk = 64

    def __init__(self, casts=None):
        self.casts = [] if casts is None else casts

    def prefill_chunk_into(self, slot, prompt, chunk_i, *, start=0, **kw):
        return int(prompt[0]) % 7, False

    def decode(self, **kw):
        return (_CountArr([1] * self.n_slots, self.casts),
                _CountArr([False] * self.n_slots, self.casts))


def _tel_with_tracer():
    tel = Telemetry(watchdog=False)
    tel.tracer = TraceCollector()
    return tel


# --------------------------------------------------------------------------
# trace ids: router-global, threaded through every span
# --------------------------------------------------------------------------

def test_router_threads_global_trace_id_through_replicas():
    """The fleet-global router rid IS the trace id each replica scheduler
    records — a request's lifecycle, queue wait, prefill and decode
    events all carry one id, whichever replica served it."""
    tel = _tel_with_tracer()
    router = Router([_FakeEngine(), _FakeEngine()], telemetry=tel)
    rids = [router.submit(Request(prompt=[i + 1], max_new=2))
            for i in range(4)]
    router.drain()
    events = tel.tracer.events
    lifecycles = {e["tid"]: e for e in events if e["name"] == "request"}
    assert set(lifecycles) == set(rids)          # global ids, not local
    for rid in rids:
        ev = lifecycles[rid]
        assert ev["args"]["tokens"] == 2
        assert ev["args"]["ttft_s"] >= 0.0
        # the same id tags its queue-wait and prefill slices
        tagged = [e["name"] for e in events if e["tid"] == rid]
        assert "queue_wait" in tagged
        assert "serve_prefill_chunk" in tagged
    # decode steps serve many requests at once: shared track, ids in args
    decodes = [e for e in events if e["name"] == "serve_decode"]
    assert decodes and all(e["tid"] == "engine" for e in decodes)
    served = {t for e in decodes for t in e["args"]["trace_ids"]}
    assert served <= set(rids) and served


def test_standalone_scheduler_uses_local_rid_as_trace_id():
    tel = _tel_with_tracer()
    sched = Scheduler(_FakeEngine(), telemetry=tel)
    rid = sched.submit(Request(prompt=[3], max_new=1))
    sched.run_until_idle()
    names = {(e["name"], e["tid"]) for e in tel.tracer.events}
    assert ("request", rid) in names


def test_explicit_trace_id_wins():
    tel = _tel_with_tracer()
    sched = Scheduler(_FakeEngine(), telemetry=tel)
    sched.submit(Request(prompt=[3], max_new=1), trace_id=777)
    sched.run_until_idle()
    assert any(e["tid"] == 777 for e in tel.tracer.events)


def test_engine_gets_trace_ids_only_when_annotating():
    """Simple engines (fakes, foreign implementations) never see trace
    kwargs; an engine that sets annotate_traces receives them."""
    seen = {}

    class _Probe(_FakeEngine):
        annotate_traces = True

        def prefill_chunk_into(self, slot, prompt, chunk_i, *, start=0,
                               trace_id=None, **kw):
            seen["prefill"] = trace_id
            return 1, False

        def decode(self, *, trace_ids=None, **kw):
            seen["decode"] = list(trace_ids or [])
            return super().decode()

    sched = Scheduler(_Probe(), telemetry=_tel_with_tracer())
    sched.submit(Request(prompt=[3], max_new=2), trace_id=42)
    sched.run_until_idle()
    assert seen["prefill"] == 42
    assert seen["decode"] == [42]


def test_trace_events_export_as_chrome_json(tmp_path):
    from dtf_tpu.telemetry.profile import export_chrome_trace

    tel = _tel_with_tracer()
    sched = Scheduler(_FakeEngine(), telemetry=tel)
    sched.submit(Request(prompt=[5], max_new=2))
    sched.run_until_idle()
    path = str(tmp_path / "serve_trace.json")
    export_chrome_trace(path, request_events=tel.tracer.events,
                        meta={"source": "test"})
    with open(path) as f:
        doc = json.load(f)
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"request", "queue_wait", "serve_prefill_chunk",
            "serve_decode"} <= names


# --------------------------------------------------------------------------
# zero added blocking readbacks (counter-instrumented, PR 3/5 idiom)
# --------------------------------------------------------------------------

def test_request_tracing_adds_zero_blocking_readbacks():
    """Tracer-on serving casts device outputs exactly as often as
    telemetry-off: trace ids and chrome events are host bookkeeping, not
    readbacks."""
    def run(tel):
        casts = []
        router = Router([_FakeEngine(casts), _FakeEngine(casts)],
                        telemetry=tel, ttft_slo_s=1.0)
        for i in range(6):
            router.submit(Request(prompt=[i + 1], max_new=3))
        router.drain()
        router.stats()
        return len(casts)

    off = run(None)
    on = run(_tel_with_tracer())
    assert off == on, (off, on)
    assert off > 0


# --------------------------------------------------------------------------
# serve heartbeat (--stats_every satellite)
# --------------------------------------------------------------------------

def test_heartbeat_emits_every_n_ticks():
    sched = Scheduler(_FakeEngine(), telemetry=None, ttft_slo_s=1.0)
    lines = []
    hb = Heartbeat(sched, every_ticks=2, emit=lines.append)
    for i in range(4):
        sched.submit(Request(prompt=[i + 1], max_new=3))
    while sched.pending:
        sched.tick()
        hb.maybe_emit()
    assert hb.emitted == len(lines) >= 1
    snap = json.loads(lines[-1])
    assert snap["serve_heartbeat"] == hb.emitted - 1
    assert "serve_occupancy" in snap
    assert "serve_ttft_p50_s" in snap
    assert "serve_ttft_slo_ok_frac" in snap


def test_heartbeat_replay_on_tick_wiring():
    sched = Scheduler(_FakeEngine())
    lines = []
    hb = Heartbeat(sched, every_ticks=1, emit=lines.append)
    arrivals = [(0.0, Request(prompt=[i + 1], max_new=2))
                for i in range(3)]
    replay(sched, arrivals, on_tick=hb.maybe_emit)
    assert lines and sched.pending == 0


def test_heartbeat_includes_router_replica_panel():
    router = Router([_FakeEngine(), _FakeEngine()], ttft_slo_s=1.0)
    lines = []
    hb = Heartbeat(router, every_ticks=1, emit=lines.append)
    for i in range(4):
        router.submit(Request(prompt=[i + 1], max_new=2))
    while router.pending:
        router.tick()
        hb.maybe_emit()
    snap = json.loads(lines[-1])
    assert "router_occupancy" in snap
    assert "router_ttft_slo_ok_frac" in snap
    assert any(k.startswith("replica0_") for k in snap)


def test_heartbeat_slo_floor_warns_once_per_excursion(caplog):
    """A sustained SLO breach logs ONE warning, re-armed only after
    compliance recovers above the floor."""
    class _Sched:
        def __init__(self):
            self.frac = 1.0
            self.pending = 0

        def stats(self):
            return {"serve_ttft_slo_ok_frac": self.frac,
                    "serve_ttft_p99_s": 2.0}

    s = _Sched()
    hb = Heartbeat(s, every_ticks=1, slo_floor=0.9, emit=lambda _: None)
    with caplog.at_level(logging.WARNING, logger="dtf_tpu"):
        hb.maybe_emit()                   # compliant: no warning
        s.frac = 0.5
        hb.maybe_emit()                   # breach: warn
        hb.maybe_emit()                   # still breached: silent
        s.frac = 1.0
        hb.maybe_emit()                   # recovered: re-armed
        s.frac = 0.5
        hb.maybe_emit()                   # second excursion: warn again
    warns = [r for r in caplog.records if "SLO" in r.getMessage()]
    assert len(warns) == 2


def test_heartbeat_rejects_bad_cadence():
    with pytest.raises(ValueError):
        Heartbeat(Scheduler(_FakeEngine()), every_ticks=0)


# --------------------------------------------------------------------------
# flight-recorder serve postmortem (in-flight ids + slot ages)
# --------------------------------------------------------------------------

def test_scheduler_postmortem_state_names_in_flight_requests():
    t = [0.0]
    sched = Scheduler(_FakeEngine(), clock=lambda: t[0])
    sched.submit(Request(prompt=[1], max_new=30))
    sched.submit(Request(prompt=[2], max_new=30))
    sched.submit(Request(prompt=[3], max_new=30))   # n_slots=2: one queues
    t[0] = 1.0
    sched.tick()
    t[0] = 3.5
    st = sched.postmortem_state()
    assert len(st["in_flight"]) == 3
    by_rid = {r["rid"]: r for r in st["in_flight"]}
    assert by_rid[0]["status"] == "running" and by_rid[0]["slot"] >= 0
    assert by_rid[2]["status"] == "queued" and by_rid[2]["slot"] == -1
    assert by_rid[0]["age_s"] == pytest.approx(3.5)
    assert st["slot_ages_s"] and st["queue_depth"] == 1
    # completed requests vanish from the in-flight view
    sched.run_until_idle()
    assert sched.postmortem_state()["in_flight"] == []


def test_postmortem_dump_carries_serve_context():
    """A crash/stall/SIGTERM dump names the router's in-flight request ids
    and per-slot ages — and the provider path touches host state only
    (the fake engine would have counted any device cast)."""
    casts = []
    tel = Telemetry(watchdog=False)
    router = Router([_FakeEngine(casts), _FakeEngine(casts)],
                    telemetry=tel)
    for i in range(4):
        router.submit(Request(prompt=[i + 1], max_new=50))
    router.tick()
    n_casts = len(casts)
    post = tel.dump_postmortem("stall", {"stalled_for_s": 99.0})
    assert len(casts) == n_casts            # dump path: zero device casts
    ctx = post["context"]["serve_router"]
    reps = [v for k, v in ctx.items() if k.startswith("replica")]
    flights = [r for rep in reps for r in rep["in_flight"]]
    assert {f["trace_id"] for f in flights} == {0, 1, 2, 3}
    assert any(rep["slot_ages_s"] for rep in reps)
    # the ISSUE 12 fleet summary rides next to the replica entries:
    # requeue/shed counters + per-replica health verdicts
    assert ctx["router"]["requeued"] == 0
    assert ctx["router"]["health"] == ["healthy", "healthy"]


def test_postmortem_provider_error_never_masks_dump():
    tel = Telemetry(watchdog=False)
    tel.add_postmortem_provider("bad", lambda: 1 / 0)
    post = tel.dump_postmortem("crash")
    assert "provider_error" in post["context"]["bad"]
    assert post["reason"] == "crash"


def test_standalone_scheduler_registers_own_provider():
    tel = Telemetry(watchdog=False)
    sched = Scheduler(_FakeEngine(), telemetry=tel)
    sched.submit(Request(prompt=[1], max_new=5))
    post = tel.dump_postmortem("sigterm")
    assert post["context"]["serve_scheduler"]["queue_depth"] == 1


# --------------------------------------------------------------------------
# the dtf.* spans of a tick, on the profiler's clock (ISSUE 24)
# --------------------------------------------------------------------------

#: every span of docs/OBSERVABILITY.md section 7's table, and its parent
DTF_SPANS = {
    "dtf.serve.tick": None,
    "dtf.serve.prefill_chunk": "dtf.serve.tick",
    "dtf.serve.decode": "dtf.serve.tick",
    "dtf.engine.prefill.dispatch": "dtf.serve.prefill_chunk",
    "dtf.engine.prefill.readback": "dtf.serve.prefill_chunk",
    "dtf.engine.decode.dispatch": "dtf.serve.decode",
    "dtf.engine.decode.readback": "dtf.serve.decode",
}


@pytest.fixture(scope="module")
def tiny_engine():
    import dataclasses

    import jax
    import jax.numpy as jnp

    from dtf_tpu.models import gpt
    from dtf_tpu.serve import DecodeEngine

    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)
    model = gpt.GPT(dataclasses.replace(cfg, decode_len=MAX_LEN))
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    return DecodeEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                        prefill_chunk=4)


def _serve_some(sched, n_ticks=None):
    for i in range(5):
        # 6 tokens = two chunks of 4: a chunk that reads nothing back, and
        # a last chunk that does
        sched.submit(Request(prompt=[1 + i, 2, 3, 4, 5, 6], max_new=3))
    if n_ticks is None:
        sched.run_until_idle()
    else:
        for _ in range(n_ticks):
            sched.tick()


def test_traced_tick_writes_every_dtf_span_nested_under_its_cause(
        tiny_engine):
    """A tiny engine under the profiler on the CPU (as the benchmark's
    ``--rehearse 1 --trace 1`` runs are), switched on by the two handles
    the benchmark uses: every span of the table is in the trace, read by
    the benchmark's own loader, and each lies inside a span of its
    parent's name — so every engine span lies inside a tick."""
    from benchmarks.lib import xtrace

    sched = Scheduler(tiny_engine, telemetry=Telemetry(watchdog=False))
    steps0 = tiny_engine.counters["decode_steps"]
    tiny_engine.annotate_traces = True
    try:
        trace_dir = xtrace.start()
        _serve_some(sched)
        trace = xtrace.stop(trace_dir)
    finally:
        tiny_engine.annotate_traces = False
    by_name = {}
    for name, start, dur in trace.host:
        by_name.setdefault(name, []).append((start, start + dur))
    assert set(DTF_SPANS) <= set(by_name), set(DTF_SPANS) - set(by_name)
    # nothing else claims the vocabulary: the table, and the collections
    # that happened to fall inside the traced stretch
    assert {n for n in by_name if n.startswith("dtf.")} - {"dtf.host.gc"} \
        == set(DTF_SPANS)
    for name, parent in DTF_SPANS.items():
        if parent is None:
            continue
        for s, e in by_name[name]:
            assert any(ps <= s and e <= pe for ps, pe in by_name[parent]), (
                name, parent)
    ticks = len(by_name["dtf.serve.tick"])
    # at most one decode a tick; the tick runs one step ahead, so a decode
    # holds at most one dispatch (step k) and one readback (step k-1), and
    # every step dispatched is read back by the time the run drains
    assert len(by_name["dtf.serve.decode"]) <= ticks
    for phase in ("dispatch", "readback"):
        inner = by_name[f"dtf.engine.decode.{phase}"]
        for ps, pe in by_name["dtf.serve.decode"]:
            assert sum(ps <= s and e <= pe for s, e in inner) <= 1, phase
    assert (len(by_name["dtf.engine.decode.dispatch"])
            == len(by_name["dtf.engine.decode.readback"])
            == tiny_engine.counters["decode_steps"] - steps0)
    # five requests of two chunks: every chunk dispatches, the last of
    # each request reads its first token back
    assert len(by_name["dtf.serve.prefill_chunk"]) == 10
    assert len(by_name["dtf.engine.prefill.dispatch"]) == 10
    assert len(by_name["dtf.engine.prefill.readback"]) == 5
    # the span-recorder keys the accepted metrics read are as they were
    rolled = sched.telemetry.spans.rollup()
    assert rolled["serve_prefill_chunk"]["count"] == 10
    assert rolled["serve_decode"]["count"] == len(by_name["dtf.serve.decode"])
    assert not any(k.startswith("dtf.") for k in rolled)
    # and the tick's host account, one sample of each a tick
    for name in ("serve_tick_cpu", "serve_tick_preempted", "serve_tick_gc"):
        assert rolled[name]["count"] == ticks, name


def test_a_collection_inside_a_traced_tick_writes_one_dtf_host_gc(
        tiny_engine, monkeypatch):
    """A forced ``gc.collect()`` inside a decode step, with automatic
    collection off so that it is the only one: one ``dtf.host.gc`` span in
    the trace, inside a ``dtf.serve.tick``, and its pause in that tick's
    ``serve_tick_gc`` sample."""
    import gc

    from benchmarks.lib import xtrace

    decode = type(tiny_engine).decode
    forced = []

    def collecting(self, **kw):
        if not forced:
            forced.append(gc.collect())
        return decode(self, **kw)

    tel = Telemetry(watchdog=False)
    sched = Scheduler(tiny_engine, telemetry=tel)
    monkeypatch.setattr(type(tiny_engine), "decode", collecting)
    monkeypatch.setattr(tiny_engine, "annotate_traces", True)
    trace_dir = xtrace.start()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        _serve_some(sched)
    finally:
        if was_enabled:
            gc.enable()
        trace = xtrace.stop(trace_dir)
    assert forced
    gcs = [(s, s + d) for name, s, d in trace.host if name == "dtf.host.gc"]
    ticks = [(s, s + d) for name, s, d in trace.host
             if name == "dtf.serve.tick"]
    assert len(gcs) == 1, gcs
    assert any(ts <= gs and ge <= te for ts, te in ticks for gs, ge in gcs)
    paused = tel.spans.rollup()["serve_tick_gc"]
    assert paused["total_s"] > 0 and paused["p99_s"] == paused["total_s"]


@pytest.mark.parametrize("telemetry,annotate,expect_some", [
    (False, False, False),     # the measured run: nothing is constructed
    (True, False, True),       # the scheduler's tick span only
    (False, True, True),       # engine spans only
])
def test_untraced_ticks_construct_no_annotation(tiny_engine, monkeypatch,
                                                telemetry, annotate,
                                                expect_some):
    """With ``telemetry=None`` and ``annotate_traces=False`` — how the
    benchmark measures and how a user's default serves — 20 ticks
    construct no ``jax.profiler.TraceAnnotation`` at all; each gate
    switches on its own layer's spans and not the other's. A scheduler
    without telemetry files nothing and installs no ``gc`` callback, so
    the garbage-collection hook never annotates for it."""
    import gc

    import jax

    made = []

    class Counted(jax.profiler.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    gc.collect()                   # no scheduler of an earlier test lingers
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counted)
    hooks = list(gc.callbacks)
    sched = Scheduler(tiny_engine, telemetry=Telemetry(watchdog=False)
                      if telemetry else None)
    monkeypatch.setattr(tiny_engine, "annotate_traces", annotate)
    _serve_some(sched, n_ticks=20)
    gc.collect()                   # a collection the hook, if any, sees
    assert sched.stats()["serve_completed"] == 5
    if not telemetry:
        assert gc.callbacks == hooks
        assert not any(k.startswith("serve_tick_") for k in sched.stats())
    if not expect_some:
        assert made == []
        return
    if telemetry:
        assert made == ["dtf.serve.tick"] * 20
    else:
        # dtf.serve.prefill_chunk / .decode are the engine's whole-call spans
        assert {name.split(".")[1] for name in made} == {"serve", "engine"}
        assert "dtf.serve.tick" not in made
