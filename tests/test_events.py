"""The fleet EVENT PLANE + run timeline + control-plane tick profiler
(ISSUE 20, tier-1 fast): the crc-framed rotated event log's round-trip /
rotation / orphan-adoption / corrupt-seam contracts (including the
``crash_in_event_rotate`` chaos verb through ``install_serve_fault``),
the Router's quarantine→requeue→recovery and swap→canary→commit/rollback
episodes landing on the plane with injectable-clock duration ground
truth, the tick profiler's phase attribution with the zero-device-
readback cast-counting proof, Heartbeat per-(replica, excursion) episode
dedup, controller/publish/stream/checkpoint mirrors, and byte-identical
timeline determinism.

Everything host-timed runs on injectable clocks; the launcher chaos e2e
(serve_gpt under DTF_FAULT_INJECT → ``python -m dtf_tpu.telemetry
timeline``) rides the slow tier.
"""

import json
import os
import subprocess
import sys

import pytest

from dtf_tpu.fault.inject import InjectedCrash, ServeFaultPlan
from dtf_tpu.serve import (Heartbeat, Request, Router, SwapConfig,
                           install_serve_fault)
from dtf_tpu.serve.health import HealthConfig
from dtf_tpu.telemetry.events import (EventLog, read_events,
                                      read_events_manifest)
from dtf_tpu.telemetry.timeline import (build_timeline, collect_entries,
                                        derive_slo_report,
                                        write_chrome_trace)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


class _FakeEngine:
    """Host-only engine (the test_serve_health idiom) with the probe
    surface probation re-admission needs."""

    n_slots = 2
    max_len = 64
    prefill_chunk = 64

    def __init__(self, clk=None):
        self.clk = clk
        self.decode_cost = 0.0
        self.probes = 0

    def prefill_chunk_into(self, slot, prompt, chunk_i, *, start=0, **kw):
        return int(prompt[0]) % 7, False

    def decode(self, **kw):
        if self.clk is not None and self.decode_cost:
            self.clk.advance(self.decode_cost)
        return [1] * self.n_slots, [False] * self.n_slots

    def probe(self):
        self.probes += 1
        if self.clk is not None:
            self.clk.advance(0.001)


class _SwapEngine(_FakeEngine):
    """Adds the hot-swap surface (the test_serve_swap idiom): tokens
    depend on the param version so a swap is visible in the stream."""

    spec_k = 0

    def __init__(self, clk=None):
        super().__init__(clk)
        self.param_version = 0
        self._params = {"w": 0}

    def set_param_version(self, v):
        self.param_version = int(v)

    def swap_params(self, params, *, draft_params=None, version=None):
        self._params = params
        self.param_version = (int(version) if version is not None
                              else self.param_version + 1)
        return self.param_version


# ---------------------------------------------------------------------------
# EventLog: round-trip, rotation, protected fields, per-writer seq
# ---------------------------------------------------------------------------

def test_emit_round_trip_caller_t_wins_and_protected_fields(tmp_path):
    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 123.5)
    # a caller-held wall stamp overrides the sink's; event/seq never do
    rec = ev.emit("ckpt_save", step=4, t=7.25, event="forged", seq=99)
    assert rec["event"] == "ckpt_save" and rec["seq"] == 0
    assert rec["t"] == 7.25 and rec["step"] == 4
    rec2 = ev.emit("train_end", step=8)
    assert rec2["t"] == 123.5 and rec2["seq"] == 1
    ev.close()
    got = read_events(d)
    assert got == [rec, rec2]
    m = read_events_manifest(d)
    assert m["records"] == 2 and len(m["shards"]) == 1
    st = ev.stats()
    assert st["events"] == 2 and st["shards_committed"] == 1
    assert st["rotations"] == 1 and st["io_errors"] == 0


def test_rotation_order_and_second_writer_never_reuses_names(tmp_path):
    d = str(tmp_path / "events")
    ev = EventLog(d, rotate_bytes=120, wall=lambda: 1.0)
    for i in range(20):
        ev.emit("tick", i=i)
    ev.close()
    m = read_events_manifest(d)
    assert len(m["shards"]) > 1 and m["records"] == 20
    assert [r["i"] for r in read_events(d)] == list(range(20))
    # seq is the writer's monotone counter — the causal tiebreak
    assert [r["seq"] for r in read_events(d)] == list(range(20))
    # a SECOND writer over the same dir: seq restarts (per-writer), but
    # shard names continue past everything on disk — order is preserved
    # by the shard sequence, never by cross-writer seq comparison
    ev2 = EventLog(d, wall=lambda: 2.0)
    assert ev2.stats()["adopted_shards"] == 0
    r = ev2.emit("resume", i=20)
    assert r["seq"] == 0
    ev2.close()
    names = [s["name"] for s in read_events_manifest(d)["shards"]]
    assert names == sorted(names) and len(set(names)) == len(names)
    assert [r["i"] for r in read_events(d)] == list(range(21))


def test_corrupt_seam_drops_deterministically(tmp_path):
    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 1.0)
    ev.arm_corrupt(2)
    for i in range(5):
        ev.emit("tick", i=i)
    ev.close()
    first = read_events(d)
    assert first == read_events(d)              # same bytes → same drops
    assert [r["i"] for r in first] == [0, 1, 3, 4]
    assert ev.stats()["injected_corrupt"] == 1


# ---------------------------------------------------------------------------
# crash_in_event_rotate: the chaos verb through install_serve_fault,
# orphan adoption on the next mount
# ---------------------------------------------------------------------------

def test_crash_in_event_rotate_verb_and_orphan_adoption(tmp_path):
    d = str(tmp_path / "events")
    clk = _Clock()
    ev = EventLog(d, rotate_bytes=1, wall=clk)   # rotate on every event
    r = Router([_FakeEngine(clk), _FakeEngine(clk)], clock=clk,
               events=ev, health=False)
    lines = []
    state = install_serve_fault(
        ServeFaultPlan.parse("crash_in_event_rotate@1"), r,
        emit=lines.append)
    ev.emit("a", i=0)                            # rotation 0 commits
    with pytest.raises(InjectedCrash):
        ev.emit("b", i=1)                        # rotation 1: shard
    assert state.fired                           # durable, commit skipped
    assert any(json.loads(ln).get("fault_inject") == "crash_in_event_rotate"
               for ln in lines)
    # the reader is NON-MUTATING but still sees the orphan...
    assert [r_["i"] for r_ in read_events(d)] == [0, 1]
    assert len(read_events_manifest(d)["shards"]) == 1
    # ...and the next mount ADOPTS it; the orphan's name is never reused
    ev2 = EventLog(d, wall=clk)
    assert ev2.stats()["adopted_shards"] == 1
    assert len(read_events_manifest(d)["shards"]) == 2
    ev2.emit("c", i=2)
    ev2.close()
    names = [s["name"] for s in read_events_manifest(d)["shards"]]
    assert len(set(names)) == 3
    assert [r_["i"] for r_ in read_events(d)] == [0, 1, 2]


# ---------------------------------------------------------------------------
# Router episodes on the plane: quarantine → requeue → recovery with
# injectable-clock duration ground truth; swap lifecycle
# ---------------------------------------------------------------------------

def _fleet(clk, ev, n=2, engine=_FakeEngine, **hc):
    cfg = dict(min_slow_s=1.0, wedge_s=5.0, quarantine_after=2,
               probation_delay_s=2.0, probation_ticks=2)
    cfg.update(hc)
    return Router([engine(clk) for _ in range(n)], clock=clk, events=ev,
                  health=HealthConfig(**cfg))


def test_quarantine_requeue_recovery_episode_durations(tmp_path):
    d = str(tmp_path / "events")
    clk = _Clock()
    ev = EventLog(d, wall=lambda: 1000.0 + clk.t)
    r = _fleet(clk, ev)
    rids = [r.submit(Request(prompt=[i + 1], max_new=6)) for i in range(6)]
    r.tick()                                     # both replicas healthy
    r.schedulers[1].engine.decode_cost = 9.0     # >= wedge_s: one strike
    r.tick()                                     # replica 1 quarantined
    t_quarantined = clk.t
    r.schedulers[1].engine.decode_cost = 0.0     # "repaired"
    while r.pending:                             # survivors finish; idle
        clk.advance(0.2)                         # clock must advance for
        r.tick()                                 # the probation delay
    for _ in range(40):
        if r.health.state(1) == "healthy":
            break
        clk.advance(0.2)
        r.tick()
    t_healthy = clk.t
    assert r.health.state(1) == "healthy"
    assert all(r.poll(rid)["status"] == "done" for rid in rids)
    ev.close()

    kinds = [e["event"] for e in read_events(d)]
    assert "health_transition" in kinds and "requeue_drain" in kinds
    # the requeue carries the pump tick; transitions carry BOTH clock
    # domains — sink wall "t" (ordering) and tracker "at" (durations)
    drain = [e for e in read_events(d) if e["event"] == "requeue_drain"][0]
    assert drain["requeued"] >= 1 and "tick" in drain
    trans = [e for e in read_events(d) if e["event"] == "health_transition"]
    assert all("at" in e and "t" in e for e in trans)

    rep = derive_slo_report(collect_entries(str(tmp_path), events_dir=d))
    assert rep["quarantine"]["episodes"] == 1
    assert rep["quarantine"]["open"] == 0
    assert rep["requeue"]["drains"] == 1
    assert rep["requeue"]["requeued"] == drain["requeued"]
    # duration ground truth, in the INJECTED clock's own domain: the
    # episode spans quarantined→healthy (probation inside), must at
    # least cover the probation delay, and is the exact "at" delta
    dur = rep["quarantine"]["duration_p50_s"]
    assert 2.0 <= dur <= clk.t
    assert t_healthy > t_quarantined
    assert dur == pytest.approx(trans[-1]["at"] - trans[0]["at"])


def test_swap_lifecycle_commit_events(tmp_path):
    d = str(tmp_path / "events")
    clk = _Clock()
    ev = EventLog(d, wall=lambda: 1000.0 + clk.t)
    r = _fleet(clk, ev, n=3, engine=_SwapEngine, probation_delay_s=1000.0)
    rids = [r.submit(Request(prompt=[i + 1], max_new=4)) for i in range(4)]
    r.start_swap({"w": 2}, config=SwapConfig(canary_ticks=2))
    r.drain()
    r.finish_swap()
    assert all(r.poll(rid)["status"] == "done" for rid in rids)
    ev.close()
    got = {e["event"]: e for e in read_events(d)}
    assert got["swap_start"]["version"] == 1
    assert got["swap_canary"]["version"] == 1
    assert got["swap_commit"]["version"] == 1
    assert got["swap_commit"]["tick"] >= got["swap_start"]["tick"]
    rep = derive_slo_report(collect_entries(str(tmp_path), events_dir=d))
    assert rep["swap"]["commits"] == 1 and rep["swap"]["rollbacks"] == 0
    assert rep["swap"]["open"] == 0 and rep["swap"]["canary_breaches"] == 0
    assert rep["swap"]["duration_p50_s"] >= 0.0


def test_swap_canary_breach_rollback_events(tmp_path):
    d = str(tmp_path / "events")
    clk = _Clock()
    ev = EventLog(d, wall=lambda: 1000.0 + clk.t)
    r = _fleet(clk, ev, n=2, engine=_SwapEngine, probation_delay_s=1000.0)
    r.start_swap({"w": 2}, config=SwapConfig(canary_ticks=4))
    r.tick()                               # canary (replica 0) swapped
    r.schedulers[0].engine.decode_cost = 9.0     # wedges on new weights
    rids = [r.submit(Request(prompt=[i + 1], max_new=4)) for i in range(4)]
    r.drain()
    r.finish_swap()
    assert all(r.poll(rid)["status"] == "done" for rid in rids)
    ev.close()
    rb = [e for e in read_events(d) if e["event"] == "swap_rollback"]
    assert len(rb) == 1 and rb[0]["cause"].startswith("canary")
    rep = derive_slo_report(collect_entries(str(tmp_path), events_dir=d))
    assert rep["swap"]["rollbacks"] == 1
    assert rep["swap"]["canary_breaches"] == 1


# ---------------------------------------------------------------------------
# Control-plane tick profiler: phase attribution, cp_profile cadence,
# the zero-device-readback cast-counting proof
# ---------------------------------------------------------------------------

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


class _CastEngine:
    """Engine whose outputs count their device casts (the
    test_serve_trace idiom) — each ``int()``/``bool()`` stands in for one
    device→host readback."""

    n_slots = 2
    max_len = 64
    prefill_chunk = 64

    def __init__(self, casts):
        self.casts = casts

    def prefill_chunk_into(self, slot, prompt, chunk_i, *, start=0, **kw):
        return int(prompt[0]) % 7, False

    def decode(self, **kw):
        return (_CountArr([1] * self.n_slots, self.casts),
                _CountArr([False] * self.n_slots, self.casts))


def _drive_cast_fleet(events):
    casts = []
    clk = _Clock()
    r = Router([_CastEngine(casts) for _ in range(2)], clock=clk,
               events=events, health=False)
    for i in range(8):
        r.submit(Request(prompt=[i + 1], max_new=5))
    while r.pending:
        r.tick()
    return casts, r


def test_cp_profiler_and_events_add_zero_device_readbacks(tmp_path):
    base_casts, _ = _drive_cast_fleet(None)
    ev = EventLog(str(tmp_path / "events"), wall=lambda: 1.0)
    on_casts, r = _drive_cast_fleet(ev)
    # the proof: the event plane + tick profiler read NO engine outputs
    # beyond what the pump already casts
    assert len(on_casts) == len(base_casts)
    st = r.stats()
    assert st["router_ticks"] > 0
    for phase in ("pick", "engine_tick", "health_sweep", "page_ops",
                  "bookkeeping"):
        assert f"cp_{phase}_total_s" in st, phase
        assert f"cp_{phase}_p99_s" in st, phase
    assert st["router_events"] == ev.stats()["events"]


def test_cp_profile_event_cadence_every_256_ticks(tmp_path):
    d = str(tmp_path / "events")
    clk = _Clock()
    ev = EventLog(d, wall=lambda: 1.0)
    r = Router([_FakeEngine(clk)], clock=clk, events=ev, health=False)
    for _ in range(257):
        r.tick()
    ev.close()
    prof = [e for e in read_events(d) if e["event"] == "cp_profile"]
    assert len(prof) == 1 and prof[0]["tick"] == 256
    assert "cp_engine_tick_total_s" in prof[0]


# ---------------------------------------------------------------------------
# Heartbeat: per-(replica, excursion) episode dedup + slo_excursion edges
# ---------------------------------------------------------------------------

class _FleetStats:
    def __init__(self):
        self.ok = 1.0
        self.r0 = 1.0

    def stats(self):
        return {"serve_completed": 1.0,
                "router_ttft_slo_ok_frac": self.ok,
                "replica0_serve_ttft_slo_ok_frac": self.r0}


def test_heartbeat_replica_episode_dedup_and_excursion_events(tmp_path,
                                                              caplog):
    import logging

    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 1.0)
    clk = _Clock()
    sched = _FleetStats()
    hb = Heartbeat(sched, every_ticks=1, slo_floor=0.9, clock=clk,
                   emit=lambda line: None, events=ev)
    with caplog.at_level(logging.WARNING, logger="dtf_tpu"):
        hb.maybe_emit()                 # clean
        sched.r0 = 0.5
        hb.maybe_emit()                 # replica0 episode enters
        hb.maybe_emit()                 # sustained — deduped, no re-WARN
        sched.r0 = 0.95
        hb.maybe_emit()                 # replica0 episode exits
        sched.ok = 0.5
        hb.maybe_emit()                 # fleet episode enters
    assert hb.replica_excursions == 1 and hb.excursions == 1
    assert hb.stats()["replica_slo_excursions"] == 1.0
    warns = [rec for rec in caplog.records
             if "replica0 TTFT SLO" in rec.getMessage()]
    assert len(warns) == 1              # ONE warn per replica episode
    ev.close()
    edges = [e for e in read_events(d) if e["event"] == "slo_excursion"]
    assert [(e["key"], e["edge"]) for e in edges] == [
        ("replica0", "enter"), ("replica0", "exit"), ("fleet", "enter")]
    ex = edges[1]
    assert ex["entered_tick"] == 2 and ex["ticks"] == ex["tick"] - 2
    rep = derive_slo_report(collect_entries(str(tmp_path), events_dir=d))
    assert rep["slo_excursions"]["episodes"] == 1
    assert rep["slo_excursions"]["open"] == 1        # the fleet episode


# ---------------------------------------------------------------------------
# Mirrors: controller run_end, publish versions, stream reweights, ckpt
# ---------------------------------------------------------------------------

def test_controller_mirror_and_run_end_no_mttr_double_count(tmp_path):
    from dtf_tpu.fault.controller import RunController

    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 1.0)
    ctrl = RunController(lambda hosts, attempt: [], 1, str(tmp_path),
                         wall=lambda: 500.0, event_log=ev)
    ctrl._emit({"state": "recovered", "mttr_s": 3.25})
    ctrl.finish({"final": "completed", "restarts": 1,
                 "causes": ["host-lost"], "mttr_s": [3.25]})
    # run_end is flushed — committed, visible without orphan recovery
    got = read_events(d, include_orphans=False)
    kinds = [e["event"] for e in got]
    assert kinds == ["controller_recovered", "run_end"]
    # the mirror carries the controller's OWN wall stamp
    assert all(e["t"] == 500.0 for e in got)
    end = got[-1]
    assert end["final"] == "completed" and end["restarts"] == 1
    # the same verdicts also live in controller.jsonl: the derived
    # report must count ONE source, or MTTR doubles
    entries = collect_entries(str(tmp_path), events_dir=d)
    assert {e["source"] for e in entries} == {"events", "controller"}
    rep = derive_slo_report(entries)
    assert rep["mttr_s"] == [3.25] and rep["mttr_mean_s"] == 3.25
    assert rep["run_final"] == "completed" and rep["restarts"] == 1
    assert rep["causes"] == ["host-lost"]


def test_publish_version_event_after_commit_only(tmp_path):
    import jax.numpy as jnp

    from dtf_tpu.publish import ParamPublisher

    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 1.0)
    pub = ParamPublisher(str(tmp_path / "pub"))
    pub.event_log = ev
    pub.publish(2, {"w": jnp.arange(4.0)})
    ev.close()
    got = [e for e in read_events(d) if e["event"] == "publish_version"]
    assert len(got) == 1
    assert got[0]["version"] == 1 and got[0]["step"] == 2
    assert got[0]["digest"]


def test_stream_reweight_and_ckpt_save_events(tmp_path):
    import numpy as np

    from dtf_tpu.checkpoint import Checkpointer
    from dtf_tpu.data.stream import MixtureStream, TokenBinSource

    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 1.0)
    rng = np.random.default_rng(0)
    for name in ("a", "b"):
        rng.integers(0, 97, 4000).astype(np.uint16).tofile(
            str(tmp_path / f"{name}.bin"))
    srcs = [TokenBinSource(str(tmp_path / f"{n}.bin"), 16, vocab_size=97,
                           seed=0, salt=i, name=n)
            for i, n in enumerate(("a", "b"))]
    stream = MixtureStream(srcs, {"a": 0.5, "b": 0.5}, 8, seed=3)
    stream.attach_event_log(ev)
    stream.reweight(4, {"a": 0.9, "b": 0.1})

    ck = Checkpointer(str(tmp_path / "ckpt"), async_save=False)
    ck.attach_event_log(ev)
    ck.save(1, {"x": np.arange(4.0)})
    ck.wait()
    ck.close()
    ev.close()
    got = {e["event"]: e for e in read_events(d)}
    rw = got["stream_reweight"]
    assert rw["at_step"] == 4 and rw["weights"]["a"] == 0.9
    assert got["ckpt_save"]["step"] == 1
    assert got["ckpt_save"]["directory"].endswith("ckpt")


# ---------------------------------------------------------------------------
# Timeline: byte-identical determinism across merged sources
# ---------------------------------------------------------------------------

def _seed_logdir(tmp_path):
    d = str(tmp_path / "events")
    ev = EventLog(d, wall=lambda: 10.0)
    ev.emit("health_transition", replica=1, state_from="healthy",
            state_to="quarantined", cause="wedged", at=5.0, t=10.5)
    ev.emit("requeue_drain", replica=1, requeued=3, shed=0, tick=7, t=10.6)
    ev.emit("health_transition", replica=1, state_from="probation",
            state_to="healthy", cause="probation passed", at=8.5, t=11.0)
    ev.emit("swap_start", version=1, canary=0, tick=9, t=11.1)
    ev.emit("swap_commit", version=1, tick=12, t=11.4)
    ev.close()
    with open(str(tmp_path / "controller.jsonl"), "w") as f:
        f.write(json.dumps({"controller": "event", "t": 9.0,
                            "state": "launch", "hosts": 2}) + "\n")
        f.write("{torn line\n")
    tel = tmp_path / "telemetry"
    tel.mkdir()
    (tel / "heartbeat.json").write_text(json.dumps(
        {"t": 12.0, "pid": 1, "step": 3, "stalled": False}))
    (tel / "postmortem.json").write_text(json.dumps(
        {"telemetry": "postmortem", "reason": "wedge", "t": 10.8,
         "pid": 1, "records": [1, 2, 3]}) + "\n")
    return str(tmp_path), d


def test_timeline_merges_all_sources_byte_identically(tmp_path):
    logdir, d = _seed_logdir(tmp_path)
    r1 = build_timeline(logdir, events_dir=d)
    r2 = build_timeline(logdir, events_dir=d)
    assert json.dumps(r1, sort_keys=True) == json.dumps(r2, sort_keys=True)
    assert r1["sources"] == {"controller": 1, "events": 5,
                             "heartbeat": 1, "postmortem": 1}
    entries = collect_entries(logdir, events_dir=d)
    assert [e["t"] for e in entries] == sorted(e["t"] for e in entries)
    # the postmortem's bulk ring is dropped from the spine
    pm = [e for e in entries if e["source"] == "postmortem"][0]
    assert pm["kind"] == "postmortem_wedge" and "records" not in pm
    slo = r1["slo"]
    assert slo["quarantine"]["episodes"] == 1
    assert slo["quarantine"]["duration_p50_s"] == 3.5   # at deltas
    assert slo["swap"]["commits"] == 1
    assert slo["requeue"]["requeued"] == 3
    # the chrome trace is byte-identical too (no wall stamps of its own)
    p1, p2 = str(tmp_path / "t1.json"), str(tmp_path / "t2.json")
    n1 = write_chrome_trace(p1, entries)
    n2 = write_chrome_trace(p2, entries)
    assert n1 == n2
    assert open(p1, "rb").read() == open(p2, "rb").read()
    tr = json.load(open(p1))["traceEvents"]
    assert any(e.get("ph") == "X" and e["name"] == "quarantine"
               for e in tr)


def test_timeline_empty_logdir_degrades_with_note(tmp_path):
    rep = build_timeline(str(tmp_path / "nothing"))
    assert rep["entries"] == 0 and "note" in rep and rep["slo"] == {}


# ---------------------------------------------------------------------------
# jax-freeness: the plane + timeline run on chipless machines
# ---------------------------------------------------------------------------

def test_event_plane_imports_without_backend(tmp_path,
                                             cpu_sim_subprocess_env):
    poison = tmp_path / "poison"
    for mod in ("jax", "tensorflow", "jaxlib"):
        p = poison / mod
        p.mkdir(parents=True)
        (p / "__init__.py").write_text(
            "raise ImportError('no backend on this machine')\n")
    env = dict(cpu_sim_subprocess_env)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{ROOT}"
    code = (
        "from dtf_tpu.telemetry.events import EventLog, read_events\n"
        "from dtf_tpu.telemetry.timeline import build_timeline\n"
        "ev = EventLog('events', wall=lambda: 1.0)\n"
        "ev.emit('train_end', step=2)\n"
        "ev.close()\n"
        "assert [e['event'] for e in read_events('events')] "
        "== ['train_end']\n"
        "rep = build_timeline('.', events_dir='events')\n"
        "assert rep['entries'] == 1, rep\n"
        "from dtf_tpu.fault.inject import ServeFaultPlan\n"
        "assert ServeFaultPlan.parse('crash_in_event_rotate@1').kind "
        "== 'crash_in_event_rotate'\n"
        "print('NO_BACKEND_OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert "NO_BACKEND_OK" in proc.stdout, (proc.stdout, proc.stderr)


# ---------------------------------------------------------------------------
# slow: the whole story through the real launchers + the timeline CLI,
# and the tiny control-plane bench pin
# ---------------------------------------------------------------------------

def _env(**extra):
    env = dict(os.environ)
    env.pop("DTF_FAULT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    env.update(extra)
    return env


@pytest.mark.slow
def test_chaos_launcher_event_plane_and_timeline_cli_e2e(tmp_path):
    """train → serve under a wedge verb, ONE event plane for both, then
    the timeline CLI derives the quarantine/requeue story from disk."""
    ev_dir = str(tmp_path / "events")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "train_gpt.py"),
         "--backend=cpu",
         "--size=tiny", "--train_steps=2", "--batch_size=16",
         "--seq_len=32", "--checkpoint_every=2", f"--logdir={tmp_path}",
         f"--event_log_dir={ev_dir}"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-1500:]

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={tmp_path}", "--replicas=2", "--n_slots=2",
         "--max_len=48", "--prefill_chunk=4",
         "--requests=5,9,2;5,9,2,7,1,3;1,2,3,4,5;8,8;2,4,6,8",
         "--n_new=6", f"--event_log_dir={ev_dir}",
         "--health_slow_s=0.15", "--health_wedge_s=0.4"],
        env=_env(DTF_FAULT_INJECT="wedge_replica@1:replica=1",
                 DTF_FAULT_WEDGE_S="0.6"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    stats = json.loads([ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1])
    assert stats["event_log"]["events"] > 0
    assert stats["event_log_dir"] == ev_dir

    chrome = str(tmp_path / "trace.json")
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.telemetry", "timeline",
         f"--logdir={tmp_path}", f"--chrome={chrome}"],
        env=_env(), capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, (proc.stdout[-1500:], proc.stderr[-1500:])
    rep = json.loads([ln for ln in proc.stdout.splitlines()
                      if ln.startswith("{")][-1])
    kinds = rep["kinds"]
    # the one plane carries train AND serve: ckpt saves, the run end,
    # the serve fleet start/summary, and the wedge's episode
    for k in ("ckpt_save", "train_end", "serve_start", "serve_summary",
              "health_transition"):
        assert k in kinds, (k, kinds)
    assert rep["slo"]["quarantine"]["episodes"] \
        + rep["slo"]["quarantine"]["open"] >= 1
    assert rep["slo"]["requeue"]["requeued"] >= 1
    assert os.path.exists(chrome)
    assert rep["chrome_trace_events"] >= rep["entries"]
