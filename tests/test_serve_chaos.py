"""Serve-tier chaos matrix (ISSUE 12, slow tier): real engines, real wall
clocks, real sleeps — the failure classes the fast suite drives with
injectable clocks, exercised the way production would hit them. Each
scenario ends in a VERIFIED drain (token identity / terminal statuses) or
a loud failure naming the phase:

- ``wedge``    — one replica wedges mid-stream; the watchdog quarantines
                 it off measured tick wall time, survivors replay its
                 in-flight requests bitwise.
- ``overload`` — a request burst against a bounded queue sheds with
                 explicit terminal statuses while admitted work completes
                 and matches offline decode.
- ``poison``   — a poisoned request isolates to itself on a live fleet.
- ``deadline`` — slow_decode pushes tight TTFT deadlines into timeouts;
                 the drain still completes.
- ``launcher`` — the whole story through scripts/serve_gpt.py with
                 ``DTF_FAULT_INJECT`` riding the env (PR 11's verb
                 pattern): wedged-run token rows == clean-run token rows.
"""

import dataclasses
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from dtf_tpu.fault.inject import ServeFaultPlan
from dtf_tpu.serve import (Request, Router, Scheduler, install_serve_fault)
from dtf_tpu.serve.health import HealthConfig

pytestmark = pytest.mark.slow  # real sleeps + subprocesses

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MAX_LEN = 48


@pytest.fixture(scope="module")
def gpt_setup():
    import jax
    import jax.numpy as jnp

    from dtf_tpu.models import gpt

    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)
    model = gpt.GPT(dataclasses.replace(cfg, decode_len=MAX_LEN))
    params = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, 1), jnp.int32))["params"]
    return cfg, model, params


def _offline(model, params, req):
    import jax
    import jax.numpy as jnp

    from dtf_tpu.models import gpt

    out = gpt.generate(
        model, params, jnp.asarray([req["prompt"]], jnp.int32),
        req["max_new"], rng=jax.random.PRNGKey(req.get("seed", 0)),
        temperature=req.get("temperature", 0.0))
    return np.asarray(out)[0, len(req["prompt"]):].tolist()


def _requests(n, seed=1):
    rng = np.random.default_rng(seed)
    return [dict(prompt=rng.integers(0, 128,
                                     int(rng.integers(2, 14))).tolist(),
                 max_new=int(rng.integers(3, 9)),
                 temperature=0.0 if i % 2 else 0.8, seed=60 + i)
            for i in range(n)]


#: tight real-clock health thresholds: CPU-sim tiny-GPT ticks are ms-scale,
#: injected wedge sleeps are 0.5s — margin both ways, quarantine_after=3
#: so an isolated cold-dispatch strike can only degrade, and probation far
#: beyond the test horizon.
_CHAOS_HEALTH = dict(slow_factor=8.0, min_slow_s=0.15, wedge_s=0.35,
                     quarantine_after=3, probation_delay_s=3600.0)


def test_chaos_wedge_replica_mid_stream(gpt_setup):
    """wedge: a replica that stops answering mid-generation is quarantined
    off measured wall time and every request still completes bitwise."""
    cfg, model, params = gpt_setup
    reqs = _requests(6)
    router = Router.build(cfg, params, n_replicas=2, n_slots=2,
                          max_len=MAX_LEN, prefill_chunk=5,
                          health=HealthConfig(**_CHAOS_HEALTH))
    plan = ServeFaultPlan.parse("wedge_replica@3:replica=1")
    state = install_serve_fault(plan, router, wedge_s=0.5,
                                emit=lambda line: None)
    rids = [router.submit(Request(**r)) for r in reqs]
    router.drain()
    assert state.fired, "wedge: injection never armed — plan tick unmet"
    st = router.stats()
    assert st["router_quarantines"] >= 1.0, \
        f"wedge: no quarantine verdict ({st})"
    assert st["router_requeued"] >= 1.0, \
        f"wedge: quarantine drained nothing ({st})"
    assert st["replica1_health"] == "quarantined", \
        f"wedge: wrong replica state ({st})"
    for r, rid in zip(reqs, rids):
        assert router.result(rid) == _offline(model, params, r), \
            f"wedge: survivor tokens diverged for {r}"
    assert router.trace_counts() == [{"prefill": 1, "decode": 1}] * 2, \
        "wedge: requeue retraced a program"


def test_chaos_overload_burst_sheds_and_drains(gpt_setup):
    """overload: a burst against a bounded queue sheds the excess with
    explicit terminal statuses; everything admitted completes and matches
    offline decode."""
    from dtf_tpu.serve import DecodeEngine

    cfg, model, params = gpt_setup
    reqs = _requests(12, seed=5)
    engine = DecodeEngine(cfg, params, n_slots=2, max_len=MAX_LEN,
                          prefill_chunk=5)
    sched = Scheduler(engine, max_queue=2, prefill_chunks_per_tick=2)
    rids = [sched.submit(Request(**r)) for r in reqs]   # one burst
    sched.run_until_idle()
    polls = [sched.poll(r) for r in rids]
    statuses = {p["status"] for p in polls}
    assert statuses == {"done", "shed"}, \
        f"overload: unexpected terminal statuses {statuses}"
    sheds = [p for p in polls if p["status"] == "shed"]
    assert sheds and all(p["retry_after_s"] > 0 for p in sheds), \
        "overload: shed without a retry hint"
    st = sched.stats()
    assert st["serve_shed"] == float(len(sheds))
    assert st["serve_queue_peak"] <= 2.0, \
        f"overload: queue grew past the bound ({st})"
    for r, rid, p in zip(reqs, rids, polls):
        if p["status"] == "done":
            assert p["tokens"] == _offline(model, params, r), \
                f"overload: admitted tokens diverged for {r}"


def test_chaos_poison_request_isolation_on_fleet(gpt_setup):
    """poison: one poisoned request fails terminally; the fleet keeps
    serving and every other request is bitwise clean."""
    cfg, model, params = gpt_setup
    reqs = _requests(5, seed=9)
    router = Router.build(cfg, params, n_replicas=2, n_slots=2,
                          max_len=MAX_LEN, prefill_chunk=5,
                          health=HealthConfig(**_CHAOS_HEALTH))
    plan = ServeFaultPlan.parse("poison_request@2")
    state = install_serve_fault(plan, router, emit=lambda line: None)
    rids = [router.submit(Request(**r)) for r in reqs]
    router.drain()
    assert state.fired, "poison: injection never fired"
    p = router.poll(rids[2])
    assert p["status"] == "error" and "InjectedPoison" in p["error"], \
        f"poison: poisoned request not isolated ({p})"
    for i, (r, rid) in enumerate(zip(reqs, rids)):
        if i == 2:
            continue
        assert router.result(rid) == _offline(model, params, r), \
            f"poison: clean request {i} diverged"
    st = router.stats()
    assert st["router_request_errors"] == 1.0
    assert st["router_quarantines"] == 0.0, \
        f"poison: replica wrongly quarantined ({st})"
    assert router.trace_counts() == [{"prefill": 1, "decode": 1}] * 2


def test_chaos_slow_decode_deadline_misses(gpt_setup):
    """deadline: slow_decode drags every tick; requests carrying a tight
    TTFT deadline time out terminally, the drain still completes, and
    late polls answer instantly instead of spinning."""
    cfg, model, params = gpt_setup
    router = Router.build(cfg, params, n_replicas=1, n_slots=2,
                          max_len=MAX_LEN, prefill_chunk=5,
                          max_queue=0)
    plan = ServeFaultPlan.parse("slow_decode@0")
    install_serve_fault(plan, router, slow_s=0.15, emit=lambda line: None)
    reqs = [dict(prompt=[3 + i, 5], max_new=6, seed=i,
                 ttft_deadline_s=0.25) for i in range(6)]
    rids = [router.submit(Request(**r)) for r in reqs]
    t0 = time.perf_counter()
    router.drain()
    drain_s = time.perf_counter() - t0
    polls = [router.poll(r) for r in rids]
    timeouts = [p for p in polls if p["status"] == "timeout"]
    assert timeouts, f"deadline: no deadline ever missed ({polls})"
    assert all(p["timeout_kind"] == "ttft" for p in timeouts)
    assert all(p["status"] in ("done", "timeout") for p in polls), \
        f"deadline: non-terminal request after drain ({polls})"
    st = router.stats()
    assert st["router_timeouts"] == float(len(timeouts))
    assert drain_s < 60.0, f"deadline: drain dragged {drain_s:.1f}s"


# ---------------------------------------------------------------------------
# launcher chaos: the whole story through scripts/serve_gpt.py
# ---------------------------------------------------------------------------

def _env(**extra):
    env = dict(os.environ)
    env.pop("DTF_FAULT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    env.update(extra)
    return env


def _serve(logdir, *args, env=None, timeout=420):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={logdir}", "--replicas=2", "--n_slots=2",
         "--max_len=48", "--prefill_chunk=4",
         "--requests=5,9,2;5,9,2,7,1,3;1,2,3,4,5;8,8;2,4,6,8",
         "--n_new=6", "--emit_tokens", "--stats_every=2", *args],
        env=env or _env(), capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"launcher: serve_gpt rc={proc.returncode}\n"
        f"{proc.stdout[-1500:]}\n{proc.stderr[-1500:]}")
    rows = {ln.split(":", 1)[0]: ln.split(":", 1)[1]
            for ln in proc.stdout.splitlines()
            if ln and ln[0].isdigit() and ":" in ln}
    stats = json.loads([ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1])
    return rows, stats, proc.stderr


def test_chaos_launcher_wedge_replica_rides_env(tmp_path):
    """launcher: DTF_FAULT_INJECT=wedge_replica rides serve_gpt exactly
    like PR 11's verbs ride the trainers — the wedged run quarantines,
    requeues, reports every request terminal, and emits token rows
    BITWISE identical to the clean run's."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "train_gpt.py"),
         "--backend=cpu",
         "--size=tiny", "--train_steps=2", "--batch_size=16",
         "--seq_len=32", "--checkpoint_every=2", f"--logdir={tmp_path}"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-1500:]

    clean_rows, clean_stats, _ = _serve(tmp_path)
    assert clean_stats["router_quarantines"] == 0.0
    assert clean_stats["request_statuses"] == {"done": 5}

    wedged_rows, wedged_stats, stderr = _serve(
        tmp_path, "--health_slow_s=0.15", "--health_wedge_s=0.4",
        env=_env(DTF_FAULT_INJECT="wedge_replica@1:replica=1",
                 DTF_FAULT_WEDGE_S="0.6"))
    assert wedged_stats["fault_inject"] == "wedge_replica@1:replica=1"
    assert wedged_stats["router_quarantines"] >= 1.0, \
        f"launcher: no quarantine ({wedged_stats})"
    assert wedged_stats["router_requeued"] >= 1.0
    assert wedged_stats["replica1_health"] == "quarantined"
    assert wedged_stats["request_statuses"] == {"done": 5}, \
        f"launcher: non-terminal requests ({wedged_stats})"
    # the acceptance-criterion property, through the real launcher:
    # survivors' completed tokens bitwise == the fault-free run's
    assert wedged_rows == clean_rows, \
        f"launcher: tokens diverged\nclean={clean_rows}\nwedged={wedged_rows}"
    # heartbeats kept flowing through the fault (stderr JSON lines)
    assert any(ln.startswith('{"serve_heartbeat"')
               for ln in stderr.splitlines()), \
        "launcher: no heartbeat survived the wedge"


# ---------------------------------------------------------------------------
# hot-swap chaos (ISSUE 14): the acceptance fleet + corrupt publish +
# the publish-serving launcher
# ---------------------------------------------------------------------------

def test_chaos_rolling_swap_spec_and_shared_pages_bitwise(gpt_setup):
    """The ISSUE 14 acceptance fleet: >= 2 replicas with SPECULATION and
    SHARED prefix pages (disaggregation) on, rolled to new weights
    mid-traffic — zero requests end shed/timeout/error, the swapped
    fleet's tokens are bitwise identical to a fresh fleet restored from
    the same version, no page ever crosses versions (pinned stays 0),
    and every per-replica program stays trace-pinned."""
    import jax
    import jax.numpy as jnp

    from dtf_tpu.serve import SwapConfig

    cfg, model, params = gpt_setup
    params2 = gpt_model_init(cfg, seed=1)

    def fleet(p):
        r = Router.build(cfg, p, n_replicas=3, n_slots=2,
                         max_len=MAX_LEN, prefill_chunk=4,
                         kv_page_size=4, prefix_pages=12,
                         prefill_replicas=1,
                         draft_cfg=cfg, draft_params=p, spec_k=2,
                         health=HealthConfig(**_CHAOS_HEALTH))
        return r

    router = fleet(params)
    # stem-shared traffic (page-aligned) + unique tails: pages AND spec
    # both carry real work across the swap
    stem = list(range(1, 9))
    rng = np.random.default_rng(3)
    reqs = [dict(prompt=stem + rng.integers(0, 128, 4).tolist(),
                 max_new=int(rng.integers(3, 7)),
                 temperature=0.0 if i % 2 else 0.8, seed=40 + i)
            for i in range(8)]
    rids = []
    for i, r in enumerate(reqs[:5]):
        rids.append(router.submit(Request(**r)))
        router.tick()
    router.start_swap(params2, version=1,
                      config=SwapConfig(canary_ticks=2))
    for r in reqs[5:]:
        rids.append(router.submit(Request(**r)))
        router.tick()
    router.drain()
    router.finish_swap()
    st = router.stats()
    assert st["router_swaps"] == 1.0 and st["router_swap_rollbacks"] == 0.0
    assert all(st[f"replica{i}_version"] == 1.0 for i in range(3)), st
    polls = [router.poll(rid) for rid in rids]
    assert all(p["status"] == "done" for p in polls), \
        f"swap: non-done terminal statuses {[p['status'] for p in polls]}"
    # every record stamped; streams bitwise per the STAMPED version
    params_of = {0: params, 1: params2}
    for r, p in zip(reqs, polls):
        assert p["version"] in (0, 1)
        assert p["tokens"] == _offline(model, params_of[p["version"]], r), \
            f"swap: tokens diverged for {r} at version {p['version']}"
    for s in router.schedulers:
        stats = s.engine.prefix_stats()
        assert stats.get("pinned", 0) == 0, f"swap: leaked pins {stats}"
    want = {"prefill": 1, "decode": 1}
    for i, tc in enumerate(router.trace_counts()):
        base = {k: v for k, v in tc.items() if not k.startswith("page_")}
        if i == 0:                       # prefill replica: no draft
            assert base == want, tc
        else:
            assert base == {**want, "draft_prefill": 1, "draft": 1}, tc

    # the bitwise fresh-fleet cross-check at the TARGET version
    fresh = fleet(params2)
    fresh.stamp_version(1)
    for r, p in zip(reqs, polls):
        if p["version"] != 1:
            continue
        frid = fresh.submit(Request(**r))
        fresh.drain()
        assert fresh.result(frid) == p["tokens"], \
            f"swap: swapped fleet != restored fleet for {r}"


def gpt_model_init(cfg, seed):
    import dataclasses as _dc

    import jax
    import jax.numpy as jnp

    from dtf_tpu.models import gpt

    model = gpt.GPT(_dc.replace(cfg, decode_len=MAX_LEN))
    return model.init(jax.random.PRNGKey(seed),
                      jnp.zeros((1, 1), jnp.int32))["params"]


def test_chaos_corrupt_publish_fleet_keeps_serving(gpt_setup, tmp_path):
    """corrupt_publish: the watcher's digest check skips a damaged
    publish with a WARN — the live fleet keeps serving its version and
    a later clean republish rolls normally."""
    from dtf_tpu.publish import ParamPublisher, PublishWatcher
    from dtf_tpu.serve import SwapConfig

    cfg, model, params = gpt_setup
    pub = ParamPublisher(str(tmp_path))
    v1 = pub.publish(10, params)
    router = Router.build(cfg, params, n_replicas=2, n_slots=2,
                          max_len=MAX_LEN, prefill_chunk=5,
                          health=HealthConfig(**_CHAOS_HEALTH))
    router.stamp_version(v1)
    watcher = PublishWatcher(str(tmp_path), applied_version=v1)
    plan = ServeFaultPlan.parse("corrupt_publish@0")
    state = install_serve_fault(plan, router, watcher=watcher,
                                emit=lambda line: None)
    pub.publish(20, gpt_model_init(cfg, seed=2))     # v2 — to be damaged
    assert router.maybe_swap_published(watcher) is None
    assert state.fired, "corrupt_publish never fired"
    assert watcher.skipped == {2}
    # the fleet NEVER left v1 and still serves bitwise
    reqs = _requests(4, seed=11)
    rids = [router.submit(Request(**r)) for r in reqs]
    router.drain()
    for r, rid in zip(reqs, rids):
        p = router.poll(rid)
        assert p["version"] == v1
        assert p["tokens"] == _offline(model, params, r)
    assert router.stats()["router_version"] == float(v1)
    # a clean republish (a NEWER version) rolls normally
    params3 = gpt_model_init(cfg, seed=3)
    v3 = pub.publish(30, params3)
    assert router.maybe_swap_published(
        watcher, config=SwapConfig(canary_ticks=1)) == v3
    router.finish_swap()
    assert router.stats()["router_version"] == float(v3)
    rid = router.submit(Request(**reqs[0]))
    router.drain()
    assert router.poll(rid)["tokens"] == _offline(model, params3, reqs[0])


def test_chaos_launcher_publish_serving_and_guarded_fallback(tmp_path):
    """launcher: train_gpt --publish_dir emits versions; serve_gpt
    --publish_dir reports the version ACTUALLY served — the newest on a
    clean dir, the older one (guarded walk, WARN) when the newest is
    corrupt, and an EXPLICITLY requested corrupt version fails loudly
    instead of falling back (the restore(step=) contract)."""
    pub_dir = str(tmp_path / "publish")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "train_gpt.py"),
         "--backend=cpu",
         "--size=tiny", "--train_steps=4", "--batch_size=16",
         "--seq_len=32", "--checkpoint_every=2", f"--logdir={tmp_path}",
         f"--publish_dir={pub_dir}", "--publish_every=2"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-1500:]
    from dtf_tpu.publish import read_manifest

    m = read_manifest(pub_dir)
    assert m is not None and m["version"] == 2, m

    _, stats, _ = _serve(tmp_path, f"--publish_dir={pub_dir}")
    assert stats["served_version"] == 2 and stats["final_version"] == 2
    assert stats["request_statuses"] == {"done": 5}
    assert all(stats[f"replica{i}_version"] == 2.0 for i in range(2))

    # live mid-run roll: start on v1 EXPLICITLY, poll the publish dir
    # every 2 ticks — the fleet rolls to v2 while serving, zero failures
    _, stats, _ = _serve(tmp_path, f"--publish_dir={pub_dir}",
                         "--publish_version=1", "--swap_poll_ticks=2",
                         "--canary_ticks=2")
    assert stats["served_version"] == 1 and stats["final_version"] == 2, \
        f"launcher: rolling swap never converged ({stats})"
    assert stats["router_swaps"] == 1.0
    assert stats["request_statuses"] == {"done": 5}

    # crash_in_publish rides train_gpt: the trainer DIES mid-publish and
    # the manifest (and therefore serving) still names version 2
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "train_gpt.py"),
         "--backend=cpu",
         "--size=tiny", "--train_steps=6", "--batch_size=16",
         "--seq_len=32", "--checkpoint_every=2", f"--logdir={tmp_path}",
         f"--publish_dir={pub_dir}", "--publish_every=2"],
        env=_env(DTF_FAULT_INJECT="crash_in_publish@6"),
        capture_output=True, text=True, timeout=420)
    assert proc.returncode != 0, "launcher: crash_in_publish never fired"
    assert "crash_in_publish" in proc.stdout, proc.stdout[-800:]
    assert read_manifest(pub_dir)["version"] == 2, \
        "launcher: a crashed publish moved the manifest"

    from dtf_tpu.fault.inject import corrupt_publish_version

    corrupt_publish_version(pub_dir, 2)
    _, stats, stderr = _serve(tmp_path, f"--publish_dir={pub_dir}")
    assert stats["served_version"] == 1, \
        f"launcher: corrupt newest not walked past ({stats})"
    assert stats["request_statuses"] == {"done": 5}

    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={tmp_path}", f"--publish_dir={pub_dir}",
         "--publish_version=2", "--replicas=2", "--n_slots=2",
         "--max_len=48", "--requests=5,9,2", "--n_new=4"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert proc.returncode != 0, \
        "launcher: explicit corrupt version served instead of failing"
    assert "digest" in (proc.stderr + proc.stdout), proc.stderr[-800:]
