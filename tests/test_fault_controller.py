"""The fault matrix, against REAL processes (ISSUE 11, slow tier).

Each scenario drives the real CLI entrypoint (``scripts/distributed.py``)
under injected faults (``DTF_FAULT_INJECT``) and asserts the contract from
docs/RESILIENCE.md: every failure ends in either a VERIFIED resume or a
loud failure whose output names the failing phase — no silent hangs. The
tier-1 fast halves (harness parity, bitwise shrink-resume, the controller
state machine) live in tests/test_elastic.py; what this tier adds is the
OS truth: SIGKILL really kills, a wedged process really ignores SIGTERM,
heartbeats really go stale, and the controller supervises it all from a
separate jax-free process context.

The workers run the fake-hosts harness (cpu multi-worker collapse —
the jaxlib blocker), so controller scenarios need no cross-process
collectives: that transport is chip-gated in test_multiprocess.py.
"""

import json
import os
import subprocess
import sys

import pytest

from dtf_tpu.fault import (ControllerConfig, RunController,
                           corrupt_latest_checkpoint)

pytestmark = pytest.mark.slow  # subprocess-heavy tier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "scripts", "distributed.py")


def _env(extra=None):
    env = dict(os.environ)
    env.pop("DTF_FAULT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    if extra:
        env.update(extra)
    return env


def _worker_cmd(logdir, *, steps, hosts=1, host=0, dph=0, ckpt_every=3,
                telemetry=True):
    cmd = [sys.executable, SCRIPT, "--backend=cpu", f"--logdir={logdir}",
           f"--train_steps={steps}", "--batch_size=32",
           f"--checkpoint_every={ckpt_every}", "--log_every=50"]
    if hosts > 1:
        worker_hosts = ",".join(f"h{i}" for i in range(hosts))
        cmd += [f"--worker_hosts={worker_hosts}", f"--task_index={host}"]
    if dph:
        cmd += [f"--devices_per_host={dph}"]
    if telemetry:
        cmd += ["--telemetry", "--telemetry_min_stall_s=2"]
    return cmd


def _ckpt_steps(logdir):
    d = os.path.join(logdir, "ckpt")
    if not os.path.isdir(d):
        return []
    return sorted(int(s) for s in os.listdir(d) if s.isdigit())


class _Launcher:
    """Controller launch callback: Popen per host, stdout to per-attempt
    log files, fault env on attempt 0 only (a relaunch must not re-trip
    the same seeded fault at the resumed step)."""

    def __init__(self, logdir, *, steps, dph, fault=None, ckpt_every=3):
        self.logdir = logdir
        self.steps = steps
        self.dph = dph
        self.fault = fault
        self.ckpt_every = ckpt_every
        self.launches = []

    def log(self, attempt, host):
        return os.path.join(self.logdir, f"attempt{attempt}_h{host}.log")

    def __call__(self, n_hosts, attempt):
        self.launches.append(n_hosts)
        extra = ({"DTF_FAULT_INJECT": self.fault}
                 if (self.fault and attempt == 0) else None)
        procs = []
        for host in range(n_hosts):
            out = open(self.log(attempt, host), "w")
            procs.append(subprocess.Popen(
                _worker_cmd(self.logdir, steps=self.steps, hosts=n_hosts,
                            host=host, dph=self.dph,
                            ckpt_every=self.ckpt_every),
                env=_env(extra), stdout=out, stderr=subprocess.STDOUT))
        return procs


_CFG = ControllerConfig(max_restarts=2, backoff_base_s=0.2,
                        backoff_max_s=2.0, wedge_timeout_s=45.0,
                        startup_timeout_s=240.0, grace_s=45.0, poll_s=0.3)


def test_host_kill_relaunches_smaller_and_resumes(tmp_path):
    """Host-lost, end to end: SIGKILL host 1 of a fake-2-host dp4 run at
    a seeded step; the controller tells host-lost from wedged (host 0 is
    alive and heartbeating), stops the survivor (its SIGTERM chain saves),
    relaunches ONE host on the dp2 survivor mesh, and the relaunch
    RESUMES from a checkpoint instead of starting over."""
    logdir = str(tmp_path / "run")
    launcher = _Launcher(logdir, steps=60, dph=2,
                         fault="kill@6:host=1")
    ctl = RunController(launcher, 2, logdir, _CFG,
                        valid_hosts=lambda n: n in (1, 2),
                        emit=lambda line: None)
    summary = ctl.run()

    assert summary["final"] == "done", ctl.events
    assert summary["causes"] == ["host_lost"]
    assert summary["restarts"] == 1
    assert launcher.launches == [2, 1]          # relaunched SMALLER
    lost = next(e for e in ctl.events if e.get("state") == "host_lost")
    assert lost["dead_hosts"] == [1]
    # the injected kill really fired in host 1's process
    h1 = open(launcher.log(0, 1)).read()
    assert '"fault_inject": "firing"' in h1 and '"kind": "kill"' in h1
    # the relaunch resumed from a durable checkpoint and finished
    relaunch = open(launcher.log(1, 0)).read()
    assert "resumed from checkpoint at step" in relaunch, relaunch[-2000:]
    assert "done: step=60" in relaunch, relaunch[-2000:]
    assert _ckpt_steps(logdir), "no checkpoint survived the kill"
    # MTTR/restart stamping (satellite): fields land in the artifact
    art = str(tmp_path / "TELEMETRY.json")
    ctl.finish(summary, art)
    row = json.load(open(art))["runs"][-1]
    assert row["telemetry"] == "controller" and row["restarts"] == 1


def test_wedge_detected_dumped_and_relaunched_same_size(tmp_path):
    """Run-wedged, end to end: the worker stops completing steps at a
    seeded step but stays ALIVE (and ignores SIGTERM, as a wedged loop
    does). Its own stall watchdog flags the heartbeat; the controller
    must conclude wedged (NOT host-lost), kill after the grace window,
    and relaunch at the SAME size; the relaunch resumes and finishes."""
    logdir = str(tmp_path / "run")
    launcher = _Launcher(logdir, steps=12, dph=0, fault="wedge@5")
    cfg = ControllerConfig(max_restarts=2, backoff_base_s=0.2,
                           wedge_timeout_s=45.0, startup_timeout_s=240.0,
                           grace_s=4.0, poll_s=0.3)
    ctl = RunController(launcher, 1, logdir, cfg, emit=lambda line: None)
    summary = ctl.run()

    assert summary["final"] == "done", ctl.events
    assert summary["causes"] == ["wedged"]
    assert launcher.launches == [1, 1]          # SAME size
    wedge = next(e for e in ctl.events if e.get("state") == "wedged")
    assert "stall" in wedge["reason"] or "stale" in wedge["reason"]
    # the wedged process ignored SIGTERM → the controller had to SIGKILL
    assert any(e.get("state") == "killed" for e in ctl.events)
    # the host's own stall postmortem hit disk before the kill
    post = os.path.join(logdir, "telemetry", "postmortem.json")
    reasons = [json.loads(line)["reason"]
               for line in open(post).read().splitlines()]
    assert "stall" in reasons, reasons
    relaunch = open(launcher.log(1, 0)).read()
    assert "resumed from checkpoint at step 3" in relaunch, \
        relaunch[-2000:]
    assert "done: step=12" in relaunch, relaunch[-2000:]


def test_sigterm_mid_checkpoint_preempts_cleanly_and_resumes(tmp_path):
    """Graceful preemption with the SIGTERM landing INSIDE
    Checkpointer.save: the chain must still run in order (flight dump →
    durable checkpoint → controller marker), the worker exits 0 at the
    seeded step, and a clean relaunch resumes from exactly that step."""
    logdir = str(tmp_path / "run")
    p = subprocess.Popen(
        _worker_cmd(logdir, steps=100_000, ckpt_every=4),
        env=_env({"DTF_FAULT_INJECT": "sigterm_in_save@4"}),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=300)
    assert p.returncode == 0, out[-2000:]
    assert '"fault_inject": "sigterm_in_save"' in out
    assert "done: step=4" in out, out[-2000:]
    assert _ckpt_steps(logdir) == [4]
    # chain artifacts: the postmortem dumped, the marker written LAST
    post = os.path.join(logdir, "telemetry", "postmortem.json")
    reasons = [json.loads(line)["reason"]
               for line in open(post).read().splitlines()]
    assert "sigterm" in reasons, reasons
    marker = json.load(open(os.path.join(logdir, "telemetry",
                                         "preempt.json")))
    assert marker["step"] == 4

    p2 = subprocess.Popen(_worker_cmd(logdir, steps=8),
                          env=_env(), stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    out2, _ = p2.communicate(timeout=300)
    assert p2.returncode == 0, out2[-2000:]
    assert "resumed from checkpoint at step 4" in out2, out2[-2000:]
    assert "done: step=8" in out2, out2[-2000:]


def test_corrupt_newest_checkpoint_falls_back_then_fails_loudly(tmp_path):
    """Checkpoint damage, both halves of the contract: (a) a corrupt
    NEWEST step falls back to the prior step with a WARN and the relaunch
    completes; (b) when EVERY step is corrupt, the relaunch fails loudly
    naming the restore phase — never a silent hang, never training
    silently from scratch."""
    logdir = str(tmp_path / "run")
    p = subprocess.Popen(_worker_cmd(logdir, steps=6, telemetry=False),
                         env=_env(), stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=300)
    assert p.returncode == 0, out[-2000:]
    steps = _ckpt_steps(logdir)
    assert steps and steps[-1] == 6, steps

    ckpt_dir = os.path.join(logdir, "ckpt")
    info = corrupt_latest_checkpoint(ckpt_dir)
    assert info["step"] == 6 and info["files"]

    p2 = subprocess.Popen(_worker_cmd(logdir, steps=10, telemetry=False),
                          env=_env(), stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    out2, _ = p2.communicate(timeout=300)
    assert p2.returncode == 0, out2[-2000:]
    assert "unreadable" in out2, out2[-2000:]           # the WARN
    assert "resumed from checkpoint at step 3" in out2, out2[-2000:]
    assert "done: step=10" in out2, out2[-2000:]

    # (b) now corrupt EVERY remaining step → loud failure, named phase
    for s in _ckpt_steps(logdir):
        for root, _, files in os.walk(os.path.join(ckpt_dir, str(s))):
            for f in files:
                path = os.path.join(root, f)
                size = os.path.getsize(path)
                if size:
                    with open(path, "r+b") as fh:
                        fh.truncate(size // 2)
    p3 = subprocess.Popen(_worker_cmd(logdir, steps=12, telemetry=False),
                          env=_env(), stdout=subprocess.PIPE,
                          stderr=subprocess.STDOUT, text=True)
    out3, _ = p3.communicate(timeout=300)
    assert p3.returncode != 0, out3[-2000:]
    assert "every checkpoint step" in out3 and "unreadable" in out3, \
        out3[-2000:]


def test_controller_cli_survives_a_kill(tmp_path):
    """`python -m dtf_tpu.fault` — the packaged controller entrypoint:
    same kill scenario via the command template; summary is the last
    stdout line (the bench.py contract), exit 0 on done."""
    logdir = str(tmp_path / "run")
    cmd = [sys.executable, "-m", "dtf_tpu.fault", "--hosts=2",
           f"--logdir={logdir}", "--max-restarts=2",
           "--backoff-base-s=0.2", "--grace-s=45",
           "--valid-hosts=1,2",
           f"--telemetry-artifact={tmp_path / 'TELEMETRY.json'}", "--",
           sys.executable, SCRIPT, "--backend=cpu",
           f"--logdir={logdir}", "--train_steps=40", "--batch_size=32",
           "--checkpoint_every=3", "--log_every=50", "--telemetry",
           "--worker_hosts={worker_hosts}", "--task_index={host}",
           "--devices_per_host=2"]
    p = subprocess.Popen(cmd, env=_env({"DTF_FAULT_INJECT":
                                        "kill@6:host=1"}),
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True)
    out, _ = p.communicate(timeout=600)
    lines = [ln for ln in out.splitlines() if ln.strip()]
    summary = json.loads(lines[-1])
    assert p.returncode == 0, out[-2000:]
    assert summary["controller"] == "summary"
    # the CLI strips DTF_FAULT_INJECT from relaunch attempts (a seeded
    # fault is one-shot), so the kill is recovered and the run completes
    assert summary["final"] == "done"
    assert summary["restarts"] == 1
    assert summary["causes"] == ["host_lost"]
    art = json.load(open(tmp_path / "TELEMETRY.json"))
    assert art["runs"][-1]["telemetry"] == "controller"
