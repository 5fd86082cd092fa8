"""Process-level fault injection — the MultiProcessRunner-style harness.

SURVEY.md §4/§5.3: TF's ecosystem tested fault paths by forking cluster
processes and killing them (``MultiProcessRunner``). The reference itself
only had ``_RecoverableSession`` (rebuild session + restore checkpoint). The
equivalent invariant here: SIGKILL a live training process mid-run, relaunch
the same command, and it must (a) survive a possibly-partial final save
(Orbax writes are atomic — tmp dir + rename), (b) restore the latest durable
step, (c) finish the run. This drives the REAL CLI entrypoint, not a
test-double loop.
"""

import os
import re
import signal
import subprocess
import sys
import time

import pytest

pytestmark = pytest.mark.slow  # subprocess-heavy tier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "scripts", "distributed.py")


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    return env


def _launch(logdir, steps):
    return subprocess.Popen(
        [sys.executable, SCRIPT, "--backend=cpu", f"--logdir={logdir}",
         f"--train_steps={steps}", "--batch_size=32",
         "--checkpoint_every=5", "--log_every=5"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)


def _has_checkpoint(logdir):
    ckpt_dir = os.path.join(logdir, "ckpt")
    if not os.path.isdir(ckpt_dir):
        return False
    return any(d.isdigit() for d in os.listdir(ckpt_dir))


def test_sigterm_saves_current_step_and_resumes(tmp_path):
    """Graceful preemption (PreemptionHook): SIGTERM mid-run must save the
    EXACT in-flight step (not just the last periodic save), exit 0, and a
    relaunch must resume from it. checkpoint_every is huge so any durable
    step beyond 0 can only have come from the preemption save."""
    logdir = str(tmp_path / "run")
    p = subprocess.Popen(
        [sys.executable, SCRIPT, "--backend=cpu", f"--logdir={logdir}",
         "--train_steps=100000", "--batch_size=32",
         "--checkpoint_every=100000", "--log_every=5"],
        env=_env(), stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    try:
        # give it time to compile + take some steps, then "preempt"
        for _ in range(30):
            if p.poll() is not None:
                pytest.fail(f"trainer exited early ({p.returncode}):\n"
                            f"{p.stdout.read()[-2000:]}")
            time.sleep(1.0)
        os.kill(p.pid, signal.SIGTERM)
        out, _ = p.communicate(timeout=300)
    finally:
        if p.poll() is None:
            p.kill()
    assert p.returncode == 0, out[-2000:]
    assert _has_checkpoint(logdir), "preemption save did not land"
    saved = max(int(d) for d in os.listdir(os.path.join(logdir, "ckpt"))
                if d.isdigit())
    assert saved >= 1, "preemption save happened before any step"

    # relaunch: must resume from exactly the preemption step and finish
    p2 = _launch(logdir, steps=saved + 5)
    out2, _ = p2.communicate(timeout=300)
    assert p2.returncode == 0, out2[-2000:]
    assert f"resumed from checkpoint at step {saved}" in out2, out2[-2000:]
    assert f"done: step={saved + 5}" in out2, out2[-2000:]


def test_sigkill_and_resume(tmp_path):
    logdir = str(tmp_path / "run")

    # phase 1: launch, wait for a durable checkpoint, SIGKILL (no cleanup).
    p = _launch(logdir, steps=10_000)
    try:
        deadline = time.time() + 300
        while time.time() < deadline and not _has_checkpoint(logdir):
            if p.poll() is not None:
                out = p.stdout.read()
                pytest.fail(f"trainer exited early ({p.returncode}):\n{out[-2000:]}")
            time.sleep(0.5)
        assert _has_checkpoint(logdir), "no checkpoint appeared within 300s"
        os.kill(p.pid, signal.SIGKILL)
        p.wait(timeout=30)
    finally:
        if p.poll() is None:
            p.kill()

    # phase 2: relaunch the SAME command with a finite step target; it must
    # restore (not start at 0) and finish at max(target, resumed_step) —
    # training may have raced past the target before the kill landed.
    p2 = _launch(logdir, steps=30)
    out, _ = p2.communicate(timeout=300)
    assert p2.returncode == 0, out[-2000:]
    m = re.search(r"resumed from checkpoint at step (\d+)", out)
    assert m, out[-2000:]
    resumed = int(m.group(1))
    assert resumed >= 5, f"resume lost progress: step {resumed}"
    assert f"done: step={max(30, resumed)}" in out, out[-2000:]
