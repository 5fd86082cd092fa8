"""chip_smoke.py off the chip: it must refuse a CPU, and its phases must
run end to end here at ``tiny`` — the rehearsal that costs no chip time.

The size and the platform are steered from HERE (``backend="cpu"``, the
tiny config below); the script's own command line has no option that lets
it pass without a TPU.
"""

import json
import os
import subprocess
import sys
import time
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from _dtf_env import cpu_sim_env  # noqa: E402

#: MEDIUM's shape, cut to what the CPU and interpret mode run in seconds
TINY = {
    "size": "tiny", "seq_len": 64, "batch": 8, "steps": 6,
    "n_requests": 3, "prompt_len": 20, "n_new": 6, "max_len": 32,
    "n_slots": 4, "prefill_chunk": 8,
    "kernels": {
        "flash": [[1, 2, 64, 16], [1, 2, 50, 16]], "window": 24,
        "ce_tokens": 48, "ce_d_model": [32], "vocab": 128,
        "gather_rows": 200, "gather_dim": 16, "gather_ids": [4, 5],
        "decode_attn": [[3, 2, 2, 16, 256]],
    },
    "fence": {"n": 64, "reps": 2},
}


def test_chip_smoke_refuses_a_cpu(cpu_sim_subprocess_env):
    """On a process that sees no TPU: non-zero exit, ``"ok": false`` as the
    last line, and no phase result before it. The launcher's platform
    check stops it at start-up, long before a compile."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env=cpu_sim_subprocess_env, capture_output=True, text=True,
        timeout=180)
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and "train" in last["error"]
    assert len(lines) == 1, lines
    assert "--backend=tpu but JAX came up on 'cpu'" in proc.stderr


def test_launcher_with_backend_tpu_on_the_cpu_raises():
    """``launch.setup`` checks what JAX actually gave it: the default
    --backend=tpu on a CPU-only process fails before the mesh is built."""
    from dtf_tpu.cli import launch

    flags = types.SimpleNamespace(
        ps_hosts="", worker_hosts="", job_name="worker", task_index=0,
        issync=True, backend="tpu", devices_per_host=0, mesh_data=-1,
        mesh_seq=1, mesh_model=1, mesh_pipe=1, mesh_expert=1)
    with pytest.raises(RuntimeError, match="came up on 'cpu'"):
        launch.setup(flags)
    flags.backend = "cpu"
    mesh, info = launch.setup(flags)
    assert mesh.devices.flat[0].platform == "cpu" and info.is_chief


def test_compile_cache_follows_the_environment(monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set: nothing is set in code. Unset:
    ``<checkout>/.jax_cache``, a fixed path (it is part of the key)."""
    import jax

    from dtf_tpu.cli import launch

    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    launch.enable_compile_cache()
    assert seen == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    launch.enable_compile_cache()
    assert seen == [("jax_compilation_cache_dir",
                     os.path.join(ROOT, ".jax_cache"))]


def test_phase_failure_is_a_nonzero_exit_with_ok_false(monkeypatch, capsys):
    def boom(*a, **kw):
        raise chip_smoke.PhaseFailed("serve: statuses {'error': 1}")

    monkeypatch.setattr(chip_smoke, "run", boom)
    assert chip_smoke.main([]) == 1
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": False, "error": "serve: statuses {'error': 1}"}


def test_success_line_is_exactly_the_contract(monkeypatch, capsys):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "run", lambda *a, **kw: device)
    assert chip_smoke.main([]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": device}


def test_phases_must_agree_on_the_device():
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    assert chip_smoke.same_device([tpu, dict(tpu)], platform="tpu",
                                  count=1) == tpu
    with pytest.raises(chip_smoke.PhaseFailed, match="disagree"):
        chip_smoke.same_device([tpu, {**tpu, "count": 4}], platform="tpu",
                               count=1)
    with pytest.raises(chip_smoke.PhaseFailed, match="wanted"):
        chip_smoke.same_device([{**tpu, "platform": "cpu"}],
                               platform="tpu", count=1)


def test_a_child_past_its_time_limit_is_killed(tmp_path):
    t0 = time.monotonic()
    with pytest.raises(chip_smoke.PhaseFailed, match="killed at"):
        chip_smoke.run_child(
            "sleeper", [sys.executable, "-c", "import time; time.sleep(60)"],
            out_dir=str(tmp_path), deadline=time.monotonic() + 2)
    assert time.monotonic() - t0 < 30


@pytest.mark.slow
def test_one_chip_phases_rehearsed_on_the_cpu(tmp_path, monkeypatch,
                                              capsys):
    """train -> checkpoint -> serve -> parity -> kernels -> fence through
    the real launchers at ``tiny``; every request matches gpt.generate
    exactly here (f32 would; tiny bf16 on one CPU path does too)."""
    for k, v in cpu_sim_env(1, os.environ).items():
        monkeypatch.setenv(k, v)
    device = chip_smoke.run(TINY, backend="cpu", chips=1,
                            out_dir=str(tmp_path / "out"))
    assert device["platform"] == "cpu" and device["count"] == 1
    phases = {p["phase"]: p for p in map(
        json.loads, capsys.readouterr().out.strip().splitlines())}
    assert list(phases) == ["train", "serve", "parity", "kernels", "fence"]
    assert phases["train"]["losses"][-1] < phases["train"]["losses"][0]
    assert phases["serve"]["request_statuses"] == {"done": 3}
    assert phases["parity"]["exact"] == 3
    assert phases["kernels"]["ok"] and phases["kernels"]["interpret"]
    # the checkpoint is gone, the logs stay
    assert os.listdir(tmp_path / "out") == ["logs"]


@pytest.mark.slow
def test_four_chip_phase_rehearsed_on_virtual_devices(tmp_path, capsys):
    """data=2 x model=2 against one device, on four virtual CPU devices:
    in f32-accumulated tiny bf16 the curves agree far inside the bound."""
    device = chip_smoke.run(
        TINY, backend="cpu", chips=4, out_dir=str(tmp_path / "out"),
        sharded_env=cpu_sim_env(4, os.environ),
        single_env=cpu_sim_env(1, os.environ))
    assert device["platform"] == "cpu" and device["count"] == 4
    phases = {p["phase"]: p for p in map(
        json.loads, capsys.readouterr().out.strip().splitlines())}
    assert list(phases) == ["train_dp2_tp2", "train_one_device",
                            "sharded_vs_one_device"]
    assert phases["train_dp2_tp2"]["mesh"]["model"] == 2
    assert phases["sharded_vs_one_device"]["max_rel_diff"] <= 1e-3
