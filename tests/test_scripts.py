"""End-to-end launcher smoke tests — every CLI entrypoint, real subprocesses.

The unit/integration suite can't catch flag-wiring regressions (a renamed
flag, a config field not plumbed, an import typo in a rarely-driven branch);
these run each launcher for a few steps on the 8-device CPU sim exactly as a
user would, plus the train→serve round trip. Tiny configs keep each run to
compile time + seconds.
"""

import os
import subprocess
import sys

import pytest

pytestmark = pytest.mark.slow  # subprocess-heavy tier

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    return env


def _run(script, *args, timeout=420):
    # the launchers default to --backend=tpu and refuse anything else
    if not any(a.startswith("--backend") for a in args):
        args = ("--backend=cpu", *args)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", script), *args],
        env=_env(), capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{script} rc={proc.returncode}\n{proc.stdout[-1500:]}\n"
        f"{proc.stderr[-1500:]}")
    return proc.stdout + proc.stderr


def test_mnist_launcher(tmp_path):
    out = _run("distributed.py", "--backend=cpu", "--train_steps=3",
               "--batch_size=32", f"--logdir={tmp_path}")
    assert "done: step=3" in out


def test_resnet_launcher(tmp_path):
    out = _run("train_resnet.py", "--config=cifar", "--train_steps=2",
               "--batch_size=16", f"--logdir={tmp_path}")
    assert "done: step=2" in out


def test_bert_launcher_flash_tp(tmp_path):
    out = _run("train_bert.py", "--size=tiny", "--attn_impl=flash",
               "--mesh_model=2", "--train_steps=2", "--batch_size=16",
               "--seq_len=32", "--eval_every=2", f"--logdir={tmp_path}")
    assert "done: step=2" in out


def test_widedeep_launcher(tmp_path):
    out = _run("train_widedeep.py", "--train_steps=2", "--batch_size=64",
               "--hash_buckets=500", "--mesh_model=2",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out


def test_gpt_launcher_full_feature_combo(tmp_path):
    """GQA + window + clip + eval + chunked loss on one run — the
    flag-plumbing sweep."""
    out = _run("train_gpt.py", "--size=tiny", "--kv_heads=2",
               "--attn_window=8", "--clip_grad_norm=1.0", "--eval_every=2",
               "--loss_chunk_vocab=48",
               "--train_steps=2", "--batch_size=16", "--seq_len=32",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out


def test_gpt_pipelined_launcher_with_eval(tmp_path):
    """--mesh_pipe>1 trains through the pipeline schedule AND reports
    held-out perplexity — the eval step runs un-pipelined against the same
    stacked params (VERDICT r3 #7 closed the eval-skip caveat)."""
    out = _run("train_gpt.py", "--size=tiny", "--mesh_pipe=2",
               "--mesh_data=4", "--eval_every=2", "--train_steps=2",
               "--batch_size=16", "--seq_len=32", f"--logdir={tmp_path}")
    assert "done: step=2" in out
    assert "eval_ppl" in out


def test_gpt_pp_x_sp_launcher(tmp_path):
    """Pipeline x sequence parallelism end to end: seq-sharded microbatch
    activations through the schedule, ring attention per shard, held-out
    eval via the un-pipelined path."""
    out = _run("train_gpt.py", "--size=tiny", "--mesh_pipe=2",
               "--mesh_seq=2", "--mesh_data=2", "--eval_every=2",
               "--train_steps=2", "--batch_size=16", "--seq_len=32",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out
    assert "eval_ppl" in out


def test_gpt_zero_bubble_launcher(tmp_path):
    """--pipe_schedule=zb end to end: the W/B-split backward trains the
    full model through make_train_step_from_grads (grads computed inside
    the schedule — no jax.grad), with held-out eval on the un-pipelined
    path. Numeric parity vs 1F1B is proven in test_gpt_pipe.py; this
    guards the launcher plumbing."""
    out = _run("train_gpt.py", "--size=tiny", "--mesh_pipe=2",
               "--mesh_data=4", "--pipe_schedule=zb", "--eval_every=2",
               "--train_steps=2", "--batch_size=16", "--seq_len=32",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out
    assert "eval_ppl" in out


def test_gpt_train_then_generate_round_trip(tmp_path):
    """The serve path: checkpoint from train_gpt.py decoded by
    generate_gpt.py, greedy and sampled, unsharded and dp2xtp2."""
    out = _run("train_gpt.py", "--size=tiny", "--train_steps=2",
               "--batch_size=16", "--seq_len=32", "--checkpoint_every=2",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out

    gen = _run("generate_gpt.py", "--size=tiny", f"--logdir={tmp_path}",
               "--prompt=5,9,2", "--n_new=6", "--batch=2")
    rows = [ln for ln in gen.splitlines() if ln.startswith("5,9,2,")]
    assert len(rows) == 2 and rows[0] == rows[1]      # greedy, broadcast

    gen_sharded = _run("generate_gpt.py", "--size=tiny",
                       f"--logdir={tmp_path}", "--prompt=5,9,2", "--n_new=6",
                       "--batch=4", "--mesh_data=2", "--mesh_model=2")
    rows_sh = [ln for ln in gen_sharded.splitlines()
               if ln.startswith("5,9,2,")]
    assert rows_sh and rows_sh[0] == rows[0]          # sharded == unsharded

    gen_sampled = _run("generate_gpt.py", "--size=tiny",
                       f"--logdir={tmp_path}", "--prompt=5,9,2", "--n_new=6",
                       "--temperature=0.9", "--top_p=0.9", "--top_k=20")
    assert any(ln.startswith("5,9,2,") for ln in gen_sampled.splitlines())


def test_serve_launcher_round_trip(tmp_path):
    """train_gpt → serve_gpt: the online half of the flagship loop. The
    launcher restores the params-only item, auto-loads the manifest (no
    --size passed!), serves explicit requests and a Poisson burst, and its
    greedy tokens for a shared prompt match generate_gpt.py's."""
    out = _run("train_gpt.py", "--size=tiny", "--train_steps=2",
               "--batch_size=16", "--seq_len=32", "--checkpoint_every=2",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out
    assert (tmp_path / "ckpt" / "model_config.json").exists()

    srv = _run("serve_gpt.py", f"--logdir={tmp_path}", "--n_slots=2",
               "--max_len=48", "--prefill_chunk=4",
               "--requests=5,9,2;1,2,3,4,5,6", "--n_new=6", "--emit_tokens")
    import json

    line = [ln for ln in srv.splitlines() if ln.startswith("{")][-1]
    stats = json.loads(line)
    assert stats["requests"] == 2 and stats["serve_completed"] == 2.0
    assert stats["tokens_per_sec"] > 0
    srv_row = [ln for ln in srv.splitlines() if ln.startswith("0:")][0]

    gen = _run("generate_gpt.py", f"--logdir={tmp_path}",
               "--prompt=5,9,2", "--n_new=6")
    gen_row = [ln for ln in gen.splitlines() if ln.startswith("5,9,2,")][0]
    # same checkpoint, same greedy prompt → same continuation
    assert gen_row == "5,9,2," + srv_row[len("0:"):]

    # a flag contradicting the manifest must fail loudly, not garble decode
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={tmp_path}", "--size=small"],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and "contradicts" in proc.stderr

    srv_p = _run("serve_gpt.py", f"--logdir={tmp_path}", "--n_slots=2",
                 "--max_len=48", "--prefill_chunk=4", "--poisson_rate=500",
                 "--n_requests=6", "--prompt_min=2", "--prompt_max=10",
                 "--new_min=2", "--new_max=8")
    stats = json.loads([ln for ln in srv_p.splitlines()
                        if ln.startswith("{")][-1])
    assert stats["mode"] == "poisson" and stats["serve_completed"] == 6.0

    # the serving tier: 2 router replicas + the prefix page cache + a TTFT
    # SLO — same checkpoint, same greedy prompt, same tokens as replica 0
    # of nothing (offline parity holds through the whole tier)
    srv_r = _run("serve_gpt.py", f"--logdir={tmp_path}", "--replicas=2",
                 "--n_slots=2", "--max_len=48", "--prefill_chunk=4",
                 "--kv_page_size=4", "--prefix_pages=8", "--ttft_slo=30",
                 "--requests=5,9,2;5,9,2,7,1,3;5,9,2,7,1,4", "--n_new=6",
                 "--emit_tokens")
    rstats = json.loads([ln for ln in srv_r.splitlines()
                         if ln.startswith("{")][-1])
    assert rstats["router_replicas"] == 2.0
    assert rstats["router_completed"] == 3.0
    assert rstats["router_ttft_slo_ok_frac"] == 1.0
    assert "replica1_serve_occupancy_mean" in rstats
    row_r = [ln for ln in srv_r.splitlines() if ln.startswith("0:")][0]
    assert row_r == srv_row          # same greedy continuation of 5,9,2

    # a page size that doesn't tile the cache fails at flag time
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={tmp_path}", "--max_len=48", "--kv_page_size=7",
         "--prefix_pages=8"],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and "does not divide" in proc.stderr


def test_serve_heartbeat_and_request_trace(tmp_path):
    """ISSUE 8 satellites through the launcher: --stats_every emits
    periodic heartbeat JSON lines (stderr; stdout's last line stays the
    one metrics line), --ttft_slo_frac warns on SLO breach, and
    --trace_out writes the Perfetto chrome trace with per-request
    lifecycles tagged by end-to-end trace ids."""
    import json

    out = _run("train_gpt.py", "--size=tiny", "--train_steps=2",
               "--batch_size=16", "--seq_len=32", "--checkpoint_every=2",
               f"--logdir={tmp_path}")
    assert "done: step=2" in out

    trace_path = tmp_path / "serve_trace.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={tmp_path}", "--replicas=2", "--n_slots=2",
         "--max_len=48", "--prefill_chunk=4", "--poisson_rate=500",
         "--n_requests=6", "--prompt_min=2", "--prompt_max=10",
         "--new_min=2", "--new_max=8", "--telemetry", "--stats_every=2",
         "--ttft_slo=1e-9", "--ttft_slo_frac=0.99",
         f"--trace_out={trace_path}"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stderr[-2000:]
    stats = json.loads([ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1])
    assert stats["router_completed"] == 6.0
    # heartbeats: periodic JSON snapshot lines on stderr, counted in the
    # final metrics line; the per-replica occupancy/TTFT panel rides them
    beats = [json.loads(ln) for ln in proc.stderr.splitlines()
             if ln.startswith('{"serve_heartbeat"')]
    assert beats and stats["heartbeats"] == len(beats)
    assert "router_occupancy" in beats[-1]
    assert any(k.startswith("replica0_") for k in beats[-1])
    # an impossible SLO (1 ns) must trip the floor warning
    assert "below the 0.990 floor" in proc.stderr
    # the chrome trace: request lifecycles with router-global trace ids
    doc = json.loads(trace_path.read_text())
    reqs = [e for e in doc["traceEvents"] if e["name"] == "request"]
    assert len(reqs) == 6
    assert {e["tid"] for e in reqs} == set(range(6))
    names = {e["name"] for e in doc["traceEvents"]}
    assert {"queue_wait", "serve_prefill_chunk", "serve_decode"} <= names
    assert stats["trace_events"] == len(doc["traceEvents"])


def test_generate_rejects_sampling_flags_at_greedy(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "generate_gpt.py"),
         "--backend=cpu",
         "--size=tiny", f"--logdir={tmp_path}", "--top_p=0.5"],
        env=_env(), capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "temperature" in (proc.stdout + proc.stderr)
