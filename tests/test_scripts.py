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


def test_bench_lm_child_tiny_pallas_loss():
    """CI-pin the DTF_LM_LOSS_PALLAS bench path (the fused head+CE row):
    the kernel runs in interpret mode on the sim, so a wiring typo can't
    surface for the first time mid-benchmark on the chip."""
    import json

    env = _env()
    env.update(DTF_LM_WHICH="gpt", DTF_LM_TINY="1", DTF_LM_STEPS="2",
               DTF_LM_LOSS_PALLAS="1")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_lm.py"),
         "--child"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    row = next(json.loads(ln[len("BENCH_LM_ROW "):])
               for ln in proc.stdout.splitlines()
               if ln.startswith("BENCH_LM_ROW "))
    assert row["loss_pallas"] is True and row["tokens_per_sec"] > 0


@pytest.mark.parametrize("which", ["gpt", "bert", "widedeep"])
def test_bench_lm_child_tiny_mode(which, tmp_path):
    """The LM bench children normally execute only on the TPU; tiny-mode
    CPU runs pin their code paths in CI so a regression can't surface for
    the first time mid-benchmark on the chip."""
    env = _env()
    env["DTF_LM_WHICH"] = which
    env["DTF_LM_TINY"] = "1"
    env["DTF_LM_STEPS"] = "2"
    if which == "widedeep":
        env["DTF_LM_BATCH"] = "64"
    elif which == "bert":
        # tiny default (8) x grad_accum 2 -> microbatch 4, which the
        # 8-device sim can't shard; the TPU target is a single chip
        env["DTF_LM_BATCH"] = "32"
        env["DTF_LM_LOSS_CHUNK"] = "48"   # CI-pin the chunked-MLM path
        env["DTF_LM_MLM_GATHER"] = "16"   # + the masked-position gather
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_lm.py"),
         "--child"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    import json

    rows = [json.loads(ln[len("BENCH_LM_ROW "):])
            for ln in proc.stdout.splitlines()
            if ln.startswith("BENCH_LM_ROW ")]
    assert len(rows) == 1
    row = rows[0]
    assert row["model"] == which and row["sec_per_step"] > 0
    key = "tokens_per_sec" if which in ("gpt", "bert") else "examples_per_sec"
    assert row[key] > 0


def test_bench_attention_tpu_child_interpret_mode():
    """CI-pin the TPU attention-bench child (incl. the h-folded forward
    grid) via its interpret-mode escape hatch — a wiring typo must not
    surface for the first time on the chip."""
    import json

    env = _env()
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    env.update(DTF_ATTN_SEQ="256", DTF_ATTN_BQ="64", DTF_ATTN_BK="64",
               DTF_ATTN_BH="2", DTF_ATTN_BQB="128", DTF_ATTN_BKB="64",
               DTF_ATTN_INTERPRET="1")
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "scripts", "bench_attention.py"), "tpu",
         "--child"],
        env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    row = next(json.loads(ln[len("ATTN_TPU_RESULT "):])
               for ln in proc.stdout.splitlines()
               if ln.startswith("ATTN_TPU_RESULT "))
    assert row["seq"] == 256 and row["block_h"] == 2
    assert row["flash_fwd_s"] > 0 and row["flash_fwdbwd_s"] > 0


def test_bench_lm_phase_child_tiny_mode():
    """CI-pin the fwd/fwdbwd phase-decomposition children: the backward
    must stay live in the timed graph (its XLA flop count must be well
    above the forward's), or the MFU attribution run would silently time
    a dead-code-eliminated graph."""
    import json

    flops = {}
    for phase in ("fwd", "fwdbwd"):
        env = _env()
        env.update(DTF_LM_WHICH="gpt", DTF_LM_TINY="1", DTF_LM_STEPS="2",
                   DTF_LM_PHASE=phase)
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "bench_lm.py"),
             "--child"],
            env=env, capture_output=True, text=True, timeout=420)
        assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
        row = next(json.loads(ln[len("BENCH_LM_ROW "):])
                   for ln in proc.stdout.splitlines()
                   if ln.startswith("BENCH_LM_ROW "))
        assert row["phase"] == phase and row["tokens_per_sec"] > 0
        flops[phase] = row.get("xla_flops_per_step", 0.0)
    assert flops["fwdbwd"] > 2.0 * flops["fwd"]


@pytest.mark.parametrize("kv,window,chunk",
                         [("0", "0", "0"), ("2", "8", "0"),
                          ("2", "8", "4")])
def test_bench_decode_child_tiny_mode(kv, window, chunk):
    """CI-pin the decode benchmark children (MHA/full, GQA/rolling, and
    chunked-prefill corners) so the serving-bench code path can't regress
    untested until the next on-chip run."""
    env = _env()
    env.update(DTF_DECODE_TINY="1", DTF_DEC_KV=kv, DTF_DEC_WINDOW=window,
               DTF_DEC_PREFILL_CHUNK=chunk)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_decode.py"),
         "--child"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    import json

    rows = [json.loads(ln[len("BENCH_DECODE_ROW "):])
            for ln in proc.stdout.splitlines()
            if ln.startswith("BENCH_DECODE_ROW ")]
    assert len(rows) == 1
    row = rows[0]
    assert row["prefill_tokens_per_sec"] > 0
    # tiny-mode decode deltas may be inside dispatch noise — then the row
    # must say so instead of carrying a nonsense number
    if row.get("decode_noise_limited"):
        assert row["decode_tokens_per_sec"] is None
    else:
        assert row["decode_tokens_per_sec"] > 0
    assert row["kv_heads"] == (int(kv) or 4) and row["window"] == int(window)
    assert row["prefill_chunk"] == int(chunk)


def test_bench_decode_serve_ab_child_tiny_mode():
    """The continuous-vs-static A/B child (--sweep-serve): one row with
    both sides' goodput and TTFT percentiles, on the CPU sim."""
    env = _env()
    env.update(DTF_DECODE_TINY="1", DTF_SERVE_RATE="500", DTF_SERVE_N="8")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_decode.py"),
         "--child", "--serve"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    import json

    rows = [json.loads(ln[len("BENCH_DECODE_ROW "):])
            for ln in proc.stdout.splitlines()
            if ln.startswith("BENCH_DECODE_ROW ")]
    assert len(rows) == 1
    row = rows[0]
    for side in ("serve", "static"):
        assert row[side]["tokens_per_sec"] > 0
        assert row[side]["ttft_p50_s"] <= row[side]["ttft_p99_s"]
    assert 0 < row["serve"]["occupancy_mean"] <= 1


def test_bench_decode_serve_prefix_ab_child_tiny_mode():
    """The prefix-cache A/B (ISSUE 6 acceptance): at hit-ratio > 0 the
    page cache strictly reduces prefill work (fewer transformer chunks,
    pages genuinely loaded) and improves TTFT p50 vs the same arrivals
    with the cache off, on the CPU sim."""
    env = _env()
    env.update(DTF_DECODE_TINY="1", DTF_SERVE_RATE="500", DTF_SERVE_N="12",
               DTF_SERVE_PREFIX="0.75")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_decode.py"),
         "--child", "--serve"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    import json

    rows = [json.loads(ln[len("BENCH_DECODE_ROW "):])
            for ln in proc.stdout.splitlines()
            if ln.startswith("BENCH_DECODE_ROW ")]
    assert len(rows) == 1
    on, off = rows[0]["serve"], rows[0]["serve_off"]
    # prefill-work reduction is deterministic (host counters)
    assert on["prefill_chunks"] < off["prefill_chunks"], (on, off)
    assert on["pages_loaded"] > 0 and on["prefix_hit_tokens"] > 0
    assert off["pages_loaded"] == 0
    # the latency claim (wall clocks — a small margin absorbs CI noise;
    # the measured gap is ~25-40% in favor of the cache)
    assert on["ttft_p50_s"] <= off["ttft_p50_s"] * 1.1, (on, off)


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


@pytest.mark.parametrize("which", ["gpt", "bert"])
def test_bench_cost_table_child_tiny_mode(which):
    """CI-pin the profiler-fallback attribution (bench_cost_table.py):
    component rows + whole-program anchors emit, percentages computable,
    so the on-chip run can't be the first execution of this code."""
    env = _env()
    env["DTF_COST_WHICH"] = which
    env["DTF_COST_TINY"] = "1"
    env["DTF_COST_ITERS"] = "3"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts",
                                      "bench_cost_table.py"), "--child"],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    import json

    rows = [json.loads(ln[len("BENCH_COST_ROW "):])
            for ln in proc.stdout.splitlines()
            if ln.startswith("BENCH_COST_ROW ")]
    assert len(rows) == 1
    row = rows[0]
    names = {c["component"] for c in row["components"]}
    assert names == {"embed", "attn_layer", "ffn_layer", "head_loss"}
    assert row["fwd_sec"] > 0 and row["fwdbwd_sec"] > row["fwd_sec"]
    assert all(c["sec"] > 0 and c["xla_flops"] > 0
               for c in row["components"])


def test_bench_io_tiny_mode():
    """CI-pin the host-side IO bench (bench_io.py): python + native rows
    emit for both the IDX epoch path and TFRecord indexing, so the
    artifact run can't be the first execution of this code. No jax, no
    device — plain host subprocess."""
    from dtf_tpu.data.native import native_available

    if not native_available():
        pytest.skip("no C++ toolchain")  # bench still runs, python-only
    env = dict(os.environ)
    env["DTF_IO_TINY"] = "1"
    env["PYTHONPATH"] = ROOT
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "bench_io.py")],
        env=env, capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, proc.stdout[-1500:] + proc.stderr[-1500:]
    import json

    row = json.loads(proc.stdout.splitlines()[-1])
    assert row["tiny"] is True
    assert row["idx_epoch"]["python_images_per_sec"] > 0
    assert row["idx_epoch"]["native_images_per_sec"] > 0
    tf = row["tfrecord_index"]
    assert tf["python_index_mb_per_sec"] > 0
    assert tf["native_index_mb_per_sec"] > 0
    assert tf["native_verifies_payload_crc"] is True
    ms = row["mixture_stream"]          # ISSUE 15: the stream tier's row
    assert ms["inline_batches_per_sec"] > 0
    assert ms["producer_depth2_batches_per_sec"] > 0
    assert abs(ms["realized_frac_a"] - 0.7) < 0.1
