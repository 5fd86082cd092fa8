#!/usr/bin/env python
"""Does the system still start on the chip? GPT-2 medium, end to end.

    python chip_smoke.py             # one chip: train -> serve -> kernels
    python chip_smoke.py --chips=4   # four chips: the sharded step only

One chip, in this order, each phase through the entry point a user would
call, at the full width of GPT-2 medium (d_model 1024, 24 layers, 16 heads,
vocab 50304, bf16; random weights from a seed):

- train:   scripts/train_gpt.py, a few steps past compile on the synthetic
  stream, default (flash) attention, one checkpoint. The loss must be
  finite and lower at the last step than at the first.
- serve:   scripts/serve_gpt.py restores that checkpoint and answers a
  handful of greedy requests (prompts of a few hundred tokens, tens of new
  tokens); all must end ``done`` with the token counts asked for.
- parity:  ``gpt.generate`` on the same params and prompts; at least one
  request's engine tokens must equal the reference exactly (all do at toy
  size in f32, tests/test_serve.py; in bf16 a near-tie argmax may flip).
- kernels: every Pallas kernel of the path, COMPILED, against its dense
  reference at real widths (fwd and grads, aligned and unaligned, masks).
- fence:   whether ``jax.block_until_ready`` waits for the device.

``--chips=4`` runs only the sharded train step (data=2 x model=2: Megatron
TP + ZeRO-1) and the same seed, batch and steps on ONE device of the same
host, and compares the loss curves.

One process holds a chip at a time, so this parent never imports jax and
its children run one after another. There is no option that lets the
script pass without a TPU: the launchers get ``--backend=tpu`` and refuse
whatever else JAX came up on (tests/test_chip_smoke.py rehearses the same
phase functions on the CPU at ``tiny``, steered from the test). Any phase
that fails ends the run: exit code 1, last line ``{"ok": false, ...}``.
On success the last stdout line is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, ".chip_smoke")       # gitignored scratch
TIME_LIMIT_S = 1150                               # the contract's 1200, less margin

#: one chip of a multi-chip host, through libtpu's own environment: the
#: launcher builds its mesh over every device the process sees
ONE_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0",
                "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                "TPU_PROCESS_BOUNDS": "1,1,1"}

#: the real size (GPT-2 medium, the widths of tests/test_chip_compile.py)
MEDIUM = {
    "size": "medium", "seq_len": 1024, "batch": 8, "steps": 12,
    "n_requests": 5, "prompt_len": 256, "n_new": 24, "max_len": 512,
    "n_slots": 8, "prefill_chunk": 128,
    "kernels": {
        # [batch, heads, seq, head_dim]: medium's heads, aligned/unaligned
        "flash": [[2, 16, 1024, 64], [2, 16, 1000, 64]],
        "window": 256,
        # fused head+CE: tokens, d_model (small and medium), vocab
        "ce_tokens": 2048, "ce_d_model": [768, 1024], "vocab": 50304,
        # a Wide&Deep table and one batch of lookups
        "gather_rows": 1_000_000, "gather_dim": 64, "gather_ids": [4096, 26],
        # slot-decode attention [slots, kv_heads, group, head_dim, max_len]:
        # the two serve cells' heads
        "decode_attn": [[8, 16, 1, 64, 1024], [8, 8, 4, 64, 2048]],
    },
    "fence": {"n": 4096, "reps": 64},
}


class PhaseFailed(Exception):
    pass


def say(phase, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


# ----------------------------------------------------------------- children

def run_child(name, argv, *, out_dir, deadline, env=None, cap_s=900):
    """Run one child to its end, alone: the chip belongs to it until it
    exits. Output goes to ``<out_dir>/logs/<name>.{out,err}``; a non-zero
    exit or the time limit fails the phase. Returns (stdout, stderr)."""
    logs = os.path.join(out_dir, "logs")
    os.makedirs(logs, exist_ok=True)
    timeout = min(cap_s, deadline - time.monotonic())
    if timeout <= 0:
        raise PhaseFailed(f"{name}: no time left inside {TIME_LIMIT_S}s")
    paths = [os.path.join(logs, f"{name}.{ext}") for ext in ("out", "err")]
    with open(paths[0], "w") as fo, open(paths[1], "w") as fe:
        proc = subprocess.Popen(argv, stdout=fo, stderr=fe, cwd=ROOT,
                                env={**os.environ, **(env or {})},
                                start_new_session=True)
        try:
            rc = proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            rc = None
        finally:
            if proc.poll() is None:          # stop everything it started
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait()
    with open(paths[0]) as fo, open(paths[1]) as fe:
        out, err = fo.read(), fe.read()
    if rc != 0:
        tail = "\n".join(err.strip().splitlines()[-25:])
        sys.stderr.write(f"--- {name} stderr tail ---\n{tail}\n")
        raise PhaseFailed(
            f"{name}: " + (f"exit code {rc}" if rc is not None
                           else f"killed at {timeout:.0f}s")
            + f" (logs: {paths[1]})")
    return out, err


def last_json(text, key):
    """The last stdout line that is a JSON object holding ``key``."""
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if key in obj:
                return obj
    raise PhaseFailed(f"no JSON line with {key!r} in the child's output")


# ------------------------------------------------------------------- phases

def train_phase(name, cfg, *, backend, out_dir, deadline, flags=(),
                env=None):
    """scripts/train_gpt.py for ``cfg['steps']`` steps; the loss curve is
    read from the LoggingHook lines the launcher already writes."""
    logdir = os.path.join(out_dir, name)
    t0 = time.monotonic()
    out, err = run_child(name, [
        sys.executable, os.path.join(ROOT, "scripts", "train_gpt.py"),
        f"--backend={backend}", f"--size={cfg['size']}",
        f"--seq_len={cfg['seq_len']}", f"--batch_size={cfg['batch']}",
        f"--train_steps={cfg['steps']}", "--log_every=1",
        f"--checkpoint_every={cfg['steps']}", f"--logdir={logdir}",
        "--seed=0", "--telemetry", *flags],
        out_dir=out_dir, deadline=deadline, env=env)
    report = last_json(out, "telemetry")
    curve = {int(s): float(v) for s, v in re.findall(
        r"\] \[(\d+)\] (?:.*, )?loss=([-+.\w]+)", err)}
    losses = [curve[s] for s in sorted(curve)]
    if f"done: step={cfg['steps']}" not in out or len(losses) < 2:
        raise PhaseFailed(f"{name}: trainer did not reach step "
                          f"{cfg['steps']} (logged {sorted(curve)})")
    rates = sorted(float(v) for v in re.findall(r"steps_per_sec=([-+.\w]+)",
                                                err))
    result = {
        "device": report["device"], "seconds": round(time.monotonic() - t0, 1),
        "compile_s": report.get("compile_s"), "losses": losses,
        "step_s_median": round(1.0 / rates[len(rates) // 2], 4),
        "tokens_per_step": cfg["batch"] * cfg["seq_len"],
        "peak_hbm_bytes": report.get("peak_hbm_bytes"), "mesh": report["mesh"]}
    say(name, **result)
    if not all(x == x and abs(x) != float("inf") for x in losses):
        raise PhaseFailed(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise PhaseFailed(f"{name}: loss did not fall: {losses[0]} -> "
                          f"{losses[-1]}")
    return {**result, "logdir": logdir}


def make_prompts(cfg, vocab):
    rng = random.Random(0)
    return [[rng.randrange(vocab) for _ in range(cfg["prompt_len"])]
            for _ in range(cfg["n_requests"])]


def serve_phase(cfg, prompts, *, backend, out_dir, logdir, deadline):
    """scripts/serve_gpt.py on the trainer's checkpoint: every request
    ``done`` with ``n_new`` tokens. Returns the tokens per request."""
    t0 = time.monotonic()
    out, _ = run_child("serve", [
        sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
        f"--backend={backend}", f"--logdir={logdir}",
        f"--n_slots={cfg['n_slots']}", f"--max_len={cfg['max_len']}",
        f"--prefill_chunk={cfg['prefill_chunk']}", f"--n_new={cfg['n_new']}",
        "--temperature=0", "--emit_tokens", "--requests="
        + ";".join(",".join(map(str, p)) for p in prompts)],
        out_dir=out_dir, deadline=deadline)
    stats = last_json(out, "request_statuses")
    served = {int(rid): [int(t) for t in toks.split(",") if t]
              for rid, toks in re.findall(r"^(\d+):([\d,]*)$", out, re.M)}
    say("serve", device=stats["device"],
        seconds=round(time.monotonic() - t0, 1),
        request_statuses=stats["request_statuses"],
        generated_tokens=stats["generated_tokens"],
        tokens_per_sec=stats["tokens_per_sec"], wall_s=stats["wall_s"],
        restored_step=stats["step"],
        peak_hbm_bytes=stats.get("peak_hbm_bytes"))
    if stats["request_statuses"] != {"done": len(prompts)}:
        raise PhaseFailed(f"serve: statuses {stats['request_statuses']}")
    counts = [len(served.get(i, ())) for i in range(len(prompts))]
    if counts != [cfg["n_new"]] * len(prompts):
        raise PhaseFailed(f"serve: token counts {counts}, asked for "
                          f"{cfg['n_new']} each")
    return {"device": stats["device"], "served": served}


def check_phases(names, spec, *, out_dir, deadline):
    """The JAX-side checks (parity, kernels, fence), one child for all of
    ``names``: this script again, as ``--child <spec.json>``."""
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "checks.json")
    with open(path, "w") as f:
        json.dump({**spec, "phases": names}, f)
    try:
        out, _ = run_child("checks", [sys.executable,
                                      os.path.abspath(__file__),
                                      "--child", path],
                           out_dir=out_dir, deadline=deadline)
    finally:
        # what the child got as far as printing is worth showing either way
        try:
            with open(os.path.join(out_dir, "logs", "checks.out")) as f:
                for line in f:
                    if line.startswith('{"phase"'):
                        print(line, end="", flush=True)
        except OSError:
            pass
    return last_json(out, "checks_device")["checks_device"]


def same_device(devices, *, platform, count):
    """All phases ran on one and the same device set, the one asked for."""
    first = devices[0]
    if any(d != first for d in devices):
        raise PhaseFailed(f"phases disagree on the device: {devices}")
    if first["platform"] != platform or first["count"] != count:
        raise PhaseFailed(f"ran on {first}, wanted {count} x {platform}")
    return first


def run_one_chip(cfg, *, backend, out_dir, deadline):
    train = train_phase("train", cfg, backend=backend, out_dir=out_dir,
                        deadline=deadline)
    prompts = make_prompts(cfg, cfg["kernels"]["vocab"])
    serve = serve_phase(cfg, prompts, backend=backend, out_dir=out_dir,
                        logdir=train["logdir"], deadline=deadline)
    checks = check_phases(["parity", "kernels", "fence"], {
        "backend": backend, "size": cfg["size"], "logdir": train["logdir"],
        "prompts": prompts, "n_new": cfg["n_new"],
        "prefill_chunk": cfg["prefill_chunk"],
        "served": [serve["served"][i] for i in range(len(prompts))],
        "kernels": cfg["kernels"], "fence": cfg["fence"]},
        out_dir=out_dir, deadline=deadline)
    return same_device([train["device"], serve["device"], checks],
                       platform=backend, count=1)


def run_four_chips(cfg, *, backend, out_dir, deadline, sharded_env=None,
                   single_env=ONE_CHIP_ENV, rtol=1e-3):
    """The sharded step against one device of the same host: same seed,
    global batch and steps, loss curves within ``rtol`` — the TP-vs-DP
    parity of tests/test_gpt.py (2e-4 in f32 at toy size), five times
    wider for bf16 at medium; the chip showed 1.8e-5 (PR 21)."""
    sharded = train_phase("train_dp2_tp2", cfg, backend=backend,
                          out_dir=out_dir, deadline=deadline,
                          flags=("--mesh_data=2", "--mesh_model=2"),
                          env=sharded_env)
    shutil.rmtree(sharded["logdir"], ignore_errors=True)
    single = train_phase("train_one_device", cfg, backend=backend,
                         out_dir=out_dir, deadline=deadline, env=single_env)
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(sharded["losses"], single["losses"]))
    say("sharded_vs_one_device", max_rel_diff=round(worst, 6), rtol=rtol,
        steps=len(single["losses"]))
    if len(sharded["losses"]) != len(single["losses"]) or not worst <= rtol:
        raise PhaseFailed(f"sharded and one-device loss curves differ by "
                          f"{worst:.4g} (rtol {rtol})")
    same_device([single["device"]], platform=backend, count=1)
    return same_device([sharded["device"]], platform=backend, count=4)


# --------------------------------------------------- the JAX side (a child)

def _rel_err(got, want):
    import jax.numpy as jnp

    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def check_parity(spec):
    """gpt.generate on the trainer's params and the served prompts."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtf_tpu.checkpoint import Checkpointer
    from dtf_tpu.models import gpt

    prompts = jnp.asarray(spec["prompts"], jnp.int32)
    n_new = spec["n_new"]
    cfg = dataclasses.replace(gpt.GPTConfig.by_name(spec["size"]),
                              decode_len=prompts.shape[1] + n_new)
    ckpt = Checkpointer(os.path.join(spec["logdir"], "ckpt"))
    params = ckpt.restore_params()
    ckpt.close()
    model = gpt.GPT(cfg)
    want = np.asarray(jax.jit(lambda params, prompts: gpt.generate(
        model, params, prompts, n_new, temperature=0.0,
        prefill_chunk=spec["prefill_chunk"]))(params, prompts))
    want = want[:, prompts.shape[1]:]
    got = np.asarray(spec["served"])
    # per request: how many leading tokens agree (n_new = all of them)
    agree = [int(n_new if (g == w).all() else np.argmin(g == w))
             for g, w in zip(got, want)]
    exact = sum(a == n_new for a in agree)
    return {"ok": exact >= 1, "requests": len(agree), "exact": exact,
            "agree_prefix": agree, "n_new": n_new}


def check_kernels(spec):
    """Compiled kernel vs dense reference, fwd and grads. The reference
    runs at HIGHEST precision (true f32); the kernels run at production
    precision, so tolerances budget for bf16 MXU rounding — errors are
    relative to the reference's largest magnitude."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtf_tpu.models import gpt
    from dtf_tpu.ops import attention as att
    from dtf_tpu.ops import decode_attention as da
    from dtf_tpu.ops import embed_gather as eg
    from dtf_tpu.ops import flash_attention as fa
    from dtf_tpu.ops.fused_ce import pallas_lm_cross_entropy
    from dtf_tpu.ops.losses import softmax_cross_entropy

    k = spec["kernels"]
    # interpret mode exists for the CPU rehearsal only: on the chip path
    # init_backend has already refused anything but a TPU
    interpret = jax.default_backend() != "tpu"
    checks = {}

    def record(name, got, want, tol):
        err = _rel_err(got, want)
        checks[name] = {"rel_err": round(err, 6), "tol": tol,
                        "ok": bool(err <= tol)}

    def against_dense(tag, flash, dense, q, kk, v):
        def loss(fn):
            def f(q, kk, v):
                o = fn(q, kk, v)
                return jnp.sum(o * (1 + jnp.cos(o))), o
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))
        (_, o_f), g_f = loss(flash)(q, kk, v)
        with jax.default_matmul_precision("highest"):
            (_, o_d), g_d = loss(dense)(q, kk, v)
        record(f"flash_fwd_{tag}", o_f, o_d, 2e-2)
        for g, w, n in zip(g_f, g_d, ("dq", "dk", "dv")):
            record(f"flash_bwd_{tag}_{n}", g, w, 5e-2)

    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    for b, h, t, d in k["flash"]:
        q, kk, v = (jax.random.normal(keys[i], (b, h, t, d), jnp.float32)
                    for i in range(3))
        for causal in (True, False):
            against_dense(
                f"t{t}_{'causal' if causal else 'full'}",
                lambda q, kk, v: fa.flash_attention(
                    q, kk, v, causal=causal, interpret=interpret),
                lambda q, kk, v: att.dense_attention(q, kk, v,
                                                     causal=causal),
                q, kk, v)
    # the first (aligned) shape again: bf16, a padding mask, a window
    b, h, t, d = k["flash"][0]
    q, kk, v = (jax.random.normal(keys[i], (b, h, t, d), jnp.float32)
                for i in range(3))
    o_b = jax.jit(lambda q, kk, v: fa.flash_attention(
        q, kk, v, causal=True, interpret=interpret))(
            *(x.astype(jnp.bfloat16) for x in (q, kk, v)))
    with jax.default_matmul_precision("highest"):
        o_d = att.dense_attention(
            *(x.astype(jnp.bfloat16).astype(jnp.float32)
              for x in (q, kk, v)), causal=True)
    record("flash_fwd_bf16_causal", o_b, o_d, 5e-2)
    mask = np.ones((b, t), bool)
    mask[0, int(t * 0.6):] = False      # a padded tail across block edges
    mask = jnp.asarray(mask)
    bias = jnp.where(mask[:, None, None, :], 0.0, -jnp.inf)
    against_dense(
        "kv_mask",
        lambda q, kk, v: fa.flash_attention(q, kk, v, kv_mask=mask,
                                            interpret=interpret),
        lambda q, kk, v: att.dense_attention(q, kk, v, bias=bias),
        q, kk, v)
    against_dense(
        f"window{k['window']}",
        lambda q, kk, v: fa.flash_attention(
            q, kk, v, causal=True, window=k["window"], interpret=interpret),
        lambda q, kk, v: att.dense_attention(q, kk, v, causal=True,
                                             window=k["window"]),
        q, kk, v)

    # fused head+CE: bf16 hidden states, f32 master head, an ignored band
    n, vocab = k["ce_tokens"], k["vocab"]
    for d_model in k["ce_d_model"]:
        x = jax.random.normal(keys[3], (n, d_model), jnp.bfloat16)
        w = jax.random.normal(keys[4], (d_model, vocab), jnp.float32) * 0.05
        lab = jax.random.randint(keys[5], (n,), 0, vocab).at[:10].set(-100)
        fused = jax.jit(jax.value_and_grad(
            lambda x, w: pallas_lm_cross_entropy(
                x, w, lab, ignore_index=-100, interpret=interpret)[0],
            argnums=(0, 1)))
        full = jax.jit(jax.value_and_grad(
            lambda x, w: softmax_cross_entropy(
                x.astype(jnp.float32) @ w, lab, ignore_index=-100)[0],
            argnums=(0, 1)))
        l_f, g_f = fused(x, w)
        with jax.default_matmul_precision("highest"):
            l_d, g_d = full(x, w)
        record(f"fused_ce_d{d_model}_fwd", jnp.asarray(l_f),
               jnp.asarray(l_d), 2e-2)
        record(f"fused_ce_d{d_model}_bwd_dx", g_f[0], g_d[0], 5e-2)
        record(f"fused_ce_d{d_model}_bwd_dw", g_f[1], g_d[1], 5e-2)

    # embedding gather fwd + scatter-add bwd: exact
    rows = k["gather_rows"]
    table = jax.random.normal(keys[6], (rows, k["gather_dim"]), jnp.float32)
    ids = jax.random.randint(keys[7], tuple(k["gather_ids"]), 0, rows)

    def lookup_loss(lookup):
        def f(tb):
            out = lookup(tb)
            return jnp.sum(out * jnp.sin(out)), out
        return jax.jit(jax.value_and_grad(f, has_aux=True))
    (_, o_g), g_g = lookup_loss(
        lambda tb: eg.gather_rows(tb, ids, interpret=interpret))(table)
    (_, o_t), g_t = lookup_loss(lambda tb: jnp.take(tb, ids, axis=0))(table)
    record("embed_gather_fwd", o_g, o_t, 0.0)
    record("embed_gather_bwd_scatter_add", g_g, g_t, 1e-6)

    # slot-decode attention: the kernel against the select write + a dense
    # softmax over the positions up to each slot's index; an inactive slot
    # (the last) rides untouched. The leaves must agree exactly.
    for slots, heads, group, d, max_len in k["decode_attn"]:
        cut = lambda i, *shape: jax.random.normal(  # noqa: E731
            keys[i], shape, jnp.bfloat16)
        q, k_new, v_new = (cut(0, slots, heads, group, d),
                           cut(1, slots, heads, d), cut(2, slots, heads, d))
        ck, cv = (cut(3, slots, heads, max_len, d),
                  cut(4, slots, heads, max_len, d))
        idx = (jnp.arange(slots, dtype=jnp.int32) * 131) % max_len
        active = jnp.arange(slots) < slots - 1
        hit = (jnp.arange(max_len)[None] == idx[:, None]) & active[:, None]
        hit = hit[:, None, :, None]
        want_k = jnp.where(hit, k_new[:, :, None], ck)
        want_v = jnp.where(hit, v_new[:, :, None], cv)
        bias = jnp.where(jnp.arange(max_len)[None] <= idx[:, None], 0.0,
                         -jnp.inf)[:, None, None]
        with jax.default_matmul_precision("highest"):
            p = jax.nn.softmax(jnp.einsum(
                "bkgd,bkld->bkgl", q.astype(jnp.float32),
                want_k.astype(jnp.float32)) * d ** -0.5 + bias, axis=-1)
            want = jnp.einsum("bkgl,bkld->bkgd", p,
                              want_v.astype(jnp.float32))
        out, got_k, got_v = jax.jit(da.decode_attention)(
            q, k_new, v_new, ck, cv, idx, active)
        tag = f"decode_attn_h{heads}g{group}_l{max_len}"
        record(f"{tag}_out", out[:-1], want[:-1], 2e-2)
        record(f"{tag}_key_leaf", got_k, want_k, 0.0)
        record(f"{tag}_value_leaf", got_v, want_v, 0.0)

    # chunked prefill == one-shot prefill, compiled: a windowed GQA stack,
    # so the rolling cache wraps mid-prompt
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, kv_heads=2, decode_len=32,
                             attn_window=8, attn_global_every=2)
    model = gpt.GPT(cfg)
    params = model.init(jax.random.PRNGKey(3),
                        jnp.zeros((1, 1), jnp.int32))["params"]
    prompt = jax.random.randint(keys[0], (2, 12), 0, cfg.vocab_size)
    one = gpt.generate(model, params, prompt, 6)
    chunked = gpt.generate(model, params, prompt, 6, prefill_chunk=5)
    checks["chunked_prefill_decode"] = {
        "ok": bool((np.asarray(one) == np.asarray(chunked)).all())}
    failed = sorted(n for n, c in checks.items() if not c["ok"])
    return {"ok": not failed, "failed": failed, "n_checks": len(checks),
            "interpret": interpret, "checks": checks}


def check_fence(spec):
    """Does ``jax.block_until_ready`` wait for the device? A chain of
    matmuls whose least possible time is known from the chip's published
    peak: a fence that returns before that has not waited. The host
    readback after it must then find the result already there."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtf_tpu.telemetry.accounting import device_peak_flops

    n, reps = spec["fence"]["n"], spec["fence"]["reps"]
    x = jnp.ones((n, n), jnp.bfloat16) / n

    @jax.jit
    def chain(x):
        return jax.lax.fori_loop(0, reps, lambda _, y: (y @ x), x)

    jax.block_until_ready(chain(x))               # compile, warm up
    np.asarray(chain(x)[0, 0])
    t0 = time.perf_counter()
    y = chain(x)
    t1 = time.perf_counter()
    jax.block_until_ready(y)
    t2 = time.perf_counter()
    np.asarray(y[0, 0])
    t3 = time.perf_counter()
    peak = device_peak_flops()
    least = 2.0 * n ** 3 * reps / peak if peak else 0.0
    return {"ok": (t2 - t0) >= least, "dispatch_s": round(t1 - t0, 6),
            "block_until_ready_s": round(t2 - t0, 6),
            "readback_after_s": round(t3 - t2, 6),
            "least_possible_s": round(least, 6)}


CHECKS = {"parity": check_parity, "kernels": check_kernels,
          "fence": check_fence}


def child_main(spec_path):
    with open(spec_path) as f:
        spec = json.load(f)
    sys.path.insert(0, ROOT)
    from dtf_tpu.cli.launch import device_report, init_backend

    init_backend(spec["backend"])
    device = device_report()["device"]
    ok = True
    for name in spec["phases"]:
        t0 = time.monotonic()
        result = CHECKS[name](spec)
        say(name, device=device, seconds=round(time.monotonic() - t0, 1),
            **result)
        ok &= result["ok"]
    print(json.dumps({"checks_device": device, "ok": ok}), flush=True)
    return 0 if ok else 1


# --------------------------------------------------------------------- main

def run(cfg, *, backend, chips, out_dir, **kw):
    """Every phase in turn; returns the device they all ran on. The big
    checkpoints are deleted on the way out, the logs stay."""
    shutil.rmtree(out_dir, ignore_errors=True)
    deadline = time.monotonic() + TIME_LIMIT_S
    try:
        if chips == 4:
            return run_four_chips(cfg, backend=backend, out_dir=out_dir,
                                  deadline=deadline, **kw)
        return run_one_chip(cfg, backend=backend, out_dir=out_dir,
                            deadline=deadline)
    finally:
        for name in os.listdir(out_dir) if os.path.isdir(out_dir) else ():
            if name != "logs":
                path = os.path.join(out_dir, name)
                (shutil.rmtree if os.path.isdir(path) else os.remove)(path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded train step and its "
                         "one-device comparison (a four-chip host)")
    ap.add_argument("--child", metavar="SPEC", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child:
        return child_main(args.child)
    t0 = time.monotonic()
    try:
        device = run(MEDIUM, backend="tpu", chips=args.chips,
                     out_dir=OUT_DIR)
    except PhaseFailed as e:
        print(json.dumps({"ok": False, "error": str(e)}))
        return 1
    say("total", seconds=round(time.monotonic() - t0, 1))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
