#!/usr/bin/env python
"""Single-chip benchmarks for BASELINE configs 4 and 5.

- **BERT-base MLM** (config 4): seq 512, gradient accumulation + ZeRO-1 —
  the exact machinery the config row names — measured as tokens/sec with
  MFU from BOTH the analytic 6N·tokens rule and XLA's own cost analysis.
- **Wide&Deep** (config 5): Criteo-shaped batch through the row-sharded
  embedding path, measured as examples/sec.
- **GPT-2 small** (the flagship, beyond the BASELINE list): seq 1024 causal
  LM with the first-party flash-attention kernel (checked on the chip by
  chip_smoke.py) — tokens/sec + MFU.

Same process contract as bench.py: parent never imports jax, children
run one at a time under the watchdog, artifact ``BENCH_LM.json`` always
gets written.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "BENCH_LM.json")
SENTINEL = "BENCH_LM_ROW "
# 1800 s cap: the child compiles TWICE (the jit itself + cost_analysis's
# lower().compile()). Actual per-job timeout = min(cap, budget left / jobs
# left).
CHILD_TIMEOUT_S = 1800
TOTAL_BUDGET_S = float(os.environ.get("DTF_LM_BUDGET_S", "5400"))


def _count_params(tree):
    import jax

    return sum(x.size for x in jax.tree.leaves(tree))


def child():
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import shard_batch
    from dtf_tpu.core.mesh import make_mesh
    from dtf_tpu.telemetry.accounting import device_peak_flops
    which = os.environ["DTF_LM_WHICH"]
    mesh = make_mesh()
    row = {"model": which, "backend": jax.default_backend(),
           "n_chips": mesh.devices.size}

    if which == "bert":
        from dtf_tpu.data.synthetic import SyntheticData
        from dtf_tpu.models import bert

        tiny = os.environ.get("DTF_LM_TINY") == "1"  # CPU-sim logic check
        batch = int(os.environ.get("DTF_LM_BATCH", "8" if tiny else "32"))
        seq = int(os.environ.get("DTF_LM_SEQ", "64" if tiny else "512"))
        accum = int(os.environ.get("DTF_LM_ACCUM", "2" if tiny else "4"))
        cfg = bert.BertConfig.tiny() if tiny else bert.BertConfig.base()
        attn = os.environ.get("DTF_LM_ATTN", "")
        if attn:  # grad-shard A/B pins dense (flash = shard_map kernel)
            import dataclasses

            cfg = dataclasses.replace(cfg, attn_impl=attn)
        model, init_fn = bert.make_init(cfg, None, seq_len=seq)
        tx = optax.adamw(1e-4, weight_decay=0.01)
        # config 4's machinery: ZeRO-1 + grad accum
        state, shardings = tr.create_train_state(
            init_fn, tx, jax.random.PRNGKey(0), mesh,
            param_rules=bert.tp_rules, zero1=True)
        lchunk = int(os.environ.get("DTF_LM_LOSS_CHUNK", "0"))
        lgather = int(os.environ.get("DTF_LM_MLM_GATHER", "0"))
        gshard = os.environ.get("DTF_LM_GRAD_SHARD") == "1"
        # record the EFFECTIVE setting: on a one-chip machine (data axis = 1)
        # make_train_step silently runs the replicated fallback, and a row
        # claiming grad_shard=true with identical timings would read as
        # "the sharded accumulator is perf-neutral".
        data_size = dict(mesh.shape).get("data", 1)
        loss_fn = bert.make_loss(model, loss_chunk=lchunk,
                                 mlm_gather=lgather)
        step = tr.make_train_step(loss_fn, tx, mesh, shardings,
                                  grad_accum=accum, grad_shard=gshard,
                                  log_grad_norm=False)
        data = shard_batch(
            SyntheticData("bert", batch, seed=0, seq_len=seq,
                          vocab_size=cfg.vocab_size).batch(0), mesh)
        n_params = _count_params(state.params)
        row.update(batch=batch, seq=seq, grad_accum=accum,
                   n_params=int(n_params), zero1=True, loss_chunk=lchunk,
                   mlm_gather=lgather, mesh_data=data_size,
                   grad_shard=gshard and data_size > 1 and accum > 1,
                   grad_shard_requested=gshard,
                   attn=attn or "auto")
        unit_scale = batch * seq  # tokens per step
    elif which == "gpt":
        from dtf_tpu.data.synthetic import SyntheticData
        from dtf_tpu.models import gpt

        tiny = os.environ.get("DTF_LM_TINY") == "1"  # CPU-sim logic check
        batch = int(os.environ.get("DTF_LM_BATCH", "8"))
        seq = int(os.environ.get("DTF_LM_SEQ", "64" if tiny else "1024"))
        import dataclasses

        size = os.environ.get("DTF_LM_GPT_SIZE", "small")
        cfg = gpt.GPTConfig.tiny() if tiny else gpt.GPTConfig.by_name(size)
        fbh = int(os.environ.get("DTF_LM_FLASH_BH", "0"))
        if fbh:  # flash head-fold knob (must divide heads; sweep-only)
            cfg = dataclasses.replace(cfg, flash_block_h=fbh)
        # Megatron TP A/B (the --tp_overlap pair): a model axis plus the
        # collective-matmul toggle. On a one-chip machine mesh_model>1 fails
        # fast -> a structured error row; the pair banks automatically the
        # first time a multi-chip pool answers.
        tp = int(os.environ.get("DTF_LM_MESH_MODEL", "1"))
        overlap = os.environ.get("DTF_LM_TP_OVERLAP") == "1"
        if tp > 1:
            from dtf_tpu.core.mesh import MeshConfig

            mesh = make_mesh(MeshConfig(model=tp))
            row["n_chips"] = mesh.devices.size
        if overlap:
            cfg = dataclasses.replace(cfg, tp_overlap=True)
        attn = os.environ.get("DTF_LM_ATTN", "")
        if attn:  # grad-shard A/B pins dense (flash = shard_map kernel)
            cfg = dataclasses.replace(cfg, attn_impl=attn)
        model, init_fn = gpt.make_init(cfg, mesh, seq_len=seq)
        tx = optax.adamw(1e-4, weight_decay=0.01)
        state, shardings = tr.create_train_state(
            init_fn, tx, jax.random.PRNGKey(0), mesh,
            param_rules=gpt.tp_rules, zero1=True)
        lchunk = int(os.environ.get("DTF_LM_LOSS_CHUNK", "0"))
        tchunk = int(os.environ.get("DTF_LM_LOSS_CHUNK_T", "0"))
        lpallas = os.environ.get("DTF_LM_LOSS_PALLAS") == "1"
        accum = int(os.environ.get("DTF_LM_ACCUM", "1"))
        gshard = os.environ.get("DTF_LM_GRAD_SHARD") == "1"
        # effective setting, not the request (see the bert branch note)
        data_size = dict(mesh.shape).get("data", 1)
        loss_fn = gpt.make_loss(model, loss_chunk=lchunk,
                                loss_chunk_tokens=tchunk,
                                loss_pallas=lpallas)
        step = tr.make_train_step(loss_fn, tx, mesh, shardings,
                                  grad_accum=accum, grad_shard=gshard,
                                  log_grad_norm=False)
        data = shard_batch(
            SyntheticData("gpt", batch, seed=0, seq_len=seq,
                          vocab_size=cfg.vocab_size).batch(0), mesh)
        row.update(batch=batch, seq=seq, attn=attn or "flash(auto)",
                   gpt_size="tiny" if tiny else size,
                   n_params=int(_count_params(state.params)), zero1=True,
                   loss_chunk=lchunk, loss_chunk_tokens=tchunk,
                   loss_pallas=lpallas, mesh_model=tp, tp_overlap=overlap,
                   grad_accum=accum, mesh_data=data_size,
                   grad_shard=gshard and data_size > 1 and accum > 1,
                   grad_shard_requested=gshard)
        unit_scale = batch * seq
    elif which == "gpt_pipe":
        # the ISSUE 18 A/B pair: fused-1F1B vs zero-bubble on the same
        # data x pipe mesh, same model, same microbatch count — tokens/sec
        # is the schedule delta (grads are BITWISE equal by construction,
        # tests/test_pipeline.py). Needs >= pipe chips; a one-chip machine
        # records a structured mesh error row instead (tp-overlap idiom).
        import dataclasses

        from dtf_tpu.core.mesh import MeshConfig
        from dtf_tpu.data.synthetic import SyntheticData
        from dtf_tpu.models import gpt, gpt_pipe

        tiny = os.environ.get("DTF_LM_TINY") == "1"  # CPU-sim logic check
        batch = int(os.environ.get("DTF_LM_BATCH", "8"))
        seq = int(os.environ.get("DTF_LM_SEQ", "64" if tiny else "1024"))
        pipe = int(os.environ.get("DTF_LM_MESH_PIPE", "2"))
        n_micro = int(os.environ.get("DTF_LM_MICRO", "4"))
        sched = os.environ.get("DTF_LM_PIPE_SCHED", "1f1b")
        size = os.environ.get("DTF_LM_GPT_SIZE", "small")
        cfg = gpt.GPTConfig.tiny() if tiny else gpt.GPTConfig.by_name(size)
        if tiny:
            cfg = dataclasses.replace(cfg, layers=max(cfg.layers, pipe))
        mesh = make_mesh(MeshConfig(pipe=pipe))
        row["n_chips"] = mesh.devices.size
        init_fn = gpt_pipe.make_pipe_init(cfg, mesh, seq_len=seq)
        tx = optax.adamw(1e-4, weight_decay=0.01)
        state, shardings = tr.create_train_state(
            init_fn, tx, jax.random.PRNGKey(0), mesh,
            param_rules=gpt_pipe.pipe_rules())
        maker = {"1f1b": gpt_pipe.make_pipe_grads_1f1b,
                 "zb": gpt_pipe.make_pipe_grads_zb}[sched]
        grads_fn = maker(cfg, mesh, n_microbatches=n_micro)
        step = tr.make_train_step_from_grads(grads_fn, tx, mesh, shardings,
                                             log_grad_norm=False)
        data = shard_batch(
            SyntheticData("gpt", batch, seed=0, seq_len=seq,
                          vocab_size=cfg.vocab_size).batch(0), mesh)
        row.update(batch=batch, seq=seq, gpt_size="tiny" if tiny else size,
                   n_params=int(_count_params(state.params)),
                   mesh_pipe=pipe, n_microbatches=n_micro,
                   pipe_schedule=sched)
        unit_scale = batch * seq
    else:
        from dtf_tpu.models import widedeep

        batch = int(os.environ.get("DTF_LM_BATCH", "8192"))
        model = widedeep.WideDeep(hash_buckets=100000)
        tx = optax.adagrad(0.01)
        state, shardings = tr.create_train_state(
            widedeep.make_init(model), tx, jax.random.PRNGKey(0), mesh,
            param_rules=widedeep.rules)
        loss_fn = widedeep.make_loss(model)
        step = tr.make_train_step(loss_fn, tx, mesh,
                                  shardings, log_grad_norm=False)
        rng = np.random.default_rng(0)
        data = shard_batch(
            {"dense": rng.random((batch, 13), np.float32),
             "sparse": rng.integers(0, 100000, (batch, 26)).astype(np.int32),
             "label": rng.integers(0, 2, (batch,)).astype(np.float32)}, mesh)
        row.update(batch=batch, hash_buckets=100000,
                   n_params=int(_count_params(state.params)))
        unit_scale = batch  # examples per step

    # Phase decomposition for MFU attribution (PERF.md §5): time the
    # forward alone / forward+backward alone instead of the full step, so
    # a low measured MFU can be pinned to fwd math, bwd math, or the
    # optimizer+update tail by subtraction across three child runs.
    phase = os.environ.get("DTF_LM_PHASE", "step")
    if phase in ("fwd", "fwdbwd"):
        import jax.numpy as jnp

        rng0 = jax.random.PRNGKey(0)
        if phase == "fwd":
            timed = jax.jit(
                lambda s, b: loss_fn(s.params, s.extra, b, rng0)[0])
        else:
            def fwdbwd(s, b):
                (loss, _), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, s.extra, b, rng0),
                    has_aux=True)(s.params)
                # grads must feed the output or XLA dead-code-eliminates
                # the entire backward; 1e-30 keeps them live at zero
                # numeric effect (same trick as bench_attention's scan)
                gsum = sum(jnp.sum(jnp.abs(g).astype(jnp.float32))
                           for g in jax.tree.leaves(grads))
                return loss + 1e-30 * gsum

            timed = jax.jit(fwdbwd)
        row["phase"] = phase

        def run():
            return timed(state, data)
    else:

        def run():
            nonlocal state
            state, metrics = step(state, data)
            return metrics["loss"]

    # XLA's own cost for whatever is being timed (step or phase graph);
    # MFU fields divide these flops by the measured time, so they must
    # describe the SAME computation the timing loop runs.
    try:
        # MFU cost analysis of the very program the timing loop runs
        # aot-ok: (bench-local, no registration surface)
        lowered = (timed.lower(state, data) if phase != "step"
                   else step.lower(state, data))  # aot-ok: second leg
        cost = lowered.compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        row["xla_flops_per_step"] = float(cost.get("flops", 0.0))
        row["xla_bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
    except Exception as e:
        row["cost_error"] = repr(e)[:300]

    for _ in range(3):
        out = run()
    float(out)
    n_steps = int(os.environ.get("DTF_LM_STEPS", "10"))
    t0 = time.perf_counter()
    for _ in range(n_steps):
        out = run()
    float(out)  # device executes the queue serially; one readback fences
    dt = time.perf_counter() - t0

    per_sec = unit_scale * n_steps / dt
    row["sec_per_step"] = round(dt / n_steps, 5)
    # the running chip's published peak; a CPU row names no mfu
    peak = device_peak_flops()
    if which in ("bert", "gpt", "gpt_pipe"):
        row["tokens_per_sec"] = round(per_sec, 1)
        if phase == "step":
            # analytic: 6 FLOPs per param per token (fwd+bwd, weight
            # FLOPs) + attention 12*L*d*s per token — a FULL-step flop
            # model, so only the full-step timing may be divided by it
            layers = cfg.layers
            width = cfg.hidden if which == "bert" else cfg.d_model
            att = 12 * layers * width * row["seq"]
            flops_tok = 6 * row["n_params"] + att
            if peak:
                row["mfu_analytic"] = round(per_sec * flops_tok / peak, 4)
    else:
        row["examples_per_sec"] = round(per_sec, 1)
    if peak and "xla_flops_per_step" in row:
        # LOWER BOUND, not the headline: XLA's cost_analysis counts a
        # lax.scan body ONCE (so grad-accum microbatches are under-counted
        # by the accum factor — BERT's 0.10 vs 0.43 analytic) and Pallas
        # custom calls report zero flops (so GPT's flash attention is
        # excluded). mfu_analytic is the comparable convention.
        row["mfu_xla"] = round(
            row["xla_flops_per_step"] * n_steps / dt / peak, 4)
    print(SENTINEL + json.dumps(row))


def _write_merged(artifact, rows, errors):
    """Replace ONLY our keys; other sections of a shared artifact (e.g.
    bench_decode.py's "decode" in BENCH_LM.json) must survive a re-run."""
    data = {}
    try:
        with open(artifact) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        data = {}
    data["rows"] = rows
    data["errors"] = errors
    with open(artifact, "w") as f:
        json.dump(data, f, indent=1)


def main():
    from _dtf_watchdog import Budget, child_argv, run_budgeted_jobs

    artifact = ARTIFACT
    if "--sweep-gpt" in sys.argv:
        # MFU search on the flagship: batch is the main lever on a single
        # chip (seq is fixed by the config), and the vocab-chunked loss is
        # what makes batch >= 32 fit (full [B,T,50k] f32 logits + their
        # cotangent would exceed HBM). Results land in a separate
        # artifact; the best combo becomes the BENCH_LM default.
        # Ordered by information value: a window that dies mid-sweep (both
        # round-5 windows did die) should have already banked the rows
        # that answer open questions. First the round-4 sweep's open
        # questions + the new levers' flagship points, then the medium
        # config, then the completion rows.
        G = "gpt"
        jobs = [
            # same-window control (58.0% banked on 512x512-block flash;
            # this re-measures it on the 512x1024 default)
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "8"},
            # does unchunked batch 16 fit HBM (~6.6 GB logits+cotangent)
            # and beat 58%? (chunking cost ~9 points at batch 8)
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "16"},
            # the two new fused losses at the flagship point
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "8",
             "DTF_LM_LOSS_PALLAS": "1"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "8",
             "DTF_LM_LOSS_CHUNK_T": "4096"},
            # GPT-2 medium (355M): wider matmuls fill the MXU better —
            # the config most likely to clear the 60% MFU north star
            {"DTF_LM_WHICH": G, "DTF_LM_GPT_SIZE": "medium",
             "DTF_LM_BATCH": "4"},
            {"DTF_LM_WHICH": G, "DTF_LM_GPT_SIZE": "medium",
             "DTF_LM_BATCH": "8", "DTF_LM_LOSS_CHUNK_T": "4096"},
            # batch scaling under each bounded-memory loss
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "16",
             "DTF_LM_LOSS_PALLAS": "1"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "16",
             "DTF_LM_LOSS_CHUNK_T": "4096"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "32",
             "DTF_LM_LOSS_PALLAS": "1"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "32",
             "DTF_LM_LOSS_CHUNK_T": "4096"},
            # vocab-chunked completion rows (the round-4 plan's ladder)
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "8",
             "DTF_LM_LOSS_CHUNK": "8192"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "16",
             "DTF_LM_LOSS_CHUNK": "8192"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "32",
             "DTF_LM_LOSS_CHUNK": "8192"},
            {"DTF_LM_WHICH": G, "DTF_LM_BATCH": "64",
             "DTF_LM_LOSS_CHUNK": "8192"},
        ]
        artifact = os.path.join(ROOT, "BENCH_LM_SWEEP.json")
    elif "--sweep-bert" in sys.argv:
        # config-4 MFU levers: chunked loss, masked-position gather
        # (~77 masked avg at 15% of seq 512; 96 covers nearly all rows),
        # and the larger batch they unlock.
        jobs = [
            {"DTF_LM_WHICH": "bert"},
            {"DTF_LM_WHICH": "bert", "DTF_LM_LOSS_CHUNK": "8192"},
            {"DTF_LM_WHICH": "bert", "DTF_LM_LOSS_CHUNK": "8192",
             "DTF_LM_MLM_GATHER": "96"},
            {"DTF_LM_WHICH": "bert", "DTF_LM_BATCH": "64",
             "DTF_LM_LOSS_CHUNK": "8192", "DTF_LM_MLM_GATHER": "96"},
            # gather WITHOUT chunking, added after the first on-chip sweep:
            # chunking alone cost ~5 MFU points (44.8% -> 39.3%) while the
            # gather won ~9 on top — the gathered head is only [B,96,V],
            # small enough to skip chunking entirely.
            {"DTF_LM_WHICH": "bert", "DTF_LM_MLM_GATHER": "96"},
        ]
        artifact = os.path.join(ROOT, "BENCH_LM_SWEEP_BERT.json")
    elif "--sweep-tp-overlap" in sys.argv:
        # the Megatron TP A/B pair (ISSUE 2): identical config, collective
        # matmul off/on — the on-chip number that decides whether the
        # ppermute rings hide ICI time behind MXU time. Needs >= 2 chips;
        # a one-chip machine records a structured mesh error instead.
        G = "gpt"
        jobs = [
            {"DTF_LM_WHICH": G, "DTF_LM_MESH_MODEL": "2"},
            {"DTF_LM_WHICH": G, "DTF_LM_MESH_MODEL": "2",
             "DTF_LM_TP_OVERLAP": "1"},
            # medium at TP2: wider matmuls give the rings more MXU time
            # to hide behind — the shape the overlap should win on
            {"DTF_LM_WHICH": G, "DTF_LM_GPT_SIZE": "medium",
             "DTF_LM_MESH_MODEL": "2"},
            {"DTF_LM_WHICH": G, "DTF_LM_GPT_SIZE": "medium",
             "DTF_LM_MESH_MODEL": "2", "DTF_LM_TP_OVERLAP": "1"},
        ]
        artifact = os.path.join(ROOT, "BENCH_LM_TP_OVERLAP.json")
    elif "--sweep-grad-shard" in sys.argv:
        # ISSUE 3 A/B: sharded vs replicated grad accumulator at identical
        # configs — BERT-base accum4 (the BASELINE config-4 machinery) and
        # GPT-2-small accum4. Both sides pin DENSE attention: flash is a
        # shard_map kernel the per-shard-group vmap cannot nest
        # (docs/ZERO.md), and an A/B must not conflate the attention
        # backend with the grad-path delta. On a one-chip machine (data=1)
        # the sharded rows record the documented replicated fallback; the
        # pair banks its real delta the first time a multi-chip pool
        # answers.
        jobs = [
            {"DTF_LM_WHICH": "bert", "DTF_LM_ATTN": "dense"},
            {"DTF_LM_WHICH": "bert", "DTF_LM_ATTN": "dense",
             "DTF_LM_GRAD_SHARD": "1"},
            {"DTF_LM_WHICH": "gpt", "DTF_LM_ATTN": "dense",
             "DTF_LM_ACCUM": "4"},
            {"DTF_LM_WHICH": "gpt", "DTF_LM_ATTN": "dense",
             "DTF_LM_ACCUM": "4", "DTF_LM_GRAD_SHARD": "1"},
        ]
        artifact = os.path.join(ROOT, "BENCH_LM_GRAD_SHARD.json")
    elif "--sweep-pipe" in sys.argv:
        # the zero-bubble A/B pair (ISSUE 18): fused-1F1B vs ZB at the
        # SAME mesh/model/microbatch count, m4 and m8 — the on-chip number
        # that says how much of the modeled bubble shrink
        # (PIPE_MEM.json bubble_model) survives real overlap. Needs >= 2
        # chips; a one-chip machine records a structured mesh error instead.
        G = "gpt_pipe"
        jobs = [
            {"DTF_LM_WHICH": G, "DTF_LM_PIPE_SCHED": "1f1b"},
            {"DTF_LM_WHICH": G, "DTF_LM_PIPE_SCHED": "zb"},
            {"DTF_LM_WHICH": G, "DTF_LM_PIPE_SCHED": "1f1b",
             "DTF_LM_MICRO": "8"},
            {"DTF_LM_WHICH": G, "DTF_LM_PIPE_SCHED": "zb",
             "DTF_LM_MICRO": "8"},
        ]
        artifact = os.path.join(ROOT, "BENCH_LM_PIPE.json")
    elif "--phases-gpt" in sys.argv:
        # fwd / fwd+bwd / full-step decomposition: pins a low MFU on fwd
        # math, bwd math, or the optimizer tail by subtraction.
        jobs = [{"DTF_LM_WHICH": "gpt", "DTF_LM_PHASE": p}
                for p in ("fwd", "fwdbwd", "step")]
        artifact = os.path.join(ROOT, "BENCH_LM_PHASES.json")
    else:
        jobs = [{"DTF_LM_WHICH": "bert"}, {"DTF_LM_WHICH": "widedeep"},
                {"DTF_LM_WHICH": "gpt"}]
    budget = Budget(TOTAL_BUDGET_S)

    def on_result(row, job, rows, errors):
        _write_merged(artifact, rows, errors)
        print(json.dumps(row if row is not None else errors[-1]))

    rows, errors = run_budgeted_jobs(
        jobs, child_argv(os.path.abspath(__file__)),
        lambda line: (json.loads(line[len(SENTINEL):])
                      if line.startswith(SENTINEL) else None),
        budget=budget, cap_s=CHILD_TIMEOUT_S, env_base=dict(os.environ),
        on_result=on_result)
    return 0 if rows and not errors else 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(main())
