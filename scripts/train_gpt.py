#!/usr/bin/env python
"""GPT causal-LM pretraining — the long-context flagship workload.

    python scripts/train_gpt.py --seq_len=2048 --mesh_seq=4 --grad_accum=2
    python scripts/train_gpt.py --size=tiny --moe_every=2 --mesh_expert=4

Every parallelism axis is flag-driven: dp over `data` (+ ZeRO-1), TP over
`model` (Megatron rules), ring attention over `seq` for long context,
Switch-MoE expert parallelism over `expert`; `--remat` trades FLOPs for HBM
on long sequences. Flash attention (fused Pallas kernel) is the single-chip
default on TPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from absl import app, flags, logging as absl_logging

from dtf_tpu.cli import flags as dflags

dflags.define_cluster_flags()
dflags.define_mesh_flags()
dflags.define_train_flags(batch_size=32, learning_rate=3e-4, train_steps=200,
                          lr_schedule="cosine")
flags.DEFINE_integer("seq_len", 512, "sequence length")
flags.DEFINE_string("size", "small", "small (gpt2-124M) | medium "
                    "(gpt2-355M) | tiny")
flags.DEFINE_boolean("zero1", True, "shard optimizer state over data axis")
flags.DEFINE_integer("moe_every", 0, "every k-th block uses Switch-MoE "
                     "(0 = dense)")
flags.DEFINE_integer("moe_top_k", 1, "experts per token: 1 = Switch, "
                     "2 = GShard top-2 (normalized gates)")
flags.DEFINE_boolean("remat", False, "jax.checkpoint each block")
flags.DEFINE_integer("kv_heads", 0, "grouped-query attention: shared K/V "
                     "heads (0 = plain MHA; must divide heads)")
flags.DEFINE_integer("attn_window", 0, "sliding-window attention: each "
                     "query sees the last N keys (0 = full causal). With "
                     "mesh_seq>1 this routes to halo attention (one "
                     "neighbor-tail ppermute); zigzag rejects windows")
flags.DEFINE_integer("attn_global_every", 0, "with attn_window: every "
                     "k-th layer uses full causal attention (alternating "
                     "local/global; 0 = all layers windowed)")
flags.DEFINE_string("attn_impl", "auto", "auto | dense | flash | ring | "
                    "zigzag (load-balanced causal ring; needs mesh_seq>1)")
flags.DEFINE_boolean("tp_overlap", False, "latency-hiding collective "
                     "matmul for the Megatron TP projections: decompose "
                     "the blocking all-gather/reduce-scatter around each "
                     "sharded einsum into a ppermute ring overlapped with "
                     "per-chunk matmuls (needs --mesh_model>1; "
                     "docs/OVERLAP.md)")
flags.DEFINE_enum("matmul_precision", "", ["", "auto", "bf16", "int8",
                                           "fp8"],
                  "low-precision compute for the Megatron TP projections: "
                  "'' = bf16 (no tuner), auto = the banked kernel-tune "
                  "winner per projection site, int8/fp8 = explicit pin "
                  "(wins over a measured winner with one WARN). Forward "
                  "only — gradients and master weights stay full "
                  "precision; with --tp_overlap the ring payload is what "
                  "quantizes (docs/TUNING.md)")
flags.DEFINE_integer("pipe_microbatches", 0, "pipeline microbatches when "
                     "mesh_pipe>1 (0 = 4x stages, the bubble-amortizing "
                     "default)")
flags.DEFINE_integer("pipe_interleave", 1, "model chunks per pipe device "
                     "(Megatron interleaved schedule when >1)")
flags.DEFINE_enum("pipe_schedule", "gpipe", ["gpipe", "1f1b", "zb"],
                  "pipeline schedule: gpipe (autodiff through the scan; "
                  "O(M) activation stash, shrink it with --remat), 1f1b "
                  "(fused forward/backward rounds; O(stages) stash, remat "
                  "built in — for depth-sharded models that exceed HBM "
                  "under gpipe), or zb (zero-bubble: 1f1b with the "
                  "backward split into B/W, weight-grads deferred into "
                  "the drain bubble — same numbers, less idle on the "
                  "MPMD executor; docs/PIPELINE.md)")
flags.DEFINE_integer("loss_chunk_vocab", 0, "compute the LM loss fused "
                     "with the lm_head in vocab chunks of this width "
                     "(0 = full logits). Removes the O(batch*seq*vocab) "
                     "logits memory — the single-chip batch ceiling. "
                     "Not with --mesh_model (TP shards the vocab dim) or "
                     "--mesh_pipe")
flags.DEFINE_integer("loss_chunk_tokens", 0, "fused LM loss chunking "
                     "TOKENS instead of vocab columns: O(chunk*vocab) "
                     "live logits, one full-vocab matmul per block — "
                     "the faster chunking axis on chip (PERF.md §5). "
                     "Mutually exclusive with --loss_chunk_vocab; same "
                     "--mesh_model/--mesh_pipe restrictions")
flags.DEFINE_boolean("loss_pallas", False, "Pallas fused head+CE kernel: "
                     "logits never leave VMEM (dtf_tpu/ops/fused_ce.py). "
                     "Mutually exclusive with the chunked-loss flags; "
                     "same --mesh_model/--mesh_pipe restrictions")
flags.DEFINE_integer("eval_every", 0, "held-out eval (val.bin or held-out "
                     "synthetic) every N steps; 0 = final eval only. On the "
                     "pipelined path the eval step runs un-pipelined "
                     "against the same stacked params.")
flags.DEFINE_string("publish_dir", "", "weight hot-swap publishing "
                    "(ISSUE 14): every --publish_every steps, emit a "
                    "params-only snapshot as the next monotone VERSION "
                    "into this dir (atomic manifest + content digest); "
                    "serve_gpt --publish_dir/--swap_poll_ticks rolls "
                    "new versions across a live fleet with zero "
                    "downtime (docs/RESILIENCE.md §9)")
flags.DEFINE_integer("publish_every", 100, "with --publish_dir: publish "
                     "a version every N steps (plus once at end of run)")
flags.DEFINE_string("event_log_dir", "", "fleet EVENT PLANE (ISSUE 20): "
                    "chief-side lifecycle events (checkpoint saves, "
                    "degraded restores, published versions, stream "
                    "reweights/faults) append to CRC-framed shards under "
                    "this dir; `python -m dtf_tpu.telemetry timeline` "
                    "merges them with the serve/fault trails into one "
                    "run story (docs/OBSERVABILITY.md §9)")
flags.DEFINE_string("stream_spec", "", "streaming data tier (ISSUE 15, "
                    "docs/DATA.md): a JSON mixture spec (inline or a "
                    ".json path) of weighted token sources — "
                    "'{\"sources\": [{\"name\": ..., \"path\": ..., "
                    "\"weight\": ...}, ...]}'. The spec is recorded in "
                    "the model-config manifest and its per-source "
                    "cursors ride every checkpoint as a 'stream' item, "
                    "so a killed run resumes the EXACT batch sequence "
                    "and a resumed run cannot silently change its "
                    "mixture. Empty: the plain --data_dir/synthetic "
                    "path")
flags.DEFINE_integer("distill_draft", 0, "acceptance-driven draft "
                     "refresh (ISSUE 19): train an N-layer EARLY-EXIT "
                     "draft of the served checkpoint named by "
                     "--distill_from, initialized from its first N "
                     "blocks (gpt.draft_truncate) — the served model "
                     "itself is never touched. Point --stream_spec at a "
                     "'servelog' source (serve_gpt --log_sink_dir's "
                     "shards) to distill on live traffic, and "
                     "--publish_dir at the dir a fleet polls via "
                     "serve_gpt --draft_publish_dir for draft-only "
                     "rolling swaps (docs/SERVING.md). 0 = off")
flags.DEFINE_string("distill_from", "", "with --distill_draft: logdir of "
                    "the SERVED checkpoint whose manifest fixes the "
                    "architecture and whose params seed the draft "
                    "(--size and the architecture flags are ignored — "
                    "a draft that drifts from the verifier's widths "
                    "could not swap in)")
FLAGS = flags.FLAGS


def main(argv):
    del argv
    import jax
    import optax
    from jax.sharding import PartitionSpec as P

    from dtf_tpu.checkpoint import Checkpointer
    from dtf_tpu.cli.launch import (emit_run_report, lm_eval_hook,
                                    profiler_hooks, setup,
                                    telemetry_from_flags)
    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import batch_shardings_for, shard_batch
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.hooks import (CheckpointHook, LoggingHook,
                               PreemptionHook, StopAtStepHook)
    from dtf_tpu.loop import Trainer
    from dtf_tpu.metrics import MetricWriter
    from dtf_tpu.models import gpt

    mesh, info = setup(FLAGS)
    sp = mesh.shape.get("seq", 1) > 1
    tel = telemetry_from_flags(FLAGS, info)

    try:
        base = gpt.GPTConfig.by_name(FLAGS.size)
    except KeyError as e:
        raise app.UsageError(f"--size: {e.args[0]}")
    import dataclasses

    if FLAGS.tp_overlap and mesh.shape.get("model", 1) <= 1:
        absl_logging.warning(
            "--tp_overlap has no effect without --mesh_model>1 (no TP "
            "collectives to hide); proceeding on the plain path")
    if FLAGS.tp_overlap and mesh.shape.get("pipe", 1) > 1:
        raise app.UsageError(
            "--tp_overlap is not supported with --mesh_pipe: pipeline "
            "stages run mesh-less (gpt_pipe) or with their own manual TP "
            "(gpt_pipe_tp), so the flag would be silently dropped")
    cfg = dataclasses.replace(base, moe_every=FLAGS.moe_every,
                              remat=FLAGS.remat, attn_impl=FLAGS.attn_impl,
                              kv_heads=FLAGS.kv_heads or None,
                              attn_window=FLAGS.attn_window,
                              attn_global_every=FLAGS.attn_global_every,
                              tp_overlap=FLAGS.tp_overlap,
                              matmul_precision=FLAGS.matmul_precision,
                              moe=dataclasses.replace(
                                  base.moe, top_k=FLAGS.moe_top_k))
    # acceptance-driven draft refresh (ISSUE 19): the architecture comes
    # from the SERVED manifest truncated to --distill_draft layers — a
    # draft that drifted from the verifier's widths could not swap in —
    # and the params seed from its first blocks (the base checkpoint is
    # read-only here; only the student trains)
    bman = distill_params = None
    if FLAGS.distill_draft:
        if not FLAGS.distill_from:
            raise app.UsageError(
                "--distill_draft needs --distill_from=<served logdir> "
                "(the checkpoint whose first layers seed the draft)")
        if mesh.shape.get("pipe", 1) > 1:
            raise app.UsageError(
                "--distill_draft does not compose with --mesh_pipe: the "
                "draft is at most served-depth minus one layer — "
                "depth-sharding it buys nothing")
        from dtf_tpu.checkpoint import load_model_config as _load_mc

        bdir = os.path.join(FLAGS.distill_from, "ckpt")
        bman = _load_mc(bdir)
        if bman is None:
            raise app.UsageError(
                f"--distill_from={FLAGS.distill_from} has no "
                "model_config.json manifest; the served architecture "
                "cannot be guessed")
        try:
            bbase = gpt.GPTConfig.by_name(bman.get("size", "small"))
        except KeyError as e:
            raise app.UsageError(
                f"--distill_from manifest size: {e.args[0]}")
        bcfg = dataclasses.replace(
            bbase, kv_heads=bman.get("kv_heads") or None,
            attn_window=int(bman.get("attn_window", 0) or 0),
            attn_global_every=int(bman.get("attn_global_every", 0) or 0))
        bck = Checkpointer(bdir)
        if bck.latest_step() is None:
            raise app.UsageError(f"no checkpoint under {bdir}")
        bparams = bck.restore_params()
        bck.close()
        try:
            cfg, distill_params = gpt.draft_truncate(
                bcfg, bparams, FLAGS.distill_draft)
        except ValueError as e:
            raise app.UsageError(str(e))
        cfg = dataclasses.replace(cfg, remat=FLAGS.remat,
                                  attn_impl=FLAGS.attn_impl)
        absl_logging.info(
            "distilling a %d-layer draft of %s (size %s, served step %d)",
            FLAGS.distill_draft, FLAGS.distill_from,
            bman.get("size", "?"), bck.last_restored_step)
    sched = dflags.make_lr_schedule(FLAGS)   # LoggingHook surfaces the LR
    tx = dflags.make_optimizer(
        FLAGS, lambda s: optax.adamw(s, weight_decay=(
            FLAGS.weight_decay if FLAGS.weight_decay >= 0 else 0.1)),
        recipe_uses_wd=True)
    if sum(map(bool, (FLAGS.loss_chunk_vocab, FLAGS.loss_chunk_tokens,
                      FLAGS.loss_pallas))) > 1:
        raise app.UsageError(
            "--loss_chunk_vocab, --loss_chunk_tokens and --loss_pallas "
            "are mutually exclusive — pick one fused-loss strategy")
    pipelined = mesh.shape.get("pipe", 1) > 1
    grads_fn = None   # set by --pipe_schedule=1f1b/zb (fused fwd/bwd path)
    if pipelined:
        from dtf_tpu.models import gpt_pipe

        if (FLAGS.loss_chunk_vocab or FLAGS.loss_chunk_tokens
                or FLAGS.loss_pallas):
            raise app.UsageError(
                "--loss_chunk_vocab/--loss_chunk_tokens/--loss_pallas are "
                "not supported with --mesh_pipe (the pipelined loss owns "
                "its head application); use them on the non-pipelined path")
        tp_in_pipe = mesh.shape.get("model", 1) > 1
        if sp and tp_in_pipe:
            raise app.UsageError(
                "--mesh_pipe>1 with BOTH --mesh_seq>1 and --mesh_model>1 "
                "is not supported; PP x SP runs ring/halo attention inside "
                "the stages, PP x TP runs Megatron splits — pick one")
        if sp and FLAGS.attn_impl == "zigzag":
            raise app.UsageError(
                "--attn_impl=zigzag cannot combine with --mesh_pipe>1; "
                "PP x SP uses the plain ring (auto)")
        # microbatch rule: n_micro | batch and (batch/n_micro) % data == 0;
        # the interleaved schedule additionally needs n_micro % pipe == 0.
        # Default: the largest feasible count <= 4x stages (amortizes the
        # (S-1)/(M+S-1) bubble without starving the data shards).
        per_data = FLAGS.batch_size // mesh.shape.get("data", 1)
        n_micro = FLAGS.pipe_microbatches
        if not n_micro:
            pipe_n = mesh.shape["pipe"]
            cands = [n for n in range(1, 4 * pipe_n + 1)
                     if per_data % n == 0
                     and (FLAGS.pipe_interleave == 1 or n % pipe_n == 0)]
            if not cands:
                raise app.UsageError(
                    f"no feasible pipeline microbatch count for batch "
                    f"{FLAGS.batch_size} / data={mesh.shape.get('data', 1)} "
                    f"/ pipe={pipe_n} / interleave={FLAGS.pipe_interleave}; "
                    "adjust --batch_size or set --pipe_microbatches")
            n_micro = max(cands)
            absl_logging.info("pipeline: using %d microbatches", n_micro)
        n_stages = mesh.shape["pipe"]
        if FLAGS.pipe_schedule in ("1f1b", "zb"):
            if FLAGS.pipe_interleave != 1 or tp_in_pipe:
                raise app.UsageError(
                    f"--pipe_schedule={FLAGS.pipe_schedule} supports "
                    "neither --pipe_interleave>1 nor --mesh_model>1; it "
                    "composes with data and seq sharding")
            if FLAGS.grad_accum != 1:
                raise app.UsageError(
                    "--grad_accum>1 is redundant with "
                    f"--pipe_schedule={FLAGS.pipe_schedule} (microbatch "
                    "accumulation is the schedule); raise "
                    "--pipe_microbatches instead")
        if tp_in_pipe:
            from dtf_tpu.models import gpt_pipe_tp

            if FLAGS.pipe_interleave != 1:
                raise app.UsageError(
                    "--pipe_interleave>1 is not supported with TP-in-pipe "
                    "(--mesh_model>1); use one or the other")
            init_fn = gpt_pipe_tp.make_pipe_tp_init(
                cfg, mesh, seq_len=FLAGS.seq_len)
            loss_fn = gpt_pipe_tp.make_pipe_tp_loss(
                cfg, mesh, n_microbatches=n_micro)
            param_rules = gpt_pipe_tp.pipe_tp_rules()
            eval_fn = gpt_pipe_tp.make_pipe_tp_eval(cfg, n_stages)
        else:
            init_fn = gpt_pipe.make_pipe_init(
                cfg, mesh, seq_len=FLAGS.seq_len,
                interleave_v=FLAGS.pipe_interleave)
            if FLAGS.pipe_schedule in ("1f1b", "zb"):
                maker = {"1f1b": gpt_pipe.make_pipe_grads_1f1b,
                         "zb": gpt_pipe.make_pipe_grads_zb}[
                             FLAGS.pipe_schedule]
                grads_fn = maker(cfg, mesh, n_microbatches=n_micro)
                loss_fn = None
            else:
                loss_fn = gpt_pipe.make_pipe_loss(
                    cfg, mesh, n_microbatches=n_micro,
                    interleave_v=FLAGS.pipe_interleave)
            param_rules = gpt_pipe.pipe_rules()
            eval_fn = gpt_pipe.make_pipe_eval(
                cfg, n_stages, interleave_v=FLAGS.pipe_interleave,
                seq_shards=mesh.shape.get("seq", 1))
        model = None
    else:
        # the model needs the mesh for ring attention (seq axis) AND for the
        # shard_map'd flash kernel (model axis) — pass it unconditionally.
        if ((FLAGS.loss_chunk_vocab or FLAGS.loss_chunk_tokens
             or FLAGS.loss_pallas) and mesh.shape.get("model", 1) > 1):
            raise app.UsageError(
                "--loss_chunk_vocab/--loss_chunk_tokens/--loss_pallas "
                "cannot combine with --mesh_model: TP shards the lm_head "
                "over the vocab dim, which fused application would fight "
                "(all-gathering W per chunk)")
        model, init_fn = gpt.make_init(cfg, mesh, seq_len=FLAGS.seq_len)
        # auto loss path: monolithic logits when they fit HBM (fastest),
        # the banked kernel-tune winner — token-chunked fused CE by
        # default — when they don't; explicit flags win but warn when
        # they force a measured-slower path (PERF.md §5, docs/TUNING.md)
        lpath = dflags.resolve_lm_loss(
            FLAGS, batch=FLAGS.batch_size, seq_len=FLAGS.seq_len,
            vocab_size=cfg.vocab_size, mesh_shape=dict(mesh.shape))
        lchunk, tchunk = lpath.chunk_vocab, lpath.chunk_tokens
        lpallas = FLAGS.loss_pallas or lpath.pallas
        loss_fn = gpt.make_loss(model, loss_chunk=lchunk,
                                loss_chunk_tokens=tchunk,
                                loss_pallas=lpallas)
        param_rules = gpt.tp_rules
        eval_fn = gpt.make_eval(model, loss_chunk=lchunk,
                                loss_chunk_tokens=tchunk,
                                loss_pallas=lpallas)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(FLAGS.seed), mesh,
        param_rules=param_rules, zero1=FLAGS.zero1)
    if distill_params is not None:
        # seed the student: the state was BUILT at the draft architecture,
        # so this is a values-only device_put onto the already-computed
        # shardings — fresh optimizer moments are exactly right for a
        # newly-initialized student
        state = state.replace(params=jax.device_put(
            distill_params, shardings.params))

    from dtf_tpu.data import formats

    # the stream spec's authority chain: a manifest written by the run
    # this logdir is resuming WINS over the flag (a resumed run cannot
    # silently change its mixture) — read it before we overwrite it below
    from dtf_tpu.checkpoint import load_model_config
    from dtf_tpu.data import stream as dstream

    prev_manifest = load_model_config(os.path.join(FLAGS.logdir, "ckpt"))
    stream = None
    try:
        stream_spec = dstream.resolve_stream_spec(FLAGS.stream_spec,
                                                  prev_manifest)
        if stream_spec is not None:
            from dtf_tpu.fault.inject import maybe_stream_fault

            stream = dstream.build_stream(
                stream_spec, global_batch=FLAGS.batch_size,
                seq_len=FLAGS.seq_len, vocab_size=cfg.vocab_size,
                seed=FLAGS.seed, host_index=info.process_id,
                host_count=info.num_processes,
                producer_depth=FLAGS.prefetch_depth,
                fault_plan=maybe_stream_fault())
    except (ValueError, OSError) as e:
        # spec-shape AND spec-content errors (missing/unreadable corpus,
        # bad reweight, indivisible batch) get the flag-error treatment
        raise app.UsageError(f"--stream_spec: {e}")
    if stream is not None:
        data = stream
    else:
        data = formats.detect_token_data(
            FLAGS.data_dir, FLAGS.batch_size, FLAGS.seq_len, mode="clm",
            vocab_size=cfg.vocab_size, seed=FLAGS.seed,
            host_index=info.process_id, host_count=info.num_processes)
        if data is None:
            if FLAGS.data_dir:
                absl_logging.warning(
                    "no token .bin in %s; using synthetic data",
                    FLAGS.data_dir)
            data = SyntheticData("gpt", FLAGS.batch_size, seed=FLAGS.seed,
                                 seq_len=FLAGS.seq_len,
                                 vocab_size=cfg.vocab_size,
                                 host_index=info.process_id,
                                 host_count=info.num_processes)
    kwargs = {}
    spec = None
    if sp:
        spec = P("data", "seq")
        probe = (stream.template_batch() if stream is not None
                 else data.batch(0))
        kwargs["batch_shardings"] = batch_shardings_for(probe, mesh, spec)
    if grads_fn is not None:
        if FLAGS.grad_shard:
            absl_logging.warning(
                "--grad_shard has no effect with --pipe_schedule="
                f"{FLAGS.pipe_schedule} "
                "(microbatching lives inside the fused schedule)")
        step = tr.make_train_step_from_grads(grads_fn, tx, mesh, shardings,
                                             telemetry=tel, **kwargs)
    else:
        # --grad_shard viability: the sharded accumulator needs a
        # pure-GSPMD loss — the shard_map kernels (ring/zigzag/halo/flash
        # attention, Pallas CE, collective-matmul overlap, pipeline
        # stages) pin their own batch-over-data layouts the
        # per-shard-group vmap cannot nest (docs/ZERO.md).
        eff_attn = gpt.effective_attn_impl(FLAGS.attn_impl, sp)
        blockers = []
        if eff_attn != "dense":
            # covers the seq-sharded ring/zigzag/halo family and flash;
            # explicit dense composes even windowed + seq-sharded (the
            # model's dense path is pure GSPMD).
            blockers.append(f"attention impl {eff_attn!r} runs in "
                            "shard_map (use --attn_impl=dense)")
        if FLAGS.loss_pallas or (not pipelined and lpath.pallas):
            blockers.append("--loss_pallas fused CE runs in shard_map")
        if FLAGS.tp_overlap and mesh.shape.get("model", 1) > 1:
            blockers.append("--tp_overlap collective matmuls run in "
                            "shard_map")
        if pipelined:
            blockers.append("pipelined stages run in shard_map")
        if FLAGS.moe_every:
            blockers.append("MoE aux losses ride mutable collections, "
                            "which shard-stacked loss calls cannot thread")
        grad_shard = dflags.resolve_grad_shard(FLAGS, mesh,
                                               blockers=blockers)
        step = tr.make_train_step(loss_fn, tx, mesh, shardings,
                                  grad_accum=FLAGS.grad_accum,
                                  grad_shard=grad_shard, telemetry=tel,
                                  **kwargs)

    tokens_per_step = model_flops = None
    if tel is not None:
        # analytic MFU model: no extra trace — an AOT cost_analysis()
        # here would re-lower the step and unpin the compile fence
        # (telemetry/accounting.py)
        from dtf_tpu.telemetry import (analytic_lm_flops_per_step,
                                       param_count)

        tokens_per_step = FLAGS.batch_size * FLAGS.seq_len
        model_flops = analytic_lm_flops_per_step(
            n_params=param_count(state.params), layers=cfg.layers,
            width=cfg.d_model, seq_len=FLAGS.seq_len,
            tokens_per_step=tokens_per_step)
        tel.set_throughput_model(tokens_per_step=tokens_per_step,
                                 model_flops_per_step=model_flops)

    writer = MetricWriter(FLAGS.logdir if info.is_chief else None)
    ckpt = Checkpointer(os.path.join(FLAGS.logdir, "ckpt"),
                        save_interval_steps=FLAGS.checkpoint_every)
    # architecture manifest next to the Orbax dir: generate_gpt.py /
    # serve_gpt.py auto-load it instead of trusting hand-matched --size
    # flags (a mismatch used to garble decode silently)
    from dtf_tpu.checkpoint import save_model_config

    manifest_cfg = {
        "model": "gpt", "size": FLAGS.size,
        "kv_heads": FLAGS.kv_heads, "attn_window": FLAGS.attn_window,
        "attn_global_every": FLAGS.attn_global_every,
        "moe_every": FLAGS.moe_every, "vocab_size": cfg.vocab_size,
        "d_model": cfg.d_model, "layers": cfg.layers, "heads": cfg.heads,
        "d_ff": cfg.d_ff, "kv_cache_dtype": ""}
    if FLAGS.distill_draft:
        # a DRAFT manifest: size names the base widths, "layers" (already
        # cfg.layers == the truncation) + "draft_layers" mark the depth —
        # serve_gpt --draft_ckpt resolves the truncated stack from it
        manifest_cfg.update({
            "size": bman.get("size", FLAGS.size),
            "kv_heads": cfg.kv_heads or 0,
            "attn_window": cfg.attn_window,
            "attn_global_every": cfg.attn_global_every,
            "moe_every": 0,
            "draft_layers": FLAGS.distill_draft,
            "distilled_from": FLAGS.distill_from})
    if stream_spec is not None:
        # the mixture identity rides the manifest: the resolve above
        # guarantees a relaunch into this logdir keeps (or is refused a
        # change of) exactly this spec
        manifest_cfg[dstream.MANIFEST_KEY] = stream_spec
    save_model_config(ckpt.directory, manifest_cfg)
    # the fleet event plane (ISSUE 20): chief-only — EventLog is a
    # single-writer log, and under the fake-hosts harness N workers over
    # one dir would interleave two generations of shards
    events = None
    if FLAGS.event_log_dir and getattr(info, "participates_in_save", True):
        from dtf_tpu.telemetry.events import EventLog

        events = EventLog(FLAGS.event_log_dir)
        ckpt.attach_event_log(events)
        if stream is not None:
            stream.attach_event_log(events)
    publisher = None
    # only the checkpoint-owning process publishes (the PreemptionHook
    # ckpt=None idiom): under the fake-hosts harness every worker is its
    # own process_index-0 program, and N publishers racing one manifest
    # would commit digests over half-written dirs
    if FLAGS.publish_dir and getattr(info, "participates_in_save", True):
        from dtf_tpu.publish import ParamPublisher

        publisher = ParamPublisher(FLAGS.publish_dir)
        if events is not None:
            publisher.event_log = events
        # the architecture manifest rides next to the publish manifest so
        # a fleet serving ONLY the publish dir still resolves the config
        save_model_config(FLAGS.publish_dir, manifest_cfg)
    place_batch = lambda b: shard_batch(  # noqa: E731
        gpt.zigzag_batch(b, mesh.shape["seq"])
        if (sp and FLAGS.attn_impl == "zigzag") else b,
        mesh, spec=spec)
    # every path evaluates — the pipelined ones via the un-pipelined
    # sequential eval over the same stacked params
    eval_hook = lm_eval_hook(
        FLAGS, info, mesh, shardings, eval_fn, writer,
        place_batch, kind="gpt", mode="clm", vocab_size=cfg.vocab_size,
        batch_shardings=kwargs.get("batch_shardings"), telemetry=tel)
    from dtf_tpu.fault import inject
    from dtf_tpu.hooks import PublishHook

    hooks = [LoggingHook(writer, FLAGS.log_every, lr_schedule=sched,
                         tokens_per_step=tokens_per_step,
                         model_flops_per_step=model_flops,
                         telemetry=tel),
             *([dstream.StreamCheckpointHook(ckpt, stream)]
               if stream is not None else []),
             CheckpointHook(ckpt, FLAGS.checkpoint_every),
             *([PublishHook(publisher, FLAGS.publish_every)]
               if publisher is not None else []),
             PreemptionHook(ckpt),
             *([eval_hook] if eval_hook else []),
             StopAtStepHook(FLAGS.train_steps),
             *profiler_hooks(FLAGS, telemetry=tel,
                             flops_per_step=model_flops)]
    fault = inject.maybe_hook(host_index=info.process_id,
                              checkpointer=ckpt, publisher=publisher)
    if fault is not None:
        hooks.insert(0, fault)   # injected faults land before save hooks
    trainer = Trainer(
        step, mesh, hooks=hooks,
        checkpointer=ckpt,
        place_batch=place_batch,
        telemetry=tel,
        prefetch=FLAGS.prefetch_depth)
    state = trainer.fit(state, iter(data))
    extra = {
        "launcher": "train_gpt", "size": FLAGS.size,
        "batch_size": FLAGS.batch_size, "seq_len": FLAGS.seq_len,
        "mesh": dict(mesh.shape)}
    if stream is not None:
        # per-source throughput / realized fractions / queue depth in the
        # RunReport (backpressure itself is the data_wait phase span)
        extra["stream"] = stream.stats()
    emit_run_report(tel, info, extra=extra)
    writer.close()
    ckpt.close()
    if events is not None:
        events.emit("train_end", step=int(state.step))
        events.close()
    print(f"done: step={int(state.step)}")


if __name__ == "__main__":
    app.run(main)
