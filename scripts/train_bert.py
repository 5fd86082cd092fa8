#!/usr/bin/env python
"""BERT-base MLM pretraining — BASELINE config 4 (grad-accum + ZeRO-1).

    python scripts/train_bert.py --grad_accum=4 --mesh_model=2 --mesh_seq=2

Parallelism is fully flag-driven: dp over `data` (ZeRO-1 shards optimizer
state there), TP over `model` (Megatron rules), context parallelism over
`seq` (ring attention).
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from absl import app, flags, logging as absl_logging

from dtf_tpu.cli import flags as dflags

dflags.define_cluster_flags()
dflags.define_mesh_flags()
dflags.define_train_flags(batch_size=64, learning_rate=1e-4, train_steps=200,
                          lr_schedule="cosine")
flags.DEFINE_integer("seq_len", 128, "sequence length")
flags.DEFINE_string("size", "base", "base | tiny")
flags.DEFINE_boolean("zero1", True, "shard optimizer state over data axis")
flags.DEFINE_string("attn_impl", "auto", "auto (flash on TPU) | dense | "
                    "flash — non-seq-sharded attention backend")
flags.DEFINE_boolean("tp_overlap", False, "latency-hiding collective "
                     "matmul for the Megatron TP projections (needs "
                     "--mesh_model>1; docs/OVERLAP.md)")
flags.DEFINE_integer("eval_every", 0, "held-out MLM eval (val.bin or "
                     "held-out synthetic) every N steps; 0 = final only")
flags.DEFINE_integer("loss_chunk_vocab", 0, "compute the MLM loss fused "
                     "with the tied-embedding decode in vocab chunks of "
                     "this width (0 = full logits); not with --mesh_model "
                     "(the embedding is vocab-sharded under TP)")
flags.DEFINE_integer("mlm_gather", 0, "score only this many gathered "
                     "masked positions per row (BERT's "
                     "max_predictions_per_seq recipe; ~7x less head work "
                     "at a 15% mask rate; 0 = score all positions). Not "
                     "with --mesh_model")
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
    from dtf_tpu.core.comms import batch_shardings_for
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.hooks import (CheckpointHook, LoggingHook,
                               PreemptionHook, StopAtStepHook)
    from dtf_tpu.loop import Trainer
    from dtf_tpu.metrics import MetricWriter
    from dtf_tpu.models import bert

    mesh, info = setup(FLAGS)
    sp = mesh.shape.get("seq", 1) > 1
    tel = telemetry_from_flags(FLAGS, info)

    if FLAGS.tp_overlap and mesh.shape.get("model", 1) <= 1:
        absl_logging.warning(
            "--tp_overlap has no effect without --mesh_model>1 (no TP "
            "collectives to hide); proceeding on the plain path")
    cfg = (bert.BertConfig.base() if FLAGS.size == "base"
           else bert.BertConfig.tiny())
    cfg = dataclasses.replace(cfg, attn_impl=FLAGS.attn_impl,
                              tp_overlap=FLAGS.tp_overlap)
    # the collective-matmul path needs the mesh in the model (tp_overlap);
    # otherwise keep the historical mesh-less construction off SP.
    model, init_fn = bert.make_init(
        cfg, mesh if (sp or FLAGS.tp_overlap) else None,
        seq_len=FLAGS.seq_len)
    sched = dflags.make_lr_schedule(FLAGS)   # LoggingHook surfaces the LR
    tx = dflags.make_optimizer(
        FLAGS, lambda s: optax.adamw(s, weight_decay=(
            FLAGS.weight_decay if FLAGS.weight_decay >= 0 else 0.01)),
        recipe_uses_wd=True)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(FLAGS.seed), mesh,
        param_rules=bert.tp_rules, zero1=FLAGS.zero1)

    from dtf_tpu.data import formats

    data = formats.detect_token_data(
        FLAGS.data_dir, FLAGS.batch_size, FLAGS.seq_len, mode="mlm",
        vocab_size=cfg.vocab_size, seed=FLAGS.seed,
        host_index=info.process_id, host_count=info.num_processes)
    if data is None:
        if FLAGS.data_dir:
            absl_logging.warning("no token .bin in %s; using synthetic data",
                                 FLAGS.data_dir)
        data = SyntheticData("bert", FLAGS.batch_size, seed=FLAGS.seed,
                             seq_len=FLAGS.seq_len, vocab_size=cfg.vocab_size,
                             host_index=info.process_id,
                             host_count=info.num_processes)
    kwargs = {}
    spec = None
    if sp:
        spec = P("data", "seq")
        kwargs["batch_shardings"] = batch_shardings_for(
            data.batch(0), mesh, spec)
    if ((FLAGS.loss_chunk_vocab or FLAGS.mlm_gather)
            and mesh.shape.get("model", 1) > 1):
        raise app.UsageError(
            "--loss_chunk_vocab/--mlm_gather cannot combine with "
            "--mesh_model: the tied embedding is vocab-sharded under TP, "
            "which the hidden-states loss paths would fight")
    if FLAGS.mlm_gather and mesh.shape.get("seq", 1) > 1:
        raise app.UsageError(
            "--mlm_gather cannot combine with --mesh_seq: the per-row "
            "gather indexes across the whole sequence, which would force "
            "GSPMD to all-gather the seq-sharded hidden states — exactly "
            "the cost seq sharding exists to avoid")
    # --grad_shard viability: everything but dense attention runs in a
    # shard_map the per-shard-group vmap cannot nest (docs/ZERO.md);
    # the model's own dispatch helper keeps this in lockstep.
    eff_attn = bert.effective_attn_impl(FLAGS.attn_impl, sp)
    blockers = []
    if eff_attn != "dense":
        blockers.append(f"attention impl {eff_attn!r} runs in shard_map"
                        + ("" if sp else " (use --attn_impl=dense)"))
    if FLAGS.tp_overlap and mesh.shape.get("model", 1) > 1:
        blockers.append("--tp_overlap collective matmuls run in shard_map")
    grad_shard = dflags.resolve_grad_shard(FLAGS, mesh, blockers=blockers)
    step = tr.make_train_step(
        bert.make_loss(model, loss_chunk=FLAGS.loss_chunk_vocab,
                       mlm_gather=FLAGS.mlm_gather), tx, mesh,
        shardings, grad_accum=FLAGS.grad_accum, grad_shard=grad_shard,
        telemetry=tel, **kwargs)

    from dtf_tpu.core.comms import shard_batch

    tokens_per_step = model_flops = None
    if tel is not None:
        # analytic MFU model (telemetry/accounting.py); an AOT
        # cost_analysis() would re-trace the step and unpin the fence
        from dtf_tpu.telemetry import (analytic_lm_flops_per_step,
                                       param_count)

        tokens_per_step = FLAGS.batch_size * FLAGS.seq_len
        model_flops = analytic_lm_flops_per_step(
            n_params=param_count(state.params), layers=cfg.layers,
            width=cfg.hidden, seq_len=FLAGS.seq_len,
            tokens_per_step=tokens_per_step)
        tel.set_throughput_model(tokens_per_step=tokens_per_step,
                                 model_flops_per_step=model_flops)

    writer = MetricWriter(FLAGS.logdir if info.is_chief else None)
    ckpt = Checkpointer(os.path.join(FLAGS.logdir, "ckpt"),
                        save_interval_steps=FLAGS.checkpoint_every)
    place_batch = lambda b: shard_batch(b, mesh, spec=spec)  # noqa: E731
    eval_hook = lm_eval_hook(
        FLAGS, info, mesh, shardings,
        bert.make_eval(model, loss_chunk=FLAGS.loss_chunk_vocab,
                       mlm_gather=FLAGS.mlm_gather), writer,
        place_batch, kind="bert", mode="mlm", vocab_size=cfg.vocab_size,
        batch_shardings=kwargs.get("batch_shardings"), telemetry=tel)
    trainer = Trainer(
        step, mesh,
        hooks=[LoggingHook(writer, FLAGS.log_every, lr_schedule=sched,
                           tokens_per_step=tokens_per_step,
                           model_flops_per_step=model_flops,
                           telemetry=tel),
               CheckpointHook(ckpt, FLAGS.checkpoint_every),
               PreemptionHook(ckpt),
               *([eval_hook] if eval_hook else []),
               StopAtStepHook(FLAGS.train_steps),
               *profiler_hooks(FLAGS, telemetry=tel,
                               flops_per_step=model_flops)],
        checkpointer=ckpt,
        place_batch=place_batch,
        telemetry=tel,
        prefetch=FLAGS.prefetch_depth)
    state = trainer.fit(state, iter(data))
    emit_run_report(tel, info, extra={
        "launcher": "train_bert", "size": FLAGS.size,
        "batch_size": FLAGS.batch_size, "seq_len": FLAGS.seq_len,
        "mesh": dict(mesh.shape)})
    writer.close()
    ckpt.close()
    print(f"done: step={int(state.step)}")


if __name__ == "__main__":
    app.run(main)
