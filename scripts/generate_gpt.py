#!/usr/bin/env python
"""GPT inference: load a train_gpt.py checkpoint, decode with the KV cache.

    python scripts/generate_gpt.py --logdir=/tmp/dtf_tpu_logs --size=tiny \
        --prompt=12,7,99 --n_new=16 --temperature=0.8 --top_p=0.9

The serving half of the flagship loop: restores params from the Orbax
checkpoint the training launcher wrote, builds the decode-mode model
(``decode_len`` sized to prompt+new), and runs :func:`dtf_tpu.models.gpt.
generate` — greedy or temperature/top-k/nucleus sampling, optionally
sharded over a (data, model) mesh (KV cache lands P('data','model')).
Prints one token-id row per batch element.
"""

import dataclasses
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from absl import app, flags

from dtf_tpu.cli import flags as dflags

dflags.define_cluster_flags()
dflags.define_mesh_flags()
flags.DEFINE_string("logdir", "/tmp/dtf_tpu_logs", "training logdir whose "
                    "ckpt/ subdir holds the checkpoint to serve")
flags.DEFINE_string("size", "small", "small (gpt2-124M) | medium "
                    "(gpt2-355M) | tiny — auto-loaded from the checkpoint "
                    "manifest when present (a contradicting flag errors)")
flags.DEFINE_integer("kv_heads", 0, "grouped-query attention heads "
                     "(0 = plain MHA); manifest wins")
flags.DEFINE_integer("attn_window", 0, "sliding-window size (0 = full "
                     "causal); manifest wins")
flags.DEFINE_integer("attn_global_every", 0, "global-attention layer "
                     "cadence; manifest wins")
flags.DEFINE_string("prompt", "", "comma-separated token ids; empty = a "
                    "fixed demo prompt")
flags.DEFINE_integer("batch", 1, "decode batch size (prompt is broadcast)")
flags.DEFINE_integer("n_new", 32, "tokens to generate")
flags.DEFINE_float("temperature", 0.0, "0 = greedy, else sampling")
flags.DEFINE_integer("num_beams", 0, "beam-search width (0/1 = off); "
                     "deterministic, excludes the sampling flags")
flags.DEFINE_float("length_penalty", 0.0, "beam rescoring alpha: "
                   "score / len**alpha (0 = pure sum-logprob)")
flags.DEFINE_integer("top_k", 0, "top-k filter (0 = off)")
flags.DEFINE_float("top_p", 1.0, "nucleus filter (1.0 = off)")
flags.DEFINE_integer("seed", 0, "sampling PRNG seed")
flags.DEFINE_integer("eos_id", -1, "stop token: once a sequence emits it, "
                     "later positions are --pad_id (-1 = no stop token)")
flags.DEFINE_integer("pad_id", 0, "pad token written after --eos_id")
flags.DEFINE_string("kv_cache_dtype", "", "'' = cache at compute dtype; "
                    "'int8' = symmetric per-slot quantization — half the "
                    "cache bytes, multiplicative with --kv_heads and "
                    "--attn_window")
flags.DEFINE_integer("prefill_chunk", 0, "prefill the prompt in chunks of "
                     "this many tokens (bounded-memory long prompts; "
                     "0 = one-shot prefill)")
FLAGS = flags.FLAGS


def main(argv):
    del argv
    import jax
    import jax.numpy as jnp
    import numpy as np

    from dtf_tpu.checkpoint import Checkpointer
    from dtf_tpu.cli.launch import init_backend
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.core.sharding import shard_tree
    from dtf_tpu.models import gpt

    if FLAGS.num_beams > 1 and (FLAGS.temperature > 0.0 or FLAGS.top_k
                                or FLAGS.top_p < 1.0):
        raise app.UsageError(
            "--num_beams is a deterministic search; it excludes "
            "--temperature/--top_k/--top_p")
    if FLAGS.temperature == 0.0 and (FLAGS.top_k or FLAGS.top_p < 1.0):
        raise app.UsageError(
            "--top_k/--top_p have no effect at --temperature=0 (greedy); "
            "set a positive temperature to sample")
    init_backend(FLAGS.backend)
    # Serving is a single-process, chief-only job: no cluster bootstrap.
    # Sharded decode is opt-in (explicit positive mesh axes) and runs on a
    # device SUBSET sized to the mesh — a serving batch is often tiny, and
    # training's all-devices mesh would demand batch % n_devices == 0.
    sharded = FLAGS.mesh_model > 1 or FLAGS.mesh_data > 1
    mesh = None
    if sharded:
        dp = max(FLAGS.mesh_data, 1)
        tp = max(FLAGS.mesh_model, 1)
        if dp * tp > len(jax.devices()):
            raise app.UsageError(
                f"mesh {dp}x{tp} exceeds {len(jax.devices())} devices")
        mesh = make_mesh(MeshConfig(data=dp, model=tp),
                         devices=jax.devices()[:dp * tp])

    from dtf_tpu.checkpoint import load_model_config

    # the config manifest train_gpt.py writes next to the Orbax dir is
    # authoritative for the architecture fields; hand-matched flags only
    # survive when they agree (a mismatch used to garble decode silently)
    ckpt_dir = os.path.join(FLAGS.logdir, "ckpt")
    try:
        decode_cfg = dflags.resolve_decode_config(
            FLAGS, load_model_config(ckpt_dir))
    except ValueError as e:
        raise app.UsageError(str(e))
    try:
        base = gpt.GPTConfig.by_name(decode_cfg["size"])
    except KeyError as e:
        raise app.UsageError(f"--size: {e.args[0]}")
    prompt_ids = ([int(t) for t in FLAGS.prompt.split(",") if t.strip()]
                  or [1, 2, 3, 4])
    if max(prompt_ids) >= base.vocab_size or min(prompt_ids) < 0:
        raise app.UsageError(
            f"prompt ids must be in [0, {base.vocab_size})")
    total = len(prompt_ids) + FLAGS.n_new
    if decode_cfg["kv_cache_dtype"] not in ("", "int8"):
        raise app.UsageError(
            f"--kv_cache_dtype={decode_cfg['kv_cache_dtype']!r}: "
            "'' or 'int8'")
    cfg = dataclasses.replace(base,
                              kv_heads=decode_cfg["kv_heads"] or None,
                              attn_window=decode_cfg["attn_window"],
                              attn_global_every=decode_cfg[
                                  "attn_global_every"],
                              kv_cache_dtype=decode_cfg["kv_cache_dtype"],
                              decode_len=total)
    model = gpt.GPT(cfg)

    ckpt = Checkpointer(ckpt_dir)
    step = ckpt.latest_step()
    if step is None:
        raise app.UsageError(f"no checkpoint under {FLAGS.logdir}/ckpt")
    # params-only restore: new checkpoints carry a dedicated params item
    # (no ~3x opt_state read); legacy ones fall back to the full-tree read
    params = ckpt.restore_params(step)
    print(f"restored checkpoint step {step} from {FLAGS.logdir}/ckpt",
          file=sys.stderr)

    if sharded:
        params = shard_tree(params, mesh, gpt.tp_rules)

    prompt = jnp.broadcast_to(jnp.asarray(prompt_ids, jnp.int32)[None, :],
                              (FLAGS.batch, len(prompt_ids)))
    if FLAGS.num_beams > 1:
        if mesh is not None:
            raise app.UsageError("--num_beams does not compose with a "
                                 "sharded decode mesh; shard the batch "
                                 "outside instead")
        out = gpt.generate_beam(
            model, params, prompt, FLAGS.n_new, num_beams=FLAGS.num_beams,
            eos_id=FLAGS.eos_id if FLAGS.eos_id >= 0 else None,
            pad_id=FLAGS.pad_id, length_penalty=FLAGS.length_penalty,
            prefill_chunk=FLAGS.prefill_chunk)
    else:
        out = gpt.generate(model, params, prompt, FLAGS.n_new,
                           rng=jax.random.PRNGKey(FLAGS.seed),
                           temperature=FLAGS.temperature,
                           top_k=FLAGS.top_k, top_p=FLAGS.top_p,
                           eos_id=FLAGS.eos_id if FLAGS.eos_id >= 0 else None,
                           pad_id=FLAGS.pad_id,
                           prefill_chunk=FLAGS.prefill_chunk, mesh=mesh)
    for row in np.asarray(out):
        print(",".join(str(int(t)) for t in row))


if __name__ == "__main__":
    app.run(main)
