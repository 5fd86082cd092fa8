"""The program's GPT as the benchmark drives it (train and serve cells).

A configuration file of this family holds GPT-2's own ``config.json`` keys;
:data:`KEYS` maps them onto ``GPTConfig``. Everything else about the model
is the program's default, so a PR that betters a default is measured.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

from benchmarks.lib.check import compare
from benchmarks.reference import common, gpt2
from dtf_tpu.cli import flags as dflags
from dtf_tpu.data.synthetic import SyntheticData
from dtf_tpu.models import gpt

#: config.json key -> GPTConfig field
KEYS = {"n_embd": "d_model", "n_layer": "layers", "n_head": "heads",
        "n_inner": "d_ff", "vocab_size": "vocab_size"}

#: Tolerances of the system (bfloat16 matmuls, float32 LayerNorm, softmax
#: and head) against the float32 reference, at 24 layers x width 1024 with
#: random weights. Measured on the v5e in PR 23 (PERF.md section 6): logits
#: differ by about 1% of their RMS and the loss by about 1e-3. The bounds
#: are four times that. An 8-bit matmul path is about ten times further
#: off than bfloat16 (3 or 4 mantissa bits for 8), so it fails them.
LOGIT_REL_RMS_TOL = 0.04
LOSS_ABS_TOL = 0.01


def model_config(config: dict) -> gpt.GPTConfig:
    return gpt.GPTConfig(**{ours: config[theirs]
                            for theirs, ours in KEYS.items()})


def reference_logits(cfg, params, input_ids):
    return gpt2.forward(params, input_ids, layers=cfg.layers,
                        heads=cfg.heads, rope_theta=cfg.rope_theta)


def build_serve(config: dict):
    """What the serve driver needs: the model's config for the engine, the
    weights from a key in one jitted call, and the reference's logits."""
    cfg = model_config(config)
    _, init_fn = gpt.make_init(cfg, None, seq_len=8)
    return types.SimpleNamespace(
        cfg=cfg, vocab_size=cfg.vocab_size,
        init_params=jax.jit(lambda key: init_fn(key)["params"]),
        reference_logits=lambda params, ids: reference_logits(
            cfg, params, ids))


def build_train(config: dict, *, batch: int, seq_len: int, mesh):
    """What the train driver needs: the recipe ``scripts/bench_lm.py`` had
    (``make_init`` -> ``make_loss``), with the LM-loss path the program
    itself picks for this shape."""
    cfg = model_config(config)
    model, init_fn = gpt.make_init(cfg, mesh, seq_len=seq_len)
    # an empty namespace: resolve_lm_loss reads its flags by getattr with
    # defaults, so this is "no loss flag given"
    path = dflags.resolve_lm_loss(
        types.SimpleNamespace(), batch=batch, seq_len=seq_len,
        vocab_size=cfg.vocab_size, mesh_shape=dict(mesh.shape))
    loss_fn = gpt.make_loss(model, loss_chunk=path.chunk_vocab,
                            loss_chunk_tokens=path.chunk_tokens,
                            loss_pallas=path.pallas)
    d_head = cfg.d_model // cfg.heads

    def check(params, seed: int, check_seq_len: int) -> dict:
        data = SyntheticData("gpt", 2, seed=seed, seq_len=check_seq_len,
                             vocab_size=cfg.vocab_size).batch(0)
        eval_fn = gpt.make_eval(model)

        @jax.jit
        def system(params, batch):
            logits = model.apply({"params": params}, batch["input_ids"],
                                 deterministic=True)
            return eval_fn(params, {}, batch)["eval_loss"], logits

        @jax.jit
        def reference(params, batch):
            logits = reference_logits(cfg, params, batch["input_ids"])
            return common.masked_mean_ce(logits, batch["labels"]), logits

        data = jax.tree.map(jnp.asarray, data)
        return compare(system(params, data), reference(params, data), seed,
                       logit_rel_rms_tol=LOGIT_REL_RMS_TOL,
                       loss_abs_tol=LOSS_ABS_TOL)

    return types.SimpleNamespace(
        cfg=cfg, init_fn=init_fn, rules=gpt.tp_rules, loss_fn=loss_fn,
        loss_path=f"chunk_vocab={path.chunk_vocab} chunk_tokens="
                  f"{path.chunk_tokens} pallas={path.pallas} ({path.source})",
        data_kind="gpt", vocab_size=cfg.vocab_size, layers=cfg.layers,
        width=cfg.d_model, check=check,
        # the head is not tied: the token table is gathered from, never
        # multiplied (lib.flops.matmul_params)
        lookup_only=("token_embed",),
        attention={"heads": cfg.heads, "d_head": d_head, "causal": True,
                   "calls_per_micro_batch": cfg.layers})
