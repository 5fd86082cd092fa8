"""The program's BERT (masked-LM pre-training) as the train driver runs it.

A configuration file of this family holds BERT's own ``config.json`` keys;
:data:`KEYS` maps them onto ``BertConfig``. The loss is
``bert.make_loss(model)`` at the program's own defaults (today
``loss_chunk=0``, ``mlm_gather=0``, as ``train_bert.py``'s flags default),
so a PR that betters the default is measured.
"""

from __future__ import annotations

import types

import jax
import jax.numpy as jnp

from benchmarks.lib.check import compare
from benchmarks.reference import bert as ref, common
from dtf_tpu.data.synthetic import SyntheticData
from dtf_tpu.models import bert

#: config.json key -> BertConfig field
KEYS = {"hidden_size": "hidden", "num_hidden_layers": "layers",
        "num_attention_heads": "heads", "intermediate_size": "intermediate",
        "max_position_embeddings": "max_positions",
        "type_vocab_size": "type_vocab", "vocab_size": "vocab_size",
        "hidden_dropout_prob": "dropout"}

#: See families/gpt.py for how these were set: four times what the v5e
#: showed in PR 23 for bfloat16 matmuls against the float32 reference at 12
#: layers x width 768 (PERF.md section 6).
LOGIT_REL_RMS_TOL = 0.04
LOSS_ABS_TOL = 0.01


def model_config(config: dict) -> bert.BertConfig:
    return bert.BertConfig(**{ours: config[theirs]
                              for theirs, ours in KEYS.items()})


def build_train(config: dict, *, batch: int, seq_len: int, mesh):
    cfg = model_config(config)
    model, init_fn = bert.make_init(cfg, mesh, seq_len=seq_len)
    loss_fn = bert.make_loss(model)

    def check(params, seed: int, check_seq_len: int) -> dict:
        data = SyntheticData("bert", 2, seed=seed, seq_len=check_seq_len,
                             vocab_size=cfg.vocab_size).batch(0)
        # the deterministic mode: the training loss draws dropout
        eval_fn = bert.make_eval(model)

        @jax.jit
        def system(params, b):
            logits = model.apply(
                {"params": params}, b["input_ids"], b["segment_ids"],
                b["attention_mask"].astype(bool), deterministic=True)
            return eval_fn(params, {}, b)["eval_mlm_loss"], logits

        @jax.jit
        def reference(params, b):
            logits = ref.forward(params, b["input_ids"], b["segment_ids"],
                                 b["attention_mask"], layers=cfg.layers,
                                 heads=cfg.heads)
            return common.masked_mean_ce(logits, b["mlm_labels"]), logits

        data = jax.tree.map(jnp.asarray, data)
        return compare(system(params, data), reference(params, data), seed,
                       logit_rel_rms_tol=LOGIT_REL_RMS_TOL,
                       loss_abs_tol=LOSS_ABS_TOL)

    return types.SimpleNamespace(
        cfg=cfg, init_fn=init_fn, rules=bert.tp_rules, loss_fn=loss_fn,
        loss_path="bert.make_loss defaults (loss_chunk=0 mlm_gather=0)",
        data_kind="bert", vocab_size=cfg.vocab_size, layers=cfg.layers,
        width=cfg.hidden, check=check,
        # the token table is also the MLM head (tied), so it is a matmul;
        # positions and segments are only looked up
        lookup_only=("pos_embed", "seg_embed"),
        attention={"heads": cfg.heads, "d_head": cfg.hidden // cfg.heads,
                   "causal": False, "calls_per_micro_batch": cfg.layers})
