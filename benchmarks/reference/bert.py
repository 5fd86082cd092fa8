"""Plain reference of BERT's masked-LM forward: float32, no kernels.

Devlin et al. 2018: token + position + segment embeddings, LayerNorm, then
post-LayerNorm encoder layers (self-attention, 4x GELU feed-forward), and
the masked-LM head: dense, GELU, LayerNorm, decoding against the transposed
token embedding plus a bias. The program's departures, followed here: the
tanh approximation of GELU, and LayerNorm's epsilon 1e-6 (Flax's default)
where the published code has 1e-12. Dropout is off: the comparison runs the
program in its deterministic mode.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c


def forward(params, input_ids, segment_ids, attention_mask, *, layers: int,
            heads: int):
    """Logits [B,T,V] in float32 at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        p = c.f32(params)
        t = input_ids.shape[1]
        x = (p["token_embed"]["embedding"][input_ids]
             + p["pos_embed"]["embedding"][jnp.arange(t)][None]
             + p["seg_embed"]["embedding"][segment_ids])
        x = c.layer_norm(x, p["ln_embed"])
        may_look = attention_mask.astype(bool)[:, None, None, :]

        def layer(x, lp):
            a = lp["attention"]
            q, k, v = (c.split_heads(c.dense(x, a[n]), heads)
                       for n in ("query", "key", "value"))
            attn = c.dense(c.merge_heads(c.attention(q, k, v, may_look)),
                           a["attn_out"])
            x = c.layer_norm(x + attn, lp["ln_attn"])
            h = c.dense(c.gelu_tanh(c.dense(x, lp["mlp_in"])), lp["mlp_out"])
            return c.layer_norm(x + h, lp["ln_mlp"]), None

        x, _ = jax.lax.scan(layer, x, c.stack_layers(p, layers))
        h = c.layer_norm(c.gelu_tanh(c.dense(x, p["mlm_dense"])), p["ln_mlm"])
        return h @ p["token_embed"]["embedding"].T + p["mlm_bias"]


def loss(params, input_ids, segment_ids, attention_mask, mlm_labels, **kw):
    """Mean cross-entropy over the masked positions (label -100 = skip)."""
    return c.masked_mean_ce(
        forward(params, input_ids, segment_ids, attention_mask, **kw),
        mlm_labels)
