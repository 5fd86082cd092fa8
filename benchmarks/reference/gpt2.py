"""Plain reference of the program's GPT: float32, no kernels, no cache.

The program's ``gpt2_medium`` has GPT-2's widths and block (Radford et al.
2019: pre-LayerNorm, 4x GELU feed-forward, causal attention), with three
departures of its own, which this reference follows because it reads the
program's parameters:

- rotary position embedding on queries and keys (Su et al. 2021, pairs of
  neighbouring features, base 10000) in place of GPT-2's learned positions;
- an output head that is not tied to the token embedding, without a bias;
- a vocabulary padded from 50257 to 50304.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.reference import common as c


def rope(x, theta: float):
    """Rotate feature pairs (2i, 2i+1) of ``x`` [B,H,T,D] by
    ``position * theta**(-2i/D)``."""
    t, d = x.shape[-2], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def forward(params, input_ids, *, layers: int, heads: int,
            rope_theta: float = 10000.0):
    """Logits [B,T,V] in float32 at the highest matmul precision."""
    with jax.default_matmul_precision("highest"):
        p = c.f32(params)
        x = p["token_embed"]["embedding"][input_ids]
        t = input_ids.shape[1]
        causal = jnp.tril(jnp.ones((t, t), bool))[None, None]

        def block(x, lp):
            h = c.layer_norm(x, lp["ln1"])
            a = lp["attention"]
            q = rope(c.split_heads(c.dense(h, a["query"]), heads), rope_theta)
            k = rope(c.split_heads(c.dense(h, a["key"]), heads), rope_theta)
            v = c.split_heads(c.dense(h, a["value"]), heads)
            x = x + c.dense(c.merge_heads(c.attention(q, k, v, causal)),
                            a["attn_out"])
            h = c.layer_norm(x, lp["ln2"])
            x = x + c.dense(c.gelu_tanh(c.dense(h, lp["mlp_in"])),
                            lp["mlp_out"])
            return x, None

        x, _ = jax.lax.scan(block, x, c.stack_layers(p, layers))
        return c.layer_norm(x, p["ln_f"]) @ p["lm_head"]["kernel"]


def loss(params, input_ids, labels, **kw):
    """Mean next-token cross-entropy (labels already shifted; -100 = skip)."""
    return c.masked_mean_ce(forward(params, input_ids, **kw), labels)
