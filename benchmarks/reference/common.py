"""Pieces both plain references share. Float32 ``jax.numpy`` only."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def stack_layers(params: dict, n_layers: int) -> dict:
    """``layer_0 .. layer_{n-1}`` stacked on a new leading axis, so the
    layers run as one ``lax.scan`` and the reference compiles in seconds."""
    return jax.tree.map(lambda *xs: jnp.stack(xs),
                        *[params[f"layer_{i}"] for i in range(n_layers)])


def layer_norm(x, p, eps: float = 1e-6):
    """Ba et al. 2016; epsilon 1e-6 is Flax's default, which the program
    leaves alone."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def dense(x, p):
    return x @ p["kernel"] + p["bias"]


def gelu_tanh(x):
    """Hendrycks & Gimpel's tanh approximation (the one GPT-2 and the
    original BERT code use)."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def split_heads(x, heads: int):
    b, t, d = x.shape
    return x.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)


def merge_heads(x):
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def attention(q, k, v, mask):
    """Softmax(Q K^T / sqrt(d) + mask) V over [B,H,T,D]; ``mask`` is True
    where a query may look."""
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / math.sqrt(q.shape[-1])
    scores = jnp.where(mask, scores, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)


def masked_mean_ce(logits, labels, ignore: int = -100):
    """Mean cross-entropy over the positions whose label is not ``ignore``."""
    valid = labels != ignore
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(
        logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.sum(valid)
