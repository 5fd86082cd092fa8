"""Plain reference of the LFM2-MoE decoder (``model_type`` ``lfm2_moe``):
float32 ``jax.numpy`` at the highest matmul precision, no kernels, no cache,
no grouped product. It imports nothing from the program and reads the
program's parameter tree, cast up where a weight is used.

The model, from the public ``Lfm2Moe`` implementation its ``config.json``
belongs to (https://huggingface.co/LiquidAI/LFM2-24B-A2B):

- block: ``h = x + Op(RMSNorm(x))``; ``y = h + FFN(RMSNorm(h))``; a final
  RMSNorm, then the head;
- ``Op`` = attention (``layer_types[i] == "full_attention"``): bias-free
  ``q, k, v`` to ``heads`` / ``kv_heads`` / ``kv_heads`` heads (grouped
  queries), RMSNorm over each head's width on ``q`` and on ``k``, rotary
  embedding on both, causal softmax(``q k^T`` / sqrt(d_head)) ``v``, bias-free
  output projection;
- ``Op`` = gated short convolution (``"conv"``): ``[B, C, X] = split3(W_in
  u)``; ``z = B * X``; ``c_t = sum_j w[j] * z_{t-(L-1)+j}`` (depthwise,
  causal, ``L = conv_L_cache`` taps, zeros before the start); ``W_out (C *
  c)``;
- FFN of the first ``num_dense_layers`` layers, and every expert:
  ``W2 (silu(W1 x) * W3 x)``;
- routing: ``s = sigmoid(W_g x)``; the ``num_experts_per_tok`` largest of
  ``s + b`` are chosen (``b``: the per-expert bias, used for the choice
  only); weights ``s_i / (sum_chosen s + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; no capacity, no dropped token.

Departures from the published model, which the configuration file lists too:

- the rotary embedding pairs neighbouring features ``(2i, 2i+1)``, as the
  program's does, where the published code pairs ``(i, i + d/2)``: the same
  function up to a fixed permutation of each head's features, which random
  weights do not see;
- the head is the token embedding, transposed (the family's convention; the
  config has no such key);
- ``experts_held = (lo, hi)`` leaves out the experts outside the range: the
  chip's share of an expert-parallel deployment (model-configs guide,
  section 4). None holds all.

Sized to run beside 10 GB of bfloat16 weights on one chip: layer by layer,
one expert at a time (a masked loop over experts: every expert sees every
token, and the mask keeps the chosen), one head group at a time.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


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


def attention(x, p, *, heads: int, kv_heads: int, eps: float, theta: float):
    b, t, d = x.shape
    d_head = d // heads
    group = heads // kv_heads

    def split(y, n):
        return y.reshape(b, t, n, d_head).transpose(0, 2, 1, 3)

    q = split(x @ _f32(p["query"]["kernel"]), heads)
    k = split(x @ _f32(p["key"]["kernel"]), kv_heads)
    v = split(x @ _f32(p["value"]["kernel"]), kv_heads)
    q = rope(rms_norm(q, p["q_norm"]["scale"], eps), theta)
    k = rope(rms_norm(k, p["k_norm"]["scale"], eps), theta)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_kv_head(args):
        qg, kh, vh = args                     # [B,group,T,D], [B,T,D] x 2
        s = jnp.einsum("bgqd,bkd->bgqk", qg, kh) / math.sqrt(d_head)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(s, axis=-1), vh)

    qg = q.reshape(b, kv_heads, group, t, d_head).transpose(1, 0, 2, 3, 4)
    out = jax.lax.map(one_kv_head, (qg, k.transpose(1, 0, 2, 3),
                                    v.transpose(1, 0, 2, 3)))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, heads, t, d_head)
    out = out.transpose(0, 2, 1, 3).reshape(b, t, d)
    return out @ _f32(p["attn_out"]["kernel"])


def short_conv(x, p, *, taps: int):
    t = x.shape[1]
    gate_b, gate_c, xs = jnp.split(x @ _f32(p["in_proj"]["kernel"]), 3,
                                   axis=-1)
    z = jnp.pad(gate_b * xs, ((0, 0), (taps - 1, 0), (0, 0)))
    w = _f32(p["conv_w"])                                      # [taps, d]
    c = sum(w[j] * z[:, j:j + t] for j in range(taps))
    return (gate_c * c) @ _f32(p["out_proj"]["kernel"])


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ _f32(w1)) * (x @ _f32(w3))) @ _f32(w2)


def route(x, p, *, top_k: int, norm_topk_prob: bool, scale: float):
    """(experts [..., k], weights [..., k], margin [...]) of tokens ``x``
    [..., d]. ``margin`` is how far the k-th largest ``s + b`` lies above
    the next one: where it is under an implementation's rounding, that
    implementation may choose otherwise and is not wrong."""
    s = jax.nn.sigmoid(x @ _f32(p["router"]))
    choice = s + _f32(p["expert_bias"]) if "expert_bias" in p else s
    top, experts = jax.lax.top_k(choice, top_k + 1)
    margin = top[..., top_k - 1] - top[..., top_k]
    experts = experts[..., :top_k]
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts, weights * scale, margin


def experts_layer(x, p, *, top_k: int, norm_topk_prob: bool, scale: float,
                  experts_held=None):
    """The routed-expert FFN of tokens ``x`` [B,T,d], the experts each token
    chose [B,T,k] and the choice's margin [B,T] (:func:`route`).
    ``p["w1"]`` holds the experts of ``experts_held`` (all of them by
    default), in order."""
    experts, weights, margin = route(
        x, p, top_k=top_k, norm_topk_prob=norm_topk_prob, scale=scale)
    lo = 0 if experts_held is None else experts_held[0]

    def one_expert(acc, args):
        e, w1, w3, w2 = args
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return acc + gate[..., None] * swiglu(x, w1, w3, w2), None

    held = lo + jnp.arange(p["w1"].shape[0])
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (held, p["w1"], p["w3"], p["w2"]))
    return out, experts, margin


def forward(params, input_ids, *, layer_types, num_dense_layers: int,
            heads: int, kv_heads: int, top_k: int, norm_eps: float,
            rope_theta: float, conv_taps: int, norm_topk_prob: bool = True,
            routed_scaling_factor: float = 1.0, experts_held=None,
            return_experts: bool = False):
    """Logits [B,T,V] in float32 at the highest matmul precision. With
    ``return_experts`` also, stacked over the expert layers, what each one
    was given (``inputs`` [L,B,T,d]: the normalised state), what it chose
    (``experts`` [L,B,T,k]) and by what margin (``margin`` [L,B,T])."""
    with jax.default_matmul_precision("highest"):
        table = params["token_embed"]["embedding"]
        x = _f32(table[input_ids])
        seen = []
        for i, kind in enumerate(layer_types):
            p = params[f"layer_{i}"]
            h = rms_norm(x, p["ln1"]["scale"], norm_eps)
            if kind == "conv":
                x = x + short_conv(h, p["conv"], taps=conv_taps)
            else:
                x = x + attention(h, p["attention"], heads=heads,
                                  kv_heads=kv_heads, eps=norm_eps,
                                  theta=rope_theta)
            h = rms_norm(x, p["ln2"]["scale"], norm_eps)
            if i < num_dense_layers:
                y = swiglu(h, p["mlp_gate"]["kernel"], p["mlp_up"]["kernel"],
                           p["mlp_out"]["kernel"])
            else:
                y, experts, margin = experts_layer(
                    h, p["experts"], top_k=top_k,
                    norm_topk_prob=norm_topk_prob,
                    scale=routed_scaling_factor, experts_held=experts_held)
                seen.append((h, experts, margin))
            x = x + y
        x = rms_norm(x, params["ln_f"]["scale"], norm_eps)
        logits = x @ _f32(table).T
    if not return_experts:
        return logits
    inputs, experts, margin = (jnp.stack(part) for part in zip(*seen))
    return logits, {"inputs": inputs, "experts": experts, "margin": margin}
