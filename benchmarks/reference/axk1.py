"""Plain reference of the A.X-K1 decoder (``model_type`` ``axk1``; the key
set of DeepSeek-V3's ``config.json``): float32 ``jax.numpy`` at the highest
matmul precision, no kernels, no cache, no absorbed product, no grouped
product. It imports nothing from the program and reads the program's
parameter tree, cast up where a weight is used.

The model, from the keys of https://huggingface.co/skt/A.X-K1 (``config``
below is that file's keys as ``benchmarks/configs/ax-k1.json`` holds them):

- block: ``h = x + Attn(RMSNorm(x))``; ``y = h + FFN(RMSNorm(h))``; a final
  RMSNorm, then the untied head; no bias anywhere; RMSNorm in float32 with
  ``rms_norm_eps``;
- latent attention: ``c_q = RMSNorm(W_qa h)`` (``q_lora_rank``); ``q = W_qb
  c_q`` -> ``heads`` x (``qk_nope_head_dim`` no-position + ``qk_rope_head_dim``
  rotary); ``[c_kv ; k_r] = W_kva h`` (``kv_lora_rank`` + rotary width);
  ``c_kv <- RMSNorm(c_kv)``; ``k_r`` is rotated and is ONE key part shared by
  all heads; ``[k_i ; v_i] = W_kvb,i c_kv`` per head (``qk_nope_head_dim`` +
  ``v_head_dim``); ``score_ij = (q_i^nope . k_i,j + q_i^rope . k_r,j) *
  sigma``; causal softmax; ``out = W_o concat_i sum_j p_ij v_i,j``;
- YaRN (``rope_scaling``): pair ``d`` of the rotary width ``R`` turns at
  ``f_d (1 - ramp_d) + (f_d / factor) ramp_d`` with ``f_d = theta^(-2d/R)``
  and ``ramp_d = clip((d - low) / (high - low), 0, 1)``, ``low = floor(R
  ln(orig / (beta_fast 2 pi)) / (2 ln theta))``, ``high = ceil(R ln(orig /
  (beta_slow 2 pi)) / (2 ln theta))``; cos and sin are scaled by
  ``m(mscale) / m(mscale_all_dim)`` with ``m(s) = 0.1 s ln(factor) + 1``;
  ``sigma = (nope + rope width)^(-1/2) m(mscale_all_dim)^2``;
- FFN of the first ``first_k_dense_replace`` layers: ``W2 (silu(W1 x) * W3
  x)`` of width ``intermediate_size``; of the others ``Shared(h) +
  Routed(h)``: one SwiGLU of width ``n_shared_experts x
  moe_intermediate_size`` every token meets, and the routed experts;
- routing: ``s = sigmoid(W_g h)`` over ``n_routed_experts``; the experts lie
  in ``n_group`` contiguous groups; a group's score is the sum of its two
  largest ``s``; the ``topk_group`` best groups are kept; the
  ``num_experts_per_tok`` largest ``s`` among the kept groups' experts are
  chosen; weights ``s_e / (sum_chosen s + 1e-6)`` (``norm_topk_prob``) times
  ``routed_scaling_factor``; no choice bias (``topk_method: "none"``); no
  capacity, no dropped token.

Departures from the published model, which the configuration file lists too:

- the rotary embedding pairs neighbouring features ``(2i, 2i+1)``, as the
  program's does: a fixed permutation of the rotary columns of ``W_qb`` and
  ``W_kva``, which random weights do not see;
- the normalisation's ``1e-6`` where the family's code has ``1e-20``
  (relative 1e-7 of a sum of eight sigmoid scores): the program's;
- ``experts_held = (lo, hi)`` leaves out the routed experts outside the
  range: the chip's share of an expert-parallel deployment (model-configs
  guide, section 4). ``p["w1"]`` then holds the held experts, in order.
  None holds all.

Sized to run at 16 384 positions beside 8 GB of bfloat16 weights on one
chip: layer by layer, one head and one block of queries at a time, the FFNs
in blocks of positions, one expert at a time (a masked loop over the held
experts: every expert sees every token, and the mask keeps the chosen).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: positions a block of queries or of FFN rows holds
BLOCK = 2048


def _f32(x):
    return jnp.asarray(x, jnp.float32)


def _expert_weight(x):
    """A routed expert's matrix as it is multiplied (the faults put a lower
    precision here)."""
    return _f32(x)


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * _f32(scale)


def _yarn_m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(config: dict):
    """[R/2] float32: the angle a rotary pair turns by per position."""
    width = config["qk_rope_head_dim"]
    theta = float(config["rope_theta"])
    d = jnp.arange(0, width, 2, dtype=jnp.float32)
    plain = theta ** (-d / width)
    sc = config.get("rope_scaling")
    if not sc or sc["factor"] == 1:
        return plain

    def turns_at(rotations):
        return (width * math.log(sc["original_max_position_embeddings"]
                                 / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_at(sc["beta_fast"])), 0)
    high = min(math.ceil(turns_at(sc["beta_slow"])), width - 1)
    ramp = jnp.clip((d / 2 - low) / max(high - low, 0.001), 0.0, 1.0)
    return plain * (1 - ramp) + plain / sc["factor"] * ramp


def rotary_amplitude(config: dict) -> float:
    sc = config.get("rope_scaling")
    if not sc:
        return 1.0
    return (_yarn_m(sc["factor"], sc.get("mscale", 1))
            / _yarn_m(sc["factor"], sc.get("mscale_all_dim", 0)))


def softmax_scale(config: dict) -> float:
    width = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    sc = config.get("rope_scaling")
    m = _yarn_m(sc["factor"], sc.get("mscale_all_dim", 0)) if sc else 1.0
    return width ** -0.5 * m * m


def key_positions(t: int):
    """The position each rotary key part is turned to."""
    return jnp.arange(t, dtype=jnp.float32)


def rope(x, positions, config: dict):
    """Rotate feature pairs (2i, 2i+1) of ``x`` [..., T, R] to ``positions``
    [T]."""
    angles = positions[:, None] * yarn_frequencies(config)[None, :]
    amp = rotary_amplitude(config)
    cos, sin = jnp.cos(angles) * amp, jnp.sin(angles) * amp
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     axis=-1).reshape(x.shape)


def normalise_latent(c_kv, scale, eps: float):
    """``c_kv`` as the keys and values are expanded from it."""
    return rms_norm(c_kv, scale, eps)


def cached_rows(c_kv, k_rope):
    """The normalised latent rows and the rotated key part as attention
    reads them back (the faults put a cache's lower precision here)."""
    return c_kv, k_rope


def _query_blocks(t: int) -> int:
    block = min(BLOCK, t)
    while t % block:
        block -= 1
    return block


def latent_attention(x, p, config: dict):
    b, t, _ = x.shape
    heads = config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    v_dim, rank = config["v_head_dim"], config["kv_lora_rank"]
    eps = config["rms_norm_eps"]

    c_q = rms_norm(x @ _f32(p["q_a"]["kernel"]), p["q_a_norm"]["scale"], eps)
    kv = x @ _f32(p["kv_a"]["kernel"])                         # [B,T,rank+R]
    c_kv = normalise_latent(kv[..., :rank], p["kv_a_norm"]["scale"], eps)
    k_rope = rope(kv[..., rank:], key_positions(t), config)    # [B,T,R]
    c_kv, k_rope = cached_rows(c_kv, k_rope)
    # one head at a time, from its own columns of W_qb and W_kvb
    w_qb = _f32(p["q_b"]["kernel"]).reshape(-1, heads, nope + rot)
    w_kvb = _f32(p["kv_b"]["kernel"]).reshape(rank, heads, nope + v_dim)

    sigma = softmax_scale(config)
    block = _query_blocks(t)
    at = jnp.arange(t)
    rows = jax.lax.dynamic_slice_in_dim

    def one_head(out, weights):
        w_q, w_kv, w_o = weights       # [q_rank,.] [rank,.] [v, d]
        q, kv_i = c_q @ w_q, c_kv @ w_kv
        q_nope = q[..., :nope]
        q_rope = rope(q[..., nope:], jnp.arange(t, dtype=jnp.float32),
                      config)
        k_nope, v = kv_i[..., :nope], kv_i[..., nope:]

        def one_block(start):
            s = (jnp.einsum("bqd,bkd->bqk", rows(q_nope, start, block, 1),
                            k_nope)
                 + jnp.einsum("bqd,bkd->bqk", rows(q_rope, start, block, 1),
                              k_rope)) * sigma
            causal = at[None, :] <= (start + jnp.arange(block))[:, None]
            s = jnp.where(causal[None], s, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1), v)

        o = jax.lax.map(one_block, jnp.arange(0, t, block))    # [n,B,blk,v]
        o = o.transpose(1, 0, 2, 3).reshape(b, t, v_dim)
        # W_o's rows of this head: the heads' outputs are never laid side
        # by side
        return out + o @ _f32(w_o), None

    w_o = p["attn_out"]["kernel"].reshape(heads, v_dim, -1)
    out, _ = jax.lax.scan(one_head, jnp.zeros_like(x),
                          (w_qb.transpose(1, 0, 2), w_kvb.transpose(1, 0, 2),
                           w_o))
    return out


def swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def swiglu_in_columns(x, w1, w3, w2, cast=_f32):
    """:func:`swiglu` of stored weights, :data:`BLOCK` columns of the
    middle width at a time, each cast up where it is used (a wide FFN's
    float32 copy would be 1.6 GB)."""
    d, n = w1.shape
    chunk = _query_blocks(n)
    cols = lambda w: w.reshape(d, n // chunk, chunk).transpose(1, 0, 2)  # noqa: E731

    def one_chunk(acc, ws):
        c1, c3, c2 = ws
        return acc + swiglu(x, cast(c1), cast(c3), cast(c2)), None

    return jax.lax.scan(one_chunk, jnp.zeros_like(x),
                        (cols(w1), cols(w3), w2.reshape(n // chunk, chunk,
                                                        d)))[0]


def _in_blocks(fn, x):
    """``fn`` over ``x`` [B,T,d] a block of positions at a time."""
    b, t, d = x.shape
    block = _query_blocks(t)
    if block == t:
        return fn(x)
    parts = jax.lax.map(fn, x.reshape(b, t // block, block, d)
                        .transpose(1, 0, 2, 3))
    return parts.transpose(1, 0, 2, 3).reshape(b, t, -1)


def router_scores(x, p):
    return jax.nn.sigmoid(x @ _f32(p["router"]))


def route(x, p, config: dict):
    """(experts [..., k], weights [..., k], margin [...]) of tokens ``x``
    [..., d]. ``margin`` is how far the k-th largest score among the kept
    groups lies above the next one there: where it is under an
    implementation's rounding, that implementation may choose otherwise and
    is not wrong."""
    k = config["num_experts_per_tok"]
    n_group, keep = config.get("n_group", 1), config.get("topk_group", 1)
    s = router_scores(x, p)
    choice = s
    if n_group > 1:
        grouped = s.reshape(s.shape[:-1] + (n_group, -1))
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        _, kept = jax.lax.top_k(group_score, keep)             # [..., keep]
        kept_mask = jnp.any(
            kept[..., None] == jnp.arange(n_group), axis=-2)    # [..., G]
        choice = jnp.where(kept_mask[..., None], grouped,
                           -jnp.inf).reshape(s.shape)
    top, experts = jax.lax.top_k(choice, k + 1)
    margin = top[..., k - 1] - top[..., k]
    experts = experts[..., :k]
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if config.get("norm_topk_prob", True):
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts, weights * config.get("routed_scaling_factor", 1.0), margin


def routed_experts(x, p, config: dict, experts_held=None):
    """The routed experts' part of tokens ``x`` [B,T,d] (the held experts'
    alone under ``experts_held``), the experts each token chose [B,T,k] and
    the choice's margin [B,T]."""
    experts, weights, margin = route(x, p, config)
    lo = 0 if experts_held is None else experts_held[0]

    def one_expert(acc, args):
        e, w1, w3, w2 = args
        gate = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        return acc + gate[..., None] * swiglu_in_columns(
            x, w1, w3, w2, _expert_weight), None

    held = lo + jnp.arange(p["w1"].shape[0])
    out, _ = jax.lax.scan(one_expert, jnp.zeros_like(x),
                          (held, p["w1"], p["w3"], p["w2"]))
    return out, experts, margin


def shared_expert(x, p):
    return swiglu_in_columns(x, p["shared_gate"]["kernel"],
                             p["shared_up"]["kernel"],
                             p["shared_out"]["kernel"])


def forward(params, input_ids, config: dict, *, experts_held=None,
            seen_positions: int = 0, seen_attention: int = 0):
    """Logits [B,T,V] in float32 at the highest matmul precision over the
    rows of the vocabulary the parameter tree holds. With ``seen_positions``
    > 0 also, for the sequence's first that many positions and stacked over
    the expert layers, what the routed experts were given (``inputs``
    [E,B,n,d]: the normalised state), and for its first ``seen_attention``
    positions what each attention was given (``attn_in`` [L,B,m,d]); a
    caller holds a layer to :func:`routed_experts` and
    :func:`latent_attention` on those (attention is causal: its first m
    outputs are those of the first m inputs)."""
    eps = config["rms_norm_eps"]
    n, m = seen_positions, seen_attention
    layers, dense = config["num_hidden_layers"], config["first_k_dense_replace"]
    b = input_ids.shape[0]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["token_embed"]["embedding"][input_ids])
        d = x.shape[-1]
        # what was seen is written layer by layer into its place: a list
        # stacked at the end would keep every layer's whole state alive
        seen = {"attn_in": jnp.zeros((layers, b, m, d)),
                "inputs": jnp.zeros((layers - dense, b, n, d))}

        for i in range(layers):
            p = params[f"layer_{i}"]
            h = rms_norm(x, p["ln1"]["scale"], eps)
            seen["attn_in"] = seen["attn_in"].at[i].set(h[:, :m])
            x = x + latent_attention(h, p["attention"], config)
            h = rms_norm(x, p["ln2"]["scale"], eps)
            if i < dense:
                y = swiglu_in_columns(h, p["mlp_gate"]["kernel"],
                                      p["mlp_up"]["kernel"],
                                      p["mlp_out"]["kernel"])
            else:
                seen["inputs"] = seen["inputs"].at[i - dense].set(h[:, :n])
                y = routed_experts(h, p["experts"], config, experts_held)[0]
                if config.get("n_shared_experts", 0):
                    y = y + shared_expert(h, p)
            x = x + y
        x = rms_norm(x, params["ln_f"]["scale"], eps)
        logits = _in_blocks(
            lambda rows: rows @ _f32(params["lm_head"]["kernel"]), x)
    return (logits, seen) if n or m else logits
