"""The program's latent-attention sparse decoder (``model_type`` ``axk1``:
A.X-K1, the key set of DeepSeek-V3) as the serve driver runs it: latent
attention with a latent cache in every layer, a dense SwiGLU FFN in the
leading layer, and in the rest one shared expert beside dropless routed
experts under group-limited choice, of which this chip holds a share
(``experts_held``) — on the flat GPT model (``dtf_tpu.models.gpt``) through
``DecodeEngine`` like any other model.

A configuration file of this family holds the source's own ``config.json``
keys; :func:`model_config` maps them onto ``GPTConfig``. ``n_routed_experts``
counts the experts HELD here and ``routed_experts_published`` the router's
width (model-configs guide, section 4). Serving only: the program has no
loss for a dropless expert layer (ROADMAP.md), so there is no
``build_train``.
"""

from __future__ import annotations

import dataclasses
import functools
import types

import jax
import jax.numpy as jnp

from benchmarks.families.lfm2_moe import (LIFTED_SHORTFALL, LIKELY_RANK_SHARE,
                                          TOKEN_LOGIT_TOL, _note_check, emitted_run,
                                          holds, init_params, meets,
                                          pooled_within, worst_readings)
from benchmarks.reference import axk1 as ref
from dtf_tpu.models import gpt
from dtf_tpu.parallel import moe

#: config.json key -> GPTConfig field (the rest: :func:`model_config`)
KEYS = {"hidden_size": "d_model", "num_hidden_layers": "layers",
        "num_attention_heads": "heads", "intermediate_size": "d_ff",
        "vocab_size": "vocab_size", "rms_norm_eps": "norm_eps",
        "first_k_dense_replace": "dense_layers", "rope_theta": "rope_theta"}

#: config.json key -> ExpertsConfig field
EXPERT_KEYS = {"routed_experts_published": "num_experts",
               "num_experts_per_tok": "top_k",
               "moe_intermediate_size": "d_ff",
               "norm_topk_prob": "norm_topk_prob",
               "routed_scaling_factor": "routed_scaling_factor",
               "n_group": "n_group", "topk_group": "topk_group"}

#: config.json key -> LatentAttentionConfig field
LATENT_KEYS = {"q_lora_rank": "q_rank", "kv_lora_rank": "kv_rank",
               "qk_nope_head_dim": "nope_dim", "qk_rope_head_dim": "rope_dim",
               "v_head_dim": "v_dim"}

#: ``rope_scaling`` key -> LatentAttentionConfig field
YARN_KEYS = {"factor": "yarn_factor",
             "original_max_position_embeddings": "yarn_original_len",
             "beta_fast": "yarn_beta_fast", "beta_slow": "yarn_beta_slow",
             "mscale": "yarn_mscale", "mscale_all_dim": "yarn_mscale_all_dim"}

#: WHAT DECIDES ``correct`` HERE, and why not the serve driver's rule alone
#: (``drivers/serve.py``: every emitted token within ``TOKEN_LOGIT_TOL`` 0.15
#: of the float32 reference's arg-max): as in ``families/lfm2_moe.py``, a
#: top-8 choice among near-equal scores flips on bfloat16's rounding of the
#: state, the flipped token's state moves, and a share of emitted tokens
#: ends up outside 0.15 though nothing is wrong. The reference is NOT told
#: what the program chose: it routes for itself from token ids and weights
#: alone, and four limits decide. Each lies between a reading of the sound
#: program and readings of faults put into the REFERENCE
#: (``tools/axk1_faults.py``), all on the v5e at the cell's widths in one
#: call (``tools/axk1_calibrate.py``; both readings of each limit are in
#: ``benchmarks/AXK1.md`` and PERF.md section 6):
#:
#: 1. :data:`LAYER_ERROR_LIMIT` — the precision of the routed experts. Each
#:    expert layer of the PROGRAM (``DroplessMoE`` with its share of the
#:    experts: router, group choice, grouping, the ``dtf_moe_gmm`` kernel) is
#:    given the reference's own input to that layer, rounded to bfloat16 so
#:    that both see the same numbers, and its output is held against the
#:    reference's share for that input: per position that met a held expert,
#:    the error's norm over the output's norm; the median, the worst layer.
#:    Sees 8-bit expert weights.
#: 2. :data:`REROUTED_SHARE_LIMIT` — the router. On the same input the
#:    program's ``route_topk`` over ``router_scores`` (float32, all 192
#:    outputs, 8 groups) chooses as the reference does; the share of
#:    (position, expert layer) pairs whose chosen sets differ. Sees a
#:    bfloat16 router and a dropped group limit.
#: 3. :data:`ATTN_ERROR_LIMIT` — the latent attention's equations ON THE
#:    ENGINE'S PATH. The program's ``LatentAttention`` is given the
#:    reference's own input to each layer's attention over the sequence's
#:    first :data:`ATTN_POSITIONS` positions (where a key's scale is not
#:    averaged away over thousands of attended positions) THROUGH ITS CACHE,
#:    as the engine applies it (:func:`attention_through_the_cache`): a
#:    first chunk into an empty cache, a chunk that continues it (the cached
#:    rows read back and expanded, the slab written), then one token a step
#:    (the absorbed form; ``dtf_mla_decode_attn`` where it engages). Median
#:    error norm over output norm of each of the three stretches that holds
#:    :data:`ATTN_MIN_POSITIONS` or more of the sequence's positions; the
#:    worst stretch of the worst layer. Sees the softmax scale without YaRN's
#:    ``m^2``, a rotary key one position late, a latent row expanded without
#:    its RMSNorm, and cached rows held in 8 bits.
#: 4. :data:`WITHIN_LIMIT` — the engine's path (positions, the latent cache,
#:    chunking, the absorbed step, slots): the share of ALL the judged
#:    requests' emitted tokens within the driver's 0.15 of the reference's
#:    arg-max, pooled over the run's sample, as in ``families/lfm2_moe.py``
#:    (a request's own share, with a bonus for few tokens, passed a request
#:    of 2 altered tokens at the rehearsal's sizes).
#:
#: Where limits 1-3 hold for a request, its tokens outside 0.15 (routed
#: otherwise, not wrong) are handed to the driver :data:`LIFTED_SHORTFALL`
#: under the row's maximum; where one does not, the logits are NaN and every
#: token counts as outside. Limit 4 is the run's: :func:`build_serve`'s
#: ``verdict`` holds it once every request is judged. Each request prints
#: what was read as a ``# check`` note.
LAYER_ERROR_LIMIT = 0.008
REROUTED_SHARE_LIMIT = 0.002
ATTN_ERROR_LIMIT = 0.0095
WITHIN_LIMIT = 0.8
#: each reading that decides a request: (its limit, ``max`` where the
#: reading must stay at or under it, ``min`` where at or over)
LIMITS = {"layer_error": (LAYER_ERROR_LIMIT, max),
          "rerouted_share": (REROUTED_SHARE_LIMIT, max),
          "attn_error": (ATTN_ERROR_LIMIT, max)}
#: each reading that decides the run: a request's, the worst over the
#: sample, and the pooled share
RUN_LIMITS = {**LIMITS, "within_share": (WITHIN_LIMIT, min)}
#: positions of a sequence whose expert layers are compared (from its start)
LAYER_POSITIONS = 2048
#: positions of a sequence whose attention outputs are compared: three
#: eighths a first chunk, three eighths a continuing chunk, the rest decoded
ATTN_POSITIONS = 64
#: a stretch is read only where it holds this many of the sequence's
#: positions: the median of a few swings past the limit (a CPU rehearsal's
#: sequences of 26 or 50 positions read up to 0.0113; PERF.md section 7).
#: The cell's sequences are 544 positions or more, so it reads all three
ATTN_MIN_POSITIONS = 8
#: positions of the cache those reach the program's attention through: whole
#: tiles of the decode kernel, one block of the continuing chunk's loop
ATTN_CACHE_LEN = 1024


def model_config(config: dict) -> gpt.GPTConfig:
    if config.get("attention_bias") or config.get("tie_word_embeddings"):
        raise ValueError("the program's latent attention has no bias and "
                         "this family's head is untied")
    if config["scoring_func"] != "sigmoid" or config["moe_layer_freq"] != 1:
        raise ValueError("sigmoid scores and an expert layer in every layer "
                         "past the dense ones are what the program runs")
    held = tuple(config["experts_held"])
    if held[1] - held[0] != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here: "
                         f"experts_held={held}")
    lo, hi = config["vocab_slice"]
    if hi - lo != config["vocab_size"]:
        raise ValueError("vocab_size counts the rows of the slice held here")
    experts = moe.ExpertsConfig(
        **{ours: config[theirs] for theirs, ours in EXPERT_KEYS.items()},
        use_expert_bias=False, experts_held=held)
    latent = gpt.LatentAttentionConfig(
        **{ours: config[theirs] for theirs, ours in LATENT_KEYS.items()},
        **{ours: config["rope_scaling"][theirs]
           for theirs, ours in YARN_KEYS.items()})
    return gpt.GPTConfig(
        **{ours: config[theirs] for theirs, ours in KEYS.items()},
        layer_kinds=("mla",) * config["num_hidden_layers"], latent=latent,
        experts=experts,
        shared_expert_ff=(config["n_shared_experts"]
                          * config["moe_intermediate_size"]),
        norm="rmsnorm", ffn="swiglu", use_bias=False, tie_head=False,
        param_dtype=jnp.bfloat16)


def attention_through_the_cache(cfg: gpt.GPTConfig):
    """``apply(p, given) -> (out, stretches)``: the program's latent
    attention with weights ``p`` on ``given`` [B, m, d] as the ENGINE
    applies it, never as one uncached product: the first ``3m/8`` positions
    a chunk into an empty cache, the next ``3m/8`` a chunk that continues it
    (``cache_index`` > 0: the cached rows are read back and expanded, the
    slab written at its place), the rest one token a step on the
    ``slot_decode`` model (absorbed; the decode kernel where it engages),
    each step writing its row. ``stretches`` are the three ranges of
    positions."""
    chunk = gpt.LatentAttention(
        dataclasses.replace(cfg, decode_len=ATTN_CACHE_LEN), None)
    step = gpt.LatentAttention(
        dataclasses.replace(cfg, decode_len=ATTN_CACHE_LEN, slot_decode=True),
        None)

    def apply(p, given):
        b, m, _ = given.shape
        first, second = 3 * m // 8, 3 * m // 4
        cache = {"cached_latent": jnp.zeros(
            (b, cfg.latent.latent_width, ATTN_CACHE_LEN), cfg.dtype),
            "cache_index": jnp.zeros((), jnp.int32)}
        outs = []
        for rows in (given[:, :first], given[:, first:second]):
            out, grown = chunk.apply({"params": p, "cache": cache}, rows,
                                     True, mutable=["cache"])
            cache = grown["cache"]
            outs.append(out)

        def one_token(cache, row):                              # row [B, d]
            out, grown = step.apply({"params": p, "cache": cache},
                                    row[:, None], True, mutable=["cache"])
            return grown["cache"], out[:, 0]

        cache = {**cache, "cache_index": jnp.broadcast_to(
            cache["cache_index"], (b,))}
        _, decoded = jax.lax.scan(one_token, cache,
                                  jnp.swapaxes(given[:, second:], 0, 1))
        outs.append(jnp.swapaxes(decoded, 0, 1))
        return (jnp.concatenate(outs, axis=1),
                ((0, first), (first, second), (second, m)))

    return apply


def median_where(values, mask, least: int):
    """The median of ``values`` where ``mask`` holds, and 0 (nothing read)
    where it holds fewer than ``least`` times. A NaN of the program's counts
    as the worst reading, never as a position left out."""
    values = jnp.where(jnp.isnan(values), jnp.inf, values)
    return jnp.where(jnp.sum(mask) >= least,
                     jnp.nanmedian(jnp.where(mask, values, jnp.nan)), 0.0)


def program_readings(cfg: gpt.GPTConfig, config: dict):
    """``read(params, seen, real) -> dict``: limits 1-3 above, the program's
    layers on what the reference's layers were given (``seen`` of
    ``reference/axk1.py: forward``; ``real`` [B, n] marks the positions of
    the sequence). Shared with ``tools/axk1_calibrate.py``."""
    held = tuple(config["experts_held"])
    first_expert_layer = config["first_k_dense_replace"]
    layer = moe.DroplessMoE(cfg.d_model, cfg.experts, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype)
    attention = attention_through_the_cache(cfg)

    def rel_error(got, want):
        return (jnp.linalg.norm(got.astype(jnp.float32) - want, axis=-1)
                / jnp.maximum(jnp.linalg.norm(want, axis=-1), 1e-30))

    def read(params, seen, real):
        layer_errors, rerouted, near_ties = [], [], []
        for j, given in enumerate(seen["inputs"]):
            p = params[f"layer_{first_expert_layer + j}"]["experts"]
            given = given.astype(cfg.dtype)
            got = layer.apply({"params": p}, given)
            with jax.default_matmul_precision("highest"):
                want, chosen, margin = ref.routed_experts(
                    given.astype(jnp.float32), p, config, held)
            near_ties.append(jnp.sum(real & (margin < 0.002)))
            met = real & (jnp.linalg.norm(want, axis=-1) > 0)
            # a layer whose positions met no held expert reads nothing
            layer_errors.append(median_where(rel_error(got, want), met, 1))
            ours, _ = moe.route_topk(
                moe.router_scores(given.reshape(-1, cfg.d_model),
                                  p["router"]), None, cfg.experts)
            differs = jnp.any(jnp.sort(ours.reshape(chosen.shape), -1)
                              != jnp.sort(chosen, -1), axis=-1)
            rerouted.append(jnp.sum(real & differs))
        attn_errors = []
        for i, given in enumerate(seen["attn_in"]):
            p = params[f"layer_{i}"]["attention"]
            given = given.astype(cfg.dtype)
            got, stretches = attention(p, given)
            with jax.default_matmul_precision("highest"):
                want = ref.latent_attention(given.astype(jnp.float32), p,
                                            config)
            error = rel_error(got, want)
            attn_errors.append(jnp.stack([median_where(
                error[:, lo:hi], real[:, lo:hi], ATTN_MIN_POSITIONS)
                for lo, hi in stretches]))
        attn_errors = jnp.stack(attn_errors)             # [layers, 3]
        pairs = len(rerouted) * jnp.sum(real)
        return {"layer_error": jnp.max(jnp.stack(layer_errors)),
                "rerouted_share": sum(rerouted) / pairs,
                "attn_error": jnp.max(attn_errors),
                "attn_error_decoded": jnp.max(attn_errors[:, 2]),
                "near_tie_share": sum(near_ties) / pairs}

    return read


def build_serve(config: dict):
    """What the serve driver needs: the model's config for the engine, the
    weights from a key (LFM2's family's draw: normal with deviation ``1 /
    sqrt(fan_in)`` leaf by leaf on the device, norm weights 1; this tree has
    no choice bias), and the reference's logits."""
    cfg = model_config(config)
    held = tuple(config["experts_held"])
    read = program_readings(cfg, config)
    notes = []                          # each judged request's readings

    def reference_logits(params, ids):
        """The float32 reference's logits for ``ids`` [B, T] (prompt +
        emitted tokens, zero-padded; the driver hands over one sequence at
        a time, and a batch gets one verdict), judged and handed over as
        :data:`LAYER_ERROR_LIMIT` above says."""
        n = min(LAYER_POSITIONS, ids.shape[1])
        logits, seen = ref.forward(params, ids, config, experts_held=held,
                                   seen_positions=n,
                                   seen_attention=ATTN_POSITIONS)
        pos = jnp.arange(ids.shape[1])[None, :]
        n_seq = jnp.max(jnp.where(ids != 0, pos + 1, 0), axis=1,
                        keepdims=True)
        readings = read(params, seen, (pos < n_seq)[:, :n])

        nxt = jnp.roll(ids, -1, axis=1)        # the token each row foretells
        top = logits.max(axis=-1)
        got = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0]
        short = top - got
        within = short <= TOKEN_LOGIT_TOL
        rank = jnp.sum(logits > got[..., None], axis=-1)
        emitted = emitted_run(rank < LIKELY_RANK_SHARE * logits.shape[-1],
                              pos < n_seq - 1, config["emitted_at_least"])
        n_emitted = jnp.maximum(jnp.sum(emitted), 1)
        within_share = jnp.sum(emitted & within) / n_emitted

        ok = meets(readings, LIMITS)
        jax.debug.callback(
            functools.partial(_note_check, notes), ok=ok, **readings,
            within_share=within_share, within=jnp.sum(emitted & within),
            emitted=n_emitted,
            worst_shortfall=jnp.max(jnp.where(emitted, short, 0.0)))
        # every position outside, not the found run alone: the driver reads
        # the emitted ones, wherever the run was found to begin
        lift = ~within[..., None] & (
            jnp.arange(logits.shape[-1])[None, None, :] == nxt[..., None])
        lifted = jnp.where(lift, (top - LIFTED_SHORTFALL)[..., None], logits)
        return jnp.where(ok, lifted, jnp.nan)

    def readings():
        return {**worst_readings(notes, LIMITS),
                **pooled_within(notes, WITHIN_LIMIT)}

    return types.SimpleNamespace(
        cfg=cfg, vocab_size=cfg.vocab_size,
        init_params=lambda key: init_params(cfg, key),
        reference_logits=reference_logits, limits=RUN_LIMITS,
        readings=readings, verdict=lambda: holds(readings(), RUN_LIMITS))
