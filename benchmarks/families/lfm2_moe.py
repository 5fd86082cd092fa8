"""The program's hybrid sparse decoder (``model_type`` ``lfm2_moe``) as the
serve driver runs it: gated short-conv and grouped-query attention layers,
a dense SwiGLU FFN in the leading layers and dropless top-k routed experts
in the rest, on the flat GPT model (``dtf_tpu.models.gpt``) through
``DecodeEngine`` like any other model.

A configuration file of this family holds the source's own ``config.json``
keys; :func:`model_config` maps them onto ``GPTConfig``. Serving only: the
program has no loss for a dropless expert layer yet (ROADMAP.md), so there
is no ``build_train``.
"""

from __future__ import annotations

import functools
import json
import math
import types
import zlib

import jax
import jax.numpy as jnp

from benchmarks.reference import lfm2_moe as ref
from dtf_tpu.models import gpt
from dtf_tpu.parallel import moe

#: config.json key -> GPTConfig field (the rest: :func:`model_config`)
KEYS = {"hidden_size": "d_model", "num_hidden_layers": "layers",
        "num_attention_heads": "heads", "num_key_value_heads": "kv_heads",
        "intermediate_size": "d_ff", "vocab_size": "vocab_size",
        "norm_eps": "norm_eps", "conv_L_cache": "conv_kernel",
        "num_dense_layers": "dense_layers"}

#: config.json key -> ExpertsConfig field
EXPERT_KEYS = {"num_experts": "num_experts", "num_experts_per_tok": "top_k",
               "moe_intermediate_size": "d_ff",
               "norm_topk_prob": "norm_topk_prob",
               "use_expert_bias": "use_expert_bias",
               "routed_scaling_factor": "routed_scaling_factor"}

#: WHAT DECIDES ``correct`` HERE. The serve driver's rule (``drivers/
#: serve.py``: every emitted token within ``TOKEN_LOGIT_TOL`` 0.15 of the
#: float32 reference's arg-max) cannot hold token by token for a model with
#: discrete routing. On the v5e at the cell's widths (PERF.md section 6, PR
#: 26) 9% of the reference's own (token, expert layer) choices lie within
#: 0.002 in score of a tie; bfloat16 activations flip some of them, a flip
#: swaps one expert of four, that moves the token's state by a few percent
#: and its neighbours' through the conv layers, and 17% of emitted tokens
#: end up outside 0.15 (worst 1.7-2.1) though nothing is wrong. The
#: reference is NOT told what the program chose: it routes for itself, and
#: three limits decide. Each lies between a reading of the program as it is
#: and a reading of a fault (``tools/lfm2_faults.py``), all taken on the v5e
#: at the cell's widths on the same 24 requests, 4179 emitted tokens:
#:
#: 1. :data:`LAYER_ERROR_LIMIT` — precision. Each expert layer of the
#:    PROGRAM (``DroplessMoE``: router, grouping, the ``dtf_moe_gmm``
#:    kernel) is given the reference's own input to that layer, rounded to
#:    bfloat16 so that both see the same numbers, and its output is held
#:    against the reference's for that input: per position the error's norm
#:    over the output's norm, the median over positions, the worst layer.
#:    Read 0.00456-0.00458 (every request and layer); against int8 weights
#:    0.01511-0.01516, float8 0.0467.
#: 2. :data:`REROUTED_SHARE_LIMIT` — the router. On the same input a
#:    float32 router chooses as the reference does; a position whose error
#:    is over :data:`REROUTED_ERROR` was routed otherwise (sound errors end
#:    at 0.005, rerouted ones start at 0.3). Read 0 of 145 096 (position,
#:    layer) pairs; against a bfloat16 router 0.0039-0.0087 of a request's.
#: 3. ``within_share_limit``, the configuration's — the engine's path
#:    (positions, both caches, chunking, slots, and the precision of
#:    whatever the engine alone holds): the share of ALL the judged requests' emitted tokens within
#:    the driver's 0.15 of the reference's arg-max, pooled over the run's
#:    sample. Not a request's own share: one routing flip early in an
#:    answer moves the state of every later token through attention, so a
#:    request's tokens are no independent draws, and a sound request read
#:    0.782 against the old per-request limit of 0.79 (PERF.md section 6,
#:    PR 36). The configuration holds the limit, and its ``rehearse``
#:    section another: 8 experts of a 64-wide model at the rehearsal's
#:    sizes flip less often than 64 of 2048 do, and a sound sample there
#:    reads near 1. Both are set between readings of the sound program and
#:    of faults at their own sizes (PERF.md section 6, PR 36). A bfloat16
#:    router and 8-bit weights are what limits 1 and 2 see.
#:
#: The driver can only count tokens outside 0.15 of the logits it is handed,
#: so where limits 1 and 2 hold for a request, its tokens outside it (routed
#: otherwise, not wrong) are handed over :data:`LIFTED_SHORTFALL` under the
#: row's maximum; where one does not hold, the logits are NaN and every
#: token counts as outside. Limit 3 is the run's: :func:`build_serve`'s
#: ``verdict`` holds it once every request is judged. Each request prints
#: what was read as a ``# check`` note. What this cannot see: a fault in the engine that spoils a few
#: tokens of a request and leaves the rest (a pad column in the conv state
#: at the cell's long prompts); tests/test_lfm2.py holds the engine to the
#: reference in float32 on the CPU, where every token agrees.
TOKEN_LOGIT_TOL = 0.15

#: median over positions of |program's expert layer - reference's| /
#: |reference's| on the same input, the worst layer
LAYER_ERROR_LIMIT = 0.008
#: a position whose expert layer's error is over this was routed otherwise
REROUTED_ERROR = 0.1
#: share of a sequence's (position, expert layer) pairs routed otherwise on
#: the same input
REROUTED_SHARE_LIMIT = 0.002
#: each reading that decides a request: (its limit, ``max`` where the
#: reading must stay at or under it, ``min`` where at or over)
LIMITS = {"layer_error": (LAYER_ERROR_LIMIT, max),
          "rerouted_share": (REROUTED_SHARE_LIMIT, max)}
#: a token among the reference's highest sixteenth of the vocabulary is
#: taken for an emitted one when the run of emitted tokens is looked for
LIKELY_RANK_SHARE = 1 / 16
#: positions of a sequence whose expert layers are compared (from its start)
LAYER_POSITIONS = 1024
#: a forgiven token's shortfall as the driver will read it: inside 0.15 and
#: not counted as the reference's arg-max
LIFTED_SHORTFALL = 0.1

#: standard deviation of the per-expert choice bias (``assumed``): a few
#: gaps between neighbouring scores of 64, so the bias really moves choices
EXPERT_BIAS_STD = 0.05


def model_config(config: dict) -> gpt.GPTConfig:
    kinds = tuple("conv" if kind == "conv" else "attn"
                  for kind in config["layer_types"])
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    if config.get("conv_bias"):
        raise ValueError("conv_bias: the program's short conv has no bias")
    experts = moe.ExpertsConfig(
        **{ours: config[theirs] for theirs, ours in EXPERT_KEYS.items()})
    return gpt.GPTConfig(
        **{ours: config[theirs] for theirs, ours in KEYS.items()},
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        layer_kinds=kinds, experts=experts, norm="rmsnorm", ffn="swiglu",
        qk_norm=True, use_bias=False, tie_head=True,
        param_dtype=jnp.bfloat16)


def reference_kwargs(config: dict) -> dict:
    return dict(
        layer_types=config["layer_types"],
        num_dense_layers=config["num_dense_layers"],
        heads=config["num_attention_heads"],
        kv_heads=config["num_key_value_heads"],
        top_k=config["num_experts_per_tok"], norm_eps=config["norm_eps"],
        rope_theta=float(config["rope_parameters"]["rope_theta"]),
        conv_taps=config["conv_L_cache"],
        norm_topk_prob=config["norm_topk_prob"],
        routed_scaling_factor=config["routed_scaling_factor"])


def init_params(cfg: gpt.GPTConfig, key):
    """Random weights on the device, leaf by leaf (one 10 GB program would
    hold every leaf's float32 draw at once): normal with deviation
    ``1 / sqrt(fan_in)``, norm weights 1, the choice bias normal with
    deviation :data:`EXPERT_BIAS_STD`."""
    model = gpt.GPT(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]

    @jax.jit
    def ones(like):
        return jnp.ones(like.shape, like.dtype)

    draw = jax.jit(
        lambda key, std, like: (std * jax.random.normal(
            key, like.shape, jnp.float32)).astype(like.dtype))

    def leaf(path, like):
        name = path[-1].key
        if name == "scale":
            return ones(like)
        if name == "expert_bias":
            std = EXPERT_BIAS_STD
        elif name == "embedding":
            std = 1.0 / math.sqrt(like.shape[-1])
        elif name == "conv_w":
            std = 1.0 / math.sqrt(like.shape[0])
        else:                       # [.., fan_in, fan_out]
            std = 1.0 / math.sqrt(like.shape[-2])
        leaf_key = jax.random.fold_in(
            key, zlib.crc32(jax.tree_util.keystr(path).encode()) % (2 ** 31))
        return draw(leaf_key, jnp.float32(std), like)

    return jax.tree_util.tree_map_with_path(leaf, shapes)


def emitted_run(likely, valid, at_least: int):
    """[B, T] bool: the run of greedy tokens at each sequence's end. The
    driver hands over prompt + emitted tokens without the boundary. A
    prompt's tokens are drawn uniformly, so one is ``likely`` (among the
    reference's highest :data:`LIKELY_RANK_SHARE` of the vocabulary) about
    that often; an emitted token nearly always is, also where the engine
    was routed otherwise. The run is the suffix of ``valid`` positions with
    the most likely over unlikely positions, and never shorter than the
    ``at_least`` tokens every request emits (an engine that emits noise
    has no likely suffix, and is judged on those)."""
    score = jnp.where(valid, jnp.where(likely, 1, -1), 0)
    suffix = jnp.cumsum(score[:, ::-1], axis=1)[:, ::-1]
    start = jnp.argmax(suffix, axis=1)[:, None]
    end = jnp.sum(valid, axis=1, keepdims=True)
    at = jnp.arange(likely.shape[1])[None, :]
    return valid & (at >= jnp.minimum(start, end - at_least))


def _note_check(notes: list, **read):
    read = {name: float(value) for name, value in read.items()}
    notes.append(read)
    print("# check: " + json.dumps(read), flush=True)


def worst_readings(notes: list, limits: dict) -> dict:
    """{name: (worst reading over the requests judged, limit)} for the
    result line: ``limits`` maps a reading to (limit, ``max`` where it must
    stay under it or ``min`` where over); a NaN reading is the worst."""
    if not notes:
        return {}
    out = {}
    for name, (limit, worst) in limits.items():
        values = [note[name] for note in notes]
        out[name] = (math.nan if any(map(math.isnan, values))
                     else worst(values), limit)
    return out


def pooled_within(notes: list, limit: float) -> dict:
    """{"within_share": (share of every judged request's emitted tokens
    within TOKEN_LOGIT_TOL, ``limit``)}; nothing where none was judged."""
    if not notes:
        return {}
    return {"within_share": (sum(n["within"] for n in notes)
                             / sum(n["emitted"] for n in notes), limit)}


def holds(readings: dict, limits: dict) -> bool:
    """The run's verdict on ``readings`` ({name: (reading, limit)}): each on
    its side of ``limits``, and at least one read."""
    return bool(readings) and bool(meets(
        {name: value for name, (value, _) in readings.items()}, limits))


def meets(readings: dict, limits: dict):
    """One request's verdict: each reading of ``limits`` on its limit's
    side (a NaN on neither)."""
    ok = True
    for name, (limit, worst) in limits.items():
        value = readings[name]
        ok = ok & (value <= limit if worst is max else value >= limit)
    return ok


def build_serve(config: dict):
    """What the serve driver needs: the model's config for the engine, the
    weights from a key, and the reference's logits."""
    cfg = model_config(config)
    kwargs = reference_kwargs(config)
    expert_kw = dict(top_k=kwargs["top_k"],
                     norm_topk_prob=kwargs["norm_topk_prob"],
                     scale=kwargs["routed_scaling_factor"])
    expert_layers = range(config["num_dense_layers"],
                          config["num_hidden_layers"])
    layer = moe.DroplessMoE(cfg.d_model, cfg.experts, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype)
    notes = []                          # each judged request's readings
    # each reading that decides the run: a request's, the worst over the
    # sample, and the pooled share
    within_limit = config["within_share_limit"]
    run_limits = {**LIMITS, "within_share": (within_limit, min)}

    def layer_errors(params, inputs):
        """[L, B, N]: per expert layer and position, the program's layer
        against the reference's on the same bfloat16-representable input."""
        errors = []
        for i, given in zip(expert_layers, inputs):
            p = params[f"layer_{i}"]["experts"]
            given = given.astype(cfg.dtype)
            got = layer.apply({"params": p}, given).astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                want = ref.experts_layer(given.astype(jnp.float32), p,
                                         **expert_kw)[0]
            errors.append(jnp.linalg.norm(got - want, axis=-1)
                          / jnp.linalg.norm(want, axis=-1))
        return jnp.stack(errors)

    def reference_logits(params, ids):
        """The float32 reference's logits for ``ids`` [B, T] (prompt +
        emitted tokens, zero-padded; the driver hands over one sequence at
        a time, and a batch gets one verdict), judged and handed over as
        :data:`TOKEN_LOGIT_TOL` above says."""
        logits, seen = ref.forward(params, ids, return_experts=True,
                                   **kwargs)
        pos = jnp.arange(ids.shape[1])[None, :]
        n_seq = jnp.max(jnp.where(ids != 0, pos + 1, 0), axis=1,
                        keepdims=True)

        n = min(LAYER_POSITIONS, ids.shape[1])
        errors = layer_errors(params, seen["inputs"][:, :, :n])
        real = jnp.broadcast_to((pos < n_seq)[None, :, :n], errors.shape)
        layer_error = jnp.max(jnp.nanmedian(
            jnp.where(real, errors, jnp.nan), axis=(1, 2)))
        rerouted = (jnp.sum(real & (errors > REROUTED_ERROR))
                    / jnp.sum(real))

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

        readings = {"layer_error": layer_error, "rerouted_share": rerouted}
        ok = meets(readings, LIMITS)
        jax.debug.callback(
            functools.partial(_note_check, notes), ok=ok, **readings,
            within_share=within_share, within=jnp.sum(emitted & within),
            emitted=n_emitted,
            worst_shortfall=jnp.max(jnp.where(emitted, short, 0.0)),
            near_tie_share=jnp.sum(real & (seen["margin"][:, :, :n] < 0.002))
            / jnp.sum(real))
        # every position outside, not the found run alone: the driver reads
        # the emitted ones, wherever the run was found to begin
        lift = ~within[..., None] & (
            jnp.arange(logits.shape[-1])[None, None, :] == nxt[..., None])
        lifted = jnp.where(lift, (top - LIFTED_SHORTFALL)[..., None], logits)
        return jnp.where(ok, lifted, jnp.nan)

    def readings():
        return {**worst_readings(notes, LIMITS),
                **pooled_within(notes, within_limit)}

    return types.SimpleNamespace(
        cfg=cfg, vocab_size=cfg.vocab_size,
        init_params=lambda key: init_params(cfg, key),
        reference_logits=reference_logits, limits=run_limits,
        readings=readings, verdict=lambda: holds(readings(), run_limits))
