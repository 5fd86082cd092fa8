"""Operations and bytes that the serving steps of a latent-attention decoder
with a share of its routed experts need, computed from shapes and counts.
They count the ALGORITHM's work (the absorbed decode step over the live
latent rows, the pairs that land on the held experts, the touched experts'
weights once), not what an implementation does: a later kernel is read
against the same work.

``config`` is a configuration file of the ``axk1`` family (the source's
``config.json`` keys, with ``n_routed_experts`` the experts held here and
``routed_experts_published`` the router's width); sizes are bytes at
``itemsize`` 2 (bfloat16).
"""

from __future__ import annotations

# the routed experts' counts are the same algorithm at the same keys
from benchmarks.lib.moe_cost import (expected_touched, expert_bytes,  # noqa: F401
                                     grouped_ffn_cost)


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def latent_width(config: dict) -> int:
    """Numbers a cached position holds a layer: the latent row and the
    shared rotary key."""
    return config["kv_lora_rank"] + config["qk_rope_head_dim"]


def attention_params(config: dict) -> int:
    """One layer's latent attention: ``W_qa``, ``W_qb``, ``W_kva``,
    ``W_kvb``, ``W_o`` (the two norms' 2048 weights are not counted)."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, rot = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    q_rank, rank, v = (config["q_lora_rank"], config["kv_lora_rank"],
                       config["v_head_dim"])
    return (d * q_rank + q_rank * heads * (nope + rot)
            + d * latent_width(config) + rank * heads * (nope + v)
            + heads * v * d)


def always_met_params(config: dict) -> tuple[int, int]:
    """Parameters every token meets whatever the routing, as ``(stored in
    bfloat16, stored in float32)``: the attention of every layer, the dense
    FFN of the leading layers, the shared expert of the others and the
    head's slice; the routers (their published width). The embedding rows a
    step looks up are a few KB and are left out."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    dense = config["first_k_dense_replace"] * 3 * d * config[
        "intermediate_size"]
    shared = expert_layers(config) * config["n_shared_experts"] * 3 * d * f
    head = config["vocab_size"] * d
    routers = expert_layers(config) * d * config["routed_experts_published"]
    return (config["num_hidden_layers"] * attention_params(config)
            + dense + shared + head, routers)


def always_read_bytes(config: dict, itemsize: int = 2) -> int:
    """Bytes of :func:`always_met_params`: what every decode step reads."""
    matrices, routers = always_met_params(config)
    return matrices * itemsize + routers * 4


def cache_bytes_per_position(config: dict, itemsize: int = 2) -> int:
    """One position's latent rows over all layers."""
    return latent_width(config) * itemsize * config["num_hidden_layers"]


def latent_attention_cost(config: dict, *, slots: float,
                          cache_positions: float, itemsize: int = 2
                          ) -> tuple[float, float]:
    """``(flops, bytes)`` of ONE layer's absorbed decode attention over
    ``slots`` active slots with ``cache_positions`` cached positions over
    all of them: every head's score over the latent row's whole width and
    its value over the row's first ``kv_lora_rank`` numbers, for each cached
    position and the slot's new one; the live rows read once, a new row
    written, the queries read and the outputs written a slot."""
    heads, width = config["num_attention_heads"], latent_width(config)
    rank = config["kv_lora_rank"]
    positions = cache_positions + slots
    flops = 2.0 * heads * (width + rank) * positions
    nbytes = itemsize * (positions * width
                         + slots * heads * (width + rank))
    return flops, nbytes


def decode_step_cost(config: dict, *, tokens: float, held_pairs: float,
                     held_touched: float, cache_positions: float,
                     itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one whole decode step over ``tokens`` active
    slots: ``held_pairs`` (token, expert) pairs land on ``held_touched`` of
    the held experts in each expert layer (means over layers), with
    ``cache_positions`` cached positions over all slots. Bytes: the
    always-read weights once, the touched held experts once an expert
    layer, the live latent rows once a layer. FLOPs: 2 per parameter a
    token meets (``W_kvb`` absorbed costs what expanding one row would),
    the held pairs' products, and the scores and values over the live
    rows."""
    nbytes = (always_read_bytes(config, itemsize)
              + expert_layers(config) * held_touched
              * expert_bytes(config, itemsize)
              + (cache_positions + tokens)
              * cache_bytes_per_position(config, itemsize))
    attention = latent_attention_cost(
        config, slots=tokens, cache_positions=cache_positions)[0]
    flops = (2.0 * tokens * sum(always_met_params(config))
             + expert_layers(config) * 2.0 * held_pairs
             * expert_bytes(config, 1)
             + config["num_hidden_layers"] * attention)
    return flops, float(nbytes)
