"""Operations and bytes that a routed-expert decoder's serving steps need,
computed from shapes and counts. They count the ALGORITHM's work (the chosen
pairs, the touched experts' weights once), not what an implementation does:
a later kernel is read against the same work.

``config`` is a configuration file of the ``lfm2_moe`` family (the source's
``config.json`` keys); sizes are bytes at ``itemsize`` 2 (bfloat16).
"""

from __future__ import annotations


def expert_layers(config: dict) -> int:
    return config["num_hidden_layers"] - config["num_dense_layers"]


def expert_bytes(config: dict, itemsize: int = 2) -> int:
    """One expert's three matrices: ``3 x hidden x moe_intermediate``."""
    return (3 * config["hidden_size"] * config["moe_intermediate_size"]
            * itemsize)


def operator_params(config: dict, kind: str) -> int:
    """Parameters of one layer's operator. Attention: ``q, k, v`` to
    ``heads`` / ``kv`` / ``kv`` heads and the output projection. Short
    conv: ``hidden -> 3 x hidden`` in, ``hidden -> hidden`` out (the
    ``conv_L_cache`` taps per channel are not counted: 6144 numbers)."""
    d = config["hidden_size"]
    if kind == "conv":
        return 4 * d * d
    d_head = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"]
    return d * d_head * (config["num_attention_heads"] + 2 * kv) + d * d


def always_met_params(config: dict) -> tuple[int, int]:
    """Parameters every token meets whatever the routing, as ``(stored in
    bfloat16, stored in float32)``: the operators, the dense FFN of the
    leading layers and the head (the tied embedding, all of it); the
    routers. The embedding rows a step looks up are a few KB and are left
    out."""
    d = config["hidden_size"]
    ops = sum(operator_params(config, kind)
              for kind in config["layer_types"])
    dense = config["num_dense_layers"] * 3 * d * config["intermediate_size"]
    head = config["vocab_size"] * d
    routers = expert_layers(config) * d * config["num_experts"]
    return ops + dense + head, routers


def always_read_bytes(config: dict, itemsize: int = 2) -> int:
    """Bytes of :func:`always_met_params`: what every decode step reads."""
    matrices, routers = always_met_params(config)
    return matrices * itemsize + routers * 4


def cache_bytes_per_position(config: dict, itemsize: int = 2) -> int:
    """K and V of one position over the attention layers."""
    d_head = config["hidden_size"] // config["num_attention_heads"]
    n_attn = sum(kind != "conv" for kind in config["layer_types"])
    return 2 * config["num_key_value_heads"] * d_head * itemsize * n_attn


def expected_touched(picks: float, experts: int) -> float:
    """Experts that get at least one of ``picks`` uniform choices:
    ``E (1 - (1 - 1/E)^picks)``. An estimate for where no counter reads the
    routing (a prefill chunk); a decode step has the program's counter."""
    return experts * (1.0 - (1.0 - 1.0 / experts) ** picks)


def grouped_ffn_cost(config: dict, *, pairs: float, touched: float,
                     itemsize: int = 2) -> tuple[float, float]:
    """``(flops, bytes)`` of one expert layer's grouped FFN over ``pairs``
    (token, expert) pairs that touch ``touched`` experts: three products of
    ``hidden x moe_intermediate`` per pair, 2 FLOPs a multiply-add; the
    touched experts' weights once, each pair's input row read and output
    row written once (the ``moe_intermediate``-wide middle can stay on the
    chip)."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    flops = 2.0 * pairs * 3 * d * f
    nbytes = touched * expert_bytes(config, itemsize) \
        + 2.0 * pairs * d * itemsize
    return flops, nbytes


def decode_step_cost(config: dict, *, tokens: float, touched: float,
                     cache_positions: float, itemsize: int = 2
                     ) -> tuple[float, float]:
    """``(flops, bytes)`` of one decode step over ``tokens`` active slots
    whose ``tokens x experts_per_tok`` picks touch ``touched`` experts in
    each expert layer (a mean over layers), with ``cache_positions`` valid
    K/V positions over all slots. Bytes: the always-read weights once, the
    touched experts once per expert layer, the valid positions once. FLOPs:
    2 per parameter a token meets, plus the attention's scores and values
    over its slot's positions."""
    k = config["num_experts_per_tok"]
    nbytes = (always_read_bytes(config, itemsize)
              + expert_layers(config) * touched
              * expert_bytes(config, itemsize)
              + cache_positions * cache_bytes_per_position(config, itemsize))
    met = (sum(always_met_params(config))
           + expert_layers(config) * k * expert_bytes(config, 1))
    attn_layers = sum(kind != "conv" for kind in config["layer_types"])
    flops = (2.0 * tokens * met
             + 4.0 * cache_positions * config["hidden_size"] * attn_layers)
    return flops, float(nbytes)
