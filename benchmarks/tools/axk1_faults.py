"""Controls for the ``axk1`` family's comparison: the plain reference
(``benchmarks/reference/axk1.py``) computed WRONG on purpose, so that a run
of the cell has to come out ``correct: false``. The program is left as it
is: the distance between a sound program and a faulty reference is the
distance between a faulty program and the sound reference, and this side of
it fits beside 8 GB of weights.

    python3 benchmarks/tools/axk1_faults.py <fault> --workload \
        axk1-serve-closed32-doc16k --seed <n> --seconds 30 --trace 0

runs ``benchmarks/run.py`` with the named fault in the reference. The
family's limits are set between such readings and the sound program's
(``families/axk1.py``; ``benchmarks/AXK1.md``); the tests apply the same
faults at rehearsal size.

- ``bf16_router``: the router's product and scores in bfloat16;
- ``int8_experts``: every routed expert's matrix through 8 bits, one scale
  per output column — the nearest precision below the configuration's;
- ``no_yarn_scale``: the softmax scale without YaRN's ``m^2``;
- ``late_rope_key``: the shared rotary key turned one position late, as a
  cache that wrote the row before advancing its position would;
- ``no_group_limit``: a plain top-8 of all 192 experts;
- ``unnormalised_latent``: keys and values expanded from ``c_kv`` without
  its RMSNorm, as a cache that stored the raw compression would read;
- ``int8_latent``: every cached row through 8 bits, the normalised ``c_kv``
  and the rotated ``k_r`` each with one scale a position, as an 8-bit
  latent cache would hold them — the nearest precision below the
  configuration's in what this cell is for.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.reference import axk1 as ref  # noqa: E402

_sound_route = ref.route


def _int8(x):
    wide = jnp.asarray(x, jnp.float32)
    scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
    return jnp.round(wide / scale) * scale


def _int8_rows(c_kv, k_rope):
    def through_8_bits(rows):
        scale = jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0
        return jnp.round(rows / scale) * scale

    return through_8_bits(c_kv), through_8_bits(k_rope)


def _scores_bf16(x, p):
    low = jnp.bfloat16
    logits = jnp.dot(x.astype(low), p["router"].astype(low),
                     preferred_element_type=jnp.float32).astype(low)
    return jax.nn.sigmoid(logits).astype(jnp.float32)


def _route_without_groups(x, p, config):
    return _sound_route(x, p, {**config, "n_group": 1, "topk_group": 1})


def _plain_softmax_scale(config):
    return (config["qk_nope_head_dim"] + config["qk_rope_head_dim"]) ** -0.5


#: fault -> (attribute of the reference module, its faulty stand-in)
FAULTS = {
    "bf16_router": ("router_scores", _scores_bf16),
    "int8_experts": ("_expert_weight", _int8),
    "no_yarn_scale": ("softmax_scale", _plain_softmax_scale),
    "late_rope_key": ("key_positions",
                      lambda t: jnp.arange(t, dtype=jnp.float32) + 1.0),
    "no_group_limit": ("route", _route_without_groups),
    "unnormalised_latent": ("normalise_latent",
                            lambda c_kv, scale, eps: c_kv),
    "int8_latent": ("cached_rows", _int8_rows),
}


def apply(fault: str):
    """Put ``fault`` into the reference module; returns what undoes it."""
    name, wrong = FAULTS[fault]
    sound = getattr(ref, name)
    setattr(ref, name, wrong)
    return lambda: setattr(ref, name, sound)


if __name__ == "__main__":
    from benchmarks import run

    apply(sys.argv[1])
    raise SystemExit(run.main(sys.argv[2:]))
