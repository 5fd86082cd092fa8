"""Controls for the ``lfm2_moe`` family's comparison: the plain reference
(``benchmarks/reference/lfm2_moe.py``) computed WRONG on purpose, so that a
run of the cell has to come out ``correct: false``. The program is left as
it is: the distance between a sound program and a faulty reference is the
distance between a faulty program and the sound reference, and this side of
it fits beside 10 GB of weights.

    python3 benchmarks/tools/lfm2_faults.py <fault> --workload \
        lfm2-serve-closed32 --seed <n> --seconds 30 --trace 0

runs ``benchmarks/run.py`` with the named fault in the reference. The
family's limits are set between such readings and the sound program's
(``families/lfm2_moe.py``; PERF.md section 6); the tests apply the same
faults at rehearsal size.

- ``int8_weights`` / ``fp8_weights``: every stored-bfloat16 matrix through 8
  bits (int8: one scale per output column; float8 e4m3: one scale per
  matrix) — the nearest precisions below the configuration's;
- ``bf16_router``: the router's product and scores in bfloat16;
- ``stale_conv_column``: the short conv reads its columns one position late,
  as a decode state that lost its newest column would.
"""

from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks.reference import lfm2_moe as ref  # noqa: E402


def _stored_low(x) -> bool:
    return x.ndim >= 2 and x.dtype == jnp.bfloat16


def _int8(x):
    wide = jnp.asarray(x, jnp.float32)
    if not _stored_low(x):
        return wide
    scale = jnp.max(jnp.abs(wide), axis=-2, keepdims=True) / 127.0
    return jnp.round(wide / scale) * scale


def _fp8(x):
    wide = jnp.asarray(x, jnp.float32)
    if not _stored_low(x):
        return wide
    scale = jnp.max(jnp.abs(wide)) / 448.0       # e4m3's largest number
    return (wide / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _route_bf16(x, p, *, top_k, norm_topk_prob, scale):
    low = jnp.bfloat16
    logits = jnp.dot(x.astype(low), p["router"].astype(low),
                     preferred_element_type=jnp.float32).astype(low)
    s = jax.nn.sigmoid(logits).astype(jnp.float32)
    choice = s + jnp.asarray(p["expert_bias"], jnp.float32) \
        if "expert_bias" in p else s
    top, experts = jax.lax.top_k(choice, top_k + 1)
    experts = experts[..., :top_k]
    weights = jnp.take_along_axis(s, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts, weights * scale, top[..., top_k - 1] - top[..., top_k]


def _short_conv_late(x, p, *, taps):
    t = x.shape[1]
    gate_b, gate_c, xs = jnp.split(
        x @ ref._f32(p["in_proj"]["kernel"]), 3, axis=-1)
    z = jnp.pad(gate_b * xs, ((0, 0), (taps, 0), (0, 0)))    # one too many
    w = ref._f32(p["conv_w"])
    c = sum(w[j] * z[:, j:j + t] for j in range(taps))
    return (gate_c * c) @ ref._f32(p["out_proj"]["kernel"])


#: fault -> (attribute of the reference module, its faulty stand-in)
FAULTS = {
    "int8_weights": ("_f32", _int8),
    "fp8_weights": ("_f32", _fp8),
    "bf16_router": ("route", _route_bf16),
    "stale_conv_column": ("short_conv", _short_conv_late),
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
