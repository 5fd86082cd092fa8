"""Low-precision matmul tier: symmetric per-channel int8 / fp8-e4m3
quantization with bf16 master weights (ISSUE 17; docs/TUNING.md).

This generalizes the ``_kv_quant`` idiom the serving KV cache shipped in
PR 6 (``models/gpt.py``: amax over the contracted axis → one f32 scale
per channel, epsilon floor so all-zero rows round-trip exactly) into the
one quantization module every consumer shares:

- :func:`quantize_channel` / :func:`dequantize` — the (values, scale)
  pair. int8 stores ``clip(round(a/s), -127, 127)``; fp8 stores
  ``(a/s)`` converted to e4m3 with the scale mapping each channel's amax
  to the e4m3 max (±448), so the format's 3 mantissa bits spend their
  dynamic range where the data lives.
- :func:`quantized_matmul` — the non-ring ``tp_dense`` compute path:
  int8×int8 with int32 accumulation (the MXU-native product; XLA's CPU
  emitter supports the same ``preferred_element_type`` contract, which
  is what makes this tier provable on the 8-device sim), or fp8 values
  upcast to f32 for a bf16-accumulated product. The ``custom_vjp``
  backward computes BOTH gradients against the full-precision operands
  (master-weight training: quantization error perturbs the forward only;
  the round/clip never zeroes a gradient).
- :func:`resolve_precision` — the tuner seam. ``""`` is bf16 (status
  quo, no store read); ``"auto"`` asks ``dtf_tpu.tune`` for the banked
  per-(site, shape) winner (quality bound enforced at selection time —
  ``search.select_precision_winner``); an explicit ``"int8"``/``"fp8"``
  wins but warns once when it overrides a measured winner (the same
  ``note_override`` contract as block shapes and spec_k).

The communicated-operand ring twins live in
``ops/collective_matmul.py`` (``ag_matmul_quant`` / ``matmul_rs_quant``
— dequant-after-ppermute, ~2x fewer ring bytes); ``core/comms.tp_dense``
is the single dispatch point that routes between them. Quality bounds
are pinned by tests/test_quant.py; no cell times a quantized matmul yet.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

#: the precision vocabulary tp_dense/TpDense accept. "" = bf16 with no
#: tuner consultation (the pre-ISSUE-17 behavior, byte for byte);
#: "auto" = the kernel-tune resolver decides per (site, shape).
PRECISIONS = ("", "auto", "bf16", "int8", "fp8")

#: e4m3 dynamic range (+/-448): per-channel scales map amax here.
FP8_E4M3_MAX = 448.0
#: amax floor — an all-zero channel quantizes to exact zeros and
#: dequantizes back bitwise (the _kv_quant contract).
_SCALE_EPS = 1e-6


def validate_precision(precision: str, *, what: str = "precision") -> str:
    if precision not in PRECISIONS:
        raise ValueError(
            f"{what}={precision!r} must be one of {PRECISIONS} "
            "('' = bf16, 'auto' = kernel-tune winner; docs/TUNING.md)")
    return precision


def quantize_channel(a: jax.Array, *, axis: int = -1,
                     dtype: str = "int8"):
    """Symmetric per-channel quantization over ``axis``.

    Returns ``(q, scale)`` with ``scale`` keeping ``axis`` as size 1 so
    ``dequantize`` is a plain broadcast multiply. ``dtype``: "int8"
    (round-to-nearest, clip to +/-127) or "fp8" (convert to e4m3 after
    scaling amax to +/-448)."""
    amax = jnp.max(jnp.abs(a.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    if dtype == "int8":
        scale = jnp.maximum(amax, _SCALE_EPS) / 127.0
        q = jnp.clip(jnp.round(a.astype(jnp.float32) / scale),
                     -127, 127).astype(jnp.int8)
        return q, scale
    if dtype == "fp8":
        scale = jnp.maximum(amax, _SCALE_EPS) / FP8_E4M3_MAX
        q = (a.astype(jnp.float32) / scale).astype(jnp.float8_e4m3fn)
        return q, scale
    raise ValueError(f"quantize_channel dtype={dtype!r} must be "
                     "'int8' or 'fp8'")


def dequantize(q: jax.Array, scale: jax.Array, dtype=jnp.float32
               ) -> jax.Array:
    """Broadcast-multiply back to ``dtype`` (the read side of the
    (values, scale) pair)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def rel_err(got: jax.Array, want: jax.Array) -> jax.Array:
    """Frobenius relative error — the quality metric the sweep rows
    bank and ``search.PRECISION_REL_ERR_CEILING`` bounds."""
    w = jnp.asarray(want, jnp.float32)
    g = jnp.asarray(got, jnp.float32)
    denom = jnp.maximum(jnp.linalg.norm(w.reshape(-1)), _SCALE_EPS)
    return jnp.linalg.norm((g - w).reshape(-1)) / denom


def _qmm_impl(precision: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` with both operands quantized along the contraction.

    x [..., t, d] scales per token row, w [d, f] per output column, so
    ``y ≈ (qx @ qw) * sx * sw`` is exact per-channel rescaling."""
    out_dtype = jnp.result_type(x.dtype, w.dtype)
    qx, sx = quantize_channel(x, axis=-1, dtype=precision)
    qw, sw = quantize_channel(w, axis=0, dtype=precision)
    if precision == "int8":
        acc = jnp.einsum("...td,df->...tf", qx, qw,
                         preferred_element_type=jnp.int32)
        acc = acc.astype(jnp.float32)
    else:
        # fp8: values are already rounded to e4m3 — upcast and take the
        # wide-accumulation product (XLA fuses convert∘dot into the fp8
        # MXU path on hardware that has one; the sim just upcasts).
        acc = jnp.einsum("...td,df->...tf", qx.astype(jnp.float32),
                         qw.astype(jnp.float32),
                         preferred_element_type=jnp.float32)
    return (acc * sx * sw).astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _quantized_matmul(precision: str, x: jax.Array, w: jax.Array
                      ) -> jax.Array:
    return _qmm_impl(precision, x, w)


def _qmm_fwd(precision, x, w):
    return _qmm_impl(precision, x, w), (x, w)


def _qmm_bwd(precision, res, dy):
    # master-weight rule: gradients flow against the FULL-precision
    # operands — the quantization perturbs the forward value only, so
    # dx/dw match the plain einsum's gradients bitwise.
    x, w = res
    dx = jnp.einsum("...tf,df->...td", dy, w).astype(x.dtype)
    dw = jnp.einsum("...td,...tf->df", x, dy).astype(w.dtype)
    return dx, dw


_quantized_matmul.defvjp(_qmm_fwd, _qmm_bwd)


def quantized_matmul(x: jax.Array, w: jax.Array, *,
                     precision: str) -> jax.Array:
    """The quantized ``tp_dense`` compute path (non-ring dispatch)."""
    if precision not in ("int8", "fp8"):
        raise ValueError(
            f"quantized_matmul precision={precision!r} must be 'int8' "
            "or 'fp8' (bf16 callers take the plain einsum)")
    return _quantized_matmul(precision, x, w)


def resolve_precision(precision: str, *, parallel: str, d_in: int,
                      d_out: int, dtype: str = "bfloat16",
                      n_devices: int = 1,
                      backend: str | None = None) -> str:
    """Resolve a ``tp_dense`` precision request to a concrete path.

    ``""``/``"bf16"`` short-circuit (no store read on the default
    path); ``"auto"`` returns the banked ``matmul_precision`` winner at
    the nearest (site, shape) — bf16 when nothing is banked; an
    explicit ``"int8"``/``"fp8"`` wins but ``note_override`` warns once
    when it disagrees with a MEASURED winner."""
    validate_precision(precision)
    if precision in ("", "bf16"):
        return "bf16"
    from dtf_tpu.tune import resolver as tune_resolver

    plan = tune_resolver.matmul_precision_plan(
        parallel=parallel, d_in=int(d_in), d_out=int(d_out), dtype=dtype,
        n_devices=int(n_devices), backend=backend)
    if precision == "auto":
        resolved = plan.precision
    else:
        resolved = precision
        tune_resolver.note_override(
            "matmul_precision", f"{parallel}:{d_in}x{d_out}", precision,
            plan.precision, source=plan.source, measured=plan.measured)
    return resolved
