"""Latency-hiding collective matmul for Megatron TP — the "collective
einsum" pattern (pjit/TPUv4 paper, arxiv 2204.06514; MLPerf TPU-v3 pod
work, arxiv 1909.09756).

The GSPMD baseline for a Megatron TP pair is a BLOCKING all-gather of the
sequence-sharded activations before the column-parallel matmul and a
blocking reduce-scatter after the row-parallel one: MXU idles while ICI
moves bytes, ICI idles while the MXU multiplies. These ops decompose each
(collective, matmul) pair into a ``ppermute`` ring — the idiom already
proven by :func:`dtf_tpu.ops.attention.ring_attention` and the pipeline's
stage boundary — so each ring step's neighbor transfer overlaps the
previous chunk's matmul under XLA's async collective scheduling:

- :func:`ag_matmul`  — all-gather ∘ matmul for the COLUMN-parallel
  in-projection (q/k/v, mlp_in): token chunks ride the ring, each chunk is
  multiplied by the local weight shard on arrival while the next chunk is
  already in flight.
- :func:`matmul_rs`  — matmul ∘ reduce-scatter for the ROW-parallel
  out-projection (attn_out, mlp_out): per-chunk partial products are
  computed while the partial-sum accumulator rides the ring.

Each op carries a ``custom_vjp`` whose backward is the MIRRORED pattern
(d(ag_matmul) needs a matmul_rs for dx; d(matmul_rs) needs an ag_matmul
for dy; both need a gather-on-contract ring for dW), so the overlap
survives autodiff — ``jax.grad`` of the naive composition would fall back
to blocking collectives.

Layout contract (the Megatron sequence-parallel convention): between
projections, activations are token-sharded over ``('seq', axis)`` — the
residual stream never materializes replicated over the TP axis. Per-shard
shapes inside shard_map:

    ag_matmul : x [..., t, d]   w [d, f]  → y [..., n*t, f]
    matmul_rs : y [..., n*t, f] w [f, d]  → z [..., t, d]

with ``n`` = TP axis size, ``t`` = local token rows, ``d`` full (model)
features, ``f`` this shard's feature slice. Exact parity with the plain
sharded einsum (fwd and grads) is pinned by tests/test_collective_matmul.py
on integer-valued data (bitwise-exact under any summation order).

The shard_map wrappers use ``check_vma=False`` (custom_vjp outputs carry
no varying-manual-axes info — the flash_attention/fused_ce precedent).
VERSION TRIPWIRE: under check_vma=False the transpose convention
"replicated inputs' cotangents are psum'd by shard_map itself" is an
unspecified internal (see ops/fused_ce.py); the exact-parity grad tests in
tests/test_collective_matmul.py are the mandatory guards and MUST stay in
the ``not slow`` tier.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from dtf_tpu.core.comms import ring_perm


def _ring_perm(n: int):
    """Send to the next ring neighbor: device i → i+1 (one ICI hop).

    Delegates to the named builder in ``core/comms.py`` — the one perm
    construction point the collective soundness pass introspects.
    """
    return ring_perm(n)


def _rows(full: jax.Array, src: jax.Array, t: int) -> jax.Array:
    """Row block ``[src*t, src*t + t)`` of the token axis (-2)."""
    return jax.lax.dynamic_slice_in_dim(full, src * t, t, axis=-2)


# ---------------------------------------------------------------------------
# ag_matmul: all-gather overlapped with matmul (column-parallel projection).
# ---------------------------------------------------------------------------

def _ag_matmul_impl(axis_name: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """y = all_gather(x, rows) @ w, as an n-step ppermute ring.

    Step k multiplies the chunk that arrived at step k-1 while ppermute
    already moves it onward — the send does not depend on the matmul, so
    XLA's async scheduler overlaps collective-permute with MXU time. The
    final chunk is folded OUTSIDE the scan (no dead last transfer, same
    shape as ring_attention's local-block-first trick, mirrored).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t = x.shape[-2]
    perm = _ring_perm(n)

    blk0 = jnp.einsum("...td,df->...tf", x, w)
    # zeros buffer derived from blk0 so it inherits the varying-manual-axes
    # type (shard_map's vma checker rejects unvarying scan carries).
    y = jnp.concatenate([blk0 * 0.0] * n, axis=-2)
    y = jax.lax.dynamic_update_slice_in_dim(y, blk0, idx * t, axis=-2)
    if n == 1:
        return y

    def body(carry, k):
        xb, y = carry
        nxt = jax.lax.ppermute(xb, axis_name, perm)   # in flight while...
        src = (idx - k) % n
        blk = jnp.einsum("...td,df->...tf", xb, w)    # ...this multiplies
        y = jax.lax.dynamic_update_slice_in_dim(y, blk, src * t, axis=-2)
        return (nxt, y), None

    # the local block was already folded above (k=0); ring steps 1..n-1
    # receive a neighbor chunk each. The LAST chunk is computed without a
    # trailing send.
    xb = jax.lax.ppermute(x, axis_name, perm)
    if n > 2:
        (xb, y), _ = jax.lax.scan(body, (xb, y), jnp.arange(1, n - 1))
    src_last = (idx - (n - 1)) % n
    blk_last = jnp.einsum("...td,df->...tf", xb, w)
    return jax.lax.dynamic_update_slice_in_dim(
        y, blk_last, src_last * t, axis=-2)


def _ring_dw(axis_name: str, chunk: jax.Array, full: jax.Array) -> jax.Array:
    """dW ring: ``Σ_s chunk_sᵀ @ full[rows s]`` with the chunks riding the
    ring — the gather-on-contracting-dim half of both backward passes.

    ``chunk`` [..., t, c] is this shard's row block of a row-sharded
    tensor; ``full`` [..., n*t, f] has all rows locally. Returns [c, f].
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t = chunk.shape[-2]
    perm = _ring_perm(n)

    acc = jnp.einsum("...tc,...tf->cf", chunk, _rows(full, idx, t))
    if n == 1:
        return acc

    def body(carry, k):
        cb, acc = carry
        nxt = jax.lax.ppermute(cb, axis_name, perm)
        src = (idx - k) % n
        acc = acc + jnp.einsum("...tc,...tf->cf", cb, _rows(full, src, t))
        return (nxt, acc), None

    cb = jax.lax.ppermute(chunk, axis_name, perm)
    if n > 2:
        (cb, acc), _ = jax.lax.scan(body, (cb, acc), jnp.arange(1, n - 1))
    src_last = (idx - (n - 1)) % n
    return acc + jnp.einsum("...tc,...tf->cf", cb,
                            _rows(full, src_last, t))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def ag_matmul(axis_name: str, x: jax.Array, w: jax.Array) -> jax.Array:
    """Column-parallel collective matmul (call inside shard_map).

    ``x`` [..., t, d]: this shard's token rows (tokens sharded over
    ``axis_name``); ``w`` [d, f]: this shard's COLUMN slice of the weight.
    Returns ``all_gather(x) @ w`` [..., n*t, f] with the gather decomposed
    into a ppermute ring overlapped with the per-chunk matmuls. Backward
    is the mirrored pattern: dx via :func:`matmul_rs`'s ring, dw via a
    gather-on-contract ring — no blocking collective appears under grad.
    """
    return _ag_matmul_impl(axis_name, x, w)


def _ag_matmul_fwd(axis_name, x, w):
    return _ag_matmul_impl(axis_name, x, w), (x, w)


def _ag_matmul_bwd(axis_name, res, dy):
    x, w = res
    # dX_full = dy @ wᵀ summed over shards, scattered back to our rows —
    # exactly the matmul_rs pattern with the transposed weight.
    dx = _matmul_rs_impl(axis_name, dy, w.T)
    # dw = all_gather(x)ᵀ @ dy, chunk by chunk as x rides the ring.
    dw = _ring_dw(axis_name, x, dy)
    return dx, dw


ag_matmul.defvjp(_ag_matmul_fwd, _ag_matmul_bwd)


# ---------------------------------------------------------------------------
# matmul_rs: matmul overlapped with reduce-scatter (row-parallel projection).
# ---------------------------------------------------------------------------

def _matmul_rs_impl(axis_name: str, y: jax.Array, w: jax.Array) -> jax.Array:
    """z = reduce_scatter(y @ w, rows), as an n-step ppermute ring.

    The partial-sum accumulator rides the ring while each step's chunk
    matmul computes: step k on device j contributes to row chunk
    ``(j - k - 1) mod n`` (the schedule whose final step lands each fully
    reduced chunk on its owner with no trailing transfer). The add depends
    on the arriving accumulator but the matmul does not — overlap again.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if y.shape[-2] % n:
        raise ValueError(
            f"matmul_rs: token rows {y.shape[-2]} not divisible by "
            f"axis {axis_name!r} size {n}")
    t = y.shape[-2] // n
    if n == 1:
        return jnp.einsum("...tf,fd->...td", y, w)
    perm = _ring_perm(n)

    def partial_for(k):
        tgt = (idx - k - 1) % n
        return jnp.einsum("...tf,fd->...td", _rows(y, tgt, t), w)

    def body(acc, k):
        return jax.lax.ppermute(acc, axis_name, perm) + partial_for(k), None

    acc = partial_for(0)
    if n > 2:
        acc, _ = jax.lax.scan(body, acc, jnp.arange(1, n - 1))
    return jax.lax.ppermute(acc, axis_name, perm) + partial_for(n - 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def matmul_rs(axis_name: str, y: jax.Array, w: jax.Array) -> jax.Array:
    """Row-parallel collective matmul (call inside shard_map).

    ``y`` [..., n*t, f]: full token rows, features sharded over
    ``axis_name``; ``w`` [f, d]: this shard's ROW slice of the weight.
    Returns ``reduce_scatter(y @ w)`` [..., t, d] — this shard's token
    rows of the fully reduced product — with the scatter decomposed into
    a ppermute ring overlapped with the per-chunk matmuls. Backward is
    the mirrored pattern (dy via :func:`ag_matmul`'s ring).
    """
    return _matmul_rs_impl(axis_name, y, w)


def _matmul_rs_fwd(axis_name, y, w):
    return _matmul_rs_impl(axis_name, y, w), (y, w)


def _matmul_rs_bwd(axis_name, res, dz):
    y, w = res
    # dY_j = all_gather(dz) @ w_jᵀ — the mirrored ag_matmul ring.
    dy = _ag_matmul_impl(axis_name, dz, w.T)
    # dw = y[rows s]ᵀ @ dz_s summed over s as dz rides the ring; the ring
    # yields dzᵀ-major [d, f] — transpose to w's [f, d].
    dw = _ring_dw(axis_name, dz, y).T
    return dy, dw


matmul_rs.defvjp(_matmul_rs_fwd, _matmul_rs_bwd)


# ---------------------------------------------------------------------------
# Quantized-communicated-operand rings (ISSUE 17): the same two schedules
# with the tensor that RIDES the ring carried as a (q, scale) pair —
# dequant-after-ppermute — so each hop moves ~2x fewer bytes on the same
# perm. The LOCAL block always computes from the original full-precision
# operand (zero quantization cost for the chunk that never travels), and
# both backwards ride the full-precision rings above (master weights:
# quantization perturbs the forward value only; docs/TUNING.md).
# ---------------------------------------------------------------------------

def _quant_ride(a: jax.Array, qdtype: str):
    """Quantize the ring payload per token row (the contraction axis is
    -1 for ag_matmul's x and matmul_rs's accumulator alike)."""
    from dtf_tpu.ops import quant

    return quant.quantize_channel(a, axis=-1, dtype=qdtype)


def _dequant_ride(q: jax.Array, scale: jax.Array, dtype) -> jax.Array:
    from dtf_tpu.ops import quant

    return quant.dequantize(q, scale, dtype)


def _ppermute_pair(axis_name: str, perm, q: jax.Array, s: jax.Array):
    # two explicit sends (values + scales) so the comms fence prices the
    # scale sideband honestly instead of hiding it in a tuple transfer.
    return (jax.lax.ppermute(q, axis_name, perm),
            jax.lax.ppermute(s, axis_name, perm))


def _ag_matmul_quant_impl(axis_name: str, qdtype: str, x: jax.Array,
                          w: jax.Array) -> jax.Array:
    """:func:`_ag_matmul_impl` with the token chunks riding the ring as
    (int8|fp8, f32-scale) pairs; each chunk dequantizes on arrival."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    t = x.shape[-2]
    perm = _ring_perm(n)

    blk0 = jnp.einsum("...td,df->...tf", x, w)   # local block: exact
    y = jnp.concatenate([blk0 * 0.0] * n, axis=-2)
    y = jax.lax.dynamic_update_slice_in_dim(y, blk0, idx * t, axis=-2)
    if n == 1:
        return y

    qx, sx = _quant_ride(x, qdtype)

    def body(carry, k):
        qb, sb, y = carry
        nq, ns = _ppermute_pair(axis_name, perm, qb, sb)
        src = (idx - k) % n
        blk = jnp.einsum("...td,df->...tf",
                         _dequant_ride(qb, sb, x.dtype), w)
        y = jax.lax.dynamic_update_slice_in_dim(y, blk, src * t, axis=-2)
        return (nq, ns, y), None

    qb, sb = _ppermute_pair(axis_name, perm, qx, sx)
    if n > 2:
        (qb, sb, y), _ = jax.lax.scan(body, (qb, sb, y),
                                      jnp.arange(1, n - 1))
    src_last = (idx - (n - 1)) % n
    blk_last = jnp.einsum("...td,df->...tf",
                          _dequant_ride(qb, sb, x.dtype), w)
    return jax.lax.dynamic_update_slice_in_dim(
        y, blk_last, src_last * t, axis=-2)


def _matmul_rs_quant_impl(axis_name: str, qdtype: str, y: jax.Array,
                          w: jax.Array) -> jax.Array:
    """:func:`_matmul_rs_impl` with the partial-sum accumulator riding
    the ring quantized (re-quantized before each of the n-1 hops — the
    bounded re-rounding the banked rel-err rows price in)."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    if y.shape[-2] % n:
        raise ValueError(
            f"matmul_rs_quant: token rows {y.shape[-2]} not divisible "
            f"by axis {axis_name!r} size {n}")
    t = y.shape[-2] // n
    if n == 1:
        return jnp.einsum("...tf,fd->...td", y, w)
    perm = _ring_perm(n)

    def partial_for(k):
        tgt = (idx - k - 1) % n
        return jnp.einsum("...tf,fd->...td", _rows(y, tgt, t), w)

    def hop(acc, k):
        qa, sa = _quant_ride(acc, qdtype)
        qa, sa = _ppermute_pair(axis_name, perm, qa, sa)
        return _dequant_ride(qa, sa, acc.dtype) + partial_for(k)

    def body(acc, k):
        return hop(acc, k), None

    acc = partial_for(0)
    if n > 2:
        acc, _ = jax.lax.scan(body, acc, jnp.arange(1, n - 1))
    return hop(acc, n - 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def ag_matmul_quant(axis_name: str, qdtype: str, x: jax.Array,
                    w: jax.Array) -> jax.Array:
    """Column-parallel collective matmul with a quantized ring payload
    (call inside shard_map). Same contract as :func:`ag_matmul`; the
    backward IS :func:`ag_matmul`'s (full-precision mirrored rings), so
    gradients are bitwise those of the bf16 overlap path."""
    return _ag_matmul_quant_impl(axis_name, qdtype, x, w)


def _ag_matmul_quant_fwd(axis_name, qdtype, x, w):
    return _ag_matmul_quant_impl(axis_name, qdtype, x, w), (x, w)


def _ag_matmul_quant_bwd(axis_name, qdtype, res, dy):
    return _ag_matmul_bwd(axis_name, res, dy)


ag_matmul_quant.defvjp(_ag_matmul_quant_fwd, _ag_matmul_quant_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def matmul_rs_quant(axis_name: str, qdtype: str, y: jax.Array,
                    w: jax.Array) -> jax.Array:
    """Row-parallel collective matmul with a quantized ring accumulator
    (call inside shard_map). Same contract as :func:`matmul_rs`;
    backward rides the full-precision mirrored rings."""
    return _matmul_rs_quant_impl(axis_name, qdtype, y, w)


def _matmul_rs_quant_fwd(axis_name, qdtype, y, w):
    return _matmul_rs_quant_impl(axis_name, qdtype, y, w), (y, w)


def _matmul_rs_quant_bwd(axis_name, qdtype, res, dz):
    return _matmul_rs_bwd(axis_name, res, dz)


matmul_rs_quant.defvjp(_matmul_rs_quant_fwd, _matmul_rs_quant_bwd)


# ---------------------------------------------------------------------------
# Global-array wrappers (outside shard_map) + the flax drop-in.
# ---------------------------------------------------------------------------

def _token_spec(axis: str) -> P:
    # activations between TP projections are token-sharded over BOTH the
    # context-parallel axis and the TP axis (Megatron-SP layout); size-1
    # axes are free to name, and every mesh carries all five axes.
    return P("data", ("seq", axis), None)


def ag_matmul_sharded(x: jax.Array, w: jax.Array, mesh: Mesh, *,
                      axis: str = "model") -> jax.Array:
    """shard_map boundary for :func:`ag_matmul`.

    ``x`` [B, T, D] token-sharded P('data', ('seq', axis), None);
    ``w`` [D, F] column-sharded P(None, axis). Returns [B, T, F] with F
    sharded over ``axis`` (the activation layout the attention/gelu paths
    already run in).
    """
    return jax.shard_map(
        functools.partial(ag_matmul, axis), mesh=mesh,
        in_specs=(_token_spec(axis), P(None, axis)),
        out_specs=P("data", "seq", axis), check_vma=False)(x, w)


def matmul_rs_sharded(y: jax.Array, w: jax.Array, mesh: Mesh, *,
                      axis: str = "model") -> jax.Array:
    """shard_map boundary for :func:`matmul_rs`.

    ``y`` [B, T, F] with F sharded over ``axis``; ``w`` [F, D]
    row-sharded P(axis, None). Returns [B, T, D] token-sharded
    P('data', ('seq', axis), None) — the residual-stream layout the next
    block's :func:`ag_matmul_sharded` consumes directly, so the only
    remaining gather is the one GSPMD inserts at the LM head.
    """
    return jax.shard_map(
        functools.partial(matmul_rs, axis), mesh=mesh,
        in_specs=(P("data", "seq", axis), P(axis, None)),
        out_specs=_token_spec(axis), check_vma=False)(y, w)


def ag_matmul_quant_sharded(x: jax.Array, w: jax.Array, mesh: Mesh, *,
                            axis: str = "model",
                            precision: str = "int8") -> jax.Array:
    """:func:`ag_matmul_sharded` with the communicated operand quantized
    to ``precision`` ('int8' | 'fp8'); same specs, ~2x fewer ring bytes."""
    return jax.shard_map(
        functools.partial(ag_matmul_quant, axis, precision), mesh=mesh,
        in_specs=(_token_spec(axis), P(None, axis)),
        out_specs=P("data", "seq", axis), check_vma=False)(x, w)


def matmul_rs_quant_sharded(y: jax.Array, w: jax.Array, mesh: Mesh, *,
                            axis: str = "model",
                            precision: str = "int8") -> jax.Array:
    """:func:`matmul_rs_sharded` with the ring accumulator quantized to
    ``precision`` ('int8' | 'fp8'); same specs, ~2x fewer ring bytes."""
    return jax.shard_map(
        functools.partial(matmul_rs_quant, axis, precision), mesh=mesh,
        in_specs=(P("data", "seq", axis), P(axis, None)),
        out_specs=_token_spec(axis), check_vma=False)(y, w)


# ---------------------------------------------------------------------------
# Introspection surface for the collective soundness pass.
# ---------------------------------------------------------------------------

class RingOp(NamedTuple):
    """One custom_vjp ring op as the analyzer sees it: the forward impl,
    the backward impl, and tiny abstract per-shard arguments for each —
    enough to trace both sides at a given axis size and hold the rings to
    the mirrored-ring invariant (``analysis/collective.py``).

    ``fwd`` is called ``fwd(axis_name, *fwd_args(n))``; ``bwd`` is called
    ``bwd(axis_name, *bwd_args(n))`` where the first bwd arg is the saved
    residual tuple and the second the output cotangent.
    """

    name: str
    fwd: object
    bwd: object
    fwd_args: object      # n -> tuple of ShapeDtypeStructs (per-shard)
    bwd_args: object      # n -> (residuals, cotangent) ShapeDtypeStructs


def ring_inventory() -> tuple[RingOp, ...]:
    """Every shipped collective-matmul ring pair, declared for the
    soundness pass. A new ring op MUST register here: the pass verifies
    (a) every perm either side binds is a true ring permutation and (b)
    the backward rides the forward's ring or its inverse — the mirrored-
    ring invariant overlap-under-grad depends on (module docstring).
    Numeric parity stays pinned by tests/test_collective_matmul.py; this
    hook is what lets a *static* pass catch a transposed perm pair or a
    backward that silently fell off the ring."""
    t, d, f = 2, 4, 4
    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    ops = [
        RingOp(
            "ag_matmul", _ag_matmul_impl, _ag_matmul_bwd,
            lambda n: (sds(t, d), sds(d, f)),
            lambda n: ((sds(t, d), sds(d, f)), sds(n * t, f))),
        RingOp(
            "matmul_rs", _matmul_rs_impl, _matmul_rs_bwd,
            lambda n: (sds(n * t, f), sds(f, d)),
            lambda n: ((sds(n * t, f), sds(f, d)), sds(t, d))),
    ]
    # the quantized-payload twins ride the SAME perm fwd and the full-
    # precision rings bwd — registering them holds the dequant-after-
    # ppermute paths to the identical mirrored-ring invariant.
    for qd in ("int8", "fp8"):
        ops.append(RingOp(
            f"ag_matmul_{qd}",
            (lambda axis_name, x, w, _q=qd:
             _ag_matmul_quant_impl(axis_name, _q, x, w)),
            (lambda axis_name, res, dy, _q=qd:
             _ag_matmul_quant_bwd(axis_name, _q, res, dy)),
            lambda n: (sds(t, d), sds(d, f)),
            lambda n: ((sds(t, d), sds(d, f)), sds(n * t, f))))
        ops.append(RingOp(
            f"matmul_rs_{qd}",
            (lambda axis_name, y, w, _q=qd:
             _matmul_rs_quant_impl(axis_name, _q, y, w)),
            (lambda axis_name, res, dz, _q=qd:
             _matmul_rs_quant_bwd(axis_name, _q, res, dz)),
            lambda n: (sds(n * t, f), sds(f, d)),
            lambda n: ((sds(n * t, f), sds(f, d)), sds(t, d))))
    return tuple(ops)
