"""Pallas TPU flash attention — fused O(T) -memory attention kernels.

The reference has no attention at all (SURVEY.md §5.7: nothing in
`zjj2wry/distributed-tensorflow` scales sequence length; its models are MNIST
softmax / ResNet / fixed-length BERT). This module is where the TPU-native
framework goes past capability parity: a first-party fused kernel for the
hottest op in the transformer stack, built on Pallas/Mosaic so the MXU sees
[block_q, d] x [d, block_k] matmuls and the softmax statistics never leave
VMEM.

Design (flash-attention-2 style, adapted to the TPU grid model):
- forward: grid (batch*heads, num_q_blocks, num_k_blocks); the k axis is the
  innermost ("arbitrary" = sequential) grid dim, with running max / sum /
  accumulator kept in VMEM scratch that persists across k iterations. Output
  and the logsumexp residual are written on the last k iteration.
- backward: the standard two-kernel split — dq loops k-blocks inside a
  q-block program; dk/dv loop q-blocks inside a k-block program — using the
  saved logsumexp plus delta = rowsum(dO * O) so p is recomputed, never
  materialised at [T, T].
- unaligned T is handled by zero-padding in the wrapper and masking inside
  the kernel (keys beyond t_k get -inf scores; padded query rows are forced
  to p = 0 in the backward so they cannot pollute dk/dv). head_dim is passed
  through as-is — Mosaic handles non-128 lane counts, at some layout cost.

Softmax statistics are float32 regardless of input dtype; p is cast back to
the value dtype for the MXU contraction (the usual bf16 flash recipe).

Runs compiled on TPU (Mosaic) and under ``interpret=True`` on CPU for the
test suite (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512x1024 blocks, picked by a block-shape sweep on a v5e in round 5
# (before PR 1, another JAX; the record is gone, PERF.md §5 keeps the
# figures): seq 8k causal fwd, 512x1024 ran 1.61 ms vs 512x512's 4.44/5.76
# ms, 1024x1024's 2.98 ms and 512x2048's 2.74 ms — with the bwd also
# fastest (9.05 vs 13.4 ms). 128x128 was grid-overhead-bound (~10 TF/s
# flat); doubling only the k-extent halves the grid's inner trip count and
# keeps the f32 score tile at [512,1024] = 2 MB, k/v residents 2x256 KB —
# far under the 16 MiB scoped-VMEM limit. Block args left at 0 resolve
# through dtf_tpu.tune.resolver first (per-shape winners in
# KERNEL_TUNE.json — none is measured for flash on the present chip and
# JAX, so today these defaults are what runs; docs/TUNING.md), and callers
# can still pin per-shape explicitly.
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
_NEG_INF = float("-inf")
_STAT_LANES = 128  # scratch stat arrays are [block_q, 128] (TPU lane width)


def _compiler_params(dims: tuple[str, ...]):
    return pltpu.CompilerParams(dimension_semantics=dims)


def _positions(i, j, block_q, block_k):
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_pos, k_pos


def _score_mask(s, i, j, *, causal, block_q, block_k, t_k, window=0):
    """-inf out invalid (padded-key / future-key / out-of-window) scores."""
    need_k_mask = (t_k % block_k) != 0
    if not (causal or need_k_mask):
        return s
    q_pos, k_pos = _positions(i, j, block_q, block_k)
    mask = k_pos < t_k
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window:
            # sliding window: query t sees keys in (t-window, t]
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return jnp.where(mask, s, _NEG_INF)


def _block_live(i, j, *, causal, window, block_q, block_k):
    """Does (q-block i, k-block j) contain ANY unmasked position? The grid
    skip condition: below-diagonal blocks for causal, plus blocks entirely
    older than the window — this is what makes windowed attention O(T·W)
    instead of O(T²/2)."""
    live = (j * block_k <= i * block_q + block_q - 1) if causal else (j >= 0)
    if causal and window:
        # newest key in block j must be inside the oldest query's window:
        # (j+1)*bk - 1 > i*bq - window  ⇔  some (qp, kp) has qp-kp < window
        live = jnp.logical_and(
            live, (j + 1) * block_k - 1 > i * block_q - window)
    return live


def _kv_sticky_map(*, causal, window, block_q, block_k, num_k):
    """k/v BlockSpec index map for grids iterating (b, i, j): on DEAD
    (i, j) tiles — skipped by ``pl.when(_block_live)`` — point the DMA at
    the q-block's DIAGONAL k-block instead of the dead j. Mosaic elides
    refetches when consecutive steps map to the same block, so dead tiles
    stop burning HBM bandwidth on k/v copies nobody reads (the bundled
    jax flash kernel's trick). The diagonal block is always live: it
    contains a diff==0 position, in-window for any window >= 1."""
    if not causal:
        return lambda b, i, j: (b, j, 0)

    def imap(b, i, j):
        diag = jnp.minimum((i * block_q + block_q - 1) // block_k,
                           num_k - 1)
        live = _block_live(i, j, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)
        return b, jax.lax.select(live, j, diag), 0

    return imap


def _q_sticky_map(*, causal, window, block_q, block_k, num_q, rank4=False):
    """q/do/lse/delta index map for the dkv grid (b, j, i): dead tiles
    point at k-block j's diagonal q-block (ceil((j·bk - bq + 1)/bq),
    computed via the floor identity). Same DMA-elision rationale as
    :func:`_kv_sticky_map`."""
    if not causal:
        if rank4:
            return lambda b, j, i: (b, i, 0, 0)
        return lambda b, j, i: (b, i, 0)

    def imap(b, j, i):
        diag = jnp.minimum((j * block_k) // block_q, num_q - 1)
        live = _block_live(i, j, causal=causal, window=window,
                           block_q=block_q, block_k=block_k)
        i_eff = jax.lax.select(live, i, diag)
        if rank4:
            return b, i_eff, 0, 0
        return b, i_eff, 0

    return imap


def _zero_padded_q_rows(p, i, *, block_q, t_q):
    """Zero p on padded query rows (their lse is -inf ⇒ exp overflows)."""
    if (t_q % block_q) == 0:
        return p
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, p.shape[1]), 0)
    return jnp.where(q_pos < t_q, p, 0.0)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, window,
                block_q, block_k, num_k, t_q, t_k, has_mask):
    mb_ref = rest[0] if has_mask else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[1:] if has_mask else rest
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    run = _block_live(i, j, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(run)
    def _block():
        q, k = q_ref[0], k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _score_mask(s, i, j, causal=causal, block_q=block_q,
                        block_k=block_k, t_k=t_k, window=window)
        if has_mask:
            # additive key-padding bias row (0 valid / -inf padded): the
            # existing -inf machinery (running max, dead-row guards) then
            # handles masked keys identically to causal-masked ones.
            s = s + mb_ref[0, 0][None, :]
        m_prev = m_scr[:, 0:1]
        l_prev = l_scr[:, 0:1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # Fully-masked-so-far rows keep m == -inf; subtracting a 0 stand-in
        # keeps exp() finite (p rows come out 0, alpha comes out 0).
        m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_next = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(j == num_k - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        m = m_scr[:, 0:1]
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        # lse is [bh, num_q, 1, block_q]: the num_q axis is blocked by i so
        # each q-block program owns its own output window (the q grid dim is
        # "parallel" — a shared window revisited across i would be UB on
        # megacore), and the trailing (1, block_q) block dims are full-size
        # (Mosaic requires trailing block dims (8,128)-divisible or full).
        lse_ref[0, 0, 0, :] = lse[:, 0]


def _fwd_kernel_hfold(q_ref, k_ref, v_ref, *rest, sm_scale, causal, window,
                      block_q, block_k, num_k, t_q, t_k, has_mask):
    """Head-folded forward: the grid's bh dim advances ``block_h`` heads per
    step, so one grid step runs block_h batched [bq,d]x[d,bk] MXU
    contractions back-to-back — amortizing the fixed per-step overhead
    (PERF.md §5 measured ~1 us/step vs sub-us of matmul work at d=128) by
    the fold factor. Separate from :func:`_fwd_kernel` on purpose: the 2-D
    kernel is the on-chip-proven default; this one is opt-in
    (``block_h > 1``) until the block sweep measures it.

    Same math as the 2-D kernel with a leading head axis [h, ...]: the
    positional/causal masks are head-independent and numpy-broadcast
    against [h, bq, bk] scores; softmax stats carry an extra leading dim.
    """
    mb_ref = rest[0] if has_mask else None
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest[1:] if has_mask else rest
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    run = _block_live(i, j, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(run)
    def _block():
        q, k = q_ref[...], k_ref[...]            # [h, bq, d], [h, bk, d]
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * sm_scale   # [h, bq, bk]
        s = _score_mask(s, i, j, causal=causal, block_q=block_q,
                        block_k=block_k, t_k=t_k, window=window)
        if has_mask:
            # every folded head shares the batch row (block_h | heads is
            # enforced by the wrapper)
            s = s + mb_ref[0, 0][None, None, :]
        m_prev = m_scr[:, :, 0:1]                # [h, bq, 1]
        l_prev = l_scr[:, :, 0:1]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
        alpha = jnp.exp(m_prev - m_safe)
        p = jnp.exp(s - m_safe)
        l_next = alpha * l_prev + jnp.sum(p, axis=2, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)  # [h, bq, d]
        m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_next, l_scr.shape)

    @pl.when(j == num_k - 1)
    def _finalize():
        l = l_scr[:, :, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[...] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        m = m_scr[:, :, 0:1]
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[:, 0, 0, :] = lse[:, :, 0]


def _mask_bias(kv_mask, b, t_k, block_k):
    """[b, 1, t_k_padded] f32 additive bias: 0 valid, -inf padded key.

    PER-BATCH, not per-(batch*head): every head reads the same row, so the
    kernels' index maps divide the bh grid index by the head count instead
    of materializing h identical copies (which the custom_vjp residuals
    would otherwise keep alive through the backward). Shaped with a size-1
    middle axis so the (1, 1, block_k) BlockSpec's trailing dims are
    (1, block_k) — the 1 is full-size, keeping the block Mosaic-legal
    (same trick as the lse residual layout)."""
    bias = jnp.where(kv_mask, 0.0, _NEG_INF).astype(jnp.float32)
    return _pad(bias.reshape(b, 1, t_k), block_k, axis=2)


def _fwd(q, k, v, mask_bias, *, sm_scale, causal, window, block_q, block_k,
         interpret, block_h=1):
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    num_q = pl.cdiv(t_q, block_q)
    num_k = pl.cdiv(t_k, block_k)
    qp = _pad(q, block_q, axis=1)
    kp = _pad(k, block_k, axis=1)
    vp = _pad(v, block_k, axis=1)
    has_mask = mask_bias is not None

    kern = functools.partial(
        _fwd_kernel_hfold if block_h > 1 else _fwd_kernel,
        sm_scale=sm_scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, num_k=num_k, t_q=t_q, t_k=t_k,
        has_mask=has_mask)
    kv_map = _kv_sticky_map(causal=causal, window=window, block_q=block_q,
                            block_k=block_k, num_k=num_k)
    in_specs = [
        pl.BlockSpec((block_h, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((block_h, block_k, d), kv_map),
        pl.BlockSpec((block_h, block_k, d), kv_map),
    ]
    inputs = [qp, kp, vp]
    if has_mask:
        heads = bh // mask_bias.shape[0]  # bias rows are per-batch
        # folded index b covers heads [b*block_h, (b+1)*block_h) — one
        # batch row serves them all (wrapper enforces block_h | heads)
        in_specs.append(
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j: (b * block_h // heads, 0,
                                          kv_map(b, i, j)[1])))
        inputs.append(mask_bias)
    out, lse = pl.pallas_call(
        kern,
        grid=(bh // block_h, num_q, num_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((block_h, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((block_h, 1, 1, block_q),
                         lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qp.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, num_q, 1, block_q), jnp.float32),
        ],
        scratch_shapes=(
            [pltpu.VMEM((block_h, block_q, _STAT_LANES), jnp.float32),
             pltpu.VMEM((block_h, block_q, _STAT_LANES), jnp.float32),
             pltpu.VMEM((block_h, block_q, d), jnp.float32)]
            if block_h > 1 else
            [pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
             pltpu.VMEM((block_q, _STAT_LANES), jnp.float32),
             pltpu.VMEM((block_q, d), jnp.float32)]),
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_flash_fwd",
    )(*inputs)
    return out[:, :t_q], lse.reshape(bh, num_q * block_q)[:, :t_q]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               sm_scale, causal, window, block_q, block_k, num_k, t_q, t_k,
               has_mask):
    mb_ref = rest[0] if has_mask else None
    dq_ref, dq_scr = rest[1:] if has_mask else rest
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros(dq_scr.shape, dq_scr.dtype)

    run = _block_live(i, j, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(run)
    def _block():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0, 0, :][:, None]
        delta = delta_ref[0, 0, 0, :][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _score_mask(s, i, j, causal=causal, block_q=block_q,
                        block_k=block_k, t_k=t_k, window=window)
        if has_mask:
            s = s + mb_ref[0, 0][None, :]
        # a fully-masked VALID q row has lse == -inf; exp(s - lse) would be
        # exp(-inf + inf) = nan — force p = 0 there (output was 0 too).
        p = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(s - lse))
        p = _zero_padded_q_rows(p, i, block_q=block_q, t_q=t_q)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_k - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                sm_scale, causal, window, block_q, block_k, num_q, t_q, t_k,
                has_mask):
    mb_ref = rest[0] if has_mask else None
    dk_ref, dv_ref, dk_scr, dv_scr = rest[1:] if has_mask else rest
    j, i = pl.program_id(1), pl.program_id(2)  # k-block outer, q-block inner

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, dk_scr.dtype)
        dv_scr[...] = jnp.zeros(dv_scr.shape, dv_scr.dtype)

    # same tile-liveness predicate as fwd/dq (it is symmetric in the tile)
    run = _block_live(i, j, causal=causal, window=window,
                      block_q=block_q, block_k=block_k)

    @pl.when(run)
    def _block():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0, 0, 0, :][:, None]
        delta = delta_ref[0, 0, 0, :][:, None]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale
        s = _score_mask(s, i, j, causal=causal, block_q=block_q,
                        block_k=block_k, t_k=t_k, window=window)
        if has_mask:
            s = s + mb_ref[0, 0][None, :]
        p = jnp.where(jnp.isneginf(lse), 0.0, jnp.exp(s - lse))
        p = _zero_padded_q_rows(p, i, block_q=block_q, t_q=t_q)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def _bwd(q, k, v, mask_bias, out, lse, do, *, sm_scale, causal, window,
         block_q, block_k, interpret):
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    num_q = pl.cdiv(t_q, block_q)
    num_k = pl.cdiv(t_k, block_k)
    has_mask = mask_bias is not None
    # delta = rowsum(dO * O): cheap elementwise+reduce, XLA fuses it fine.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    qp, dop = _pad(q, block_q, 1), _pad(do, block_q, 1)
    kp, vp = _pad(k, block_k, 1), _pad(v, block_k, 1)
    lsep = _pad(lse, block_q, 1).reshape(bh, num_q, 1, block_q)
    deltap = _pad(delta, block_q, 1).reshape(bh, num_q, 1, block_q)
    if has_mask:
        # The residual bias arrived padded to the FORWARD block_k; when
        # the bwd runs its own block_k the k-grid may cover more columns
        # than that pad — slice back to t_k and re-pad for THIS grid, or
        # the last mask block reads out of bounds.
        mask_bias = _pad(mask_bias[:, :, :t_k], block_k, 2)
    mask_in = [mask_bias] if has_mask else []
    heads = bh // mask_bias.shape[0] if has_mask else 1  # bias is per-batch

    def mask_spec(index_map):
        return ([pl.BlockSpec((1, 1, block_k), index_map)]
                if has_mask else [])

    kv_map = _kv_sticky_map(causal=causal, window=window, block_q=block_q,
                            block_k=block_k, num_k=num_k)
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_k=num_k, t_q=t_q, t_k=t_k,
            has_mask=has_mask),
        grid=(bh, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j: (b, i, 0, 0)),
        ] + mask_spec(lambda b, i, j: (b // heads, 0, kv_map(b, i, j)[1])),
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_flash_dq",
    )(qp, kp, vp, dop, lsep, deltap, *mask_in)

    q_map = _q_sticky_map(causal=causal, window=window, block_q=block_q,
                          block_k=block_k, num_q=num_q)
    q_map4 = _q_sticky_map(causal=causal, window=window, block_q=block_q,
                           block_k=block_k, num_q=num_q, rank4=True)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, num_q=num_q, t_q=t_q, t_k=t_k,
            has_mask=has_mask),
        grid=(bh, num_k, num_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, 1, 1, block_q), q_map4),
            pl.BlockSpec((1, 1, 1, block_q), q_map4),
        ] + mask_spec(lambda b, j, i: (b // heads, 0, j)),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_flash_dkv",
    )(qp, kp, vp, dop, lsep, deltap, *mask_in)
    return dq[:, :t_q], dk[:, :t_k], dv[:, :t_k]


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------


def _pad(x, multiple, axis):
    rem = x.shape[axis] % multiple
    if rem == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, multiple - rem)
    return jnp.pad(x, widths)


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash(q, k, v, mask_bias, causal, window, sm_scale, block_q, block_k,
           interpret, block_h, block_q_bwd, block_k_bwd):
    out, _ = _fwd(q, k, v, mask_bias, sm_scale=sm_scale, causal=causal,
                  window=window, block_q=block_q, block_k=block_k,
                  interpret=interpret, block_h=block_h)
    return out


def _flash_fwd(q, k, v, mask_bias, causal, window, sm_scale, block_q,
               block_k, interpret, block_h, block_q_bwd, block_k_bwd):
    out, lse = _fwd(q, k, v, mask_bias, sm_scale=sm_scale, causal=causal,
                    window=window, block_q=block_q, block_k=block_k,
                    interpret=interpret, block_h=block_h)
    return out, (q, k, v, mask_bias, out, lse)


def _flash_bwd(causal, window, sm_scale, block_q, block_k, interpret,
               block_h, block_q_bwd, block_k_bwd, res, do):
    del block_h  # fwd-only lever; the backward keeps the proven 2-D grids
    q, k, v, mask_bias, out, lse = res
    # The backward's two grids stream the OPPOSITE extents from the
    # forward (_dq scans k; _dkv scans q), so the fwd-optimal block shape
    # need not be bwd-optimal — 0 inherits the fwd blocks unless the
    # kernel-tune cache holds a measured pair for the shape.
    dq, dk, dv = _bwd(q, k, v, mask_bias, out, lse, do, sm_scale=sm_scale,
                      causal=causal, window=window,
                      block_q=block_q_bwd or block_q,
                      block_k=block_k_bwd or block_k, interpret=interpret)
    dmb = None if mask_bias is None else jnp.zeros_like(mask_bias)
    return dq, dk, dv, dmb


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_sharded(q, k, v, mesh, *, causal: bool = False,
                            window: int = 0,
                            kv_mask: Optional[jax.Array] = None,
                            block_h: int = 0,
                            interpret: bool = False) -> jax.Array:
    """Per-shard flash kernel over a (data, model) mesh: batch/head dims are
    partitioned, seq stays whole per shard. Pallas calls can't be
    GSPMD-partitioned from outside, so the shard_map boundary is where the
    parallelism lives. ``mesh=None`` falls through to the plain kernel.
    Shared by the GPT (causal) and BERT (kv_mask) model paths.

    check_vma=False: pallas_call out_shapes carry no varying-manual-axes
    info, so shard_map's vma checker can't type them.
    """
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        return flash_attention(q, k, v, causal=causal, window=window,
                               kv_mask=kv_mask, block_h=block_h,
                               interpret=interpret)
    if mesh.shape.get("seq", 1) > 1:
        # the in_specs below replicate the sequence dim, so forcing flash
        # on a seq-sharded mesh would silently all-gather T and compute the
        # whole attention redundantly on every seq shard (ADVICE r3) —
        # reject explicitly, mirroring the zigzag+window rejection
        raise ValueError(
            "flash attention keeps the sequence whole per shard; on a mesh "
            f"with seq={mesh.shape['seq']} use attn_impl='ring'/'zigzag' "
            "(full causal) or the halo path (windowed) instead")
    # fewer sequences than data shards (a two-sequence evaluation on a
    # data-parallel mesh of four) cannot be sharded at all: such a batch
    # stays whole on every data shard. Any other batch the axis does not
    # divide is a mis-sized training batch, and the shard_map refuses it.
    batch_axis = None if q.shape[0] < mesh.shape.get("data", 1) else "data"
    spec = P(batch_axis, "model", None, None)
    if kv_mask is None:
        fn = functools.partial(flash_attention, causal=causal, window=window,
                               block_h=block_h, interpret=interpret)
        return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)

    def fn(q, k, v, m):
        return flash_attention(q, k, v, causal=causal, window=window,
                               kv_mask=m, block_h=block_h,
                               interpret=interpret)

    return jax.shard_map(
        fn, mesh=mesh, in_specs=(spec, spec, spec, P(batch_axis, None)),
        out_specs=spec, check_vma=False)(q, k, v, kv_mask)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = False,
                    window: int = 0,
                    kv_mask: Optional[jax.Array] = None,
                    sm_scale: Optional[float] = None,
                    block_q: int = 0,
                    block_k: int = 0,
                    block_h: int = 0,
                    block_q_bwd: int = 0,
                    block_k_bwd: int = 0,
                    interpret: bool = False) -> jax.Array:
    """Fused attention. [B, H, T, D] → [B, H, T, D]; differentiable.

    ``sm_scale`` defaults to ``1/sqrt(head_dim)`` (the *original* head_dim,
    before any internal padding). Unaligned T is padded+masked internally.

    ``kv_mask``: [B, T_k] bool, True = valid key (the BERT/encoder padding
    mask). Rides through the kernels as a precomputed additive -inf bias
    row. A query row whose keys are ALL masked produces output 0 and
    gradient 0 (same contract as ``dense_attention``'s dead-row handling).

    ``window > 0`` (requires ``causal``): sliding-window locality — query t
    attends keys in (t-window, t]. Blocks entirely outside the window are
    SKIPPED at the grid level, so compute is O(T·window) not O(T²/2).

    ``block_h > 1`` (opt-in): fold that many heads into each forward grid
    step — batched MXU contractions amortize the fixed per-step overhead
    (see :func:`_fwd_kernel_hfold`). Must divide ``heads``. Forward only;
    the backward keeps its proven 2-D grids.

    ``block_q_bwd`` / ``block_k_bwd`` (0 = auto): separate block shape
    for the two backward kernels. The backward streams the opposite
    extents from the forward (``_dq`` scans k-blocks, ``_dkv`` scans
    q-blocks), so the fwd-optimal shape is not necessarily bwd-optimal
    (the train cells' ``flash_bwd_roofline`` measures the backward).

    Block arguments left at 0 resolve through the kernel-tune cache
    (:mod:`dtf_tpu.tune.resolver` — the banked per-shape on-chip
    winners; docs/TUNING.md), falling back to the module defaults.
    Explicit values always win; an explicit value that differs from a
    MEASURED winner warns once. When the forward blocks are pinned
    explicitly, unset backward blocks keep the old inherit-the-fwd
    contract instead of mixing a tuned bwd with a pinned fwd.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, H, T, D], got shape {q.shape}")
    if window < 0 or (window and not causal):
        raise ValueError(
            f"window={window} must be >= 0 and requires causal=True")
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    if not (block_q and block_k and block_h):
        from dtf_tpu.tune import resolver as _tune

        plan = _tune.flash_plan(
            seq=t_q, heads=h, head_dim=d, dtype=jnp.dtype(q.dtype).name,
            causal=causal, window=int(window),
            n_devices=jax.device_count(),
            backend=jax.default_backend())
        for what, explicit, won in (("block_q", block_q, plan.block_q),
                                    ("block_k", block_k, plan.block_k)):
            if explicit:
                _tune.note_override("flash_fwd", what, explicit, won,
                                    source=plan.source,
                                    measured=plan.measured)
        if not (block_q or block_k or block_q_bwd or block_k_bwd):
            # fully-auto forward: the banked backward winner applies;
            # a pinned forward keeps bwd on the inherit contract.
            block_q_bwd, block_k_bwd = plan.block_q_bwd, plan.block_k_bwd
        block_q = block_q or plan.block_q
        block_k = block_k or plan.block_k
        block_h = block_h or plan.block_h
    block_h = block_h or 1
    if block_h < 1 or h % block_h:
        raise ValueError(f"block_h={block_h} must be >= 1 and divide "
                         f"heads={h}")
    scale = float(sm_scale) if sm_scale is not None else d ** -0.5
    block_q = min(block_q, max(t_q, 1))
    block_k = min(block_k, max(t_k, 1))
    qr = q.reshape(b * h, t_q, d)
    kr = k.reshape(b * h, t_k, d)
    vr = v.reshape(b * h, t_k, d)
    mask_bias = None
    if kv_mask is not None:
        if kv_mask.shape != (b, t_k):
            raise ValueError(
                f"kv_mask shape {kv_mask.shape} != (batch, t_k)=({b}, {t_k})")
        mask_bias = _mask_bias(kv_mask, b, t_k, block_k)
    out = _flash(qr, kr, vr, mask_bias, causal, int(window), scale,
                 block_q, block_k, interpret, int(block_h),
                 min(block_q_bwd, max(t_q, 1)),
                 min(block_k_bwd, max(t_k, 1)))
    return out.reshape(b, h, t_q, d)
