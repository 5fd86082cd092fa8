"""Pallas fused LM-head + cross-entropy: logits never touch HBM.

The monolithic loss path materializes [N, V] f32 logits AND their
cotangent (~1.6 GB each for GPT-2's 50k vocab at batch 8 x seq 1024);
the jnp chunked variants (``dtf_tpu/ops/losses.py``) bound that memory
but still stream O(N·V) floats through HBM once per direction. This
kernel computes the head matmul and the CE in VMEM tiles — the same
move flash attention makes for the score matrix (SURVEY.md §2b N3:
Pallas where XLA's fusion cannot reach; the reference has no analogue,
its MNIST softmax is three orders of magnitude smaller):

- forward: grid (token-blocks, vocab-blocks), online logsumexp in
  scratch exactly like ``flash_attention._fwd_kernel``'s (m, l) carry,
  plus a target-logit accumulator (iota-compare pick, no one-hot).
  Outputs per-token lse and picked-target — O(N), not O(N·V).
- backward: dlogits = dce · (softmax − onehot) is REBUILT per tile from
  the saved lse (flash's recompute trade: extra MXU flops for zero HBM
  logits traffic). Two kernels, mirroring flash's dq / dkv split —
  ``dx += dlogits @ Wᵀ`` accumulates over vocab-blocks with dx blocked
  by token, ``dW += xᵀ @ dlogits`` accumulates over token-blocks with
  dW blocked by vocab — because a single grid cannot give both outputs
  consecutive revisits (Mosaic's accumulation contract).

Semantics match :func:`dtf_tpu.ops.losses.softmax_cross_entropy`
(ignore_index, clamped-count mean, out-of-range labels pick nothing);
parity-tested in interpret mode against the full path, fwd and grads
(tests/test_fused_ce.py). ``bias`` is not supported — the GPT flagship
head is bias-free; BERT's MLM path should gather masked positions
first (``--mlm_gather``), after which N is small and chunking is moot.

VMEM sizing: one tile holds x [bn, D] + w [D, bv] + logits f32 [bn, bv]
+ f32 accumulators. The forward and dx kernels fit the 512x1024 default
under the chip's 16 MiB scoped limit up to D = 1024 with f32 master
weights; the dW kernel holds three [D, bv] blocks (w in, dW out, f32
accumulator) and does not, so it picks its own vocab block from the shapes
(:func:`_dw_block_v`). For much wider models shrink ``block_v``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from dtf_tpu.ops.flash_attention import _compiler_params, _pad

_NEG_INF = float("-inf")
_STAT_LANES = 128
# Last-resort fallback tile — block args left at 0 resolve through the
# kernel-tune cache first (dtf_tpu.tune.resolver; docs/TUNING.md).
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_V = 1024
#: the scoped-VMEM limit Mosaic gives one kernel on a v5e (its compiler
#: refuses a kernel whose tiles and temporaries need more)
_SCOPED_VMEM_BYTES = 16 * 2 ** 20


def _col_ids(j, shape, block_v):
    return j * block_v + jax.lax.broadcasted_iota(jnp.int32, shape, 1)


def _fwd_kernel(x_ref, w_ref, lab_ref, lse_ref, tgt_ref, m_scr, l_scr,
                t_scr, *, v, block_v, num_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, m_scr.dtype)
        l_scr[...] = jnp.zeros(l_scr.shape, l_scr.dtype)
        t_scr[...] = jnp.zeros(t_scr.shape, t_scr.dtype)

    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bn, bv]
    gid = _col_ids(j, logits.shape, block_v)
    lab = lab_ref[0, 0][:, None]                     # [bn, 1]
    live = gid < v
    masked = jnp.where(live, logits, _NEG_INF)       # pad cols dead
    m_prev = m_scr[:, 0:1]
    m_next = jnp.maximum(m_prev, jnp.max(masked, axis=1, keepdims=True))
    m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
    alpha = jnp.exp(m_prev - m_safe)
    l_scr[...] = jnp.broadcast_to(
        alpha * l_scr[:, 0:1]
        + jnp.sum(jnp.exp(masked - m_safe), axis=1, keepdims=True),
        l_scr.shape)
    m_scr[...] = jnp.broadcast_to(m_next, m_scr.shape)
    # target pick: raw logit where the column IS the label (out-of-range
    # labels match no live column -> picked stays 0, the full-path rule)
    t_scr[...] = t_scr[...] + jnp.broadcast_to(
        jnp.sum(jnp.where((gid == lab) & live, logits, 0.0),
                axis=1, keepdims=True), t_scr.shape)

    @pl.when(j == num_v - 1)
    def _finalize():
        l = l_scr[:, 0:1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        lse_ref[0, 0, :] = (m_scr[:, 0:1] + jnp.log(l_safe))[:, 0]
        tgt_ref[0, 0, :] = t_scr[:, 0]


def _dlogits(x_ref, w_ref, lab_ref, lse_ref, dce_ref, j, *, v, block_v):
    """Rebuild this tile's dlogits = dce · (softmax − onehot) from the
    saved lse — THE shared recompute both backward kernels run (a
    one-sided edit here cannot desynchronize dx from dW)."""
    logits = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    gid = _col_ids(j, logits.shape, block_v)
    lab = lab_ref[0, 0][:, None]
    live = gid < v
    lse = lse_ref[0, 0][:, None]
    dce = dce_ref[0, 0][:, None]
    p = jnp.where(live, jnp.exp(logits - lse), 0.0)
    return dce * (p - jnp.where((gid == lab) & live, 1.0, 0.0))


def _dx_kernel(x_ref, w_ref, lab_ref, lse_ref, dce_ref, dx_ref, acc_scr,
               *, v, block_v, num_v):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    dl = _dlogits(x_ref, w_ref, lab_ref, lse_ref, dce_ref, j,
                  v=v, block_v=block_v)
    acc_scr[...] = acc_scr[...] + jax.lax.dot_general(
        dl.astype(w_ref.dtype), w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)          # [bn, D]

    @pl.when(j == num_v - 1)
    def _finalize():
        dx_ref[...] = acc_scr[...].astype(dx_ref.dtype)


def _dw_kernel(x_ref, w_ref, lab_ref, lse_ref, dce_ref, dw_ref, acc_scr,
               *, v, block_v, num_n):
    # grid (vocab-blocks, token-blocks): dW blocked by vocab, accumulated
    # across token steps
    j, i = pl.program_id(0), pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, acc_scr.dtype)

    dl = _dlogits(x_ref, w_ref, lab_ref, lse_ref, dce_ref, j,
                  v=v, block_v=block_v)
    acc_scr[...] = acc_scr[...] + jax.lax.dot_general(
        x_ref[...], dl.astype(x_ref.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [D, bv]

    @pl.when(i == num_n - 1)
    def _finalize():
        dw_ref[...] = acc_scr[...].astype(dw_ref.dtype)


def _dw_block_v(block_n, d, block_v, x_dtype, w_dtype):
    """The dW kernel's vocab block: ``block_v`` halved until its tile set
    fits scoped VMEM. The bound — x, w and dW blocks double-buffered, the
    f32 accumulator, the f32 logits/dlogits tiles, an f32 copy of x for a
    sub-f32 w — was checked against what the v5e compiler itself asks for
    at 30 shapes (D 768-2048, f32/bf16 w, blocks 256-1024): never under,
    at most ~1.5x over. At GPT-2 widths with f32 master weights 1024
    becomes 512 (1024 needs 18-23 MiB, 512 needs 10-13)."""
    xb, wb = jnp.dtype(x_dtype).itemsize, jnp.dtype(w_dtype).itemsize

    def need(bv):
        return (2 * block_n * d * xb + 4 * d * bv * wb + d * bv * 4
                + 2 * block_n * bv * 4
                + (block_n * d * 4 if wb < 4 else 0))

    bv = block_v
    while need(bv) > _SCOPED_VMEM_BYTES and bv % 256 == 0:
        bv //= 2
    return bv


def _prep(x, w, labels, block_n, block_v):
    n, d = x.shape
    v = w.shape[1]
    num_n = pl.cdiv(n, block_n)
    num_v = pl.cdiv(v, block_v)
    xp = _pad(x, block_n, 0)
    wp = _pad(w, block_v, 1)
    labp = _pad(labels.reshape(-1), block_n, 0).reshape(num_n, 1, block_n)
    return n, d, v, num_n, num_v, xp, wp, labp


def _run_fwd(x, w, labels, block_n, block_v, interpret):
    n, d, v, num_n, num_v, xp, wp, labp = _prep(x, w, labels, block_n,
                                                block_v)
    lse, tgt = pl.pallas_call(
        functools.partial(_fwd_kernel, v=v, block_v=block_v, num_v=num_v),
        grid=(num_n, num_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((num_n, 1, block_n), jnp.float32),
            jax.ShapeDtypeStruct((num_n, 1, block_n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_n, _STAT_LANES), jnp.float32)
                        for _ in range(3)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp, labp)
    return lse.reshape(-1)[:n], tgt.reshape(-1)[:n]


def _run_bwd(x, w, labels, lse, dce, block_n, block_v, interpret):
    n, d, v, num_n, num_v, xp, wp, labp = _prep(x, w, labels, block_n,
                                                block_v)
    lsep = _pad(lse, block_n, 0).reshape(num_n, 1, block_n)
    dcep = _pad(dce, block_n, 0).reshape(num_n, 1, block_n)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, num_v=num_v, v=v, block_v=block_v),
        grid=(num_n, num_v),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, block_v), lambda i, j: (0, j)),
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, d), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((block_n, d), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp, labp, lsep, dcep)
    # w is padded to a multiple of block_v, which every halving divides
    bv_dw = _dw_block_v(block_n, d, block_v, x.dtype, w.dtype)
    dw = pl.pallas_call(
        functools.partial(_dw_kernel, num_n=num_n, v=v, block_v=bv_dw),
        grid=(wp.shape[1] // bv_dw, num_n),
        in_specs=[
            pl.BlockSpec((block_n, d), lambda j, i: (i, 0)),
            pl.BlockSpec((d, bv_dw), lambda j, i: (0, j)),
            pl.BlockSpec((1, 1, block_n), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda j, i: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_n), lambda j, i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((d, bv_dw), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct(wp.shape, w.dtype),
        scratch_shapes=[pltpu.VMEM((d, bv_dw), jnp.float32)],
        compiler_params=_compiler_params(("parallel", "arbitrary")),
        interpret=interpret,
    )(xp, wp, labp, lsep, dcep)
    return dx[:n], dw[:, :v]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce(x, w, labels, ignore_index, block_n, block_v, interpret,
              axis_names):
    out, _ = _fused_ce_fwd(x, w, labels, ignore_index, block_n, block_v,
                           interpret, axis_names)
    return out


def _valid(labels, ignore_index):
    if ignore_index is None:
        return jnp.ones(labels.shape, jnp.float32)
    return (labels != ignore_index).astype(jnp.float32)


def _fused_ce_fwd(x, w, labels, ignore_index, block_n, block_v, interpret,
                  axis_names):
    lse, tgt = _run_fwd(x, w, labels, block_n, block_v, interpret)
    valid = _valid(labels, ignore_index)
    ce_sum = jnp.sum((lse - tgt) * valid)
    cnt = valid.sum()
    if axis_names:
        # inside a shard_map over token-sharding axes: the mean and count
        # are global, so every shard returns identical (replicated) values
        ce_sum = jax.lax.psum(ce_sum, axis_names)
        cnt = jax.lax.psum(cnt, axis_names)
    cnt = jnp.maximum(cnt, 1.0)
    mean = ce_sum / cnt
    return (mean, cnt), (x, w, labels, lse, valid, cnt)


def _fused_ce_bwd(ignore_index, block_n, block_v, interpret, axis_names,
                  res, g):
    x, w, labels, lse, valid, cnt = res
    g_mean, _g_cnt = g                         # cnt is not differentiable
    if axis_names:
        # Measured shard_map transpose behavior (check_vma=False, CPU sim,
        # tests/test_fused_ce.py::test_sharded_matches_unsharded_grads):
        # a replicated (P()) OUTPUT's cotangent arrives divided by the
        # shard count, and the replicated w INPUT's cotangent is psum'd
        # by shard_map itself. So: undo the division here, add no psum.
        #
        # VERSION TRIPWIRE (ADVICE r5 #1): both halves of that convention
        # are UNSPECIFIED shard_map internals under check_vma=False — a
        # JAX upgrade is free to change either, which would silently
        # mis-scale dx and dw by a factor of the shard count. The fast-
        # tier parity tests
        #   tests/test_fused_ce.py::test_sharded_matches_unsharded_grads
        #   tests/test_fused_ce.py::test_gpt_loss_pallas_matches_full
        # are the mandatory guards: they compare these gradients against
        # the unsharded path and MUST stay in the `not slow` tier. If they
        # start failing after a jax bump, re-measure the convention here
        # (or restructure: per-shard sums out of the custom_vjp, explicit
        # psum outside it, under a vma-checked shard_map).
        g_mean = g_mean * jax.lax.psum(1.0, axis_names)
    dce = (g_mean / cnt) * valid               # [N] (cnt is already global)
    dx, dw = _run_bwd(x, w, labels, lse, dce, block_n, block_v, interpret)
    return dx, dw, None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def pallas_lm_cross_entropy(x: jax.Array, w_head: jax.Array,
                            labels: jax.Array, *,
                            ignore_index: int | None = None,
                            block_n: int = 0,
                            block_v: int = 0,
                            interpret: bool = False,
                            axis_names: tuple = (),
                            ) -> tuple[jax.Array, jax.Array]:
    """(mean_loss, valid_count) — same contract as
    :func:`dtf_tpu.ops.losses.softmax_cross_entropy`, with the [N, V]
    logits living only in VMEM tiles (module docstring).

    ``block_n`` / ``block_v`` left at 0 resolve through the kernel-tune
    cache (:mod:`dtf_tpu.tune.resolver`; docs/TUNING.md), falling back
    to the 512x1024 module defaults; explicit values win, warning once
    when they differ from a measured winner.

    ``axis_names``: set when calling from INSIDE a shard_map whose named
    axes shard the tokens — the loss/count psum across them and dW's
    cotangent is psum'd in the backward. Callers under plain jit use
    :func:`pallas_lm_cross_entropy_sharded` instead, which owns the
    shard_map boundary (a bare pallas_call cannot be GSPMD-partitioned
    from outside: jit would all-gather the tokens and run the kernel
    redundantly per device — the flash_attention_sharded lesson)."""
    xf = x.reshape(-1, x.shape[-1])
    lab = labels.reshape(-1).astype(jnp.int32)
    n = xf.shape[0]
    if not (block_n and block_v):
        from dtf_tpu.tune import resolver as _tune

        plan = _tune.fused_ce_plan(
            vocab=int(w_head.shape[1]), d_model=int(xf.shape[1]),
            dtype=jnp.dtype(x.dtype).name, n_devices=jax.device_count(),
            backend=jax.default_backend())
        for what, explicit, won in (("block_n", block_n, plan.block_n),
                                    ("block_v", block_v, plan.block_v)):
            if explicit:
                _tune.note_override("fused_ce", what, explicit, won,
                                    source=plan.source,
                                    measured=plan.measured)
        block_n = block_n or plan.block_n
        block_v = block_v or plan.block_v
    bn = min(block_n, max(n, 1))
    bv = min(block_v, max(w_head.shape[1], 1))
    return _fused_ce(xf, w_head, lab, ignore_index, bn, bv, interpret,
                     tuple(axis_names))


def pallas_lm_cross_entropy_sharded(x, w_head, labels, mesh, *,
                                    ignore_index: int | None = None,
                                    block_n: int = 0,
                                    block_v: int = 0,
                                    interpret: bool = False):
    """The shard_map boundary for DP/SP meshes: tokens partition over
    (data, seq), ``w_head`` stays replicated, each shard runs the kernel
    on its LOCAL tokens, and the mean/count/dW are psum'd inside. With
    ``mesh=None`` or no token-sharding axes this is the plain call."""
    from jax.sharding import PartitionSpec as P

    if mesh is not None and mesh.shape.get("model", 1) > 1:
        raise ValueError(
            "pallas fused CE keeps the vocab whole per shard; it cannot "
            "combine with a model (TP) mesh axis — use the standard loss")
    axes = tuple(a for a in ("data", "seq")
                 if mesh is not None and mesh.shape.get(a, 1) > 1)
    if not axes:
        return pallas_lm_cross_entropy(
            x, w_head, labels, ignore_index=ignore_index, block_n=block_n,
            block_v=block_v, interpret=interpret)

    def fn(xl, wl, labl):
        return pallas_lm_cross_entropy(
            xl, wl, labl, ignore_index=ignore_index, block_n=block_n,
            block_v=block_v, interpret=interpret, axis_names=axes)

    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(P("data", "seq", None), P(None, None), P("data", "seq")),
        out_specs=(P(), P()), check_vma=False)(x, w_head, labels)
