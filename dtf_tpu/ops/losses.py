"""GSPMD-friendly losses for sharded logits (TP vocab sharding).

``take_along_axis`` on a vocab-sharded class dim is a sharded gather —
ambiguous/expensive under GSPMD. The one-hot contraction form keeps the
whole loss as matmul/reduce ops the partitioner handles natively (the psum
over the vocab shards is inserted automatically), which is how large-vocab
MLM heads stay TP-sharded end-to-end.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def softmax_cross_entropy(logits: jax.Array, labels: jax.Array,
                          *, ignore_index: int | None = None,
                          ) -> tuple[jax.Array, jax.Array]:
    """Per-example CE for integer labels via one-hot contraction.

    logits [..., V] (V may be mesh-sharded), labels [...] int. Returns
    (mean_loss, valid_count). With ``ignore_index`` (e.g. -100 for unmasked
    MLM positions), ignored positions contribute 0 and the mean is over valid
    positions only (psum-safe: both numerator and denominator are reductions).
    """
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    valid = (labels != ignore_index) if ignore_index is not None else None
    safe_labels = jnp.where(valid, labels, 0) if valid is not None else labels
    one_hot = jax.nn.one_hot(safe_labels, logits.shape[-1],
                             dtype=logits.dtype)
    picked = jnp.sum(one_hot * logits, axis=-1)
    return _masked_mean(lse - picked, labels, ignore_index)


def _masked_mean(ce: jax.Array, labels: jax.Array,
                 ignore_index: int | None) -> tuple[jax.Array, jax.Array]:
    """The shared ignore/mean tail: (mean over valid, valid_count), count
    clamped to 1 so an all-ignored batch yields 0.0 rather than NaN. ONE
    definition — both CE implementations promise identical semantics."""
    if ignore_index is None:
        return ce.mean(), jnp.asarray(ce.size, jnp.float32)
    valid = labels != ignore_index
    ce = jnp.where(valid, ce, 0.0)
    n = jnp.maximum(valid.sum().astype(jnp.float32), 1.0)
    return ce.sum() / n, n


def chunked_lm_cross_entropy(x: jax.Array, w_head: jax.Array,
                             labels: jax.Array, *, chunk: int = 8192,
                             bias: jax.Array | None = None,
                             ignore_index: int | None = None,
                             ) -> tuple[jax.Array, jax.Array]:
    """Next-token CE fused with the LM head, never materializing [N, V].

    The full-logits path costs O(N·V) f32 twice (logits + their cotangent)
    — 1.6 GB each for GPT-2's 50k vocab at batch 8 x seq 1024, which is
    what caps the batch size (the single-chip MFU lever). This scans the
    vocab in ``chunk``-column slices of the head kernel: each step is an
    MXU-shaped [N, D] x [D, chunk] matmul feeding an online logsumexp and
    a pick of the target logit, with the chunk rematerialized in the
    backward (``jax.checkpoint``), so live memory is O(N·chunk).

    ``x`` [..., D] (pre-head activations, post-final-LN), ``w_head``
    [D, V] (the untied lm_head kernel — or a tied embedding transposed),
    ``bias`` optional [V] (BERT's mlm_bias), ``labels`` [...] int.
    Returns (mean_loss, valid_count) with the same ignore/mean semantics
    as :func:`softmax_cross_entropy` — exact same numbers, different
    memory.
    """
    lead = x.shape[:-1]
    d = x.shape[-1]
    v = w_head.shape[1]
    xf = x.reshape(-1, d)
    lab = labels.reshape(-1)
    n = xf.shape[0]
    n_chunks = -(-v // chunk)
    v_pad = n_chunks * chunk
    wp = jnp.pad(w_head, ((0, 0), (0, v_pad - v))) if v_pad != v else w_head
    bp = None
    if bias is not None:
        bp = jnp.pad(bias, (0, v_pad - v)) if v_pad != v else bias

    @jax.checkpoint
    def body(carry, c):
        m, s, tgt = carry                       # [N], [N], [N]
        w_c = jax.lax.dynamic_slice_in_dim(wp, c * chunk, chunk, axis=1)
        logits = jnp.dot(xf, w_c,
                         preferred_element_type=jnp.float32)  # [N, chunk]
        if bp is not None:
            logits = logits + jax.lax.dynamic_slice_in_dim(
                bp, c * chunk, chunk)[None, :].astype(jnp.float32)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        gid = col + c * chunk                   # global vocab ids
        logits = jnp.where(gid < v, logits, -jnp.inf)  # pad cols dead
        m_new = jnp.maximum(m, jnp.max(logits, axis=1))
        # m is -inf until the first live chunk; guard the rescale
        alpha = jnp.exp(jnp.where(jnp.isneginf(m), -jnp.inf, m - m_new))
        s = s * alpha + jnp.sum(jnp.exp(logits - m_new[:, None]), axis=1)
        # Restrict the pick to live columns: a label in [V, V_pad) would
        # otherwise match a padded -inf column and poison tgt, where the
        # full path's out-of-range one_hot is all-zero (picked stays 0).
        tgt = tgt + jnp.sum(
            jnp.where((gid == lab[:, None]) & (gid < v), logits, 0.0), axis=1)
        return (m_new, s, tgt), None

    init = (jnp.full((n,), -jnp.inf, jnp.float32),
            jnp.zeros((n,), jnp.float32), jnp.zeros((n,), jnp.float32))
    (m, s, tgt), _ = jax.lax.scan(body, init, jnp.arange(n_chunks))
    ce = (m + jnp.log(s)) - tgt                 # [N]
    return _masked_mean(ce.reshape(lead), labels, ignore_index)


def token_chunked_lm_cross_entropy(x: jax.Array, w_head: jax.Array,
                                   labels: jax.Array, *, chunk: int = 4096,
                                   bias: jax.Array | None = None,
                                   ignore_index: int | None = None,
                                   ) -> tuple[jax.Array, jax.Array]:
    """Fused head+CE chunking TOKENS instead of vocab columns.

    Same memory guarantee as :func:`chunked_lm_cross_entropy` — live
    logits are O(chunk·V) instead of O(N·V) — but each scan step is ONE
    full-vocab matmul ([chunk, D] x [D, V]) followed by a plain CE, with
    no online-logsumexp carry. On-chip rows from before PR 1 showed the
    vocab-chunked scan costs ~9 GPT MFU points over the monolithic loss
    (BENCH_LM_SWEEP.json, another JAX; no cell runs either): its
    per-step [N, chunk] max/rescale/pick passes are VPU traffic over
    the whole activation set repeated every chunk, and its carries
    serialize against the matmul.
    Token chunking does the lse/pick arithmetic ONCE per token on an
    MXU-shaped [chunk, V] tile, so it should sit between the monolithic
    and vocab-chunked points at the same bounded memory. Chunk the vocab
    instead when the HEAD matmul itself must stay narrow (e.g. a [D, V]
    too big to tile comfortably — not the case at GPT-2 scale).

    Semantics identical to :func:`softmax_cross_entropy` (same
    ignore/mean tail, same out-of-range-label behavior). ``w_head``
    [D, V]; each chunk's logits are rematerialized in the backward
    (``jax.checkpoint``), so the cotangent is also O(chunk·V).
    """
    d = x.shape[-1]
    v = w_head.shape[1]
    xf = x.reshape(-1, d)
    lab = labels.reshape(-1)
    n = xf.shape[0]
    n_chunks = -(-n // chunk)
    n_pad = n_chunks * chunk
    live = jnp.arange(n_pad) < n                # padded rows contribute 0
    if n_pad != n:
        xf = jnp.pad(xf, ((0, n_pad - n), (0, 0)))
        lab = jnp.pad(lab, (0, n_pad - n))
    bf = None if bias is None else bias.astype(jnp.float32)

    @jax.checkpoint
    def body(carry, inp):
        tot, cnt = carry
        xc, lc, rowc = inp                      # [chunk,D], [chunk], [chunk]
        logits = jnp.dot(xc, w_head,
                         preferred_element_type=jnp.float32)  # [chunk, V]
        if bf is not None:
            logits = logits + bf[None, :]
        lse = jax.nn.logsumexp(logits, axis=-1)
        valid = rowc if ignore_index is None else (
            rowc & (lc != ignore_index))
        safe = jnp.where(valid, lc, 0)
        # iota-compare pick (the vocab-chunked path's pattern): fuses to a
        # masked reduce with no materialized [chunk, V] f32 one_hot. An
        # out-of-range label matches no column -> picked 0, the exact
        # full-path behavior (softmax_cross_entropy above).
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        picked = jnp.sum(
            jnp.where(col == safe[:, None], logits, 0.0), axis=-1)
        ce = jnp.where(valid, lse - picked, 0.0)
        return (tot + ce.sum(), cnt + valid.sum(dtype=jnp.float32)), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)),
        (xf.reshape(n_chunks, chunk, d), lab.reshape(n_chunks, chunk),
         live.reshape(n_chunks, chunk)))
    # same clamped-count contract as _masked_mean (all-ignored -> 0.0)
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt, cnt
