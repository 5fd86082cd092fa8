"""Slot-decode attention as one in-place kernel (Pallas TPU).

One token per slot of a serving batch attends its own slot's K/V cache and
leaves its roped K row and its V row there. The XLA spelling of that step
(``models/gpt.py``) reads all ``max_len`` positions of every slot and writes
every cache leaf back whole through a position-mask select; this kernel
reads each slot's live positions once and writes one 128-position tile a
slot, into the leaf as it lies (``input_output_aliases``).

The leaf as it lies: the TPU keeps a ``[slots, kv_heads, max_len, d_head]``
leaf with a ``d_head`` under its 128 lanes POSITION-minor (PERF.md section 6,
PR 25), so the kernel takes the leaves as ``[slots, kv_heads, d_head,
max_len]`` — the same bytes, a bitcast for the compiler — and a block of
them is K^T and V^T of all heads at once, ``[kv_heads * d_head, block]``.

All heads in one product. A decode step has one query row a head, which
would leave the matrix unit a product a head; instead the slot's queries
arrive as ``[group * kv_heads, kv_heads * d_head]`` with row (g, h) holding
member g of head h in head h's columns and zeros beside (built by XLA, 1 MB
a layer at the serve cells' shapes), so scores are ONE product against K^T,
``[rows, block]``, and the output ONE product of the probabilities against
V^T, ``[rows, kv_heads * d_head]``, of which row (g, h) keeps head h's
columns. Grouped queries attend the un-expanded cache this way too: query
head ``h * group + g`` reads head ``h``. Statistics are float32, the
probabilities cast to the cache's dtype before the second product, as the
XLA path does.

Grid ``(slots, max_len // block)``, the position blocks inner. The per-slot
cache indices ride in as scalar-prefetch operands. A slot works on its
blocks up to the one that holds its index and idles through the other
steps, which come FIRST and already name the slot's first block: Pallas
fetches a step's block while the step before runs, so the first block of a
slot comes in under the last block's work of the slot before, and no step
fetches a block it has no work for. The new row never enters the blocks'
softmax as a cached position: its score and value start the running
maximum, sum and accumulator (so no slot's softmax is ever empty), and the
cached positions strictly before the index follow block by block.

The write: the output leaves are blocked ``[kv_heads, d_head, 128]`` at the
tile that holds position ``index % max_len``; the step that has that tile in
its input block copies it out with the new column selected in where the slot
is active, and as it was where it is not (a slot mid-prefill rides the step
untouched). Everything else of the leaf is never written.

Behind ONE module-level ``jax.jit``: the attention layers of a decode
program share one trace and one lowering of the kernel.

**The latent cache** (``models/gpt.py: LatentAttention``) has a second
kernel of the same design, ``dtf_mla_decode_attn``
(:func:`latent_decode_attention`): ONE leaf ``[slots, width, max_len]``,
position-minor by its own shape, whose block ``[width, block]`` is the key
of all heads at once and whose first ``rank`` rows are the value. The
absorbed queries of all heads are the product's rows as they come (no
block-diagonal widening: every head reads the same key), so scores are
``[heads, width] @ [width, block]`` and the output ``[heads, block]`` against
the block's first ``rank`` rows. The new row is written as a column of the
one tile that holds the slot's index, and starts the running softmax.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: positions of the tile an active slot writes back: the lane width
TILE = 128
#: bytes of one K (or V) input block the block size aims at
_BLOCK_BYTES = 1024 * 1024


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def block_positions(kv_heads: int, d_head: int, max_len: int,
                    itemsize: int) -> int:
    """Positions of an input block: whole tiles, about ``_BLOCK_BYTES`` of
    one leaf across all its heads, a divisor of ``max_len`` (which callers
    have checked is whole tiles)."""
    tiles = max(1, _BLOCK_BYTES // (kv_heads * d_head * TILE * itemsize))
    tiles = min(tiles, max_len // TILE)
    while (max_len // TILE) % tiles:
        tiles -= 1
    return tiles * TILE


def engages(*, cache_dtype, d_head: int, max_len: int, window: int,
            mesh) -> bool:
    """Whether the slot-decode step of this layer runs the kernel, from what
    the code can see: the TPU backend, a bfloat16 or float32 cache of whole
    128-position tiles whose head width lies under the lane width (the
    position-minor leaf the kernel is written for) in whole sublane tiles,
    no rolling window, one device."""
    dtype = jnp.dtype(cache_dtype)
    return (on_tpu()
            and dtype in (jnp.bfloat16, jnp.float32)
            and d_head < TILE and d_head % (32 // dtype.itemsize) == 0
            and max_len % TILE == 0
            and not window
            and (mesh is None or mesh.size == 1))


def latent_engages(*, cache_dtype, width: int, rank: int, max_len: int,
                   mesh) -> bool:
    """Whether a latent layer's slot-decode step runs
    :func:`latent_decode_attention`: the TPU backend, a bfloat16 or float32
    leaf of whole 128-position tiles whose width and value rank are whole
    sublane tiles, one device."""
    dtype = jnp.dtype(cache_dtype)
    sublanes = 32 // dtype.itemsize
    return (on_tpu()
            and dtype in (jnp.bfloat16, jnp.float32)
            and width % sublanes == 0 and rank % sublanes == 0
            and max_len % TILE == 0
            and (mesh is None or mesh.size == 1))


def _idle_steps(idx, block: int, max_len: int):
    """Of a slot's ``max_len // block`` grid steps, those with no block to
    work on: all but the blocks up to the one that holds its index."""
    return max_len // block - 1 - jnp.minimum(idx, max_len - 1) // block


def _kernel(idx_ref, act_ref, q_ref, kr_ref, vr_ref, kc_ref, vc_ref,
            kt_ref, vt_ref, o_ref, kt_out, vt_out, m_ref, l_ref, acc_ref, *,
            block: int, max_len: int, scale: float):
    s = pl.program_id(0)
    idx = idx_ref[s]
    pos = jax.lax.rem(idx, max_len)
    # the slot's idle steps come FIRST, with its first block already asked
    # for: the fetch runs under them and under the slot before
    j = pl.program_id(1) - _idle_steps(idx, block, max_len)
    cdt = kt_ref.dtype
    heads, d_head, _ = kt_out.shape
    q = q_ref[...]                                      # [G*H, H*D]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        k_new = kr_ref[...].astype(cdt).astype(jnp.float32)      # [1, H*D]
        v_new = vr_ref[...].astype(cdt).astype(jnp.float32)
        m_ref[...] = jnp.sum(q.astype(jnp.float32) * k_new, axis=-1,
                             keepdims=True) * scale              # [G*H, 1]
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(v_new, acc_ref.shape)

    @pl.when((j >= 0) & (j * block < jnp.minimum(idx, max_len)))
    def _cached():
        kt = kt_ref[...].reshape(heads * d_head, block)
        vt = vt_ref[...].reshape(heads * d_head, block)
        sc = jnp.dot(q, kt, preferred_element_type=jnp.float32) * scale
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where((at < idx) & (at != pos), sc, -jnp.inf)   # [G*H, B]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(cdt), vt, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [G*H, H*D]
        m_ref[...] = m_new

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        # row (g, h) keeps its own head's columns; the H rows of a group
        # member then sum to that member's [H*D] output row
        group = o_ref.shape[0]
        o = (acc_ref[...] / l_ref[...]).reshape(group, heads, -1)
        h = jax.lax.broadcasted_iota(jnp.int32, o.shape, 1)
        c = jax.lax.broadcasted_iota(jnp.int32, o.shape, 2)
        own = (c >= h * d_head) & (c < (h + 1) * d_head)
        o_ref[...] = jnp.sum(jnp.where(own, o, 0.0), axis=1).astype(
            o_ref.dtype)

    @pl.when(j == pos // block)
    def _write():
        at = pl.ds(pl.multiple_of(jax.lax.rem(pos, block) // TILE * TILE,
                                  TILE), TILE)
        lane = jax.lax.broadcasted_iota(jnp.int32, (d_head, TILE), 1)
        hit = (lane == jax.lax.rem(pos, TILE)) & (act_ref[s] != 0)

        def head(h, cols):
            # head h's new row stands in lane 0 as a column [d_head, 1]
            for out, leaf, col in zip((kt_out, vt_out), (kt_ref, vt_ref),
                                      cols):
                new = jnp.broadcast_to(col[:, :1], (d_head, TILE))
                out[h] = jnp.where(hit, new.astype(cdt), leaf[h, :, at])
            return tuple(pltpu.roll(col, TILE - 1, 1) for col in cols)

        jax.lax.fori_loop(0, heads, head,
                          (kc_ref[...].astype(jnp.float32),
                           vc_ref[...].astype(jnp.float32)))


def decode_attention(q: jax.Array, k_new: jax.Array, v_new: jax.Array,
                     cached_key: jax.Array, cached_value: jax.Array,
                     index: jax.Array, active: jax.Array):
    """``q`` [S, kv_heads, group, d] (roped), ``k_new`` (roped) / ``v_new``
    [S, kv_heads, d], the cache leaves [S, kv_heads, max_len, d], ``index``
    [S] int32 (each slot's position), ``active`` [S] bool. Returns
    ``(out [S, kv_heads, group, d] in q's dtype, cached_key, cached_value)``
    with row ``index % max_len`` of every active slot written; the leaves
    are updated in place where the caller donates them. Off the TPU (the
    tests) the kernel runs in interpret mode."""
    return _decode_attention(q, k_new, v_new, cached_key, cached_value,
                             index, active,
                             interpret=jax.default_backend() != "tpu")


@functools.partial(jax.jit, static_argnames=("interpret",))
def _decode_attention(q, k_new, v_new, cached_key, cached_value, index,
                      active, *, interpret: bool):
    n_slots, kv_heads, group, d_head = q.shape
    max_len = cached_key.shape[2]
    rows, width = group * kv_heads, kv_heads * d_head
    block = block_positions(kv_heads, d_head, max_len,
                            cached_key.dtype.itemsize)
    kt = jnp.swapaxes(cached_key, 2, 3)         # the leaf as it lies
    vt = jnp.swapaxes(cached_value, 2, 3)
    # every query head in ONE product against the block's [H*D, positions]:
    # row (g, h) holds head h's member g in head h's columns, zeros beside
    q_wide = (jnp.swapaxes(q, 1, 2)[:, :, :, None, :]
              * jnp.eye(kv_heads, dtype=q.dtype)[:, :, None]
              ).reshape(n_slots, rows, width)

    def columns(new):
        # [S, d, heads] under TILE lanes: a head's row as a column, which is
        # how the position-minor leaf takes it
        return jnp.pad(jnp.swapaxes(new.astype(kt.dtype), 1, 2),
                       ((0, 0), (0, 0), (0, TILE - kv_heads)))

    def live_block(s, j, idx, act):
        return (s, 0, 0,
                jnp.maximum(j - _idle_steps(idx[s], block, max_len), 0))

    def written_tile(s, j, idx, act):
        return (s, 0, 0, jax.lax.rem(idx[s], max_len) // TILE)

    def per_slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda s, j, idx, act: (s,) + (0,) * len(shape))

    cache_in = pl.BlockSpec((None, kv_heads, d_head, block), live_block)
    cache_out = pl.BlockSpec((None, kv_heads, d_head, TILE), written_tile)
    out, kt, vt = pl.pallas_call(
        functools.partial(_kernel, block=block, max_len=max_len,
                          scale=d_head ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_slots, max_len // block),
            in_specs=[per_slot(rows, width), per_slot(1, width),
                      per_slot(1, width), per_slot(d_head, TILE),
                      per_slot(d_head, TILE), cache_in, cache_in],
            out_specs=[per_slot(group, width), cache_out, cache_out],
            scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, 1), jnp.float32),
                            pltpu.VMEM((rows, width), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_slots, group, width), q.dtype),
                   jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        # operands count the two scalar-prefetch arrays
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="dtf_decode_attn",
    )(index.astype(jnp.int32), active.astype(jnp.int32), q_wide,
      k_new.reshape(n_slots, 1, width), v_new.reshape(n_slots, 1, width),
      columns(k_new), columns(v_new), kt, vt)
    out = jnp.swapaxes(out.reshape(n_slots, group, kv_heads, d_head), 1, 2)
    return out, jnp.swapaxes(kt, 2, 3), jnp.swapaxes(vt, 2, 3)


def _latent_kernel(idx_ref, act_ref, q_ref, new_ref, col_ref, lt_ref, o_ref,
                   lt_out, m_ref, l_ref, acc_ref, *, block: int,
                   max_len: int, rank: int, scale: float):
    s = pl.program_id(0)
    idx = idx_ref[s]
    pos = jax.lax.rem(idx, max_len)
    # idle steps first, as in _kernel: the slot's first block is fetched
    # under them and under the slot before
    j = pl.program_id(1) - _idle_steps(idx, block, max_len)
    cdt = lt_ref.dtype
    width = lt_ref.shape[0]
    q = q_ref[...]                                      # [H, W]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        new = new_ref[...].astype(cdt).astype(jnp.float32)       # [1, W]
        m_ref[...] = jnp.sum(q.astype(jnp.float32) * new, axis=-1,
                             keepdims=True) * scale              # [H, 1]
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(new[:, :rank], acc_ref.shape)

    @pl.when((j >= 0) & (j * block < jnp.minimum(idx, max_len)))
    def _cached():
        lt = lt_ref[...]                                         # [W, B]
        sc = jnp.dot(q, lt, preferred_element_type=jnp.float32) * scale
        at = j * block + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where((at < idx) & (at != pos), sc, -jnp.inf)   # [H, B]
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p.astype(cdt), lt[:rank], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)                  # [H, rank]
        m_ref[...] = m_new

    @pl.when(pl.program_id(1) == pl.num_programs(1) - 1)
    def _finish():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)

    @pl.when(j == pos // block)
    def _write():
        at = pl.ds(pl.multiple_of(jax.lax.rem(pos, block) // TILE * TILE,
                                  TILE), TILE)
        lane = jax.lax.broadcasted_iota(jnp.int32, (width, TILE), 1)
        hit = (lane == jax.lax.rem(pos, TILE)) & (act_ref[s] != 0)
        # the new row stands in lane 0 as a column [W, 1]
        new = jnp.broadcast_to(col_ref[...][:, :1], (width, TILE))
        lt_out[...] = jnp.where(hit, new.astype(cdt), lt_ref[:, at])


def latent_decode_attention(q: jax.Array, new: jax.Array,
                            cached_latent: jax.Array, index: jax.Array,
                            active: jax.Array, *, rank: int, scale: float):
    """``q`` [S, heads, width] (each head's absorbed no-position query
    beside its rotated part), ``new`` [S, width] (the token's normalised
    latent row beside its rotated key part), the leaf [S, width, max_len],
    ``index`` [S] int32, ``active`` [S] bool. Returns ``(out [S, heads,
    rank] in q's dtype: each head's probabilities over the latent rows'
    first ``rank`` numbers, cached_latent)`` with column ``index`` of every
    active slot written; the leaf is updated in place where the caller
    donates it. Off the TPU (the tests) the kernel runs in interpret
    mode."""
    return _latent_decode_attention(
        q, new, cached_latent, index, active, rank=rank, scale=float(scale),
        interpret=jax.default_backend() != "tpu")


@functools.partial(jax.jit, static_argnames=("rank", "scale", "interpret"))
def _latent_decode_attention(q, new, cached_latent, index, active, *,
                             rank: int, scale: float, interpret: bool):
    n_slots, heads, width = q.shape
    max_len = cached_latent.shape[2]
    block = block_positions(1, width, max_len, cached_latent.dtype.itemsize)
    # an inactive slot (mid-prefill: thousands of rows, none of them this
    # step's to read) rides as a slot at index 0: one block fetched, nothing
    # computed, tile 0 copied back as it was
    index = jnp.where(active, index, 0)
    # the new row as a column under TILE lanes, as the leaf takes it
    column = jnp.pad(new.astype(cached_latent.dtype)[:, :, None],
                     ((0, 0), (0, 0), (0, TILE - 1)))

    def live_block(s, j, idx, act):
        return (s, 0, jnp.maximum(j - _idle_steps(idx[s], block, max_len), 0))

    def written_tile(s, j, idx, act):
        return (s, 0, jax.lax.rem(idx[s], max_len) // TILE)

    def per_slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda s, j, idx, act: (s,) + (0,) * len(shape))

    out, cached_latent = pl.pallas_call(
        functools.partial(_latent_kernel, block=block, max_len=max_len,
                          rank=rank, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n_slots, max_len // block),
            in_specs=[per_slot(heads, width), per_slot(1, width),
                      per_slot(width, TILE),
                      pl.BlockSpec((None, width, block), live_block)],
            out_specs=[per_slot(heads, rank),
                       pl.BlockSpec((None, width, TILE), written_tile)],
            scratch_shapes=[pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, 1), jnp.float32),
                            pltpu.VMEM((heads, rank), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((n_slots, heads, rank), q.dtype),
                   jax.ShapeDtypeStruct(cached_latent.shape,
                                        cached_latent.dtype)],
        # operands count the two scalar-prefetch arrays
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="dtf_mla_decode_attn",
    )(index.astype(jnp.int32), active.astype(jnp.int32), q,
      new.reshape(n_slots, 1, width), column, cached_latent)
    return out, cached_latent
