"""Pallas TPU flash attention — fused O(T) -memory attention kernels.

The reference has no attention at all (SURVEY.md §5.7: nothing in
`zjj2wry/distributed-tensorflow` scales sequence length; its models are MNIST
softmax / ResNet / fixed-length BERT). This module is where the TPU-native
framework goes past capability parity: a first-party fused kernel for the
hottest op in the transformer stack, built on Pallas/Mosaic so the MXU sees
[tile_q, d] x [d, tile_k] matmuls and the softmax statistics never leave
VMEM.

Design (flash-attention-2 style, adapted to the TPU grid model):
- two levels of tile. A ``BlockSpec`` fetches a BLOCK pair (a head's whole
  Q, K and V where they fit: 128 KB each at t 1024, d_head 64); the grid
  step computes it as a STATIC list of [tile_q, tile_k] score tiles,
  unrolled at trace time into straight-line code (a rolled loop over tiles
  ran 1.5-2.3 x slower on the chip: PERF.md §6, PR 35). Where one grid step
  holds the whole sequence its offsets are Python ints, and the list is
  exact: tiles above the causal diagonal, older than the window or made of
  padded keys are not in it, and positions, compares and the select are
  built only for the tiles a mask edge crosses (:func:`_key_walk`,
  :func:`_query_walk`; :func:`flash_tiles` lists them for tests and for
  ``scripts/flash_sweep.py``). Where the sequence takes several blocks the
  offsets are program ids: dead blocks are skipped at the grid
  (:func:`_block_live`, with sticky index maps), a block no mask edge
  crosses runs every tile unmasked, and a block one crosses runs every
  tile masked — two straight-line variants under ``pl.when`` (and a
  third with no tile, for a dead block whose row is its only grid step:
  nothing else would store its zeros).
- forward: grid (batch*heads, num_q_blocks, num_k_blocks); the k axis is the
  innermost ("arbitrary" = sequential) grid dim. Running max / sum /
  accumulator of a query tile are values carried along its row of key
  tiles; where one grid step covers every key (num_k == 1) they never
  touch scratch, else VMEM scratch holds them between grid steps (read and
  written once a step). Output and the logsumexp residual are written on
  the last k step.
- backward: the standard two-kernel split — dq walks key tiles along each
  query tile; dk/dv walk query tiles along each key tile, on TRANSPOSED
  scores ([keys, queries]: logsumexp and delta are rows as they are stored,
  and both accumulating products are plain [k, q] x [q, d]) — using the
  saved logsumexp plus delta = rowsum(dO * O) so p is recomputed, never
  materialised at [T, T].
- unaligned T is handled by zero-padding in the wrapper and masking inside
  the kernel (keys beyond t_k get -inf scores; padded query rows carry
  dO = 0, so they add nothing to dk/dv). head_dim is passed through as-is —
  Mosaic handles non-128 lane counts, at some layout cost.

Softmax statistics are float32 regardless of input dtype; p is cast back to
the value dtype for the MXU contraction (the usual bf16 flash recipe). A
softmax scale that is a power of two (d_head 16, 64, 256) is folded into
the resident operand once a tile row: exact, so the scores are bit for bit
the ones ``s * scale`` gives.

Runs compiled on TPU (Mosaic) and under ``interpret=True`` on CPU for the
test suite (tests/test_flash_attention.py).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = float("-inf")
_STAT_LANES = 128  # h-fold scratch stat arrays are [block_q, 128] (lane width)
_NT = (((1,), (1,)), ((), ()))   # a @ b.T
_NN = (((1,), (0,)), ((), ()))   # a @ b


def _compiler_params(dims: tuple[str, ...]):
    return pltpu.CompilerParams(dimension_semantics=dims)


# ---------------------------------------------------------------------------
# blocks from the shape
# ---------------------------------------------------------------------------


class FlashBlocks(NamedTuple):
    """What each kernel fetches and computes: ``(block_q, block_k, tile_q,
    tile_k)`` — the block pair a grid step fetches and the score tile it is
    computed in ([tile_q, tile_k]; dkv's is its transpose)."""
    fwd: tuple[int, int, int, int]
    dq: tuple[int, int, int, int]
    dkv: tuple[int, int, int, int]


_VMEM_LIMIT = 16 * 2 ** 20     # scoped VMEM a kernel may use on a v5e


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def vmem_bytes(kernel: str, blocks: tuple[int, int, int, int], d_head: int,
               itemsize: int = 2) -> int:
    """Estimate of the VMEM one grid step of ``kernel`` ("fwd" | "dq" |
    "dkv") holds: every BlockSpec operand twice (the pipeline's two
    buffers), the float32 accumulators between grid steps, and six float32
    score-sized tiles (s, p, in the backward dp and ds, and the copies the
    compiler keeps: four were refused at [1024, 512] that this lets
    through)."""
    bq, bk, tq, tk = blocks
    d = _round_up(d_head, 128)                 # a row occupies whole lanes
    q_rows, k_rows = {"fwd": (2, 2), "dq": (3, 2), "dkv": (2, 4)}[kernel]
    acc = (2 * bk if kernel == "dkv" else bq) * d * 4
    return (2 * itemsize * d * (q_rows * bq + k_rows * bk)
            + acc + 6 * tq * tk * 4 + 4 * 8 * 4 * (bq + bk))


#: The rule's constants, read off ``scripts/flash_sweep.py``'s tables on a
#: v5e (PERF.md §6, PR 35: causal bf16[128, 1024, 64], key-masked
#: bf16[384, 512, 64], causal bf16[16, 8192, 128]); each is the fastest
#: tile there or within 2% of it. ``_TILE``: (tile_q, tile_k) by (kernel,
#: causal) where the sequence is one block. Each row was read at ONE length
#: only — the causal ones at t 1024, the others at t 512 — so ``causal``
#: here also stands for the length it was swept at. A causal forward drops
#: a quarter of 512 x 512 tiles and still beats 256 x 256 (fewer rescales
#: of the statistics); the backward kernels have no statistics and take the
#: smaller tile that drops more. ``_LONG_TILE``: over several blocks no
#: tile is dropped inside a block, so tiles are large, and the forward's
#: spans the block's keys (one update of the statistics a block).
_TILE = {
    ("fwd", True): (512, 512), ("fwd", False): (256, 512),
    ("dq", True): (256, 256), ("dq", False): (256, 256),
    ("dkv", True): (256, 256), ("dkv", False): (512, 256),
}
_LONG_TILE = {"fwd": (512, 1024), "dq": (512, 512), "dkv": (512, 512)}
_WHOLE = 1024        # the longest sequence one grid step holds whole
_LONG_BLOCK = 1024   # the square blocks a longer one goes in


def flash_blocks(t_q: int, t_k: int, d_head: int, *, causal: bool = False,
                 itemsize: int = 2) -> FlashBlocks:
    """Blocks for a call nobody measured, from what the call can see.

    - a sequence of up to ``_WHOLE`` rows is ONE block: Q, K and V of a
      head are fetched once a head, the grid step's offsets are static and
      it computes exactly the tiles the mask leaves (the two train cells:
      1024 and 512); a longer one goes in blocks of ``_LONG_BLOCK``, dead
      blocks skipped at the grid;
    - the compute tile is ``_TILE[kernel, causal]`` (one block) or
      ``_LONG_TILE[kernel]`` (several), each side halved (to 128 at least)
      until it divides its block; every block is a multiple of 128 (a
      shorter axis is padded up to one and masked);
    - while :func:`vmem_bytes` is over the limit, first the tile's longer
      side is halved (down to 256 x 256), then the longer block.

    A window (a narrower triangle) and a key mask (a bias row of 4 B a
    key) change no block: no sweep has shown that they should.
    """
    t128 = [_round_up(max(t, 1), 128) for t in (t_q, t_k)]
    whole = max(t128) <= _WHOLE

    def pick(kernel):
        tiles = list(_TILE[kernel, bool(causal)] if whole
                     else _LONG_TILE[kernel])
        blocks = [t if whole else min(t, _LONG_BLOCK) for t in t128]
        while True:
            for x in range(2):      # each side halved until it divides
                while tiles[x] > 128 and blocks[x] % tiles[x]:
                    tiles[x] //= 2
            fit = (*blocks, *tiles)
            if vmem_bytes(kernel, fit, d_head, itemsize) <= _VMEM_LIMIT:
                return fit
            if max(tiles) > 256:
                tiles[tiles.index(max(tiles))] //= 2
            elif max(blocks) > 256:
                x = blocks.index(max(blocks))
                blocks[x] = _round_up(blocks[x] // 2, 128)
            else:
                return fit

    return FlashBlocks(fwd=pick("fwd"), dq=pick("dq"), dkv=pick("dkv"))


# ---------------------------------------------------------------------------
# which tiles a grid step computes, and which of them it masks
# ---------------------------------------------------------------------------


def _clamped(lo, a, b, hi):
    hi = max(hi, lo)
    a = min(max(a, lo), hi)
    return lo, a, min(max(b, a), hi), hi


def _key_walk(q0, nq, kb, *, sub, n_sub, causal, window, t_q, t_k):
    """Key tiles ``s`` (keys ``kb + s*sub ...``) that queries
    ``[q0, q0 + nq)`` compute: ``(lo, a, b, hi)`` with ``[lo, hi)`` live,
    ``[lo, a)`` crossed by the window's far edge, ``[b, hi)`` crossed by the
    causal diagonal or holding padded keys, ``[a, b)`` free of any mask.
    Python ints (offsets known at trace time)."""
    lo = a = 0
    hi = min(n_sub, -(-(t_k - kb) // sub))           # holds a real key
    b = min(hi, max(t_k - kb, 0) // sub)             # first padded key
    if causal:
        last_q = min(q0 + nq, t_q)                   # one past the last real
        hi = min(hi, max(last_q - kb + sub - 1, 0) // sub)
        b = min(b, max(q0 - kb + 1, 0) // sub)
        if window:
            lo = max(q0 - window + 1 - kb, 0) // sub
            a = max(q0 + nq - 1 - window - kb + sub, 0) // sub
    return _clamped(lo, a, b, hi)


def _query_walk(k0, nk, qb, *, sub, n_sub, causal, window, t_q, t_k):
    """Query tiles ``s`` (queries ``qb + s*sub ...``) that keys
    ``[k0, k0 + nk)`` compute, as :func:`_key_walk`: ``[lo, a)`` crossed by
    the causal diagonal, ``[b, hi)`` by the window's far edge. Tiles of
    padded queries alone are skipped; a tile that holds some needs no mask
    for them (their dO, logsumexp and delta are zeros), and none for padded
    keys (their dk/dv rows are sliced away)."""
    lo = a = 0
    b = hi = min(n_sub, -(-(t_q - qb) // sub))
    if causal:
        lo = max(k0 - qb, 0) // sub
        a = (max(k0 + nk - 1 - qb, 0) + sub - 1) // sub
        if window:
            last_k = min(k0 + nk, t_k)
            hi = min(hi, (max(last_k - 1 + window - qb, 0) + sub - 1) // sub)
            b = min(hi, max(window + k0 - qb, 0) // sub)
    return _clamped(lo, a, b, hi)


_DEAD = "dead"      # a block with no valid pair: a variant with no tiles


def _row_tiles(kernel, r, blocks, *, q0, k0, masked=None, **edges):
    """``[(s, masked)]``: the tiles that resident tile ``r`` of a block at
    ``(q0, k0)`` computes along its row. Offsets known (``masked`` None):
    the exact list of the walk. Offsets traced: every tile of the block,
    all ``masked`` or all not — the caller has asked which — or none at
    all (``_DEAD``)."""
    bq, bk, tq, tk = blocks
    n = bq // tq if kernel == "dkv" else bk // tk
    if masked is _DEAD:
        return []
    if masked is not None:
        return [(s, masked) for s in range(n)]
    if kernel == "dkv":
        lo, a, b, hi = _query_walk(k0 + r * tk, tk, q0, sub=tq, n_sub=n,
                                   **edges)
    else:
        lo, a, b, hi = _key_walk(q0 + r * tq, tq, k0, sub=tk, n_sub=n,
                                 **edges)
    return [(s, not a <= s < b) for s in range(lo, hi)]


def _block_interior(q0, k0, blocks, *, causal, window, t_k, key_limit):
    """No mask edge crosses the block at ``(q0, k0)``: every pair in it is
    valid (``key_limit``: padded keys count as an edge — not for dkv)."""
    bq, bk = blocks[:2]
    ok = []
    if key_limit and t_k % bk:
        ok.append(k0 + bk <= t_k)
    if causal:
        ok.append(k0 + bk - 1 <= q0)
        if window:
            ok.append(q0 + bq - 1 - k0 < window)
    if all(isinstance(x, bool) for x in ok):
        return all(ok)
    return functools.reduce(jnp.logical_and, ok)


def flash_tiles(t_q: int, t_k: int, blocks: tuple[int, int, int, int], *,
                causal: bool = False, window: int = 0,
                kernel: str = "fwd") -> list[tuple[int, int, int, int, bool]]:
    """Every score tile one call of ``kernel`` ("fwd" | "dq" | "dkv")
    computes, as ``(q0, k0, nq, nk, masked)`` — the kernels' own lists.
    The mechanism's counter: tiles computed ÷ tiles in the square, tiles
    masked ÷ tiles computed (``scripts/flash_sweep.py``). Exact where the
    sequence is one block; over several, a block a mask edge crosses is
    computed whole and masked."""
    bq, bk, tq, tk = blocks
    num_q, num_k = -(-t_q // bq), -(-t_k // bk)
    edges = dict(causal=causal, window=window, t_q=t_q, t_k=t_k)
    out = []
    for i in range(num_q):
        for j in range(num_k):
            if not _block_live(i, j, causal=causal, window=window,
                               block_q=bq, block_k=bk):
                continue
            masked = None
            if num_q * num_k > 1:
                masked = not _block_interior(
                    i * bq, j * bk, blocks, causal=causal, window=window,
                    t_k=t_k, key_limit=kernel != "dkv")
            for r in range(bk // tk if kernel == "dkv" else bq // tq):
                for s, m in _row_tiles(kernel, r, blocks, q0=i * bq,
                                       k0=j * bk, masked=masked, **edges):
                    qt, kt = (s, r) if kernel == "dkv" else (r, s)
                    out.append((i * bq + qt * tq, j * bk + kt * tk, tq, tk,
                                m))
    return out


def _tile_mask(q0, k0, shape, *, causal, window, t_k, keys_first=False):
    """True where a (query, key) pair of the tile at (q0, k0) is valid.
    ``keys_first``: the tile is [keys, queries] (dkv's transposed scores),
    whose padded keys need no mask (their dk/dv rows are sliced away)."""
    q_pos = q0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          1 if keys_first else 0)
    k_pos = k0 + jax.lax.broadcasted_iota(jnp.int32, shape,
                                          0 if keys_first else 1)
    mask = None if keys_first else k_pos < t_k
    if causal:
        ok = q_pos >= k_pos
        mask = ok if mask is None else jnp.logical_and(mask, ok)
        if window:
            # sliding window: query t sees keys in (t-window, t]
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return mask


def _prescale(x, sm_scale):
    """Fold the softmax scale into a resident operand when that is exact (a
    power of two): ``(x', what is left to multiply the scores by)``."""
    if math.frexp(sm_scale)[0] == 0.5:
        return x * sm_scale, 1.0
    return x, sm_scale


def _positions(i, j, block_q, block_k):
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return q_pos, k_pos


def _score_mask(s, i, j, *, causal, block_q, block_k, t_k, window=0):
    """-inf out invalid (padded-key / future-key / out-of-window) scores of
    a whole block (the h-fold kernel's; the 2-D kernels mask by tile)."""
    need_k_mask = (t_k % block_k) != 0
    if not (causal or need_k_mask):
        return s
    q_pos, k_pos = _positions(i, j, block_q, block_k)
    mask = k_pos < t_k
    if causal:
        mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window:
            mask = jnp.logical_and(mask, q_pos - k_pos < window)
    return jnp.where(mask, s, _NEG_INF)


def _block_live(i, j, *, causal, window, block_q, block_k):
    """Does (q-block i, k-block j) contain ANY unmasked position? The grid
    skip condition: below-diagonal blocks for causal, plus blocks entirely
    older than the window — this is what makes windowed attention O(T·W)
    instead of O(T²/2). Decides whole grid steps where the sequence takes
    several blocks; the walks above decide inside one that holds it all."""
    if not causal:
        return True
    live = j * block_k <= i * block_q + block_q - 1
    if window:
        # newest key in block j must be inside the oldest query's window:
        # (j+1)*bk - 1 > i*bq - window  ⇔  some (qp, kp) has qp-kp < window
        newer = (j + 1) * block_k - 1 > i * block_q - window
        live = (live and newer) if isinstance(live, bool) else (
            jnp.logical_and(live, newer))
    return live


def _variants(kernel, i, j, blocks, *, causal, window, t_k, static,
              one_step):
    """``[(condition, masked)]``: the straight-line variants of one grid
    step and when each runs. ``masked`` None: offsets are static, the tile
    lists exact. Else a block runs unmasked when no mask edge crosses it
    and masked when one does. A dead block is skipped where scratch
    carries the row over several grid steps (its init and finalize steps
    write the output); where the row is ``one_step`` nothing else would,
    so it runs the ``_DEAD`` variant: no tile, the empty row's zeros."""
    if static:
        return [(True, None)]
    bq, bk = blocks[:2]
    live = _block_live(i, j, causal=causal, window=window, block_q=bq,
                       block_k=bk)
    inner = _block_interior(i * bq, j * bk, blocks, causal=causal,
                            window=window, t_k=t_k,
                            key_limit=kernel != "dkv")
    if inner is True:
        out = [(live, False)]
    else:
        edge = jnp.logical_not(inner)
        if live is not True:
            edge = jnp.logical_and(live, edge)
        out = [(inner, False), (edge, True)]   # interior implies live
    if one_step and live is not True:
        out.append((jnp.logical_not(live), _DEAD))
    return out


def _when(cond):
    """``pl.when`` that runs the body outright for a Python ``True``."""
    if cond is True:
        return lambda fn: fn()
    return pl.when(cond)


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


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims,
                               preferred_element_type=jnp.float32)


def _rows(r, n):
    return slice(r * n, (r + 1) * n)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, sm_scale, causal, window, blocks,
                num_q, num_k, t_q, t_k, has_mask):
    mb_ref = rest[0] if has_mask else None
    o_ref, lse_ref, *scratch = rest[1:] if has_mask else rest
    block_q, block_k, tq, tk = blocks
    # one block a head: the offsets are Python ints and the tile lists exact
    static = num_q == num_k == 1
    i, j = (0, 0) if static else (pl.program_id(1), pl.program_id(2))
    q0, k0 = i * block_q, j * block_k
    edges = dict(causal=causal, window=window, t_q=t_q, t_k=t_k)

    def tile(q, s_scale, r, s, carry, masked):
        keys = _rows(s, tk)
        sc = _dot(q, k_ref[0, keys, :], _NT)                 # [tq, tk]
        if s_scale != 1.0:
            sc = sc * s_scale
        if masked:
            sc = jnp.where(
                _tile_mask(q0 + r * tq, k0 + s * tk, sc.shape, causal=causal,
                           window=window, t_k=t_k), sc, _NEG_INF)
        if has_mask:
            # additive key-padding bias row (0 valid / -inf padded): the
            # -inf machinery (running max, dead-row guards) then handles
            # masked keys identically to causal-masked ones.
            sc = sc + mb_ref[0, 0, :, keys]
        m_next = jnp.max(sc, axis=1, keepdims=True)
        if carry is not None:
            m_prev, l_prev, acc = carry
            m_next = jnp.maximum(m_prev, m_next)
        # Fully-masked-so-far rows keep m == -inf; subtracting a 0 stand-in
        # keeps exp() finite (p rows come out 0, alpha comes out 0).
        m_safe = jnp.where(m_next == _NEG_INF, 0.0, m_next)
        p = jnp.exp(sc - m_safe)
        l_next = jnp.sum(p, axis=1, keepdims=True)
        pv = _dot(p.astype(v_ref.dtype), v_ref[0, keys, :], _NN)
        if carry is not None:   # the row's first tile has nothing to rescale
            alpha = jnp.exp(m_prev - m_safe)
            l_next, pv = alpha * l_prev + l_next, acc * alpha + pv
        return m_next, l_next, pv

    def finish(rows, m, l, acc):
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, rows, :] = (acc * (1.0 / l_safe)).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        # lse is [bh, num_q, 1, block_q]: the num_q axis is blocked by i so
        # each q-block program owns its own output window (the q grid dim is
        # "parallel" — a shared window revisited across i would be UB on
        # megacore), and the trailing (1, block_q) block dims are full-size
        # (Mosaic requires trailing block dims (8,128)-divisible or full).
        lse_ref[0, 0, 0, rows] = lse[:, 0]

    def init():
        return (jnp.full((tq, 1), _NEG_INF, jnp.float32),
                jnp.zeros((tq, 1), jnp.float32),
                jnp.zeros((tq, q_ref.shape[2]), jnp.float32))

    def run(masked):
        for r in range(block_q // tq):
            rows = _rows(r, tq)
            q, s_scale = _prescale(q_ref[0, rows, :], sm_scale)
            # the statistics ride along the row of tiles as values; scratch
            # holds them only between grid steps
            carry = (None if num_k == 1
                     else tuple(ref[rows, :] for ref in scratch))
            for s, m in _row_tiles("fwd", r, blocks, q0=q0, k0=k0,
                                   masked=masked, **edges):
                carry = tile(q, s_scale, r, s, carry, m)
            carry = init() if carry is None else carry
            if num_k == 1:
                finish(rows, *carry)
            else:
                for ref, val in zip(scratch, carry):
                    ref[rows, :] = val

    if num_k > 1:
        @pl.when(j == 0)
        def _init():
            m_scr, l_scr, acc_scr = scratch
            m_scr[...] = jnp.full(m_scr.shape, _NEG_INF, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    for cond, masked in _variants("fwd", i, j, blocks, causal=causal,
                                  window=window, t_k=t_k, static=static,
                                  one_step=num_k == 1):
        _when(cond)(functools.partial(run, masked))

    if num_k > 1:
        @pl.when(j == num_k - 1)
        def _finalize():
            for r in range(block_q // tq):
                rows = _rows(r, tq)
                finish(rows, *(ref[rows, :] for ref in scratch))


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
            s = s + mb_ref[0, 0, 0][None, None, :]
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


def _mask_bias(kv_mask):
    """[b, t_k] f32 additive bias: 0 valid, -inf padded key.

    PER-BATCH, not per-(batch*head): every head reads the same row, so the
    kernels' index maps divide the bh grid index by the head count instead
    of materializing h identical copies (which the custom_vjp residuals
    would otherwise keep alive through the backward)."""
    return jnp.where(kv_mask, 0.0, _NEG_INF).astype(jnp.float32)


def _bias_blocks(mask_bias, block_k):
    """The bias as [b, num_k, 1, block_k] for a grid whose key blocks are
    ``block_k``: the (1, block_k) trailing block dims are full-size, so
    the block is Mosaic-legal (same trick as the lse residual layout)."""
    b = mask_bias.shape[0]
    return _pad(mask_bias, block_k, axis=1).reshape(b, -1, 1, block_k)


# The three calls below are jitted at module level: a model's layers call
# them with one signature, so the kernel is traced and lowered ONCE a
# program and called from every layer, where a bare ``pallas_call`` is
# traced and lowered again at each of a step's 72 call sites — seconds of
# every run's set-up (PERF.md §6, PR 35; ``dtf_decode_attn`` does the same).
_STATIC = ("sm_scale", "causal", "window", "blocks", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC + ("block_h",))
def _fwd(q, k, v, mask_bias, *, sm_scale, causal, window, blocks,
         interpret, block_h=1):
    block_q, block_k = blocks[:2]
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    num_q = pl.cdiv(t_q, block_q)
    num_k = pl.cdiv(t_k, block_k)
    has_mask = mask_bias is not None
    statics = dict(sm_scale=sm_scale, causal=causal, window=window,
                   num_k=num_k, t_q=t_q, t_k=t_k, has_mask=has_mask)
    if block_h > 1:     # the h-fold kernel computes its block as one tile
        kern = functools.partial(_fwd_kernel_hfold, block_q=block_q,
                                 block_k=block_k, **statics)
    else:
        kern = functools.partial(_fwd_kernel, blocks=blocks, num_q=num_q,
                                 **statics)
    kv_map = _kv_sticky_map(causal=causal, window=window, block_q=block_q,
                            block_k=block_k, num_k=num_k)
    in_specs = [
        pl.BlockSpec((block_h, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((block_h, block_k, d), kv_map),
        pl.BlockSpec((block_h, block_k, d), kv_map),
    ]
    inputs = [_pad(q, block_q, axis=1), _pad(k, block_k, axis=1),
              _pad(v, block_k, axis=1)]
    if has_mask:
        heads = bh // mask_bias.shape[0]  # bias rows are per-batch
        # folded index b covers heads [b*block_h, (b+1)*block_h) — one
        # batch row serves them all (wrapper enforces block_h | heads)
        in_specs.append(
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda b, i, j: (b * block_h // heads,
                                          kv_map(b, i, j)[1], 0, 0)))
        inputs.append(_bias_blocks(mask_bias, block_k))
    if block_h > 1:
        scratch = [pltpu.VMEM((block_h, block_q, _STAT_LANES), jnp.float32),
                   pltpu.VMEM((block_h, block_q, _STAT_LANES), jnp.float32),
                   pltpu.VMEM((block_h, block_q, d), jnp.float32)]
    elif num_k > 1:
        scratch = [pltpu.VMEM((block_q, 1), jnp.float32),
                   pltpu.VMEM((block_q, 1), jnp.float32),
                   pltpu.VMEM((block_q, d), jnp.float32)]
    else:
        scratch = []
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
            jax.ShapeDtypeStruct((bh, num_q * block_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, num_q, 1, block_q), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_flash_fwd",
    )(*inputs)
    return out[:, :t_q], lse.reshape(bh, num_q * block_q)[:, :t_q]


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               sm_scale, causal, window, blocks, num_q, num_k, t_q, t_k,
               has_mask):
    mb_ref = rest[0] if has_mask else None
    dq_ref, *scratch = rest[1:] if has_mask else rest
    block_q, block_k, tq, tk = blocks
    static = num_q == num_k == 1
    i, j = (0, 0) if static else (pl.program_id(1), pl.program_id(2))
    q0, k0 = i * block_q, j * block_k
    edges = dict(causal=causal, window=window, t_q=t_q, t_k=t_k)

    def run(masked):
        for r in range(block_q // tq):
            rows = _rows(r, tq)
            q, s_scale = _prescale(q_ref[0, rows, :], sm_scale)
            do = do_ref[0, rows, :]
            lse = lse_ref[0, 0, 0, rows][:, None]
            delta = delta_ref[0, 0, 0, rows][:, None]
            # a fully-masked VALID q row has lse == -inf; exp(s - lse) would
            # be exp(-inf + inf) = nan — +inf in its place gives p = 0 there
            # (the output was 0 too), once a row and not once a score.
            lse = jnp.where(lse == _NEG_INF, jnp.inf, lse)
            acc = None if num_k == 1 else scratch[0][rows, :]
            for s, m in _row_tiles("dq", r, blocks, q0=q0, k0=k0,
                                   masked=masked, **edges):
                keys = _rows(s, tk)
                k, v = k_ref[0, keys, :], v_ref[0, keys, :]
                sc = _dot(q, k, _NT)                         # [tq, tk]
                if s_scale != 1.0:
                    sc = sc * s_scale
                if m:
                    sc = jnp.where(
                        _tile_mask(q0 + r * tq, k0 + s * tk, sc.shape,
                                   causal=causal, window=window, t_k=t_k),
                        sc, _NEG_INF)
                if has_mask:
                    sc = sc + mb_ref[0, 0, :, keys]
                p = jnp.exp(sc - lse)
                ds = p * (_dot(do, v, _NT) - delta)   # × sm_scale: at the end
                dq = _dot(ds.astype(k.dtype), k, _NN)
                acc = dq if acc is None else acc + dq
            if acc is None:
                acc = jnp.zeros((tq, q.shape[1]), jnp.float32)
            if num_k == 1:
                dq_ref[0, rows, :] = (acc * sm_scale).astype(dq_ref.dtype)
            else:
                scratch[0][rows, :] = acc

    if num_k > 1:
        @pl.when(j == 0)
        def _init():
            scratch[0][...] = jnp.zeros(scratch[0].shape, jnp.float32)

    for cond, masked in _variants("dq", i, j, blocks, causal=causal,
                                  window=window, t_k=t_k, static=static,
                                  one_step=num_k == 1):
        _when(cond)(functools.partial(run, masked))

    if num_k > 1:
        @pl.when(j == num_k - 1)
        def _finalize():
            dq_ref[0] = (scratch[0][...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                sm_scale, causal, window, blocks, num_q, num_k, t_q, t_k,
                has_mask):
    """dk and dv of one key block, on TRANSPOSED scores [keys, queries]:
    logsumexp and delta are rows as they lie in memory, and ``p^T dO`` and
    ``ds^T q`` are plain products. The key mask never meets a score here:
    rows of dk/dv are independent, so a masked key's rows are zeroed once
    at the end (a select: whatever overflowed in them is dropped)."""
    mb_ref = rest[0] if has_mask else None
    dk_ref, dv_ref, *scratch = rest[1:] if has_mask else rest
    block_q, block_k, tq, tk = blocks
    static = num_q == num_k == 1
    # k-block outer, q-block inner
    j, i = (0, 0) if static else (pl.program_id(1), pl.program_id(2))
    q0, k0 = i * block_q, j * block_k
    edges = dict(causal=causal, window=window, t_q=t_q, t_k=t_k)

    def finish(rows, dk, dv):
        dk = dk * sm_scale
        if has_mask:
            keep = mb_ref[0, 0, 0, rows][:, None] == 0.0     # [tk, 1]
            dk, dv = jnp.where(keep, dk, 0.0), jnp.where(keep, dv, 0.0)
        dk_ref[0, rows, :] = dk.astype(dk_ref.dtype)
        dv_ref[0, rows, :] = dv.astype(dv_ref.dtype)

    def run(masked):
        for r in range(block_k // tk):
            rows = _rows(r, tk)
            k, s_scale = _prescale(k_ref[0, rows, :], sm_scale)
            v = v_ref[0, rows, :]
            acc = (None if num_q == 1
                   else (scratch[0][rows, :], scratch[1][rows, :]))
            for s, m in _row_tiles("dkv", r, blocks, q0=q0, k0=k0,
                                   masked=masked, **edges):
                qs = _rows(s, tq)
                q, do = q_ref[0, qs, :], do_ref[0, qs, :]
                lse, delta = lse_ref[0, 0, :, qs], delta_ref[0, 0, :, qs]
                lse = jnp.where(lse == _NEG_INF, jnp.inf, lse)  # as in dq
                sc = _dot(k, q, _NT)                         # [tk, tq]
                if s_scale != 1.0:
                    sc = sc * s_scale
                if m:
                    sc = jnp.where(
                        _tile_mask(q0 + s * tq, k0 + r * tk, sc.shape,
                                   causal=causal, window=window, t_k=t_k,
                                   keys_first=True), sc, _NEG_INF)
                p = jnp.exp(sc - lse)
                dv = _dot(p.astype(do.dtype), do, _NN)
                ds = p * (_dot(v, do, _NT) - delta)   # × sm_scale: at the end
                dk = _dot(ds.astype(q.dtype), q, _NN)
                acc = (dk, dv) if acc is None else (acc[0] + dk, acc[1] + dv)
            if acc is None:
                acc = (jnp.zeros((tk, k.shape[1]), jnp.float32),) * 2
            if num_q == 1:
                finish(rows, *acc)
            else:
                scratch[0][rows, :], scratch[1][rows, :] = acc

    if num_q > 1:
        @pl.when(i == 0)
        def _init():
            for ref in scratch:
                ref[...] = jnp.zeros(ref.shape, jnp.float32)

    # same block-liveness predicate as fwd/dq (it is symmetric in the block)
    for cond, masked in _variants("dkv", i, j, blocks, causal=causal,
                                  window=window, t_k=t_k, static=static,
                                  one_step=num_q == 1):
        _when(cond)(functools.partial(run, masked))

    if num_q > 1:
        @pl.when(i == num_q - 1)
        def _finalize():
            for r in range(block_k // tk):
                rows = _rows(r, tk)
                finish(rows, scratch[0][rows, :], scratch[1][rows, :])


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_dq(q, k, v, mask_bias, lse, delta, do, *, sm_scale, causal, window,
            blocks, interpret):
    # every operand is padded for THIS kernel's grid: the two backward
    # kernels (and the forward) may run different blocks. q stays the first
    # operand — the benchmark's flash_attn_roofline finds the call by it.
    block_q, block_k = blocks[:2]
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    num_q, num_k = pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k)
    rows = [_pad(x, block_q, 1).reshape(bh, num_q, 1, block_q)
            for x in (lse, delta)]
    kv_map = _kv_sticky_map(causal=causal, window=window, block_q=block_q,
                            block_k=block_k, num_k=num_k)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j: (b, i, 0, 0)),
        pl.BlockSpec((1, 1, 1, block_q), lambda b, i, j: (b, i, 0, 0)),
    ]
    mask_in = []
    if mask_bias is not None:
        heads = bh // mask_bias.shape[0]  # bias is per-batch
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, block_k),
            lambda b, i, j: (b // heads, kv_map(b, i, j)[1], 0, 0)))
        mask_in = [_bias_blocks(mask_bias, block_k)]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=causal, window=window,
            blocks=blocks, num_q=num_q, num_k=num_k, t_q=t_q, t_k=t_k,
            has_mask=mask_bias is not None),
        grid=(bh, num_q, num_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, num_q * block_q, d), q.dtype),
        scratch_shapes=([pltpu.VMEM((block_q, d), jnp.float32)]
                        if num_k > 1 else []),
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_flash_dq",
    )(_pad(q, block_q, 1), _pad(k, block_k, 1), _pad(v, block_k, 1),
      _pad(do, block_q, 1), *rows, *mask_in)
    return dq[:, :t_q]


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_dkv(q, k, v, mask_bias, lse, delta, do, *, sm_scale, causal,
             window, blocks, interpret):
    block_q, block_k = blocks[:2]
    bh, t_q, d = q.shape
    t_k = k.shape[1]
    num_q, num_k = pl.cdiv(t_q, block_q), pl.cdiv(t_k, block_k)
    rows = [_pad(x, block_q, 1).reshape(bh, num_q, 1, block_q)
            for x in (lse, delta)]
    q_map = _q_sticky_map(causal=causal, window=window, block_q=block_q,
                          block_k=block_k, num_q=num_q)
    q_map4 = _q_sticky_map(causal=causal, window=window, block_q=block_q,
                           block_k=block_k, num_q=num_q, rank4=True)
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, 1, 1, block_q), q_map4),
        pl.BlockSpec((1, 1, 1, block_q), q_map4),
    ]
    mask_in = []
    if mask_bias is not None:
        heads = bh // mask_bias.shape[0]
        in_specs.append(pl.BlockSpec(
            (1, 1, 1, block_k), lambda b, j, i: (b // heads, j, 0, 0)))
        mask_in = [_bias_blocks(mask_bias, block_k)]
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=causal, window=window,
            blocks=blocks, num_q=num_q, num_k=num_k, t_q=t_q, t_k=t_k,
            has_mask=mask_bias is not None),
        grid=(bh, num_k, num_q),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, num_k * block_k, d), x.dtype)
                   for x in (k, v)],
        scratch_shapes=([pltpu.VMEM((block_k, d), jnp.float32)] * 2
                        if num_q > 1 else []),
        compiler_params=_compiler_params(("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_flash_dkv",
    )(_pad(q, block_q, 1), _pad(k, block_k, 1), _pad(v, block_k, 1),
      _pad(do, block_q, 1), *rows, *mask_in)
    return dk[:, :t_k], dv[:, :t_k]


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, mask_bias, causal, window, sm_scale, blocks, interpret,
           block_h):
    out, _ = _fwd(q, k, v, mask_bias, sm_scale=sm_scale, causal=causal,
                  window=window, blocks=blocks.fwd, interpret=interpret,
                  block_h=block_h)
    return out


def _flash_fwd(q, k, v, mask_bias, causal, window, sm_scale, blocks,
               interpret, block_h):
    out, lse = _fwd(q, k, v, mask_bias, sm_scale=sm_scale, causal=causal,
                    window=window, blocks=blocks.fwd, interpret=interpret,
                    block_h=block_h)
    return out, (q, k, v, mask_bias, out, lse)


def _flash_bwd(causal, window, sm_scale, blocks, interpret, block_h, res,
               do):
    del block_h  # fwd-only lever; the backward keeps the proven 2-D grids
    q, k, v, mask_bias, out, lse = res
    # delta = rowsum(dO * O): cheap elementwise+reduce, XLA fuses it fine.
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    kw = dict(sm_scale=sm_scale, causal=causal, window=window,
              interpret=interpret)
    # the two kernels stream OPPOSITE extents (_dq walks keys along a query
    # tile, _dkv queries along a key tile), so each has blocks of its own
    dq = _bwd_dq(q, k, v, mask_bias, lse, delta, do, blocks=blocks.dq, **kw)
    dk, dv = _bwd_dkv(q, k, v, mask_bias, lse, delta, do, blocks=blocks.dkv,
                      **kw)
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


def resolve_blocks(t_q: int, t_k: int, d_head: int, *, causal: bool,
                   itemsize: int, plan=None, block_q: int = 0,
                   block_k: int = 0, block_q_bwd: int = 0,
                   block_k_bwd: int = 0) -> FlashBlocks:
    """Explicit arguments, then a MEASURED tuner entry (``plan``), then
    :func:`flash_blocks`. A pinned or banked pair names the (query, key)
    BLOCK of a kernel; its tile is the rule's a side where that divides the
    block, else the block whole (one tile, as before there were two
    levels). When the forward pair is pinned and the backward's is not, the
    backward inherits the forward's."""
    rule = flash_blocks(t_q, t_k, d_head, causal=causal, itemsize=itemsize)
    banked = plan is not None and plan.measured

    def one(kernel, bq, bk):
        r_bq, r_bk, r_tq, r_tk = getattr(rule, kernel)
        if not (bq or bk):
            return r_bq, r_bk, r_tq, r_tk
        bq = min(bq, max(t_q, 1)) if bq else r_bq
        bk = min(bk, max(t_k, 1)) if bk else r_bk
        return (bq, bk, bq if bq % r_tq else r_tq, bk if bk % r_tk else r_tk)

    fq = block_q or (banked and plan.block_q) or 0
    fk = block_k or (banked and plan.block_k) or 0
    if block_q or block_k:      # a pinned forward: bwd inherits unless set
        bq_b, bk_b = block_q_bwd or fq, block_k_bwd or fk
    else:
        bq_b = block_q_bwd or (banked and plan.block_q_bwd) or fq
        bk_b = block_k_bwd or (banked and plan.block_k_bwd) or fk
    return FlashBlocks(fwd=one("fwd", fq, fk), dq=one("dq", bq_b, bk_b),
                       dkv=one("dkv", bq_b, bk_b))


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
    attends keys in (t-window, t]. Tiles (blocks, over several) entirely
    outside the window are SKIPPED, so compute is O(T·window) not O(T²/2).

    ``block_h > 1`` (opt-in): fold that many heads into each forward grid
    step — batched MXU contractions amortize the fixed per-step overhead
    (see :func:`_fwd_kernel_hfold`). Must divide ``heads``. Forward only;
    the backward keeps its proven 2-D grids.

    ``block_q`` / ``block_k`` (0 = auto): the (query, key) BLOCK one
    forward grid step fetches. ``block_q_bwd`` / ``block_k_bwd``: the same
    pair for the two backward kernels, which stream the opposite extents
    (``_dq`` walks keys, ``_dkv`` queries; the train cells'
    ``flash_bwd_roofline`` measures them). The score tile a block is
    computed in is the shape rule's.

    Block arguments left at 0 resolve, in this order, through a MEASURED
    entry of the kernel-tune cache (:mod:`dtf_tpu.tune.resolver` — none
    is banked for flash today; docs/TUNING.md) and :func:`flash_blocks`,
    the shape rule read off the on-chip sweep. Explicit values always win;
    an explicit value that differs from a MEASURED winner warns once. When
    the forward blocks are pinned explicitly, unset backward blocks keep
    the inherit-the-fwd contract instead of mixing a tuned bwd with a
    pinned fwd.
    """
    if q.ndim != 4:
        raise ValueError(f"expected [B, H, T, D], got shape {q.shape}")
    if window < 0 or (window and not causal):
        raise ValueError(
            f"window={window} must be >= 0 and requires causal=True")
    b, h, t_q, d = q.shape
    t_k = k.shape[2]
    plan = None
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
        block_h = block_h or plan.block_h
    block_h = block_h or 1
    if block_h < 1 or h % block_h:
        raise ValueError(f"block_h={block_h} must be >= 1 and divide "
                         f"heads={h}")
    blocks = resolve_blocks(
        t_q, t_k, d, causal=causal, itemsize=jnp.dtype(q.dtype).itemsize,
        plan=plan, block_q=block_q, block_k=block_k,
        block_q_bwd=block_q_bwd, block_k_bwd=block_k_bwd)
    scale = float(sm_scale) if sm_scale is not None else d ** -0.5
    qr = q.reshape(b * h, t_q, d)
    kr = k.reshape(b * h, t_k, d)
    vr = v.reshape(b * h, t_k, d)
    mask_bias = None
    if kv_mask is not None:
        if kv_mask.shape != (b, t_k):
            raise ValueError(
                f"kv_mask shape {kv_mask.shape} != (batch, t_k)=({b}, {t_k})")
        mask_bias = _mask_bias(kv_mask)
    out = _flash(qr, kr, vr, mask_bias, causal, int(window), scale, blocks,
                 interpret, int(block_h))
    return out.reshape(b, h, t_q, d)
