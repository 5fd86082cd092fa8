"""Grouped matrix product for a dropless expert layer (Pallas TPU).

``out[r] = x[r] @ w[group_of_row(r)]`` for rows laid out group by group.
The caller (:mod:`dtf_tpu.parallel.moe`) pads every group to a whole number
of ``tm``-row tiles, so a tile never straddles two experts and the kernel
needs no row mask: one grid step is one plain ``[tm, K] @ [K, tn]`` product
against the tile's own expert, found through a scalar-prefetched
``tile_group`` table (the megablox technique, minus its partial-tile
bookkeeping).

Grid ``(N // tn, tiles)`` with the tiles INNER: consecutive tiles of one
expert name the same weight block, which Pallas then does not fetch again,
so every touched expert's weights cross HBM once per call and an expert no
row chose is never read — the whole point at decode, where a step is the
time it takes to stream the chosen experts. Tiles past ``n_used`` (the
layout's static worst case is ``pairs + groups * (tm - 1)`` rows) keep the
last used expert's block and the last used row tile, so they cost no
traffic, and write zeros: a layer that holds a sixteenth of its experts has
a worst case sixteen times its expected rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: rows of a tile must fill bfloat16's sublane packing
MIN_TILE_ROWS = 16


def _kernel(tile_group_ref, n_used_ref, x_ref, w_ref, o_ref):
    del tile_group_ref                      # read by the index maps only
    used = pl.program_id(1) < n_used_ref[0]

    @pl.when(used)
    def _product():
        o_ref[...] = jnp.dot(
            x_ref[...], w_ref[...],
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    @pl.when(jnp.logical_not(used))
    def _empty():
        o_ref[...] = jnp.zeros_like(o_ref)


#: bytes of one weight block: two of them (double-buffered) and the row
#: tiles stay inside the 16 MiB of scoped VMEM
_WEIGHT_BLOCK_BYTES = 4 * 1024 * 1024


def column_tile(k: int, n: int, itemsize: int = 2) -> int:
    """Columns of a weight block ``[k, tn]``: the widest lane-aligned
    divisor of ``n``, at most half of it, whose block stays under
    ``_WEIGHT_BLOCK_BYTES`` (2048 x 1536 -> 768 and 1536 x 2048 -> 1024, 3
    MiB each; 7168 x 2048 -> 256, 3.5 MiB, and 2048 x 7168 -> 1024, 4 MiB);
    the whole width where ``n`` has no such divisor."""
    fits = [tn for tn in range(128, n // 2 + 1, 128)
            if n % tn == 0 and k * tn * itemsize <= _WEIGHT_BLOCK_BYTES]
    return max(fits) if fits else n


@functools.partial(jax.jit, static_argnames=("tm", "interpret"))
def grouped_matmul(x: jax.Array, w: jax.Array, tile_group: jax.Array,
                   n_used: jax.Array, *, tm: int,
                   interpret: bool = False) -> jax.Array:
    """``x`` [M, K] (M a multiple of ``tm``), ``w`` [G, K, N],
    ``tile_group`` [M // tm] int32 (the expert of each row tile), ``n_used``
    [1] int32 (tiles that hold rows). Returns [M, N] in ``x``'s dtype,
    accumulated in float32."""
    m, k = x.shape
    n = w.shape[2]
    if m % tm or tm % MIN_TILE_ROWS:
        raise ValueError(
            f"rows {m} must be whole tiles of tm={tm}, a multiple of "
            f"{MIN_TILE_ROWS}")
    tn = column_tile(k, n, w.dtype.itemsize)

    def row_tile(j, i, tg, nu):
        # a tile past the used ones names the last used one: no fetch
        return jnp.maximum(jnp.minimum(i, nu[0] - 1), 0), 0

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm),
            in_specs=[
                pl.BlockSpec((tm, k), row_tile),
                pl.BlockSpec((None, k, tn),
                             lambda j, i, tg, nu: (tg[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, tg, nu: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="dtf_moe_gmm",
    )(tile_group, n_used, x, w)
