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
last used expert's block, so they cost no traffic, and write zeros.
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


def column_tile(n: int) -> int:
    """Two column tiles where the halves stay lane-aligned (a 2048 x 768
    bfloat16 block is 3 MiB, 6 MiB double-buffered: inside the 16 MiB of
    scoped VMEM with room for the row tiles), else the whole width."""
    return n // 2 if n % 256 == 0 else n


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
    tn = column_tile(n)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // tn, m // tm),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, tg, nu: (i, 0)),
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
