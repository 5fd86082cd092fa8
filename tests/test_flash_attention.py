"""Flash-attention kernel vs dense reference — fwd and grads, interpret mode.

CPU has no Mosaic, so every pallas_call here runs with interpret=True; the
same code path is compiled for the chip at real widths by
tests/test_chip_compile.py and run there by chip_smoke.py.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.ops.attention import dense_attention
from dtf_tpu.ops.flash_attention import flash_attention


def _rand(shape, dtype, seed):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32
                             ).astype(dtype)


def _flash(q, k, v, **kw):
    return flash_attention(q, k, v, block_q=32, block_k=32, interpret=True,
                           **kw)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("t", [64, 67])  # aligned and padded paths
def test_forward_matches_dense(causal, t):
    b, h, d = 2, 3, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, i) for i in range(3))
    out = _flash(q, k, v, causal=causal)
    ref = dense_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_grads_match_dense(causal):
    b, h, t, d = 2, 2, 48, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, 10 + i) for i in range(3))
    g = _rand((b, h, t, d), jnp.float32, 99)

    def loss_flash(q, k, v):
        return jnp.sum(_flash(q, k, v, causal=causal) * g)

    def loss_dense(q, k, v):
        return jnp.sum(dense_attention(q, k, v, causal=causal) * g)

    grads_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    grads_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(grads_f, grads_d, "qkv"):
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")


def test_grads_match_dense_unaligned():
    """Padded query rows must not pollute dk/dv (the q-mask in the bwd)."""
    b, h, t, d = 1, 2, 41, 8
    q, k, v = (_rand((b, h, t, d), jnp.float32, 20 + i) for i in range(3))

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    grads_f = jax.grad(functools.partial(loss, _flash), argnums=(0, 1, 2))(
        q, k, v)
    grads_d = jax.grad(
        functools.partial(loss, dense_attention), argnums=(0, 1, 2))(q, k, v)
    for gf, gd in zip(grads_f, grads_d):
        assert np.all(np.isfinite(gf))
        np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("bqb,bkb", [(16, 64), (64, 16), (64, 64)])
def test_bwd_blocks_differ_from_fwd(bqb, bkb):
    """block_q_bwd/block_k_bwd reshape ONLY the backward grids: forward
    output and all three grads must match dense with bwd blocks unlike
    the fwd ones (incl. unaligned T so both pads differ), causal+window."""
    b, h, t, d = 1, 2, 83, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, 30 + i) for i in range(3))
    g = _rand((b, h, t, d), jnp.float32, 77)

    # kv_mask included: the residual bias is padded to the FWD block_k and
    # must be re-padded for the bwd grid (the review-found OOB read)
    kv_mask = jnp.arange(t)[None, :] < (t - 7)
    for kw in ({"causal": True}, {"causal": True, "window": 24},
               {"kv_mask": kv_mask}):
        dense_kw = (dict(kw) if "kv_mask" not in kw
                    else {"bias": jnp.where(kv_mask, 0.0, -jnp.inf)[
                        :, None, None, :]})

        def loss_flash(q, k, v):
            out = flash_attention(q, k, v, block_q=32, block_k=32,
                                  block_q_bwd=bqb, block_k_bwd=bkb,
                                  interpret=True, **kw)
            return jnp.sum(out * g)

        def loss_dense(q, k, v):
            return jnp.sum(dense_attention(q, k, v, **dense_kw) * g)

        np.testing.assert_allclose(
            flash_attention(q, k, v, block_q=32, block_k=32,
                            block_q_bwd=bqb, block_k_bwd=bkb,
                            interpret=True, **kw),
            dense_attention(q, k, v, **dense_kw), atol=2e-5, rtol=2e-5)
        grads_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        grads_d = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
        for gf, gd, name in zip(grads_f, grads_d, "qkv"):
            np.testing.assert_allclose(gf, gd, atol=1e-4, rtol=1e-4,
                                       err_msg=f"d{name} {kw}")


def test_bf16_close_to_f32_dense():
    b, h, t, d = 2, 2, 64, 32
    qf, kf, vf = (_rand((b, h, t, d), jnp.float32, 30 + i) for i in range(3))
    out = _flash(qf.astype(jnp.bfloat16), kf.astype(jnp.bfloat16),
                 vf.astype(jnp.bfloat16))
    assert out.dtype == jnp.bfloat16
    ref = dense_attention(qf, kf, vf)
    np.testing.assert_allclose(out.astype(jnp.float32), ref, atol=4e-2,
                               rtol=4e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_kv_mask_matches_dense_bias(causal):
    """Padding mask (kv_mask) == dense with a -inf bias, fwd and grads —
    including a row with a masked tail crossing a block boundary."""
    b, h, t, d = 2, 3, 67, 32
    q, k, v = (_rand((b, h, t, d), jnp.float32, s) for s in range(3))
    mask = np.ones((b, t), bool)
    mask[0, 40:] = False            # crosses the 32-block boundary
    mask[1, :5] = False             # masked head of the sequence
    mask = jnp.asarray(mask)
    bias = jnp.where(mask[:, None, None, :], 0.0, -jnp.inf)

    want = dense_attention(q, k, v, causal=causal, bias=bias)
    got = _flash(q, k, v, causal=causal, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    f = lambda q, k, v: _flash(  # noqa: E731
        q, k, v, causal=causal, kv_mask=mask).sum()
    g = lambda q, k, v: dense_attention(  # noqa: E731
        q, k, v, causal=causal, bias=bias).sum()
    for a, b_ in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                     jax.grad(g, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_kv_mask_all_masked_row_zero_output_and_grad():
    """A sequence whose keys are ALL padded: output 0, grads finite and 0
    into that sequence's K/V (the nan trap is exp(s - (-inf)) in the bwd)."""
    b, h, t, d = 2, 2, 32, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, s) for s in range(3))
    mask = np.ones((b, t), bool)
    mask[1, :] = False
    mask = jnp.asarray(mask)
    out = _flash(q, k, v, kv_mask=mask)
    np.testing.assert_array_equal(np.asarray(out[1]), 0.0)
    dq, dk, dv = jax.grad(
        lambda q, k, v: _flash(q, k, v, kv_mask=mask).sum(),
        (0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_array_equal(np.asarray(dk[1]), 0.0)
    np.testing.assert_array_equal(np.asarray(dv[1]), 0.0)


@pytest.mark.parametrize("t,window", [(128, 32), (130, 48), (96, 96)])
def test_window_matches_dense(t, window):
    """Sliding-window flash == dense with the window mask, fwd and grads —
    windows smaller than, straddling, and equal to block boundaries."""
    b, h, d = 2, 2, 32
    q, k, v = (_rand((b, h, t, d), jnp.float32, s) for s in range(3))
    want = dense_attention(q, k, v, causal=True, window=window)
    got = _flash(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    f = lambda q, k, v: _flash(  # noqa: E731
        q, k, v, causal=True, window=window).sum()
    g = lambda q, k, v: dense_attention(  # noqa: E731
        q, k, v, causal=True, window=window).sum()
    for a, b_ in zip(jax.grad(f, (0, 1, 2))(q, k, v),
                     jax.grad(g, (0, 1, 2))(q, k, v)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-4, atol=2e-4)


def test_window_geq_t_equals_full_causal():
    b, h, t, d = 1, 2, 64, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, s) for s in range(3))
    full = _flash(q, k, v, causal=True)
    win = _flash(q, k, v, causal=True, window=t)
    np.testing.assert_allclose(np.asarray(full), np.asarray(win),
                               rtol=1e-6, atol=1e-6)


def test_window_composes_with_kv_mask():
    b, h, t, d = 2, 2, 64, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, s) for s in range(3))
    mask = np.ones((b, t), bool)
    mask[0, 50:] = False
    mask = jnp.asarray(mask)
    bias = jnp.where(mask[:, None, None, :], 0.0, -jnp.inf)
    want = dense_attention(q, k, v, causal=True, window=24, bias=bias)
    got = _flash(q, k, v, causal=True, window=24, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_window_requires_causal():
    q, k, v = (_rand((1, 1, 16, 8), jnp.float32, s) for s in range(3))
    with pytest.raises(ValueError, match="causal"):
        _flash(q, k, v, causal=False, window=8)


def test_kv_mask_shape_validated():
    q, k, v = (_rand((2, 2, 16, 8), jnp.float32, s) for s in range(3))
    with pytest.raises(ValueError, match="kv_mask"):
        _flash(q, k, v, kv_mask=jnp.ones((2, 8), bool))


def test_cross_attention_lengths():
    b, h, tq, tk, d = 1, 2, 33, 70, 16
    q = _rand((b, h, tq, d), jnp.float32, 40)
    k = _rand((b, h, tk, d), jnp.float32, 41)
    v = _rand((b, h, tk, d), jnp.float32, 42)
    out = _flash(q, k, v)
    ref = dense_attention(q, k, v)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_sm_scale_override():
    b, h, t, d = 1, 1, 32, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, 50 + i) for i in range(3))
    out = _flash(q, k, v, sm_scale=0.5)
    ref = dense_attention(q, k, v, sm_scale=0.5)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_sharded_rejects_seq_mesh():
    """ADVICE r3: forcing flash on a seq-sharded mesh would silently
    all-gather the sequence per shard — must raise, pointing at ring/halo."""
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.ops.flash_attention import flash_attention_sharded

    mesh = make_mesh(MeshConfig(data=2, seq=2, model=2))
    q = jnp.zeros((2, 2, 64, 32))
    with pytest.raises(ValueError, match="seq"):
        flash_attention_sharded(q, q, q, mesh, causal=True)


@pytest.mark.parametrize("batch", [2, 8, 6])
def test_flash_sharded_keeps_whole_only_a_batch_smaller_than_the_data_axis(
        batch):
    """Two sequences evaluated on a data-parallel mesh of four (the
    benchmark's reference check in a four-chip cell) cannot be sharded:
    every data shard computes the whole batch. Eight are sharded as ever;
    six, a mis-sized training batch, are still refused."""
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.ops.flash_attention import flash_attention_sharded

    mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (batch, 2, 16, 8))
               for i in range(3))
    if batch == 6:
        with pytest.raises(ValueError, match="divisible|evenly"):
            flash_attention_sharded(q, k, v, mesh, causal=True,
                                    interpret=True)
        return
    got = flash_attention_sharded(q, k, v, mesh, causal=True,
                                  interpret=True)
    np.testing.assert_allclose(got, dense_attention(q, k, v, causal=True),
                               atol=2e-5)
    mask = jnp.ones((batch, 16), bool).at[:, 12:].set(False)
    got = flash_attention_sharded(q, k, v, mesh, kv_mask=mask,
                                  interpret=True)
    assert got.shape == q.shape and bool(jnp.isfinite(got).all())


@pytest.mark.parametrize("block_h", [2, 4])
@pytest.mark.parametrize("causal,window", [(False, 0), (True, 0), (True, 24)])
def test_hfold_forward_matches_dense(block_h, causal, window):
    """Head-folded forward grid (block_h heads per step) == dense, across
    full/causal/windowed and the padded-T path."""
    b, h, t, d = 2, 4, 67, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, 7 + i) for i in range(3))
    out = _flash(q, k, v, causal=causal, window=window, block_h=block_h)
    ref = dense_attention(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_hfold_kv_mask_and_grads():
    """h-fold with the per-batch padding mask; grads route through the
    (unchanged 2-D) backward."""
    b, h, t, d = 2, 4, 64, 16
    q, k, v = (_rand((b, h, t, d), jnp.float32, 20 + i) for i in range(3))
    mask = np.ones((b, t), bool)
    mask[0, 50:] = False
    mask = jnp.asarray(mask)
    bias = jnp.where(mask[:, None, None, :], 0.0, -jnp.inf)
    out = _flash(q, k, v, kv_mask=mask, block_h=2)
    ref = dense_attention(q, k, v, bias=bias)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)
    g = jax.grad(lambda q, k, v: _flash(
        q, k, v, causal=True, block_h=2).sum(), (0, 1, 2))(q, k, v)
    gw = jax.grad(lambda q, k, v: dense_attention(
        q, k, v, causal=True).sum(), (0, 1, 2))(q, k, v)
    for a, b_ in zip(g, gw):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   atol=2e-4, rtol=2e-4)


def test_hfold_rejects_nondivisible():
    q = jnp.zeros((2, 3, 32, 16))
    with pytest.raises(ValueError, match="block_h"):
        _flash(q, q, q, block_h=2)


# ---- the sub-tile walk (PR 35): a grid step computes only live sub-tiles ---


def _pair_valid(t_q, t_k, causal, window):
    """[t_q, t_k] bool: the dense mask the kernels implement."""
    qp, kp = np.arange(t_q)[:, None], np.arange(t_k)[None, :]
    ok = np.ones((t_q, t_k), bool)
    if causal:
        ok &= qp >= kp
        if window:
            ok &= qp - kp < window
    return ok


@pytest.mark.parametrize("kernel", ["fwd", "dkv"])
@pytest.mark.parametrize("t_q,t_k,blocks,causal,window", [
    (1024, 1024, (1024, 1024, 256, 256), True, 0),
    (1024, 1024, (1024, 1024, 128, 128), True, 0),
    (1024, 1024, (1024, 1024, 256, 512), True, 0),
    (1024, 1024, (1024, 1024, 256, 128), True, 300),
    (640, 640, (640, 640, 128, 128), True, 128),      # window == tile
    (1000, 1000, (1024, 1024, 256, 128), True, 0),    # padded both ways
    (1000, 1000, (1024, 1024, 256, 256), True, 200),
    (520, 520, (640, 640, 128, 128), False, 0),       # padding alone
    (384, 896, (384, 896, 128, 128), True, 0),        # t_q != t_k
    (896, 384, (896, 384, 128, 128), True, 64),
    (200, 450, (256, 512, 128, 128), True, 0),
    (512, 512, (512, 512, 256, 256), False, 0),
    (1024, 1024, (512, 512, 256, 128), True, 0),      # several blocks
    (1000, 1000, (256, 512, 128, 256), True, 200),
    (520, 520, (256, 256, 128, 128), False, 0),
    (4096, 4096, (1024, 1024, 512, 512), True, 0),
])
def test_tiles_match_brute_force(kernel, t_q, t_k, blocks, causal, window):
    """``flash_tiles`` — the kernels' own lists — against the dense mask.
    One block a head (offsets static): exactly the tiles that hold an
    unmasked pair are computed, and exactly those a mask edge crosses are
    masked (for fwd/dq a padded key is such an edge; dkv needs no mask for
    padded rows of either kind). Several blocks: every tile with an
    unmasked pair is computed, every computed tile with a dead pair is
    masked, and nothing is computed in a dead block."""
    from dtf_tpu.ops.flash_attention import flash_tiles

    bq, bk, tq, tk = blocks
    pad_q, pad_k = -(-t_q // bq) * bq, -(-t_k // bk) * bk
    ok = np.zeros((pad_q, pad_k), bool)
    ok[:t_q, :t_k] = _pair_valid(t_q, t_k, causal, window)
    # what a mask must see: every pair the dense rule kills — padded keys
    # too, for fwd/dq; padded query rows never count
    dead = ~np.pad(_pair_valid(pad_q, t_k, causal, window),
                   ((0, 0), (0, pad_k - t_k)),
                   constant_values=(kernel == "dkv"))
    live, edge = {}, {}
    for q0 in range(0, pad_q, tq):
        for k0 in range(0, pad_k, tk):
            live[(q0, k0)] = bool(ok[q0:q0 + tq, k0:k0 + tk].any())
            edge[(q0, k0)] = bool(dead[q0:q0 + tq, k0:k0 + tk].any())
    tiles = flash_tiles(t_q, t_k, blocks, causal=causal, window=window,
                        kernel=kernel)
    got = {(q0, k0): m for q0, k0, *_, m in tiles}
    assert len(got) == len(tiles)
    assert all(shape == [tq, tk] for shape in ([n, m] for _, _, n, m, _
                                               in tiles))
    if (pad_q, pad_k) == (bq, bk):
        assert got == {at: edge[at] for at in live if live[at]}
        return
    assert {at for at in live if live[at]} <= set(got)
    assert all(got[at] for at in got if edge[at])
    for q0, k0 in got:      # its block holds an unmasked pair
        i, j = q0 // bq * bq, k0 // bk * bk
        assert ok[i:i + bq, j:j + bk].any()


@pytest.mark.parametrize("sub,live,masked", [(256, 10, 4), (128, 36, 8)])
def test_causal_1024_tile_counts(sub, live, masked):
    """ISSUE 35's arithmetic: of the 16 tiles of 256² in a causal 1024²
    square 10 are computed and 4 of them masked; of 64 of 128², 36 and 8."""
    from dtf_tpu.ops.flash_attention import flash_tiles

    for kernel in ("fwd", "dq", "dkv"):
        tiles = flash_tiles(1024, 1024, (1024, 1024, sub, sub), causal=True,
                            kernel=kernel)
        assert (len(tiles), sum(m for *_, m in tiles)) == (live, masked)


def _tiled(fwd, bwd=None, tile=128):
    """``FlashBlocks`` with the score tile forced to ``tile`` a side, so
    mask edges cross tiles INSIDE a grid step; ``fwd`` / ``bwd``: the
    (query, key) block of the forward and of both backward kernels."""
    from dtf_tpu.ops.flash_attention import FlashBlocks

    fwd, bwd = (*fwd, tile, tile), (*(bwd or fwd), tile, tile)
    return FlashBlocks(fwd=fwd, dq=bwd, dkv=bwd)


def _flash_with(blocks, q, k, v, *, causal=False, window=0, kv_mask=None):
    """``flash_attention`` below its block resolution: the custom-vjp core
    takes the ``FlashBlocks`` as they are (as ``scripts/flash_sweep.py``
    hands them to the kernels)."""
    from dtf_tpu.ops import flash_attention as fa

    b, h, t_q, d = q.shape
    bias = None if kv_mask is None else fa._mask_bias(kv_mask)
    out = fa._flash(*(x.reshape(b * h, -1, d) for x in (q, k, v)), bias,
                    causal, window, d ** -0.5, blocks, True, 1)
    return out.reshape(q.shape)


def _check_against_dense(t_q, t_k, kw, blocks, d=16, atol=2e-5, gtol=2e-4):
    b, h = 2, 2
    q = _rand((b, h, t_q, d), jnp.float32, 60)
    k = _rand((b, h, t_k, d), jnp.float32, 61)
    v = _rand((b, h, t_k, d), jnp.float32, 62)
    g = _rand((b, h, t_q, d), jnp.float32, 63)
    dense_kw = dict(kw)
    if "kv_mask" in kw:
        dense_kw["bias"] = jnp.where(dense_kw.pop("kv_mask"), 0.0,
                                     -jnp.inf)[:, None, None, :]
    flash = functools.partial(_flash_with, blocks, **kw)

    np.testing.assert_allclose(flash(q, k, v),
                               dense_attention(q, k, v, **dense_kw),
                               atol=atol, rtol=atol)
    grads_f = jax.grad(lambda *a: jnp.sum(flash(*a) * g), (0, 1, 2))(q, k, v)
    grads_d = jax.grad(lambda *a: jnp.sum(
        dense_attention(*a, **dense_kw) * g), (0, 1, 2))(q, k, v)
    for gf, gd, name in zip(grads_f, grads_d, "qkv"):
        assert np.isfinite(np.asarray(gf)).all(), name
        np.testing.assert_allclose(gf, gd, atol=gtol, rtol=gtol,
                                   err_msg=f"d{name}")
    return grads_f


def _tail_mask(t, pad):
    m = np.ones((2, t), bool)
    m[0, t - pad:] = False       # a padded tail that crosses a tile
    m[1, :pad // 2] = False      # and a masked head of the sequence
    return jnp.asarray(m)


WALK_CASES = {
    "causal": (512, 512, {"causal": True}),
    "window": (512, 512, {"causal": True, "window": 160}),
    "window_lt_sub": (384, 384, {"causal": True, "window": 40}),
    "kv_mask": (384, 384, {"kv_mask": _tail_mask(384, 150)}),
    "causal_kv_mask": (384, 384, {"causal": True,
                                  "kv_mask": _tail_mask(384, 150)}),
    "unaligned": (330, 330, {"causal": True}),
    "unaligned_noncausal": (330, 330, {}),
    "cross": (200, 450, {}),
    "cross_causal": (450, 200, {"causal": True}),
    # keys past the last query: whole key blocks are dead
    "cross_causal_short_q": (200, 450, {"causal": True}),
    # queries whose window lies past the last key: whole query blocks are
    # dead, and their rows all-masked (output 0, gradient 0)
    "cross_window_short_k": (450, 200, {"causal": True, "window": 100}),
}

# how a sequence of up to 512 goes in blocks, tiles of 128 throughout
WALK_BLOCKS = {
    # one block a head: the offsets are static, the tile lists exact, the
    # statistics values carried along a row of tiles, no scratch
    "one": _tiled((512, 512)),
    # blocks of a quarter of the sequence at most: the grid skips dead
    # blocks, a block runs all its tiles masked or all unmasked, scratch
    # carries between grid steps
    "many": _tiled((128, 256), (256, 128)),
}


@pytest.mark.parametrize("num_k", ["one", "many", "row"])
@pytest.mark.parametrize("case", sorted(WALK_CASES))
def test_sub_tile_walk_matches_dense(case, num_k):
    """Forward and all three gradients against ``dense_attention``, each
    mask kind under each way to cut it in blocks: ``WALK_BLOCKS``, and
    ``row`` — several blocks of 128, each row of them ONE grid step (the
    forward's and dq's keys, dkv's queries): traced offsets and no
    scratch, so a dead block has to write its own zeros."""
    t_q, t_k, kw = WALK_CASES[case]
    if num_k == "row":
        pad_q, pad_k = (-(-t // 128) * 128 for t in (t_q, t_k))
        blocks = _tiled((128, pad_k))._replace(dkv=(pad_q, 128, 128, 128))
    else:
        blocks = WALK_BLOCKS[num_k]
    _, dk, dv = _check_against_dense(t_q, t_k, kw, blocks)
    if case == "cross_causal_short_q":      # exact zeros, not stale VMEM
        assert not np.asarray(dk)[:, :, t_q:].any()
        assert not np.asarray(dv)[:, :, t_q:].any()


@pytest.mark.parametrize("d,pow2", [(16, True), (24, False)])
def test_scale_folded_only_when_exact(d, pow2):
    """d_head 16 gives a power-of-two scale, folded into the hoisted
    operand; d_head 24 does not and multiplies the scores — both match."""
    import math

    assert (math.frexp(d ** -0.5)[0] == 0.5) == pow2
    _check_against_dense(256, 256, {"causal": True}, _tiled((128, 256)), d=d)


def test_many_tiles_a_row():
    """Twelve tiles along a row, one block (exact lists) and three."""
    _check_against_dense(128, 768, {}, _tiled((128, 768), tile=64))
    _check_against_dense(768, 768, {"causal": True, "window": 200},
                         _tiled((256, 256), tile=64))


@pytest.mark.parametrize("name,shape,kw", [
    ("gpt2m-train-b8s1024", (1024, 1024, 64), dict(causal=True)),
    ("bert-base-train-b256s512", (512, 512, 64), dict()),
    ("long", (8192, 8192, 128), dict(causal=True)),
    ("unaligned", (1000, 1000, 64), dict(causal=True)),
    ("cross", (300, 5000, 128), dict()),
    ("long_noncausal", (4096, 4096, 64), dict()),
])
def test_shape_rule_blocks_are_legal(name, shape, kw):
    """``flash_blocks`` at both train cells' shapes, the sweep's long one
    and a few odd ones: every block and tile a multiple of 128, the tile
    dividing its block, padding under one block, and the estimate under
    the 16 MiB of scoped VMEM (tests/test_chip_compile.py asks the chip's
    compiler the same at the first three)."""
    from dtf_tpu.ops import flash_attention as fa

    t_q, t_k, d = shape
    blocks = fa.flash_blocks(t_q, t_k, d, **kw)
    assert blocks == fa.flash_blocks(t_q, t_k, d, **kw)   # shapes alone
    for kernel in ("fwd", "dq", "dkv"):
        bq, bk, tq, tk = getattr(blocks, kernel)
        assert all(x % 128 == 0 for x in (bq, bk, tq, tk)), (kernel, blocks)
        assert bq % tq == 0 and bk % tk == 0
        assert -(-t_q // bq) * bq - t_q < bq and -(-t_k // bk) * bk - t_k < bk
        assert fa.vmem_bytes(kernel, (bq, bk, tq, tk), d) <= fa._VMEM_LIMIT
