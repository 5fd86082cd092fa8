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
