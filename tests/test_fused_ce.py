"""Pallas fused head+CE kernel vs the full-logits reference — interpret
mode (the CPU has no Mosaic; tests/test_chip_compile.py compiles the same
kernels for the chip at real widths, chip_smoke.py runs them there)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.ops.fused_ce import pallas_lm_cross_entropy
from dtf_tpu.ops.losses import softmax_cross_entropy


def _data(seed=0, b=3, t=5, d=16, v=103):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, t, d), jnp.float32)
    w = jax.random.normal(ks[1], (d, v), jnp.float32)
    labels = jax.random.randint(ks[2], (b, t), 0, v)
    return x, w, labels


@pytest.mark.parametrize("ignore", [None, -100])
def test_matches_full_path(ignore):
    """Loss, count, and grads wrt x AND w — with unaligned N (15 tokens,
    block 8) and unaligned V (103, block 32), ignored positions, and an
    out-of-range label, all at once."""
    x, w, labels = _data()
    if ignore is not None:
        labels = labels.at[0, 1].set(ignore).at[2, 3].set(ignore)
    labels = labels.at[1, 4].set(200)  # out of range: picks nothing

    def full(x, w):
        return softmax_cross_entropy(x @ w, labels, ignore_index=ignore)

    def fused(x, w):
        return pallas_lm_cross_entropy(x, w, labels, ignore_index=ignore,
                                       block_n=8, block_v=32,
                                       interpret=True)

    (lf, nf), (lp, np_) = full(x, w), fused(x, w)
    np.testing.assert_allclose(float(lp), float(lf), rtol=1e-6)
    assert float(np_) == float(nf)
    gf = jax.grad(lambda x, w: full(x, w)[0], (0, 1))(x, w)
    gp = jax.grad(lambda x, w: fused(x, w)[0], (0, 1))(x, w)
    for a, b_, name in zip(gp, gf, "xw"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-6, err_msg=name)


def test_dw_kernel_picks_its_own_vocab_block(monkeypatch):
    """The dW kernel halves its vocab block until its tiles fit scoped
    VMEM (the chip's compiler refused 512x1024 at GPT-2 widths with f32
    master weights); the fwd/dx kernels keep the block they were given.
    Grads must not notice."""
    from dtf_tpu.ops import fused_ce

    # the real widths: 1024 -> 512 at d_model 768 and 1024, f32 head
    for d in (768, 1024):
        assert fused_ce._dw_block_v(512, d, 1024, jnp.bfloat16,
                                    jnp.float32) == 512
    assert fused_ce._dw_block_v(8, 16, 32, jnp.float32, jnp.float32) == 32
    # force the halving at a toy size: 512 -> 128 under a 64 KiB "limit"
    monkeypatch.setattr(fused_ce, "_SCOPED_VMEM_BYTES", 64 * 1024)
    assert fused_ce._dw_block_v(8, 16, 512, jnp.float32,
                                jnp.float32) == 128
    x, w, labels = _data(seed=3, v=700)
    gf = jax.grad(lambda x, w: softmax_cross_entropy(x @ w, labels)[0],
                  (0, 1))(x, w)
    gp = jax.grad(lambda x, w: pallas_lm_cross_entropy(
        x, w, labels, block_n=8, block_v=512, interpret=True)[0],
        (0, 1))(x, w)
    for a, b_, name in zip(gp, gf, "xw"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-6, err_msg=name)


def test_all_ignored_is_zero_not_nan():
    x, w, labels = _data(seed=1)
    labels = jnp.full_like(labels, -100)
    loss, cnt = pallas_lm_cross_entropy(
        x, w, labels, ignore_index=-100, block_n=8, block_v=32,
        interpret=True)
    assert float(loss) == 0.0 and float(cnt) == 1.0  # clamped-count rule
    g = jax.grad(lambda x: pallas_lm_cross_entropy(
        x, w, labels, ignore_index=-100, block_n=8, block_v=32,
        interpret=True)[0])(x)
    assert np.all(np.asarray(g) == 0.0)


def test_bf16_activations_f32_head():
    """The production dtype mix: bf16 hidden states, f32 head kernel."""
    x, w, labels = _data(seed=2)
    xb = x.astype(jnp.bfloat16)

    lf, _ = softmax_cross_entropy(
        xb.astype(jnp.float32) @ w, labels, ignore_index=-100)
    lp, _ = pallas_lm_cross_entropy(xb, w, labels, ignore_index=-100,
                                    block_n=8, block_v=32, interpret=True)
    np.testing.assert_allclose(float(lp), float(lf), rtol=2e-2)
    dx, dw = jax.grad(lambda x, w: pallas_lm_cross_entropy(
        x, w, labels, ignore_index=-100, block_n=8, block_v=32,
        interpret=True)[0], (0, 1))(xb, w)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(dx, np.float32)))
    assert np.all(np.isfinite(np.asarray(dw)))


def test_sharded_matches_unsharded_grads(mesh8):
    """The shard_map boundary (DP over tokens, w replicated): loss, count,
    dx AND dW must equal the single-device kernel — dW is the tripwire
    for the replicated-input cotangent psum (exactly once, not 0 or 8x)."""
    from dtf_tpu.ops.fused_ce import pallas_lm_cross_entropy_sharded

    x, w, labels = _data(seed=3, b=8, t=4)
    labels = labels.at[0, 1].set(-100)

    def ref(x, w):
        return softmax_cross_entropy(x @ w, labels, ignore_index=-100)

    def sharded(x, w):
        return pallas_lm_cross_entropy_sharded(
            x, w, labels, mesh8, ignore_index=-100, block_n=4, block_v=32,
            interpret=True)

    (lf, nf), (ls, ns) = ref(x, w), sharded(x, w)
    np.testing.assert_allclose(float(ls), float(lf), rtol=1e-6)
    assert float(ns) == float(nf)
    gf = jax.grad(lambda x, w: ref(x, w)[0], (0, 1))(x, w)
    gs = jax.grad(lambda x, w: sharded(x, w)[0], (0, 1))(x, w)
    for a, b_, name in zip(gs, gf, "xw"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=2e-5, atol=2e-6, err_msg=name)


def test_gpt_loss_pallas_matches_full(mesh8):
    """make_loss(loss_pallas=True) end to end through the GPT model."""
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import shard_batch
    from dtf_tpu.models import gpt
    from tests.test_gpt import SEQ, data_batch

    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)
    model, init_fn = gpt.make_init(cfg, mesh8, seq_len=SEQ)
    tx = optax.adam(1e-3)
    state, _ = tr.create_train_state(init_fn, tx, jax.random.PRNGKey(0),
                                     mesh8, param_rules=gpt.tp_rules)
    batch = shard_batch(data_batch(), mesh8)
    rng = jax.random.PRNGKey(1)
    full, _ = gpt.make_loss(model)(state.params, state.extra, batch, rng)
    fused, _ = gpt.make_loss(model, loss_pallas=True)(
        state.params, state.extra, batch, rng)
    np.testing.assert_allclose(float(fused), float(full), rtol=1e-6)
    with pytest.raises(ValueError, match="mutually exclusive"):
        gpt.make_loss(model, loss_chunk=48, loss_pallas=True)
