"""--optimizer flag surface: every named family trains, and each composes
with the framework's optimizer machinery (ZeRO-1 sharded state, grad-accum,
LR schedule, global-norm clipping).

The reference hardcodes GradientDescentOptimizer (SURVEY.md §3.1 frame
``opt = GradientDescentOptimizer``); the capability successor is a recipe
surface: each launcher keeps its era-faithful default (adamw for BERT/GPT,
nesterov SGD for ResNet, adam for Wide&Deep, plain SGD for distributed.py
— SURVEY.md §2a) while ``--optimizer`` swaps in the at-scale families
(lamb: the BERT large-batch recipe; adafactor: factored second moments,
the memory-lean TPU option).
"""

from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dtf_tpu.core import sharding as shd
from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import shard_batch
from dtf_tpu.cli.flags import make_optimizer
from tests.test_train import linear_init, linear_loss, make_batch

OPTIMIZERS = ["sgd", "momentum", "adam", "adamw", "lamb", "adafactor"]


def fl(**kw):
    base = dict(learning_rate=0.05, lr_schedule="constant", warmup_steps=-1,
                lr_min_ratio=0.0, train_steps=100, optimizer="",
                weight_decay=-1.0, clip_grad_norm=0.0)
    base.update(kw)
    return SimpleNamespace(**base)


@pytest.mark.parametrize("name", OPTIMIZERS)
def test_named_optimizer_trains_with_zero1_and_accum(mesh8, name):
    """Loss decreases over 12 steps for every family, with ZeRO-1 state
    sharding and 4-way grad accumulation both on — the BERT config-4
    machinery under each optimizer."""
    tx = make_optimizer(fl(optimizer=name), optax.sgd)
    state, shardings = tr.create_train_state(
        linear_init, tx, jax.random.PRNGKey(0), mesh8, zero1=True)
    step = tr.make_train_step(linear_loss, tx, mesh8, shardings,
                              grad_accum=4)
    batch = shard_batch(make_batch(), mesh8)
    losses = []
    for _ in range(12):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


def test_empty_flag_uses_recipe_default(mesh8):
    """--optimizer="" keeps the launcher's recipe numerics exactly (the
    launch-compatibility contract): same params as hand-built adamw."""
    runs = []
    for tx in (make_optimizer(fl(), lambda s: optax.adamw(s, weight_decay=0.01)),
               optax.adamw(0.05, weight_decay=0.01)):
        state, shardings = tr.create_train_state(
            linear_init, tx, jax.random.PRNGKey(0), mesh8)
        step = tr.make_train_step(linear_loss, tx, mesh8, shardings)
        batch = shard_batch(make_batch(), mesh8)
        for _ in range(5):
            state, _ = step(state, batch)
        runs.append(state.params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6), runs[0], runs[1])


def test_weight_decay_flag_reaches_adamw(mesh8):
    """--weight_decay changes the trajectory of a decayed optimizer (i.e.
    the flag is actually plumbed through, not dropped)."""
    params = []
    for wd in (0.0, 0.5):
        tx = make_optimizer(fl(optimizer="adamw", weight_decay=wd), optax.sgd)
        state, shardings = tr.create_train_state(
            linear_init, tx, jax.random.PRNGKey(0), mesh8)
        step = tr.make_train_step(linear_loss, tx, mesh8, shardings)
        batch = shard_batch(make_batch(), mesh8)
        for _ in range(5):
            state, _ = step(state, batch)
        params.append(np.asarray(state.params["w"]))
    assert np.abs(params[0] - params[1]).max() > 1e-6


def test_unknown_optimizer_raises():
    with pytest.raises(ValueError, match="optimizer"):
        make_optimizer(fl(optimizer="adagrab"), optax.sgd)


def test_ignored_weight_decay_raises():
    """An explicitly-set --weight_decay that nothing would consume is an
    error, not a silent no-op (a wd sweep would otherwise train N
    identical runs)."""
    for name in ("sgd", "momentum", "adam"):
        with pytest.raises(ValueError, match="weight_decay"):
            make_optimizer(fl(optimizer=name, weight_decay=0.1), optax.sgd)
    with pytest.raises(ValueError, match="weight_decay"):
        make_optimizer(fl(weight_decay=0.1), optax.sgd)  # recipe ignores it
    # but a recipe that declares it consumes wd is fine (BERT/GPT/ResNet)
    make_optimizer(fl(weight_decay=0.1), optax.adam, recipe_uses_wd=True)
    # and decay-bearing families are fine
    make_optimizer(fl(optimizer="adafactor", weight_decay=0.1), optax.sgd)


def test_clipping_composes_with_named_optimizer(mesh8):
    """--clip_grad_norm wraps the override too (wrap_optimizer runs inside
    make_optimizer): a tiny clip norm must change the first update."""
    params = []
    for clip in (0.0, 1e-3):
        tx = make_optimizer(fl(optimizer="momentum", clip_grad_norm=clip),
                            optax.sgd)
        state, shardings = tr.create_train_state(
            linear_init, tx, jax.random.PRNGKey(0), mesh8)
        step = tr.make_train_step(linear_loss, tx, mesh8, shardings)
        batch = shard_batch(make_batch(), mesh8)
        state, _ = step(state, batch)
        params.append(np.asarray(state.params["w"]))
    assert np.abs(params[0] - params[1]).max() > 1e-7


@pytest.mark.parametrize("zero1", [True, False])
def test_adafactor_composes_with_tensor_parallel_bias(mesh_4x2, zero1):
    """The crash case the r5 review found: a 1-D bias TP-sharded P("model")
    has adafactor placeholder moments of shape (1,) — SAME rank, different
    dims — which must not inherit the param's spec (4-way partition of a
    size-1 dim is invalid). Covers both the ZeRO-1 and mirror spec paths."""

    def init(rng):
        return {"params": {"w": jax.random.normal(rng, (4, 8)) * 0.1,
                           "b": jnp.zeros((8,))}}

    def loss(params, extra, batch, rng):
        mse = jnp.mean((batch["x"] @ params["w"] + params["b"]
                        - batch["y"]) ** 2)
        return mse, tr.LossAux(extra=extra, metrics={"mse": mse})

    r = np.random.RandomState(0)
    x = r.randn(64, 4).astype(np.float32)
    batch = {"x": x, "y": (x @ r.randn(4, 8)).astype(np.float32)}
    tx = make_optimizer(fl(optimizer="adafactor"), optax.sgd)
    state, shardings = tr.create_train_state(
        init, tx, jax.random.PRNGKey(0), mesh_4x2,
        param_rules=[("b", shd.P("model")), ("w", shd.P(None, "model"))],
        zero1=zero1)
    step = tr.make_train_step(loss, tx, mesh_4x2, shardings)
    state, metrics = step(state, shard_batch(batch, mesh_4x2))
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("name", ["lamb", "adafactor"])
def test_new_family_checkpoint_roundtrip_resumes_identically(
        mesh8, tmp_path, name):
    """Orbax round-trip for the new optimizer families' state trees
    (adafactor's FactoredState is the non-obvious one: rank-reduced
    leaves + ZeRO-1 fresh specs must restore sharding-correct), and the
    resumed run continues bit-identically to the uninterrupted one."""
    from dtf_tpu.checkpoint import Checkpointer

    def build():
        tx = make_optimizer(fl(optimizer=name, learning_rate=0.01),
                            optax.sgd)
        state, shardings = tr.create_train_state(
            linear_init, tx, jax.random.PRNGKey(0), mesh8, zero1=True)
        step = tr.make_train_step(linear_loss, tx, mesh8, shardings)
        return state, step

    batch = shard_batch(make_batch(), mesh8)
    state, step = build()
    for _ in range(3):
        state, _ = step(state, batch)
    # save BEFORE stepping on: the train step donates its input buffers
    ckpt = Checkpointer(tmp_path / "ckpt", async_save=False)
    ckpt.save(3, state, force=True)
    ckpt.wait()
    straight = state
    for _ in range(2):
        straight, _ = step(straight, batch)
    fresh, step2 = build()
    resumed = ckpt.restore(fresh)
    assert int(resumed.step) == 3
    for _ in range(2):
        resumed, _ = step2(resumed, batch)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), straight.params, resumed.params)


def test_adafactor_zero1_specs_are_valid(mesh8):
    """adafactor's factored second moments are rank-reduced vs their params
    ((d0,)/(1,) for a 2-D param), so the ZeRO-1 spec builder cannot reuse
    the param's spec — the fallback starts fresh and data-shards a dim only
    if it divides. The sharded state must materialize AND large factored
    leaves must actually end up sharded over data."""
    big_init = lambda rng: {"params": {  # noqa: E731 — mirrors linear_init
        "w": jax.random.normal(rng, (256, 256)) * 0.01}}
    # min_dim_size_to_factor default is 128, so (256, 256) IS factored:
    # v_row/v_col have shape (256,), divisible by the 8-way data axis
    tx = make_optimizer(fl(optimizer="adafactor"), optax.sgd)
    state, shardings = tr.create_train_state(
        big_init, tx, jax.random.PRNGKey(0), mesh8, zero1=True)
    factored = [s for s in jax.tree.leaves(
        jax.tree.map(lambda x: x.sharding.spec, state.opt_state))
        if s == shd.P("data")]
    assert factored, "no state leaf got a fresh data-axis ZeRO-1 spec"

    # and it still trains (bias-free loss: this model is just one matmul)
    def loss(params, extra, batch, rng):
        mse = jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
        return mse, tr.LossAux(extra=extra, metrics={"mse": mse})

    step = tr.make_train_step(loss, tx, mesh8, shardings)
    batch = {"x": np.random.RandomState(0).randn(64, 256).astype(np.float32)}
    batch["y"] = batch["x"] @ np.random.RandomState(1).randn(
        256, 256).astype(np.float32)
    state, metrics = step(state, shard_batch(batch, mesh8))
    assert np.isfinite(float(metrics["loss"]))


def test_decoupled_decay_promotes_recipe_l2():
    """ADVICE r5 #2: --optimizer=lamb/adafactor with no --weight_decay must
    not silently drop ALL regularization when a launcher's recipe is
    loss-side L2 — the recipe coefficient moves into --weight_decay."""
    from dtf_tpu.cli.flags import resolve_loss_l2

    # decoupled family, wd unset: loss L2 dropped, recipe 1e-4 promoted
    f = fl(optimizer="lamb")
    assert resolve_loss_l2(f, recipe_l2=1e-4) == 0.0
    assert f.weight_decay == pytest.approx(1e-4)
    tx = make_optimizer(f, optax.sgd, recipe_uses_wd=True)
    assert tx is not None   # lamb now carries the promoted decay

    # decoupled family, wd set explicitly: respected, not overwritten
    f = fl(optimizer="adafactor", weight_decay=0.3)
    assert resolve_loss_l2(f, recipe_l2=1e-4) == 0.0
    assert f.weight_decay == pytest.approx(0.3)

    # recipe path (no override): L2 stays on the loss side
    f = fl()
    assert resolve_loss_l2(f, recipe_l2=1e-4) == pytest.approx(1e-4)
    assert f.weight_decay == -1.0
    f = fl(weight_decay=0.05)
    assert resolve_loss_l2(f, recipe_l2=1e-4) == pytest.approx(0.05)

    # non-decoupled override keeps the loss-side L2 at the recipe value
    f = fl(optimizer="momentum")
    assert resolve_loss_l2(f, recipe_l2=1e-4) == pytest.approx(1e-4)
    assert f.weight_decay == -1.0


def test_resolve_lm_loss_auto_picks_from_hbm_estimate():
    """ISSUE 2 satellite: the LM loss path is an HBM decision (PERF.md §5
    — chunking costs ~9 GPT MFU points, it is a memory lever). Monolithic
    when the [B,T,V] logits fit per device, the banked kernel-tune
    winner (token-chunked by default) when they don't; explicit flags
    win (with a warning when they force the slow path on a fitting
    config). Returns LmLossPath; the chunk fields destructure like the
    old 2-tuple (sliced here). Tuner-winner paths are pinned separately
    in tests/test_tune.py."""
    from unittest import mock

    from dtf_tpu.cli.flags import AUTO_LOSS_CHUNK_TOKENS, resolve_lm_loss

    def lf(**kw):
        base = dict(loss_chunk_vocab=0, loss_chunk_tokens=0,
                    loss_pallas=False)
        base.update(kw)
        return SimpleNamespace(**base)

    gpt = dict(seq_len=1024, vocab_size=50304)
    # b8 s1024 V50k: ~3.3 GB logits+cotangent -> fits, monolithic
    assert resolve_lm_loss(lf(), batch=8, **gpt)[:2] == (0, 0)
    # b32: ~13 GB -> the token-chunked fused loss (banked winner and
    # heuristic default agree)
    r = resolve_lm_loss(lf(), batch=32, **gpt)
    assert r[:2] == (0, AUTO_LOSS_CHUNK_TOKENS) and not r.pallas
    # data/seq sharding divides the per-device logits share back under
    # the budget
    assert resolve_lm_loss(lf(), batch=32, mesh_shape={"data": 4},
                           **gpt)[:2] == (0, 0)
    # fused losses cannot ride a TP/pipe mesh: monolithic even when big
    assert resolve_lm_loss(lf(), batch=32, mesh_shape={"model": 2},
                           **gpt)[:2] == (0, 0)
    assert resolve_lm_loss(lf(), batch=32, mesh_shape={"pipe": 2},
                           **gpt)[:2] == (0, 0)
    # explicit flags are honored either way; forcing the slow path on a
    # fitting config warns, as does the vocab scan where the banked
    # winner is the token axis
    with mock.patch("absl.logging.warning") as warn:
        r = resolve_lm_loss(lf(loss_chunk_vocab=8192), batch=8, **gpt)
        assert r[:2] == (8192, 0) and r.source == "explicit"
        assert warn.called
    with mock.patch("absl.logging.warning") as warn:
        assert resolve_lm_loss(lf(loss_chunk_tokens=4096), batch=32,
                               **gpt)[:2] == (0, 4096)
        assert not warn.called   # logits do NOT fit: the flag is right
