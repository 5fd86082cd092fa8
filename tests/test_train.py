import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import shard_batch
from dtf_tpu.core.mesh import MeshConfig, make_mesh


def linear_init(rng):
    k1, _ = jax.random.split(rng)
    return {"params": {"w": jax.random.normal(k1, (4, 2)) * 0.1,
                       "b": jnp.zeros((2,))}}


def linear_loss(params, extra, batch, rng):
    pred = batch["x"] @ params["w"] + params["b"]
    loss = jnp.mean((pred - batch["y"]) ** 2)
    return loss, tr.LossAux(extra=extra, metrics={"mse": loss})


def linear_eval(params, extra, batch):
    pred = batch["x"] @ params["w"] + params["b"]
    return {"eval_loss": jnp.mean((pred - batch["y"]) ** 2)}


def make_batch(n=64, seed=0):
    r = np.random.RandomState(seed)
    x = r.randn(n, 4).astype(np.float32)
    w_true = r.randn(4, 2).astype(np.float32)
    return {"x": x, "y": x @ w_true}


def build(mesh, grad_accum=1, zero1=True, lr=0.1):
    tx = optax.adam(lr)
    rng = jax.random.PRNGKey(0)
    state, shardings = tr.create_train_state(linear_init, tx, rng, mesh)
    step = tr.make_train_step(linear_loss, tx, mesh, shardings,
                              grad_accum=grad_accum)
    return state, step


def run_steps(mesh, n_steps=20, grad_accum=1):
    state, step = build(mesh, grad_accum=grad_accum)
    batch = shard_batch(make_batch(), mesh)
    losses = []
    for _ in range(n_steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def test_loss_decreases(mesh8):
    state, losses = run_steps(mesh8)
    assert losses[-1] < losses[0] * 0.5
    assert int(state.step) == 20


def test_dp8_matches_single_device():
    # SyncReplicasOptimizer parity invariant (SURVEY.md §3.3): mean-gradient
    # over 8 data shards == single-device full-batch gradient, so training is
    # bitwise-comparable across mesh sizes at f32 tolerance.
    mesh8 = make_mesh(MeshConfig(data=8))
    mesh1 = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    s8, l8 = run_steps(mesh8, 10)
    s1, l1 = run_steps(mesh1, 10)
    np.testing.assert_allclose(l8, l1, rtol=2e-5)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
        s8.params, s1.params)


def test_grad_accum_matches_full_batch(mesh8):
    _, l_full = run_steps(mesh8, 8, grad_accum=1)
    _, l_accum = run_steps(mesh8, 8, grad_accum=4)
    np.testing.assert_allclose(l_full, l_accum, rtol=1e-4)


def test_zero1_opt_state_is_sharded(mesh8):
    tx = optax.adam(0.1)
    state, shardings = tr.create_train_state(
        linear_init, tx, jax.random.PRNGKey(0), mesh8)
    # (4,2) has no dim divisible by 8 → replicated; use bigger params.
    def big_init(rng):
        return {"params": {"w": jnp.ones((16, 8))}}
    state, shardings = tr.create_train_state(big_init, tx,
                                             jax.random.PRNGKey(0), mesh8)
    mu = state.opt_state[0].mu["w"]
    assert mu.sharding.spec == P("data", None)
    assert mu.addressable_shards[0].data.shape == (2, 8)


def test_determinism_same_seed_same_params(mesh8):
    # The SPMD replacement for the reference's race-freedom story
    # (SURVEY.md §5.2): same seed ⇒ identical params after N steps.
    s1, _ = run_steps(mesh8, 5)
    s2, _ = run_steps(mesh8, 5)
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)), s1.params, s2.params)


def test_metrics_and_extra_passthrough(mesh8):
    state, step = build(mesh8)
    batch = shard_batch(make_batch(), mesh8)
    state, metrics = step(state, batch)
    assert set(metrics) == {"mse", "loss", "grad_norm"}
    assert metrics["grad_norm"] > 0


def test_wrap_optimizer_clips_global_norm():
    """--clip_grad_norm flag: global-norm clip before the update; 0 = off."""
    from types import SimpleNamespace

    import optax

    from dtf_tpu.cli.flags import wrap_optimizer

    params = {"w": jnp.zeros(3)}
    grads = {"w": jnp.asarray([3.0, 4.0, 0.0])}      # global norm 5
    tx = wrap_optimizer(optax.sgd(1.0), SimpleNamespace(clip_grad_norm=1.0))
    upd, _ = tx.update(grads, tx.init(params), params)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(upd["w"])), 1.0, rtol=1e-6)
    tx0 = wrap_optimizer(optax.sgd(1.0), SimpleNamespace(clip_grad_norm=0.0))
    upd0, _ = tx0.update(grads, tx0.init(params), params)
    np.testing.assert_allclose(
        float(jnp.linalg.norm(upd0["w"])), 5.0, rtol=1e-6)


def test_make_lr_schedule_shapes():
    """Flag -> schedule mapping: warmup ramp, decay tail, floor, the
    constant fast path (plain float), and bad kinds rejected."""
    from types import SimpleNamespace

    from dtf_tpu.cli.flags import make_lr_schedule

    def fl(**kw):
        base = dict(learning_rate=1.0, lr_schedule="constant",
                    warmup_steps=-1, lr_min_ratio=0.0, train_steps=100)
        base.update(kw)
        return SimpleNamespace(**base)

    assert make_lr_schedule(fl()) == 1.0                  # plain float
    sched = make_lr_schedule(fl(lr_schedule="linear", warmup_steps=10,
                                lr_min_ratio=0.1))
    np.testing.assert_allclose(float(sched(0)), 0.0)
    np.testing.assert_allclose(float(sched(5)), 0.5)       # mid-warmup
    np.testing.assert_allclose(float(sched(10)), 1.0)      # peak
    np.testing.assert_allclose(float(sched(100)), 0.1)     # floor
    cos = make_lr_schedule(fl(lr_schedule="cosine", warmup_steps=0))
    np.testing.assert_allclose(float(cos(0)), 1.0)
    np.testing.assert_allclose(float(cos(100)), 0.0, atol=1e-7)
    # auto warmup: min(1000, steps//10+1) = 11 for decaying schedules
    auto = make_lr_schedule(fl(lr_schedule="cosine"))
    np.testing.assert_allclose(float(auto(11)), 1.0)
    import pytest

    with pytest.raises(ValueError, match="lr_schedule"):
        make_lr_schedule(fl(lr_schedule="bogus"))


def test_lr_schedule_composes_with_grad_accum_and_zero1(mesh8):
    """The schedule's step counter (optax state count) advances ONCE per
    global step under grad-accum (the update sees the accumulated mean
    gradient) and stays consistent under ZeRO-1 sharding: accum vs
    full-batch training stay numerically identical while the LR moves
    through warmup+decay (VERDICT r4 #4)."""
    from types import SimpleNamespace

    from dtf_tpu.cli.flags import make_lr_schedule

    sched = make_lr_schedule(SimpleNamespace(
        learning_rate=0.1, lr_schedule="cosine", warmup_steps=3,
        lr_min_ratio=0.0, train_steps=8))
    results = []
    for accum in (1, 4):
        tx = optax.adam(sched)
        state, shardings = tr.create_train_state(
            linear_init, tx, jax.random.PRNGKey(0), mesh8)
        step = tr.make_train_step(linear_loss, tx, mesh8, shardings,
                                  grad_accum=accum)
        batch = shard_batch(make_batch(), mesh8)
        for _ in range(8):
            state, _ = step(state, batch)
        results.append(state)
    # the schedule advanced by global steps, not microbatches: both runs
    # end at the same schedule position with the same params
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-6),
        results[0].params, results[1].params)
    counts = [c for c in jax.tree.leaves(results[1].opt_state)
              if getattr(c, "ndim", None) == 0 and c.dtype == jnp.int32]
    assert counts and all(int(c) == 8 for c in counts)


def test_train_steps_donate_their_state(mesh8):
    """Train steps donate their state unless the caller says otherwise.
    Asserted on the lowering's own args_info, the surface the analyzer's
    memory pass introspects."""
    tx = optax.adam(0.1)
    rng = jax.random.PRNGKey(0)
    state, shardings = tr.abstract_train_state(linear_init, tx, rng, mesh8)
    batch = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype),
        make_batch(16))
    for donate in (True, False):
        step = tr.make_train_step(linear_loss, tx, mesh8, shardings,
                                  donate=donate)
        donated = [getattr(a, "donated", False)
                   for a in jax.tree.leaves(step.lower(state,
                                                       batch).args_info)]
        assert any(donated) is donate, (donate, donated)
