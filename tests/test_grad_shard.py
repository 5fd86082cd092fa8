"""ZeRO-1 sharded gradient accumulation (``make_train_step(grad_shard=)``).

The contract (ISSUE 3 / docs/ZERO.md): the reduce-scattered 1/N shard
accumulator is a LAYOUT decision, not a numerics change — Σwᵢgᵢ/Σwᵢ over
the finer shard×microbatch grid combines to exactly the full-batch
gradient. On integer-valued data with power-of-two count weights both
paths are bitwise identical after one step; the fence half is covered by
the comms-budget tests (reduce-scatter appears, all-reduce bytes drop,
temp bytes shrink).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dtf_tpu.analysis import hlo
from dtf_tpu.core import sharding as shd
from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import shard_batch
from dtf_tpu.core.mesh import MeshConfig, make_mesh

D = 32


def int_init(rng):
    """Integer-valued params: f32 sums of integers are exact, so the two
    accumulation orders (per-microbatch vs per-shard-group) are bitwise
    comparable."""
    del rng
    return {"params": {"w": jnp.ones((D, D), jnp.float32),
                       "b": jnp.zeros((D,), jnp.float32)}}


def counted_loss(params, extra, batch, rng):
    """The MLM-count idiom: a mean over data-dependent valid positions,
    with the count returned as ``LossAux.weight`` so microbatch (and
    shard-group) gradients combine as Σwᵢgᵢ/Σwᵢ."""
    del rng
    pred = batch["x"] @ params["w"] + params["b"]
    mask = batch["mask"]
    se = ((pred - batch["y"]) ** 2).sum(-1)
    n = mask.sum()
    loss = (se * mask).sum() / n
    return loss, tr.LossAux(extra=extra, metrics={"mse": loss}, weight=n)


def pow2_mask(n_rows, total=None, _idx=0):
    """A mask whose count over EVERY aligned power-of-two row block is a
    power of two or zero, so both paths' count divisions round-trip
    losslessly ((Σwg)/w is exact) at every grouping granularity — the
    microbatch blocks of the replicated path AND the per-data-shard
    groups of the sharded one — while staying NON-uniform across small
    blocks (zero groups included, exercising the 0-weight guard: the
    loss's own 0/0 must not poison Σwg). Zero blocks stay <= 8 rows so no
    whole microbatch is ever weightless."""
    if total is None:
        total = n_rows // 2
    if n_rows == 1:
        return np.array([float(total)], np.float32)
    half = n_rows // 2
    if total == 1:
        left, right = (1, 0) if _idx % 2 else (0, 1)
    elif 0 < total <= half and n_rows <= 8 and _idx % 2:
        left, right = total, 0                 # lopsided: non-uniformity
    else:
        left = right = total // 2
    return np.concatenate([pow2_mask(half, left, 2 * _idx + 1),
                           pow2_mask(half, right, 2 * _idx + 2)])


def make_int_batch(n_rows, seed=0):
    r = np.random.default_rng(seed)
    return {"x": r.integers(-3, 4, (n_rows, D)).astype(np.float32),
            "y": r.integers(-3, 4, (n_rows, D)).astype(np.float32),
            "mask": pow2_mask(n_rows)}


def run(mesh, *, grad_shard, grad_accum, steps=1, rules=(), batch=None,
        batch_spec=None, tx=None):
    tx = tx or optax.adam(1e-3)
    state, shardings = tr.create_train_state(
        int_init, tx, jax.random.PRNGKey(0), mesh, param_rules=rules)
    kw = {}
    if batch_spec is not None:
        from dtf_tpu.core.comms import batch_shardings_for

        kw["batch_shardings"] = batch_shardings_for(batch, mesh, batch_spec)
    step = tr.make_train_step(counted_loss, tx, mesh, shardings,
                              grad_accum=grad_accum, grad_shard=grad_shard,
                              **kw)
    placed = shard_batch(batch, mesh, spec=batch_spec)
    for _ in range(steps):
        state, metrics = step(state, placed)
    return state, metrics, step.lower(state, placed).compile()


def assert_trees_bitwise(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("grad_accum", [2, 4])
def test_bitwise_parity_dp4(grad_accum):
    """Acceptance: sharded vs replicated exact (bitwise, integer data)
    under grad_accum in {2,4} with non-uniform (incl. zero) weights."""
    mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    batch = make_int_batch(64)
    s_rep, m_rep, _ = run(mesh, grad_shard=False, grad_accum=grad_accum,
                          batch=batch)
    s_sh, m_sh, _ = run(mesh, grad_shard=True, grad_accum=grad_accum,
                        batch=batch)
    assert_trees_bitwise(s_rep.params, s_sh.params)
    assert_trees_bitwise(s_rep.opt_state, s_sh.opt_state)
    # loss and weighted metrics are exact sums of the same integers
    assert float(m_rep["loss"]) == float(m_sh["loss"])
    assert float(m_rep["mse"]) == float(m_sh["mse"])
    assert np.isfinite(float(m_sh["loss"]))


def test_bitwise_parity_dp2_sp2():
    """dp2 x sp2: the group split composes with a seq axis in the mesh."""
    mesh = make_mesh(MeshConfig(data=2, seq=2), devices=jax.devices()[:4])
    batch = make_int_batch(32)
    s_rep, m_rep, _ = run(mesh, grad_shard=False, grad_accum=2, batch=batch)
    s_sh, m_sh, _ = run(mesh, grad_shard=True, grad_accum=2, batch=batch)
    assert_trees_bitwise(s_rep.params, s_sh.params)
    assert float(m_rep["loss"]) == float(m_sh["loss"])


def test_bitwise_parity_dp4_tp2_with_rules():
    """dp4 x tp2: shard specs EXTEND the Megatron param placement (the
    accumulator shard carries both the model axis and the data shard)."""
    mesh = make_mesh(MeshConfig(data=4, model=2))
    rules = [(r"w", P(None, "model")), (r"b", P("model"))]
    batch = make_int_batch(64)
    s_rep, m_rep, c_rep = run(mesh, grad_shard=False, grad_accum=2,
                              rules=rules, batch=batch)
    s_sh, m_sh, c_sh = run(mesh, grad_shard=True, grad_accum=2,
                           rules=rules, batch=batch)
    assert_trees_bitwise(s_rep.params, s_sh.params)
    assert float(m_rep["loss"]) == float(m_sh["loss"])
    # the swap is visible in the compiled collectives
    b_rep, b_sh = hlo.comms_budget(c_rep), hlo.comms_budget(c_sh)
    assert b_rep["reduce-scatter"]["count"] == 0
    assert b_sh["reduce-scatter"]["count"] > 0
    assert b_sh["all-reduce"]["bytes"] < b_rep["all-reduce"]["bytes"]


def test_grad_norm_from_shards_close():
    """grad_norm comes from per-shard square norms + psum; only the
    reduction ORDER differs from the replicated vdot, so it is ulp-close,
    not bitwise."""
    mesh = make_mesh(MeshConfig(data=8))
    batch = make_int_batch(64)
    _, m_rep, _ = run(mesh, grad_shard=False, grad_accum=2, batch=batch)
    _, m_sh, _ = run(mesh, grad_shard=True, grad_accum=2, batch=batch)
    np.testing.assert_allclose(float(m_sh["grad_norm"]),
                               float(m_rep["grad_norm"]), rtol=1e-6)


def test_multi_step_training_stays_close():
    """Past step 1 params are no longer integer-valued, so contraction
    order inside the per-group dots differs at the ulp level — training
    must still track tightly."""
    mesh = make_mesh(MeshConfig(data=8))
    batch = make_int_batch(64)
    s_rep, _, _ = run(mesh, grad_shard=False, grad_accum=4, steps=5,
                      batch=batch)
    s_sh, _, _ = run(mesh, grad_shard=True, grad_accum=4, steps=5,
                     batch=batch)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6),
        s_rep.params, s_sh.params)


def test_swap_in_compiled_collectives_and_temp_dp8():
    """The fence story in miniature: reduce-scatter appears, the gradient
    all-reduce disappears (only scalar loss/metric all-reduces remain),
    and peak temp allocation shrinks with the 1/N accumulator."""
    mesh = make_mesh(MeshConfig(data=8))
    batch = make_int_batch(64)
    _, _, c_rep = run(mesh, grad_shard=False, grad_accum=4, batch=batch)
    _, _, c_sh = run(mesh, grad_shard=True, grad_accum=4, batch=batch)
    b_rep, b_sh = hlo.comms_budget(c_rep), hlo.comms_budget(c_sh)
    assert b_rep["reduce-scatter"]["count"] == 0
    assert b_sh["reduce-scatter"]["count"] >= 2          # w and b leaves
    # gradient-sync result bytes: the sharded path moves ~1/N per leaf
    assert b_sh["all-reduce"]["bytes"] < b_rep["all-reduce"]["bytes"] / 2
    assert (b_sh["memory"]["temp_bytes"] < b_rep["memory"]["temp_bytes"])


def test_data1_and_extra_fall_back_to_replicated():
    """Safe fallback: data=1 meshes and models with mutable collections
    take the replicated path (identical program, no crash)."""
    mesh1 = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    batch = make_int_batch(16)
    s_rep, m_rep, _ = run(mesh1, grad_shard=False, grad_accum=2, batch=batch)
    s_sh, m_sh, _ = run(mesh1, grad_shard=True, grad_accum=2, batch=batch)
    assert_trees_bitwise(s_rep.params, s_sh.params)
    assert float(m_rep["loss"]) == float(m_sh["loss"])

    # a loss that threads a mutable collection: grad_shard must fall back
    # (per-shard-group calls cannot thread one `extra` carry), not crash
    def bn_init(rng):
        del rng
        return {"params": {"w": jnp.ones((D, D), jnp.float32)},
                "stats": {"count": jnp.zeros((), jnp.float32)}}

    def bn_loss(params, extra, batch, rng):
        del rng
        pred = batch["x"] @ params["w"]
        loss = jnp.mean((pred - batch["y"]) ** 2)
        new_extra = {"stats": {"count": extra["stats"]["count"] + 1.0}}
        return loss, tr.LossAux(extra=new_extra, metrics={"mse": loss})

    mesh8 = make_mesh(MeshConfig(data=8))
    tx = optax.adam(1e-3)
    state, shardings = tr.create_train_state(
        bn_init, tx, jax.random.PRNGKey(0), mesh8)
    step = tr.make_train_step(bn_loss, tx, mesh8, shardings, grad_accum=2,
                              grad_shard=True)
    state, metrics = step(state, shard_batch(make_int_batch(32), mesh8))
    assert np.isfinite(float(metrics["loss"]))
    # the replicated path advanced `extra` once per microbatch
    assert float(state.extra["stats"]["count"]) == 2.0


def test_zero1_param_shard_specs_pair_with_opt_specs():
    """The accumulator layout must line up shard-for-shard with the
    ZeRO-1 optimizer moments: same placement logic, same chosen dim."""
    mesh = make_mesh(MeshConfig(data=4, model=2))
    params = {"w": jax.ShapeDtypeStruct((D, D), jnp.float32),
              "b": jax.ShapeDtypeStruct((D,), jnp.float32),
              "scalar": jax.ShapeDtypeStruct((), jnp.float32)}
    param_specs = {"w": P(None, "model"), "b": P("model"), "scalar": P()}
    shard = shd.zero1_param_shard_specs(params, param_specs, mesh)
    assert shard["w"] == P("data", "model")
    assert shard["b"] == P("model")       # no free divisible dim: fallback
    assert shard["scalar"] == P()
    tx = optax.adam(1e-3)
    opt = shd.zero1_opt_specs(tx, params, param_specs, mesh)
    mu = opt[0].mu
    assert mu["w"] == shard["w"] and mu["b"] == shard["b"]


def test_launcher_grad_shard_resolution():
    """cli.flags.resolve_grad_shard: the safe-fallback gate warns and
    disables instead of letting a shard_map kernel crash at trace time."""
    from types import SimpleNamespace

    from dtf_tpu.cli.flags import resolve_grad_shard

    mesh8 = make_mesh(MeshConfig(data=8))
    mesh1 = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    ok = SimpleNamespace(grad_shard=True, grad_accum=4)
    assert resolve_grad_shard(ok, mesh8) is True
    assert resolve_grad_shard(ok, mesh1) is False            # data=1
    assert resolve_grad_shard(
        SimpleNamespace(grad_shard=True, grad_accum=1), mesh8) is False
    assert resolve_grad_shard(ok, mesh8, blockers=["flash"]) is False
    assert resolve_grad_shard(
        SimpleNamespace(grad_shard=False, grad_accum=4), mesh8) is False


def test_golden_records_the_swap():
    """The committed STATIC_ANALYSIS.json must show the bert_accum vs
    bert_grad_shard swap: reduce-scatter appears, all-reduce count drops
    and its bytes do not grow — the tier-1 comms fence of the --grad_shard
    path. (The accumulator's G -> G/N saving is ~44 KB at these toy shapes
    and no longer shows in temp_bytes under the installed XLA, where other
    buffers move by more: docs/ZERO.md.)"""
    from dtf_tpu.analysis import runner

    golden = hlo.load_golden(runner.golden_path())
    rep = golden["budgets"]["bert_accum"]
    sh = golden["budgets"]["bert_grad_shard"]
    assert rep["reduce-scatter"]["count"] == 0
    assert sh["reduce-scatter"]["count"] > 0
    assert sh["all-reduce"]["count"] < rep["all-reduce"]["count"]
    assert sh["all-reduce"]["bytes"] <= rep["all-reduce"]["bytes"]
