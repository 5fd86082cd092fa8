"""Low-precision compute tier (ISSUE 17; docs/TUNING.md "Precision
winners").

Covers the quantization primitives (zero-channel bitwise round-trip,
per-element error bounds, the rel-err quality metric), the master-weight
``quantized_matmul`` (forward inside the selection ceiling, gradients
BITWISE equal to the plain einsum's), the quantized-operand collective
rings (forward within tolerance of the bf16 rings, gradients bitwise —
the backward rides the full-precision ring bwd), the ``tp_dense``
dispatch seam, the tuner plumbing (fallback, planted winner, nearest
shape, hard ``parallel`` match, explicit-pin warn-once, the rel-err
ceiling at selection time), and the srclint precision-literal fence.

Gradient parity is EXACT on integer-valued f32 data (the
test_collective_matmul idiom): quantization perturbs only the FORWARD,
so dx/dw must be the plain path's bits.
"""

import json
import os
import textwrap
from unittest import mock

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dtf_tpu.core import comms
from dtf_tpu.core.mesh import MeshConfig, make_mesh
from dtf_tpu.ops import collective_matmul as cm
from dtf_tpu.ops import quant
from dtf_tpu.tune import cache, resolver, search

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PRECISIONS_UNDER_TEST = ("int8", "fp8")


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    local = tmp_path / "KERNEL_TUNE.local.json"
    golden = tmp_path / "KERNEL_TUNE.json"
    monkeypatch.setenv("DTF_KERNEL_TUNE_PATH", str(local))
    monkeypatch.setenv("DTF_KERNEL_TUNE_GOLDEN", str(golden))
    resolver.invalidate()
    yield {"local": str(local), "golden": str(golden)}
    resolver.invalidate()


def _ints(rng, *shape):
    return rng.integers(-4, 5, shape).astype(np.float32)


def _plan_key(parallel="column", d_in=768, d_out=3072, backend="cpu",
              **kw):
    """matmul_precision_plan kwargs; Entry keys add site='tp_dense'."""
    return dict(parallel=parallel, d_in=d_in, d_out=d_out,
                dtype="bfloat16", n_devices=1, backend=backend, **kw)


def _precision_key(**kw):
    return dict(site="tp_dense", **_plan_key(**kw))


# ------------------------------------------------------------ primitives


@pytest.mark.parametrize("dtype", PRECISIONS_UNDER_TEST)
def test_zero_channel_roundtrips_bitwise(dtype):
    """The _kv_quant contract: an all-zero channel quantizes to exact
    zeros and dequantizes back bitwise (epsilon floor, no 0/0)."""
    a = jnp.zeros((3, 8), jnp.float32).at[1].set(
        jnp.arange(8, dtype=jnp.float32) - 4)
    q, s = quant.quantize_channel(a, axis=-1, dtype=dtype)
    assert s.shape == (3, 1)
    back = np.asarray(quant.dequantize(q, s))
    np.testing.assert_array_equal(back[0], np.zeros(8, np.float32))
    np.testing.assert_array_equal(back[2], np.zeros(8, np.float32))
    assert np.any(back[1] != 0)


@pytest.mark.parametrize("dtype,bound", [("int8", 0.01), ("fp8", 0.08)])
def test_quantize_dequantize_error_bound(dtype, bound):
    """Per-channel symmetric round-trip error: int8 resolves amax/127
    (worst-case half a step), e4m3's 3 mantissa bits ~6% relative."""
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    q, s = quant.quantize_channel(a, axis=-1, dtype=dtype)
    err = float(quant.rel_err(quant.dequantize(q, s), a))
    assert err < bound, (dtype, err)


@pytest.mark.parametrize("precision", PRECISIONS_UNDER_TEST)
def test_quantized_matmul_within_selection_ceiling(precision):
    """The forward quality bound the sweep banks and the selector
    enforces: rel_err vs the f32 reference under the ceiling at a
    real projection shape."""
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(64, 48)) / 8.0, jnp.bfloat16)
    ref = jnp.einsum("btd,df->btf", x.astype(jnp.float32),
                     w.astype(jnp.float32))
    got = quant.quantized_matmul(x, w, precision=precision)
    assert got.dtype == jnp.bfloat16
    err = float(quant.rel_err(got, ref))
    assert err < search.PRECISION_REL_ERR_CEILING, (precision, err)


@pytest.mark.parametrize("precision", PRECISIONS_UNDER_TEST)
def test_quantized_matmul_grads_bitwise(precision):
    """Master-weight rule: quantization perturbs the forward only —
    dx/dw are the plain einsum's gradients, bit for bit."""
    rng = np.random.default_rng(2)
    x, w = jnp.asarray(_ints(rng, 2, 8, 16)), jnp.asarray(_ints(rng, 16, 6))
    ct = jnp.asarray(_ints(rng, 2, 8, 6))

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) * ct)

    g_q = jax.grad(loss(lambda x, w: quant.quantized_matmul(
        x, w, precision=precision)), argnums=(0, 1))(x, w)
    g_ref = jax.grad(loss(lambda x, w: jnp.einsum("btd,df->btf", x, w)),
                     argnums=(0, 1))(x, w)
    for a, b, name in zip(g_q, g_ref, ("dx", "dw")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_quantized_matmul_rejects_bf16():
    x = jnp.ones((1, 2, 4), jnp.float32)
    w = jnp.ones((4, 3), jnp.float32)
    with pytest.raises(ValueError, match="must be 'int8' or 'fp8'"):
        quant.quantized_matmul(x, w, precision="bf16")
    with pytest.raises(ValueError, match="must be one of"):
        quant.validate_precision("int4")


# ------------------------------------------------------- quantized rings


def _ring_parity(mesh, op_q, op_ref, x, w, ct, *, precision,
                 x_spec, w_spec):
    xs = jax.device_put(x, NamedSharding(mesh, x_spec))
    ws = jax.device_put(w, NamedSharding(mesh, w_spec))
    out_ref = np.asarray(jax.jit(
        lambda x, w: op_ref(x, w, mesh))(xs, ws))
    out_q = np.asarray(jax.jit(
        lambda x, w: op_q(x, w, mesh, precision=precision))(xs, ws))
    err = float(quant.rel_err(jnp.asarray(out_q), jnp.asarray(out_ref)))
    assert err < search.PRECISION_REL_ERR_CEILING, err

    def loss(fn):
        return lambda x, w: jnp.sum(fn(x, w) * ct)

    g_q = jax.jit(jax.grad(loss(
        lambda x, w: op_q(x, w, mesh, precision=precision)),
        argnums=(0, 1)))(xs, ws)
    g_ref = jax.jit(jax.grad(loss(lambda x, w: op_ref(x, w, mesh)),
                             argnums=(0, 1)))(xs, ws)
    for a, b, name in zip(g_q, g_ref, ("dx", "dw")):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)


def test_ag_ring_quant_parity(mesh_4x2):
    """ag_matmul_quant vs the bf16 ring: forward inside the ceiling
    (the local block comes from the ORIGINAL x — only communicated
    blocks are rounded), gradients bitwise (same full-precision bwd)."""
    rng = np.random.default_rng(3)
    _ring_parity(mesh_4x2, cm.ag_matmul_quant_sharded, cm.ag_matmul_sharded,
                 _ints(rng, 8, 16, 8), _ints(rng, 8, 6),
                 jnp.asarray(_ints(rng, 8, 16, 6)), precision="int8",
                 x_spec=P("data", ("seq", "model"), None),
                 w_spec=P(None, "model"))


def test_rs_ring_quant_parity(mesh_4x2):
    """matmul_rs_quant: the accumulator is re-quantized before each of
    the n-1 hops (bounded re-rounding) — still inside the ceiling, and
    the backward is the bf16 ring's bits."""
    rng = np.random.default_rng(4)
    _ring_parity(mesh_4x2, cm.matmul_rs_quant_sharded, cm.matmul_rs_sharded,
                 _ints(rng, 8, 16, 6), _ints(rng, 6, 8),
                 jnp.asarray(_ints(rng, 8, 16, 8)), precision="int8",
                 x_spec=P("data", "seq", "model"),
                 w_spec=P("model", None))


def test_ring_inventory_has_quant_pairs():
    """The soundness pass traces the quant rings' fwd AND bwd: the
    inventory must name them."""
    names = [op.name for op in cm.ring_inventory()]
    assert "ag_matmul_int8" in names and "matmul_rs_int8" in names
    assert "ag_matmul_fp8" in names and "matmul_rs_fp8" in names


@pytest.mark.slow
@pytest.mark.parametrize("precision", PRECISIONS_UNDER_TEST)
def test_ring_quant_parity_tp4(precision):
    """tp4: the first size where the ring scan bodies execute (tp2
    unrolls them away) — both ops, both precisions."""
    mesh = make_mesh(MeshConfig(data=2, model=4))
    rng = np.random.default_rng(5)
    _ring_parity(mesh, cm.ag_matmul_quant_sharded, cm.ag_matmul_sharded,
                 _ints(rng, 4, 16, 8), _ints(rng, 8, 8),
                 jnp.asarray(_ints(rng, 4, 16, 8)), precision=precision,
                 x_spec=P("data", ("seq", "model"), None),
                 w_spec=P(None, "model"))
    _ring_parity(mesh, cm.matmul_rs_quant_sharded, cm.matmul_rs_sharded,
                 _ints(rng, 4, 16, 8), _ints(rng, 8, 8),
                 jnp.asarray(_ints(rng, 4, 16, 8)), precision=precision,
                 x_spec=P("data", "seq", "model"),
                 w_spec=P("model", None))


# -------------------------------------------------------- tp_dense seam


def test_tp_dense_empty_precision_is_bf16_bitwise(mesh_4x2):
    """'' must be the pre-ISSUE-17 path byte for byte (and consult no
    store — proven by resolving with a poisoned store path)."""
    rng = np.random.default_rng(6)
    x, w, b = _ints(rng, 8, 16, 8), _ints(rng, 8, 6), _ints(rng, 6)
    xs = jax.device_put(x, NamedSharding(mesh_4x2,
                                         P("data", ("seq", "model"), None)))
    got = jax.jit(lambda x: comms.tp_dense(
        x, w, b, mesh_4x2, parallel="column", overlap=True))(xs)
    want = jax.jit(lambda x: comms.tp_dense(
        x, w, b, mesh_4x2, parallel="column", overlap=True,
        precision=""))(xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_dense_quantized_offline_path(tune_env):
    """No viable ring (mesh=None): an explicit int8 routes through
    quantized_matmul — same numbers as calling it directly."""
    rng = np.random.default_rng(7)
    x, w, b = _ints(rng, 2, 8, 16), _ints(rng, 16, 6), _ints(rng, 6)
    got = comms.tp_dense(x, w, b, None, parallel="column",
                         precision="int8")
    want = quant.quantized_matmul(jnp.asarray(x), jnp.asarray(w),
                                  precision="int8") + b
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_tp_dense_quantized_ring_dispatch(tune_env, mesh_4x2):
    """overlap + viable + int8 → the quantized ring (bitwise equal to
    calling ag_matmul_quant_sharded directly)."""
    rng = np.random.default_rng(8)
    x, w = _ints(rng, 8, 16, 8), _ints(rng, 8, 6)
    xs = jax.device_put(x, NamedSharding(mesh_4x2,
                                         P("data", ("seq", "model"), None)))
    got = jax.jit(lambda x: comms.tp_dense(
        x, w, None, mesh_4x2, parallel="column", overlap=True,
        precision="int8"))(xs)
    want = jax.jit(lambda x: cm.ag_matmul_quant_sharded(
        x, jnp.asarray(w), mesh_4x2, precision="int8"))(xs)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_gpt_config_validates_precision():
    from dtf_tpu.models import gpt

    with pytest.raises(ValueError, match="matmul_precision"):
        gpt.GPTConfig.tiny(matmul_precision="int4")


# ------------------------------------------------------ tuner plumbing


def test_precision_plan_fallback_and_planted_winner(tune_env):
    plan = resolver.matmul_precision_plan(**_plan_key())
    assert plan.precision == "bf16" and not plan.measured
    assert quant.resolve_precision(
        "auto", parallel="column", d_in=768, d_out=3072,
        backend="cpu") == "bf16"

    cache.merge_entries(tune_env["local"], [cache.Entry(
        kind="matmul_precision", key=_precision_key(),
        winner={"precision": "int8", "rel_err": 0.006},
        source="test-planted", measured=True)])
    assert quant.resolve_precision(
        "auto", parallel="column", d_in=768, d_out=3072,
        backend="cpu") == "int8"
    # nearest shape: d_in/d_out are soft fields
    assert resolver.matmul_precision_plan(
        **_plan_key(d_in=512, d_out=2048)).precision == "int8"
    # parallel is HARD: a column winner never answers for the row ring
    assert resolver.matmul_precision_plan(
        **_plan_key(parallel="row")).precision == "bf16"


def test_explicit_pin_warns_over_measured_winner(tune_env):
    cache.merge_entries(tune_env["local"], [cache.Entry(
        kind="matmul_precision", key=_precision_key(),
        winner={"precision": "int8"}, source="test-planted",
        measured=True)])
    with mock.patch.object(resolver, "_warn_override_once") as warn:
        out = quant.resolve_precision(
            "bf16", parallel="column", d_in=768, d_out=3072,
            backend="cpu")
        assert out == "bf16"
        warn.assert_not_called()     # ''/'bf16' short-circuit: no consult
        got = quant.resolve_precision(
            "fp8", parallel="row", d_in=768, d_out=3072, backend="cpu")
        warn.assert_not_called()     # row site: fallback, not measured
        assert got == "fp8"
        quant.resolve_precision("fp8", parallel="column", d_in=768,
                                d_out=3072, backend="cpu")
        warn.assert_called_once()    # explicit beats measured int8


def test_select_precision_winner_enforces_ceiling():
    rows = [
        {"precision": "bf16", "matmul_s": 1.0},             # no rel_err: ok
        {"precision": "int8", "matmul_s": 0.4, "rel_err": 0.2},  # > ceiling
        {"precision": "fp8", "matmul_s": 0.6, "rel_err": 0.01},
    ]
    assert search.select_precision_winner(rows)["precision"] == "fp8"
    # every low-precision row out of bound -> bf16 wins by default
    rows[2]["rel_err"] = 0.9
    assert search.select_precision_winner(rows)["precision"] == "bf16"
    # a low-precision row with NO banked rel_err never wins
    assert search.select_precision_winner(
        [{"precision": "int8", "matmul_s": 0.1}]) is None


def test_seed_precision_entries_from_sweep_rows(tmp_path):
    rows = [
        {"parallel": "column", "d_in": 768, "d_out": 3072,
         "dtype": "bfloat16", "backend": "tpu", "n_devices": 1,
         "precision": "bf16", "matmul_s": 1.0},
        {"parallel": "column", "d_in": 768, "d_out": 3072,
         "dtype": "bfloat16", "backend": "tpu", "n_devices": 1,
         "precision": "int8", "matmul_s": 0.5, "rel_err": 0.005},
        # second group: int8 out of bound -> banks bf16
        {"parallel": "row", "d_in": 3072, "d_out": 768,
         "dtype": "bfloat16", "backend": "tpu", "n_devices": 1,
         "precision": "bf16", "matmul_s": 1.0},
        {"parallel": "row", "d_in": 3072, "d_out": 768,
         "dtype": "bfloat16", "backend": "tpu", "n_devices": 1,
         "precision": "int8", "matmul_s": 0.5, "rel_err": 0.2},
    ]
    with open(tmp_path / search.SWEEP_ARTIFACT, "w") as f:
        json.dump({"precision_rows": rows}, f)
    entries = search.seed_precision_entries(str(tmp_path))
    by_par = {e.key["parallel"]: e for e in entries}
    assert by_par["column"].winner["precision"] == "int8"
    assert by_par["column"].measured
    assert by_par["column"].metric["alternatives"]["bf16"] == 1.0
    assert by_par["row"].winner["precision"] == "bf16"


def test_precision_policy_entries_cover_draft_widths():
    """The serving-draft int8 policy defaults: all four gpt2_draft
    projection sites, measured=False (an explicit flag never warns
    about overriding a guess)."""
    entries = search.precision_policy_entries()
    keys = {(e.key["parallel"], e.key["d_in"], e.key["d_out"])
            for e in entries}
    assert keys == {("column", 384, 384), ("column", 384, 1536),
                    ("row", 384, 384), ("row", 1536, 384)}
    assert all(not e.measured for e in entries)
    assert all(e.winner["precision"] == "int8" for e in entries)


def test_committed_golden_resolves_draft_precision():
    """The shipped KERNEL_TUNE.json answers 'auto' at the draft widths
    (the tier-1 seed-drift fence guarantees it stays banked)."""
    plan = resolver.matmul_precision_plan(
        parallel="column", d_in=384, d_out=1536, dtype="bfloat16",
        n_devices=1, backend="tpu")
    assert plan.precision == "int8"


# ------------------------------------------------------------- srclint


def test_srclint_fences_precision_literals(tmp_path):
    from dtf_tpu.analysis import srclint

    scripts = tmp_path / "scripts"
    scripts.mkdir()
    bad = scripts / "launch_thing.py"
    bad.write_text(textwrap.dedent("""\
        from dtf_tpu.core import comms
        from dtf_tpu.ops import collective_matmul as cm
        def f(x, w, mesh):
            a = comms.tp_dense(x, w, None, mesh, parallel="column",
                               precision="int8")
            b = cm.ag_matmul_quant_sharded(x, w, mesh, precision="fp8")
            return a, b
    """))
    probs = srclint.lint_file(str(bad))
    assert sum("precision literal" in p for p in probs) == 2
    ok = scripts / "launch_ok.py"
    ok.write_text(textwrap.dedent("""\
        from dtf_tpu.core import comms
        def f(x, w, mesh, cfg, resolved):
            a = comms.tp_dense(x, w, None, mesh, parallel="column",
                               precision="")
            b = comms.tp_dense(x, w, None, mesh, parallel="column",
                               precision="auto")
            c = comms.tp_dense(x, w, None, mesh, parallel="column",
                               precision=cfg.matmul_precision)
            d = comms.tp_dense(x, w, None, mesh, parallel="column",
                               precision=resolved)
            e = comms.tp_dense(x, w, None, mesh, parallel="row",
                               precision="int8")  # noqa: pinned A/B
            return a, b, c, d, e
    """))
    assert not [p for p in srclint.lint_file(str(ok))
                if "precision literal" in p]
    # the shipped tree is clean (ops/+tune/+tests are the only callers
    # allowed to spell a concrete precision)
    tree_probs = []
    for f in srclint._py_files([os.path.join(ROOT, "dtf_tpu"),
                                os.path.join(ROOT, "scripts")]):
        tree_probs += srclint.lint_file(f)
    assert not [p for p in tree_probs if "precision literal" in p]
