"""The plain references against the system, at tiny sizes on the CPU.

In float32 the two must agree to rounding: that is what shows the reference
computes the same function. In the configuration's own bfloat16 they must
agree inside the tolerances the benchmark uses on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import bert as bert_family, gpt as gpt_family
from benchmarks.lib.check import compare
from benchmarks.reference import bert as ref_bert, gpt2 as ref_gpt
from dtf_tpu.data.synthetic import SyntheticData
from dtf_tpu.models import bert, gpt


def _jitter(params, seed=9):
    """Biases start at 0 and LayerNorm scales at 1; move every leaf so a
    reference that dropped one of them would be caught."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, None)])
def test_gpt_reference_matches_the_system(dtype, tol):
    cfg = gpt.GPTConfig.tiny(dtype=dtype)
    model, init_fn = gpt.make_init(cfg, None, seq_len=64)
    params = _jitter(init_fn(jax.random.PRNGKey(3))["params"])
    batch = SyntheticData("gpt", 2, seed=5, seq_len=64,
                          vocab_size=cfg.vocab_size).batch(0)
    sys_logits = model.apply({"params": params}, batch["input_ids"])
    sys_loss = gpt.make_eval(model)(params, {}, batch)["eval_loss"]
    ref_logits = ref_gpt.forward(params, batch["input_ids"],
                                 layers=cfg.layers, heads=cfg.heads)
    ref_loss = ref_gpt.loss(params, batch["input_ids"], batch["labels"],
                            layers=cfg.layers, heads=cfg.heads)
    if tol is not None:
        np.testing.assert_allclose(sys_logits, ref_logits, atol=tol)
        assert float(sys_loss) == pytest.approx(float(ref_loss), abs=tol)
    else:
        out = compare((sys_loss, sys_logits), (ref_loss, ref_logits), 5,
                      logit_rel_rms_tol=gpt_family.LOGIT_REL_RMS_TOL,
                      loss_abs_tol=gpt_family.LOSS_ABS_TOL)
        assert out["ok"], out


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-4),
                                       (jnp.bfloat16, None)])
def test_bert_reference_matches_the_system(dtype, tol):
    cfg = bert.BertConfig.tiny(dtype=dtype)
    model, init_fn = bert.make_init(cfg, None, seq_len=64)
    params = _jitter(init_fn(jax.random.PRNGKey(3))["params"])
    b = SyntheticData("bert", 2, seed=5, seq_len=64,
                      vocab_size=cfg.vocab_size).batch(0)
    sys_logits = model.apply({"params": params}, b["input_ids"],
                             b["segment_ids"], b["attention_mask"].astype(bool))
    sys_loss = bert.make_eval(model)(params, {}, b)["eval_mlm_loss"]
    args = (params, b["input_ids"], b["segment_ids"], b["attention_mask"])
    kw = dict(layers=cfg.layers, heads=cfg.heads)
    ref_logits = ref_bert.forward(*args, **kw)
    ref_loss = ref_bert.loss(*args, b["mlm_labels"], **kw)
    if tol is not None:
        np.testing.assert_allclose(sys_logits, ref_logits, atol=tol)
        assert float(sys_loss) == pytest.approx(float(ref_loss), abs=tol)
    else:
        out = compare((sys_loss, sys_logits), (ref_loss, ref_logits), 5,
                      logit_rel_rms_tol=bert_family.LOGIT_REL_RMS_TOL,
                      loss_abs_tol=bert_family.LOSS_ABS_TOL)
        assert out["ok"], out


def test_compare_fails_a_system_that_is_off_by_more_than_the_tolerance():
    rng = np.random.default_rng(0)
    ref = rng.standard_normal((2, 8, 16)).astype(np.float32)
    good = compare((1.0, ref * 1.01), (1.0, ref), 0,
                   logit_rel_rms_tol=0.04, loss_abs_tol=0.01)
    assert good["ok"] and good["logit_rel_rms_err"] == pytest.approx(0.01,
                                                                     rel=1e-3)
    for system in ((1.0, ref * 1.1), (1.02, ref), (float("nan"), ref)):
        assert not compare(system, (1.0, ref), 0, logit_rel_rms_tol=0.04,
                           loss_abs_tol=0.01)["ok"]
