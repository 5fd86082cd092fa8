"""The hybrid sparse decoder (conv + attention layers, dropless routed
experts) on the flat GPT model and through ``DecodeEngine``, against the
plain float32 reference (``benchmarks/reference/lfm2_moe.py``, which imports
nothing from the program). CPU, tiny sizes, seeded weights."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import lfm2_moe as ref
from dtf_tpu.models import gpt
from dtf_tpu.parallel import moe
from dtf_tpu.serve.engine import DecodeEngine
from dtf_tpu.serve.scheduler import Request, Scheduler
from dtf_tpu.telemetry import Telemetry

KINDS = ("conv", "attn", "conv", "conv")
REF_KW = dict(layer_types=["conv", "full_attention", "conv", "conv"],
              num_dense_layers=1, heads=4, kv_heads=2, top_k=2,
              norm_eps=1e-5, rope_theta=1e6, conv_taps=3)
EXPERTS = moe.ExpertsConfig(num_experts=8, top_k=2, d_ff=16)


def tiny(dtype=jnp.float32, experts=EXPERTS, **kw) -> gpt.GPTConfig:
    return gpt.GPTConfig(
        vocab_size=128, d_model=32, layers=4, heads=4, kv_heads=2, d_ff=48,
        dtype=dtype, param_dtype=dtype, norm="rmsnorm", norm_eps=1e-5,
        ffn="swiglu", qk_norm=True, use_bias=False, tie_head=True,
        layer_kinds=KINDS, conv_kernel=3, rope_theta=1e6, experts=experts,
        dense_layers=1, **kw)


def jitter(params, seed=9, scale=0.1):
    """Norm weights start at 1 and the choice bias at 0: move every leaf so
    a reference that dropped one of them would be caught."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        x + scale * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def params():
    _, init_fn = gpt.make_init(tiny(), None, seq_len=8)
    return jitter(init_fn(jax.random.PRNGKey(0))["params"])


def reference_logits(params, seq):
    return np.asarray(ref.forward(params, jnp.asarray([seq]), **REF_KW))[0]


def shortfall(params, prompt, tokens) -> float:
    """The serve cell's comparison: how far below the reference's arg-max
    each emitted token's reference logit lies, at worst."""
    logits = reference_logits(params, list(prompt) + list(tokens))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(max(row.max() - row[t] for row, t in zip(rows, tokens)))


# ---- the model ------------------------------------------------------------


def test_full_forward_matches_the_reference(params):
    """Every new piece at once, float32 against float32: RMSNorm, QK-norm,
    rotary at theta 1e6, GQA, the short conv, SwiGLU, sigmoid-and-bias
    routing, the grouped product, the tied head."""
    cfg = tiny()
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 128)
    got = gpt.GPT(cfg).apply({"params": params}, ids)
    want = ref.forward(params, ids, **REF_KW)
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_param_tree_is_stored_in_bfloat16_where_it_is_large():
    cfg = tiny(dtype=jnp.bfloat16)
    _, init_fn = gpt.make_init(cfg, None, seq_len=8)
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))["params"]
    flat = {jax.tree_util.keystr(p): s.dtype for p, s
            in jax.tree_util.tree_flatten_with_path(shapes)[0]}
    f32 = {k for k, d in flat.items() if d == jnp.float32}
    assert all(any(name in k for name in ("scale", "router", "expert_bias",
                                          "conv_w")) for k in f32), f32
    assert flat["['layer_1']['experts']['w1']"] == jnp.bfloat16
    assert flat["['token_embed']['embedding']"] == jnp.bfloat16
    assert "['lm_head']['kernel']" not in flat          # tied


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="layer_kinds"):
        dataclasses.replace(tiny(), layer_kinds=("conv", "attn"))
    with pytest.raises(ValueError, match="pick one"):
        tiny(moe_every=2)
    with pytest.raises(ValueError, match="experts_held"):
        moe.ExpertsConfig(num_experts=8, experts_held=(4, 12))
    with pytest.raises(ValueError, match="top_k"):
        moe.ExpertsConfig(num_experts=8, top_k=9)


# ---- routing and the grouped product --------------------------------------


def test_bias_moves_the_choice_and_not_the_weight():
    cfg = moe.ExpertsConfig(num_experts=6, top_k=2)
    scores = jnp.asarray([[0.9, 0.8, 0.5, 0.4, 0.3, 0.2]])
    experts, weights = moe.route_topk(scores, None, cfg)
    assert sorted(experts[0].tolist()) == [0, 1]
    bias = jnp.asarray([0.0, -0.5, 0.0, 0.0, 0.0, 0.65])
    experts_b, weights_b = moe.route_topk(scores, bias, cfg)
    assert sorted(experts_b[0].tolist()) == [0, 5]      # 0.9 and 0.2 + 0.65
    got = dict(zip(experts_b[0].tolist(), weights_b[0].tolist()))
    # the weights are the chosen experts' own scores, normalised over them
    assert got[0] == pytest.approx(0.9 / (1.1 + 1e-6))
    assert got[5] == pytest.approx(0.2 / (1.1 + 1e-6))
    assert float(weights_b.sum()) == pytest.approx(1.1 / (1.1 + 1e-6))
    raw = moe.route_topk(scores, bias, dataclasses.replace(
        cfg, norm_topk_prob=False, routed_scaling_factor=2.0))[1]
    assert sorted(raw[0].tolist()) == pytest.approx([0.4, 1.8])


def _layer_and_loop(cfg, x, seed=3, skew=None, token_mask=None):
    layer = moe.DroplessMoE(x.shape[-1], cfg, dtype=jnp.float32)
    p = layer.init(jax.random.PRNGKey(seed), x)["params"]
    p = {**p, "expert_bias": 0.05 * jax.random.normal(
        jax.random.PRNGKey(seed + 1), p["expert_bias"].shape)}
    if skew is not None:
        # the bias decides: this expert is always chosen, two never are
        p = {**p, "expert_bias": p["expert_bias"].at[skew].add(2.0)
             .at[:2].add(-2.0)}
    got, mut = layer.apply({"params": p}, x, token_mask,
                           mutable=["moe_stats"])
    lo, hi = cfg.held
    full = dataclasses.replace(cfg, experts_held=None)
    want, chosen, _ = ref.experts_layer(
        x, p, top_k=full.top_k, norm_topk_prob=True, scale=1.0,
        experts_held=(lo, hi))
    return got, want, mut, chosen, p


def test_grouped_product_equals_the_loop_over_experts_under_a_skewed_router():
    """One expert takes most tokens, several take none: group sizes from 0
    to nearly all."""
    cfg = moe.ExpertsConfig(num_experts=8, top_k=2, d_ff=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 32))
    got, want, mut, chosen, _ = _layer_and_loop(cfg, x, skew=5)
    np.testing.assert_allclose(got, want, atol=2e-5)
    load = np.bincount(np.asarray(chosen).ravel(), minlength=8)
    assert load[5] == 80 and load[0] == load[1] == 0        # really skewed
    assert int(mut["moe_stats"]["touched"][0]) == int((load > 0).sum())
    assert int(mut["moe_stats"]["max_load"][0]) == int(load.max())


def test_masked_tokens_choose_no_expert():
    cfg = moe.ExpertsConfig(num_experts=8, top_k=2, d_ff=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 12, 32))
    mask = jnp.arange(12)[None, :] < 5
    got, want, mut, chosen, _ = _layer_and_loop(cfg, x, token_mask=mask)
    np.testing.assert_allclose(got[:, :5], want[:, :5], atol=2e-5)
    assert not np.asarray(got[:, 5:]).any()
    load = np.bincount(np.asarray(chosen)[:, :5].ravel(), minlength=8)
    assert int(mut["moe_stats"]["touched"][0]) == int((load > 0).sum())


def test_eight_shares_add_up_to_the_uncut_layer():
    """The share test of the model-configs guide, section 4: the layer is
    told which experts it holds, routes over all of them and computes its
    own experts' part; over 8 disjoint ranges the parts add up to what the
    uncut reference gives for the whole layer."""
    whole = moe.ExpertsConfig(num_experts=16, top_k=4, d_ff=16)
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 32))
    layer = moe.DroplessMoE(32, whole, dtype=jnp.float32)
    p = layer.init(jax.random.PRNGKey(3), x)["params"]
    p = {**p, "expert_bias": 0.05 * jax.random.normal(
        jax.random.PRNGKey(4), (16,))}
    want, _, _ = ref.experts_layer(x, p, top_k=4, norm_topk_prob=True,
                                   scale=1.0)
    total = jnp.zeros_like(x)
    for lo in range(0, 16, 2):
        share = dataclasses.replace(whole, experts_held=(lo, lo + 2))
        held = {**p, **{w: p[w][lo:lo + 2] for w in ("w1", "w2", "w3")}}
        part = moe.DroplessMoE(32, share, dtype=jnp.float32).apply(
            {"params": held}, x)
        # a share is the reference's share too
        ref_part, _, _ = ref.experts_layer(
            x, held, top_k=4, norm_topk_prob=True, scale=1.0,
            experts_held=(lo, lo + 2))
        np.testing.assert_allclose(part, ref_part, atol=2e-5)
        total = total + part
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert float(jnp.abs(want).max()) > 0.1


def test_group_layout_pads_every_group_to_whole_tiles():
    group = jnp.asarray([2, 0, 2, 3, 2, 0, 3, 2, 2], jnp.int32)  # 3 = left out
    lay = moe.group_layout(group, jnp.asarray([2, 0, 5], jnp.int32), tm=4)
    assert lay["n_used"].tolist() == [3]                 # 1 + 0 + 2 tiles
    assert lay["tile_group"].tolist()[:3] == [0, 2, 2]
    src, valid = np.asarray(lay["src"]), np.asarray(lay["valid"])
    assert src[valid].tolist() == [1, 5, 0, 2, 4, 7, 8]  # stable, by group
    assert valid.tolist()[:12] == [1, 1, 0, 0, 1, 1, 1, 1, 1, 0, 0, 0]
    rows = np.asarray(lay["row_of_pair"])
    kept = np.asarray(lay["kept"])
    assert kept.tolist() == [1, 1, 1, 0, 1, 1, 0, 1, 1]
    assert (src[rows[kept]] == np.arange(9)[kept]).all()


@pytest.mark.parametrize("tm,counts", [
    (16, [40, 0, 3, 0, 17, 16, 0, 1]),       # empty groups, a full tile
    (16, [0, 0, 0, 77, 0, 0, 0, 0]),         # one group takes all
    (32, [5, 6, 7, 8, 9, 10, 11, 12]),       # every group under one tile
    (16, [0, 0, 0, 0, 0, 0, 0, 0]),          # nothing to compute
])
def test_the_pallas_grouped_product_equals_ragged_dot(tm, counts):
    """``dtf_moe_gmm`` itself, interpreted: rows laid out by
    :func:`moe.group_layout`, every group padded to whole tiles, tiles past
    ``n_used`` skipped, against ``jax.lax.ragged_dot`` on the packed rows.
    (``DroplessMoE`` takes the kernel on a TPU only; the described-v5e
    compile in test_chip_compile.py is its other fence.)"""
    from dtf_tpu.ops import moe_gmm

    counts = jnp.asarray(counts, jnp.int32)
    group = jnp.repeat(jnp.arange(8, dtype=jnp.int32), counts,
                       total_repeat_length=int(counts.sum()))
    group = jax.random.permutation(jax.random.PRNGKey(0), group)
    # two pairs left out (an expert held elsewhere, a masked token)
    group = jnp.concatenate([group, jnp.full((2,), 8, jnp.int32)])
    x = jax.random.normal(jax.random.PRNGKey(1), (group.shape[0], 128))
    w = jax.random.normal(jax.random.PRNGKey(2), (8, 128, 256)) / 11.0
    lay = moe.group_layout(group, counts, tm)
    rows = jnp.where(lay["valid"][:, None], x[lay["src"]], 0)
    got = moe_gmm.grouped_matmul(rows, w, lay["tile_group"], lay["n_used"],
                                 tm=tm, interpret=True)
    order = jnp.argsort(group, stable=True)[:int(counts.sum())]
    want = jax.lax.ragged_dot(x[order], w, counts)
    kept = np.asarray(lay["kept"])
    np.testing.assert_allclose(
        np.asarray(got)[np.asarray(lay["row_of_pair"])[kept]],
        np.asarray(want)[np.argsort(np.asarray(order))], atol=2e-5)
    assert kept.sum() == int(counts.sum())


# ---- the engine: K/V and conv state side by side --------------------------


def served(params, jobs, *, n_slots=3, max_len=64, prefill_chunk=8,
           telemetry=None, **engine_kw):
    eng = DecodeEngine(tiny(), params, n_slots=n_slots, max_len=max_len,
                       prefill_chunk=prefill_chunk, **engine_kw)
    sched = Scheduler(eng, telemetry=telemetry)
    rng = np.random.default_rng(0)
    rids = {}
    for n_prompt, n_out in jobs:
        prompt = rng.integers(0, 128, n_prompt).tolist()
        rids[sched.submit(Request(prompt=prompt, max_new=n_out))] = prompt
    sched.run_until_idle()
    return eng, sched, {rid: (prompt, sched.poll(rid)["tokens"])
                        for rid, prompt in rids.items()}


def test_engine_matches_the_reference_through_both_caches(params):
    """Chunked prefill with a ragged last chunk (13 = 8 + 5, 30 = 3 x 8 + 6,
    5 < one chunk), then slot decode through K/V and conv state, six
    requests on three slots so that slots are re-used while their
    neighbours decode: every emitted token is the full forward's arg-max
    (in float32 the two agree to rounding, so in logit and not by luck)."""
    eng, _, out = served(params, [(13, 9), (30, 5), (5, 12), (21, 20),
                                  (9, 3), (17, 6)])
    for prompt, tokens in out.values():
        assert shortfall(params, prompt, tokens) <= 1e-4
    assert eng.trace_counts == {"prefill": 1, "decode": 1}
    assert set(eng.programs) == {"prefill", "decode"}


def test_a_readmitted_slot_sees_no_trace_of_the_longer_request(params):
    """A stale K/V row needs no clearing, a stale conv state IS read: the
    first chunk of an admission zeroes it. One slot, a long request and then
    a short one; the short one's tokens are what a fresh engine gives."""
    _, _, both = served(params, [(40, 16), (6, 10)], n_slots=1)
    _, _, alone = served(params, [(40, 1), (6, 10)], n_slots=1)
    (long_prompt, _), (short_prompt, after_long) = both.values()
    assert list(alone.values())[1] == (short_prompt, after_long)
    assert shortfall(params, short_prompt, after_long) <= 1e-4


def test_two_slots_at_different_positions_do_not_disturb_each_other(params):
    """The second request is admitted (its prefill interleaved chunk by
    chunk) while the first decodes; each stream equals the one it has
    alone on the engine."""
    jobs = [(11, 24), (27, 9)]
    _, _, together = served(params, jobs, n_slots=2)
    for (prompt, tokens), job in zip(together.values(), jobs):
        assert shortfall(params, prompt, tokens) <= 1e-4
        assert len(tokens) == job[1]


def test_a_pad_column_never_enters_the_conv_state(params):
    """The ragged last chunk is right-padded: the state must be the last 3
    VALID columns. Serving the same prompt with chunk widths that pad it
    differently (5 = 5 of 8, 5 = 4 + 1 of 4, 5 of 16) gives one stream."""
    streams = []
    for chunk in (4, 8, 16):
        _, _, out = served(params, [(5, 10)], n_slots=1, prefill_chunk=chunk)
        streams.append(next(iter(out.values())))
    assert streams[0] == streams[1] == streams[2]
    assert shortfall(params, *streams[0]) <= 1e-4


def test_sampled_requests_follow_the_reference_distribution_too(params):
    """Sampling rides the same logits: a seeded top-k request through the
    engine is the offline ``generate`` stream (which one-shot-prefills the
    conv state from zeros and decodes with the scalar index)."""
    cfg = tiny()
    prompt = np.random.default_rng(4).integers(0, 128, 10).tolist()
    eng = DecodeEngine(cfg, params, n_slots=2, max_len=48, prefill_chunk=4)
    sched = Scheduler(eng)
    rid = sched.submit(Request(prompt=prompt, max_new=12, temperature=0.8,
                               top_k=20, seed=7))
    sched.run_until_idle()
    model = gpt.GPT(dataclasses.replace(cfg, decode_len=48))
    want = gpt.generate(model, params, jnp.asarray([prompt]), 12,
                        temperature=0.8, top_k=20,
                        rng=jax.random.PRNGKey(7), prefill_chunk=4)
    assert sched.poll(rid)["tokens"] == np.asarray(want)[0, 10:].tolist()


def test_cache_bytes_counts_both_kinds_of_state(params):
    eng = DecodeEngine(tiny(), params, n_slots=3, max_len=64,
                       prefill_chunk=8)
    kv = 3 * 64 * 1 * (2 * 2 * 8 * 4)        # slots x len x 1 attn layer
    conv = 3 * 3 * (3 * 32 * 4)              # slots x 3 conv layers x [3, 32]
    index = 3 * 4                            # the attention layer's [slots]
    assert eng.cache_bytes() == kv + conv + index
    leaves = {jax.tree_util.keystr(p)[-14:]: x.shape for p, x in
              jax.tree_util.tree_flatten_with_path(eng._state["cache"])[0]}
    assert leaves["['conv_state']"] == (3, 3, 32)


@pytest.mark.parametrize("kw,match", [
    (dict(kv_page_size=8, prefix_pages=4), "prefix page cache"),
    (dict(spec_k=2), "speculative decoding"),
    (dict(draft=True), "speculative decoding"),
    (dict(int8=True), "int8 KV cache"),
])
def test_engine_refuses_what_a_recurrent_state_cannot_serve(params, kw,
                                                            match):
    cfg = tiny(kv_cache_dtype="int8") if kw.pop("int8", False) else tiny()
    if kw.pop("draft", False):
        kw = dict(draft_cfg=gpt.GPTConfig.tiny(), draft_params={})
    with pytest.raises(ValueError, match=match):
        DecodeEngine(cfg, params, n_slots=2, max_len=32, prefill_chunk=4,
                     **kw)


def test_moe_counters_ride_the_readback_and_reach_the_span_recorder(params):
    tel = Telemetry(watchdog=False)
    eng, sched, out = served(params, [(13, 9), (30, 5), (5, 12)],
                             telemetry=tel)
    steps, c = eng.counters["decode_steps"], eng.counters
    roll = tel.spans.rollup()
    # 3 expert layers; every active slot picks 2 experts in each
    assert c["moe_decode_picks"] == 3 * roll["serve_moe_picks"]["total_s"]
    # ... a finished slot too, until it is admitted again (it stays active)
    emitted = sum(len(tokens) - 1 for _, tokens in out.values())
    assert 3 * 2 * emitted <= c["moe_decode_picks"] <= 3 * 2 * 3 * steps
    assert c["moe_prefill_picks"] == 2 * (13 + 30 + 5)
    assert roll["serve_moe_prefill_picks"]["count"] == c["prefill_chunks"]
    assert roll["serve_moe_picks"]["count"] == steps
    touched = roll["serve_moe_experts_touched"]
    assert touched["total_s"] * 3 == pytest.approx(c["moe_experts_touched"])
    assert 1.0 <= touched["mean_s"] <= 6.0       # at most 3 slots x 2 picks
    assert roll["serve_moe_max_load_over_mean"]["mean_s"] >= 1.0
    assert roll["serve_moe_cache_positions"]["p99_s"] <= 3 * 64
    # a dense model's engine has none of it
    dense = gpt.GPTConfig.tiny()
    _, init_fn = gpt.make_init(dense, None, seq_len=8)
    eng2 = DecodeEngine(dense, init_fn(jax.random.PRNGKey(0))["params"],
                        n_slots=2, max_len=32, prefill_chunk=4)
    eng2.prefill(0, [1, 2, 3])
    eng2.decode()
    assert eng2.moe_samples is None
    assert not [k for k in eng2.counters if k.startswith("moe_")]


def test_reference_reports_what_each_expert_layer_saw_chose_and_by_how_much(
        params):
    """``return_experts``: what a checker needs to hold another
    implementation's expert layer against the reference on the reference's
    own inputs, and to see how near a tie each choice was."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, 128)
    logits, seen = ref.forward(params, ids, return_experts=True, **REF_KW)
    np.testing.assert_array_equal(logits, ref.forward(params, ids, **REF_KW))
    assert seen["inputs"].shape == (3, 2, 12, 32)
    assert seen["experts"].shape == (3, 2, 12, 2)
    assert seen["margin"].shape == (3, 2, 12)
    for n, i in enumerate((1, 2, 3)):
        p = params[f"layer_{i}"]["experts"]
        _, experts, margin = ref.experts_layer(
            seen["inputs"][n], p, top_k=2, norm_topk_prob=True, scale=1.0)
        np.testing.assert_array_equal(experts, seen["experts"][n])
        s = np.sort(np.asarray(
            jax.nn.sigmoid(seen["inputs"][n] @ p["router"])
            + p["expert_bias"]), axis=-1)
        np.testing.assert_allclose(margin, s[..., -2] - s[..., -3],
                                   atol=1e-6)
    assert float(seen["margin"].min()) >= 0.0


def test_no_table_of_the_programs_choices_exists():
    """The comparison that decides ``correct`` routes for itself: the
    engine keeps no log of its choices, the expert layer has no knob for
    one, and the reference takes no hint."""
    import inspect

    from dtf_tpu.serve import engine as serve_engine

    assert not hasattr(serve_engine, "ROUTING_AUDIT")
    fields = {f.name for f in dataclasses.fields(moe.ExpertsConfig)}
    assert fields == {"num_experts", "top_k", "d_ff", "norm_topk_prob",
                      "use_expert_bias", "routed_scaling_factor",
                      "experts_held", "n_group", "topk_group"}
    assert "hint" not in inspect.signature(ref.forward).parameters
