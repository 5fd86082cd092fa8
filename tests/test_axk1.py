"""Latent attention with a latent cache, group-limited routing and a shared
expert on the flat GPT model and through ``DecodeEngine``, against the plain
float32 reference (``benchmarks/reference/axk1.py``, which imports nothing
from the program). CPU, tiny sizes, seeded weights."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import axk1 as ref
from dtf_tpu.models import gpt
from dtf_tpu.ops import decode_attention
from dtf_tpu.parallel import moe
from dtf_tpu.serve import engine as serve_engine
from dtf_tpu.serve.engine import DecodeEngine
from dtf_tpu.serve.scheduler import Request, Scheduler
from dtf_tpu.telemetry import Telemetry

YARN = dict(beta_fast=32, beta_slow=1, factor=32, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=64, type="yarn")
#: the reference's view of :func:`tiny`: the source's key names
CONFIG = dict(
    num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, kv_lora_rank=16,
    q_lora_rank=24, rms_norm_eps=1e-6, rope_theta=10000, rope_scaling=YARN,
    num_experts_per_tok=4, n_group=4, topk_group=2, n_routed_experts=16,
    n_shared_experts=1, norm_topk_prob=True, routed_scaling_factor=2.5)
LATENT = gpt.LatentAttentionConfig(
    q_rank=24, kv_rank=16, nope_dim=8, rope_dim=4, v_dim=8, yarn_factor=32,
    yarn_original_len=64, yarn_mscale=1, yarn_mscale_all_dim=1)
EXPERTS = moe.ExpertsConfig(
    num_experts=16, top_k=4, d_ff=16, use_expert_bias=False,
    routed_scaling_factor=2.5, n_group=4, topk_group=2)
#: the published widths' YaRN block
PUBLISHED = gpt.LatentAttentionConfig(
    yarn_factor=32, yarn_original_len=4096, yarn_beta_fast=32,
    yarn_beta_slow=1, yarn_mscale=1, yarn_mscale_all_dim=1)


def tiny(dtype=jnp.float32, experts=EXPERTS, **kw) -> gpt.GPTConfig:
    return gpt.GPTConfig(
        vocab_size=128, d_model=32, layers=3, heads=4, d_ff=48, dtype=dtype,
        param_dtype=dtype, norm="rmsnorm", norm_eps=1e-6, ffn="swiglu",
        use_bias=False, layer_kinds=("mla",) * 3, latent=LATENT,
        experts=experts, dense_layers=1, shared_expert_ff=16, **kw)


def jitter(params, seed=9, scale=0.1):
    """Norm weights start at 1: move every leaf so a reference that dropped
    one of them would be caught."""
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
    return np.asarray(ref.forward(params, jnp.asarray([seq]), CONFIG))[0]


def through_the_cache(cfg, params, seq, n_prompt, chunk):
    """Logits [len(seq), V] of ``seq``: its first ``n_prompt`` tokens
    prefilled in ``chunk``-token applies that continue the latent cache,
    the rest decoded one token at a time through it."""
    cfg = dataclasses.replace(cfg, decode_len=len(seq) + 3)
    pre = gpt.GPT(dataclasses.replace(cfg, chunked_prefill=True))
    dec = gpt.GPT(cfg)
    cache = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype),
        jax.eval_shape(lambda: dec.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)))["cache"])
    rows = []
    ids = jnp.asarray([seq])
    for s0 in range(0, n_prompt, chunk):
        logits, mut = pre.apply({"params": params, "cache": cache},
                                ids[:, s0:min(s0 + chunk, n_prompt)],
                                mutable=["cache"])
        cache = mut["cache"]
        rows.append(logits[0])
    for j in range(n_prompt, len(seq)):
        logits, mut = dec.apply({"params": params, "cache": cache},
                                ids[:, j:j + 1], mutable=["cache"])
        cache = mut["cache"]
        rows.append(logits[0])
    return np.concatenate([np.asarray(r, np.float32) for r in rows]), cache


# ---- the model ------------------------------------------------------------


def test_full_forward_matches_the_reference(params):
    """Every new piece at once, float32 against float32: the low-rank query
    path and its norm, the joint compression, the shared rotary key, YaRN,
    the softmax scale, group-limited routing, the shared expert, the untied
    head."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 24), 0, 128)
    got = gpt.GPT(tiny()).apply({"params": params}, ids)
    want = ref.forward(params, ids, CONFIG)
    np.testing.assert_allclose(got, want, atol=5e-5)


@pytest.mark.parametrize("chunk", [4, 7, 16])
def test_prefill_in_chunks_then_decode_matches_the_reference(params, chunk):
    """Float32: the expanded prefill over a cache it continues, then the
    absorbed decode through the latent rows, against one full forward.
    5e-5: float32 sums reassociated (blocks of a running softmax, the
    absorbed product's other order), on logits of size ~1."""
    seq = jax.random.randint(jax.random.PRNGKey(2), (30,), 0, 128).tolist()
    got, _ = through_the_cache(tiny(), params, seq, n_prompt=19, chunk=chunk)
    np.testing.assert_allclose(got, reference_logits(params, seq), atol=5e-5)


def test_bfloat16_through_the_cache_stays_near_the_reference():
    """bfloat16 storage and products against the float32 reference on the
    same (bfloat16-representable) weights: the worst logit error stays
    under 6% of the logits' spread. A rounding of 2^-9 a stored activation
    through 3 layers and the head gives ~1%; a wrong position, a stale row
    or a missing norm is off by the spread itself. Routing flips on
    rounding are part of it at this size (16 experts, scores 0.02 apart),
    which is why it is not tighter."""
    cfg = tiny(dtype=jnp.bfloat16)
    _, init_fn = gpt.make_init(cfg, None, seq_len=8)
    params = init_fn(jax.random.PRNGKey(3))["params"]
    seq = jax.random.randint(jax.random.PRNGKey(4), (24,), 0, 128).tolist()
    got, _ = through_the_cache(cfg, params, seq, n_prompt=13, chunk=5)
    want = reference_logits(params, seq)
    err = np.abs(got - want).max(axis=-1) / want.std(axis=-1)
    assert np.median(err) < 0.06, err


def test_absorbed_equals_expanded_in_float32(params):
    """One position's logits by both forms of the attention: as the last
    token of a prefill (expanded: keys and values from ``W_kvb c_kv``) and
    as a decode step (absorbed: the query through ``W_kvb^K``, the output
    through ``W_kvb^V``)."""
    seq = jax.random.randint(jax.random.PRNGKey(5), (17,), 0, 128).tolist()
    expanded, _ = through_the_cache(tiny(), params, seq, 17, chunk=17)
    absorbed, _ = through_the_cache(tiny(), params, seq, 16, chunk=16)
    np.testing.assert_allclose(absorbed[-1], expanded[-1], atol=2e-5)


def test_yarn_frequencies_and_scale_follow_the_closed_forms():
    theta, width = 10000.0, 64
    low = math.floor(width * math.log(4096 / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(width * math.log(4096 / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (10, 23)
    freqs = np.asarray(PUBLISHED.frequencies(theta))
    plain = theta ** (-np.arange(0, width, 2) / width)
    ramp = np.clip((np.arange(32) - low) / (high - low), 0, 1)
    np.testing.assert_allclose(freqs, plain * (1 - ramp) + plain / 32 * ramp,
                               rtol=1e-6)
    np.testing.assert_array_equal(freqs[:11], plain[:11].astype(np.float32))
    np.testing.assert_allclose(freqs[23:], plain[23:] / 32, rtol=1e-6)
    m = 0.1 * math.log(32) + 1
    assert m == pytest.approx(1.34657, abs=1e-5)
    assert PUBLISHED.softmax_scale == pytest.approx(192 ** -0.5 * 1.81326,
                                                    rel=1e-5)
    # mscale == mscale_all_dim: cos and sin keep their amplitude, and the
    # program refuses a configuration whose amplitude would be another
    with pytest.raises(ValueError, match="scales cos and sin"):
        dataclasses.replace(PUBLISHED, yarn_mscale_all_dim=0.0)
    assert ref.rotary_amplitude({"rope_scaling": YARN}) == 1.0
    # the reference's, from the source's keys
    published = dict(qk_rope_head_dim=64, qk_nope_head_dim=128,
                     rope_theta=10000,
                     rope_scaling={**YARN,
                                   "original_max_position_embeddings": 4096})
    np.testing.assert_allclose(ref.yarn_frequencies(published), freqs,
                               rtol=1e-6)
    assert ref.softmax_scale(published) == pytest.approx(
        PUBLISHED.softmax_scale)


def test_yarn_factor_one_is_plain_rotary():
    plain = gpt.LatentAttentionConfig(rope_dim=8)
    np.testing.assert_array_equal(
        plain.frequencies(10000.0),
        10000.0 ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8))
    assert plain.softmax_scale == (128 + 8) ** -0.5
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 2, 5, 8))
    np.testing.assert_array_equal(
        gpt.rope(x, jnp.arange(5), 10000.0),
        gpt.rope(x, jnp.arange(5), 10000.0,
                 freqs=plain.frequencies(10000.0)))


def test_config_refuses_what_it_cannot_mean():
    with pytest.raises(ValueError, match="latent gives"):
        dataclasses.replace(tiny(), latent=None)
    with pytest.raises(ValueError, match="no int8 form"):
        tiny(kv_cache_dtype="int8")
    with pytest.raises(ValueError, match="shared_expert_ff"):
        tiny(experts=None)
    with pytest.raises(ValueError, match="n_group"):
        moe.ExpertsConfig(num_experts=16, top_k=4, n_group=3)
    with pytest.raises(ValueError, match="topk_group"):
        moe.ExpertsConfig(num_experts=16, top_k=6, n_group=8, topk_group=2)


def test_untied_head_is_stored_as_the_other_matrices():
    cfg = tiny(dtype=jnp.bfloat16)
    _, init_fn = gpt.make_init(cfg, None, seq_len=8)
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))["params"]
    assert shapes["lm_head"]["kernel"].dtype == jnp.bfloat16
    assert shapes["lm_head"]["kernel"].shape == (32, 128)
    f32 = [jax.tree_util.keystr(p) for p, s
           in jax.tree_util.tree_flatten_with_path(shapes)[0]
           if s.dtype == jnp.float32]
    assert all("scale" in k or "router" in k for k in f32), f32


# ---- routing --------------------------------------------------------------


def _route_topk_before_groups(scores, bias, cfg):
    """``parallel/moe.py: route_topk`` as it stood before group-limited
    choice (PR 26)."""
    choice = scores if bias is None else scores + bias[None, :]
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


@pytest.mark.parametrize("with_bias", [False, True])
def test_one_group_routes_as_before_bit_for_bit(with_bias):
    cfg = moe.ExpertsConfig(num_experts=64, top_k=4,
                            routed_scaling_factor=1.5)
    scores = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0),
                                              (200, 64)))
    bias = (0.05 * jax.random.normal(jax.random.PRNGKey(1), (64,))
            if with_bias else None)
    for got, want in zip(moe.route_topk(scores, bias, cfg),
                         _route_topk_before_groups(scores, bias, cfg)):
        np.testing.assert_array_equal(got, want)


def test_grouped_choice_follows_the_reference_and_its_group_limit():
    cfg = moe.ExpertsConfig(num_experts=192, top_k=8, use_expert_bias=False,
                            n_group=8, topk_group=4,
                            routed_scaling_factor=2.5)
    x = jax.random.normal(jax.random.PRNGKey(0), (300, 32))
    w_g = jax.random.normal(jax.random.PRNGKey(1), (32, 192)) / 32 ** 0.5
    with jax.default_matmul_precision("highest"):
        scores = jax.nn.sigmoid(x @ w_g)
        want_e, want_w, margin = ref.route(x, {"router": w_g}, dict(
            num_experts_per_tok=8, n_group=8, topk_group=4,
            routed_scaling_factor=2.5))
    experts, weights = moe.route_topk(scores, None, cfg)
    clear = np.asarray(margin) > 1e-6
    np.testing.assert_array_equal(np.sort(experts, -1)[clear],
                                  np.sort(want_e, -1)[clear])
    np.testing.assert_allclose(np.sort(weights, -1)[clear],
                               np.sort(want_w, -1)[clear], rtol=1e-5)
    assert clear.mean() > 0.99
    # never more than topk_group groups, and the choice differs from a
    # plain top-8 of 192 for many tokens (the limit does something)
    groups = np.asarray(experts) // 24
    assert max(len(set(row)) for row in groups) <= 4
    plain, _ = moe.route_topk(scores, None, dataclasses.replace(
        cfg, n_group=1, topk_group=1))
    assert (np.sort(plain, -1) != np.sort(experts, -1)).any(-1).mean() > 0.2
    # a group's score is the sum of its two largest: one huge score does
    # not carry a group past four with two good ones
    s = jnp.full((1, 192), 0.01).at[0, 0].set(0.9)
    for g in range(1, 5):
        s = s.at[0, 24 * g:24 * g + 2].set(0.5 - 0.01 * g)
    chosen, _ = moe.route_topk(s, None, cfg)
    # group 0 scores 0.9 + 0.01, four others 0.98, 0.96, 0.94, 0.92: the
    # highest score of all is not chosen
    assert sorted(chosen[0].tolist()) == [24, 25, 48, 49, 72, 73, 96, 97]


def test_the_shares_add_up_to_the_uncut_layer(params):
    """The share test (model-configs guide, section 4): the parts of an
    expert layer's result that the shares give, with the shared expert
    (which every chip computes alike) counted once, add up to what the
    uncut reference gives for the whole layer. Four shares of four experts
    here; every share runs the PROGRAM's block with ``experts_held``."""
    p = params["layer_1"]
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 32))
    with jax.default_matmul_precision("highest"):
        h1 = ref.rms_norm(x, p["ln1"]["scale"], 1e-6)
        base = x + ref.latent_attention(h1, p["attention"], CONFIG)
        h2 = ref.rms_norm(base, p["ln2"]["scale"], 1e-6)
        shared = ref.shared_expert(h2, p)
        routed, chosen, _ = ref.routed_experts(h2, p["experts"], CONFIG)
        uncut = base + shared + routed
    total = 0.0
    for lo in range(0, 16, 4):
        cfg = tiny(experts=dataclasses.replace(EXPERTS,
                                               experts_held=(lo, lo + 4)))
        held = {**p, "experts": {
            "router": p["experts"]["router"],
            **{w: p["experts"][w][lo:lo + 4] for w in ("w1", "w3", "w2")}}}
        out = gpt.Block(cfg, None, False, 0, op="mla", experts=True).apply(
            {"params": held}, x, True)
        with jax.default_matmul_precision("highest"):
            want = base + shared + ref.routed_experts(
                h2, held["experts"], CONFIG, (lo, lo + 4))[0]
        np.testing.assert_allclose(out, want, atol=5e-5)   # a share alone
        total = total + out
    np.testing.assert_allclose(total - 3 * (base + shared), uncut, atol=2e-4)
    assert len(set(np.asarray(chosen).ravel() // 4)) == 4  # every share met


# ---- the decode kernel -----------------------------------------------------


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_latent_decode_kernel_matches_the_xla_step(dtype, tol):
    """``dtf_mla_decode_attn`` in interpret mode against the XLA spelling
    of the same absorbed step: outputs, the written column, and that an
    inactive slot and every other column are left as they were. Indices at
    a block's edge, inside it, at 0 and at the last position."""
    slots, heads, width, rank, max_len = 6, 4, 32, 16, 512
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(keys[0], (slots, heads, width), dtype)
    new = jax.random.normal(keys[1], (slots, width), dtype)
    leaf = jax.random.normal(keys[2], (slots, width, max_len), dtype)
    index = jnp.asarray([0, 5, 127, 128, 300, 511], jnp.int32)
    active = jnp.asarray([True, True, False, True, True, True])
    out, got = decode_attention.latent_decode_attention(
        q, new, leaf, index, active, rank=rank, scale=0.2)

    lane = jnp.arange(max_len)
    hit = (lane[None, :] == index[:, None]) & active[:, None]
    want_leaf = jnp.where(hit[:, None, :], new[:, :, None], leaf)
    np.testing.assert_array_equal(got, want_leaf)
    # an inactive slot's step still attends its new row (its output is
    # thrown away by the caller): compare the active ones
    s = jnp.einsum("shw,swl->shl", q, want_leaf,
                   preferred_element_type=jnp.float32) * 0.2
    s = jnp.where(lane[None, None, :] <= index[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    want = jnp.einsum("shl,scl->shc", p.astype(dtype), want_leaf[:, :rank],
                      preferred_element_type=jnp.float32)
    np.testing.assert_allclose(
        np.asarray(out, np.float32)[np.asarray(active)],
        np.asarray(want)[np.asarray(active)], atol=tol, rtol=tol)


def test_latent_kernel_engages_only_where_it_is_written_for(monkeypatch):
    kw = dict(cache_dtype=jnp.bfloat16, width=576, rank=512, max_len=16384,
              mesh=None)
    assert not decode_attention.latent_engages(**kw)          # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert decode_attention.latent_engages(**kw)
    assert not decode_attention.latent_engages(**{**kw, "max_len": 1000})
    assert not decode_attention.latent_engages(**{**kw, "width": 20})
    assert not decode_attention.latent_engages(
        **{**kw, "cache_dtype": jnp.int8})
    assert decode_attention.block_positions(1, 576, 16384, 2) == 512


# ---- the engine ------------------------------------------------------------


def serve(cfg, params, requests, **engine_kw):
    engine = DecodeEngine(cfg, params, **{
        "n_slots": 2, "max_len": 40, "prefill_chunk": 4, **engine_kw})
    tel = Telemetry(watchdog=False)
    sched = Scheduler(engine, telemetry=tel)
    rids = [sched.submit(Request(prompt=p, max_new=n)) for p, n in requests]
    sched.run_until_idle()
    return engine, tel, [sched.poll(r)["tokens"] for r in rids]


def shortfall(params, prompt, tokens) -> float:
    logits = reference_logits(params, list(prompt) + list(tokens))
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    return float(max(row.max() - row[t] for row, t in zip(rows, tokens)))


def test_engine_serves_the_reference_tokens_on_its_two_programs(params):
    """Five requests over two slots (so slots are re-used over stale
    latent rows, which validity-by-index never reads), ragged last chunks,
    decode interleaved with prefill: every emitted token is the float32
    reference's arg-max or within 1e-4 of it, on exactly two programs."""
    rng = np.random.default_rng(0)
    requests = [(rng.integers(0, 128, n).tolist(), m)
                for n, m in ((9, 6), (14, 5), (3, 7), (21, 4), (6, 6))]
    engine, tel, tokens = serve(tiny(), params, requests)
    assert engine.trace_counts == {"prefill": 1, "decode": 1}
    for (prompt, n), toks in zip(requests, tokens):
        assert len(toks) == n
        assert shortfall(params, prompt, toks) < 1e-4
    rollup = tel.spans.rollup()
    # 4 picks a token of 16 experts all held: every pair lands here
    assert rollup["serve_moe_held_pairs"]["mean_s"] == pytest.approx(
        rollup["serve_moe_picks"]["mean_s"])
    assert 0 < rollup["serve_moe_held_touched"]["mean_s"] <= 16
    assert 0 < rollup["serve_decode_attn_live_pct"]["mean_s"] <= 100


def test_engine_with_a_share_of_the_experts_follows_the_reference(params):
    held = {name: ({**layer, "experts": {
        "router": layer["experts"]["router"],
        **{w: layer["experts"][w][4:8] for w in ("w1", "w3", "w2")}}}
        if "experts" in layer else layer)
        for name, layer in params.items()}
    cfg = tiny(experts=dataclasses.replace(EXPERTS, experts_held=(4, 8)))
    prompt = np.random.default_rng(1).integers(0, 128, 11).tolist()
    engine, tel, (tokens,) = serve(cfg, held, [(prompt, 8)])
    logits = np.asarray(ref.forward(
        held, jnp.asarray([prompt + tokens]), CONFIG, experts_held=(4, 8)))[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    assert max(r.max() - r[t] for r, t in zip(rows, tokens)) < 1e-4
    rollup = tel.spans.rollup()
    assert (rollup["serve_moe_held_pairs"]["mean_s"]
            < rollup["serve_moe_picks"]["mean_s"])
    assert rollup["serve_moe_held_touched"]["mean_s"] <= 4


def test_pad_columns_of_a_ragged_chunk_are_never_written(params):
    """A 6-token prompt in 4-token chunks: the second chunk holds 2 tokens
    and 2 pad columns. Positions 6 and 7 of the slot's latent rows stay as
    they were (zeros from construction), position 5 is written."""
    engine = DecodeEngine(tiny(), params, n_slots=2, max_len=40,
                          prefill_chunk=4)
    engine.prefill(1, [5, 6, 7, 8, 9, 10])
    for name, layer in engine._state["cache"].items():
        leaf = np.array(layer["attention"]["cached_latent"])
        assert leaf.shape == (2, 20, 40), name
        assert np.abs(leaf[1, :, :6]).min(axis=0).max() > 0   # written
        assert not leaf[1, :, 6:].any()                       # pad: not
        assert not leaf[0].any()                              # other slot
        assert int(layer["attention"]["cache_index"][1]) == 6


def test_a_chunk_that_would_cross_the_cache_end_is_not_wrapped(params):
    """max_len 21 is no multiple of the chunk: the last chunk's slab is
    moved back to fit, its rows land at their own positions, and the
    tokens are the reference's."""
    prompt = np.random.default_rng(2).integers(0, 128, 19).tolist()
    engine, _, (tokens,) = serve(tiny(), params, [(prompt, 2)], max_len=21,
                                 prefill_chunk=8)
    assert shortfall(params, prompt, tokens) < 1e-4


def test_a_dropped_engine_is_not_kept_alive_by_its_telemetry(params):
    """The scheduler hangs a postmortem provider on the telemetry object;
    held strongly it kept a dropped scheduler's engine, and the engine's
    whole cache on the device, alive for as long as the telemetry object
    lived (the benchmark's traced run then checks against the reference
    beside a 3.6 GB cache nobody serves from)."""
    import gc
    import weakref

    engine = DecodeEngine(tiny(), params, n_slots=2, max_len=40,
                          prefill_chunk=4)
    tel = Telemetry(watchdog=False)
    sched = Scheduler(engine, telemetry=tel)
    sched.submit(Request(prompt=[1, 2, 3], max_new=2))
    sched.run_until_idle()
    provider = next(iter(tel.flight._providers.values()))
    assert provider()                       # the live scheduler's state
    alive = weakref.ref(engine)
    del sched, engine
    gc.collect()
    assert alive() is None
    assert provider() == {}


def test_cache_bytes_counts_the_latent_rows(params):
    engine = DecodeEngine(tiny(), params, n_slots=3, max_len=40,
                          prefill_chunk=4)
    # 3 layers x (3 slots x 20 numbers x 40 positions x 4 B + 3 indices)
    assert engine.cache_bytes() == 3 * (3 * 20 * 40 * 4 + 3 * 4)


def test_an_unknown_leaf_still_fails_loudly():
    cache = {"layer_0": {"attention": {"cached_mystery": jnp.zeros((2, 3))}}}
    with pytest.raises(ValueError, match="unknown cache leaf"):
        serve_engine._slice_slot_cache(cache, 0)
    with pytest.raises(ValueError, match="latent cache"):
        gpt._paged_leaf_check("cached_latent")


def test_what_stays_refused_under_a_latent_cache_says_so(params):
    kw = dict(n_slots=2, max_len=40, prefill_chunk=4)
    with pytest.raises(ValueError, match="prefix page cache.*latent"):
        DecodeEngine(tiny(), params, kv_page_size=4, prefix_pages=8, **kw)
    with pytest.raises(ValueError, match="speculative decoding.*latent"):
        DecodeEngine(tiny(), params, draft_cfg=tiny(), draft_params=params,
                     spec_k=2, **kw)
    with pytest.raises(ValueError, match="no int8 form"):
        DecodeEngine(tiny(kv_cache_dtype="int8"), params, **kw)
    with pytest.raises(ValueError, match="slot VERIFY"):
        cfg = dataclasses.replace(tiny(), decode_len=16, slot_decode=True)
        gpt.GPT(cfg).init(jax.random.PRNGKey(0),
                          jnp.zeros((2, 3), jnp.int32))
