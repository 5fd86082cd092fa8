"""Kernel autotuner (dtf_tpu/tune + the kernel wiring; docs/TUNING.md).

Covers the ISSUE 10 satellite-4 list: cache round-trip, corrupt/stale
fallback, deterministic winner selection with injected timings, bitwise
parity of tuned vs default blocks on integer data (fwd + grad over
causal / windowed / masked / GQA-shaped inputs), the trace-count pin
(resolver lookups never retrace), the explicit-override warning, and the
srclint block-literal fence.
"""

import json
import os
import subprocess
import sys
import textwrap
from unittest import mock

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from dtf_tpu.tune import cache, resolver, search  # noqa: E402


@pytest.fixture
def tune_env(tmp_path, monkeypatch):
    """Isolated cache files + a clean resolver, restored afterwards."""
    local = tmp_path / "KERNEL_TUNE.local.json"
    golden = tmp_path / "KERNEL_TUNE.json"
    monkeypatch.setenv("DTF_KERNEL_TUNE_PATH", str(local))
    monkeypatch.setenv("DTF_KERNEL_TUNE_GOLDEN", str(golden))
    resolver.invalidate()
    yield {"local": str(local), "golden": str(golden)}
    resolver.invalidate()


def _plant(path, entries):
    cache.merge_entries(path, entries, generated_by="test")
    resolver.invalidate()


def _flash_entries(winner_fwd, winner_bwd=None, *, backend="cpu",
                   measured=True, seq=96, heads=4, head_dim=16,
                   causal=True):
    key = dict(seq=seq, heads=heads, head_dim=head_dim, dtype="float32",
               causal=causal, window=0, n_devices=8, backend=backend)
    out = [cache.Entry(kind="flash_fwd", key=key, winner=winner_fwd,
                       metric={"flash_fwd_s": 1.0}, source="test-planted",
                       measured=measured)]
    if winner_bwd:
        out.append(cache.Entry(kind="flash_bwd", key=key,
                               winner=winner_bwd, source="test-planted",
                               measured=measured))
    return out


# ------------------------------------------------------------- cache


def test_cache_roundtrip(tune_env):
    entries = _flash_entries({"block_q": 32, "block_k": 48, "block_h": 1},
                             {"block_q_bwd": 16, "block_k_bwd": 48})
    n = cache.merge_entries(tune_env["local"], entries)
    assert n == 2
    loaded = cache.load_file(tune_env["local"])
    assert {e.canonical_key() for e in loaded} == {
        e.canonical_key() for e in entries}
    store = cache.TuneStore.from_files(tune_env["local"],
                                       tune_env["golden"])
    hit = store.lookup("flash_fwd", entries[0].key)
    assert hit is not None and hit.winner["block_q"] == 32
    # merge is idempotent and replaces same-key entries
    entries2 = _flash_entries({"block_q": 64, "block_k": 64, "block_h": 1})
    assert cache.merge_entries(tune_env["local"], entries2) == 2
    store = cache.TuneStore.from_files(tune_env["local"],
                                       tune_env["golden"])
    assert store.lookup("flash_fwd",
                        entries[0].key).winner["block_q"] == 64


def test_local_shadows_golden(tune_env):
    _plant(tune_env["golden"],
           _flash_entries({"block_q": 512, "block_k": 512, "block_h": 1}))
    _plant(tune_env["local"],
           _flash_entries({"block_q": 128, "block_k": 256, "block_h": 1}))
    store = cache.load_store()
    hit = store.lookup("flash_fwd", _flash_entries({})[0].key)
    assert hit.winner == {"block_q": 128, "block_k": 256, "block_h": 1}


def test_nearest_shape_lookup(tune_env):
    """A query at an unswept shape resolves to the closest banked
    winner (the CPU sim resolves to on-chip data, not literals);
    hard-field mismatches never match."""
    _plant(tune_env["golden"], _flash_entries(
        {"block_q": 320, "block_k": 640, "block_h": 1}, backend="tpu",
        seq=8192, heads=8, head_dim=128))
    store = cache.load_store()
    near = store.lookup("flash_fwd", dict(
        seq=1024, heads=12, head_dim=64, dtype="bfloat16", causal=True,
        window=0, n_devices=8, backend="cpu"))
    assert near is not None and near.winner["block_q"] == 320
    assert store.lookup("flash_fwd", dict(causal=False, seq=1024)) is None


def test_corrupt_cache_falls_back(tune_env):
    with open(tune_env["local"], "w") as f:
        f.write("{ not json !")
    _plant(tune_env["golden"],
           _flash_entries({"block_q": 96, "block_k": 96, "block_h": 1}))
    plan = resolver.flash_plan(seq=96, heads=4, head_dim=16,
                               dtype="float32", causal=True, window=0,
                               n_devices=8, backend="cpu")
    assert plan.block_q == 96            # golden still consulted
    # both corrupt -> built-in defaults, no raise
    with open(tune_env["golden"], "w") as f:
        f.write("[]")
    resolver.invalidate()
    plan = resolver.flash_plan(seq=96, heads=4, head_dim=16,
                               dtype="float32", causal=True, window=0,
                               n_devices=8, backend="cpu")
    # nothing banked: 0s, and flash_attention's shape rule decides
    assert (plan.block_q, plan.block_k) == (0, 0)
    assert plan.block_q_bwd == 0 and not plan.measured


def test_stale_schema_ignored(tune_env):
    payload = {"schema": 999, "entries": [
        _flash_entries({"block_q": 7, "block_k": 7, "block_h": 1})[0]
        .to_json()]}
    with open(tune_env["golden"], "w") as f:
        json.dump(payload, f)
    resolver.invalidate()
    assert cache.load_file(tune_env["golden"]) == []
    plan = resolver.flash_plan(seq=96, heads=4, head_dim=16,
                               dtype="float32", causal=True, window=0,
                               n_devices=8, backend="cpu")
    assert plan.block_q == 0 and not plan.measured


# ------------------------------------------------------- winner selection


def test_select_winner_deterministic_with_injected_timings():
    rows = [{"block_q": 512, "block_k": 512, "flash_fwd_s": 3.0},
            {"block_q": 512, "block_k": 1024, "flash_fwd_s": 1.0},
            {"block_q": 1024, "block_k": 512, "flash_fwd_s": 2.0}]
    assert search.select_winner(rows, metric="flash_fwd_s")[
        "block_k"] == 1024
    # tie: canonical-JSON order, stable across row order
    tie = [{"block_q": 1024, "block_k": 512, "flash_fwd_s": 1.0},
           {"block_q": 512, "block_k": 1024, "flash_fwd_s": 1.0}]
    w1 = search.select_winner(tie, metric="flash_fwd_s")
    w2 = search.select_winner(list(reversed(tie)), metric="flash_fwd_s")
    assert w1 == w2
    # rows missing the metric (dead child) are skipped; all-dead -> None
    rows[1]["flash_fwd_s"] = None
    assert search.select_winner(rows, metric="flash_fwd_s")[
        "flash_fwd_s"] == 2.0
    assert search.select_winner([{"a": 1}], metric="flash_fwd_s") is None
    # higher-is-better metrics flip the ordering
    mfu = [{"path": "monolithic", "mfu": 0.58},
           {"path": "chunk_vocab", "mfu": 0.49}]
    assert search.select_winner(mfu, metric="mfu",
                                lower_is_better=False)["mfu"] == 0.58


def test_seeded_golden_matches_banked_artifacts():
    """The committed KERNEL_TUNE.json must stay derivable from the
    committed sweep artifacts: monolithic where logits fit, token-chunk
    where they don't. No flash block sweep has been taken on the present
    chip and JAX, so no flash entry may claim to be measured — and an
    unmeasured one decides nothing: the kernels take their blocks from
    the shape rule (``flash_attention.flash_blocks``)."""
    flash = [e for e in search.seed_entries(ROOT)
             if e.kind.startswith("flash")]
    assert flash and not any(e.measured for e in flash)
    assert not any(e.key.get("backend") == "tpu" for e in flash)
    plan = resolver.flash_plan(seq=1024, heads=16, head_dim=64,
                               dtype="bfloat16", causal=True, window=0,
                               n_devices=1, backend="tpu")
    assert (plan.block_q, plan.block_k, plan.measured) == (512, 1024, False)
    from dtf_tpu.ops import flash_attention as fa
    shape = dict(causal=True, itemsize=2)
    assert (fa.resolve_blocks(1024, 1024, 64, plan=plan, **shape)
            == fa.flash_blocks(1024, 1024, 64, causal=True))
    lm = [e for e in search.seed_entries(ROOT) if e.kind == "lm_loss"]
    by_fits = {bool(e.key["fits"]): e for e in lm}
    assert by_fits[True].winner["path"] == "monolithic"
    assert by_fits[True].measured
    assert by_fits[False].winner == {"path": "chunk_tokens", "chunk": 4096}
    # the committed golden file itself carries exactly these winners
    committed = {e.canonical_key(): e.winner
                 for e in cache.load_file(os.path.join(
                     ROOT, cache.GOLDEN_BASENAME))}
    for e in search.seed_entries(ROOT):
        assert committed.get(e.canonical_key()) == e.winner, (
            "KERNEL_TUNE.json is stale vs the artifacts: re-run "
            "`python -m dtf_tpu.tune seed` and commit")


def test_reseed_reproduces_persisted_sweep_rows(tmp_path):
    """Measured rows persisted in KERNEL_TUNE_SWEEP.json: a
    re-seed must reproduce the measured winners PER SHAPE (not
    revert them to older artifacts, not mix shapes into one winner)."""
    rows = [
        # train shape: (1024, h12, d64) — 256x512 wins fwd, bwd row set
        {"backend": "tpu", "seq": 1024, "b": 8, "h": 12, "d": 64,
         "dtype": "bfloat16", "block_q": 256, "block_k": 512,
         "block_h": 1, "block_q_bwd": 0, "block_k_bwd": 0,
         "flash_fwd_s": 0.001, "flash_fwdbwd_s": 0.004},
        {"backend": "tpu", "seq": 1024, "b": 8, "h": 12, "d": 64,
         "dtype": "bfloat16", "block_q": 512, "block_k": 512,
         "block_h": 1, "block_q_bwd": 0, "block_k_bwd": 0,
         "flash_fwd_s": 0.002, "flash_fwdbwd_s": 0.005},
        {"backend": "tpu", "seq": 1024, "b": 8, "h": 12, "d": 64,
         "dtype": "bfloat16", "block_q": 256, "block_k": 512,
         "block_h": 1, "block_q_bwd": 128, "block_k_bwd": 512,
         "flash_fwdbwd_s": 0.003},
        # a second shape with a DIFFERENT fwd winner must not leak
        {"backend": "tpu", "seq": 4096, "b": 2, "h": 8, "d": 128,
         "dtype": "bfloat16", "block_q": 1024, "block_k": 1024,
         "block_h": 1, "block_q_bwd": 0, "block_k_bwd": 0,
         "flash_fwd_s": 0.0005, "flash_fwdbwd_s": 0.002},
    ]
    with open(tmp_path / search.SWEEP_ARTIFACT, "w") as f:
        json.dump({"rows": rows}, f)
    entries = {(e.kind, e.key["seq"]): e
               for e in search.seed_flash_entries(str(tmp_path))}
    assert entries[("flash_fwd", 1024)].winner["block_q"] == 256
    # the standalone bwd row wins over the inherited pair for its shape
    assert entries[("flash_bwd", 1024)].winner == {
        "block_q_bwd": 128, "block_k_bwd": 512}
    assert entries[("flash_fwd", 4096)].winner["block_q"] == 1024
    # the 4096 shape has no standalone bwd rows -> inherited fwd pair
    assert entries[("flash_bwd", 4096)].winner == {
        "block_q_bwd": 1024, "block_k_bwd": 1024}


# ------------------------------------------------------------ resolver


def _int_qkv(shape=(1, 4, 96, 16), seed=0, kv_heads=None):
    rs = np.random.RandomState(seed)
    import jax.numpy as jnp

    def mk(i, h):
        return jnp.asarray(rs.randint(-3, 4, (shape[0], h) + shape[2:])
                           .astype(np.float32))

    q = mk(0, shape[1])
    if kv_heads:
        # GQA-shaped K/V: kv_heads distinct heads repeated to match q —
        # exactly what the model does before the kernel (gpt.expand_kv)
        k = mk(1, kv_heads).repeat(shape[1] // kv_heads, axis=1)
        v = mk(2, kv_heads).repeat(shape[1] // kv_heads, axis=1)
    else:
        k, v = mk(1, shape[1]), mk(2, shape[1])
    return q, k, v


@pytest.mark.parametrize("case", ["causal", "windowed", "masked", "gqa"])
def test_tuned_blocks_bitwise_match_default_blocks(tune_env, case):
    """The tuner changes scheduling, never math. Two pins on integer
    data, fwd + grads, per masking case: (a) BITWISE — resolving
    through the tuner is identical to hand-pinning the same blocks (the
    resolver injects values, nothing else); (b) numeric — the tuned
    blocks match the shape rule's (what an unmeasured shape runs) to the
    tolerance the kernel's own cross-block tests use (block partitions
    legitimately reorder the online-softmax summation, so cross-BLOCK
    bitwise equality is not a thing even on integer inputs)."""
    import jax
    import jax.numpy as jnp

    from dtf_tpu.ops import flash_attention as fa

    planted_fwd = {"block_q": 32, "block_k": 48, "block_h": 1}
    planted_bwd = {"block_q_bwd": 48, "block_k_bwd": 32}
    # the masked (encoder) case is non-causal — causal is a HARD key
    # field, so it needs its own planted bucket
    _plant(tune_env["golden"], _flash_entries(
        planted_fwd, planted_bwd, causal=(case != "masked")))
    kw = dict(causal=True, interpret=True)
    kv_mask = None
    if case == "windowed":
        kw["window"] = 40
    q, k, v = _int_qkv(kv_heads=2 if case == "gqa" else None)
    if case == "masked":
        kw = dict(interpret=True)
        kv_mask = jnp.asarray(
            np.r_[np.ones(80, bool), np.zeros(16, bool)])[None, :]

    def run(**blocks):
        mk = dict(kw)
        if kv_mask is not None:
            mk["kv_mask"] = kv_mask

        def loss(q, k, v):
            return fa.flash_attention(q, k, v, **mk, **blocks).sum()

        out = fa.flash_attention(q, k, v, **mk, **blocks)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, grads

    out_t, g_t = run()                   # tuner-resolved (the planted
    #                                      winner, incl. the bwd pair)
    out_p, g_p = run(block_q=planted_fwd["block_q"],     # same blocks,
                     block_k=planted_fwd["block_k"],     # hand-pinned
                     **planted_bwd)
    assert (np.asarray(out_t) == np.asarray(out_p)).all()
    for gt, gp in zip(g_t, g_p):
        assert (np.asarray(gt) == np.asarray(gp)).all()
    # what an unmeasured shape gets: the kernel's own shape rule
    rule = fa.flash_blocks(q.shape[2], k.shape[2], q.shape[3],
                           causal=case != "masked").fwd
    out_d, g_d = run(block_q=rule[0], block_k=rule[1])
    np.testing.assert_allclose(np.asarray(out_t), np.asarray(out_d),
                               atol=2e-5, rtol=2e-5)
    for gt, gd in zip(g_t, g_d):
        np.testing.assert_allclose(np.asarray(gt), np.asarray(gd),
                                   atol=1e-4, rtol=1e-4)


def test_fused_ce_tuned_matches_default(tune_env):
    import jax
    import jax.numpy as jnp

    from dtf_tpu.ops import fused_ce as fc

    _plant(tune_env["golden"], [cache.Entry(
        kind="fused_ce",
        key=dict(vocab=64, d_model=16, dtype="float32", n_devices=8,
                 backend="cpu"),
        winner={"block_n": 8, "block_v": 32}, source="test-planted",
        measured=True)])
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randint(-2, 3, (24, 16)).astype(np.float32))
    w = jnp.asarray(rs.randint(-2, 3, (16, 64)).astype(np.float32))
    lab = jnp.asarray(rs.randint(0, 64, (24,)))

    def run(**blocks):
        loss, cnt = fc.pallas_lm_cross_entropy(
            x, w, lab, ignore_index=-100, interpret=True, **blocks)
        g = jax.grad(lambda x, w: fc.pallas_lm_cross_entropy(
            x, w, lab, ignore_index=-100, interpret=True, **blocks)[0],
            argnums=(0, 1))(x, w)
        return loss, cnt, g

    lt, ct, gt = run()                       # tuner-resolved (8, 32)
    lp, cp, gp = run(block_n=8, block_v=32)  # same tile, hand-pinned
    assert float(lt) == float(lp) and float(ct) == float(cp)
    for a, b in zip(gt, gp):
        assert (np.asarray(a) == np.asarray(b)).all()
    ld, cd, gd = run(block_n=fc.DEFAULT_BLOCK_N, block_v=fc.DEFAULT_BLOCK_V)
    assert float(ct) == float(cd)
    np.testing.assert_allclose(float(lt), float(ld), rtol=1e-6)
    for a, b in zip(gt, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5)


def test_resolver_never_retraces(tune_env):
    """Resolver lookups are trace-time Python over cached plain ints: a
    second call at the same shape reuses the jit cache (trace count
    pinned at 1) and returns the IDENTICAL plan object."""
    import jax

    from dtf_tpu.ops import flash_attention as fa

    _plant(tune_env["golden"], _flash_entries(
        {"block_q": 32, "block_k": 32, "block_h": 1}))
    q, k, v = _int_qkv()
    traces = {"n": 0}

    def f(q, k, v):
        traces["n"] += 1
        return fa.flash_attention(q, k, v, causal=True, interpret=True)

    jf = jax.jit(f)
    o1 = jf(q, k, v)
    o2 = jf(q, k, v)
    assert traces["n"] == 1
    assert (np.asarray(o1) == np.asarray(o2)).all()
    p1 = resolver.flash_plan(seq=96, heads=4, head_dim=16,
                             dtype="float32", causal=True, window=0,
                             n_devices=8, backend="cpu")
    p2 = resolver.flash_plan(seq=96, heads=4, head_dim=16,
                             dtype="float32", causal=True, window=0,
                             n_devices=8, backend="cpu")
    assert p1 is p2


def test_explicit_override_of_measured_winner_warns_once(tune_env):
    from dtf_tpu.ops import flash_attention as fa

    _plant(tune_env["golden"], _flash_entries(
        {"block_q": 32, "block_k": 32, "block_h": 1}, measured=True))
    q, k, v = _int_qkv()
    with mock.patch("absl.logging.warning") as warn:
        fa.flash_attention(q, k, v, causal=True, block_q=64,
                           interpret=True)
        assert warn.call_count == 1
        fa.flash_attention(q, k, v, causal=True, block_q=64,
                           interpret=True)
        assert warn.call_count == 1      # once per distinct override
    # a policy-seeded (measured=False) entry never warns
    _plant(tune_env["golden"], _flash_entries(
        {"block_q": 32, "block_k": 32, "block_h": 1}, measured=False))
    with mock.patch("absl.logging.warning") as warn:
        fa.flash_attention(q, k, v, causal=True, block_q=64,
                           interpret=True)
        assert not warn.called


def test_explicit_fwd_blocks_keep_bwd_inherit_contract(tune_env):
    """Pinning the forward must NOT silently mix in a tuned backward:
    unset bwd blocks inherit the pinned fwd (the pre-tuner contract)."""
    import jax

    from dtf_tpu.ops import flash_attention as fa

    _plant(tune_env["golden"], _flash_entries(
        {"block_q": 32, "block_k": 48, "block_h": 1},
        {"block_q_bwd": 48, "block_k_bwd": 32}))
    q, k, v = _int_qkv()

    def g(**blocks):
        return jax.grad(lambda q: fa.flash_attention(
            q, k, v, causal=True, interpret=True, **blocks).sum())(q)

    # pinned fwd + explicit matching bwd == pinned fwd with bwd unset
    a = g(block_q=16, block_k=16)
    b = g(block_q=16, block_k=16, block_q_bwd=16, block_k_bwd=16)
    assert (np.asarray(a) == np.asarray(b)).all()


# ------------------------------------------------ flags.resolve_lm_loss


def _loss_flags(**kw):
    from types import SimpleNamespace

    base = dict(loss_chunk_vocab=0, loss_chunk_tokens=0, loss_pallas=False)
    base.update(kw)
    return SimpleNamespace(**base)


def test_resolve_lm_loss_honors_banked_winner(tune_env):
    from dtf_tpu.cli.flags import resolve_lm_loss

    gpt = dict(seq_len=1024, vocab_size=50304)
    # banked pallas winner in the not-fits bucket -> pallas path
    _plant(tune_env["golden"], [cache.Entry(
        kind="lm_loss",
        key=dict(fits=False, vocab=50304, seq=1024, batch=16,
                 n_devices=1, backend="tpu"),
        winner={"path": "pallas", "chunk": 0}, source="test-planted",
        measured=True)])
    r = resolve_lm_loss(_loss_flags(), batch=32, **gpt)
    assert r[:2] == (0, 0) and r.pallas and r.source == "test-planted"
    # a banked MONOLITHIC winner must not talk a non-fitting shape into
    # an OOM: the heuristic token-chunk fallback applies instead
    _plant(tune_env["golden"], [cache.Entry(
        kind="lm_loss",
        key=dict(fits=False, vocab=50304, seq=1024, batch=16,
                 n_devices=1, backend="tpu"),
        winner={"path": "monolithic", "chunk": 0}, source="test-planted",
        measured=True)])
    r = resolve_lm_loss(_loss_flags(), batch=32, **gpt)
    assert r[:2] == (0, 4096) and not r.pallas
    # a measured bounded-memory winner that BEAT monolithic on a fitting
    # shape is honored over the heuristic
    _plant(tune_env["golden"], [cache.Entry(
        kind="lm_loss",
        key=dict(fits=True, vocab=50304, seq=1024, batch=8,
                 n_devices=1, backend="tpu"),
        winner={"path": "chunk_tokens", "chunk": 2048},
        source="test-planted", measured=True)])
    r = resolve_lm_loss(_loss_flags(), batch=8, **gpt)
    assert r[:2] == (0, 2048)


def test_resolve_lm_loss_explicit_vocab_chunk_warns_measured_slower(
        tune_env):
    from dtf_tpu.cli.flags import resolve_lm_loss

    gpt = dict(seq_len=1024, vocab_size=50304)
    with mock.patch("absl.logging.warning") as warn:
        r = resolve_lm_loss(_loss_flags(loss_chunk_vocab=8192), batch=32,
                            **gpt)
        assert r[:2] == (8192, 0) and r.source == "explicit"
        assert warn.called
        assert "measured-slower" in warn.call_args[0][0]


# ------------------------------------------------- cache writes, no jax


def test_merge_entries_invalidates_resolver_plans(tune_env):
    """A cache-file WRITE must drop the memoized plans: bank-then-
    resolve in one process returns the fresh winner without a manual
    resolver.invalidate()."""
    _plant(tune_env["local"],
           _flash_entries({"block_q": 32, "block_k": 32, "block_h": 1}))
    kw = dict(seq=96, heads=4, head_dim=16, dtype="float32", causal=True,
              window=0, n_devices=8, backend="cpu")
    assert resolver.flash_plan(**kw).block_q == 32
    cache.merge_entries(tune_env["local"], _flash_entries(
        {"block_q": 64, "block_k": 96, "block_h": 1}))
    assert resolver.flash_plan(**kw).block_q == 64


def test_tune_package_resolves_without_jax(cpu_sim_subprocess_env):
    """The jax-free-at-module-level invariant is load-bearing: a parent
    that imports dtf_tpu.tune and then starts children that need the
    chip must stay off jax itself. Poison jax and prove import + a full
    resolve still work."""
    code = (
        "import builtins\n"
        "real = builtins.__import__\n"
        "def imp(name, *a, **k):\n"
        "    if name == 'jax' or name.startswith(('jax.', 'jaxlib')) \\\n"
        "            or name.startswith('tensorflow'):\n"
        "        raise ImportError('backend poisoned: ' + name)\n"
        "    return real(name, *a, **k)\n"
        "builtins.__import__ = imp\n"
        "from dtf_tpu.tune import cache, resolver, search\n"
        "p = resolver.flash_plan(seq=1024, heads=12, head_dim=64,\n"
        "                        dtype='bfloat16', causal=True, window=0,\n"
        "                        n_devices=8, backend='cpu')\n"
        "assert p.block_q and p.block_k\n"
        "assert search.seed_entries('%s')\n"
        "print('TUNE_NO_JAX_OK', p.block_q)\n" % ROOT)
    proc = subprocess.run([sys.executable, "-c", code],
                          env=dict(cpu_sim_subprocess_env), cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "TUNE_NO_JAX_OK" in proc.stdout


# ------------------------------------------------------------- srclint


def test_srclint_fences_block_literals(tmp_path):
    from dtf_tpu.analysis import srclint

    scripts = tmp_path / "scripts"
    scripts.mkdir()
    bad = scripts / "launch_thing.py"
    bad.write_text(textwrap.dedent("""\
        from dtf_tpu.ops.flash_attention import flash_attention
        def f(q):
            return flash_attention(q, q, q, causal=True, block_q=512,
                                   block_k=1024)
    """))
    probs = srclint.lint_file(str(bad))
    assert sum("block-shape literal" in p for p in probs) == 2
    # 0 is the resolver sentinel — legal; variables are legal; noqa pins
    ok = scripts / "launch_ok.py"
    ok.write_text(textwrap.dedent("""\
        from dtf_tpu.ops.flash_attention import flash_attention
        def f(q, bq):
            a = flash_attention(q, q, q, causal=True, block_q=0)
            b = flash_attention(q, q, q, causal=True, block_q=bq)
            c = flash_attention(q, q, q, block_q=64)  # noqa: pinned
            return a, b, c
    """))
    assert not [p for p in srclint.lint_file(str(ok))
                if "block-shape" in p]
    # fused-CE spelling is fenced too
    ce = scripts / "launch_ce.py"
    ce.write_text(textwrap.dedent("""\
        from dtf_tpu.ops.fused_ce import pallas_lm_cross_entropy
        def f(x, w, lab):
            return pallas_lm_cross_entropy(x, w, lab, block_v=1024)
    """))
    assert any("block-shape literal" in p
               for p in srclint.lint_file(str(ce)))
    # ops/ + tune/ + tests keep their pins without noqa
    for sub in ("dtf_tpu/ops", "dtf_tpu/tune", "tests"):
        d = tmp_path / sub
        d.mkdir(parents=True, exist_ok=True)
        f = d / ("test_x.py" if sub == "tests" else "x.py")
        f.write_text("def f(q, fa):\n"
                     "    return fa.flash_attention(q, q, q, block_q=32)\n")
        assert not [p for p in srclint.lint_file(str(f))
                    if "block-shape" in p], sub
    # an ANCESTOR named tests/ must not exempt a launcher (anchoring:
    # only the immediate parent counts for unanchored files) — tmp_path
    # already sits under pytest's tmp tree, so fabricate the hole
    hole = tmp_path / "tests" / "ci_checkout" / "scripts"
    hole.mkdir(parents=True)
    lf = hole / "launch.py"
    lf.write_text("def f(q, fa):\n"
                  "    return fa.flash_attention(q, q, q, block_q=32)\n")
    assert any("block-shape" in p for p in srclint.lint_file(str(lf)))


def test_srclint_fences_backend_imports_in_tune(tmp_path):
    from dtf_tpu.analysis import srclint

    d = tmp_path / "dtf_tpu" / "tune"
    d.mkdir(parents=True)
    bad = d / "cache.py"
    bad.write_text("import jax\n")
    probs = srclint.lint_file(str(bad))
    assert any("module-level 'jax' import in dtf_tpu/tune/" in p
               for p in probs)
    ok = d / "resolver.py"
    ok.write_text("def f():\n    import jax\n    return jax\n")
    assert not [p for p in srclint.lint_file(str(ok))
                if "module-level" in p]


def test_shipped_tree_is_block_literal_clean():
    from dtf_tpu.analysis import srclint

    probs = []
    for pkg in ("dtf_tpu", "scripts"):
        for base, dirs, files in os.walk(os.path.join(ROOT, pkg)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in sorted(files):
                if f.endswith(".py"):
                    probs += [p for p in srclint.lint_file(
                        os.path.join(base, f)) if "block-shape" in p]
    assert probs == []
