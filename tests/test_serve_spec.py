"""Speculative decoding (ISSUE 13): token-distribution identity against
non-speculative decode, the four-program trace fence, per-row rollback
after partial acceptance, the draft-failure fallback, and the tuner-owned
draft width.

The acceptance contract: a spec engine's delivered token streams are
IDENTICAL to plain decode (greedy bitwise and seeded sampling alike — the
verifier's own samples ARE the stream; draft proposals only decide how
many positions each dispatch keeps), across pages on/off and mixed-length
churn, with ``trace_counts`` pinned at exactly four programs.

Most tests share ONE module-scope self-draft engine (admission fully
resets a slot — the PR 4 contract — so schedulers can churn it freely);
the identity matrix builds its own variants."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.fault.inject import ServeFaultPlan
from dtf_tpu.models import gpt
from dtf_tpu.serve import (DecodeEngine, Request, Scheduler,
                           install_serve_fault)

CFG = gpt.GPTConfig.tiny(dtype=jnp.float32)
MAX_LEN = 48

_OFFLINE_CACHE: dict = {}


@pytest.fixture(scope="module")
def params():
    model = gpt.GPT(dataclasses.replace(CFG, decode_len=MAX_LEN))
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 1), jnp.int32))["params"]


@pytest.fixture(scope="module")
def spec_engine(params):
    """The shared self-draft spec engine (k=3, pages off). Tests that
    wrap ``draft_propose`` restore it (correctness never depends on the
    draft anyway, but the fence tests want the real one)."""
    return _spec_engine(params, spec_k=3)


def _offline(params, req: dict, eos_id=None) -> list[int]:
    """Per-request reference: batch-1 offline generate(), truncated the
    way the engine terminates — memoized (the identity matrix replays
    the same request set against several engine variants)."""
    key = (tuple(req["prompt"]), req["max_new"],
           req.get("temperature", 0.0), req.get("top_k", 0),
           req.get("top_p", 1.0), req.get("seed", 0), eos_id)
    if key in _OFFLINE_CACHE:
        return _OFFLINE_CACHE[key]
    model = gpt.GPT(dataclasses.replace(CFG, decode_len=MAX_LEN))
    out = gpt.generate(
        model, params, jnp.asarray([req["prompt"]], jnp.int32),
        req["max_new"], rng=jax.random.PRNGKey(req.get("seed", 0)),
        temperature=req.get("temperature", 0.0),
        top_k=req.get("top_k", 0), top_p=req.get("top_p", 1.0),
        eos_id=eos_id)
    toks = np.asarray(out)[0, len(req["prompt"]):].tolist()
    if eos_id is not None and eos_id in toks:
        toks = toks[:toks.index(eos_id) + 1]
    _OFFLINE_CACHE[key] = toks
    return toks


def _mixed_reqs(n=6, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        t_p = int(rng.integers(1, 20))
        reqs.append(dict(
            prompt=rng.integers(0, CFG.vocab_size, t_p).tolist(),
            max_new=int(rng.integers(1, 16)),
            temperature=0.0 if i % 2 == 0 else 0.9,
            top_k=0 if i < 4 else 3, top_p=1.0 if i % 3 else 0.9,
            seed=100 + i))
    return reqs


def _spec_engine(params, *, draft="self", spec_k=3, pages=False, **kw):
    if draft == "self":
        dcfg, dparams = CFG, params
    else:                       # truncated early-exit draft (1 of 2 layers)
        dcfg, dparams = gpt.draft_truncate(CFG, params, 1)
    page_kw = (dict(kv_page_size=4, prefix_pages=8, page_save_after=1)
               if pages else {})
    return DecodeEngine(CFG, params, n_slots=4, max_len=MAX_LEN,
                        prefill_chunk=5, draft_cfg=dcfg,
                        draft_params=dparams, spec_k=spec_k,
                        **page_kw, **kw)


# --------------------------------------------------------- identity matrix

@pytest.mark.parametrize("draft,pages", [("self", False), ("self", True),
                                         ("truncated", True)])
def test_spec_identity_matrix(params, draft, pages):
    """THE acceptance matrix: spec on × {self, truncated} draft ×
    {pages off, pages on}, mixed-length greedy+sampled churn with more
    requests than slots — every stream bitwise equals per-request offline
    generate(), i.e. equals what the non-speculative engine (PR 4
    identity) would emit. A truncated random-init draft has ~zero
    acceptance — correctness must not depend on proposal quality."""
    eng = _spec_engine(params, draft=draft, pages=pages)
    sched = Scheduler(eng, None, prefill_chunks_per_tick=2)
    reqs = _mixed_reqs()
    rids = [sched.submit(Request(**r)) for r in reqs]
    sched.run_until_idle()
    for r, rid in zip(reqs, rids):
        assert sched.poll(rid)["tokens"] == _offline(params, r), r
    assert eng.trace_counts == {"prefill": 1, "decode": 1,
                                "draft_prefill": 1, "draft": 1}
    if draft == "self":
        # self-draft + greedy rows should actually ACCEPT (the win
        # mechanism is live, not just correct)
        assert sched._spec_accepted > 0


def test_spec_identity_quantized_draft(params):
    """ISSUE 17's serving win: an int8-quantized DRAFT proposes (TpDense
    routes through quantized_matmul), the bf16 verifier samples every
    delivered token — streams stay bitwise equal to offline generate().
    Draft precision is a throughput/acceptance knob, never correctness."""
    dcfg, dparams = gpt.draft_truncate(CFG, params, 1)
    dcfg = dataclasses.replace(dcfg, matmul_precision="int8")
    eng = DecodeEngine(CFG, params, n_slots=4, max_len=MAX_LEN,
                       prefill_chunk=5, draft_cfg=dcfg,
                       draft_params=dparams, spec_k=3)
    sched = Scheduler(eng, None, prefill_chunks_per_tick=2)
    reqs = _mixed_reqs(4, seed=5)
    rids = [sched.submit(Request(**r)) for r in reqs]
    sched.run_until_idle()
    for r, rid in zip(reqs, rids):
        assert sched.poll(rid)["tokens"] == _offline(params, r), r


@pytest.mark.slow
def test_spec_eos_and_budget_edges(params, spec_engine):
    """EOS mid-verify-chain truncates delivery exactly where offline
    stops; max_new smaller than k caps delivery; max_new=1 works."""
    reqs = [dict(prompt=[3, 1, 4, 1, 5], max_new=12, seed=7),
            dict(prompt=[2, 7, 1, 8], max_new=1, seed=8),
            dict(prompt=[9, 9], max_new=2, seed=9)]
    eos = 11
    sched = Scheduler(spec_engine, None)
    rids = [sched.submit(Request(**r, eos_id=eos)) for r in reqs]
    sched.run_until_idle()
    for r, rid in zip(reqs, rids):
        assert sched.poll(rid)["tokens"] == _offline(params, r,
                                                     eos_id=eos), r


def test_spec_engine_keeps_the_synchronous_tick(params, spec_engine):
    """A speculative engine does not run ahead (its acceptance accounting
    reads each step before the next): every tick reads its own step, and
    ``serve_decode_runahead`` reads 0 on each."""
    from dtf_tpu.telemetry import Telemetry

    assert not spec_engine.can_run_ahead
    tel = Telemetry(watchdog=False)
    sched = Scheduler(spec_engine, telemetry=tel)
    steps0 = spec_engine.counters["decode_steps"]
    req = dict(prompt=[3, 1, 4, 1, 5], max_new=7)
    rid = sched.submit(Request(**req))
    sched.run_until_idle()
    assert sched._inflight is None
    roll = tel.spans.rollup()["serve_decode_runahead"]
    assert roll["count"] == spec_engine.counters["decode_steps"] - steps0
    assert roll["total_s"] == 0.0
    assert sched.poll(rid)["tokens"] == _offline(params, req)


@pytest.mark.slow
def test_spec_int8_matches_nonspec_int8():
    """int8 KV: the verify branch reads its own in-chunk keys back
    dequantized exactly like sequential decode does, so spec-vs-plain
    identity holds at the quantized dtype too (token level)."""
    cfg8 = dataclasses.replace(CFG, kv_cache_dtype="int8")
    model8 = gpt.GPT(dataclasses.replace(cfg8, decode_len=MAX_LEN))
    params8 = model8.init(jax.random.PRNGKey(1),
                          jnp.zeros((1, 1), jnp.int32))["params"]
    plain = DecodeEngine(cfg8, params8, n_slots=2, max_len=MAX_LEN,
                         prefill_chunk=5)
    spec = DecodeEngine(cfg8, params8, n_slots=2, max_len=MAX_LEN,
                        prefill_chunk=5, draft_cfg=cfg8,
                        draft_params=params8, spec_k=3)
    reqs = _mixed_reqs(3, seed=3)
    outs = []
    for eng in (plain, spec):
        sched = Scheduler(eng, None)
        rids = [sched.submit(Request(**r)) for r in reqs]
        sched.run_until_idle()
        outs.append([sched.poll(rid)["tokens"] for rid in rids])
    assert outs[0] == outs[1]


# ------------------------------------------------- rollback + trace fences

@pytest.mark.slow
def test_partial_acceptance_rollback(params, spec_engine):
    """Per-row rollback correctness: a draft that returns PARTIALLY
    correct proposals (crafted corruption at a rotating position) must
    yield exactly the offline stream — the rejected tail's cache writes
    are dead weight behind the rolled-back index, and the continuation
    after the rollback boundary stays bitwise right."""
    orig = spec_engine.draft_propose
    tick = [0]

    def corrupting(**kw):
        props = np.asarray(orig(**kw)).copy()
        props[:, tick[0] % props.shape[1]] += 1
        tick[0] += 1
        return props % CFG.vocab_size

    spec_engine.draft_propose = corrupting
    try:
        sched = Scheduler(spec_engine, None)
        reqs = _mixed_reqs(5, seed=4)
        rids = [sched.submit(Request(**r)) for r in reqs]
        sched.run_until_idle()
        for r, rid in zip(reqs, rids):
            assert sched.poll(rid)["tokens"] == _offline(params, r), r
    finally:
        spec_engine.draft_propose = orig


def test_four_programs_pinned_compile_flat(params, spec_engine):
    """Exactly FOUR programs exist and steady-state churn retraces
    nothing — trace_counts pinned {prefill, decode, draft_prefill,
    draft: 1} with the jax.monitoring compile-events cross-check (the
    PR 4/5 fence idiom)."""
    events = []
    mon = getattr(jax, "monitoring", None)
    if mon is not None and hasattr(mon, "register_event_listener"):
        mon.register_event_listener(
            lambda name, *a, **kw: events.append(name))
    assert spec_engine.trace_counts == {"prefill": 1, "decode": 1,
                                        "draft_prefill": 1, "draft": 1}
    sched = Scheduler(spec_engine, None, prefill_chunks_per_tick=1)
    sched.submit(Request(prompt=[1, 2, 3], max_new=2))
    sched.run_until_idle()
    baseline = len([e for e in events if "compil" in e])
    rng = np.random.default_rng(1)
    for i in range(6):
        t_p = int(rng.integers(1, 20))
        sched.submit(Request(
            prompt=rng.integers(0, CFG.vocab_size, t_p).tolist(),
            max_new=int(rng.integers(1, 10)),
            temperature=float(i % 2), top_k=i, eos_id=i if i % 2 else None,
            seed=i))
    sched.run_until_idle()
    assert spec_engine.trace_counts == {"prefill": 1, "decode": 1,
                                        "draft_prefill": 1, "draft": 1}
    steady = len([e for e in events if "compil" in e])
    if baseline:
        assert steady == baseline, (
            f"{steady - baseline} backend compiles during steady-state "
            "spec churn")


# --------------------------------------------------------- chaos fallback

@pytest.mark.slow
def test_draft_poison_falls_back_to_plain_decode(params, spec_engine):
    """poison_draft chaos: while the marked request runs, draft_propose
    raises — the engine must fall back to verify-with-null-proposals
    (plain decode) instead of erroring the request or the replica, and
    every stream stays offline-identical."""
    orig = spec_engine.draft_propose
    fallbacks0 = spec_engine.counters["draft_fallbacks"]
    sched = Scheduler(spec_engine, None)
    state = install_serve_fault(ServeFaultPlan.parse("poison_draft@1"),
                                sched)
    reqs = _mixed_reqs(4, seed=6)
    # the marked request must actually DECODE (draft poison fires while
    # it is running) — a 1-token request would end at prefill
    reqs[1]["max_new"] = max(reqs[1]["max_new"], 8)
    try:
        rids = [sched.submit(Request(**r)) for r in reqs]
        sched.run_until_idle()
        assert state.fired
        assert spec_engine.counters["draft_fallbacks"] > fallbacks0
        for r, rid in zip(reqs, rids):
            st = sched.poll(rid)
            assert st["status"] == "done"
            assert st["tokens"] == _offline(params, r), r
    finally:
        spec_engine.draft_propose = orig


def test_draft_exception_fallback_direct(params, spec_engine):
    """Engine-level: a draft that always raises degrades to plain decode
    (1+ token per tick, correct stream), never to an error."""
    orig = spec_engine.draft_propose
    fallbacks0 = spec_engine.counters["draft_fallbacks"]

    def boom(**kw):
        raise RuntimeError("draft down")

    spec_engine.draft_propose = boom
    try:
        sched = Scheduler(spec_engine, None)
        r = dict(prompt=[5, 4, 3], max_new=6, seed=2)
        rid = sched.submit(Request(**r))
        sched.run_until_idle()
        assert sched.poll(rid)["tokens"] == _offline(params, r)
        assert spec_engine.counters["draft_fallbacks"] > fallbacks0
    finally:
        spec_engine.draft_propose = orig


# ------------------------------------------------------- tuner integration

def test_spec_k_resolves_through_tuner(params, tmp_path, monkeypatch):
    """spec_k=0 with a draft = the banked per-(model, draft, slots)
    winner decides (the block-shape sentinel contract); the architecture
    labels hard-match, so a foreign pair falls back to the default."""
    from dtf_tpu.serve.engine import _cfg_label
    from dtf_tpu.tune import resolver
    from dtf_tpu.tune.cache import SCHEMA_VERSION, invalidate_cache

    path = tmp_path / "KERNEL_TUNE.local.json"
    path.write_text(json.dumps({
        "schema": SCHEMA_VERSION, "entries": [
            {"kind": "spec_k",
             "key": {"model": _cfg_label(CFG), "draft": _cfg_label(CFG),
                     "n_slots": 4, "backend": "cpu"},
             "winner": {"k": 2}, "measured": True,
             "source": "test row"}]}))
    monkeypatch.setenv("DTF_KERNEL_TUNE_PATH", str(path))
    invalidate_cache()
    try:
        eng = _spec_engine(params, spec_k=0)
        assert eng.spec_k == 2          # the banked winner
        # a DIFFERENT draft architecture must not inherit the winner
        # (hard string match) — asserted at the resolver, no compile
        dcfg, _ = gpt.draft_truncate(CFG, params, 1)
        plan = resolver.spec_k_plan(
            model=_cfg_label(CFG), draft=_cfg_label(dcfg), n_slots=4,
            backend="cpu")
        assert plan.k == resolver.FALLBACK_SPEC_K and not plan.measured
        # the banked pair resolves at the resolver too, measured
        hit = resolver.spec_k_plan(
            model=_cfg_label(CFG), draft=_cfg_label(CFG), n_slots=4,
            backend="cpu")
        assert hit.k == 2 and hit.measured
    finally:
        monkeypatch.delenv("DTF_KERNEL_TUNE_PATH")
        invalidate_cache()


# ------------------------------------------------------------- validation

def test_spec_validation_errors(params):
    with pytest.raises(ValueError, match="needs a draft model"):
        DecodeEngine(CFG, params, n_slots=2, max_len=MAX_LEN,
                     prefill_chunk=5, spec_k=3)
    with pytest.raises(ValueError, match="windowless"):
        wcfg = dataclasses.replace(CFG, attn_window=8)
        DecodeEngine(wcfg, params, n_slots=2, max_len=MAX_LEN,
                     prefill_chunk=5, draft_cfg=wcfg,
                     draft_params=params, spec_k=2)
    with pytest.raises(ValueError, match="vocab"):
        dcfg = dataclasses.replace(CFG, vocab_size=64)
        DecodeEngine(CFG, params, n_slots=2, max_len=MAX_LEN,
                     prefill_chunk=5, draft_cfg=dcfg, draft_params=params,
                     spec_k=2)
    with pytest.raises(ValueError, match="draft n_layers"):
        gpt.draft_truncate(CFG, params, CFG.layers)


def test_draft_truncate_shares_leaves(params):
    dcfg, dparams = gpt.draft_truncate(CFG, params, 1)
    assert dcfg.layers == 1
    assert set(dparams) == {"token_embed", "layer_0", "ln_f", "lm_head"}
    # shared, not copied
    assert dparams["ln_f"] is params["ln_f"]
