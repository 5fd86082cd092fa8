"""What PR 26 adds to the benchmark: the ``lfm2-24b-a2b`` configuration and
its family, ``lib/moe_cost.py`` against hand counts, the readers of the new
per-layer metrics, and the comparison that decides the serve cell's
``correct`` — at the rehearsal sizes on the CPU, whole and broken on
purpose."""

import importlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.drivers.serve import TOKEN_LOGIT_TOL, check_tokens
from benchmarks.families import lfm2_moe as family
from benchmarks.lib import moe_cost, peaks as peak_table, xtrace
from benchmarks.readers import decode_hbm_roofline, moe_gmm_roofline, span
from dtf_tpu.models import gpt
from benchmarks.tools import lfm2_faults
from dtf_tpu.serve import engine as serve_engine
from dtf_tpu.serve.scheduler import Request, Scheduler

ROOT = bench_run.ROOT
CONFIG_FILE = "benchmarks/configs/lfm2-24b-a2b.json"
with open(os.path.join(ROOT, CONFIG_FILE)) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
REHEARSAL = {**CONFIG, **CONFIG["rehearse"]}

#: the source's config.json (catalog row LFM2-24B-A2B): every width, and
#: the keys the cut changes
PUBLISHED = {
    "hidden_size": 2048, "intermediate_size": 11776,
    "moe_intermediate_size": 1536, "num_attention_heads": 32,
    "num_key_value_heads": 8, "num_experts": 64, "num_experts_per_tok": 4,
    "conv_L_cache": 3, "vocab_size": 65536, "norm_eps": 1e-05,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "conv_bias": False,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"}}
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "num_experts",
          "num_experts_per_tok", "conv_L_cache", "vocab_size"}


# ---- the configuration ------------------------------------------------------


def test_config_entry_and_file_with_widths_named_by_their_keys():
    """``test_bench_manifest.test_config_entry_and_file`` for this entry,
    with the width rule spelled by key (conftest.py has why)."""
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "lfm2-24b-a2b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == CONFIG_FILE and len(entry["why"]) <= 200
    assert CONFIG["source"] == entry["source"]
    assert CONFIG["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types"]
    assert not WIDTHS & set(entry["reduced"])
    mod = importlib.import_module(f"benchmarks.families.{CONFIG['family']}")
    assert set(mod.KEYS) | set(mod.EXPERT_KEYS) <= set(CONFIG)
    assert any(w["config"] == entry["name"] for w in MANIFEST["workloads"])
    for key in ("assumed", "departures", "deployment", "rehearse",
                "published"):
        assert CONFIG[key], key


def test_every_published_width_is_unchanged_and_the_cut_is_depth_only():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    kinds = CONFIG["layer_types"]
    assert len(kinds) == CONFIG["num_hidden_layers"] == 9
    assert CONFIG["num_dense_layers"] == 1
    # published layer 1, then two whole periods (attention first: layers
    # 2-9 of conv, conv, full_attention, conv, conv, conv, full_attention..)
    period = ["full_attention", "conv", "conv", "conv"]
    assert kinds == ["conv"] + period + period
    assert kinds.count("conv") == 3 * kinds.count("full_attention") + 1


def test_family_maps_the_file_onto_the_program():
    cfg = family.model_config(CONFIG)
    assert (cfg.d_model, cfg.layers, cfg.heads, cfg.kv_heads, cfg.d_ff) == (
        2048, 9, 32, 8, 11776)
    assert cfg.layer_kinds == ("conv", "attn", "conv", "conv", "conv",
                               "attn", "conv", "conv", "conv")
    assert cfg.experts.num_experts == 64 and cfg.experts.top_k == 4
    assert cfg.experts.d_ff == 1536 and cfg.experts.experts_held is None
    assert cfg.rope_theta == 1e6 and cfg.norm_eps == 1e-5
    assert cfg.param_dtype == jnp.bfloat16 and cfg.tie_head
    assert not hasattr(family, "build_train")
    # 10.36 GB of weights in the tree the engine is given
    model = gpt.GPT(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert 10.3e9 < nbytes < 10.4e9
    experts = sum(s.size * 2 for p, s in
                  jax.tree_util.tree_flatten_with_path(shapes)[0]
                  if p[-1].key in ("w1", "w2", "w3"))
    assert experts == 8 * 64 * moe_cost.expert_bytes(CONFIG)


def test_weights_come_from_the_seed_alone():
    fam = family.build_serve(REHEARSAL)
    a = fam.init_params(jax.random.PRNGKey(2**31 + 5))
    b = fam.init_params(jax.random.PRNGKey(2**31 + 5))
    c = fam.init_params(jax.random.PRNGKey(7))
    same = jax.tree.map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree.leaves(same))
    bias = a["layer_1"]["experts"]["expert_bias"]
    assert float(jnp.std(bias)) > 0.01           # small and not zero
    assert not bool(jnp.all(bias == c["layer_1"]["experts"]["expert_bias"]))
    assert a["layer_1"]["experts"]["w1"].dtype == jnp.bfloat16
    assert float(a["ln_f"]["scale"].min()) == 1.0


# ---- lib/moe_cost.py against hand counts ------------------------------------

TOY = {"hidden_size": 8, "intermediate_size": 20, "moe_intermediate_size": 4,
       "num_attention_heads": 4, "num_key_value_heads": 2, "num_experts": 6,
       "num_experts_per_tok": 2, "vocab_size": 50, "num_hidden_layers": 3,
       "num_dense_layers": 1, "layer_types": ["conv", "full_attention",
                                              "conv"]}


def test_moe_cost_against_hand_counts():
    assert moe_cost.expert_layers(TOY) == 2
    assert moe_cost.expert_bytes(TOY) == 3 * 8 * 4 * 2            # 192
    # attention: q 8x8, k and v 8x4 each (2 kv heads of 2), out 8x8
    assert moe_cost.operator_params(TOY, "full_attention") == 64 + 64 + 64
    assert moe_cost.operator_params(TOY, "conv") == 4 * 64
    matrices, routers = moe_cost.always_met_params(TOY)
    # two conv + one attention, one dense FFN 3x8x20, the head 50x8
    assert matrices == 2 * 256 + 192 + 480 + 400
    assert routers == 2 * 8 * 6
    assert moe_cost.always_read_bytes(TOY) == matrices * 2 + routers * 4
    # K and V of one position: 2 x (2 heads x 2) x 2 B, one attention layer
    assert moe_cost.cache_bytes_per_position(TOY) == 16
    # 5 pairs on 3 experts: 2x5x3x8x4 FLOPs; 3 experts + rows in and out
    assert moe_cost.grouped_ffn_cost(TOY, pairs=5, touched=3) == (
        960.0, 3 * 192 + 2 * 5 * 8 * 2)
    flops, nbytes = moe_cost.decode_step_cost(
        TOY, tokens=3, touched=2.5, cache_positions=40)
    assert nbytes == (moe_cost.always_read_bytes(TOY) + 2 * 2.5 * 192
                      + 40 * 16)
    met = matrices + routers + 2 * 2 * 96     # 2 layers x 2 experts x 3x8x4
    assert flops == 2 * 3 * met + 4 * 40 * 8 * 1
    assert moe_cost.expected_touched(1, 64) == pytest.approx(1.0)
    assert moe_cost.expected_touched(128, 64) == pytest.approx(55.47, abs=.01)
    assert moe_cost.expected_touched(10**6, 64) == pytest.approx(64.0)


def test_moe_cost_at_the_cells_widths():
    """The bytes PERF.md reckons with."""
    assert moe_cost.expert_bytes(CONFIG) == 18_874_368
    assert moe_cost.expert_layers(CONFIG) * 64 * moe_cost.expert_bytes(
        CONFIG) == pytest.approx(9.66e9, rel=1e-3)
    assert moe_cost.always_read_bytes(CONFIG) == pytest.approx(0.694e9,
                                                               rel=1e-3)
    assert moe_cost.cache_bytes_per_position(CONFIG) == 4096


# ---- the readers --------------------------------------------------------------

TPU = "/device:TPU:0"


def rollup(**means):
    return {f"serve_moe_{name}": {"count": 10, "mean_s": value,
                                  "total_s": 10 * value, "p50_s": value,
                                  "p99_s": value}
            for name, value in means.items()}


def gmm_op(start, dur):
    return ("%dtf_moe_gmm.7 = bf16[1088,1536]{1,0} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"', float(start), float(dur))


def make_obs(spans, trace=True):
    modules = [("jit_prefill_fn(1)", 0.0, 30e6), ("jit_decode_fn(2)", 40e6,
                                                  40e6),
               ("jit_decode_fn(2)", 90e6, 20e6)]
    ops = [gmm_op(1e6, 20e6), gmm_op(41e6, 10e6), gmm_op(91e6, 10e6),
           ("%fusion.1 = f32[8]{0} fusion(%dtf_moe_gmm.7)", 60e6, 5e6)]
    return {"spans": spans, "values": {}, "chips": 1,
            "peaks": peak_table.peaks_for("TPU v5 lite"),
            "trace": xtrace.Trace(ops={TPU: ops}, modules={TPU: modules},
                                  host=[]) if trace else None}


SPANS = rollup(picks=128.0, experts_touched=50.0, cache_positions=20000.0,
               prefill_picks=1024.0, max_load_over_mean=3.5)


def test_new_readers_return_nothing_where_nothing_is_to_read():
    args = dict(config_file=CONFIG_FILE)
    kernel = r"^%?\w*dtf_moe_gmm"
    for obs in (make_obs(SPANS, trace=False),            # an untraced run
                make_obs({}),                            # no counters: GPT
                {**make_obs(SPANS), "peaks": None}):     # a rehearsal
        assert decode_hbm_roofline.read(obs, **args) is None
        assert moe_gmm_roofline.read(obs, kernel=kernel, **args) is None
    assert span.read(make_obs({}), span="serve_moe_picks") is None
    # a trace with no such kernel or program (the parent's) reads nothing
    assert moe_gmm_roofline.read(make_obs(SPANS), kernel=r"^%nosuch",
                                 **args) is None
    assert decode_hbm_roofline.read(make_obs(SPANS), program="jit_other",
                                    **args) is None


def test_counter_metrics_read_the_mean_through_the_span_reader():
    """A counter on the span channel is read by the reader that was there:
    ``mean_s`` of numbers is their mean; 100 / 64 experts = 1.5625."""
    obs = make_obs(SPANS)
    for name, want in (("moe_experts_touched_pct", 78.125),
                       ("moe_max_load_over_mean", 3.5)):
        with open(os.path.join(ROOT, "benchmarks", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "span"
        assert span.read(obs, **spec["args"]) == pytest.approx(want)


def test_decode_hbm_roofline_is_least_time_over_the_programs_median():
    obs = make_obs(SPANS)
    flops, nbytes = moe_cost.decode_step_cost(
        CONFIG, tokens=32.0, touched=50.0, cache_positions=20000.0)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9                     # memory-bound
    # the two decode executions took 40 and 20 ms: median 30
    assert decode_hbm_roofline.read(obs, config_file=CONFIG_FILE) == (
        pytest.approx(100.0 * least / 0.030))


def test_moe_gmm_roofline_sums_both_programs_calls():
    obs = make_obs(SPANS)
    decode = moe_cost.grouped_ffn_cost(CONFIG, pairs=128.0, touched=50.0)
    prefill = moe_cost.grouped_ffn_cost(
        CONFIG, pairs=1024.0, touched=moe_cost.expected_touched(1024.0, 64))
    least = 8 * (2 * max(decode[0] / 197e12, decode[1] / 819e9)
                 + 1 * max(prefill[0] / 197e12, prefill[1] / 819e9))
    # the kernel's three events took 20 + 10 + 10 ms; the fusion that only
    # READS its output is not counted
    assert moe_gmm_roofline.read(
        obs, config_file=CONFIG_FILE, kernel=r"^%?\w*dtf_moe_gmm") == (
        pytest.approx(100.0 * least / 0.040))


def test_new_metrics_are_declared_for_the_new_cell_alone():
    new = {"moe_experts_touched_pct": "itl_ms_p95",
           "moe_max_load_over_mean": "itl_ms_p95",
           "decode_hbm_roofline": "itl_ms_p95",
           "moe_gmm_roofline": "serve_tokens_per_s"}
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name, moves in new.items():
        assert by_name[name]["workloads"] == ["lfm2-serve-closed32"]
        assert by_name[name]["moves"] == moves
    assert [m["name"] for m in MANIFEST["per_layer"]][-4:] == list(new)
    cells = {w["name"]: w for w in MANIFEST["workloads"]}
    assert cells["lfm2-serve-closed32"]["chips"] == 1
    assert cells["gpt2m-train-dp4-zero1"]["chips"] == 4


# ---- the comparison that decides the serve cell's `correct` -----------------


#: the requests of :func:`serve` below emit 24 tokens each
SERVED = {**REHEARSAL, "emitted_at_least": 24}


@pytest.fixture(scope="module")
def fam():
    return family.build_serve(SERVED)


@pytest.fixture(scope="module")
def weights(fam):
    return fam.init_params(jax.random.PRNGKey(2**31 + 26))


def serve(fam, params, n=8, max_len=96, chunk=8):
    eng = serve_engine.DecodeEngine(fam.cfg, params, n_slots=4,
                                    max_len=max_len, prefill_chunk=chunk)
    sched = Scheduler(eng)
    rng = np.random.default_rng(1)
    jobs = []
    for _ in range(n):
        prompt = rng.integers(0, fam.vocab_size,
                              int(rng.integers(5, 40))).tolist()
        jobs.append((sched.submit(Request(prompt=prompt, max_new=24)),
                     prompt))
    sched.run_until_idle()
    return [(prompt, sched.poll(rid)["tokens"]) for rid, prompt in jobs]


@pytest.fixture(scope="module")
def sample(fam, weights):
    """What a sound bfloat16 engine emitted: every comparison below judges
    these same tokens."""
    return serve(fam, weights)


def checked(fam, weights, sample, capsys):
    check = check_tokens(fam.reference_logits, weights, sample, 96)
    notes = [json.loads(line.split(": ", 1)[1]) for line
             in capsys.readouterr().out.splitlines()
             if line.startswith("# check: ")]
    assert len(notes) == len(sample)
    return check, notes


def test_bfloat16_engine_meets_every_limit(fam, weights, sample, capsys):
    """The timed path at the rehearsal widths against the float32 reference
    routing for itself: the program's expert layers agree with it on its
    own inputs, no position is routed otherwise, and the emitted tokens lie
    within the driver's limit, which this family does not widen."""
    assert family.TOKEN_LOGIT_TOL == TOKEN_LOGIT_TOL == 0.15
    check, notes = checked(fam, weights, sample, capsys)
    assert check["ok"] and check["tokens"] == 8 * 24, check
    for note in notes:
        assert note["ok"] == 1.0
        assert 0.002 < note["layer_error"] < family.LAYER_ERROR_LIMIT / 1.5
        assert note["rerouted_share"] == 0.0
        assert note["within_share"] >= 0.9 and 24 <= note["emitted"] <= 28


#: fault in the reference -> the limit of the family that catches it
CAUGHT_BY = {"int8_weights": "layer_error", "fp8_weights": "layer_error",
             "bf16_router": "rerouted_share",
             "stale_conv_column": "within_share"}


@pytest.mark.parametrize("fault", sorted(lfm2_faults.FAULTS))
def test_a_faulty_reference_fails_the_same_tokens(fam, weights, sample,
                                                  capsys, fault):
    """The controls of ``tools/lfm2_faults.py``, as the chip runs them
    (PERF.md section 6): 8-bit weights and a bfloat16 router fail the
    expert layers' comparison, a conv one column late fails the tokens'."""
    undo = lfm2_faults.apply(fault)
    try:
        fresh = family.build_serve(SERVED)         # nothing traced before
        check, notes = checked(fresh, weights, sample, capsys)
    finally:
        undo()
    assert not check["ok"] and check["outside_tolerance"] > 0, check
    limit = {"layer_error": family.LAYER_ERROR_LIMIT,
             "rerouted_share": family.REROUTED_SHARE_LIMIT}
    name = CAUGHT_BY[fault]
    if name == "within_share":
        assert all(n["within_score"] < family.WITHIN_LIMIT
                   for n in notes), notes
    else:
        assert any(n[name] > limit[name] for n in notes), notes


def test_emitted_run_is_the_likely_suffix_and_never_under_the_floor():
    likely = jnp.asarray([[0, 0, 1, 0, 0, 0, 1, 1, 0, 1, 1, 0, 0]], bool)
    valid = jnp.asarray([[1] * 11 + [0, 0]], bool)
    run = family.emitted_run(likely, valid, 2)
    # the lone hit at 2 does not pay for the three misses after it
    assert run[0].tolist() == [0] * 6 + [1] * 5 + [0, 0]
    assert family.emitted_run(likely, valid, 7)[0].tolist() == (
        [0] * 4 + [1] * 7 + [0, 0])
    # an engine that emits noise is judged on the floor's tokens
    noise = family.emitted_run(jnp.zeros((1, 13), bool), valid, 3)
    assert noise[0].tolist() == [0] * 8 + [1] * 3 + [0, 0]


def test_a_pad_column_in_the_conv_state_fails_the_comparison(fam, weights,
                                                             monkeypatch):
    """The ragged last chunk's pad enters the state when the conv ignores
    ``prefill_len``: what the engine then emits is no longer what the
    reference's arg-max is, for most of every request."""
    whole = gpt.ShortConv.__call__
    monkeypatch.setattr(
        gpt.ShortConv, "__call__",
        lambda self, x, prefill_len=None, decode_active=None: whole(
            self, x, None, decode_active))
    check = check_tokens(fam.reference_logits, weights,
                         serve(fam, weights), 96)
    assert not check["ok"], check


def test_a_conv_state_never_zeroed_fails_the_comparison(fam, weights,
                                                        monkeypatch):
    """Admission does not zero the state: with prompts this short the stale
    columns reach the emitted tokens and the comparison fails. (At the
    cell's prompts, 64 tokens and more, they reach them only through
    attention over the first two positions: PERF.md section 7.)"""
    monkeypatch.setattr(gpt, "_RECURRENT_CACHE_KEYS", frozenset())
    check = check_tokens(fam.reference_logits, weights,
                         serve(fam, weights, n=16), 96)
    assert not check["ok"], check


def test_the_calibration_tool_rehearses(tmp_path, monkeypatch, capsys):
    """``tools/lfm2_calibrate.py``: the call that re-takes every reading the
    limits lie between, on the rehearsal sizes."""
    from benchmarks.tools import lfm2_calibrate

    monkeypatch.chdir(tmp_path)
    assert lfm2_calibrate.main(["3", str(2**31 + 7), "--rehearse"]) == 0
    with open(tmp_path / "chiprun_out" / "lfm2_calibration.json") as f:
        read = json.load(f)["references"]
    assert set(read) == {"sound", *lfm2_faults.FAULTS}
    assert all(len(rows) == 3 for rows in read.values())
    assert max(r["layer_error"] for r in read["sound"]) \
        < family.LAYER_ERROR_LIMIT < min(
            r["layer_error"] for r in read["int8_weights"])
    assert max(r["within_share"] for r in read["stale_conv_column"]) < 0.2
    # the reference module is whole again
    assert all(getattr(lfm2_faults.ref, name) is not wrong
               for name, wrong in lfm2_faults.FAULTS.values())
    assert len(capsys.readouterr().out.splitlines()) == 5
