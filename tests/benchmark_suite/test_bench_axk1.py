"""What PR 31 adds to the benchmark: the ``ax-k1`` configuration and its
family, ``lib/mla_moe_cost.py`` against hand counts, the readers of the new
per-layer metrics, the cell's traffic letter for letter, and the comparison
that decides the serve cell's ``correct`` — at the rehearsal sizes on the
CPU, whole and under each fault of ``tools/axk1_faults.py``."""

import contextlib
import functools
import importlib
import io
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import run as bench_run
from benchmarks.drivers.serve import check_tokens
from benchmarks.families import axk1 as family
from benchmarks.lib import loadgen, mla_moe_cost, peaks as peak_table, xtrace
from benchmarks.readers import (latent_attn_roofline, mla_decode_hbm_roofline,
                                moe_gmm_held_roofline, span)
from benchmarks.reference import axk1 as reference
from benchmarks.tools import axk1_faults
from dtf_tpu.models import gpt
from dtf_tpu.serve import engine as serve_engine
from dtf_tpu.serve.scheduler import Request, Scheduler

ROOT = bench_run.ROOT
CELL = "axk1-serve-closed32-doc16k"
CONFIG_FILE = "benchmarks/configs/ax-k1.json"
with open(os.path.join(ROOT, CONFIG_FILE)) as _f:
    CONFIG = json.load(_f)
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
with open(os.path.join(ROOT, "benchmarks", "traffic",
                       "serve-closed-32-doc16k.json")) as _f:
    TRAFFIC = json.load(_f)
REHEARSAL = {**CONFIG, **CONFIG["rehearse"]}

#: the source's config.json (catalog row A.X-K1): every key but the three
#: the cut changes
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 7168, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 131072,
    "model_type": "axk1", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 64, "num_experts_per_tok": 8,
    "num_key_value_heads": 64, "q_lora_rank": 1536, "qk_nope_head_dim": 128,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "seq_aux": True,
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "none",
    "v_head_dim": 128}
WIDTHS = {"hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
          "v_head_dim", "num_experts_per_tok", "n_group", "topk_group",
          "routed_scaling_factor"}


# ---- the configuration ------------------------------------------------------


def test_config_entry_and_file_with_widths_named_by_their_keys():
    """``test_bench_manifest.test_config_entry_file_and_widths`` for this
    entry, with the published widths named by key."""
    entry = next(c for c in MANIFEST["configs"] if c["name"] == "ax-k1")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert entry["file"] == CONFIG_FILE and len(entry["why"]) <= 200
    assert CONFIG["source"] == entry["source"]
    assert CONFIG["reduced"] == entry["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert not WIDTHS & set(entry["reduced"])
    mod = importlib.import_module(f"benchmarks.families.{CONFIG['family']}")
    for keys in (mod.KEYS, mod.EXPERT_KEYS, mod.LATENT_KEYS):
        assert set(keys) <= set(CONFIG)
        assert set(keys) <= set(REHEARSAL)
    assert set(mod.YARN_KEYS) <= set(CONFIG["rope_scaling"])
    assert [w["name"] for w in MANIFEST["workloads"]
            if w["config"] == "ax-k1"] == [CELL]
    for key in ("assumed", "departures", "deployment", "rehearse",
                "published", "bytes"):
        assert CONFIG[key], key
    assert set(CONFIG["assumed"]) >= {"topk_method", "group_score",
                                      "weights"}


def test_every_published_key_is_unchanged_and_the_cut_is_the_stated_share():
    for key, value in PUBLISHED.items():
        assert CONFIG[key] == value, key
    assert CONFIG["published"] == {"num_hidden_layers": 61,
                                   "n_routed_experts": 192,
                                   "vocab_size": 163840}
    # the floors of a model_config cut: a whole period and four layers past
    # the dense one, eight routed experts, an eighth of the vocabulary
    assert CONFIG["num_hidden_layers"] - CONFIG["first_k_dense_replace"] >= 4
    assert CONFIG["n_routed_experts"] == 12 >= 8
    assert CONFIG["experts_held"] == [0, 12]
    assert CONFIG["routed_experts_published"] == 192 == 16 * 12
    assert CONFIG["vocab_size"] * 8 == 163840
    assert CONFIG["vocab_slice"] == [0, 20480]
    assert "16 chips share each layer" in CONFIG["deployment"]


def test_family_maps_the_file_onto_the_program():
    cfg = family.model_config(CONFIG)
    assert (cfg.d_model, cfg.layers, cfg.heads, cfg.d_ff, cfg.vocab_size) == (
        7168, 6, 64, 18432, 20480)
    assert cfg.layer_kinds == ("mla",) * 6 and cfg.dense_layers == 1
    la = cfg.latent
    assert (la.q_rank, la.kv_rank, la.nope_dim, la.rope_dim, la.v_dim) == (
        1536, 512, 128, 64, 128)
    assert la.latent_width == 576 and la.yarn_factor == 32
    assert la.softmax_scale == pytest.approx(192 ** -0.5 * 1.81326, rel=1e-5)
    ex = cfg.experts
    assert (ex.num_experts, ex.top_k, ex.d_ff, ex.n_group, ex.topk_group) == (
        192, 8, 2048, 8, 4)
    assert ex.held == (0, 12) and not ex.use_expert_bias
    assert ex.routed_scaling_factor == 2.5 and cfg.shared_expert_ff == 2048
    assert cfg.param_dtype == jnp.bfloat16 and not cfg.tie_head
    assert cfg.norm_eps == 1e-6 and cfg.rope_theta == 10000
    assert not hasattr(family, "build_train")
    # the parameters and bytes of the tree the engine is given
    model = gpt.GPT(cfg)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = sum(s.size for s in jax.tree.leaves(shapes))
    nbytes = sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(shapes))
    assert count == pytest.approx(4.166e9, rel=1e-3)
    assert nbytes == pytest.approx(8.346e9, rel=1e-3)
    matrices, routers = mla_moe_cost.always_met_params(CONFIG)
    held = 5 * 12 * mla_moe_cost.expert_bytes(CONFIG, 1)
    norms = sum(s.size for p, s
                in jax.tree_util.tree_flatten_with_path(shapes)[0]
                if p[-1].key == "scale")
    embedding = 20480 * 7168
    assert count == matrices + routers + held + norms + embedding
    # the latent cache: 576 numbers a position a layer
    state = serve_engine.engine_state_struct(cfg, n_slots=32, max_len=16384)
    cache = sum(s.size * s.dtype.itemsize
                for s in jax.tree.leaves(state["cache"]))
    assert cache == 32 * 16384 * 6 * 1152 + 6 * 32 * 4


def test_weights_come_from_the_seed_alone():
    fam = family.build_serve(REHEARSAL)
    a = fam.init_params(jax.random.PRNGKey(2**31 + 5))
    b = fam.init_params(jax.random.PRNGKey(2**31 + 5))
    c = fam.init_params(jax.random.PRNGKey(7))
    same = jax.tree.map(lambda x, y: bool(jnp.all(x == y)), a, b)
    assert all(jax.tree.leaves(same))
    w1 = a["layer_1"]["experts"]["w1"]
    assert w1.dtype == jnp.bfloat16 and w1.shape == (8, 64, 32)
    assert not bool(jnp.all(w1 == c["layer_1"]["experts"]["w1"]))
    assert float(jnp.std(w1.astype(jnp.float32))) == pytest.approx(
        1 / 8, rel=0.05)                              # 1 / sqrt(fan_in 64)
    assert a["layer_1"]["experts"]["router"].shape == (64, 16)
    assert "expert_bias" not in a["layer_1"]["experts"]
    assert float(a["ln_f"]["scale"].min()) == 1.0
    assert float(a["layer_0"]["attention"]["kv_a_norm"]["scale"].max()) == 1.0
    assert a["lm_head"]["kernel"].shape == (64, 256)


# ---- the cell ---------------------------------------------------------------


def test_the_cell_is_declared_with_the_issues_traffic_letter_for_letter():
    cell = next(w for w in MANIFEST["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "ax-k1", "chips": 1,
                    "traffic": "serve-closed-32-doc16k"}
    assert len(cell["why"]) <= 200 and "16x its share" in cell["why"]
    assert TRAFFIC["kind"] == "serve" and TRAFFIC["clients"] == 32
    assert TRAFFIC["engine"] == {"n_slots": 32, "max_len": 16384,
                                 "prefill_chunk": 512}
    assert "scheduler" not in TRAFFIC                 # at its defaults
    lengths = TRAFFIC["lengths"]
    assert lengths["prompt"] == {"median": 4096, "sigma": 0.7, "min": 512,
                                 "max": 15360}
    assert lengths["output"] == {"median": 192, "sigma": 0.6, "min": 32,
                                 "max": 512}
    assert (lengths["pool"], lengths["pool_seed"]) == (256, 31)
    assert (TRAFFIC["warm_completions"], TRAFFIC["check_requests"],
            TRAFFIC["trace_seconds"]) == (32, 4, 2.0)
    drawn = loadgen.request_lengths(lengths)
    assert drawn.sum(axis=1).max() <= 15872 <= TRAFFIC["engine"]["max_len"]
    assert 4800 < drawn[:, 0].mean() < 5600           # mean prompt ~5.2 k
    assert CONFIG["emitted_at_least"] == lengths["output"]["min"]


SERVE_CELLS = ["gpt2m-serve-closed32", "lfm2-serve-closed32", CELL]


def _metric(name, unit, better, source, layer, moves, cells=(CELL,)):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": list(cells)}


#: PR 31's own per-layer metrics, declared by PR 36 as PR 31 wrote them
#: (``benchmarks/AXK1.md``), with ``mla_cache_live_pct`` become
#: ``decode_attn_live_pct``: the counter it reads is every serve cell's.
DECLARED = [
    _metric("mla_decode_hbm_roofline", "%", "higher", "device_trace",
            "serve engine", "itl_ms_p95"),
    _metric("decode_attn_live_pct", "%", "lower", "program_counter",
            "serve engine", "itl_ms_p95", SERVE_CELLS),
    _metric("moe_held_pairs_per_expert", "ratio", "higher", "program_counter",
            "model blocks", "itl_ms_p95"),
    _metric("moe_held_touched_pct", "%", "lower", "program_counter",
            "model blocks", "itl_ms_p95"),
    _metric("moe_gmm_held_roofline", "%", "higher", "device_trace", "kernels",
            "serve_tokens_per_s"),
    _metric("mla_decode_attn_roofline", "%", "higher", "device_trace",
            "kernels", "itl_ms_p95"),
]


def test_the_cell_reports_the_serve_metrics():
    """By name, wherever a later PR puts its own entries."""
    by_name = {m["name"]: m
               for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]}
    shared = {"serve_tokens_per_s", "ttft_ms_p95", "itl_ms_p95",
              "decode_step_ms_p50", "slot_occupancy_pct",
              "tick_host_ms_mean", "idle_ms_per_tick.decode_dispatch",
              "idle_ms_per_tick.prefill_dispatch",
              "idle_ms_per_tick.readback", "idle_ms_per_tick.scheduler",
              "decode_device_ms_p50", "prefill_chunk_device_ms_p50",
              "decode_attn_live_pct", "sampler_greedy_steps_pct",
              "host_operands_per_tick", "device_reads_per_tick",
              "setup_engine_s", "setup_warm_s"}
    for name in shared:
        assert sorted(by_name[name]["workloads"]) == sorted(SERVE_CELLS)
    reporting = {m["name"] for m in by_name.values()
                 if CELL in m.get("workloads", [CELL])}
    # a later PR appends metrics of this cell: what is here stays
    assert reporting >= shared | {m["name"] for m in DECLARED} | {
        "setup_s", "setup_imports_s", "setup_devices_s", "setup_init_s",
        "moe_max_load_over_mean"}
    for m in MANIFEST["per_layer"]:
        if CELL in m.get("workloads", []):
            assert CELL in by_name[m["moves"]].get("workloads", [CELL])


@pytest.mark.parametrize("entry", DECLARED, ids=lambda e: e["name"])
def test_the_cells_metric_is_declared_as_written(entry):
    """Each entry stands in ``per_layer`` as ``benchmarks/AXK1.md`` has it,
    with its file and reader."""
    assert entry in MANIFEST["per_layer"]
    assert entry["source"] in ("device_trace", "program_counter")
    moved = next(m for m in MANIFEST["end_to_end"]
                 if m["name"] == entry["moves"])
    assert set(entry["workloads"]) <= set(moved["workloads"])
    if entry["name"].endswith("_roofline"):
        assert (entry["unit"], entry["better"]) == ("%", "higher")
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           entry["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read)
    assert spec["args"].get("config_file", CONFIG_FILE) == CONFIG_FILE


# ---- lib/mla_moe_cost.py against hand counts ----------------------------------

TOY = {"hidden_size": 8, "intermediate_size": 20, "moe_intermediate_size": 4,
       "num_attention_heads": 2, "q_lora_rank": 6, "kv_lora_rank": 4,
       "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
       "n_routed_experts": 3, "routed_experts_published": 12,
       "n_shared_experts": 1, "num_experts_per_tok": 2, "vocab_size": 50,
       "num_hidden_layers": 3, "first_k_dense_replace": 1}


def test_mla_moe_cost_against_hand_counts():
    assert mla_moe_cost.expert_layers(TOY) == 2
    assert mla_moe_cost.expert_bytes(TOY) == 3 * 8 * 4 * 2            # 192
    assert mla_moe_cost.latent_width(TOY) == 6
    # q_a 8x6, q_b 6x(2x5), kv_a 8x6, kv_b 4x(2x6), o (2x3)x8
    assert mla_moe_cost.attention_params(TOY) == 48 + 60 + 48 + 48 + 48
    matrices, routers = mla_moe_cost.always_met_params(TOY)
    # three attentions, one dense FFN 3x8x20, two shared experts 3x8x4, head
    assert matrices == 3 * 252 + 480 + 2 * 96 + 400
    assert routers == 2 * 8 * 12
    assert mla_moe_cost.always_read_bytes(TOY) == matrices * 2 + routers * 4
    assert mla_moe_cost.cache_bytes_per_position(TOY) == 6 * 2 * 3
    # 5 pairs on 2 held experts: 2x5x3x8x4 FLOPs; 2 experts + rows in, out
    assert mla_moe_cost.grouped_ffn_cost(TOY, pairs=5, touched=2) == (
        960.0, 2 * 192 + 2 * 5 * 8 * 2)
    # one layer, 3 slots, 40 cached positions: 43 rows of 6 numbers met by 2
    # heads' scores (6) and values (4); the rows, and 3 x 2 heads x (6 + 4)
    flops, nbytes = mla_moe_cost.latent_attention_cost(
        TOY, slots=3, cache_positions=40)
    assert flops == 2 * 2 * (6 + 4) * 43
    assert nbytes == 2 * (43 * 6 + 3 * 2 * 10)
    flops, nbytes = mla_moe_cost.decode_step_cost(
        TOY, tokens=3, held_pairs=1.5, held_touched=1.25, cache_positions=40)
    assert nbytes == (mla_moe_cost.always_read_bytes(TOY) + 2 * 1.25 * 192
                      + 43 * 36)
    assert flops == (2 * 3 * (matrices + routers) + 2 * 2 * 1.5 * 96
                     + 3 * 2 * 2 * 10 * 43)


def test_mla_moe_cost_at_the_cells_widths():
    """The bytes ISSUE 31 and PERF.md reckon with."""
    assert mla_moe_cost.attention_params(CONFIG) == 101_122_048
    assert mla_moe_cost.expert_bytes(CONFIG) == 88_080_384
    assert 12 * mla_moe_cost.expert_bytes(CONFIG) == pytest.approx(
        1.057e9, rel=1e-3)
    assert mla_moe_cost.cache_bytes_per_position(CONFIG) == 6 * 1152
    # attention 6 x 101.12 M + dense 396.36 M + 5 shared 44.04 M + the head
    # slice 146.80 M, in bfloat16, + 5 routers 7168 x 192 in float32
    assert mla_moe_cost.always_read_bytes(CONFIG) == pytest.approx(
        2 * (606.73e6 + 396.36e6 + 220.20e6 + 146.80e6) + 4 * 6.88e6,
        rel=1e-3)
    # a decode step of the issue's reckoning: 32 slots at ~5.4 k positions,
    # ~74% of the 12 held experts touched: ~8 GB, memory-bound
    flops, nbytes = mla_moe_cost.decode_step_cost(
        CONFIG, tokens=32, held_pairs=16, held_touched=8.9,
        cache_positions=32 * 5400)
    assert nbytes == pytest.approx(2.77e9 + 5 * 8.9 * 88.08e6 + 1.19e9,
                                   rel=1e-2)
    assert nbytes / 819e9 > flops / 197e12


# ---- the readers --------------------------------------------------------------

TPU = "/device:TPU:0"


def rollup(**means):
    return {f"serve_{name}": {"count": 10, "mean_s": value,
                              "total_s": 10 * value, "p50_s": value,
                              "p99_s": value}
            for name, value in means.items()}


def custom_call(name, start, dur):
    return (f"%{name}.7 = bf16[448,2048]{{1,0}} custom-call(%a, %b), "
            'custom_call_target="tpu_custom_call"', float(start), float(dur))


def make_obs(spans, trace=True):
    modules = [("jit_prefill_fn(1)", 0.0, 30e6),
               ("jit_decode_fn(2)", 40e6, 40e6),
               ("jit_decode_fn(2)", 90e6, 20e6)]
    ops = [custom_call("dtf_moe_gmm", 1e6, 20e6),
           custom_call("dtf_moe_gmm", 41e6, 10e6),
           custom_call("dtf_moe_gmm", 91e6, 10e6),
           custom_call("dtf_mla_decode_attn", 52e6, 6e6),
           custom_call("dtf_mla_decode_attn", 102e6, 6e6),
           ("%fusion.1 = f32[8]{0} fusion(%dtf_mla_decode_attn.7)", 60e6,
            5e6)]
    return {"spans": spans, "values": {}, "chips": 1,
            "peaks": peak_table.peaks_for("TPU v5 lite"),
            "trace": xtrace.Trace(ops={TPU: ops}, modules={TPU: modules},
                                  host=[]) if trace else None}


SPANS = rollup(moe_picks=256.0, moe_held_pairs=15.0, moe_held_touched=9.0,
               moe_cache_positions=170000.0, moe_prefill_picks=4096.0,
               decode_attn_live_pct=33.0)
GMM = r"^%?\w*dtf_moe_gmm"
ATTN = r"^%?\w*dtf_mla_decode_attn"


def test_new_readers_return_nothing_where_nothing_is_to_read():
    """An untraced run, a program without the counters (the parent commit:
    ``serve_moe_held_*`` do not exist there), a rehearsal, a trace without
    the kernel or the program: the metric is left out, nothing raises."""
    args = dict(config_file=CONFIG_FILE)
    parent = {k: v for k, v in SPANS.items() if "held" not in k}
    for obs in (make_obs(SPANS, trace=False), make_obs({}), make_obs(parent),
                {**make_obs(SPANS), "peaks": None}):
        assert mla_decode_hbm_roofline.read(obs, **args) is None
        assert moe_gmm_held_roofline.read(obs, kernel=GMM, **args) is None
    for obs in (make_obs(SPANS, trace=False), make_obs({}),
                {**make_obs(SPANS), "peaks": None}):
        assert latent_attn_roofline.read(obs, kernel=ATTN, **args) is None
    assert span.read(make_obs({}), span="serve_moe_held_pairs") is None
    for reader, kernel in ((moe_gmm_held_roofline, GMM),
                           (latent_attn_roofline, ATTN)):
        assert reader.read(make_obs(SPANS), kernel=r"^%nosuch",
                           **args) is None
    assert mla_decode_hbm_roofline.read(make_obs(SPANS), program="jit_other",
                                        **args) is None


def test_counter_metrics_read_the_mean_through_the_span_reader():
    obs = make_obs(SPANS)
    for name, want in (("moe_held_pairs_per_expert", 15.0 / 12),
                       ("moe_held_touched_pct", 75.0),
                       ("decode_attn_live_pct", 33.0)):
        with open(os.path.join(ROOT, "benchmarks", "metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == "span"
        assert span.read(obs, **spec["args"]) == pytest.approx(want)


def test_mla_decode_hbm_roofline_is_least_time_over_the_programs_median():
    flops, nbytes = mla_moe_cost.decode_step_cost(
        CONFIG, tokens=32.0, held_pairs=15.0, held_touched=9.0,
        cache_positions=170000.0)
    least = max(flops / 197e12, nbytes / 819e9)
    assert least == nbytes / 819e9                     # memory-bound
    # the two decode executions took 40 and 20 ms: median 30
    assert mla_decode_hbm_roofline.read(
        make_obs(SPANS), config_file=CONFIG_FILE) == pytest.approx(
            100.0 * least / 0.030)


def test_moe_gmm_held_roofline_sums_both_programs_calls():
    decode = mla_moe_cost.grouped_ffn_cost(CONFIG, pairs=15.0, touched=9.0)
    chunk = mla_moe_cost.grouped_ffn_cost(
        CONFIG, pairs=256.0,
        touched=mla_moe_cost.expected_touched(256.0, 12))
    least = 5 * (2 * max(decode[0] / 197e12, decode[1] / 819e9)
                 + 1 * max(chunk[0] / 197e12, chunk[1] / 819e9))
    assert moe_gmm_held_roofline.read(
        make_obs(SPANS), config_file=CONFIG_FILE, kernel=GMM) == (
        pytest.approx(100.0 * least / 0.040))


def test_latent_attn_roofline_counts_a_call_a_layer_of_every_step():
    flops, nbytes = mla_moe_cost.latent_attention_cost(
        CONFIG, slots=32.0, cache_positions=170000.0)
    least = 2 * 6 * max(flops / 197e12, nbytes / 819e9)
    # the kernel's two events took 6 + 6 ms; the fusion that only READS
    # its output is not counted
    assert latent_attn_roofline.read(
        make_obs(SPANS), config_file=CONFIG_FILE, kernel=ATTN) == (
        pytest.approx(100.0 * least / 0.012))


# ---- the comparison that decides the serve cell's `correct` -----------------

#: the requests of :func:`served` below emit 24 tokens each
SERVED = {**REHEARSAL, "emitted_at_least": 24}


@pytest.fixture(scope="module")
def served():
    """(family, params, [(prompt, emitted tokens)]) of three requests served
    by the engine at the rehearsal widths, in bfloat16 as the cell runs."""
    fam = family.build_serve(SERVED)
    params = fam.init_params(jax.random.PRNGKey(5))
    sched = Scheduler(serve_engine.DecodeEngine(
        fam.cfg, params, n_slots=2, max_len=128, prefill_chunk=8))
    rng = np.random.default_rng(5)
    jobs = []
    for n in (70, 41, 90):
        prompt = rng.integers(1, fam.vocab_size, n).tolist()
        jobs.append((sched.submit(Request(prompt=prompt, max_new=24)),
                     prompt))
    sched.run_until_idle()
    return fam, params, [(prompt, sched.poll(rid)["tokens"])
                         for rid, prompt in jobs]


def test_the_sound_program_is_correct_by_its_family(served, capfd):
    fam, params, sample = served
    check = check_tokens(fam.reference_logits, params, sample, 128)
    assert check["ok"] and check["tokens"] == 72, check
    notes = [json.loads(line.split(": ", 1)[1]) for line
             in capfd.readouterr().out.splitlines()
             if line.startswith("# check: ")]
    assert len(notes) == 3 and all(n["ok"] == 1.0 for n in notes)
    for n in notes:
        assert n["rerouted_share"] == 0.0
        assert n["layer_error"] < family.LAYER_ERROR_LIMIT
        assert n["attn_error"] < family.ATTN_ERROR_LIMIT
        assert n["emitted"] >= 24
    jax.effects_barrier()
    assert set(fam.readings()) == set(family.RUN_LIMITS)
    assert fam.verdict(), fam.readings()


@pytest.mark.parametrize("fault,reading", [
    ("bf16_router", "rerouted_share"),
    ("int8_experts", "layer_error"),
    ("no_yarn_scale", "attn_error"),
    ("late_rope_key", "attn_error"),
    ("no_group_limit", "rerouted_share"),
    ("unnormalised_latent", "attn_error")])
def test_each_fault_in_the_reference_reads_not_correct(served, capfd, fault,
                                                       reading):
    """The controls of ``tools/axk1_faults.py`` at rehearsal size: with the
    fault in the reference, the sound program's tokens come out not
    correct, and the reading that the fault is there to move is over its
    limit on some request."""
    fam, params, sample = served
    undo = axk1_faults.apply(fault)
    try:
        # a fresh family: the jitted comparison must trace the faulty module
        fresh = family.build_serve(SERVED)
        check = check_tokens(fresh.reference_logits, params, sample, 128)
    finally:
        undo()
    assert not check["ok"], check
    notes = [json.loads(line.split(": ", 1)[1]) for line
             in capfd.readouterr().out.splitlines()
             if line.startswith("# check: ")]
    limit = {"rerouted_share": family.REROUTED_SHARE_LIMIT,
             "layer_error": family.LAYER_ERROR_LIMIT,
             "attn_error": family.ATTN_ERROR_LIMIT}[reading]
    assert max(n[reading] for n in notes) > limit, notes
    # and the result line's `limits` carry it, with its limit
    jax.effects_barrier()
    assert fresh.readings()[reading][0] > limit == fresh.readings()[reading][1]


def test_8_bit_cached_rows_move_the_attention_reading(served, capfd):
    """``int8_latent`` at rehearsal size. A row of 16 numbers loses less to
    8 bits than the cell's rows of 512 (on the chip the fault reads
    0.0132-0.0136 against the sound 0.0064-0.0069 and the limit between
    them, ``benchmarks/AXK1.md``), so here the control is held to moving the
    reading it is there to move, by a third or more on every request, and
    no other."""
    fam, params, sample = served

    def notes(reference_logits):
        check_tokens(reference_logits, params, sample, 128)
        return [json.loads(line.split(": ", 1)[1]) for line
                in capfd.readouterr().out.splitlines()
                if line.startswith("# check: {\"attn_error")]

    sound = notes(fam.reference_logits)
    undo = axk1_faults.apply("int8_latent")
    try:
        rounded = notes(family.build_serve(SERVED).reference_logits)
    finally:
        undo()
    assert len(sound) == len(rounded) == 3
    for was, now in zip(sound, rounded):
        assert now["attn_error"] > 1.33 * was["attn_error"]
        assert now["attn_error_decoded"] > 1.33 * was["attn_error_decoded"]
        assert now["rerouted_share"] == was["rerouted_share"] == 0.0
        assert now["layer_error"] == pytest.approx(was["layer_error"],
                                                   rel=0.1)


def test_a_short_sequence_reads_the_stretches_it_fills(served):
    """A rehearsal's prompts start at 4 tokens. A stretch of the attention
    reading that holds fewer than ATTN_MIN_POSITIONS of a sequence's
    positions is not read (the median of two read up to 0.0113 against the
    limit of 0.0095), and a sequence that fills none reads 0, never NaN."""
    fam, params, _ = served
    ids = jnp.asarray(np.random.default_rng(7).integers(
        1, fam.vocab_size, (1, 128)), jnp.int32)
    _, seen = reference.forward(
        params, ids, SERVED, experts_held=tuple(SERVED["experts_held"]),
        seen_positions=128, seen_attention=family.ATTN_POSITIONS)
    read = jax.jit(family.program_readings(fam.cfg, SERVED))

    def reading(n):                # of the sequence's first n positions
        got = read(params, seen, jnp.arange(128)[None, :] < n)
        return {name: float(v) for name, v in got.items()}

    # stretches: 0-24 a first chunk, 24-48 a continuing one, 48-64 decoded
    assert reading(26)["attn_error"] == reading(24)["attn_error"]
    assert reading(50)["attn_error_decoded"] == 0.0
    assert reading(56)["attn_error_decoded"] > 0.0
    assert reading(64)["attn_error"] == pytest.approx(
        reading(100)["attn_error"])
    short = reading(5)
    assert short["attn_error"] == 0.0
    assert 0.0 <= short["layer_error"] < family.LAYER_ERROR_LIMIT


def test_a_layer_or_stretch_with_nothing_to_read_reads_0_and_a_nan_fails():
    values = jnp.asarray([[0.1, 0.2, 0.3, jnp.nan]])
    mask = jnp.asarray([[True, True, False, False]])
    assert float(family.median_where(values, mask, 2)) == pytest.approx(0.15)
    assert float(family.median_where(values, mask, 3)) == 0.0
    assert float(family.median_where(values, ~mask, 1)) == float("inf")


@functools.cache
def rehearsal() -> dict:
    """The last line of one traced rehearsal through ``run.py``, run once
    for the two tests below."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 31),
                             "--seconds", "1", "--trace", "1", "--rehearse",
                             "1"])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_cell_rehearses_through_run_py():
    last = rehearsal()
    assert last["correct"] and last["failed"] == 0 and last["attempted"] > 0
    assert last["metrics"] == {}                 # a CPU reports no metric
    assert {"decode_step_ms_p50", "slot_occupancy_pct",
            "tick_host_ms_mean"} <= set(last["rehearsal"]["would_report"])
    # the family's own limits, the worst of its requests, on the line
    assert set(family.LIMITS) <= set(last["limits"])


def test_the_cells_own_metrics_are_read_once_they_are_declared():
    """The counters' metrics out of a rehearsal of the manifest itself (the
    three that read the device trace need a chip's)."""
    assert {"decode_attn_live_pct", "sampler_greedy_steps_pct",
            "moe_max_load_over_mean", "moe_held_pairs_per_expert",
            "moe_held_touched_pct", "host_operands_per_tick",
            "device_reads_per_tick"} <= set(
                rehearsal()["rehearsal"]["would_report"])
