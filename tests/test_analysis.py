"""Static analyzer (dtf_tpu/analysis): negative-path fixtures must be
caught, shipping configs must be clean, and the comms-budget fence must
trip on an injected collective."""

import copy
import json
import os
import subprocess
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dtf_tpu.analysis import configs as cfgs
from dtf_tpu.analysis import hlo
from dtf_tpu.analysis import jaxpr as aj
from dtf_tpu.analysis import runner
from dtf_tpu.analysis import specs as asp
from dtf_tpu.analysis.findings import errors

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: a stand-in mesh: specs-pass functions only read ``.shape``.
MESH42 = types.SimpleNamespace(shape={"data": 4, "model": 2})

PARAMS = {
    "embed": {"embedding": jax.ShapeDtypeStruct((1 << 11, 1 << 10),
                                                jnp.float32)},
    "dense": {"kernel": jax.ShapeDtypeStruct((16, 8), jnp.float32),
              "bias": jax.ShapeDtypeStruct((8,), jnp.float32)},
}
GOOD_RULES = [
    (r"embed/embedding", P("model", None)),
    (r"kernel", P(None, "model")),
]


def _checks(findings):
    return {f.check for f in errors(findings)}


# ------------------------------------------------------------- specs pass

def test_clean_rulebook_has_no_findings():
    assert not errors(asp.lint_rules(
        PARAMS, GOOD_RULES, MESH42.shape, config="fix"))


def test_dead_rule_detected():
    rules = GOOD_RULES + [(r"no_such_leaf", P("model"))]
    assert "dead-rule" in _checks(
        asp.lint_rules(PARAMS, rules, MESH42.shape, config="fix"))


def test_shadowed_rule_detected():
    # matches kernels, but the earlier generic rule wins every path
    rules = GOOD_RULES + [(r"dense/kernel", P("model", None))]
    assert "shadowed-rule" in _checks(
        asp.lint_rules(PARAMS, rules, MESH42.shape, config="fix"))


def test_duplicate_mesh_axis_detected():
    rules = [(r"kernel", P("model", "model"))]
    assert "duplicate-axis" in _checks(
        asp.lint_rules(PARAMS, rules, MESH42.shape, config="fix"))


def test_indivisible_dim_detected():
    # dim 6 sharded over data=4 -> ragged shards
    params = {"w": jax.ShapeDtypeStruct((6, 8), jnp.float32)}
    assert "indivisible-dim" in _checks(asp.lint_rules(
        params, [(r"w", P("data", None))], MESH42.shape, config="fix"))


def test_rank_overflow_detected():
    rules = GOOD_RULES + [(r"bias", P(None, "model"))]
    assert "rank-overflow" in _checks(
        asp.lint_rules(PARAMS, rules, MESH42.shape, config="fix"))


def test_unknown_axis_detected():
    assert "unknown-axis" in _checks(asp.lint_rules(
        PARAMS, [(r"kernel", P(None, "modle"))],   # typo'd axis
        MESH42.shape, config="fix"))


def test_large_replicated_leaf_detected():
    # embedding (2^21 elems) matched by NO rule while other rules exist
    rules = [(r"kernel", P(None, "model"))]
    assert "replicated-large-leaf" in _checks(
        asp.lint_rules(PARAMS, rules, MESH42.shape, config="fix"))


def test_large_replicated_leaf_ok_when_declared_or_dp():
    rules = [(r"kernel", P(None, "model"))]
    ok = asp.lint_rules(PARAMS, rules, MESH42.shape, config="fix",
                        replicated_ok=(r"^embed/",))
    assert not errors(ok)
    # pure-DP (empty rulebook) replicates everything by design
    assert not errors(asp.lint_rules(PARAMS, (), MESH42.shape, config="fix"))


@pytest.mark.parametrize("opt_name", sorted(cfgs.OPTIMIZER_FAMILIES))
def test_zero1_specs_clean_for_every_optimizer_family(opt_name):
    tx = cfgs.OPTIMIZER_FAMILIES[opt_name]()
    for zero1 in (True, False):
        findings = asp.lint_opt_specs(
            tx, PARAMS, GOOD_RULES, MESH42, config="fix",
            opt_name=opt_name, zero1=zero1)
        assert not errors(findings), findings


def test_zero1_catches_bad_param_spec_propagation():
    # a duplicate-axis param spec propagates into the zero1 state specs
    rules = [(r"kernel", P("model", "model"))]
    findings = asp.lint_opt_specs(
        optax.adam(1e-3), PARAMS, rules, MESH42, config="fix")
    assert "duplicate-axis" in _checks(findings)


# ------------------------------------------------------------- jaxpr pass

def test_jaxpr_flags_collective_outside_shard_map():
    closed = jax.make_jaxpr(
        jax.vmap(lambda x: jax.lax.psum(x, "i"), axis_name="i"))(
            jnp.ones((4, 2)))
    assert "collective-outside-shard-map" in {
        f.check for f in aj.lint_jaxpr(closed, config="fix")}


def test_jaxpr_allows_collective_inside_shard_map(mesh8):
    def f(x):
        return jax.shard_map(lambda y: jax.lax.psum(y, "data"), mesh=mesh8,
                             in_specs=P("data"), out_specs=P())(x)

    closed = jax.make_jaxpr(jax.jit(f))(jnp.ones(8))
    assert not aj.lint_jaxpr(closed, config="fix")


def test_jaxpr_flags_host_callback():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct(x.shape, x.dtype),
            x)

    closed = jax.make_jaxpr(f)(jnp.ones(4))
    assert "host-callback" in {
        f.check for f in aj.lint_jaxpr(closed, config="fix")}


def test_jaxpr_flags_float64_leak():
    with jax.enable_x64():
        closed = jax.make_jaxpr(
            lambda x: x.astype(jnp.float64) * 2.0)(jnp.ones(4))
    assert "float64-leak" in {
        f.check for f in aj.lint_jaxpr(closed, config="fix")}


# --------------------------------------------------------------- hlo pass

_FAKE_HLO = """
HloModule jit_step
fused_computation {
  ROOT t = f32[8,4]{1,0} add(p0, p1)
}
ENTRY main {
  ar = f32[16,8]{1,0} all-reduce(x), replica_groups={}
  ag.1 = bf16[4,2]{1,0} all-gather(y), dimensions={0}
  start = (f32[8]{0}, f32[8]{0}) all-reduce-start(z)
  done = f32[8]{0} all-reduce-done(start)
  cp = u32[2]{0} collective-permute(w), source_target_pairs={{0,1}}
  ROOT r = f32[] constant(0)
}
"""


def test_collective_stats_counts_and_bytes():
    stats = hlo.collective_stats(_FAKE_HLO)
    # all-reduce: plain (16*8*4 B) + start (two f32[8] = 64 B); done skipped
    assert stats["all-reduce"]["count"] == 2
    assert stats["all-reduce"]["bytes"] == 16 * 8 * 4 + 2 * 8 * 4
    assert stats["all-gather"] == {"count": 1, "bytes": 4 * 2 * 2}
    assert stats["collective-permute"] == {"count": 1, "bytes": 2 * 4}
    assert stats["reduce-scatter"]["count"] == 0
    assert stats["total"]["count"] == 4


def test_budget_fence_trips_on_injected_collective():
    stats = hlo.collective_stats(_FAKE_HLO)
    golden = copy.deepcopy(stats)
    assert not hlo.check_budget(stats, golden, config="fix")
    golden["all-gather"]["count"] += 1          # a resharding crept in
    findings = hlo.check_budget(stats, golden, config="fix")
    assert "collective-count-drift" in {f.check for f in findings}


def test_injected_resharding_allgather_detected(mesh8):
    """A spec change that makes XLA move a weight shows up in the budget."""
    w = jax.ShapeDtypeStruct((16, 8), jnp.float32)

    def loss(w):
        return (w @ jnp.ones((8, 4))).sum()

    clean = jax.jit(
        loss, in_shardings=NamedSharding(mesh8, P())).lower(w).compile()
    resharded = jax.jit(
        loss, in_shardings=NamedSharding(mesh8, P("data", None))
    ).lower(w).compile()
    b_clean = hlo.comms_budget(clean)
    b_resh = hlo.comms_budget(resharded)
    assert b_clean["total"]["count"] == 0
    assert b_resh["total"]["count"] > 0
    assert hlo.check_budget(b_resh, b_clean, config="fix")


# ------------------------------------------- shipping configs + the fence

@pytest.mark.parametrize("name", sorted(cfgs.BY_NAME))
def test_shipping_config_specs_clean(name):
    assert not errors(runner.run_specs(cfgs.BY_NAME[name]))


@pytest.mark.parametrize("name", ["mnist", "bert", "gpt_pipe"])
def test_shipping_config_jaxpr_clean(name):
    assert not errors(runner.run_jaxpr(cfgs.BY_NAME[name]))


GOLDEN = runner.golden_path()
# bert_accum/bert_grad_shard ride the fast tier so the --grad_shard
# reduce-scatter swap AND its accumulator temp-bytes fence fail in tier-1
# (ISSUE 3; docs/ZERO.md). gpt_serve rides it so the SERVING decode
# graph's collectives (dtf_tpu/serve; docs/SERVING.md) are fenced in
# tier-1 too — decode is a per-token hot path, an accidental cache
# resharding there is worse than one in a train step; gpt_serve_int8
# fences the quantized-KV variant of the same graph (ISSUE 6) so the
# dequant-on-read path can't silently grow a collective either.
# gpt_eval/gpt_prefill/gpt_pages complete the whole-inventory fence
# (ISSUE 7): every AOT program in the system — eval step, serve
# admission, page cache tick — fails tier-1 on drift, not just the
# train steps and the decode view. gpt_serve_spec/gpt_serve_disagg
# (ISSUE 13) fence the speculative tick (draft_all ∘ verify) and the
# disaggregated prefill-replica admission (prefill ∘ page_save — the
# page pool as KV transport).
FAST_BUDGET_CONFIGS = ["mnist", "widedeep", "bert", "bert_accum",
                       "bert_grad_shard", "gpt_serve", "gpt_serve_int8",
                       "gpt_eval", "gpt_prefill", "gpt_pages",
                       "gpt_serve_spec", "gpt_serve_disagg"]


@pytest.mark.parametrize("name", FAST_BUDGET_CONFIGS)
def test_comms_budget_matches_golden(name):
    golden = hlo.load_golden(GOLDEN)
    assert name in golden["budgets"], (
        f"no golden for {name}; run python -m dtf_tpu.analysis "
        f"--write-golden")
    view, lowered, compiled = runner.compile_program(cfgs.BY_NAME[name])
    budget = hlo.comms_budget(compiled)
    findings = hlo.check_budget(budget, golden["budgets"][name],
                                config=name)
    # ISSUE 9: the memory pass rides the SAME tier-1 compile — the HBM
    # breakdown fence, the resident-state accounting cross-check and
    # donation soundness all fail here, not on chip
    findings += runner.run_memory(cfgs.BY_NAME[name], golden, view,
                                  lowered, compiled, budget=budget)
    assert not findings, findings
    # every fast-tier graph moves data over the mesh: the DP gradient
    # mean in the train steps and the TP row-parallel projections are
    # all-reduces; the page programs' pool gather/scatter over data
    # shards is all-gathers — a budget of zero collectives would mean
    # the fence is staring at the wrong graph
    assert budget["total"]["count"] > 0
    if name != "gpt_pages":
        assert budget["all-reduce"]["count"] > 0


@pytest.mark.slow
@pytest.mark.parametrize(
    "name", sorted(set(cfgs.BY_NAME) - set(FAST_BUDGET_CONFIGS)))
def test_comms_budget_matches_golden_slow(name):
    golden = hlo.load_golden(GOLDEN)
    view, lowered, compiled = runner.compile_program(cfgs.BY_NAME[name])
    budget = hlo.comms_budget(compiled)
    findings = hlo.check_budget(budget, golden["budgets"][name],
                                config=name)
    findings += runner.run_memory(cfgs.BY_NAME[name], golden, view,
                                  lowered, compiled, budget=budget)
    assert not findings, findings


# ------------------------------------------------- collective soundness

MESH42_REAL = None   # built lazily (needs the 8-device sim)


def _mesh42():
    global MESH42_REAL
    if MESH42_REAL is None:
        MESH42_REAL = jax.sharding.Mesh(
            np.array(jax.devices()).reshape(4, 2), ("data", "model"))
    return MESH42_REAL


def _collective_checks(fn, *args):
    from dtf_tpu.analysis import collective as col

    closed = jax.make_jaxpr(jax.jit(fn))(*args)
    return {f.check for f in col.lint_collectives(closed, config="fix")}


def test_collective_flags_mutated_perm():
    """ISSUE 7 seeded defect 1: a duplicated destination in a ppermute
    perm (nondeterministic overwrite) — the transposed-pair class the
    parity tests only catch if a test exercises that exact ring."""
    mesh = _mesh42()

    def f(x):
        def body(y):
            return jax.lax.ppermute(              # noqa: seeded defect
                y, "data", [(0, 1), (1, 2), (2, 3), (3, 1)])
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(x)

    assert _collective_checks(f, jnp.ones(8)) == {"ppermute-not-permutation"}


def test_collective_flags_dropped_psum():
    """ISSUE 7 seeded defect 3: contracting a sharded dim and escaping
    claiming replication, with no reduction — each shard returns its
    local partial sum; compiles clean, trains silently wrong."""
    mesh = _mesh42()

    def dropped(x, w):
        def body(xs, ws):
            return jnp.einsum("ik,kj->ij", xs, ws)   # k sharded: partial!
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(None, "data"), P("data", None)),
                             out_specs=P(), check_vma=False)(x, w)

    assert _collective_checks(
        dropped, jnp.ones((4, 8)), jnp.ones((8, 4))) == {
            "unreduced-partial-escape"}

    def kept(x, w):
        def body(xs, ws):
            return jax.lax.psum(jnp.einsum("ik,kj->ij", xs, ws), "data")
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(P(None, "data"), P("data", None)),
                             out_specs=P(), check_vma=False)(x, w)

    assert not _collective_checks(kept, jnp.ones((4, 8)), jnp.ones((8, 4)))


def test_collective_partial_shift_is_legal():
    """A halo-style edge shift (unique pairs, no wraparound) is NOT a
    defect — receivers of nothing get zeros by ppermute's contract."""
    from dtf_tpu.core.comms import shift_perm

    mesh = _mesh42()

    def f(x):
        def body(y):
            # distinct name: this module also hand-types seeded-defect
            # perms, and the srclint blessing is file-global (a name with
            # any non-builder assignment anywhere is tainted)
            edge = shift_perm(4)
            return jax.lax.ppermute(y, "data", edge)
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"))(x)

    assert not _collective_checks(f, jnp.ones(8))


def test_collective_flags_unknown_axis():
    """A collective bound over an axis the enclosing mesh doesn't carry
    (a vmap axis crossing into shard_map) resolves against whatever is
    in scope — never what the rulebook meant."""
    mesh = _mesh42()

    def f(x):
        def body(y):
            return jax.vmap(lambda v: jax.lax.psum(v, "v"),
                            axis_name="v")(y)
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P("data"), check_vma=False)(x)

    assert "unknown-collective-axis" in _collective_checks(
        f, jnp.ones((8, 4)))


def test_ring_soundness_flags_non_mirrored_bwd():
    """ISSUE 7 seeded defect 2: a backward ring that is neither the
    forward ring nor its inverse (here stride-2 vs stride-1), and a
    backward with no ring at all (silent blocking-collective fallback) —
    both break the mirrored-ring invariant overlap-under-grad needs."""
    from dtf_tpu.analysis import collective as col
    from dtf_tpu.ops.collective_matmul import RingOp, _ag_matmul_impl

    sds = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731

    def stride2_bwd(axis_name, res, dy):
        x, w = res
        n = jax.lax.axis_size(axis_name)
        perm = [(i, (i + 2) % n) for i in range(n)]
        moved = jax.lax.ppermute(dy, axis_name, perm)  # noqa: seeded defect
        return moved[:x.shape[0]] * 0 + x, w

    def no_ring_bwd(axis_name, res, dy):
        return res

    mk = lambda name, bwd: RingOp(                         # noqa: E731
        name, _ag_matmul_impl, bwd,
        lambda n: (sds(2, 4), sds(4, 4)),
        lambda n: ((sds(2, 4), sds(4, 4)), sds(n * 2, 4)))
    assert {f.check for f in col.ring_soundness(
        [mk("stride2", stride2_bwd)], axis_sizes=(4,))} == {
            "ring-not-mirrored"}
    assert {f.check for f in col.ring_soundness(
        [mk("noring", no_ring_bwd)], axis_sizes=(4,))} == {
            "ring-not-mirrored"}


def test_ring_soundness_shipping_rings_clean():
    """The registered collective-matmul ring pairs pass their own fence."""
    from dtf_tpu.analysis import collective as col

    assert not col.ring_soundness()


@pytest.mark.parametrize("name", ["mnist", "bert", "gpt_overlap",
                                  "gpt_serve", "gpt_prefill"])
def test_shipping_config_collectives_clean(name):
    """The clean tree stays finding-free under the soundness pass —
    including the ring-heaviest config (gpt_overlap: collective matmul
    under grad) and the serving programs."""
    assert not errors(runner.run_collective(cfgs.BY_NAME[name]))


# ------------------------------------------------- provenance + dtypes

_F8_HLO = """
ENTRY main {
  ag = f8e4m3fn[16,8]{1,0} all-gather(x), dimensions={0}, metadata={op_name="q" source_file="/w/repo/dtf_tpu/ops/q.py" source_line=12}
  ar = s4[64]{0} all-reduce(y), metadata={op_name="k" source_file="/w/repo/dtf_tpu/core/k.py" source_line=7}
  ROOT r = f32[] constant(0)
}
"""

_UNKNOWN_DTYPE_HLO = """
ENTRY main {
  ag = f6e3m2[16]{0} all-gather(x), dimensions={0}
  ROOT r = f32[] constant(0)
}
"""


def test_f8_and_s4_collectives_count_bytes():
    """ISSUE 7 satellite: fp8 and packed 4-bit collective results must
    count real bytes — 0-byte fp8 rows are a hole in the byte fence."""
    stats = hlo.collective_stats(_F8_HLO)
    assert stats["all-gather"] == {"count": 1, "bytes": 16 * 8}   # 1 B/elem
    assert stats["all-reduce"] == {"count": 1, "bytes": 64 // 2}  # 4 bits
    assert "unknown_dtypes" not in stats


def test_combined_collective_with_index_comments_counts_every_operand():
    """The all-reduce combiner merges a step's gradient reductions into
    one op with a long tuple type, which XLA prints with ``/*index=5*/``
    markers; the matcher used to stop at the marker's ``=`` and the whole
    gradient all-reduce went uncounted."""
    text = (
        "  %ar = (f32[10]{0}, f32[10,784]{1,0}, f32[], f32[], f32[4]{0}, "
        "/*index=5*/f32[16]{0}, f32[2,2]{1,0}) all-reduce(%a, %b, %c, %d, "
        "%e, /*index=5*/%f, %g), channel_id=1, to_apply=%add\n")
    want = 4 * (10 + 7840 + 1 + 1 + 4 + 16 + 4)
    assert hlo.collective_stats(text)["all-reduce"] == {
        "count": 1, "bytes": want}
    from dtf_tpu.analysis import provenance

    assert provenance.collective_provenance(text)["all-reduce"] == {
        "<unattributed>": {"count": 1, "bytes": want}}


def test_unknown_collective_dtype_is_a_finding():
    """An unrecognized non-token dtype must fail closed, not count 0 B."""
    stats = hlo.collective_stats(_UNKNOWN_DTYPE_HLO)
    assert stats["unknown_dtypes"] == ["f6e3m2"]
    findings = hlo.check_budget(stats, copy.deepcopy(stats), config="fix")
    assert {f.check for f in findings} == {"unknown-dtype"}


#: the same module as this XLA prints it: op metadata carries a
#: stack_frame_id into the tables at the head of the text
_FRAMES_HLO = """HloModule jit_f, is_scheduled=true

FileNames
1 "/w/repo/dtf_tpu/ops/q.py"
2 "/w/repo/dtf_tpu/core/k.py"

FunctionNames
1 "gather"
2 "reduce"

FileLocations
1 {file_name_id=1 function_name_id=1 line=12 end_line=12 column=4 end_column=9}
2 {file_name_id=2 function_name_id=2 line=7 end_line=7 column=4 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}


ENTRY main {
  ag = f8e4m3fn[16,8]{1,0} all-gather(x), dimensions={0}, metadata={op_name="q" stack_frame_id=1}
  ar = s4[64]{0} all-reduce(y), metadata={op_name="k" stack_frame_id=2}
  cp = f32[4]{0} collective-permute(z), metadata={op_name="p"}
  ROOT r = f32[] constant(0)
}
"""


@pytest.mark.parametrize("hlo_text", [_F8_HLO, _FRAMES_HLO],
                         ids=["inline_source_line", "stack_frame_tables"])
def test_provenance_parses_source_lines(hlo_text):
    from dtf_tpu.analysis import provenance

    prov = provenance.collective_provenance(hlo_text)
    assert prov["all-gather"] == {
        "dtf_tpu/ops/q.py:12": {"count": 1, "bytes": 128}}
    assert prov["all-reduce"] == {
        "dtf_tpu/core/k.py:7": {"count": 1, "bytes": 32}}
    if "collective-permute" in prov:   # no frame, no guess
        assert list(prov["collective-permute"]) == ["<unattributed>"]


def test_drift_finding_names_the_offending_line():
    """The whole point of provenance: a count drift names file:line, not
    just 'all-reduce 1→2'."""
    budget = hlo.collective_stats(_F8_HLO)
    from dtf_tpu.analysis import provenance

    budget["provenance"] = provenance.collective_provenance(_F8_HLO)
    golden = copy.deepcopy(budget)
    golden["all-reduce"]["count"] += 1
    golden["provenance"]["all-reduce"]["dtf_tpu/core/k.py:7"]["count"] += 1
    findings = hlo.check_budget(budget, golden, config="fix")
    drift = [f for f in findings if f.check == "collective-count-drift"]
    assert drift and "dtf_tpu/core/k.py:7" in drift[0].detail, findings


def test_provenance_delta_lines():
    from dtf_tpu.analysis import provenance

    got = {"all-reduce": {"a.py:1": {"count": 2, "bytes": 64}}}
    want = {"all-reduce": {"a.py:1": {"count": 1, "bytes": 32}},
            "all-gather": {"b.py:9": {"count": 1, "bytes": 8}}}
    lines = provenance.provenance_delta(got, want)
    assert any("a.py:1" in ln and "+1" in ln for ln in lines)
    assert any("b.py:9" in ln and "-1" in ln for ln in lines)
    assert not provenance.provenance_delta(want, copy.deepcopy(want))


# ------------------------------------------------------------ CLI + lint

def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    env["_DTF_TPU_ANALYSIS_REEXEC"] = "1"   # already pinned by this env
    return env


def test_cli_smoke_json_line():
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "--configs=mnist",
         "--passes=specs,jaxpr"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=300)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert out["ok"] is True and out["findings"] == 0


def test_cli_unknown_config_is_structured_error():
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "--configs=nope"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=120)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 2 and out["ok"] is False


@pytest.mark.slow
def test_cli_full_run_zero_findings():
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=1500)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, (proc.stderr[-2000:], out)
    assert out["ok"] is True and out["findings"] == 0, out


def test_srclint_fences_direct_collectives_in_models(tmp_path):
    """ISSUE 2 satellite: models/ must route TP collectives through
    core.comms — a direct jax.lax.all_gather/psum_scatter there escapes
    both the comms-budget fence choke point and the --tp_overlap
    dispatch. Outside models/ (ops/, core/) the same call is fine."""
    from dtf_tpu.analysis import srclint

    mdir = tmp_path / "models"
    mdir.mkdir()
    bad = mdir / "bad.py"
    bad.write_text(
        "import jax\nfrom jax import lax\n\n"
        "def f(x):\n"
        "    y = jax.lax.all_gather(x, 'model')\n"
        "    return lax.psum_scatter(y, 'model')\n")
    probs = srclint.lint_file(str(bad))
    assert sum("core.comms" in p for p in probs) == 2, probs

    ok = mdir / "ok.py"   # comms routing + noqa'd call are both exempt
    ok.write_text(
        "import jax\nfrom dtf_tpu.core import comms\n\n"
        "def f(x):\n"
        "    x = comms.all_gather(x, 'model')\n"
        "    return jax.lax.all_gather(x, 'model')  # noqa: fence\n")
    assert not srclint.lint_file(str(ok))

    outside = tmp_path / "ops.py"  # not models/: direct lax is the point
    outside.write_text(
        "import jax\n\ndef f(x):\n"
        "    return jax.lax.all_gather(x, 'seq')\n")
    assert not srclint.lint_file(str(outside))

    # the shipping models tree itself must be clean under the new rule
    models_dir = os.path.join(ROOT, "dtf_tpu", "models")
    probs = []
    for f in sorted(os.listdir(models_dir)):
        if f.endswith(".py"):
            probs += [p for p in srclint.lint_file(
                os.path.join(models_dir, f)) if "core.comms" in p]
    assert not probs, probs


def test_srclint_fences_backend_imports_in_telemetry(tmp_path):
    """ISSUE 8 satellite: dtf_tpu/telemetry/ must import without a
    backend — module-level jax/tensorflow imports there are findings
    (the loop.py lazy-import idiom); lazy in-function imports and an
    explicit noqa are the sanctioned spellings. The shipping telemetry
    package itself must be clean under the rule."""
    from dtf_tpu.analysis import srclint

    tdir = tmp_path / "dtf_tpu" / "telemetry"
    tdir.mkdir(parents=True)
    bad = tdir / "bad.py"
    bad.write_text(
        "import jax\n"
        "from tensorflow.tsl.profiler.protobuf import xplane_pb2\n\n"
        "def f():\n"
        "    return jax.devices(), xplane_pb2\n")
    probs = srclint.lint_file(str(bad))
    assert sum("without a backend" in p for p in probs) == 2, probs

    wrapped = tdir / "wrapped.py"   # try-wrapping still runs on import
    wrapped.write_text(
        "try:\n"
        "    import tensorflow\n"
        "except ImportError:\n"
        "    tensorflow = None\n"
        "if True:\n"
        "    import jax\n"
        "X = (jax, tensorflow)\n")
    probs = srclint.lint_file(str(wrapped))
    assert sum("without a backend" in p for p in probs) == 2, probs

    ok = tdir / "ok.py"   # lazy import + noqa'd module import both pass
    ok.write_text(
        "import jaxtyping_not_a_backend as jt  # unrelated root\n\n"
        "def f():\n"
        "    import jax\n\n"
        "    return jax.devices(), jt\n")
    assert not srclint.lint_file(str(ok))
    noqa = tdir / "noqa.py"
    noqa.write_text("import jax  # noqa: deliberate\nX = jax\n")
    assert not srclint.lint_file(str(noqa))

    outside = tmp_path / "dtf_tpu" / "other.py"   # rule scoped to telemetry/
    outside.write_text("import jax\nY = jax\n")
    assert not srclint.lint_file(str(outside))

    # the shipping telemetry package stays clean — xplane/profile/trace
    # parse traces on chipless machines and must keep importing that way
    tel_dir = os.path.join(ROOT, "dtf_tpu", "telemetry")
    probs = []
    for f in sorted(os.listdir(tel_dir)):
        if f.endswith(".py"):
            probs += [p for p in srclint.lint_file(
                os.path.join(tel_dir, f)) if "without a backend" in p]
    assert not probs, probs


def test_srclint_fences_backend_imports_in_fault(tmp_path):
    """ISSUE 11 satellite: dtf_tpu/fault/ is fenced like telemetry/ and
    tune/ — the run controller supervises a possibly-wedged backend from
    a clean process and must never import what it has to outlive. Lazy
    in-function imports pass; the shipping fault package must be clean."""
    from dtf_tpu.analysis import srclint

    fdir = tmp_path / "dtf_tpu" / "fault"
    fdir.mkdir(parents=True)
    bad = fdir / "bad.py"
    bad.write_text("import jax\n\ndef f():\n    return jax.devices()\n")
    probs = srclint.lint_file(str(bad))
    assert sum("without a backend" in p for p in probs) == 1, probs
    assert "dtf_tpu/fault/" in probs[0]

    ok = fdir / "ok.py"
    ok.write_text("def f():\n    import jax\n\n    return jax.devices()\n")
    assert not srclint.lint_file(str(ok))

    fault_dir = os.path.join(ROOT, "dtf_tpu", "fault")
    probs = []
    for f in sorted(os.listdir(fault_dir)):
        if f.endswith(".py"):
            probs += [p for p in srclint.lint_file(
                os.path.join(fault_dir, f)) if "without a backend" in p]
    assert not probs, probs


def test_srclint_fences_backend_imports_in_stream(tmp_path):
    """ISSUE 15 satellite: dtf_tpu/data/stream/ is fenced like fault/ and
    tune/ — the mixture stream is pure host IO whose producer thread must
    run with no backend present. Lazy in-function imports
    pass; the shipping stream package must be clean."""
    from dtf_tpu.analysis import srclint

    sdir = tmp_path / "dtf_tpu" / "data" / "stream"
    sdir.mkdir(parents=True)
    bad = sdir / "bad.py"
    bad.write_text("import jax\n\ndef f():\n    return jax.devices()\n")
    probs = srclint.lint_file(str(bad))
    assert sum("without a backend" in p for p in probs) == 1, probs
    assert "dtf_tpu/stream/" in probs[0]

    ok = sdir / "ok.py"
    ok.write_text("def f():\n    import jax\n\n    return jax.devices()\n")
    assert not srclint.lint_file(str(ok))

    stream_dir = os.path.join(ROOT, "dtf_tpu", "data", "stream")
    probs = []
    for f in sorted(os.listdir(stream_dir)):
        if f.endswith(".py"):
            probs += [p for p in srclint.lint_file(
                os.path.join(stream_dir, f)) if "without a backend" in p]
    assert not probs, probs


def test_stream_package_imports_without_backend(tmp_path,
                                                cpu_sim_subprocess_env):
    """Dynamic twin of the stream fence: build a mixture over two token
    corpora, run it through the background producer, and checkpoint-shape
    its state — in a child whose jax/jaxlib/tensorflow imports are
    POISONED. The data tier must be drivable (and benchable) on a machine
    with no backend at all."""
    import subprocess
    import sys as _sys

    poison = tmp_path / "poison"
    for mod in ("jax", "tensorflow", "jaxlib"):
        d = poison / mod
        d.mkdir(parents=True)
        (d / "__init__.py").write_text(
            "raise ImportError('no backend on this machine')\n")
    env = dict(cpu_sim_subprocess_env)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{ROOT}"
    code = (
        "import numpy as np, os\n"
        "r = np.random.default_rng(0)\n"
        "for n in ('a', 'b'):\n"
        "    r.integers(0, 97, 4000).astype(np.uint16).tofile(n + '.bin')\n"
        "from dtf_tpu.data.stream import MixtureStream, TokenBinSource\n"
        "srcs = [TokenBinSource(n + '.bin', 16, vocab_size=97, salt=i,\n"
        "                       name=n) for i, n in enumerate('ab')]\n"
        "st = MixtureStream(srcs, {'a': 0.7, 'b': 0.3}, 8, seed=1,\n"
        "                   producer_depth=2)\n"
        "it = iter(st)\n"
        "bs = [next(it) for _ in range(4)]\n"
        "st.close()\n"
        "assert bs[0]['input_ids'].shape == (8, 16)\n"
        "assert st.state_at(2)['next_step'] == 2\n"
        "from dtf_tpu.fault.inject import StreamFaultPlan\n"
        "assert StreamFaultPlan.parse('stall_source@3').kind == "
        "'stall_source'\n"
        "print('NO_BACKEND_OK')\n")
    proc = subprocess.run([_sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert "NO_BACKEND_OK" in proc.stdout, (proc.stdout, proc.stderr)


def test_fault_package_imports_without_backend(tmp_path,
                                               cpu_sim_subprocess_env):
    """Dynamic twin: the controller imports and classifies in a child
    whose jax/jaxlib/tensorflow imports are poisoned — the chief process
    supervising a wedged backend must not be hangable by an import."""
    import subprocess
    import sys as _sys

    poison = tmp_path / "poison"
    for mod in ("jax", "tensorflow", "jaxlib"):
        d = poison / mod
        d.mkdir(parents=True)
        (d / "__init__.py").write_text(
            "raise ImportError('no backend on this machine')\n")
    env = dict(cpu_sim_subprocess_env)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{ROOT}"
    code = (
        "from dtf_tpu.fault import (ControllerConfig, ControllerPolicy,\n"
        "                           HostObservation, FaultPlan)\n"
        "p = ControllerPolicy()\n"
        "d = p.classify([HostObservation(0, False, 137, None)],\n"
        "               config=ControllerConfig(), since_launch_s=1)\n"
        "assert d.kind == 'host_lost', d\n"
        "assert FaultPlan.parse('kill@3').kind == 'kill'\n"
        "print('NO_BACKEND_OK')\n")
    proc = subprocess.run([_sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert "NO_BACKEND_OK" in proc.stdout, (proc.stdout, proc.stderr)


def test_telemetry_package_imports_without_jax_or_tf(
        tmp_path, cpu_sim_subprocess_env):
    """The dynamic twin of the srclint fence: the parser modules import
    (and tolerantly degrade) in a child whose jax/tensorflow imports are
    POISONED — the report path must work on a machine with no backend."""
    import subprocess
    import sys as _sys

    poison = tmp_path / "poison"
    for mod in ("jax", "tensorflow", "jaxlib"):
        d = poison / mod
        d.mkdir(parents=True)
        (d / "__init__.py").write_text(
            "raise ImportError('no backend on this machine')\n")
    env = dict(cpu_sim_subprocess_env)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{ROOT}"
    code = (
        "from dtf_tpu.telemetry import xplane, profile, trace\n"
        "ok, reason = xplane.xplane_available()\n"
        "assert not ok and 'xplane_pb2' in reason, (ok, reason)\n"
        "rep = profile.parse_logdir('/nonexistent')\n"
        "assert 'degraded' in rep, rep\n"
        "print('NO_BACKEND_OK')\n")
    proc = subprocess.run([_sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert "NO_BACKEND_OK" in proc.stdout, (proc.stdout, proc.stderr)


def test_srclint_fences_raw_ppermute_perms(tmp_path):
    """ISSUE 7 satellite: a ppermute perm outside core/comms.py /
    ops/collective_matmul.py must be a name bound from
    ring_perm/shift_perm — the named builders the soundness pass
    introspects. Raw pair lists (inline or hand-assembled) are findings;
    the two ring modules themselves are exempt (they ARE the builders)."""
    from dtf_tpu.analysis import srclint

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n"
        "def f(x, n):\n"
        "    perm = [(i, (i + 1) % n) for i in range(n)]\n"
        "    y = jax.lax.ppermute(x, 'seq', perm)\n"
        "    return jax.lax.ppermute(y, 'seq', [(0, 1), (1, 0)])\n")
    probs = srclint.lint_file(str(bad))
    assert sum("ring_perm" in p for p in probs) == 2, probs

    ok = tmp_path / "ok.py"
    ok.write_text(
        "import jax\n"
        "from dtf_tpu.core.comms import ring_perm, shift_perm\n\n"
        "def f(x, n):\n"
        "    perm = ring_perm(n)\n"
        "    x = jax.lax.ppermute(x, 'seq', perm)\n"
        "    x = jax.lax.ppermute(x, 'seq', shift_perm(n))\n"
        "    halo = shift_perm(n, shift=-1)\n"
        "    return jax.lax.ppermute(x, 'seq', halo)\n")
    assert not srclint.lint_file(str(ok))

    # the two ring modules themselves stay exempt, and the shipping tree
    # (attention/pipeline now routed through the builders) is clean
    root_files = [os.path.join(ROOT, "dtf_tpu", "ops", "attention.py"),
                  os.path.join(ROOT, "dtf_tpu", "parallel", "pipeline.py"),
                  os.path.join(ROOT, "dtf_tpu", "core", "comms.py"),
                  os.path.join(ROOT, "dtf_tpu", "ops",
                               "collective_matmul.py")]
    for f in root_files:
        assert not [p for p in srclint.lint_file(f) if "ring_perm" in p], f


def test_cli_diff_mode_smoke():
    """--diff prints per-line provenance deltas (0 on a clean tree) and
    keeps the one-JSON-last-line contract."""
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "--configs=mnist",
         "--diff"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=600)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert out["mode"] == "diff" and out["changed_lines"] == {"mnist": 0}


def test_cli_exits_nonzero_on_error_finding(tmp_path):
    """ISSUE 7 satellite: the CLI is a usable pre-commit gate — any
    error finding (here: a doctored golden) must exit 1, not 0."""
    golden = hlo.load_golden(GOLDEN)
    doctored = {"_meta": golden["_meta"],
                "budgets": {"mnist": copy.deepcopy(
                    golden["budgets"]["mnist"])}}
    doctored["budgets"]["mnist"]["all-reduce"]["count"] += 1
    gpath = tmp_path / "golden.json"
    gpath.write_text(json.dumps(doctored))
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "--configs=mnist",
         "--passes=hlo", f"--golden={gpath}"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=600)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 1 and out["ok"] is False
    assert any(d["check"] == "collective-count-drift"
               for d in out["details"])


def test_cli_reports_comms_delta():
    """The analysis JSON line carries per-config collective-bytes deltas
    vs golden (a PR's comms cost at a glance; 0 on a clean fence)."""
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "--configs=mnist",
         "--passes=hlo"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=600)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert out["comms_delta_bytes"] == {"mnist": 0}


def test_lint_script_clean():
    proc = subprocess.run(
        ["bash", os.path.join(ROOT, "scripts", "lint.sh")],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-500:]


def test_every_registered_rulebook_is_analyzed(mesh8):
    """models.rulebooks() is the registration point; every non-empty
    rulebook there must be exercised by at least one registry config (a
    new model's rules must not silently escape analysis)."""
    from dtf_tpu.models import rulebooks

    analyzed = set()
    for c in cfgs.REGISTRY:
        view = c.spec_view(c.mesh())
        analyzed.update(pat for pat, _ in view.rules)
    for name, rules in rulebooks().items():
        missing = [pat for pat, _ in rules if pat not in analyzed]
        assert not missing, (name, missing)
