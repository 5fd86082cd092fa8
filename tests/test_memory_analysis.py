"""The memory pass (dtf_tpu/analysis/memory.py): breakdown fence,
resident-state accounting, donation soundness, and the HBM fit
planner — seeded defects must each produce exactly their
finding class, the shipping tree must be finding-free."""

import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from dtf_tpu.analysis import configs as cfgs
from dtf_tpu.analysis import hlo
from dtf_tpu.analysis import memory as mem
from dtf_tpu.analysis import runner
from dtf_tpu.analysis.findings import errors

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _checks(findings):
    return {f.check for f in findings}


# ----------------------------------------------------------- pricing math

def test_leaf_device_bytes_replicated_and_sharded(mesh8):
    # replicated: full extent on every device
    assert mem.leaf_device_bytes((16, 8), jnp.float32) == 16 * 8 * 4
    sh = NamedSharding(mesh8, P("data", None))
    assert mem.leaf_device_bytes((16, 8), jnp.float32, sh) == 2 * 8 * 4
    # ragged shard: ceil-div (XLA pads up), 10/8 -> 2 rows per device
    assert mem.leaf_device_bytes((10,), jnp.int8, NamedSharding(
        mesh8, P("data"))) == 2


def test_leaf_device_bytes_multi_axis_tuple(mesh_4x2):
    sh = NamedSharding(mesh_4x2, P(("data", "model"), None))
    assert mem.leaf_device_bytes((16, 4), jnp.float32, sh) == 2 * 4 * 4


def test_affine_temp_model_exact_on_linear_points():
    model = mem.affine_temp_model({2: 300, 4: 500})
    assert mem.predict_temp(model, 8) == 900
    assert mem.predict_temp(model, 2) == 300


# ------------------------------------------------------- breakdown fence

def test_fmt_bytes_spelling():
    assert mem.fmt_bytes(453 * 1024) == "453K"
    assert mem.fmt_bytes(1536 * 1024) == "1.5M"
    assert mem.fmt_bytes(512) == "512"


def test_check_memory_clean_and_per_field_drift():
    got = {"temp_bytes": 453 * 1024, "arg_bytes": 100, "out_bytes": 50,
           "alias_bytes": 0, "gen_code_bytes": 0}
    assert not mem.check_memory(got, dict(got), config="fix")
    want = dict(got, temp_bytes=536 * 1024)
    findings = mem.check_memory(got, want, config="fix")
    assert _checks(findings) == {"memory-bytes-drift"}
    # the drift finding names the field AND the humanized delta
    assert "temp_bytes 536K→453K" in findings[0].detail


def test_check_memory_fails_closed_when_unavailable():
    findings = mem.check_memory(None, {"temp_bytes": 1}, config="fix")
    assert _checks(findings) == {"memory-unavailable"}
    # no golden memory yet -> nothing to fence (write-golden first)
    assert not mem.check_memory({"temp_bytes": 1}, None, config="fix")


def test_memory_delta_lines():
    lines = mem.memory_delta({"temp_bytes": 453 * 1024},
                             {"temp_bytes": 536 * 1024, "arg_bytes": 4})
    assert any("temp_bytes 536K→453K" in ln for ln in lines)
    assert any("arg_bytes" in ln for ln in lines)
    assert not mem.memory_delta({"temp_bytes": 1}, {"temp_bytes": 1})


def test_golden_records_full_memory_breakdown_for_every_config():
    """The regenerated golden carries all fenced fields per budget."""
    golden = hlo.load_golden(runner.golden_path())
    want = {name for name, _ in mem.MEMORY_FIELDS}
    for name, budget in golden["budgets"].items():
        assert set(budget.get("memory", {})) == want, name


# --------------------------------------------------- donation soundness

def _donated_lowered(aliasable: bool):
    """A program donating arg 0 — USED either way (a pruned donated arg
    is rightly skipped); ``aliasable=False`` gives it a shape no output
    matches, so XLA silently drops the donation."""
    y = jax.ShapeDtypeStruct((4,), jnp.float32)
    if aliasable:
        x = jax.ShapeDtypeStruct((4,), jnp.float32)
        fn = lambda x, y: x + y                      # noqa: E731
    else:
        x = jax.ShapeDtypeStruct((7, 3), jnp.float32)
        fn = lambda x, y: y * 2.0 + x.sum()          # noqa: E731
    low = jax.jit(fn, donate_argnums=(0,)).lower(x, y)
    return low, low.compile()


def test_seeded_dropped_donation_is_exactly_its_finding():
    low, comp = _donated_lowered(aliasable=False)
    findings = mem.donation_soundness("fix", low, comp)
    assert _checks(findings) == {"dropped-donation"}


def test_aliased_donation_is_clean():
    low, comp = _donated_lowered(aliasable=True)
    assert comp.as_text().count("input_output_alias") == 1
    assert not mem.donation_soundness("fix", low, comp)


def test_aliased_param_numbers_parses_header():
    hdr = ("HloModule jit_f, is_scheduled=true, input_output_alias={ "
           "{0}: (0, {}, may-alias), {1}: (2, {}, may-alias) }, "
           "entry_computation_layout={(f32[4]{0})->f32[4]{0}}\nbody")
    assert mem.aliased_param_numbers(hdr) == {0, 2}
    assert mem.aliased_param_numbers("HloModule jit_f\nbody") == set()


@pytest.mark.parametrize("name", ["decode", "prefill", "load"])
def test_serve_programs_donate_their_state_soundly(name):
    """The serve engine's programs donate their state (the in-place KV
    cache, PR 25). On the ``gpt_serve`` mesh, with the engine's output
    pins, every donated leaf must be aliased: a dropped one would be
    deleted at dispatch with nowhere to go. (The analysis VIEWS of these
    programs compile unpinned and so undonated — engine.decode_step_view
    — which is why the real table is checked here.)"""
    from dtf_tpu.models import gpt
    from dtf_tpu.serve import engine, pages

    mesh = cfgs.BY_NAME["gpt_serve"].mesh()
    programs, _ = engine.program_table(gpt.GPTConfig.tiny(), n_slots=8,
                                       max_len=64, mesh=mesh)
    if name == "load":
        state = programs["decode"].abstract_args[1]
        pool = pages.pool_abstract(state["cache"], 4, 16, mesh)
        programs = engine.page_program_table(
            state, pool, n_pages=4, max_len=64, kv_page_size=16, mesh=mesh)
    low = programs[name].lower()
    assert any(mem.donated_flags(low))
    assert not mem.donation_soundness(name, low, low.compile())


# ------------------------------------------------ state accounting model

def test_resident_model_matches_compiled_arguments_exactly():
    """The analytic model prices mnist's (state, batch) to the byte of
    what the executable allocates — the cross-check's clean baseline."""
    view, lowered, compiled = runner.compile_program(cfgs.BY_NAME["mnist"])
    rb = mem.resident_bytes(view)
    got = compiled.memory_analysis().argument_size_in_bytes
    assert rb["total_bytes"] == int(got)
    assert not mem.state_accounting("mnist", view, compiled)


def test_seeded_dtype_mutated_leaf_is_exactly_its_finding():
    """A state leaf whose declared dtype silently halves (f32 -> bf16 in
    the introspected model but not the program) must drift."""
    view, lowered, compiled = runner.compile_program(cfgs.BY_NAME["mnist"])

    def shrink(x):
        if x.dtype == jnp.float32 and int(np.prod(x.shape)) > 1024:
            return jax.ShapeDtypeStruct(x.shape, jnp.bfloat16)
        return x

    tampered = dataclasses.replace(
        view, state=jax.tree.map(shrink, view.state))
    findings = mem.state_accounting("mnist", tampered, compiled)
    assert _checks(findings) == {"state-accounting-drift"}


def test_replication_change_names_the_leaf(mesh8):
    """A leaf the executable committed REPLICATED while the model
    declares it data-sharded is named path-and-spec in the finding."""
    sh = NamedSharding(mesh8, P("data", None))
    rep = NamedSharding(mesh8, P())

    def f(state, batch):
        return state["w"].sum() + batch.sum()

    w = jax.ShapeDtypeStruct((16, 8), jnp.float32, sharding=rep)
    b = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=rep)
    compiled = jax.jit(f).lower({"w": w}, b).compile()
    declared = cfgs.StepView(
        step=None,
        state={"w": jax.ShapeDtypeStruct((16, 8), jnp.float32,
                                         sharding=sh)},
        batch=jax.ShapeDtypeStruct((8,), jnp.float32, sharding=rep))
    findings = mem.state_accounting("fix", declared, compiled)
    assert "state-accounting-drift" in _checks(findings)
    assert any("w" in f.detail and "replication" in f.detail
               for f in findings)


@pytest.mark.parametrize("name", ["gpt_serve", "gpt_serve_int8", "bert"])
def test_shipping_configs_memory_pass_clean(name):
    """Shipped tree finding-free under the whole memory pass (golden
    fence + accounting + donation) — rides the warm compile cache."""
    golden = hlo.load_golden(runner.golden_path())
    findings = runner.run_memory(cfgs.BY_NAME[name], golden)
    assert not errors(findings), findings


# ------------------------------------------------------- the fit planner

def test_fit_serve_reports_bf16_and_int8_slots():
    out = mem.fit("gpt_serve", hbm_gb=16, max_len=1024, kv_page_size=64,
                  slots=64, log_sink=True)
    assert out["kind"] == "serve"
    # the serve-log sink (ISSUE 19) is host-side file IO: the fit row is
    # an explicit HBM no-op, and train configs reject the flag outright
    assert out["log_sink"] == {"hbm_delta_bytes": 0,
                               "host_side_only": True}
    with pytest.raises(ValueError, match="serve config"):
        mem.fit("mnist", hbm_gb=1, log_sink=True)
    bf16, int8 = out["kv"]["bf16"], out["kv"]["int8"]
    assert bf16["max_slots"] > 0
    # int8 KV halves cache bytes (scales add ~1/d_head back): strictly
    # more slots per HBM byte, short of a full 2x
    assert bf16["max_slots"] < int8["max_slots"] <= 2 * bf16["max_slots"]
    assert int8["kv_bytes_per_slot_per_device"] < \
        bf16["kv_bytes_per_slot_per_device"]
    # page bytes scale with page_size/max_len — times the data-axis size
    # (4): slots shard over 'data', pool pages replicate across it
    assert bf16["page_bytes_per_device"] == pytest.approx(
        bf16["kv_bytes_per_slot_per_device"] * 64 / 1024 * 4, rel=0.05)
    assert bf16["max_pages_at_slots"] > 0
    # slots shard evenly over the data axis
    assert bf16["max_slots"] % 4 == 0


def test_fit_train_inverts_the_temp_model():
    out = mem.fit("mnist", hbm_gb=1)
    assert out["kind"] == "train" and out["scale"] == "program"
    assert out["opt"] == "sgd"
    assert out["max_global_batch"] > 0
    # the answer is consistent with the model it reports
    tm = out["temp_model"]
    used = (out["resident_bytes_per_device"]["total_bytes"]
            + tm["intercept_bytes"]
            + out["max_global_batch"] * tm["bytes_per_batch_row"])
    assert used <= (1 << 30)
    assert out["max_global_batch"] % 8 == 0   # data-axis grain


def _env():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    env["_DTF_TPU_ANALYSIS_REEXEC"] = "1"
    return env


def test_fit_cli_one_json_line():
    """The acceptance-criteria invocation: one JSON line, max slots for
    bf16 AND int8 KV."""
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "fit",
         "--config=gpt_serve", "--hbm-gb=16"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=300)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert out["ok"] is True
    assert out["kv"]["bf16"]["max_slots"] > 0
    assert out["kv"]["int8"]["max_slots"] > out["kv"]["bf16"]["max_slots"]


def test_fit_cli_unknown_config_is_structured_error():
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "fit",
         "--config=nope", "--hbm-gb=16"],
        env=_env(), cwd=ROOT, capture_output=True, text=True, timeout=120)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 2 and out["ok"] is False


def test_memory_pass_registered():
    assert "memory" in runner.ALL_PASSES
