"""Fenced program executor (ISSUE 18): the one place AOT programs are born.

Units on dtf_tpu/core/executor.py (trace fence, bare-operand lowering,
AOT compile, donation gate, table registration), migration regressions
(make_train_step / make_eval_step return registered Programs whose trace
fence pins at 1 in steady state), and the srclint ``raw-aot-compile``
fence that makes the choke point structural.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dtf_tpu.core import executor
from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import shard_batch
from dtf_tpu.core.mesh import MeshConfig, make_mesh

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# units
# ---------------------------------------------------------------------------

def test_fenced_counts_per_trace_not_per_call():
    counts = {}
    f = jax.jit(executor.fenced("p", lambda x: x * 2, counts))
    assert counts == {"p": 0}          # registered at build time
    f(jnp.ones((4,)))
    f(jnp.ones((4,)))                  # same shape: cached, no retrace
    assert counts["p"] == 1
    f(jnp.ones((8,)))                  # new shape: one retrace
    assert counts["p"] == 2
    # counts=None is the no-op wrapper (the body itself comes back)
    body = lambda x: x
    assert executor.fenced("q", body, None) is body


def test_donation_argnums_follow_the_caller():
    assert executor.donation_argnums(True) == (0,)
    assert executor.donation_argnums(False) == ()
    assert executor.donation_argnums(True, (0, 1)) == (0, 1)


def test_program_bare_lower_uses_registered_abstracts():
    abs_x = jax.ShapeDtypeStruct((4,), jnp.float32)
    prog = executor.program("double", lambda x: x * 2,
                            abstract_args=(abs_x,))
    lowered = prog.lower()             # no operands: the registered ones
    compiled = lowered.compile()
    np.testing.assert_array_equal(
        np.asarray(compiled(jnp.ones((4,)))), 2 * np.ones((4,)))
    # without a registration, bare lower() is an error, not a guess
    bare = executor.program("nope", lambda x: x)
    with pytest.raises(ValueError, match="abstract_args"):
        bare.lower()


def test_program_aot_pins_compiled_and_rejects_reshapes():
    counts = {}
    abs_x = jax.ShapeDtypeStruct((4,), jnp.float32)
    prog = executor.program("p", lambda x: x + 1, counts=counts,
                            abstract_args=(abs_x,))
    assert prog.compiled is None
    exe = prog.aot()
    assert prog.compiled is exe
    assert counts["p"] == 1            # AOT traced the fenced body once
    np.testing.assert_array_equal(np.asarray(exe(jnp.zeros((4,)))),
                                  np.ones((4,)))
    # the executable rejects a reshaped operand instead of retracing
    with pytest.raises(Exception):
        exe(jnp.zeros((8,)))
    assert counts["p"] == 1


def test_program_delegates_jit_surface_and_registers_in_table():
    table = {}
    prog = executor.program("f", lambda x: x * 3, table=table)
    assert table == {"f": prog}
    assert repr(prog) == "Program('f')"
    # __call__ and the jit API surface both reach the wrapped jit
    np.testing.assert_array_equal(np.asarray(prog(jnp.ones((2,)))),
                                  3 * np.ones((2,)))
    assert prog.eval_shape(jax.ShapeDtypeStruct((2,), jnp.float32)).shape \
        == (2,)


# ---------------------------------------------------------------------------
# migration regressions: the trainer programs ride the executor
# ---------------------------------------------------------------------------

def _tiny_trainer(mesh):
    def init_fn(rng):
        return {"params": {"w": jnp.zeros((4,), jnp.float32)}}

    def loss_fn(params, extra, batch, rng):
        loss = jnp.mean((batch["x"] @ params["w"]) ** 2)
        return loss, tr.LossAux(extra=extra)

    tx = optax.sgd(0.1)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh)
    return init_fn, loss_fn, tx, state, shardings


def test_train_step_is_a_registered_fenced_program():
    from dtf_tpu.telemetry.fence import CompileFence

    mesh = make_mesh(MeshConfig(data=8))
    _, loss_fn, tx, state, shardings = _tiny_trainer(mesh)
    fence = CompileFence()
    step = tr.make_train_step(loss_fn, tx, mesh, shardings,
                              telemetry=fence)
    assert isinstance(step, executor.Program)
    assert step.name == "train_step"
    # the analysis StepView.of reads this instead of re-spelling the pins
    assert step.arg_shardings is not None
    batch = {"x": np.ones((8, 4), np.float32)}
    state, _ = step(state, shard_batch(batch, mesh))
    state, _ = step(state, shard_batch(batch, mesh))
    jax.block_until_ready(state.params)
    assert fence.trace_counts["train_step"] == 1   # steady state: no retrace


def test_eval_step_is_a_registered_program():
    from dtf_tpu.telemetry.fence import CompileFence

    mesh = make_mesh(MeshConfig(data=8))
    _, _, tx, state, shardings = _tiny_trainer(mesh)

    def eval_fn(params, extra, batch):
        return {"eval_loss": jnp.mean(batch["x"] @ params["w"])}

    fence = CompileFence()
    step = tr.make_eval_step(eval_fn, mesh, shardings, telemetry=fence)
    assert isinstance(step, executor.Program)
    batch = {"x": np.ones((8, 4), np.float32)}
    m1 = step(state, shard_batch(batch, mesh))
    m2 = step(state, shard_batch(batch, mesh))
    assert np.isfinite(float(m1["eval_loss"]))
    assert float(m1["eval_loss"]) == float(m2["eval_loss"])
    assert fence.trace_counts["eval_step"] == 1


def test_serve_program_table_registers_fenced_programs():
    """The serve tier's program table is built once and shared by the
    engine AND the analysis step views — each entry is a Program with
    registered abstracts (so the analyzer lowers the exact served
    graph), and the table registers under the engine's fence names."""
    import dataclasses

    from dtf_tpu.models import gpt
    from dtf_tpu.serve.engine import program_table

    cfg = dataclasses.replace(gpt.GPTConfig.tiny(dtype=jnp.float32),
                              decode_len=8)
    mesh = make_mesh(MeshConfig(data=8))
    programs, _ = program_table(cfg, n_slots=2, max_len=16, mesh=mesh)
    assert set(programs) >= {"prefill", "decode"}
    for name, prog in programs.items():
        assert isinstance(prog, executor.Program), name
        assert prog.abstract_args is not None, name


# ---------------------------------------------------------------------------
# the srclint raw-aot-compile fence
# ---------------------------------------------------------------------------

def test_srclint_fences_raw_aot_compiles(tmp_path):
    from dtf_tpu.analysis import srclint

    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\n\n"
        "def f(g, x):\n"
        "    lowered = jax.jit(g).lower(x)\n"
        "    return lowered.compile()\n")
    probs = srclint.lint_file(str(bad))
    assert sum("AOT idiom" in p for p in probs) == 2, probs

    ok = tmp_path / "ok.py"   # pinned sites + the skip cases are exempt
    ok.write_text(
        "import re\nimport jax\n\n"
        "def f(g, x, s):\n"
        "    exe = jax.jit(g).lower(x).compile()  # aot-ok: bench leg\n"
        "    pat = re.compile('x')\n"
        "    return exe, pat, s.lower()\n")
    assert not [p for p in srclint.lint_file(str(ok)) if "AOT idiom" in p]

    # the pin covers its line AND the next — the two-line idiom
    two = tmp_path / "two.py"
    two.write_text(
        "import jax\n\n"
        "def f(g, x):\n"
        "    # aot-ok: measured sweep\n"
        "    return jax.jit(g).lower(x).compile()\n")
    assert not [p for p in srclint.lint_file(str(two)) if "AOT idiom" in p]

    # blessed homes: core/executor.py, tune/ (which has its own backend-
    # import fence — only the AOT findings are in scope here), tests
    for sub, name in (("core", "executor.py"), ("tune", "sweep.py")):
        d = tmp_path / sub
        d.mkdir(exist_ok=True)
        f = d / name
        f.write_text("import jax\n\ndef f(g, x):\n"
                     "    return jax.jit(g).lower(x).compile()\n")
        assert not [p for p in srclint.lint_file(str(f))
                    if "AOT idiom" in p], (sub, name)
    t = tmp_path / "test_thing.py"
    t.write_text("import jax\n\ndef f(g, x):\n"
                 "    return jax.jit(g).lower(x).compile()\n")
    assert not [p for p in srclint.lint_file(str(t)) if "AOT idiom" in p]


@pytest.mark.slow
def test_shipped_tree_has_no_raw_aot_sites():
    """Every raw lower/compile in the shipping tree is either in a
    blessed home or carries an ``# aot-ok: <why>`` pin — the executor is
    the choke point by construction, not convention."""
    from dtf_tpu.analysis import srclint

    paths = [os.path.join(ROOT, "dtf_tpu"), os.path.join(ROOT, "scripts"),
             os.path.join(ROOT, "bench.py"),
             os.path.join(ROOT, "__graft_entry__.py")]
    probs = []
    for f in srclint._py_files(paths):
        probs += [p for p in srclint.lint_file(f) if "AOT idiom" in p]
    assert not probs, probs
