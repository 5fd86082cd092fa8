"""Minimal source linter — the ``scripts/lint.sh`` fallback when pyflakes
is not installed (the container policy is no new deps; see ISSUE/PR notes).

Pyflakes-grade checks that matter for this codebase, AST-only (no
imports executed):

- syntax errors (files that won't even parse),
- unused imports (module scope; ``# noqa`` and ``__init__.py`` re-exports
  honored),
- duplicate top-level definitions (a copy-pasted ``def test_x`` silently
  shadowing the first is a real way to lose a test),
- ``import *`` (kills static analysis),
- ``except:`` bare handlers (swallow KeyboardInterrupt in launch loops),
- direct ``jax.lax.all_gather``/``psum_scatter`` calls in ``models/`` —
  model code must route TP collectives through ``dtf_tpu.core.comms``
  (one choke point: the comms-budget fence and the ``--tp_overlap``
  collective-matmul dispatch both live behind it),
- raw ``jax.lax.ppermute`` perm lists outside ``core/comms.py`` /
  ``ops/collective_matmul.py`` — a perm at a ppermute call site must be
  a name bound from the named builders ``ring_perm``/``shift_perm``
  (``core/comms.py``), the construction the collective soundness pass
  (``analysis/collective.py``) introspects; a hand-typed pair list with
  one transposed entry compiles clean and trains silently wrong,
- blocking device readbacks (``int(...)``/``float(...)``/``.item()``) in
  the iteration loop of ``dtf_tpu/loop.py``'s ``Trainer.fit`` — the hot
  path is SYNC-FREE (PR 3: a per-step readback serializes dispatch
  against compute and defeats the prefetch double-buffer); designated
  backpressure points carry a ``# blocking-ok: <why>`` marker. This
  protects the invariant statically; tests/test_telemetry.py proves it
  dynamically with the counter-instrumented fit,
- integer block-shape literals at flash-attention / Pallas fused-CE
  call sites outside ``dtf_tpu/ops/`` + ``dtf_tpu/tune/`` (and test
  files, whose parity pins are the point) — launchers and models must
  leave block args at 0 so the kernel-tune resolver supplies the banked
  per-shape winner (KERNEL_TUNE.json; docs/TUNING.md). A hard-coded
  literal silently freezes a shape the autotuner has since beaten —
  the PR 7 ring-perm fence idiom applied to block shapes,
- module-level ``jax`` / ``tensorflow`` imports in ``dtf_tpu/telemetry/``
  — the telemetry package (the XPlane parser and report CLI especially)
  must import without ANY backend present: reports are generated on
  machines with no chip from traces captured on one (the loop.py
  lazy-import idiom, enforced). Backend-touching helpers import lazily inside functions; a
  deliberate exception carries ``# noqa``.

Usage: ``python -m dtf_tpu.analysis.srclint PATH [PATH ...]`` — prints one
finding per line, exits 1 if any.
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Iterator


def _py_files(paths: list[str]) -> Iterator[str]:
    for p in paths:
        if os.path.isfile(p):
            yield p
        else:
            for root, dirs, files in os.walk(p):
                dirs[:] = [d for d in dirs if d != "__pycache__"]
                for f in sorted(files):
                    if f.endswith(".py"):
                        yield os.path.join(root, f)


def _noqa_lines(src: str) -> set[int]:
    return {i for i, line in enumerate(src.splitlines(), 1)
            if "# noqa" in line}


class _Names(ast.NodeVisitor):
    """Collect every identifier USED (loads + attribute roots)."""

    def __init__(self):
        self.used: set[str] = set()

    def visit_Name(self, node):
        self.used.add(node.id)

    def visit_Attribute(self, node):
        self.generic_visit(node)


def lint_file(path: str) -> list[str]:
    with open(path, encoding="utf-8") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        return [f"{path}:{e.lineno}: syntax error: {e.msg}"]

    problems: list[str] = []
    noqa = _noqa_lines(src)
    is_init = os.path.basename(path) == "__init__.py"

    names = _Names()
    names.visit(tree)
    # names referenced in module __all__ strings count as used
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            names.used.add(node.value)

    # ---- unused imports (module top level only — conservative) ----
    for node in tree.body:
        if isinstance(node, ast.Import):
            for alias in node.names:
                bound = alias.asname or alias.name.split(".")[0]
                if (not is_init and node.lineno not in noqa
                        and bound not in names.used):
                    problems.append(
                        f"{path}:{node.lineno}: unused import {bound!r}")
        elif isinstance(node, ast.ImportFrom):
            if node.module == "__future__":
                continue
            for alias in node.names:
                if alias.name == "*":
                    problems.append(
                        f"{path}:{node.lineno}: import * from "
                        f"{node.module!r}")
                    continue
                bound = alias.asname or alias.name
                if (not is_init and node.lineno not in noqa
                        and bound not in names.used):
                    problems.append(
                        f"{path}:{node.lineno}: unused import {bound!r}")

    # ---- duplicate top-level defs ----
    seen: dict[str, int] = {}
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            if node.name in seen and node.lineno not in noqa:
                problems.append(
                    f"{path}:{node.lineno}: {node.name!r} redefines the "
                    f"one at line {seen[node.name]}")
            seen[node.name] = node.lineno

    # ---- bare except ----
    for node in ast.walk(tree):
        if (isinstance(node, ast.ExceptHandler) and node.type is None
                and node.lineno not in noqa):
            problems.append(f"{path}:{node.lineno}: bare 'except:'")

    # ---- direct lax collectives in models/ (must route through comms) ----
    # absolute path + segment test (a relative `srclint gpt.py` run from
    # inside models/ must still be fenced; `submodels/` must not be),
    # anchored on the package root: only segments AFTER the last
    # `dtf_tpu` count, so a checkout living under some ancestor named
    # "models" (/home/ml/models/repo/...) doesn't fence the whole tree.
    # Without a `dtf_tpu` anchor (fixtures, scratch files) only the
    # immediate parent directory counts.
    dirs = os.path.abspath(path).replace(os.sep, "/").split("/")[:-1]
    anchored = "dtf_tpu" in dirs
    if anchored:
        dirs = dirs[len(dirs) - dirs[::-1].index("dtf_tpu"):]
        in_models = "models" in dirs
    else:
        in_models = bool(dirs) and dirs[-1] == "models"
    if in_models:
        fenced = ("all_gather", "psum_scatter")
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in fenced
                    and node.lineno not in noqa):
                continue
            base = node.func.value    # jax.lax.X or lax.X
            is_lax = (isinstance(base, ast.Name) and base.id == "lax") or (
                isinstance(base, ast.Attribute) and base.attr == "lax")
            if is_lax:
                problems.append(
                    f"{path}:{node.lineno}: direct jax.lax."
                    f"{node.func.attr} in models/ — route through "
                    f"dtf_tpu.core.comms (the comms-budget fence and "
                    f"--tp_overlap dispatch choke point)")

    # ---- block-shape literals at tuned-kernel call sites ----
    # anchored files: dirs is already trimmed past the last `dtf_tpu`
    # segment (the models/ fence above), so `in` checks are in-package.
    # Unanchored files (scripts/, tests/, scratch): only the IMMEDIATE
    # parent counts — a checkout under /home/ci/tests/... must not
    # exempt every file, nor an ancestor named ops/ bless one (the same
    # anchoring discipline as the models/ fence).
    base = os.path.basename(path)
    in_tests = (("tests" in dirs) if anchored
                else (bool(dirs) and dirs[-1] == "tests")) \
        or base.startswith("test_")
    blessed_block_module = bool(dirs) and dirs[-1] in ("ops", "tune")
    if not (blessed_block_module or in_tests):
        problems += _block_literals(tree, path, noqa)
        problems += _precision_literals(tree, path, noqa)

    # ---- raw ppermute perm lists (must come from the named builders) ----
    blessed_perm_module = (
        ("dtf_tpu" in dirs or (bool(dirs) and dirs[-1] in ("core", "ops")))
        and ((base == "comms.py" and (not dirs or dirs[-1] == "core"))
             or (base == "collective_matmul.py"
                 and (not dirs or dirs[-1] == "ops"))))
    if not blessed_perm_module:
        problems += _raw_ppermute_perms(tree, path, noqa)

    # ---- blocking readbacks in the trainer hot path (loop.py fit) ----
    if os.path.basename(path) == "loop.py" and (
            "dtf_tpu" in dirs or not dirs or dirs[-1] == "dtf_tpu"):
        problems += _hotpath_readbacks(tree, path, noqa, src)

    # ---- raw AOT lower/compile outside the executor (ISSUE 18) ----
    # core/executor.py is the one sanctioned home of the
    # jit→lower→compile idiom; tune/ sweeps compile candidate programs
    # by design, and tests exercise raw AOT surfaces directly.
    blessed_aot_module = (
        (base == "executor.py" and (not dirs or dirs[-1] == "core"))
        or (("tune" in dirs) if anchored
            else (bool(dirs) and dirs[-1] == "tune")))
    if not (blessed_aot_module or in_tests):
        problems += _raw_aot_compiles(tree, path, noqa, src)

    # ---- backend imports fenced out of telemetry/tune/fault/stream ----
    # telemetry: reports parse traces on chipless machines. tune: a
    # parent may import the package and then start children that need
    # the chip — a parent that has touched jax would hold it.
    # fault: the run controller supervises possibly-WEDGED backends from
    # a clean chief process — importing the thing it must outlive would
    # be fatal.
    for pkg, why in (("telemetry", "reports parse traces on chipless "
                      "machines"),
                     ("tune", "a parent may import it and then start "
                      "children that need the chip — a parent that "
                      "has imported a backend may hold it"),
                     ("fault", "the run controller supervises a possibly-"
                      "wedged backend from a clean process and must "
                      "never import what it has to outlive"),
                     ("stream", "the mixture stream is pure host IO "
                      "whose producer thread must run — and be "
                      "testable — with no backend present")):
        in_pkg = (pkg in dirs if anchored
                  else bool(dirs) and dirs[-1] == pkg)
        if in_pkg:
            problems += _backend_imports(tree, path, noqa, pkg, why)

    # logsink.py is the ONE jax-free module inside serve/ (ISSUE 19):
    # backend-free processes (distill tooling, the poison-import test)
    # load it by file location because serve/__init__ pulls jax — a
    # module-level backend import here would defeat that load path.
    if base == "logsink.py" and (("serve" in dirs) if anchored
                                 else bool(dirs) and dirs[-1] == "serve"):
        problems += _backend_imports(
            tree, path, noqa, "serve/logsink",
            "the serve-log sink is host-side file IO loaded by file "
            "location in backend-free processes; serve/__init__ owns "
            "the jax imports")

    return problems


#: module roots whose import pulls a backend (or its proto stack) into
#: the process — fenced at telemetry module level, lazy-only inside.
_BACKEND_ROOTS = ("jax", "jaxlib", "tensorflow")


def _backend_imports(tree, path: str, noqa: set,
                     pkg: str = "telemetry",
                     why: str = "reports parse traces on chipless "
                     "machines") -> list:
    """Import-time backend imports in a fenced package (``telemetry/``,
    ``tune/``) — these must stay importable in a process with no
    jax/tensorflow at all. Lazy imports inside
    functions are the sanctioned spelling; anything that executes at
    module import time is fenced, including imports wrapped in try/if
    or sitting in a class body (they still run on import)."""
    def module_time_nodes(body):
        # every statement that executes when the module is imported:
        # descend into try/if/with/class bodies, NOT into functions
        # (a def's body runs at call time — that's the lazy spelling)
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            yield node
            for attr in ("body", "orelse", "finalbody"):
                yield from module_time_nodes(getattr(node, attr, []) or [])
            for h in getattr(node, "handlers", []) or []:
                yield from module_time_nodes(h.body)

    problems = []
    for node in module_time_nodes(tree.body):
        roots = []
        if isinstance(node, ast.Import):
            roots = [a.name.split(".")[0] for a in node.names]
        elif isinstance(node, ast.ImportFrom) and node.level == 0 \
                and node.module:
            roots = [node.module.split(".")[0]]
        for root in roots:
            if root in _BACKEND_ROOTS and node.lineno not in noqa:
                problems.append(
                    f"{path}:{node.lineno}: module-level '{root}' import "
                    f"in dtf_tpu/{pkg}/ — the {pkg} package must "
                    f"import without a backend ({why}); import it "
                    f"lazily inside the function that needs it")
    return problems


#: tuned-kernel entry points and the block kwargs the tuner owns: an
#: int literal for one of these outside ops//tune/ (and tests) bypasses
#: the kernel-tune resolver (dtf_tpu/tune; docs/TUNING.md).
_TUNED_KERNEL_CALLS = {
    "flash_attention": ("block_q", "block_k", "block_h",
                        "block_q_bwd", "block_k_bwd"),
    "flash_attention_sharded": ("block_h",),
    "pallas_lm_cross_entropy": ("block_n", "block_v"),
    "pallas_lm_cross_entropy_sharded": ("block_n", "block_v"),
}


def _block_literals(tree, path: str, noqa: set) -> list:
    """Nonzero int literals for tuner-owned block kwargs at flash /
    fused-CE call sites — launchers and models must leave them at 0 (the
    resolver sentinel) or thread a resolved variable, so the banked
    per-shape winners actually apply. 0 is the sentinel itself and
    stays legal; a deliberate pin carries ``# noqa`` with its why."""
    problems = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.lineno not in noqa):
            continue
        fn = node.func
        fn_name = (fn.id if isinstance(fn, ast.Name)
                   else fn.attr if isinstance(fn, ast.Attribute) else None)
        fenced = _TUNED_KERNEL_CALLS.get(fn_name or "")
        if not fenced:
            continue
        for kw in node.keywords:
            if (kw.arg in fenced and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, int)
                    and not isinstance(kw.value.value, bool)
                    and kw.value.value != 0
                    and kw.value.lineno not in noqa):
                problems.append(
                    f"{path}:{kw.value.lineno}: block-shape literal "
                    f"{kw.arg}={kw.value.value} at a {fn_name} call — "
                    f"leave it 0 so the kernel-tune resolver supplies "
                    f"the banked winner (dtf_tpu/tune, KERNEL_TUNE.json; "
                    f"docs/TUNING.md), or mark a deliberate pin with "
                    f"'# noqa: <why>'")
    return problems


#: the tp_dense/ring entry points whose ``precision`` kwarg the tuner
#: owns (ISSUE 17), and the literal values that stay legal anywhere:
#: "" (bf16 status quo) and "auto" (resolver decides). A hard-coded
#: "int8"/"fp8" outside ops//tune/ (and tests) bypasses the measured
#: quality bound exactly the way a block-shape literal bypasses the
#: banked block winner — same fence, string edition.
_PRECISION_CALLS = ("tp_dense", "TpDense", "quantized_matmul",
                    "ag_matmul_quant_sharded", "matmul_rs_quant_sharded")
_PRECISION_FREE_LITERALS = ("", "auto")


def _precision_literals(tree, path: str, noqa: set) -> list:
    """String precision literals other than ''/'auto' at tp_dense / ring
    call sites — launchers and models must pass '' (bf16), 'auto' (the
    kernel-tune winner), or thread a resolved variable (e.g.
    ``precision=cfg.matmul_precision``, which is an Attribute, not a
    Constant, and passes untouched). A deliberate pin carries
    ``# noqa`` with its why."""
    problems = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.lineno not in noqa):
            continue
        fn = node.func
        fn_name = (fn.id if isinstance(fn, ast.Name)
                   else fn.attr if isinstance(fn, ast.Attribute) else None)
        if fn_name not in _PRECISION_CALLS:
            continue
        for kw in node.keywords:
            if (kw.arg == "precision"
                    and isinstance(kw.value, ast.Constant)
                    and isinstance(kw.value.value, str)
                    and kw.value.value not in _PRECISION_FREE_LITERALS
                    and kw.value.lineno not in noqa):
                problems.append(
                    f"{path}:{kw.value.lineno}: precision literal "
                    f"{kw.value.value!r} at a {fn_name} call — pass '' "
                    f"(bf16), 'auto' (the kernel-tune winner under its "
                    f"rel-err ceiling), or a resolved variable "
                    f"(dtf_tpu/tune, KERNEL_TUNE.json; docs/TUNING.md), "
                    f"or mark a deliberate pin with '# noqa: <why>'")
    return problems


#: the sanctioned perm constructors (core/comms.py) — the introspection
#: surface of the collective soundness pass.
_PERM_BUILDERS = ("ring_perm", "shift_perm")


def _raw_ppermute_perms(tree, path: str, noqa: set) -> list:
    """``jax.lax.ppermute`` calls whose ``perm`` is not a name bound from
    ``ring_perm``/``shift_perm`` — outside the two ring modules, rings
    must come from the named helpers the soundness pass can introspect
    (the PR 2 fence idiom, applied to perm construction).

    A name counts as blessed only when EVERY assignment to it in the file
    is a builder call — a second function hand-typing a pair list into a
    name some other scope blessed (``perm`` is the idiomatic name
    everywhere) must not ride the first function's blessing.
    """
    def _is_builder(value) -> bool:
        if not isinstance(value, ast.Call):
            return False
        fn = value.func
        fn_name = (fn.id if isinstance(fn, ast.Name)
                   else fn.attr if isinstance(fn, ast.Attribute) else None)
        return fn_name in _PERM_BUILDERS

    #: in-place mutators that de-bless a builder-built list.
    _MUTATORS = ("append", "extend", "insert", "remove", "pop", "sort",
                 "reverse", "clear")

    blessed: set[str] = set()
    tainted: set[str] = set()
    for node in ast.walk(tree):
        targets = ()
        if isinstance(node, ast.Assign):
            targets, value = node.targets, node.value
        elif isinstance(node, ast.AnnAssign) and node.value is not None:
            targets, value = (node.target,), node.value
        elif isinstance(node, ast.AugAssign):
            # perm += [...] hand-edits a blessed list — taint it
            targets, value = (node.target,), None
        for tgt in targets:
            names = ([tgt] if isinstance(tgt, ast.Name)
                     else [e for e in ast.walk(tgt)
                           if isinstance(e, ast.Name)])
            for nm in names:
                (blessed if _is_builder(value) else tainted).add(nm.id)
        # perm.append((0, 2)) mutates in place — taint too
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in _MUTATORS
                and isinstance(node.func.value, ast.Name)):
            tainted.add(node.func.value.id)
    blessed -= tainted

    problems = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.lineno not in noqa):
            continue
        # every spelling: jax.lax.ppermute / lax.ppermute / a bare
        # `ppermute` from `from jax.lax import ppermute` — leaving one
        # spelling unfenced leaves the hole open
        if isinstance(node.func, ast.Attribute):
            if node.func.attr != "ppermute":
                continue
        elif not (isinstance(node.func, ast.Name)
                  and node.func.id == "ppermute"):
            continue
        perm = None
        if len(node.args) >= 3:
            perm = node.args[2]
        else:
            perm = next((kw.value for kw in node.keywords
                         if kw.arg == "perm"), None)
        if (isinstance(perm, ast.Name) and perm.id in blessed):
            continue
        if isinstance(perm, ast.Call):
            fn = perm.func
            fn_name = (fn.id if isinstance(fn, ast.Name)
                       else fn.attr if isinstance(fn, ast.Attribute)
                       else None)
            if fn_name in _PERM_BUILDERS:
                continue
        problems.append(
            f"{path}:{node.lineno}: raw perm at jax.lax.ppermute call — "
            f"build it with core.comms.ring_perm/shift_perm (the named "
            f"helpers the collective soundness pass introspects); a "
            f"hand-typed pair list dodges the ring fence")
    return problems


def _raw_aot_compiles(tree, path: str, noqa: set, src: str) -> list:
    """``.lower(args)`` / ``.compile(`` attribute calls outside
    ``core/executor.py`` (+ tune/ + tests) — the AOT idiom must route
    through :func:`dtf_tpu.core.executor.program`, the one place that
    owns the recompile fence, sharding pins, the donation gate and the
    analysis step-view registration (ISSUE 18). A deliberate raw site
    carries ``# aot-ok: <why>`` (covers its line and the next, so the
    idiomatic two-line ``.lower(...)\\n.compile()`` needs one pin).

    Skipped on purpose: no-argument ``.lower()`` (``str.lower`` — the
    bare-operand Program.lower() spelling is executor-internal) and
    ``re.compile(``."""
    ok: set[int] = set()
    for i, line in enumerate(src.splitlines(), 1):
        if "# aot-ok" in line:
            ok.update((i, i + 1))
    problems = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("lower", "compile")
                and node.lineno not in noqa
                and node.lineno not in ok):
            continue
        if (node.func.attr == "lower" and not node.args
                and not node.keywords):
            continue                      # str.lower()
        fn_base = node.func.value
        if (node.func.attr == "compile"
                and isinstance(fn_base, ast.Name) and fn_base.id == "re"):
            continue                      # re.compile()
        problems.append(
            f"{path}:{node.lineno}: raw .{node.func.attr}( AOT idiom — "
            f"route through dtf_tpu.core.executor.program (the fence / "
            f"pins / donation / step-view choke point; docs/ANALYSIS.md), "
            f"or mark a deliberate site with '# aot-ok: <why>'")
    return problems


def _hotpath_readbacks(tree, path: str, noqa: set, src: str) -> list:
    """``int()``/``float()``/``.item()`` inside the iteration loop of
    ``Trainer.fit`` — each is a blocking device readback serializing host
    dispatch against device compute (the PR 3 sync-free invariant). The
    one-time resume sync sits BEFORE the loop and is legal; an intentional
    backpressure point inside it must carry ``# blocking-ok: <why>``."""
    allowed = {i for i, line in enumerate(src.splitlines(), 1)
               if "# blocking-ok" in line}

    def loops_of_fit():
        for cls in ast.walk(tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            for fn in cls.body:
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and fn.name == "fit":
                    for node in ast.walk(fn):
                        if isinstance(node, (ast.For, ast.While)):
                            yield node

    problems = []
    seen: set[int] = set()
    for loop in loops_of_fit():
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call) or node.lineno in seen:
                continue
            name = None
            if isinstance(node.func, ast.Name) and \
                    node.func.id in ("int", "float"):
                name = f"{node.func.id}(...)"
            elif isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "item":
                name = ".item()"
            if name is None or node.lineno in noqa \
                    or node.lineno in allowed:
                continue
            seen.add(node.lineno)
            problems.append(
                f"{path}:{node.lineno}: {name} in Trainer.fit's hot loop "
                f"— a blocking device readback breaks the sync-free loop "
                f"(PR 3); move it to a hook or mark a designated "
                f"backpressure point with '# blocking-ok: <why>'")
    return problems


def main(argv: list[str]) -> int:
    paths = argv or ["dtf_tpu"]
    problems = []
    n = 0
    for f in _py_files(paths):
        n += 1
        problems += lint_file(f)
    for p in problems:
        print(p)
    print(f"srclint: {n} files, {len(problems)} problems")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
