"""The repo's documents and root records agree with the tree (no JAX).

Two checks that hold the state PR 28 reached, when the pre-benchmark
harness went: a document names no script, module, test or record that is
not there, and every ``*.json`` at the root still has a reader.
"""

import ast
import fnmatch
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "CLAUDE.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

#: names a document may give though no checkout holds them: they are
#: written at run time (and listed in .gitignore) or by a sweep that has
#: not been taken.
WRITTEN_AT_RUN_TIME = {
    "KERNEL_TUNE.local.json",   # this machine's winners (tune/cache.py)
    "KERNEL_TUNE_SWEEP.json",   # raw sweep rows, once a PR measures any
    "ATTN_BENCH.json",          # block-sweep rows tune/search.py would read
    "BENCH_LM.json",            # loss-path / serve rows, the same
    "COPYCHECK.json",
    # manifests that the sinks and the publisher keep in their own
    # directories (serve/logsink.py, telemetry/events.py, publish.py)
    "SERVELOG_MANIFEST.json", "EVENTS_MANIFEST.json",
    "PUBLISH_MANIFEST.json",
}

_CODE = re.compile(r"```.*?```|`[^`\n]+`", re.S)
_IN_TREE = re.compile(r"^(?:scripts|dtf_tpu|tests|benchmarks|docs)/\S*$")
_ROOT_JSON = re.compile(r"^[A-Z][A-Za-z0-9_.-]*\.json$")
_BARE_PY = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*\.py$")


@functools.lru_cache(maxsize=None)
def _py_basenames() -> frozenset:
    names = {n for n in os.listdir(ROOT) if n.endswith(".py")}
    for top in ("scripts", "tests", "dtf_tpu", "benchmarks"):
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(n for n in files if n.endswith(".py"))
    return frozenset(names)


def _exists(path: str) -> bool:
    full = os.path.join(ROOT, path)
    if any(c in path for c in "*?["):
        return bool(glob.glob(full))
    return os.path.exists(full)


def _missing_paths(text: str) -> list:
    missing = []
    for span in _CODE.findall(text):
        for word in span.strip("`").split():
            word = word.strip("\"'()[],;")
            # `tests/test_x.py::test_y`, `dtf_tpu/hooks.py:419`
            word = re.split(r"::|:\d", word)[0].rstrip(".:")
            if "<" in word or "{" in word or "…" in word or "..." in word:
                continue        # a pattern for the reader, not a path
            if _IN_TREE.match(word):
                ok = _exists(word)
            elif _ROOT_JSON.match(word):
                ok = word in WRITTEN_AT_RUN_TIME or _exists(word)
            elif _BARE_PY.match(word):
                ok = word in _py_basenames()
            else:
                continue
            if not ok and word not in missing:
                missing.append(word)
    return missing


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_paths_that_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        missing = _missing_paths(f.read())
    assert not missing, (
        f"{doc} names paths that are not in the tree: {missing}")


def test_the_path_check_sees_a_missing_file():
    text = ("run `python scripts/no_such_bench.py --x`, read `NO_SUCH.json`"
            " and `no_such_module.py`; `scripts/train_gpt.py` is there, "
            "so are `scripts/train_*.py`, `<logdir>/TELEMETRY.json` and "
            "`tests/test_repo_records.py::test_x`\n"
            "```\npython scripts/gone.py\n```")
    assert _missing_paths(text) == [
        "scripts/no_such_bench.py", "NO_SUCH.json", "no_such_module.py",
        "scripts/gone.py"]


# --------------------------------------------------------------------------
# every record at the root has a reader
# --------------------------------------------------------------------------

#: the driver's own files: it writes or reads them by these names.
_DRIVERS = re.compile(r"^(BASELINE|BENCHMARK|BENCH_r\d+|MULTICHIP_r\d+)\.json$")


with open(os.path.join(ROOT, ".gitignore")) as _f:
    _IGNORED = [ln.strip() for ln in _f if ln.strip() and "/" not in ln]

ROOT_RECORDS = sorted(
    n for n in os.listdir(ROOT)
    if n.endswith(".json")
    and not any(fnmatch.fnmatch(n, pat) for pat in _IGNORED))


@functools.lru_cache(maxsize=None)
def _string_literals() -> tuple:
    """Every string the package and the benchmark compute with:
    docstrings and comments name files too, and do not read them."""
    out = []
    for top in ("dtf_tpu", "benchmarks"):
        for d, _, files in os.walk(os.path.join(ROOT, top)):
            for n in files:
                if not n.endswith(".py"):
                    continue
                with open(os.path.join(d, n)) as f:
                    tree = ast.parse(f.read())
                bare = {id(s.value) for s in ast.walk(tree)
                        if isinstance(s, ast.Expr)
                        and isinstance(s.value, ast.Constant)}
                out += [c.value for c in ast.walk(tree)
                        if isinstance(c, ast.Constant)
                        and isinstance(c.value, str) and id(c) not in bare]
    return tuple(out)


@pytest.mark.parametrize("name", ROOT_RECORDS)
def test_root_record_has_a_reader(name):
    if _DRIVERS.match(name):
        return
    assert any(name in s for s in _string_literals()), (
        f"{name} lies at the root and no module under dtf_tpu/ or "
        f"benchmarks/ opens it: delete it, or say here who reads it")
