"""Test bootstrap: force an 8-device virtual CPU mesh.

Distributed-semantics tests need 8 simulated devices on the CPU (the moral
equivalent of TF's create_in_process_cluster; SURVEY.md §4), and the device
count is read once per process, before jax starts. So if the current
process came up without that config — a plain shell, or a shell on the
machine with the chip — we re-exec pytest once with a CPU-pinned
environment. This keeps `python -m pytest tests/` working from any shell
without wrapper scripts, and keeps the suite off the chip.
"""

import os
import sys

# Repo root on sys.path so `import dtf_tpu` (and _dtf_env) work without
# installation.
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from _dtf_env import cpu_sim_env, is_cpu_sim  # noqa: E402

if (not is_cpu_sim(os.environ, 8)
        and os.environ.get("_DTF_TPU_TEST_REEXEC") != "1"):
    env = cpu_sim_env(8, os.environ)
    env["_DTF_TPU_TEST_REEXEC"] = "1"
    os.execve(sys.executable, [sys.executable, "-m", "pytest"] + sys.argv[1:], env)

import jax  # noqa: E402
import pytest  # noqa: E402

# The persistent cache's executable loader prints benign `cpu_aot_loader`
# feature-mismatch warnings on every warm deserialization in some
# environments (CLAUDE.md).  With the memory pass now fencing every
# program's HBM breakdown, a real memory-fence failure must not scroll
# away inside that noise — downgrade exactly this class (pattern-matched
# on both the warnings and logging spellings; everything else stays
# loud).
import logging  # noqa: E402
import warnings  # noqa: E402

warnings.filterwarnings("ignore", message=r".*cpu_aot_loader.*")


class _CpuAotLoaderNoise(logging.Filter):
    def filter(self, record):  # pragma: no cover — env-dependent noise
        # scoped to the loader's own messages: a NEW "feature mismatch"
        # from anywhere else must stay loud
        return "cpu_aot_loader" not in record.getMessage()


for _name in ("jax", "jax._src.compiler", "jax._src.compilation_cache",
              "absl"):
    logging.getLogger(_name).addFilter(_CpuAotLoaderNoise())

# Persistent compilation cache: the suite's wall-clock is dominated by
# recompiling identical 8-device shard_map graphs every run. With the
# cache, a warm run spends seconds where a cold one spends minutes. Safe
# across code edits — the cache key hashes the HLO, not the Python source.
# Placed by the launchers' own helper: JAX_COMPILATION_CACHE_DIR when the
# caller set one, else <checkout>/.jax_cache.
from dtf_tpu.cli.launch import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)


#: ``test_bench_manifest.test_config_entry_and_file`` refuses a ``reduced``
#: key that CONTAINS ``hidden``, and the contract makes ``reduced`` name the
#: source's depth key ``num_hidden_layers``. ``tests/benchmark_suite/`` is the
#: benchmark's own and not a ``model_config`` PR's to edit, so PR 31's case is
#: marked from here as ``tests/benchmark_suite/conftest.py`` marks LFM2's:
#: expected to fail, STRICTLY (the day a ``benchmark`` PR repairs the pattern
#: the case passes, the marker turns that into a failure, and both markers
#: go; PERF.md section 7 a). ``test_bench_axk1.py`` makes the same checks
#: with the widths named by key.
DEPTH_KEY_READ_AS_WIDTH = (
    "test_bench_manifest.py::test_config_entry_and_file[ax-k1]")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(DEPTH_KEY_READ_AS_WIDTH):
            item.add_marker(pytest.mark.xfail(
                reason="the accepted width pattern matches 'hidden' inside "
                       "the depth key num_hidden_layers; "
                       "test_bench_axk1.py checks the entry instead",
                strict=True))


@pytest.fixture(scope="session")
def cpu_sim_subprocess_env():
    """A CPU-pinned env for subprocess children (probe/bench tests) —
    1 virtual device (fast import)."""
    return cpu_sim_env(1, os.environ)


@pytest.fixture(scope="session")
def mesh8():
    from dtf_tpu.core.mesh import MeshConfig, make_mesh

    assert len(jax.devices()) == 8, "conftest failed to force 8 CPU devices"
    return make_mesh(MeshConfig(data=8))


@pytest.fixture(scope="session")
def mesh_2x2x2():
    from dtf_tpu.core.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(data=2, seq=2, model=2))


@pytest.fixture(scope="session")
def mesh_4x2():
    from dtf_tpu.core.mesh import MeshConfig, make_mesh

    return make_mesh(MeshConfig(data=4, seq=1, model=2))
