"""Clean CPU-sim environment construction, shared by every bootstrap.

tests/conftest.py, __graft_entry__.py and the analysis/tune CLIs all need
the same thing: a child/re-exec environment pinned to an N-virtual-device
CPU backend, whatever platform the calling shell was pointed at. One
builder lives here so the copies cannot drift. Must stay importable
without jax.
"""

from __future__ import annotations

def device_flag(n_devices: int) -> str:
    return f"--xla_force_host_platform_device_count={n_devices}"


def is_cpu_sim(env, n_devices: int) -> bool:
    """True when ``env`` already pins this process to an n-device CPU sim."""
    return (env.get("JAX_PLATFORMS") == "cpu"
            and device_flag(n_devices) in env.get("XLA_FLAGS", ""))


def cpu_sim_env(n_devices: int, base_env) -> dict:
    """A copy of ``base_env`` pinned to the n-device CPU sim."""
    env = dict(base_env)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                        + device_flag(n_devices)).strip()
    return env
