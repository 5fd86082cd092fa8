"""Watchdogged-subprocess runner shared by bench.py and scripts/bench_*.py.

A chip belongs to one process at a time, so a script that measures several
configurations runs each in a child process with a hard timeout, one after
the other, and the parent NEVER imports jax (a parent that has touched
jax holds the chip). This module must therefore stay importable without
jax/dtf_tpu.
"""

from __future__ import annotations

import subprocess
import sys
import time
from typing import Callable, Optional


def run_watchdogged(argv: list[str], parse_line: Callable[[str], object], *,
                    timeout_s: float, retries: int = 3, backoff_s: float = 15,
                    env: Optional[dict] = None):
    """Run ``argv`` under a timeout, retrying with linear backoff.

    After each attempt the child's stdout is scanned bottom-up; the first
    line for which ``parse_line`` returns non-None is the result. Returns
    ``(result, errors)`` — result None if every attempt failed, errors a
    list of one human-readable string per failed attempt.
    """
    errors: list[str] = []
    for attempt in range(retries):
        if attempt:
            time.sleep(backoff_s * attempt)
        try:
            proc = subprocess.run(
                argv, env=env, stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, timeout=timeout_s, text=True)
        except subprocess.TimeoutExpired:
            errors.append(f"attempt {attempt + 1}: timeout after "
                          f"{timeout_s}s")
            continue
        for line in reversed(proc.stdout.strip().splitlines()):
            result = parse_line(line)
            if result is not None:
                return result, errors
        tail = (proc.stderr or "").strip().splitlines()[-5:]
        errors.append(f"attempt {attempt + 1}: rc={proc.returncode}, "
                      f"stderr tail: {' | '.join(tail) if tail else 'empty'}")
    return None, errors


def child_argv(script_path: str) -> list[str]:
    return [sys.executable, script_path, "--child"]


class Budget:
    """Total wall-clock budget for an artifact-producing script: each
    child's timeout is sized to what remains of it, so a number or a
    structured error lands inside the caller's own time limit."""

    def __init__(self, total_s: float):
        self.total_s = float(total_s)
        self._t0 = time.monotonic()

    def elapsed(self) -> float:
        return time.monotonic() - self._t0

    def remaining(self, margin_s: float = 0.0) -> float:
        return max(0.0, self.total_s - self.elapsed() - margin_s)


#: a minimal end-to-end backend exercise: import jax, jit one op, read the
#: value back, say which backend answered.
_PROBE_CODE = (
    "import time; t0 = time.time()\n"
    "import jax, jax.numpy as jnp\n"
    "x = jnp.ones((256, 256), jnp.bfloat16)\n"
    "y = jax.jit(lambda a: a @ a)(x)\n"
    "jax.block_until_ready(y)\n"
    "print('DTF_PROBE_OK', jax.default_backend(),\n"
    "      round(time.time() - t0, 1), flush=True)\n"
)


def run_budgeted_jobs(jobs: list, argv: list[str], parse_line, *,
                      budget: "Budget", cap_s: float,
                      env_base: Optional[dict] = None, on_result=None):
    """Run env-dict ``jobs`` through watchdogged children, sizing each
    child's timeout to the remaining budget split over the jobs left
    (min'd with ``cap_s``). One attempt per job: retrying would only
    burn the budget the later jobs need.

    Returns ``(rows, errors)``; failures append ``{"env": job, "errors":
    [...]}``. ``on_result(row_or_None, job, rows, errors)`` fires after
    every job for incremental artifact writes (partial progress must
    survive a later hang). One driver loop, so that a script with
    several jobs cannot drift on budget math or error shape.
    """
    rows, errors = [], []
    for i, job in enumerate(jobs):
        env = dict(env_base if env_base is not None else {})
        env.update(job)
        per_job = budget.remaining(30) / max(1, len(jobs) - i)
        row, errs = run_watchdogged(
            argv, parse_line, timeout_s=min(cap_s, max(60.0, per_job)),
            retries=1, backoff_s=0, env=env)
        if row is None:
            errors.append({"env": job, "errors": errs})
        else:
            rows.append(row)
        if on_result is not None:
            on_result(row, job, rows, errors)
    return rows, errors


def fence(out):
    """Block until a device computation has ACTUALLY finished, by host
    readback: a transfer cannot complete before the program has. The
    timing fence of the bench children. ``chip_smoke.py``'s fence phase
    checks on the chip whether ``jax.block_until_ready`` alone waits as
    well (CHANGES.md, PR 21, says what it saw); until the benches are
    rebuilt as cells they keep the readback, which is right either way.
    Accepts any array / pytree; returns the first leaf as a numpy array.
    """
    import jax
    import numpy as np

    return np.asarray(jax.tree.leaves(out)[0])


def probe_backend(*, timeout_s: float = 90, env: Optional[dict] = None):
    """Which backend a child of this script would get: one short child,
    finished before the first measurement child starts. Only for a
    script whose parent picks its job list by the answer; ``bench.py``
    just starts its child, which fails by itself without a chip.

    Returns ``(backend_name_or_None, errors)``.
    """

    def parse(line: str):
        parts = line.split()
        if len(parts) >= 2 and parts[0] == "DTF_PROBE_OK":
            return parts[1]
        return None

    return run_watchdogged([sys.executable, "-c", _PROBE_CODE], parse,
                           timeout_s=timeout_s, retries=1, env=env)
