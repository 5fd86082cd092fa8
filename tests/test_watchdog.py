"""The shared watchdogged-subprocess runner (_dtf_watchdog.py): one child
at a time under a hard timeout, the parent off jax. Tested with fake
children — no jax, no TPU (except the probe tests, which import jax in a
CPU-pinned child)."""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from _dtf_watchdog import Budget, probe_backend, run_watchdogged


def _json_parse(line):
    try:
        d = json.loads(line)
    except (json.JSONDecodeError, ValueError):
        return None
    return d if isinstance(d, dict) and "value" in d else None


def test_success_returns_last_matching_line():
    code = ("import json\n"
            "print('noise')\n"
            "print(json.dumps({'value': 1}))\n"
            "print(json.dumps({'value': 2}))\n"
            "print('trailing noise')\n")
    result, errors = run_watchdogged(
        [sys.executable, "-c", code], _json_parse, timeout_s=30, retries=1)
    assert result == {"value": 2}
    assert errors == []


def test_timeout_then_success_retries(tmp_path):
    # first run sleeps past the timeout; second run succeeds (state via file)
    flag = tmp_path / "ran_once"
    code = (f"import json, os, time\n"
            f"p = {str(flag)!r}\n"
            f"if not os.path.exists(p):\n"
            f"    open(p, 'w').close(); time.sleep(60)\n"
            f"print(json.dumps({{'value': 7}}))\n")
    result, errors = run_watchdogged(
        [sys.executable, "-c", code], _json_parse,
        timeout_s=5, retries=2, backoff_s=0)
    assert result == {"value": 7}
    assert len(errors) == 1 and "timeout" in errors[0]


def test_all_attempts_fail_collects_errors():
    code = "import sys; print('no result here'); sys.exit(3)"
    result, errors = run_watchdogged(
        [sys.executable, "-c", code], _json_parse,
        timeout_s=30, retries=2, backoff_s=0)
    assert result is None
    assert len(errors) == 2
    assert all("rc=3" in e for e in errors)


def test_crash_with_stderr_tail_recorded():
    code = "raise RuntimeError('backend exploded')"
    result, errors = run_watchdogged(
        [sys.executable, "-c", code], _json_parse,
        timeout_s=30, retries=1, backoff_s=0)
    assert result is None
    assert "backend exploded" in errors[0]


def test_run_budgeted_jobs_collects_rows_and_errors(tmp_path):
    from _dtf_watchdog import run_budgeted_jobs

    code = ("import json, os\n"
            "v = os.environ['JOB_VAL']\n"
            "if v == 'boom':\n"
            "    raise SystemExit(3)\n"
            "print(json.dumps({'value': int(v)}))\n")
    seen = []
    rows, errors = run_budgeted_jobs(
        [{"JOB_VAL": "1"}, {"JOB_VAL": "boom"}, {"JOB_VAL": "3"}],
        [sys.executable, "-c", code], _json_parse,
        budget=Budget(300), cap_s=60, env_base=dict(os.environ),
        on_result=lambda row, job, rows, errors: seen.append(
            (row, dict(job))))
    assert rows == [{"value": 1}, {"value": 3}]
    assert len(errors) == 1 and errors[0]["env"] == {"JOB_VAL": "boom"}
    assert "rc=3" in errors[0]["errors"][0]
    assert len(seen) == 3 and seen[1][0] is None


def test_budget_counts_down():
    b = Budget(100.0)
    assert 99.0 < b.remaining() <= 100.0
    assert b.remaining(margin_s=40) <= 60.0
    assert Budget(0.0).remaining() == 0.0


def test_probe_backend_success_on_cpu(cpu_sim_subprocess_env):
    backend, errors = probe_backend(timeout_s=120,
                                    env=cpu_sim_subprocess_env)
    assert backend == "cpu"
    assert errors == []


def test_probe_backend_fails_fast_on_broken_platform(cpu_sim_subprocess_env):
    env = dict(cpu_sim_subprocess_env)
    env["JAX_PLATFORMS"] = "no_such_platform"
    t0 = time.monotonic()
    backend, errors = probe_backend(timeout_s=120, env=env)
    assert backend is None
    assert len(errors) == 1 and time.monotonic() - t0 < 60


def test_bench_exits_nonzero_without_a_chip(cpu_sim_subprocess_env):
    """No chip, no number: on a process that sees no TPU bench.py exits 1
    and its LAST stdout line is a parseable error — never a zero value,
    never numbers carried over from committed files."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench.py")],
        env=cpu_sim_subprocess_env, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True, timeout=120)
    assert proc.returncode == 1
    last = proc.stdout.strip().splitlines()[-1]
    result = json.loads(last)
    assert "value" not in result and "measurement failed" in result["error"]
    assert "came up on 'cpu'" in result["error"]
    assert "banked_from_committed_artifacts" not in result
