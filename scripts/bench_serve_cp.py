#!/usr/bin/env python
"""Control-plane tick rate of the serve Router at ZERO device work.

The Router pump is pure host code — pick/admission, engine ticks, the
health sweep, page bookkeeping, the swap machine (ISSUE 20's tick
profiler attributes each phase inside :meth:`Router.tick`). This bench
drives a fleet of FAKE engines (host arithmetic stand-ins for the AOT
programs: no jax arrays, no device, no compile) through the real Router
+ Scheduler + HealthTracker stack and measures what the control plane
alone can sustain: ticks/sec and requests/sec. That number bounds serve
throughput from above for small models — when decode is fast, the pump
IS the ceiling — and regressions here are silent on-chip (they hide
inside the decode wall).

Artifact: ``CONTROL_PLANE.json`` (bounded history, `_dtf_artifact`
merge). FAIL-CLOSED FENCE (the bench_telemetry mfu idiom): a row whose
``ticks_per_sec`` falls more than ``--tol`` (rel., default 50% — host
timing under CI load is noisy; the fence catches collapses, not jitter)
below the newest committed row of the SAME config exits 1 and is NOT
merged. Intentional control-plane cost rides
``--allow-regression="<why>"``; the justification is recorded in the row.

The parent NEVER imports dtf_tpu/jax; the child re-invokes under
``_dtf_env.cpu_sim_env`` — one virtual CPU device, and even that stays
idle, so it never needs the chip (the row is chip-independent).
Tiny mode DTF_CP_TINY=1 is CI-pinned in tests/test_events.py.
"""

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ARTIFACT = os.path.join(ROOT, "CONTROL_PLANE.json")
SENTINEL = "SERVE_CP "
CHILD_TIMEOUT_S = float(os.environ.get("DTF_CP_TIMEOUT_S", "600"))
TOL_DEFAULT = float(os.environ.get("DTF_CP_TOL", "0.50"))

#: fence identity — rows measured under different fleet shapes are
#: never comparable.
CONFIG_KEYS = ("replicas", "n_slots", "requests", "max_new", "tiny")


def child():
    """The measured half: real Router/Scheduler/HealthTracker over fake
    host-only engines. Runs in the CPU-sim env (dtf_tpu imports jax at
    package level) but never touches a device array."""
    from dtf_tpu.serve import Request, Router

    tiny = os.environ.get("DTF_CP_TINY") == "1"
    replicas = int(os.environ.get("DTF_CP_REPLICAS", "4"))
    n_slots = int(os.environ.get("DTF_CP_SLOTS", "4"))
    n_requests = int(os.environ.get("DTF_CP_REQUESTS",
                                    "64" if tiny else "2048"))
    max_new = int(os.environ.get("DTF_CP_MAX_NEW", "8"))

    class _FakeEngine:
        """Deterministic host stand-in for DecodeEngine's pump surface:
        one chunk per prompt, constant decode emissions."""

        max_len = 64
        prefill_chunk = 64

        def __init__(self, slots):
            self.n_slots = slots

        def prefill_chunk_into(self, slot, prompt, chunk_i, *, start=0,
                               **kw):
            return int(prompt[0]) % 7, False

        def decode(self, **kw):
            return [1] * self.n_slots, [False] * self.n_slots

    router = Router([_FakeEngine(n_slots) for _ in range(replicas)])
    for i in range(n_requests):
        router.submit(Request(prompt=[1 + i % 5], max_new=max_new))
    t0 = time.perf_counter()
    while router.pending:
        router.tick()
    wall = time.perf_counter() - t0
    st = router.stats()
    ticks = int(st["router_ticks"])
    done = int(st["router_completed"])
    report = {"bench": "serve_cp", "tiny": tiny, "replicas": replicas,
              "n_slots": n_slots, "requests": n_requests,
              "max_new": max_new, "completed": done, "ticks": ticks,
              "wall_s": round(wall, 4),
              "ticks_per_sec": round(ticks / max(wall, 1e-9), 1),
              "requests_per_sec": round(done / max(wall, 1e-9), 1)}
    # the profiler's own attribution rides the row: where a control-plane
    # regression landed is in the phase split, not just the headline rate
    for k, v in st.items():
        if k.startswith("cp_"):
            report[k] = v
    print(SENTINEL + json.dumps(report))


def same_config(a, b) -> bool:
    from _dtf_artifact import same_config as _same

    return _same(a, b, CONFIG_KEYS)


def check_fence(prev_runs, report, *, tol_frac=TOL_DEFAULT):
    """``(ok, detail)`` — ok=False means ticks/sec collapsed beyond
    tolerance vs the newest committed same-config row (fail closed)."""
    if "error" in report or report.get("ticks_per_sec") is None:
        return True, {"fenced": False, "reason": "no measured rate in row"}
    base = None
    for row in reversed(prev_runs or []):
        if ("error" not in row and row.get("ticks_per_sec")
                and same_config(row, report)):
            base = row
            break
    if base is None:
        return True, {"fenced": False,
                      "reason": "no committed baseline for this config"}
    floor = base["ticks_per_sec"] * (1.0 - tol_frac)
    detail = {"fenced": True, "baseline_ticks_per_sec":
              base["ticks_per_sec"], "baseline_ts": base.get("ts"),
              "ticks_per_sec": report["ticks_per_sec"],
              "floor": round(floor, 2), "tol_frac": tol_frac}
    return report["ticks_per_sec"] >= floor, detail


def _parse_args(argv):
    tol, justification = TOL_DEFAULT, None
    for a in argv:
        if a.startswith("--tol="):
            tol = float(a.split("=", 1)[1])
        elif a.startswith("--allow-regression="):
            justification = a.split("=", 1)[1]
        elif a == "--allow-regression":
            justification = "(no reason given)"
    return tol, justification


def main(argv=()):
    from _dtf_artifact import load_runs, merge_runs
    from _dtf_env import cpu_sim_env

    tol, justification = _parse_args(argv)
    meta = {"ts": round(time.time(), 1),
            "round": os.environ.get("DTF_ROUND", "")}
    env = cpu_sim_env(1, os.environ)
    env.setdefault("PYTHONPATH", ROOT)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child"],
            env=env, cwd=ROOT, capture_output=True, text=True,
            timeout=CHILD_TIMEOUT_S)
        report = None
        for line in proc.stdout.splitlines():
            if line.startswith(SENTINEL):
                try:
                    report = json.loads(line[len(SENTINEL):])
                except ValueError:
                    pass
        if report is None:
            report = {"bench": "serve_cp",
                      "error": (f"child rc={proc.returncode}, no report: "
                                + proc.stderr[-1500:])}
    except subprocess.TimeoutExpired:
        report = {"bench": "serve_cp",
                  "error": f"child timed out after {CHILD_TIMEOUT_S}s"}

    ok, fence = check_fence(load_runs(ARTIFACT), report, tol_frac=tol)
    if not ok and justification is None:
        # fail CLOSED: the regressed row does NOT replace the committed
        # baseline — rerun with --allow-regression="why" if intended
        print(json.dumps({"ok": False,
                          "ticks_per_sec": report.get("ticks_per_sec"),
                          "cp_fence": fence,
                          "error": "control-plane ticks/sec regression vs "
                                   "committed CONTROL_PLANE.json row (row "
                                   "not merged; justify with "
                                   "--allow-regression)"}))
        return 1
    if not ok:
        report = {**report, "regression_justification": justification}
        fence = {**fence, "justified": justification}
    merge_runs(ARTIFACT, report, meta)
    print(json.dumps({"ok": "error" not in report,
                      "ticks_per_sec": report.get("ticks_per_sec"),
                      "requests_per_sec": report.get("requests_per_sec"),
                      "cp_fence": fence}))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(main(sys.argv[1:]))
