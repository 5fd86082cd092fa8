#!/usr/bin/env python
"""Telemetry-enabled LM training run → the committed TELEMETRY.json artifact.

Runs a short GPT training loop (synthetic data) with the full telemetry
stack on — step-phase spans, MFU/goodput accounting, the compile fence,
the flight recorder — and merges the resulting RunReport into
TELEMETRY.json with round timestamps (the BENCH_LM.json artifact pattern:
bounded history, sections survive re-runs): an on-chip goodput/MFU/
phase-breakdown row next to the throughput benches.

Same resilience contract as bench.py / bench_cost_table.py: this parent
NEVER imports jax, the child runs under the watchdog behind a probe-first
budget, and the artifact is always written (a report row or a structured
error). CPU-sim runs work any round (tiny config; logic check) — pass
DTF_TEL_TINY=1 or just run without a chip and let the probe route it.

MFU REGRESSION FENCE (ROADMAP item 3 — hold the line once won): a tpu
row whose ``mfu`` falls more than ``--mfu-tol`` (rel., default 10%)
below the newest committed TELEMETRY.json row of the SAME config fails
CLOSED — exit 1, the regressed row is NOT merged, the committed artifact
keeps the golden. An intentional change rides
``--allow-mfu-regression="<why>"`` (the comms-budget --write-golden
idiom): the new row merges with the justification recorded and becomes
the next baseline. CPU-sim rows are never fenced — sim MFU is a logic
check, not a measurement.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from _dtf_artifact import load_runs, merge_runs, same_config as _same

ARTIFACT = os.path.join(ROOT, "TELEMETRY.json")
SENTINEL = "TELEMETRY_REPORT "
CHILD_TIMEOUT_S = 900
TOTAL_BUDGET_S = float(os.environ.get("DTF_TEL_BUDGET_S", "1200"))
MFU_TOL_DEFAULT = float(os.environ.get("DTF_TEL_MFU_TOL", "0.10"))

#: the identity of a telemetry row for fence purposes — rows measured
#: under different shapes/models/backends are never comparable.
CONFIG_KEYS = ("backend", "model", "tiny", "batch", "seq")


def same_config(a, b) -> bool:
    return _same(a, b, CONFIG_KEYS)


def fence_baseline(prev_runs, report):
    """Newest committed row comparable to ``report`` that carries a
    measured mfu (error rows and mfu-less rows can't be baselines)."""
    for row in reversed(prev_runs or []):
        if ("error" not in row and row.get("mfu") is not None
                and same_config(row, report)):
            return row
    return None


def check_mfu_fence(prev_runs, report, *, tol_frac=MFU_TOL_DEFAULT):
    """``(ok, detail)`` — ok=False means a tpu row regressed beyond
    tolerance vs its committed baseline (the fail-closed case). CPU rows
    and first-of-config rows pass with an explanatory detail."""
    backend = report.get("backend")
    if backend in (None, "cpu"):
        return True, {"fenced": False, "reason": "cpu-sim row (logic "
                                                 "check, never fenced)"}
    if "error" in report or report.get("mfu") is None:
        return True, {"fenced": False, "reason": "no measured mfu in row"}
    base = fence_baseline(prev_runs, report)
    if base is None:
        return True, {"fenced": False,
                      "reason": "no committed baseline for this config"}
    floor = base["mfu"] * (1.0 - tol_frac)
    detail = {"fenced": True, "baseline_mfu": base["mfu"],
              "baseline_ts": base.get("ts"), "mfu": report["mfu"],
              "floor": round(floor, 8), "tol_frac": tol_frac}
    return report["mfu"] >= floor, detail


def child():
    import jax
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.mesh import make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.hooks import LoggingHook, StopAtStepHook
    from dtf_tpu.loop import Trainer
    from dtf_tpu.metrics import MetricWriter
    from dtf_tpu.models import gpt
    from dtf_tpu.telemetry import (Telemetry, analytic_lm_flops_per_step,
                                   param_count)

    tiny = os.environ.get("DTF_TEL_TINY") == "1"
    # batch must divide over the data axis (8-way on the CPU sim)
    b = int(os.environ.get("DTF_TEL_BATCH", "8"))
    s = int(os.environ.get("DTF_TEL_SEQ", "64" if tiny else "512"))
    n_steps = int(os.environ.get("DTF_TEL_STEPS", "12"))
    cfg = gpt.GPTConfig.tiny() if tiny else gpt.GPTConfig.gpt2_small()

    mesh = make_mesh()
    # global-batch FLOPs vs the whole mesh's peak (n_devices divisor)
    tel = Telemetry(min_stall_s=300.0, n_devices=mesh.devices.size)
    model, init_fn = gpt.make_init(cfg, mesh, seq_len=s)
    tx = optax.adamw(1e-4)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh, param_rules=gpt.tp_rules)
    step = tr.make_train_step(gpt.make_loss(model), tx, mesh, shardings,
                              telemetry=tel)
    tokens = b * s
    tel.set_throughput_model(
        tokens_per_step=tokens,
        model_flops_per_step=analytic_lm_flops_per_step(
            n_params=param_count(state.params), layers=cfg.layers,
            width=cfg.d_model, seq_len=s, tokens_per_step=tokens))

    data = SyntheticData("gpt", b, seed=0, seq_len=s,
                         vocab_size=cfg.vocab_size)
    trainer = Trainer(
        step, mesh,
        hooks=[LoggingHook(MetricWriter(None, also_log=False), 4,
                           tokens_per_step=tokens, telemetry=tel),
               StopAtStepHook(n_steps)],
        telemetry=tel)
    trainer.fit(state, iter(data))
    report = tel.finish({
        "backend": jax.default_backend(),
        "n_devices": mesh.devices.size,
        "model": "gpt", "tiny": tiny, "batch": b, "seq": s})
    print(SENTINEL + json.dumps(report))


def _parse_args(argv):
    """--mfu-tol=X and --allow-mfu-regression="why" (no argparse: the
    --child re-invocation must pass through untouched)."""
    tol, justification = MFU_TOL_DEFAULT, None
    for a in argv:
        if a.startswith("--mfu-tol="):
            tol = float(a.split("=", 1)[1])
        elif a.startswith("--allow-mfu-regression="):
            justification = a.split("=", 1)[1]
        elif a == "--allow-mfu-regression":
            justification = "(no reason given)"
    return tol, justification


def main(argv=()):
    from _dtf_watchdog import Budget, child_argv, probe_backend, \
        run_watchdogged

    tol, justification = _parse_args(argv)
    budget = Budget(TOTAL_BUDGET_S)
    meta = {"ts": round(time.time(), 1),
            "round": os.environ.get("DTF_ROUND", "")}
    backend, errs = probe_backend(
        timeout_s=min(90, max(10.0, budget.remaining(10))),
        env=dict(os.environ))
    if backend is None:
        merge_runs(ARTIFACT, {
            "telemetry": "run_report_error",
            "error": ("backend unavailable (probe failed): "
                      + "; ".join(errs))[:2000]}, meta)
        print(json.dumps({"error": "probe failed"}))
        return 0

    def parse(line):
        if line.startswith(SENTINEL):
            try:
                return json.loads(line[len(SENTINEL):])
            except ValueError:
                return None
        return None

    report, errors = run_watchdogged(
        child_argv(os.path.abspath(__file__)), parse,
        timeout_s=min(CHILD_TIMEOUT_S, max(60.0, budget.remaining(30))),
        retries=1, backoff_s=0, env=dict(os.environ))
    if report is None:
        report = {"telemetry": "run_report_error",
                  "error": (f"probe OK (backend={backend}) but telemetry "
                            "run failed: " + "; ".join(errors))[:2000]}

    # ---- MFU regression fence (vs the COMMITTED artifact, pre-merge) ----
    ok, fence = check_mfu_fence(load_runs(ARTIFACT), report, tol_frac=tol)
    if not ok and justification is None:
        # fail CLOSED: the regressed row does NOT replace the committed
        # baseline — rerun with --allow-mfu-regression="why" if intended
        print(json.dumps({"ok": False, "backend": backend,
                          "mfu": report.get("mfu"), "mfu_fence": fence,
                          "error": "mfu regression vs committed "
                                   "TELEMETRY.json row (row not merged; "
                                   "justify with --allow-mfu-regression)"}))
        return 1
    if not ok:
        report = {**report, "mfu_justification": justification}
        fence = {**fence, "justified": justification}
    merge_runs(ARTIFACT, report, meta)
    print(json.dumps({"ok": "error" not in report,
                      "backend": backend,
                      "mfu": report.get("mfu"),
                      "mfu_fence": fence,
                      "goodput": report.get("goodput_buckets",
                                            {}).get("goodput")}))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(main(sys.argv[1:]))
