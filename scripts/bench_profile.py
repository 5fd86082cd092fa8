#!/usr/bin/env python
"""Device-time attribution bench → the committed DEVICE_PROFILE.json.

Runs the GPT train step AOT-compiled on whatever backend answers, captures
an XPlane window over N annotated steps, and parses it with
dtf_tpu/telemetry/profile.py into per-category device-time buckets (MXU / Pallas / fusions /
collectives by kind), per-collective ``file:line`` provenance (the
compiled program's own optimized HLO supplies the join table — no second
trace), measured comm/compute overlap efficiency for the ppermute rings,
and the device-derived MFU cross-check of the analytic one.

Process contract: the parent NEVER imports jax, asks a short probe child
which backend answers (the CPU needs an extra flag, below), runs the
child under the watchdog inside a hard budget, always writes the artifact
(a row or a structured error), and prints EXACTLY ONE JSON line with rc 0
even without a backend (kept until the benchmark PR turns this into a
cell — ROADMAP C1). On the CPU sim the parent adds
``--xla_cpu_enable_xprof_traceme=true`` so the backend emits the per-op
events (a logic check).

REGRESSION FENCE (the comms-budget fail-closed idiom): a tpu row whose
``mfu_device`` falls more than ``--tol`` (rel., default 10%) below — or
whose ring ``hidden_frac`` drops more than ``--overlap-tol`` (abs.,
default 0.10) under — the newest committed same-config row fails closed:
exit 1, row not merged. Intentional changes ride
``--allow-regression="<why>"``, which merges the row with the
justification recorded.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from _dtf_artifact import load_runs, merge_runs, same_config as _same

ARTIFACT = os.environ.get("DTF_PROF_ARTIFACT",
                          os.path.join(ROOT, "DEVICE_PROFILE.json"))
SENTINEL = "DEVICE_PROFILE_ROW "
CHILD_TIMEOUT_S = 900
TOTAL_BUDGET_S = float(os.environ.get("DTF_PROF_BUDGET_S", "1200"))
MFU_TOL_DEFAULT = float(os.environ.get("DTF_PROF_MFU_TOL", "0.10"))
OVERLAP_TOL_DEFAULT = float(os.environ.get("DTF_PROF_OVERLAP_TOL", "0.10"))
CPU_OP_TRACE_FLAG = "--xla_cpu_enable_xprof_traceme=true"

CONFIG_KEYS = ("backend", "model", "tiny", "batch", "seq")


def child():
    import tempfile

    import jax
    import optax

    from _dtf_watchdog import fence
    from dtf_tpu.analysis.provenance import profile_site_map
    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import shard_batch
    from dtf_tpu.core.mesh import make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.models import gpt
    from dtf_tpu.telemetry import (analytic_lm_flops_per_step,
                                   param_count)
    from dtf_tpu.telemetry import profile as profile_mod
    from dtf_tpu.telemetry.accounting import device_peak_flops
    from dtf_tpu.telemetry.xplane import load_trace

    tiny = os.environ.get("DTF_PROF_TINY") == "1" \
        or jax.default_backend() == "cpu"
    b = int(os.environ.get("DTF_PROF_BATCH", "8"))
    s = int(os.environ.get("DTF_PROF_SEQ", "64" if tiny else "512"))
    n_steps = int(os.environ.get("DTF_PROF_STEPS", "4"))
    cfg = gpt.GPTConfig.tiny() if tiny else gpt.GPTConfig.gpt2_small()

    mesh = make_mesh()
    model, init_fn = gpt.make_init(cfg, mesh, seq_len=s)
    tx = optax.adamw(1e-4)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh, param_rules=gpt.tp_rules)
    step = tr.make_train_step(gpt.make_loss(model), tx, mesh, shardings)
    data = SyntheticData("gpt", b, seed=0, seq_len=s,
                         vocab_size=cfg.vocab_size)
    batches = [shard_batch(data.batch(i), mesh) for i in range(2)]
    # ONE AOT program: the compiled step both runs the loop and supplies
    # the optimized-HLO text whose instruction names join profiled
    # collective events back to their Python file:line (no second trace)
    # the ONE-AOT-program contract above needs the compiled object's
    # aot-ok: HLO text — bench-local, not a fleet program
    compiled = step.lower(state, batches[0]).compile()
    site_map = profile_site_map(compiled.as_text())

    for i in range(2):                                   # warm + settle
        state, _ = compiled(state, batches[i % 2])
    fence(state.step)

    trace_dir = tempfile.mkdtemp(prefix="dtf_profile_")
    jax.profiler.start_trace(trace_dir)
    for i in range(n_steps):
        with jax.profiler.StepTraceAnnotation("train", step_num=i):
            state, _ = compiled(state, batches[i % 2])
    fence(state.step)        # device work must land INSIDE the window
    jax.profiler.stop_trace()

    flops = analytic_lm_flops_per_step(
        n_params=param_count(state.params), layers=cfg.layers,
        width=cfg.d_model, seq_len=s, tokens_per_step=b * s)
    trace, reason = load_trace(trace_dir)
    if trace is None:
        report = {"degraded": reason}
    else:
        report = profile_mod.analyze(
            trace, site_map=site_map, model_flops_per_step=flops,
            peak_flops=device_peak_flops(), n_devices=mesh.devices.size)
        # bound the artifact row: the long tail of tiny collective sites
        # is in the trace dir, not the committed JSON
        report["collectives"] = report.get("collectives", [])[:20]
    report.update({
        "telemetry": "device_profile",
        "backend": jax.default_backend(), "model": "gpt", "tiny": tiny,
        "batch": b, "seq": s, "steps_traced": n_steps,
        "n_devices": int(mesh.devices.size),
        "model_flops_per_step": flops, "trace_dir": trace_dir})
    print(SENTINEL + json.dumps(report))


def same_config(a, b) -> bool:
    return _same(a, b, CONFIG_KEYS)


def _ring_hidden_frac(row):
    ov = row.get("overlap") or {}
    ring = ov.get("collective-permute")
    return ring.get("hidden_frac") if ring else None


def fence_baseline(prev_runs, report):
    for row in reversed(prev_runs or []):
        if ("error" not in row and "degraded" not in row
                and row.get("mfu_device") is not None
                and same_config(row, report)):
            return row
    return None


def check_profile_fence(prev_runs, report, *, mfu_tol=MFU_TOL_DEFAULT,
                        overlap_tol=OVERLAP_TOL_DEFAULT):
    """``(ok, detail)`` — fail closed when a tpu row's device MFU drops
    beyond ``mfu_tol`` (relative) or the ppermute-ring overlap efficiency
    drops beyond ``overlap_tol`` (absolute) vs the committed baseline.
    CPU-sim rows are never fenced (one host plane folds 8 sim devices —
    sim overlap is a logic check, docs/OBSERVABILITY.md)."""
    backend = report.get("backend")
    if backend in (None, "cpu"):
        return True, {"fenced": False, "reason": "cpu-sim row"}
    if "error" in report or report.get("mfu_device") is None:
        return True, {"fenced": False, "reason": "no measured mfu_device"}
    base = fence_baseline(prev_runs, report)
    if base is None:
        return True, {"fenced": False,
                      "reason": "no committed baseline for this config"}
    detail = {"fenced": True, "baseline_ts": base.get("ts")}
    ok = True
    floor = base["mfu_device"] * (1.0 - mfu_tol)
    detail["mfu_device"] = {"got": report["mfu_device"],
                            "baseline": base["mfu_device"],
                            "floor": round(floor, 8), "tol_frac": mfu_tol}
    if report["mfu_device"] < floor:
        ok = False
    got_ring, base_ring = _ring_hidden_frac(report), _ring_hidden_frac(base)
    if got_ring is not None and base_ring is not None:
        detail["ring_hidden_frac"] = {
            "got": got_ring, "baseline": base_ring,
            "floor": round(base_ring - overlap_tol, 4),
            "tol_abs": overlap_tol}
        if got_ring < base_ring - overlap_tol:
            ok = False
    return ok, detail


def _parse_args(argv):
    mfu_tol, overlap_tol, justification = \
        MFU_TOL_DEFAULT, OVERLAP_TOL_DEFAULT, None
    for a in argv:
        if a.startswith("--tol="):
            mfu_tol = float(a.split("=", 1)[1])
        elif a.startswith("--overlap-tol="):
            overlap_tol = float(a.split("=", 1)[1])
        elif a.startswith("--allow-regression="):
            justification = a.split("=", 1)[1]
        elif a == "--allow-regression":
            justification = "(no reason given)"
    return mfu_tol, overlap_tol, justification


def main(argv=()):
    from _dtf_watchdog import Budget, child_argv, probe_backend, \
        run_watchdogged

    mfu_tol, overlap_tol, justification = _parse_args(argv)
    budget = Budget(TOTAL_BUDGET_S)
    meta = {"ts": round(time.time(), 1),
            "round": os.environ.get("DTF_ROUND", "")}
    backend, errs = probe_backend(
        timeout_s=min(90, max(10.0, budget.remaining(10))),
        env=dict(os.environ))
    if backend is None:
        merge_runs(ARTIFACT, {
            "telemetry": "device_profile_error",
            "error": ("backend unavailable (probe failed): "
                      + "; ".join(errs))[:2000]}, meta)
        print(json.dumps({"error": "probe failed"}))
        return 0

    env = dict(os.environ)
    if backend == "cpu":
        # the CPU backend only emits per-op TraceMe events behind this
        # flag (xplane.py CPU_OP_TRACE_FLAG) — without it the sim round
        # trip degrades to step windows with no buckets
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " "
                            + CPU_OP_TRACE_FLAG).strip()

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
        retries=1, backoff_s=0, env=env)
    if report is None:
        report = {"telemetry": "device_profile_error",
                  "error": (f"probe OK (backend={backend}) but profile "
                            "run failed: " + "; ".join(errors))[:2000]}

    ok, fence = check_profile_fence(load_runs(ARTIFACT), report, mfu_tol=mfu_tol,
                                    overlap_tol=overlap_tol)
    if not ok and justification is None:
        print(json.dumps({"ok": False, "backend": backend,
                          "mfu_device": report.get("mfu_device"),
                          "profile_fence": fence,
                          "error": "device-profile regression vs "
                                   "committed DEVICE_PROFILE.json row "
                                   "(row not merged; justify with "
                                   "--allow-regression)"}))
        return 1
    if not ok:
        report = {**report, "regression_justification": justification}
        fence = {**fence, "justified": justification}
    merge_runs(ARTIFACT, report, meta)
    buckets = report.get("buckets") or {}
    print(json.dumps({
        "ok": "error" not in report,
        "backend": backend,
        "mfu_device": report.get("mfu_device"),
        "device_busy_frac": (report.get("steps") or {}).get(
            "device_busy_frac"),
        "top_buckets": sorted(
            ((k, v["frac"]) for k, v in buckets.items()),
            key=lambda kv: -kv[1])[:4],
        "profile_fence": fence}))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(main(sys.argv[1:]))
