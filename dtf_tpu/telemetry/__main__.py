"""``python -m dtf_tpu.telemetry report|timeline`` — run analytics, ONE
JSON line (bench.py idiom: stdout's last line is always one JSON object).

    python -m dtf_tpu.telemetry report --logdir=/tmp/run/profile
    python -m dtf_tpu.telemetry report --logdir=... --hlo=step.hlo.txt \
        --flops=1.2e12 --peak=1.97e14 --n-devices=8 --chrome=trace.json
    python -m dtf_tpu.telemetry timeline --logdir=/tmp/run \
        [--events-dir=...] [--chrome=timeline.trace.json]

``timeline`` merges the fleet event plane with controller.jsonl,
heartbeat liveness files and postmortem dumps into one causally-ordered
run story + a derived SLO report (MTTR, swap/quarantine/excursion
episodes) — see :mod:`dtf_tpu.telemetry.timeline`. Deterministic: the
same logdir bytes yield a byte-identical report and chrome trace.

Parses the newest XPlane session under ``--logdir`` into per-category
device-time buckets, per-collective ``file:line`` provenance rows (when
``--hlo`` supplies the optimized HLO text of the profiled program(s)),
comm/compute overlap efficiency, and — with ``--flops``/``--peak`` — the
device-derived MFU cross-check. ``--chrome`` additionally writes a
Perfetto-loadable chrome-trace JSON of the device slices.

Parsing needs no backend, but importing the ``dtf_tpu`` package pulls
jax — so like ``python -m dtf_tpu.analysis`` this re-execs into a
CPU-pinned env first: a report must never take a chip that a run on the
same machine is using. Exit 0 even on a degraded parse (the reason rides
inside the JSON); exit 2 only when the reporter itself crashed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def _reexec_if_needed(argv: list[str]) -> None:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from _dtf_env import cpu_sim_env, is_cpu_sim

    if is_cpu_sim(os.environ, 1):
        return
    if os.environ.get("_DTF_TPU_TELEMETRY_REEXEC") == "1":
        return
    import subprocess

    env = cpu_sim_env(1, os.environ)
    env["_DTF_TPU_TELEMETRY_REEXEC"] = "1"
    env.setdefault("PYTHONPATH", root)
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.telemetry"] + argv,
        env=env, cwd=root, timeout=600)
    sys.exit(proc.returncode)


def _run_report(args) -> dict:
    from dtf_tpu.telemetry import profile as profile_mod

    site_map = None
    if args.hlo:
        from dtf_tpu.analysis.provenance import profile_site_map

        texts = []
        for p in args.hlo:
            with open(p) as f:
                texts.append(f.read())
        site_map = profile_site_map(texts)
    report = profile_mod.parse_logdir(
        args.logdir, site_map=site_map, step_name=args.step_name,
        model_flops_per_step=args.flops, peak_flops=args.peak,
        n_devices=args.n_devices)
    report["telemetry"] = "device_profile"
    if args.chrome:
        from dtf_tpu.telemetry.xplane import load_trace

        trace, reason = load_trace(args.logdir, step_name=args.step_name)
        if trace is not None:
            profile_mod.export_chrome_trace(args.chrome, trace=trace)
            report["chrome_trace"] = args.chrome
        else:
            report["chrome_trace_error"] = reason
    return report


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    try:
        _reexec_if_needed(argv)
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — the JSON-last-line contract
        print(json.dumps({"telemetry": "device_profile",
                          "error": f"reexec failed: {e}"}))
        return 2
    p = argparse.ArgumentParser(prog="python -m dtf_tpu.telemetry")
    sub = p.add_subparsers(dest="cmd", required=True)
    rep = sub.add_parser("report", help="parse an XPlane trace dir")
    rep.add_argument("--logdir", required=True,
                     help="profiler logdir (the ProfilerHook dir or a "
                          "plugins/profile/<ts> session)")
    rep.add_argument("--hlo", action="append", default=[],
                     help="optimized-HLO text file(s) of the profiled "
                          "program(s) for the file:line provenance join; "
                          "repeatable")
    rep.add_argument("--chrome", default="",
                     help="also write a Perfetto chrome-trace JSON here")
    rep.add_argument("--step-name", default="train",
                     help="StepTraceAnnotation name bounding each step")
    rep.add_argument("--flops", type=float, default=None,
                     help="model FLOPs per step (device-MFU cross-check)")
    rep.add_argument("--peak", type=float, default=None,
                     help="per-chip peak FLOP/s of the TRACED device "
                          "(telemetry.accounting.DEVICE_PEAKS); required "
                          "with --flops — this CLI runs where the trace "
                          "is read, not where it was taken")
    rep.add_argument("--n-devices", type=int, default=1)
    tl = sub.add_parser("timeline", help="merge a run's host-side trails "
                        "into one ordered timeline + SLO report")
    tl.add_argument("--logdir", required=True,
                    help="the run's logdir (holds controller.jsonl, "
                         "telemetry/, and/or the event plane)")
    tl.add_argument("--events-dir", default="",
                    help="event-plane directory when it is not the logdir "
                         "or <logdir>/events")
    tl.add_argument("--chrome", default="",
                    help="also write a Perfetto chrome-trace JSON here")
    args = p.parse_args(argv)
    if args.cmd == "timeline":
        from dtf_tpu.telemetry.timeline import build_timeline

        try:
            report = build_timeline(args.logdir,
                                    events_dir=args.events_dir or None,
                                    chrome=args.chrome)
        except Exception as e:  # noqa: BLE001 — one JSON line no matter what
            print(json.dumps({"telemetry": "timeline",
                              "error": f"{type(e).__name__}: {e}"}))
            return 2
        print(json.dumps(report, sort_keys=True))
        return 0
    if args.peak is None and args.flops is not None:
        p.error("--flops needs --peak: the traced device's per-chip peak "
                "(telemetry.accounting.DEVICE_PEAKS)")
    try:
        report = _run_report(args)
    except Exception as e:  # noqa: BLE001 — one JSON line no matter what
        print(json.dumps({"telemetry": "device_profile",
                          "error": f"{type(e).__name__}: {e}"}))
        return 2
    print(json.dumps(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
