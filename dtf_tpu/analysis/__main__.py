"""``python -m dtf_tpu.analysis`` — run every static pass, print ONE JSON line.

bench.py's resilience idiom: stdout's LAST line is always exactly one JSON
object, whatever the backend situation.  The analyzer never needs a chip —
but it does need the 8-device CPU sim, so if the calling environment is not
already pinned there (a shell on the machine with the chip, say) it
re-execs itself into a CPU-pinned child exactly like
``__graft_entry__.dryrun_multichip`` — and never takes the chip.

    python -m dtf_tpu.analysis                       # all configs, all passes
    python -m dtf_tpu.analysis --configs=bert,gpt    # subset
    python -m dtf_tpu.analysis --passes=specs,jaxpr,collective   # no compile
    python -m dtf_tpu.analysis --write-golden        # regenerate the fence
    python -m dtf_tpu.analysis --diff                # per-line provenance +
                                                     # memory-field delta vs
                                                     # golden (PR review aid)
    python -m dtf_tpu.analysis fit --config=gpt_serve --hbm-gb=16
                                                     # HBM fit planner: max
                                                     # KV slots (bf16+int8)
                                                     # / max global batch

Exit status: 0 = no error findings, 1 = findings, 2 = analyzer crashed.
The non-zero-on-error contract is what makes ``scripts/lint.sh --full``
usable as a pre-commit gate.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

N_DEVICES = 8


def _reexec_if_needed(argv: list[str]) -> None:
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from _dtf_env import cpu_sim_env, is_cpu_sim

    if is_cpu_sim(os.environ, N_DEVICES):
        return
    if os.environ.get("_DTF_TPU_ANALYSIS_REEXEC") == "1":
        return
    import subprocess

    env = cpu_sim_env(N_DEVICES, os.environ)
    env["_DTF_TPU_ANALYSIS_REEXEC"] = "1"
    env.setdefault("PYTHONPATH", root)
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis"] + argv,
        env=env, cwd=root, timeout=1800)
    sys.exit(proc.returncode)


def _fit_main(argv: list[str]) -> int:
    """``python -m dtf_tpu.analysis fit`` — the HBM fit planner."""
    parser = argparse.ArgumentParser(
        prog="python -m dtf_tpu.analysis fit",
        description="Invert the static memory model: what fits a chip.")
    parser.add_argument("--config", required=True,
                        help="registry config name (serve configs answer "
                             "max KV slots bf16+int8; train configs max "
                             "global batch)")
    parser.add_argument("--hbm-gb", type=float, required=True,
                        help="per-chip HBM budget in GiB (v5e: 16)")
    parser.add_argument("--max-len", type=int, default=1024,
                        help="serve: per-slot cache length (prompt + "
                             "generated tokens)")
    parser.add_argument("--kv-page-size", type=int, default=64,
                        help="serve: prefix-cache page size in tokens")
    parser.add_argument("--slots", type=int, default=None,
                        help="serve: fix the slot count and report the "
                             "page-pool size the remaining HBM buys")
    parser.add_argument("--opt", default=None,
                        help="train: optimizer family to price moments "
                             "for (default: the config's launcher family)")
    parser.add_argument("--grad-accum", type=int, default=1,
                        help="train: price a grad_accum f32 accumulator")
    parser.add_argument("--grad-shard", action="store_true",
                        help="train: accumulator ZeRO-1-sharded over data")
    parser.add_argument("--act-scale", type=float, default=None,
                        help="train: activation-slope multiplier "
                             "(≈ (L·T·d)_real/(L·T·d)_program) — switches "
                             "the resident side to the real-scale spec "
                             "view")
    parser.add_argument("--hosts", type=int, default=None,
                        help="train: the mesh is split across N hosts — "
                             "price the elastic shrink (with --lost) "
                             "before the controller relaunches "
                             "(docs/RESILIENCE.md)")
    parser.add_argument("--lost", type=int, default=0,
                        help="train: hosts lost; the survivor mesh "
                             "(data axis scaled down) is priced at the "
                             "SAME global batch next to the full mesh")
    parser.add_argument("--precision", default=None,
                        choices=("int8", "fp8"),
                        help="price the low-precision tier next to bf16: "
                             "serve configs report max_slots with 8-bit "
                             "weights (+ per-channel scale sideband), "
                             "train configs the activation-temp shrink "
                             "(docs/ANALYSIS.md, docs/TUNING.md)")
    parser.add_argument("--log-sink", action="store_true",
                        help="serve: price the request log sink (ISSUE "
                             "19) next to the fleet — it is host-side "
                             "file IO with zero device readbacks, so the "
                             "answer is an explicit HBM no-op (the row "
                             "exists so capacity planning can SAY so "
                             "instead of leaving it to folklore)")
    args = parser.parse_args(argv)

    from dtf_tpu.analysis import configs as cfgs
    from dtf_tpu.analysis import memory as memory_pass

    if args.config not in cfgs.BY_NAME:
        print(json.dumps({"ok": False,
                          "error": f"unknown config {args.config!r}; have "
                                   f"{sorted(cfgs.BY_NAME)}"}))
        return 2
    try:
        out = memory_pass.fit(
            args.config, hbm_gb=args.hbm_gb, max_len=args.max_len,
            kv_page_size=args.kv_page_size, slots=args.slots, opt=args.opt,
            grad_accum=args.grad_accum, grad_shard=args.grad_shard,
            act_scale=args.act_scale, hosts=args.hosts, lost=args.lost,
            precision=args.precision, log_sink=args.log_sink)
    except Exception as e:  # noqa: BLE001 — last line must still be JSON
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:500]}))
        return 2
    print(json.dumps({"ok": True, **out}))
    return 0


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    try:
        _reexec_if_needed(argv)
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — the JSON-last-line contract
        # (child timeout, missing _dtf_env, ...) must hold even when the
        # bootstrap itself dies — exactly the TPU-pointed environments the
        # re-exec exists to protect.
        print(json.dumps({"ok": False,
                          "error": f"bootstrap: {type(e).__name__}: "
                                   f"{e}"[:500]}))
        return 2

    if argv and argv[0] == "fit":
        return _fit_main(argv[1:])

    parser = argparse.ArgumentParser(prog="python -m dtf_tpu.analysis")
    parser.add_argument("--configs", default="",
                        help="comma-separated registry names (default all)")
    parser.add_argument("--passes",
                        default="host,specs,jaxpr,collective,hlo,memory",
                        help="comma-separated passes to run")
    parser.add_argument("--write-golden", action="store_true",
                        help="regenerate STATIC_ANALYSIS.json comms + "
                             "memory budgets")
    parser.add_argument("--golden", default="",
                        help="override golden path")
    parser.add_argument("--diff", action="store_true",
                        help="print the per-source-line collective "
                             "provenance delta vs the golden (PR review "
                             "aid; compiles, no findings verdict)")
    args = parser.parse_args(argv)

    from dtf_tpu.analysis import configs as cfgs
    from dtf_tpu.analysis import hlo as hlo_pass
    from dtf_tpu.analysis import runner
    from dtf_tpu.analysis.findings import severity_counts

    names = [n for n in args.configs.split(",") if n]
    for n in names:
        if n not in cfgs.BY_NAME:
            print(json.dumps({"ok": False,
                              "error": f"unknown config {n!r}; have "
                                       f"{sorted(cfgs.BY_NAME)}"}))
            return 2
    passes = [p for p in args.passes.split(",") if p]
    bad = [p for p in passes if p not in runner.ALL_PASSES]
    if bad:
        # a typo'd pass must not silently disable the fence (exit 0, ran
        # nothing) — same contract as unknown --configs
        print(json.dumps({"ok": False,
                          "error": f"unknown passes {bad}; valid: "
                                   f"{','.join(runner.ALL_PASSES)}"}))
        return 2
    golden_file = args.golden or runner.golden_path()

    try:
        if args.write_golden:
            budgets = {
                c.name: runner.compile_budget(c)
                for c in (cfgs.REGISTRY if not names
                          else [cfgs.BY_NAME[n] for n in names])}
            import jax

            existing = (hlo_pass.load_golden(golden_file).get("budgets", {})
                        if os.path.exists(golden_file) else {})
            existing.update(budgets)
            hlo_pass.save_golden(
                golden_file, existing,
                meta={"jax": jax.__version__, "devices": N_DEVICES,
                      "regen": "python -m dtf_tpu.analysis --write-golden",
                      "note": "comms budget of each config's tiny AOT-"
                              "compiled train step on the 8-device CPU sim"})
            print(json.dumps({"ok": True, "wrote": golden_file,
                              "configs": sorted(budgets)}))
            return 0

        golden = (hlo_pass.load_golden(golden_file)
                  if os.path.exists(golden_file) else {"budgets": {}})

        if args.diff:
            # review aid, not a verdict: compile each config, print the
            # per-line provenance delta AND the per-field memory delta vs
            # golden as plain lines, keep the one-JSON-last-line contract
            # with a summary object.
            from dtf_tpu.analysis import memory as memory_pass
            from dtf_tpu.analysis import provenance

            diff_counts = {}
            for c in (cfgs.REGISTRY if not names
                      else [cfgs.BY_NAME[n] for n in names]):
                budget = runner.compile_budget(c)
                want = golden.get("budgets", {}).get(c.name, {})
                lines = provenance.provenance_delta(
                    budget.get("provenance"), want.get("provenance"))
                lines += memory_pass.memory_delta(
                    budget.get("memory"), want.get("memory"))
                diff_counts[c.name] = len(lines)
                for line in lines:
                    print(f"{c.name}: {line}")
            print(json.dumps({"ok": True, "mode": "diff",
                              "changed_lines": diff_counts}))
            return 0

        budgets: dict = {}
        findings = runner.analyze(names or None, passes, golden=golden,
                                  budgets_out=budgets)
    except Exception as e:  # noqa: BLE001 — last line must still be JSON
        print(json.dumps({"ok": False,
                          "error": f"{type(e).__name__}: {e}"[:500]}))
        return 2

    counts = severity_counts(findings)
    out = {
        "ok": counts["error"] == 0,
        "configs": names or sorted(cfgs.BY_NAME),
        "passes": passes,
        "findings": counts["error"] + counts["warning"],
        "severities": counts,
        "details": [f.to_json() for f in findings
                    if f.severity != "info"][:50],
    }
    if budgets:
        # per-config collective-bytes delta vs the committed golden, so a
        # PR's comms cost shows up in its analysis line (0 everywhere on a
        # clean fence; a drift here pairs with a hlo finding above).
        gb = golden.get("budgets", {})
        out["comms_delta_bytes"] = {
            name: b["total"]["bytes"]
            - gb.get(name, {}).get("total", {}).get("bytes", 0)
            for name, b in sorted(budgets.items())}
        # per-config peak temp allocation (AOT memory_analysis) — the HBM
        # where grad accumulators and activation stashes live; the
        # bert_accum vs bert_grad_shard rows show the --grad_shard
        # accumulator shrink at a glance (docs/ZERO.md).
        out["temp_bytes"] = {
            name: b.get("memory", {}).get("temp_bytes", 0)
            for name, b in sorted(budgets.items())}
        # per-config peak-resident estimate (args + outputs + temps +
        # code − donated aliases) — the number the fit planner budgets
        # against a chip's HBM.
        from dtf_tpu.analysis import memory as memory_pass

        out["hbm_peak_bytes"] = {
            name: memory_pass.hbm_peak_bytes(b.get("memory", {}))
            for name, b in sorted(budgets.items())}
    print(json.dumps(out))
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
