#!/usr/bin/env bash
# Static lint gate: pyflakes over the package when available, otherwise the
# bundled AST linter (dtf_tpu/analysis/srclint.py — no-new-deps container
# policy), plus the analyzer's own source tree. Wired into the fast tier
# via tests/test_analysis.py::test_lint_script_clean.
#
#   scripts/lint.sh             # lint dtf_tpu/ + scripts/ + tests/
#   scripts/lint.sh --analyze   # + the static analyzer's cheap passes
#                               #   (host,specs,jaxpr,collective — no
#                               #   compiles)
#   scripts/lint.sh --full      # + the WHOLE analyzer (all passes incl.
#                               #   the AOT comms-budget fence AND the
#                               #   memory pass: HBM breakdown fence,
#                               #   state-accounting cross-check,
#                               #   donation soundness) — the
#                               #   pre-commit gate: exits non-zero on any
#                               #   error finding. The analysis CLI
#                               #   re-execs itself into the 8-device CPU
#                               #   sim (_dtf_env.cpu_sim_env), so it
#                               #   never takes a chip.
#   scripts/lint.sh PATH ...    # lint specific paths
set -u
ROOT="$(cd "$(dirname "$0")/.." && pwd)"
cd "$ROOT"

ANALYZE=0
FULL=0
if [ "${1:-}" = "--analyze" ]; then ANALYZE=1; shift; fi
if [ "${1:-}" = "--full" ]; then FULL=1; shift; fi

TARGETS=("$@")
if [ ${#TARGETS[@]} -eq 0 ]; then
  TARGETS=(dtf_tpu scripts tests bench.py chip_smoke.py __graft_entry__.py _dtf_env.py _dtf_watchdog.py)
fi

# Lint must not touch an accelerator backend: plain CPU, no device sim.
export JAX_PLATFORMS=cpu

if python -c "import pyflakes" 2>/dev/null; then
  echo "lint: pyflakes"
  # pyflakes ignores `# noqa` (a flake8 feature) and has no __init__.py
  # re-export exemption, so filter those two classes — otherwise the
  # repo's own clean tree fails wherever pyflakes happens to be installed
  # (srclint, the fallback, already honors both).
  python - "${TARGETS[@]}" <<'PYEOF'
import re, subprocess, sys
proc = subprocess.run([sys.executable, "-m", "pyflakes", *sys.argv[1:]],
                      capture_output=True, text=True)
kept = []
for line in proc.stdout.splitlines():
    m = re.match(r"(.+?):(\d+):(?:\d+:?)?\s*(.*)", line)
    if m:
        path, lno, msg = m.group(1), int(m.group(2)), m.group(3)
        if "imported but unused" in msg:
            if path.endswith("__init__.py"):
                continue
            try:
                with open(path) as f:
                    src = f.readlines()
                if "# noqa" in src[lno - 1]:
                    continue
            except OSError:
                pass
    kept.append(line)
print("\n".join(kept))
sys.stderr.write(proc.stderr)
sys.exit(1 if kept or proc.returncode > 1 else 0)
PYEOF
else
  echo "lint: srclint (pyflakes not installed)"
  python -m dtf_tpu.analysis.srclint "${TARGETS[@]}"
fi
rc=$?
[ $rc -ne 0 ] && exit $rc

if [ "$ANALYZE" = "1" ]; then
  echo "lint: dtf_tpu.analysis (host,specs,jaxpr,collective)"
  python -m dtf_tpu.analysis --passes=host,specs,jaxpr,collective
  rc=$?
fi

if [ "$FULL" = "1" ]; then
  echo "lint: dtf_tpu.analysis (all passes incl. comms + memory fences)"
  # the CLI exits 1 on any error finding and 2 on a crash — srclint above
  # plus this is the whole static gate (docs/ANALYSIS.md)
  python -m dtf_tpu.analysis
  rc=$?
fi

exit $rc
