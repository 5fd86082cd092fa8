#!/usr/bin/env python
"""Low-precision matmul A/B: bf16 vs int8 vs fp8 at the tp_dense sites
(ISSUE 17; docs/TUNING.md "Precision winners").

Each child times ONE (shape, precision) cell with the scan-amortized
loop proven in bench_attention (many iterations inside one jitted
``lax.scan``, null-jit round trip subtracted — one dispatch's fixed cost
would swamp a 768x3072 matmul)
and reports the quality bound next to the speed: ``rel_err`` is the
Frobenius relative error vs the f32 reference on the SAME operands.
Selection happens later, in ``tune.search.select_precision_winner``:
fastest ``matmul_s`` among rows inside the rel-err ceiling, bf16 exempt.

On a TPU backend the rows bank into KERNEL_TUNE_SWEEP.json
``precision_rows`` (replace-by-identity, crash-safe after every row) and
the committed KERNEL_TUNE.json golden is re-seeded from them — same
contract as bench_tune's flash rows: the golden stays re-derivable from
committed artifacts. On the CPU sim the sweep is a tiny wiring check
(interpret-grade timings are not MXU-predictive) and rows land ONLY in
BENCH_QUANT.json, never the committed sweep artifact.

Process contract: the parent never imports jax (its children need the
chip, one after the other), prints ONE JSON line last, and exits 0 even
without a backend (kept until the benchmark PR — ROADMAP C1).
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "BENCH_QUANT.json")
SENTINEL = "QUANT_ROW "
CHILD_TIMEOUT_S = 600
TOTAL_BUDGET_S = float(os.environ.get("DTF_QUANT_BUDGET_S", "3600"))
PROBE_TIMEOUT_S = 90

#: the tp_dense sites worth a winner: the GPT-2-small flagship's four
#: projections (qkv/attn-proj column 768x768, mlp_in column 768x3072,
#: attn_out row 768x768, mlp_out row 3072x768) and the gpt2_draft twin
#: at d384/ff1536 — the shapes the serving draft actually runs.
QUANT_SITES = (
    {"parallel": "column", "d_in": 768, "d_out": 768},
    {"parallel": "column", "d_in": 768, "d_out": 3072},
    {"parallel": "row", "d_in": 768, "d_out": 768},
    {"parallel": "row", "d_in": 3072, "d_out": 768},
    {"parallel": "column", "d_in": 384, "d_out": 384},
    {"parallel": "column", "d_in": 384, "d_out": 1536},
    {"parallel": "row", "d_in": 384, "d_out": 384},
    {"parallel": "row", "d_in": 1536, "d_out": 384},
)
PRECISIONS = ("bf16", "int8", "fp8")
#: CPU-sim wiring-check cell (one site, bf16+int8; fp8 exercises the
#: same code path as int8 and interpret timing is meaningless anyway).
CPU_SITES = ({"parallel": "column", "d_in": 16, "d_out": 32},)
CPU_PRECISIONS = ("bf16", "int8")


def _job(site, precision, *, b=8, t=1024):
    return {"DTF_QUANT_PARALLEL": site["parallel"],
            "DTF_QUANT_D_IN": str(site["d_in"]),
            "DTF_QUANT_D_OUT": str(site["d_out"]),
            "DTF_QUANT_B": str(b), "DTF_QUANT_T": str(t),
            "DTF_QUANT_PRECISION": precision}


def child():
    import statistics

    import jax
    import jax.numpy as jnp
    from jax import lax

    from dtf_tpu.ops import quant

    parallel = os.environ["DTF_QUANT_PARALLEL"]
    d_in = int(os.environ["DTF_QUANT_D_IN"])
    d_out = int(os.environ["DTF_QUANT_D_OUT"])
    b = int(os.environ.get("DTF_QUANT_B", "8"))
    t = int(os.environ.get("DTF_QUANT_T", "1024"))
    precision = os.environ.get("DTF_QUANT_PRECISION", "int8")
    reps = int(os.environ.get("DTF_QUANT_REPS", "50"))

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (b, t, d_in), jnp.bfloat16)
    w = (jax.random.normal(kw, (d_in, d_out), jnp.bfloat16)
         / jnp.bfloat16(d_in ** 0.5))

    if precision == "bf16":
        mm = lambda a: jnp.einsum("btd,df->btf", a, w)  # noqa: E731
    else:
        mm = lambda a: quant.quantized_matmul(  # noqa: E731
            a, w, precision=precision)

    # quality bound on the same operands the timing loop runs (f32 ref)
    ref = jnp.einsum("btd,df->btf", x.astype(jnp.float32),
                     w.astype(jnp.float32))
    err = float(quant.rel_err(jax.jit(mm)(x), ref))

    def med_timed(fn, *args, n=3):
        float(fn(*args))  # compile + warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            float(fn(*args))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    null_s = med_timed(jax.jit(lambda v: v * 2.0), jnp.float32(1.0), n=5)

    # scan-amortized: the carry folds the output back into the next
    # iteration's activations at 1e-30 (rounds away in bf16, but XLA
    # cannot hoist the loop-invariant matmul out of the scan body).
    @jax.jit
    def loop(x0):
        def body(c, _):
            y = mm(c)
            return c + jnp.bfloat16(1e-30) * y.astype(
                jnp.float32).sum().astype(jnp.bfloat16), None

        out, _ = lax.scan(body, x0, None, length=reps)
        return out.astype(jnp.float32).sum()

    total = med_timed(loop, x)
    matmul_s = max(total - null_s, reps * 1e-7) / reps
    flops = 2.0 * b * t * d_in * d_out
    print(SENTINEL + json.dumps({
        "parallel": parallel, "d_in": d_in, "d_out": d_out, "b": b, "t": t,
        "dtype": "bfloat16", "precision": precision,
        "backend": jax.default_backend(), "n_devices": 1,
        "matmul_s": round(matmul_s, 9),
        "matmul_tflops": round(flops / matmul_s / 1e12, 3),
        "rel_err": round(err, 6)}))


def persist_precision_row(row):
    """One measured row into KERNEL_TUNE_SWEEP.json ``precision_rows``
    (replace-by-identity) — bench_tune's _persist_sweep_row contract:
    the committed golden stays re-derivable from committed artifacts."""
    from dtf_tpu.tune import search

    path = os.path.join(ROOT, search.SWEEP_ARTIFACT)
    data = {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        data = {}
    rows = data.get("precision_rows", [])

    def ident(r):
        return (r.get("parallel"), r.get("d_in"), r.get("d_out"),
                r.get("b"), r.get("t"), r.get("dtype"), r.get("precision"),
                r.get("backend"), r.get("n_devices"))

    rows = [r for r in rows if ident(r) != ident(row)] + [row]
    data["precision_rows"] = rows
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def reseed_golden():
    """Re-derive matmul_precision winners from the banked rows and merge
    them into BOTH caches (local + committed golden)."""
    from dtf_tpu.tune import cache, search

    entries = search.seed_precision_entries(ROOT)
    if entries:
        cache.merge_entries(cache.local_path(), entries,
                            generated_by="bench_quant.py")
        cache.merge_entries(cache.golden_path(), entries,
                            generated_by="bench_quant.py")
    return {e.canonical_key(): e.winner for e in entries}


def _write_merged(rows, errors):
    data = {}
    try:
        with open(ARTIFACT) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        data = {}
    data["rows"] = rows
    data["errors"] = errors
    with open(ARTIFACT, "w") as f:
        json.dump(data, f, indent=1)


def main():
    from _dtf_watchdog import Budget, child_argv, probe_backend, \
        run_budgeted_jobs

    summary = {"rows": 0, "errors": 0, "winners": {}}
    budget = Budget(TOTAL_BUDGET_S)
    backend, probe_errors = probe_backend(
        timeout_s=min(PROBE_TIMEOUT_S, max(10.0, budget.remaining(10))),
        env=dict(os.environ))
    summary["backend"] = backend
    if backend is None:
        summary["probe"] = ("backend unavailable: "
                            + "; ".join(probe_errors))[:2000]
        print(json.dumps(summary))
        return 0

    on_tpu = backend == "tpu" and os.environ.get("DTF_QUANT_SMOKE") != "1"
    if on_tpu:
        jobs = [_job(s, p) for s in QUANT_SITES for p in PRECISIONS]
    else:
        jobs = [_job(s, p, b=1, t=8)
                for s in CPU_SITES for p in CPU_PRECISIONS]

    def on_result(row, job, rows, errors):
        _write_merged(rows, errors)
        summary["rows"] = len(rows)
        summary["errors"] = len(errors)
        if row is not None and on_tpu:
            persist_precision_row(row)
            summary["winners"] = reseed_golden()

    run_budgeted_jobs(
        jobs, child_argv(os.path.abspath(__file__)),
        lambda line: (json.loads(line[len(SENTINEL):])
                      if line.startswith(SENTINEL) else None),
        budget=budget, cap_s=CHILD_TIMEOUT_S, env_base=dict(os.environ),
        on_result=on_result)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(main())
