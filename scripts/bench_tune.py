#!/usr/bin/env python
"""Kernel autotune sweep: measure candidates on the chip, bank winners
into the kernel-tune cache (docs/TUNING.md).

What it does, in order:

1. SELECT from banked artifacts (always, whatever the backend does):
   re-derive winners from the sweep rows on disk (KERNEL_TUNE_SWEEP.json,
   BENCH_LM_SWEEP.json, BENCH_LM.json loss_path) and refresh the
   committed ``KERNEL_TUNE.json`` golden — the step that turns raw rows
   into defaults without hand-transcription.
2. MEASURE on chip: flash forward blocks then the
   independent backward blocks (fwd pinned at its winner-so-far) at the
   registered shapes — the GPT-2-small TRAIN shape first (b8 h12 d64
   s1024: the flagship's actual attention), then the long-context bench
   shape (b2 h8 d128 s8192) — each candidate in its own watchdogged
   child (``bench_attention.py tpu --child``, the proven scan-amortized
   timing), winners banked incrementally after EVERY row so a run cut
   mid-sweep still flips whatever was measured. Then the LM
   loss-path A/B (monolithic vs token-chunked vs --loss_pallas, batch
   8 and 16) via ``bench_lm.py --child`` rows, merged under
   BENCH_LM.json's ``loss_path`` section.
3. On a CPU-only backend: a tiny interpret-mode sweep instead — an
   end-to-end wiring check of measure->select->bank (NOT MXU-predictive;
   banked into the LOCAL cache only, measured=false). Keys already
   banked are skipped: the second invocation re-sweeps nothing.

Process contract (kill-tested in tests/test_tune.py): the parent never
imports jax — its children need the chip, one after the other — prints
ONE JSON line last no matter what the backend does, and exits 0; without
a backend it still refreshes the golden from banked artifacts. (One of
the four scripts that still ask a short probe child which backend
answers, because the job list depends on it; they keep the exit-0
contract until the benchmark PR turns them into cells — ROADMAP C1.)

Run this BEFORE bench_lm/bench_profile so their rows are measured at
tuned defaults.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

BENCH_LM_ARTIFACT = os.path.join(ROOT, "BENCH_LM.json")
ATTN_SENTINEL = "ATTN_TPU_RESULT "
LM_SENTINEL = "BENCH_LM_ROW "
TOTAL_BUDGET_S = float(os.environ.get("DTF_TUNE_BUDGET_S", "5400"))
CHILD_TIMEOUT_S = 900
PROBE_TIMEOUT_S = 90

#: the sweep registry: train shape first (highest value — the flagship
#: trains here), then the long-context bench shape ATTN_BENCH tracks.
TPU_SHAPES = (
    {"name": "gpt2_train", "seq": 1024, "b": 8, "h": 12, "d": 64},
    {"name": "longctx8k", "seq": 8192, "b": 2, "h": 8, "d": 128},
)
#: CPU-sim wiring-check shape (interpret mode; tiny on purpose).
CPU_SHAPE = {"name": "cpu_sim", "seq": 128, "b": 1, "h": 2, "d": 32}
CPU_FWD_CANDIDATES = ((64, 64), (128, 128))
CPU_BWD_CANDIDATES = ((64, 128),)

#: matmul-precision A/B cells (bench_quant children): bf16/int8/fp8 at
#: the tp_dense sites — the GPT-2-small flagship's four projections and
#: the gpt2_draft twin (the shapes the serving draft actually runs).
#: Rows land under KERNEL_TUNE_SWEEP.json "precision_rows" and seed the
#: matmul_precision winners (quality-bounded: see
#: search.select_precision_winner).
QUANT_SENTINEL = "QUANT_ROW "
PRECISION_SITES = (
    {"parallel": "column", "d_in": 768, "d_out": 768},
    {"parallel": "column", "d_in": 768, "d_out": 3072},
    {"parallel": "row", "d_in": 768, "d_out": 768},
    {"parallel": "row", "d_in": 3072, "d_out": 768},
    {"parallel": "column", "d_in": 384, "d_out": 384},
    {"parallel": "column", "d_in": 384, "d_out": 1536},
    {"parallel": "row", "d_in": 384, "d_out": 384},
    {"parallel": "row", "d_in": 1536, "d_out": 384},
)
PRECISION_CANDIDATES = ("bf16", "int8", "fp8")
#: CPU wiring-check cell (interpret-grade timing, never banked to the
#: committed sweep artifact — not MXU-predictive).
CPU_PRECISION_SITES = ({"parallel": "column", "d_in": 16, "d_out": 32},)
CPU_PRECISION_CANDIDATES = ("bf16", "int8")

#: loss-path A/B jobs (bench_lm children): rows land under
#: BENCH_LM.json "loss_path" and seed the lm_loss winners.
LOSS_PATH_JOBS = (
    {"DTF_LM_WHICH": "gpt", "DTF_LM_BATCH": "8"},
    {"DTF_LM_WHICH": "gpt", "DTF_LM_BATCH": "8",
     "DTF_LM_LOSS_CHUNK_T": "4096"},
    {"DTF_LM_WHICH": "gpt", "DTF_LM_BATCH": "8", "DTF_LM_LOSS_PALLAS": "1"},
    {"DTF_LM_WHICH": "gpt", "DTF_LM_BATCH": "16",
     "DTF_LM_LOSS_CHUNK_T": "4096"},
    {"DTF_LM_WHICH": "gpt", "DTF_LM_BATCH": "16",
     "DTF_LM_LOSS_CHUNK": "8192"},
    {"DTF_LM_WHICH": "gpt", "DTF_LM_BATCH": "16",
     "DTF_LM_LOSS_PALLAS": "1"},
)


def _attn_job(shape, *, bq=0, bk=0, bqb=0, bkb=0, interpret=False):
    job = {"DTF_ATTN_SEQ": str(shape["seq"]), "DTF_ATTN_B": str(shape["b"]),
           "DTF_ATTN_H": str(shape["h"]), "DTF_ATTN_D": str(shape["d"])}
    if bq:
        job["DTF_ATTN_BQ"] = str(bq)
    if bk:
        job["DTF_ATTN_BK"] = str(bk)
    if bqb:
        job["DTF_ATTN_BQB"] = str(bqb)
    if bkb:
        job["DTF_ATTN_BKB"] = str(bkb)
    if interpret:
        job["DTF_ATTN_INTERPRET"] = "1"
    return job


def _attn_key(shape, backend):
    return dict(seq=shape["seq"], heads=shape["h"], head_dim=shape["d"],
                dtype="bfloat16", causal=True, window=0, n_devices=1,
                backend=backend)


def _already_banked(cache, kind, key) -> bool:
    """EXACT-key presence in the local cache (nearest-match lookup must
    not make the skip fuzzy — a new shape always measures)."""
    probe = cache.Entry(kind=kind, key=key, winner={})
    return any(e.canonical_key() == probe.canonical_key()
               for e in cache.load_file(cache.local_path()))


def _bank_flash(cache, search, shape, backend, fwd_rows, bwd_rows, *,
                measured, source):
    """Select winners over the rows so far and merge them into the local
    cache (and, for on-chip rows, the committed golden)."""
    entries = []
    fwd = search.select_winner(fwd_rows, metric="flash_fwd_s")
    if fwd:
        entries.append(cache.Entry(
            kind="flash_fwd", key=_attn_key(shape, backend),
            winner={"block_q": int(fwd["block_q"]),
                    "block_k": int(fwd["block_k"]),
                    "block_h": int(fwd.get("block_h", 1))},
            metric={"flash_fwd_s": fwd.get("flash_fwd_s"),
                    "flash_fwd_tflops": fwd.get("flash_fwd_tflops")},
            source=source, measured=measured))
    bwd = search.select_winner(bwd_rows, metric="flash_fwdbwd_s")
    if bwd:
        entries.append(cache.Entry(
            kind="flash_bwd", key=_attn_key(shape, backend),
            winner={"block_q_bwd": int(bwd.get("block_q_bwd") or 0),
                    "block_k_bwd": int(bwd.get("block_k_bwd") or 0)},
            metric={"flash_fwdbwd_s": bwd.get("flash_fwdbwd_s")},
            source=source, measured=measured))
    if entries:
        cache.merge_entries(cache.local_path(), entries,
                            generated_by="bench_tune.py")
        if measured:
            cache.merge_entries(cache.golden_path(), entries,
                               generated_by="bench_tune.py")
    return {e.kind: e.winner for e in entries}


def _persist_sweep_row(search, row):
    """Measured flash rows into the committed KERNEL_TUNE_SWEEP.json so
    the golden stays re-derivable from artifacts (`tune seed` after a
    measuring round reproduces, not reverts, the banked winners).
    Same-(shape, blocks) rows are replaced; interpret rows never land
    here (the caller gates on measured)."""
    path = os.path.join(ROOT, search.SWEEP_ARTIFACT)
    data = {}
    try:
        with open(path) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        data = {}
    rows = data.get("rows", [])

    def ident(r):
        return (r.get("seq"), r.get("b"), r.get("h"), r.get("d"),
                r.get("dtype"), r.get("block_q"), r.get("block_k"),
                r.get("block_h"), r.get("block_q_bwd"),
                r.get("block_k_bwd"))

    rows = [r for r in rows if ident(r) != ident(row)] + [row]
    data["rows"] = rows
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def _quant_job(site, precision, *, b=8, t=1024):
    return {"DTF_QUANT_PARALLEL": site["parallel"],
            "DTF_QUANT_D_IN": str(site["d_in"]),
            "DTF_QUANT_D_OUT": str(site["d_out"]),
            "DTF_QUANT_B": str(b), "DTF_QUANT_T": str(t),
            "DTF_QUANT_PRECISION": precision}


def _precision_key(site, backend):
    return dict(site="tp_dense", parallel=site["parallel"],
                d_in=site["d_in"], d_out=site["d_out"], dtype="bfloat16",
                n_devices=1, backend=backend)


def _persist_precision_row(search, row):
    """Measured precision cells into KERNEL_TUNE_SWEEP.json (same
    replace-by-identity contract as _persist_sweep_row): `tune seed`
    after a measuring round reproduces, not reverts, the winners."""
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


def _sweep_precision(sites, precisions, *, backend, measured, budget,
                     run_jobs, cache, search, summary, b=8, t=1024):
    """Per site: one bench_quant child per precision candidate; measured
    rows persist to the sweep artifact and re-seed the golden after
    EVERY row (a run cut mid-sweep keeps whatever was measured).
    Interpret-mode rows (measured=False) are a wiring check only."""
    argv = [sys.executable,
            os.path.join(ROOT, "scripts", "bench_quant.py"), "--child"]
    parse = lambda line: (json.loads(line[len(QUANT_SENTINEL):])  # noqa: E731
                          if line.startswith(QUANT_SENTINEL) else None)
    for site in sites:
        if measured and _already_banked(cache, "matmul_precision",
                                        _precision_key(site, backend)):
            summary["resweep_skipped"] += 1
            continue

        def bank(row, job, rows, errs):
            if row is not None and measured:
                _persist_precision_row(search, row)
                entries = search.seed_precision_entries(ROOT)
                if entries:
                    cache.merge_entries(cache.local_path(), entries,
                                        generated_by="bench_tune.py")
                    cache.merge_entries(cache.golden_path(), entries,
                                        generated_by="bench_tune.py")
                    summary["winners"].update(
                        {e.canonical_key(): e.winner for e in entries})
            summary["precision_rows"] = summary.get(
                "precision_rows", 0) + (1 if row is not None else 0)

        jobs = [_quant_job(site, p, b=b, t=t) for p in precisions]
        rows, errs = run_jobs(jobs, argv, parse, budget=budget,
                              on_result=bank)
        summary["errors"] += len(errs)


def _merge_loss_rows(rows, errors):
    """Loss-path rows into BENCH_LM.json's own section (satellite 2);
    sibling sections survive, same contract as bench_lm's writer."""
    data = {}
    try:
        with open(BENCH_LM_ARTIFACT) as f:
            data = json.load(f)
    except (OSError, json.JSONDecodeError):
        data = {}
    data["loss_path"] = {"rows": rows, "errors": errors}
    with open(BENCH_LM_ARTIFACT, "w") as f:
        json.dump(data, f, indent=1)


def _sweep_flash(shapes, fwd_cands, bwd_cands, *, backend, interpret,
                 budget, run_jobs, cache, search, summary):
    """Per shape: fwd candidates, bank, then bwd candidates with the fwd
    winner pinned, bank again. Winners merge after every row."""
    attn_argv = [sys.executable,
                 os.path.join(ROOT, "scripts", "bench_attention.py"),
                 "tpu", "--child"]
    parse = lambda line: (json.loads(line[len(ATTN_SENTINEL):])  # noqa: E731
                          if line.startswith(ATTN_SENTINEL) else None)
    measured = not interpret
    source = ("bench_tune.py on-chip sweep" if measured else
              "bench_tune.py cpu_sim e2e (interpret; wiring check, not "
              "MXU-predictive)")
    for shape in shapes:
        if _already_banked(cache, "flash_fwd", _attn_key(shape, backend)) \
                and _already_banked(cache, "flash_bwd",
                                    _attn_key(shape, backend)):
            summary["resweep_skipped"] += 1
            continue
        fwd_rows: list = []
        bwd_rows: list = []

        def bank(row, job, rows, errs):
            if row is not None:
                (bwd_rows if row.get("block_q_bwd") or
                 row.get("block_k_bwd") else fwd_rows).append(row)
                if measured:
                    _persist_sweep_row(search, row)
            summary["winners"].update({
                f"{k}@{shape['name']}": v for k, v in _bank_flash(
                    cache, search, shape, backend, fwd_rows, bwd_rows,
                    measured=measured, source=source).items()})
            summary["flash_rows"] = summary.get("flash_rows", 0) + (
                1 if row is not None else 0)

        cands = [c for c in fwd_cands(shape["seq"])]
        jobs = [_attn_job(shape, bq=bq, bk=bk, interpret=interpret)
                for bq, bk in cands]
        rows, errs = run_jobs(jobs, attn_argv, parse, budget=budget,
                              on_result=bank)
        summary["errors"] += len(errs)
        fwd = search.select_winner(fwd_rows, metric="flash_fwd_s")
        if fwd is None:
            continue     # no fwd data → a bwd sweep would pin garbage
        jobs = [_attn_job(shape, bq=int(fwd["block_q"]),
                          bk=int(fwd["block_k"]), bqb=bqb, bkb=bkb,
                          interpret=interpret)
                for bqb, bkb in bwd_cands(shape["seq"])]
        rows, errs = run_jobs(jobs, attn_argv, parse, budget=budget,
                              on_result=bank)
        summary["errors"] += len(errs)


def main() -> int:
    from _dtf_watchdog import Budget, probe_backend, run_budgeted_jobs

    from dtf_tpu.tune import cache, search

    summary = {"flash_rows": 0, "loss_rows": 0, "resweep_skipped": 0,
               "errors": 0, "winners": {}, "banked_golden": 0}

    # 1. SELECT from banked artifacts — runs no matter what the backend
    # does; this is what turns banked sweep rows into defaults.
    entries = search.seed_entries(ROOT)
    summary["banked_golden"] = cache.merge_entries(
        cache.golden_path(), entries, generated_by="bench_tune.py select")
    summary["selected"] = sorted({e.kind for e in entries})

    budget = Budget(TOTAL_BUDGET_S)
    backend, probe_errors = probe_backend(
        timeout_s=min(PROBE_TIMEOUT_S, max(10.0, budget.remaining(10))),
        env=dict(os.environ))
    summary["backend"] = backend

    def run_jobs(jobs, argv, parse, *, budget, on_result):
        return run_budgeted_jobs(
            jobs, argv, parse, budget=budget, cap_s=CHILD_TIMEOUT_S,
            env_base=dict(os.environ), on_result=on_result)

    if backend is None:
        # no backend: the selection above already refreshed the golden;
        # record it and keep the one-line rc-0 contract.
        summary["probe"] = ("backend unavailable: "
                            + "; ".join(probe_errors))[:2000]
        print(json.dumps(summary))
        return 0

    smoke = os.environ.get("DTF_TUNE_SMOKE") == "1"
    if backend != "tpu" or smoke:
        # 3. CPU-sim e2e wiring check (or the test-tier smoke): tiny
        # interpret sweep, local cache only, skip-if-banked.
        _sweep_flash(
            (CPU_SHAPE,),
            lambda seq: [(min(q, seq), min(k, seq))
                         for q, k in CPU_FWD_CANDIDATES],
            lambda seq: [(min(q, seq), min(k, seq))
                         for q, k in CPU_BWD_CANDIDATES],
            backend=backend, interpret=True, budget=budget,
            run_jobs=run_jobs, cache=cache, search=search,
            summary=summary)
        _sweep_precision(
            CPU_PRECISION_SITES, CPU_PRECISION_CANDIDATES,
            backend=backend, measured=False, budget=budget,
            run_jobs=run_jobs, cache=cache, search=search,
            summary=summary, b=1, t=8)
        print(json.dumps(summary))
        return 0

    # 2. MEASURE on chip.
    _sweep_flash((dict(s) for s in TPU_SHAPES), search.flash_fwd_candidates,
                 search.flash_bwd_candidates, backend=backend,
                 interpret=False, budget=budget, run_jobs=run_jobs,
                 cache=cache, search=search, summary=summary)

    lm_argv = [sys.executable, os.path.join(ROOT, "scripts", "bench_lm.py"),
               "--child"]
    lm_parse = lambda line: (json.loads(line[len(LM_SENTINEL):])  # noqa: E731
                             if line.startswith(LM_SENTINEL) else None)

    def on_loss(row, job, rows, errs):
        _merge_loss_rows(rows, errs)
        summary["loss_rows"] = len(rows)
        # re-select lm_loss winners over EVERYTHING banked (the sweep
        # artifact + the fresh loss_path rows just merged)
        lm = search.seed_lm_loss_entries(ROOT)
        if lm:
            cache.merge_entries(cache.local_path(), lm,
                                generated_by="bench_tune.py")
            cache.merge_entries(cache.golden_path(), lm,
                                generated_by="bench_tune.py")
            summary["winners"].update(
                {e.canonical_key(): e.winner for e in lm})

    rows, errs = run_jobs(list(LOSS_PATH_JOBS), lm_argv, lm_parse,
                          budget=budget, on_result=on_loss)
    summary["errors"] += len(errs)

    # matmul-precision cells last: each child is a single small matmul
    # (minutes for the full grid), and the winners they bank replace the
    # int8 draft policy defaults with timed rows at the same keys.
    _sweep_precision(PRECISION_SITES, PRECISION_CANDIDATES,
                     backend=backend, measured=True, budget=budget,
                     run_jobs=run_jobs, cache=cache, search=search,
                     summary=summary)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
