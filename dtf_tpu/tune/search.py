"""Candidate spaces, deterministic winner selection, artifact seeding.

The WRITE side of the tuner: the candidate grids below are what a sweep
on the chip times, :func:`select_winner` picks among its rows, and
:func:`seed_entries` re-derives the committed ``KERNEL_TUNE.json`` golden
from the sweep artifacts in the repo (KERNEL_TUNE_SWEEP.json block
sweeps where one exists, BENCH_LM_SWEEP.json loss rows) when
``python -m dtf_tpu.tune seed`` is run. No hand-transcription of winners
into literals. Nothing in the repo produces such rows today
(docs/TUNING.md; ROADMAP C1c): the source strings below that speak of
queued rows name measurements that were never taken.

Winner selection is DETERMINISTIC on purpose: min metric, ties broken
by the canonical JSON of the candidate params — two runs over the same
rows bank the same winner, and tests inject synthetic timings to pin
the ordering (tests/test_tune.py).

How a new kernel registers candidates: add a ``<kind>_candidates()``
grid here, give the kernel a 0-sentinel block argument resolved through
a :mod:`dtf_tpu.tune.resolver` plan, time the grid on the chip, and
extend :func:`seed_entries` if its rows land in a committed artifact
(docs/TUNING.md walks an example).

jax-free at module level (package discipline).
"""

from __future__ import annotations

import json
import os
from typing import Optional

from dtf_tpu.tune.cache import Entry

#: forward block grid (the round-5 sweep's shapes): square vs
#: rectangular vs doubled-k, the axes that moved the needle on v5e.
FLASH_FWD_CANDIDATES = ((256, 256), (512, 512), (512, 1024), (1024, 512),
                        (1024, 1024), (512, 2048))
#: backward grid (fwd pinned at its winner): the _dq/_dkv kernels stream
#: the opposite extents from the forward, so the optimum may differ —
#: (512, 1024) repeats the inherited default as a same-window control.
FLASH_BWD_CANDIDATES = ((512, 512), (1024, 512), (512, 1024),
                        (1024, 1024), (256, 1024))
#: fused-CE tile grid: token-block x vocab-block around the 512x1024
#: default (VMEM bound ~8 MB at D<=1024 — fused_ce.py docstring).
FUSED_CE_CANDIDATES = ((256, 1024), (512, 512), (512, 1024), (512, 2048),
                       (1024, 1024))
#: LM loss paths to A/B (chunk values are the banked sweep shapes:
#: AUTO_LOSS_CHUNK_TOKENS / the vocab ladder's 8192).
LM_LOSS_CANDIDATES = (("monolithic", 0), ("chunk_tokens", 4096),
                      ("chunk_vocab", 8192), ("pallas", 0))
#: the tp_dense precision axis, A/B'd per (parallel, shape) site. bf16
#: is the control every row is judged against.
MATMUL_PRECISION_CANDIDATES = ("bf16", "int8", "fp8")
#: quality ceiling a low-precision row must beat to be ELIGIBLE as a
#: winner: Frobenius rel-err of the quantized projection output vs the
#: bf16 control on the same seeded operands. 5e-2 is deliberately loose
#: — per-channel symmetric int8 on activation-scale data lands ~1e-2;
#: a row near the ceiling signals an outlier-heavy shape where low
#: precision should NOT win (docs/TUNING.md "Precision winners").
PRECISION_REL_ERR_CEILING = 5e-2


def flash_fwd_candidates(seq: int) -> list[tuple[int, int]]:
    """The fwd grid clamped to the sequence (a block wider than T just
    re-measures the T-sized clamp the wrapper applies)."""
    out, seen = [], set()
    for bq, bk in FLASH_FWD_CANDIDATES:
        c = (min(bq, seq), min(bk, seq))
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def flash_bwd_candidates(seq: int) -> list[tuple[int, int]]:
    out, seen = [], set()
    for bq, bk in FLASH_BWD_CANDIDATES:
        c = (min(bq, seq), min(bk, seq))
        if c not in seen:
            seen.add(c)
            out.append(c)
    return out


def select_winner(rows: list[dict], *, metric: str,
                  lower_is_better: bool = True) -> Optional[dict]:
    """The winning row: best ``metric``, deterministic tie-break.

    Rows missing the metric (a child that died mid-sweep) are skipped;
    an empty field → None (caller keeps the previous winner). Ties
    break on the canonical JSON of the row so injected-equal timings
    still select reproducibly."""
    live = [r for r in rows
            if isinstance(r.get(metric), (int, float))]
    if not live:
        return None
    sign = 1.0 if lower_is_better else -1.0
    return min(live, key=lambda r: (sign * float(r[metric]),
                                    json.dumps(r, sort_keys=True)))


def select_precision_winner(rows: list[dict]) -> Optional[dict]:
    """The winning precision row for ONE (parallel, d_in, d_out) site:
    fastest ``matmul_s`` among rows that pass the quality bound.

    bf16 rows are exempt from the ceiling (they ARE the reference); a
    low-precision row missing its ``rel_err`` is dropped, not trusted —
    the bound is the whole point of tuner ownership."""
    eligible = []
    for r in rows:
        if r.get("precision") == "bf16":
            eligible.append(r)
            continue
        err = r.get("rel_err")
        if isinstance(err, (int, float)) and \
                float(err) <= PRECISION_REL_ERR_CEILING:
            eligible.append(r)
    return select_winner(eligible, metric="matmul_s")


# --------------------------------------------------------------- seeding


def _read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
        return data if isinstance(data, dict) else {}
    except (OSError, json.JSONDecodeError, UnicodeDecodeError):
        return {}


def _attn_key(row: dict, backend: str = "tpu") -> dict:
    return dict(seq=int(row.get("seq", 0)), heads=int(row.get("h", 0)),
                head_dim=int(row.get("d", 0)),
                dtype=str(row.get("dtype", "bfloat16")), causal=True,
                window=0, n_devices=1, backend=backend)


#: raw on-chip sweep rows are persisted here (committed), so the golden
#: is ALWAYS re-derivable from artifacts — a re-seed after a measuring
#: round reproduces the measured winners instead of reverting them to
#: older data.
SWEEP_ARTIFACT = "KERNEL_TUNE_SWEEP.json"


def _shape_of(row: dict) -> tuple:
    return (int(row.get("seq", 0)), int(row.get("h", 0)),
            int(row.get("d", 0)), str(row.get("dtype", "bfloat16")))


def _is_bwd_row(row: dict) -> bool:
    return bool(row.get("block_q_bwd") or row.get("block_k_bwd"))


def seed_flash_entries(root: str) -> list[Entry]:
    """flash_fwd/flash_bwd winners per SHAPE from the banked sweeps:
    persisted rows (KERNEL_TUNE_SWEEP.json) plus, where one exists,
    ATTN_BENCH.json's ``tpu.block_sweep`` / ``tpu.bwd_block_sweep``.
    Neither file is committed today: no sweep has been taken on the
    present chip and JAX, so this seeds nothing and the kernels run
    their built-in defaults (``measured: false``).

    - fwd: min ``flash_fwd_s`` over the shape's fwd rows.
    - bwd: min ``flash_fwdbwd_s`` over the shape's STANDALONE bwd rows
      (block_q_bwd/block_k_bwd set, fwd pinned) when any exist;
      otherwise the shape's best fwd+bwd row seeds the INHERITED pair
      that measurement actually ran — so the default comes from data
      either way, and re-seeding after the standalone rows bank flips
      it to the independent optimum automatically.
    """
    tpu = _read_json(os.path.join(root, "ATTN_BENCH.json")).get("tpu", {})
    rows = list((tpu.get("block_sweep") or {}).get("rows") or [])
    rows += list((tpu.get("bwd_block_sweep") or {}).get("rows") or [])
    rows += [r for r in _read_json(
        os.path.join(root, SWEEP_ARTIFACT)).get("rows", [])
        if r.get("backend") == "tpu"]
    shapes: dict[tuple, dict] = {}
    for r in rows:
        if not all(_shape_of(r)[:3]):
            continue
        g = shapes.setdefault(_shape_of(r), {"fwd": [], "bwd": []})
        g["bwd" if _is_bwd_row(r) else "fwd"].append(r)
    entries: list[Entry] = []
    for g in shapes.values():
        fwd = select_winner(g["fwd"], metric="flash_fwd_s")
        if fwd:
            entries.append(Entry(
                kind="flash_fwd", key=_attn_key(fwd),
                winner={"block_q": int(fwd["block_q"]),
                        "block_k": int(fwd["block_k"]),
                        "block_h": int(fwd.get("block_h", 1))},
                metric={"flash_fwd_s": fwd.get("flash_fwd_s"),
                        "flash_fwd_tflops": fwd.get("flash_fwd_tflops")},
                source=("banked fwd block-sweep rows (ATTN_BENCH.json / "
                        "KERNEL_TUNE_SWEEP.json, v5e)"),
                measured=True))
        if g["bwd"]:
            bwd = select_winner(g["bwd"], metric="flash_fwdbwd_s")
            if bwd:
                entries.append(Entry(
                    kind="flash_bwd", key=_attn_key(bwd),
                    winner={"block_q_bwd": int(bwd.get("block_q_bwd")
                                               or 0),
                            "block_k_bwd": int(bwd.get("block_k_bwd")
                                               or 0)},
                    metric={"flash_fwdbwd_s": bwd.get("flash_fwdbwd_s")},
                    source=("banked STANDALONE bwd block-sweep rows "
                            "(fwd pinned; ATTN_BENCH.json / "
                            "KERNEL_TUNE_SWEEP.json, v5e)"),
                    measured=True))
        elif fwd is not None:
            bwd = select_winner(g["fwd"], metric="flash_fwdbwd_s")
            if bwd:
                entries.append(Entry(
                    kind="flash_bwd", key=_attn_key(bwd),
                    winner={"block_q_bwd": int(bwd["block_q"]),
                            "block_k_bwd": int(bwd["block_k"])},
                    metric={"flash_fwdbwd_s": bwd.get("flash_fwdbwd_s")},
                    source=("banked fwd+bwd rows (bwd INHERITED the fwd "
                            "blocks in this measurement; the standalone "
                            "bwd sweep is queued — bench_attention "
                            "--sweep-blocks-bwd / bench_tune — and "
                            "re-seeding banks its independent optimum)"),
                    measured=True))
    return entries


def _lm_row_path(row: dict) -> tuple[str, int]:
    if row.get("loss_pallas"):
        return "pallas", 0
    if row.get("loss_chunk_tokens"):
        return "chunk_tokens", int(row["loss_chunk_tokens"])
    if row.get("loss_chunk"):
        return "chunk_vocab", int(row["loss_chunk"])
    return "monolithic", 0


def seed_lm_loss_entries(root: str) -> list[Entry]:
    """lm_loss winners per fits-bucket from the GPT sweep rows.

    Bucketing uses the same per-device HBM estimate as
    ``flags.resolve_lm_loss`` (logits + cotangent vs the budget
    fraction), so a banked winner lands in exactly the bucket the
    resolver will query. Within the fits=True bucket the data decides
    outright (round 5: monolithic 58.0%% vs vocab-chunk 48.9%%). In the
    fits=False bucket only the vocab scan is measured so far; the
    token-chunk A/B was never taken, and until one banks, the
    entry encodes a chunk-axis ordering from before PR 1 (token chunking:
    one full-vocab MXU matmul per block vs the serialized vocab scan
    that costs ~9 MFU points) as a measured=False policy winner — the
    measured vocab rows are recorded as alternatives in the metric."""
    from dtf_tpu.cli.flags import (AUTO_LOSS_CHUNK_TOKENS,
                                   HBM_BYTES_PER_CHIP,
                                   LOGITS_HBM_FRACTION)

    raw = list(_read_json(
        os.path.join(root, "BENCH_LM_SWEEP.json")).get("rows", []))
    # A/B rows under BENCH_LM.json "loss_path", where that file exists,
    # join the pool — newer rows land later and win ties deterministically only
    # via the canonical-JSON tie-break, but a real delta decides on data.
    raw += list((_read_json(os.path.join(root, "BENCH_LM.json"))
                 .get("loss_path") or {}).get("rows", []))
    rows = [r for r in raw
            if r.get("model") == "gpt" and r.get("phase", "step") == "step"
            and r.get("gpt_size", "small") == "small"]
    buckets: dict[bool, list[dict]] = {True: [], False: []}
    vocab = 50304   # the GPT flagship vocab (models/gpt.py)
    for r in rows:
        batch, seq = int(r.get("batch", 0)), int(r.get("seq", 0))
        if not (batch and seq):
            continue
        est = 2 * batch * seq * vocab * 4
        fits = est <= LOGITS_HBM_FRACTION * HBM_BYTES_PER_CHIP
        path, chunk = _lm_row_path(r)
        buckets[fits].append({
            "path": path, "chunk": chunk, "batch": batch, "seq": seq,
            "mfu": r.get("mfu_analytic"),
            "tokens_per_sec": r.get("tokens_per_sec")})
    entries: list[Entry] = []
    for fits, brows in buckets.items():
        if not brows:
            continue
        alts = {f"{b['path']}_b{b['batch']}": b["mfu"] for b in brows
                if isinstance(b.get("mfu"), (int, float))}
        rep = brows[0]
        key = dict(fits=fits, vocab=vocab, seq=rep["seq"],
                   batch=rep["batch"], n_devices=1, backend="tpu")
        best = select_winner(brows, metric="mfu", lower_is_better=False)
        paths = {b["path"] for b in brows}
        if fits or (best and best["path"] != "chunk_vocab") or \
                "chunk_tokens" in paths:
            if best is None:
                continue
            entries.append(Entry(
                kind="lm_loss", key=key,
                winner={"path": best["path"], "chunk": best["chunk"]},
                metric={"mfu": best["mfu"], "alternatives": alts},
                source=("BENCH_LM_SWEEP.json gpt rows (v5e, round 5): "
                        "best measured mfu_analytic in this fits bucket"),
                measured=True))
        else:
            # only the vocab scan is measured where logits don't fit:
            # bank the token-chunk preference until an A/B on the chip
            # replaces it with data.
            entries.append(Entry(
                kind="lm_loss", key=key,
                winner={"path": "chunk_tokens",
                        "chunk": AUTO_LOSS_CHUNK_TOKENS},
                metric={"alternatives": alts},
                source=("PERF.md §5 chunk-axis ordering (vocab "
                        "scan costs ~9 MFU points; token chunking is "
                        "one full-vocab MXU matmul per block). The "
                        "mono/token/pallas A/B rows ride bench_tune's "
                        "loss_path queue; re-seed after they bank."),
                measured=False))
    return entries


def seed_spec_k_entries(root: str) -> list[Entry]:
    """spec_k winners per (model, draft, slots, backend) from the serve
    sweep rows (a draft-k axis, merged under BENCH_LM.json "serve"; no
    such file is committed): best GOODPUT tokens/sec among the swept k
    values on the same seeded arrivals. Rows carry the architecture
    labels the engine's resolver queries (``model_arch``/``draft_arch``
    — serve/engine.py ``_cfg_label``), so a banked winner lands exactly
    where ``DecodeEngine(spec_k=0)`` will look."""
    rows = list((_read_json(os.path.join(root, "BENCH_LM.json"))
                 .get("serve") or {}).get("rows", []))
    groups: dict[tuple, list[dict]] = {}
    for r in rows:
        k = int(r.get("spec_k", 0) or 0)
        if not k or not r.get("model_arch") or not r.get("draft_arch"):
            continue
        serve = r.get("serve") or {}
        if not isinstance(serve.get("tokens_per_sec"), (int, float)):
            continue
        slots = int(r.get("n_slots", 0)) // max(int(r.get("replicas", 1)
                                                    or 1), 1)
        gk = (str(r["model_arch"]), str(r["draft_arch"]), slots,
              str(r.get("backend", "tpu")))
        groups.setdefault(gk, []).append({
            "k": k, "tokens_per_sec": float(serve["tokens_per_sec"]),
            "accept_rate": serve.get("accept_rate")})
    entries: list[Entry] = []
    for (m, d, slots, backend), brows in sorted(groups.items()):
        best = select_winner(brows, metric="tokens_per_sec",
                             lower_is_better=False)
        if best is None:
            continue
        entries.append(Entry(
            kind="spec_k",
            key=dict(model=m, draft=d, n_slots=slots, backend=backend),
            winner={"k": int(best["k"])},
            metric={"tokens_per_sec": best["tokens_per_sec"],
                    "accept_rate": best.get("accept_rate"),
                    "alternatives": {f"k{b['k']}": b["tokens_per_sec"]
                                     for b in brows}},
            source=("BENCH_LM.json serve rows (bench_decode "
                    "--sweep-serve draft-k axis): best goodput on the "
                    "same seeded arrivals"),
            measured=True))
    return entries


def spec_policy_entries() -> list[Entry]:
    """The flagship (gpt2_small, gpt2_draft) spec_k default until the
    on-chip draft-k sweep banks: k=4 — acceptance on natural text decays
    with depth while verify cost grows with k+1, and 4 is the center of
    the swept grid (2/4/8). measured=False: the resolver uses it but an
    explicit --spec_k never warns about overriding a guess."""
    return [Entry(
        kind="spec_k",
        key=dict(model="d768L12h12kv12v50304",     # gpt2_small
                 draft="d384L3h6kv6v50304",        # gpt2_draft
                 n_slots=8, backend="tpu"),
        winner={"k": 4},
        source=("policy default pending the queued bench_decode "
                "--sweep-serve draft-k rows (re-seed after they bank)"),
        measured=False)]


def seed_precision_entries(root: str) -> list[Entry]:
    """matmul_precision winners per (parallel, d_in, d_out, dtype) site
    from banked precision rows (KERNEL_TUNE_SWEEP.json
    ``precision_rows``): fastest ``matmul_s`` among rows inside the
    rel-err ceiling — a site where nothing beats bf16 banks bf16, which
    is itself useful data (``--matmul_precision=int8`` there warns)."""
    rows = [r for r in _read_json(
        os.path.join(root, SWEEP_ARTIFACT)).get("precision_rows", [])
        if r.get("parallel") and r.get("d_in") and r.get("d_out")]
    groups: dict[tuple, list[dict]] = {}
    for r in rows:
        gk = (str(r["parallel"]), int(r["d_in"]), int(r["d_out"]),
              str(r.get("dtype", "bfloat16")),
              str(r.get("backend", "tpu")), int(r.get("n_devices", 1)))
        groups.setdefault(gk, []).append(r)
    entries: list[Entry] = []
    for (parallel, d_in, d_out, dtype, backend, n_dev), brows in \
            sorted(groups.items()):
        best = select_precision_winner(brows)
        if best is None:
            continue
        entries.append(Entry(
            kind="matmul_precision",
            key=dict(site="tp_dense", parallel=parallel, d_in=d_in,
                     d_out=d_out, dtype=dtype, n_devices=n_dev,
                     backend=backend),
            winner={"precision": str(best["precision"]),
                    "rel_err": best.get("rel_err")},
            metric={"matmul_s": best.get("matmul_s"),
                    "alternatives": {
                        str(b["precision"]): b.get("matmul_s")
                        for b in brows}},
            source=("banked bench_quant precision rows "
                    "(KERNEL_TUNE_SWEEP.json precision_rows): fastest "
                    "matmul_s inside the rel-err ceiling "
                    f"({PRECISION_REL_ERR_CEILING:g})"),
            measured=True))
    return entries


def precision_policy_entries() -> list[Entry]:
    """The quantized-DRAFT serving default until the on-chip precision
    sweep banks: int8 at the gpt2_draft projection widths (384<->1536).
    The draft's output never reaches a user — the bf16 verifier owns
    the emitted token stream byte-for-byte (tests/test_serve_spec.py) —
    so a draft-side quality miss costs only acceptance rate, never
    correctness; that asymmetry is why the draft gets the first
    low-precision win. measured=False: an explicit --draft_precision
    never warns about overriding a guess; timed rows at the same keys
    would replace these."""
    src = ("policy default pending the queued bench_quant precision "
           "rows (draft-side only: the bf16 verifier keeps emitted "
           "tokens byte-identical; re-seed after rows bank)")

    def _e(parallel, d_in, d_out):
        return Entry(
            kind="matmul_precision",
            key=dict(site="tp_dense", parallel=parallel, d_in=d_in,
                     d_out=d_out, dtype="bfloat16", n_devices=1,
                     backend="tpu"),
            winner={"precision": "int8"}, source=src, measured=False)

    # gpt2_draft (d384, ff1536): qkv/attn-proj 384x384 column,
    # mlp_in 384x1536 column, attn_out/mlp_out row back into d_model.
    return [_e("column", 384, 384), _e("column", 384, 1536),
            _e("row", 384, 384), _e("row", 1536, 384)]


def cpu_sim_fallback_entries() -> list[Entry]:
    """Deterministic CPU-sim entries mirroring the built-in defaults.

    Interpret-mode timings are not MXU-predictive, so the CPU sim
    should resolve like the chip does — nearest-shape lookup already
    lands on the banked tpu winners; these entries exist so a tree with
    a pruned tpu section still resolves deterministically (and so tests
    have a stable backend='cpu' row to assert against)."""
    src = ("cpu_sim_fallback: mirrors the built-in defaults — "
           "interpret-mode timing is not predictive of the MXU")
    return [
        Entry(kind="flash_fwd",
              key=dict(seq=1024, heads=12, head_dim=64, dtype="bfloat16",
                       causal=True, window=0, n_devices=8, backend="cpu"),
              winner={"block_q": 512, "block_k": 1024, "block_h": 1},
              source=src, measured=False),
        Entry(kind="fused_ce",
              key=dict(vocab=50304, d_model=768, dtype="bfloat16",
                       n_devices=8, backend="cpu"),
              winner={"block_n": 512, "block_v": 1024},
              source=src, measured=False),
    ]


def seed_entries(root: Optional[str] = None) -> list[Entry]:
    """Everything the committed artifacts support, in one list."""
    from dtf_tpu.tune.cache import repo_root

    root = root or repo_root()
    # policy entries FIRST: merge_entries is last-wins per canonical key,
    # so a measured spec_k row banking at the policy's exact key replaces
    # the guess instead of being shadowed by it.
    return (spec_policy_entries() + precision_policy_entries()
            + seed_flash_entries(root) + seed_lm_loss_entries(root)
            + seed_spec_k_entries(root) + seed_precision_entries(root)
            + cpu_sim_fallback_entries())
