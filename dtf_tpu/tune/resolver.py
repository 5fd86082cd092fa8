"""Tuner-backed resolution for kernel block shapes and the LM loss path.

The single choke point the kernels and launchers consult when a block
argument is left at its 0 sentinel (``flash_attention``,
``pallas_lm_cross_entropy``) or the loss-path flags are unset
(``flags.resolve_lm_loss``). Resolution order:

1. explicit caller values — always win; when they override a MEASURED
   winner at the consulted shape a warning names both (once per process
   per shape, so a sweep harness doesn't drown in it);
2. the nearest banked winner from the cache store
   (``KERNEL_TUNE.local.json`` shadowing the committed
   ``KERNEL_TUNE.json`` — see :mod:`dtf_tpu.tune.cache`);
3. the built-in defaults. Flash attention has none here: a plan with
   ``block_q == 0`` (nothing banked) or ``measured=False`` leaves the
   blocks to ``ops.flash_attention.flash_blocks``, the shape rule read off
   the on-chip sweep (PERF.md §6, PR 35).

Every resolve is process-cached (``lru_cache``): kernels call this
inside jit traces and a cache-file re-read per call would be absurd.
The cached plan is a plain frozen dataclass of ints — resolving twice
returns the identical object, so resolver lookups can never perturb a
traced program or retrace an AOT one (pinned by
tests/test_tune.py::test_resolver_never_retraces).

jax-free at module level; callers pass backend/n_devices in.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

from dtf_tpu.tune import cache as _cache

# Built-in fallbacks for the fused-CE tile (see ops/fused_ce.py for the
# measurement provenance); these literals only fire when both cache files
# are missing or stale. Flash attention's unmeasured blocks come from its
# own shape rule (``ops.flash_attention.flash_blocks``), not from here.
FALLBACK_BLOCK_N = 512
FALLBACK_BLOCK_V = 1024
FALLBACK_SOURCE = "builtin-default (no kernel-tune cache entry)"


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    #: 0 = nothing banked: ``flash_attention``'s shape rule decides. The
    #: kernel also takes the rule over any plan with ``measured=False``.
    block_q: int
    block_k: int
    block_h: int
    #: 0 = no banked backward winner: inherit the forward blocks (the
    #: pre-tuner contract of ``flash_attention``'s custom_vjp).
    block_q_bwd: int
    block_k_bwd: int
    source: str
    measured: bool


@dataclasses.dataclass(frozen=True)
class FusedCePlan:
    block_n: int
    block_v: int
    source: str
    measured: bool


@dataclasses.dataclass(frozen=True)
class LossPathPlan:
    #: "monolithic" | "chunk_tokens" | "chunk_vocab" | "pallas"
    path: str
    chunk: int
    source: str
    measured: bool


#: speculative-decode draft width when no winner is banked: proposals are
#: cheap relative to a verify pass and acceptance decays with depth, so a
#: mid-size default loses little either way (a measured
#: per-(model, draft, slots) winner in the cache goes over it).
FALLBACK_SPEC_K = 4


@dataclasses.dataclass(frozen=True)
class SpecKPlan:
    k: int
    source: str
    measured: bool


#: matmul precision when no winner is banked: bf16 — the status-quo
#: numerics. Low precision only ever turns ON from banked data (a row
#: that beat bf16 on time AND passed the rel-err ceiling at selection,
#: ``search.select_precision_winner``) or an explicit pin.
FALLBACK_PRECISION = "bf16"


@dataclasses.dataclass(frozen=True)
class PrecisionPlan:
    #: "bf16" | "int8" | "fp8" — what tp_dense should actually run.
    precision: str
    source: str
    measured: bool


@functools.lru_cache(maxsize=1024)
def flash_plan(*, seq: int, heads: int, head_dim: int, dtype: str,
               causal: bool, window: int, n_devices: int = 1,
               backend: Optional[str] = None) -> FlashPlan:
    """The tuned flash block shapes for one attention shape."""
    key = dict(seq=seq, heads=heads, head_dim=head_dim, dtype=dtype,
               causal=causal, window=window, n_devices=n_devices,
               backend=backend)
    store = _cache.load_store()
    fwd = store.lookup("flash_fwd", key)
    bwd = store.lookup("flash_bwd", key)
    bq = bk = bh = 0
    src, measured = FALLBACK_SOURCE, False
    if fwd is not None:
        bq = int(fwd.winner.get("block_q", 0))
        bk = int(fwd.winner.get("block_k", 0))
        bh = int(fwd.winner.get("block_h", 1))
        src, measured = fwd.source, fwd.measured
    bqb = bkb = 0
    if bwd is not None:
        bqb = int(bwd.winner.get("block_q_bwd", 0))
        bkb = int(bwd.winner.get("block_k_bwd", 0))
    if bh < 1 or (heads and heads % bh):
        bh = 1   # a banked fold from a different head count must not
        # turn into a wrapper ValueError — clamp to the proven kernel
    return FlashPlan(block_q=bq, block_k=bk, block_h=bh or 1,
                     block_q_bwd=bqb, block_k_bwd=bkb,
                     source=src, measured=measured)


@functools.lru_cache(maxsize=1024)
def fused_ce_plan(*, vocab: int, d_model: int, dtype: str,
                  n_devices: int = 1,
                  backend: Optional[str] = None) -> FusedCePlan:
    """The tuned Pallas fused-CE tile shape for one head shape."""
    key = dict(vocab=vocab, d_model=d_model, dtype=dtype,
               n_devices=n_devices, backend=backend)
    e = _cache.load_store().lookup("fused_ce", key)
    if e is None:
        return FusedCePlan(FALLBACK_BLOCK_N, FALLBACK_BLOCK_V,
                           FALLBACK_SOURCE, False)
    return FusedCePlan(
        block_n=int(e.winner.get("block_n", 0)) or FALLBACK_BLOCK_N,
        block_v=int(e.winner.get("block_v", 0)) or FALLBACK_BLOCK_V,
        source=e.source, measured=e.measured)


@functools.lru_cache(maxsize=256)
def lm_loss_winner(*, fits: bool, vocab: int, seq: int, batch: int,
                   n_devices: int = 1,
                   backend: Optional[str] = None
                   ) -> Optional[LossPathPlan]:
    """The banked LM loss-path winner for a (fits, shape) bucket, or
    None when nothing is banked (``flags.resolve_lm_loss`` then applies
    its HBM heuristic unchanged)."""
    key = dict(fits=fits, vocab=vocab, seq=seq, batch=batch,
               n_devices=n_devices, backend=backend)
    e = _cache.load_store().lookup("lm_loss", key)
    if e is None or "path" not in e.winner:
        return None
    return LossPathPlan(path=str(e.winner["path"]),
                        chunk=int(e.winner.get("chunk", 0)),
                        source=e.source, measured=e.measured)


@functools.lru_cache(maxsize=256)
def spec_k_plan(*, model: str, draft: str, n_slots: int,
                backend: Optional[str] = None) -> SpecKPlan:
    """The tuned speculative draft width for one (model, draft, slots)
    serving shape — ``DecodeEngine``'s 0-sentinel ``spec_k`` resolves
    here; an explicit ``--spec_k`` wins with a warn-once when it
    overrides a measured winner (``note_override``). Model/draft are
    architecture labels (hard-matched: a k measured for one pair never
    resolves for another); ``n_slots`` is soft (nearest batch)."""
    key = dict(model=model, draft=draft, n_slots=n_slots, backend=backend)
    e = _cache.load_store().lookup("spec_k", key)
    if e is None or "k" not in e.winner:
        return SpecKPlan(FALLBACK_SPEC_K, FALLBACK_SOURCE, False)
    return SpecKPlan(k=int(e.winner["k"]), source=e.source,
                     measured=e.measured)


@functools.lru_cache(maxsize=512)
def matmul_precision_plan(*, parallel: str, d_in: int, d_out: int,
                          dtype: str, n_devices: int = 1,
                          backend: Optional[str] = None) -> PrecisionPlan:
    """The tuned compute precision for one ``tp_dense`` projection site —
    ``precision='auto'`` resolves here; an explicit ``--matmul_precision``
    wins with a warn-once when it overrides a measured winner
    (``ops/quant.resolve_precision`` calls ``note_override``).

    ``site``/``parallel`` are hard-matched (a winner measured for the
    column ring never resolves for the row ring — different error
    model); d_in/d_out are soft (nearest shape), dtype adds the usual
    small penalty. The quality bound is enforced at SELECTION time
    (``search.select_precision_winner`` drops rows whose banked rel-err
    exceeds the ceiling), so any entry that resolves here already passed
    it — the plan just reports the winner."""
    key = dict(site="tp_dense", parallel=parallel, d_in=d_in, d_out=d_out,
               dtype=dtype, n_devices=n_devices, backend=backend)
    e = _cache.load_store().lookup("matmul_precision", key)
    if e is None or "precision" not in e.winner:
        return PrecisionPlan(FALLBACK_PRECISION, FALLBACK_SOURCE, False)
    return PrecisionPlan(precision=str(e.winner["precision"]),
                         source=e.source, measured=e.measured)


@functools.lru_cache(maxsize=256)
def _warn_override_once(kind: str, what: str, explicit: str,
                        winner: str, source: str) -> None:
    try:
        from absl import logging as absl_logging

        absl_logging.warning(
            "explicit %s %s=%s overrides the measured kernel-tune "
            "winner %s (%s); drop the explicit value to track the "
            "banked optimum, or bank a new measurement "
            "(docs/TUNING.md) if the shape changed", kind, what,
            explicit, winner, source)
    except Exception:  # pragma: no cover
        pass


def note_override(kind: str, what: str, explicit, winner, *,
                  source: str, measured: bool) -> None:
    """Warn (once per distinct override) when an explicit value beats a
    measured winner. Policy-seeded (measured=False) entries never warn —
    overriding a guess is not a finding."""
    if measured and explicit != winner:
        _warn_override_once(kind, what, str(explicit), str(winner), source)


def _clear_plans() -> None:
    flash_plan.cache_clear()
    fused_ce_plan.cache_clear()
    lm_loss_winner.cache_clear()
    spec_k_plan.cache_clear()
    matmul_precision_plan.cache_clear()
    _warn_override_once.cache_clear()


# every store invalidation (including cache.merge_entries writes) must
# drop the memoized plans too, or a same-process bank-then-resolve
# serves pre-merge winners; registered once at import.
_cache.on_invalidate(_clear_plans)


def invalidate() -> None:
    """Drop every resolver/process cache (tests plant cache files via
    DTF_KERNEL_TUNE_PATH/_GOLDEN and re-resolve)."""
    _cache.invalidate_cache()     # store + registered plan caches
