"""Kernel autotuner: persistent block-shape / loss-path winners.

PERF.md round 5 closed with every remaining MFU lever measured but
hand-tuned: the on-chip block sweep showed 512x1024 flash blocks run the
same attention 2.75x faster than the old 512x512 default, the backward
runs ~92 TF/s against the forward's ~170 with its own (separately
swept) block optimum, and the loss-path data says the monolithic
[B,T,V] matmul wins while it fits and token chunking is the right
bounded-memory fallback. Each of those findings used to be flipped into
a hard-coded literal by hand each round. This package is the mechanism
that does it automatically — the same static-search-then-pin discipline
the pjit-TPUv4 work applies to sharding (PAPERS.md, arxiv 2204.06514):

- :mod:`dtf_tpu.tune.cache` — the persistent winner store: a committed
  repo golden ``KERNEL_TUNE.json`` (banked on-chip winners, readable
  where there is no chip) shadowed by a machine-local
  ``KERNEL_TUNE.local.json`` next to ``.jax_cache/`` (winners measured
  on THIS machine, gitignored), with nearest-shape lookup so a query at
  an unswept shape resolves to the closest banked winner instead of a
  hard-coded literal.
- :mod:`dtf_tpu.tune.search` — the candidate spaces, the deterministic
  winner selection, and the artifact seeding that turns the committed
  sweep rows (KERNEL_TUNE_SWEEP.json block sweeps, BENCH_LM_SWEEP.json
  loss rows) into golden entries.
- :mod:`dtf_tpu.tune.resolver` — the read side consumed by the kernels
  and launchers: ``flash_attention`` / ``pallas_lm_cross_entropy``
  resolve 0-valued block args here, ``flags.resolve_lm_loss`` resolves
  the LM loss path here. Explicit values still win (with a warning when
  they override a measured winner).

``scripts/bench_tune.py`` is the write side: its parent starts one
child per candidate, so the whole package is jax-free at module level
(the telemetry/ discipline) — a parent whose children need the chip
stays off jax, and resolution must work on a backendless machine.

Docs: docs/TUNING.md.
"""

from dtf_tpu.tune.cache import (Entry, TuneStore, golden_path,  # noqa: F401
                                invalidate_cache, load_store, local_path,
                                merge_entries)
from dtf_tpu.tune.resolver import (FlashPlan, FusedCePlan,  # noqa: F401
                                   LossPathPlan, flash_plan, fused_ce_plan,
                                   invalidate, lm_loss_winner)
