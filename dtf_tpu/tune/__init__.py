"""Kernel autotuner: persistent block-shape / loss-path winners.

Flash block shapes (forward and backward each have their own optimum)
and the LM loss path (the monolithic [B,T,V] matmul while the logits
fit, token chunking as the bounded-memory fallback) are choices that
depend on the shape and the chip. This package keeps such winners in a
file instead of hard-coded literals — the same static-search-then-pin
discipline the pjit-TPUv4 work applies to sharding (PAPERS.md, arxiv
2204.06514):

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

Nothing in the repo measures for the tuner today (docs/TUNING.md): an
entry changes in the PR that measures it on the chip, through
``merge_entries`` or ``python -m dtf_tpu.tune seed``. The whole package
is jax-free at module level (the telemetry/ discipline) — a parent whose
children need the chip stays off jax, and resolution must work on a
backendless machine.

Docs: docs/TUNING.md.
"""

from dtf_tpu.tune.cache import (Entry, TuneStore, golden_path,  # noqa: F401
                                invalidate_cache, load_store, local_path,
                                merge_entries)
from dtf_tpu.tune.resolver import (FlashPlan, FusedCePlan,  # noqa: F401
                                   LossPathPlan, flash_plan, fused_ce_plan,
                                   invalidate, lm_loss_winner)
