"""Static sharding & collectives analyzer.

Validates the framework's parallelism configuration WITHOUT touching a
device, so a wrong regex rule, a mesh-indivisible dimension, or an
XLA-inserted resharding all-gather fails in tier-1 in seconds instead of
burning chip time (budgeted per PR).

Three passes, one CLI (``python -m dtf_tpu.analysis``):

- :mod:`dtf_tpu.analysis.specs` — rulebook linting: dead/shadowed regex
  rules, duplicate mesh axes in one spec, rank overflow, mesh-indivisible
  dims, large leaves silently falling to REPLICATED, and the same checks on
  every optimizer family's ZeRO-1 state specs.
- :mod:`dtf_tpu.analysis.hlo` — AOT-compile the real pjit train step on the
  8-device CPU sim, parse the optimized HLO, and fence the collective mix
  (counts + bytes) against the committed ``STATIC_ANALYSIS.json`` golden.
- :mod:`dtf_tpu.analysis.jaxpr` — trace-level lints: float64 leaks, host
  callbacks inside the step, axis collectives outside ``shard_map``.
- :mod:`dtf_tpu.analysis.host` — host-plane soundness over the jax-free
  control plane (serve/fault/telemetry/data-stream/publish): lock
  discipline, signal-handler deadlock, atomic-write choke point, clock
  discipline (pure AST on :mod:`dtf_tpu.analysis.hostmodel`, no imports).

The config registry (:mod:`dtf_tpu.analysis.configs`) covers the five
BASELINE workloads plus the GPT flagship and the ``gpt_pipe*`` variants.
"""

from dtf_tpu.analysis.findings import Finding, severity_counts  # noqa: F401
