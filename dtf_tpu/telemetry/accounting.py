"""MFU / goodput accounting — "what fraction of the hardware are we using,
and what fraction of the wall clock actually trained?".

MFU follows two conventions:

- **analytic**: 6 FLOPs per parameter per token (fwd+bwd weight FLOPs)
  plus the attention term ``12·L·d·s`` per token — the "Scalable Training
  of Language Models using JAX pjit and TPUv4" (arxiv 2204.06514)
  accounting, comparable across papers;
- **XLA cost analysis**: the AOT ``compiled.cost_analysis()`` flops of the
  actual program — a LOWER bound (scan
  bodies counted once, Pallas custom calls report zero).

Goodput = productive step wall time / total run wall time, with the
non-productive remainder attributed to named buckets (compile, checkpoint,
eval, logging, restore, data_wait, h2d, other) — the run-level accounting
the TPU-pod scaling literature reports runs by. Bucket seconds come from
host timers only (the trainer's per-hook timing plus jax.monitoring's
compile-duration events); nothing here reads a device value.
"""

from __future__ import annotations

from typing import Mapping, Optional

#: Published peaks of ONE chip, keyed by the ``device_kind`` JAX reports.
#: THE table: every utilization this repo prints divides by a row of it.
#: "TPU v5 lite" is the v5e — Google Cloud documentation, "TPU v5e":
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB of HBM at 819 GB/s.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}

#: ResNet-50 v1.5 @224 fwd ≈ 4.09e9 MAC-derived FLOPs/image (2 FLOPs per
#: MAC), training ≈ 3× fwd.
RESNET50_TRAIN_FLOPS_PER_IMG = 3 * 4.09e9


def device_peaks(device=None) -> Optional[dict]:
    """The :data:`DEVICE_PEAKS` row of ``device`` (default: the first
    device JAX reports). ``None`` on the CPU — a CPU run reports no
    utilization rather than one against a chip it did not run on. An
    accelerator missing from the table is an error, not a default."""
    import jax

    d = device if device is not None else jax.devices()[0]
    if d.platform == "cpu":
        return None
    if d.device_kind not in DEVICE_PEAKS:
        raise ValueError(
            f"no published peaks for device_kind {d.device_kind!r} "
            f"(platform {d.platform!r}); add a sourced row to "
            f"telemetry.accounting.DEVICE_PEAKS")
    return DEVICE_PEAKS[d.device_kind]


def device_peak_flops(device=None) -> Optional[float]:
    """bf16 peak FLOP/s of one chip, or None on the CPU."""
    peaks = device_peaks(device)
    return peaks["bf16_flops"] if peaks else None

#: goodput buckets the trainer/hook instrumentation feeds; anything else
#: lands in "other" so the report always sums to the measured overhead.
GOODPUT_BUCKETS = ("compile", "checkpoint", "eval", "logging", "restore",
                   "data_wait", "h2d", "hooks", "profile", "preempt_sync",
                   "other")

#: buckets that are BACKPRESSURE, not lost time: in the sync-free loop the
#: host blocks inside LoggingHook's metrics readback (and generic hooks)
#: precisely while the DEVICE works through the dispatched step queue —
#: charging that wait as overhead would invert goodput on healthy runs
#: (report ~0.1 while the device is ~99% busy). h2d is the async transfer
#: dispatch overlapping compute. preempt_sync is PreemptionHook's periodic
#: multi-host flag allgather — a device readback that absorbs the host's
#: accumulated run-ahead exactly like LoggingHook's metrics readback (the
#: rare preemption-save it also covers is once-per-dying-run noise). These
#: are reported per-bucket but excluded from the productive-time
#: subtraction.
BACKPRESSURE_BUCKETS = ("logging", "hooks", "h2d", "preempt_sync")


def param_count(params) -> int:
    """Total parameter count from array METADATA only (``x.size`` never
    materializes a value, so this is safe on live training state)."""
    import jax

    return int(sum(x.size for x in jax.tree.leaves(params)))


def analytic_lm_flops_per_step(*, n_params: int, layers: int, width: int,
                               seq_len: int, tokens_per_step: int) -> float:
    """Full-step (fwd+bwd) FLOPs for a dense transformer LM step —
    ``(6·N + 12·L·d·s) · tokens``, ``N`` being every parameter the
    caller counts (the benchmark's ``mfu_pct`` leaves out tables that are
    only looked up: ``benchmarks/lib/flops.py``)."""
    return float(6 * n_params + 12 * layers * width * seq_len) \
        * tokens_per_step


def cost_analysis_flops(fn, *args) -> Optional[float]:
    """Best-effort AOT flops of ``fn(*args)``.

    Returns None when the backend/program offers no cost analysis. NOTE:
    lowering here is a fresh trace of ``fn`` — callers that pin trace
    counts (the compile fence) must account for it or prefer the analytic
    path.
    """
    try:
        # chipless cost analysis of a caller-owned program — no
        # aot-ok: fence/pins/donation decision is being made here
        cost = fn.lower(*args).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0]
        flops = float(cost.get("flops", 0.0))
        return flops or None
    except Exception:
        return None


class GoodputTracker:
    """Accumulates overhead seconds into named buckets.

    ``account(bucket, seconds)`` from anywhere on the host (trainer hook
    timing, checkpoint restore, compile-duration events). Unknown bucket
    names fold into ``other`` — the report must always reconcile.
    """

    def __init__(self):
        self.buckets: dict[str, float] = {}

    def account(self, bucket: str, seconds: float) -> None:
        if bucket not in GOODPUT_BUCKETS:
            bucket = "other"
        self.buckets[bucket] = self.buckets.get(bucket, 0.0) + seconds

    def report(self, total_s: float) -> Mapping[str, float]:
        """``{goodput, productive_s, <bucket>_s...}`` for ``total_s`` of
        wall clock. Productive = total − Σ overheads, clamped at 0, where
        overhead EXCLUDES the :data:`BACKPRESSURE_BUCKETS` (the host's
        wait on device compute — see their note). Remaining bucket times
        can still overlap the async device timeline (a compile inside the
        first dispatch), so the subtraction is an upper bound on lost
        time, i.e. goodput is conservative on short runs."""
        overhead = sum(s for b, s in self.buckets.items()
                       if b not in BACKPRESSURE_BUCKETS)
        productive = max(total_s - overhead, 0.0)
        out = {"goodput": round(productive / total_s, 4) if total_s else 0.0,
               "productive_s": round(productive, 3),
               "total_s": round(total_s, 3)}
        for name, s in sorted(self.buckets.items()):
            out[f"{name}_s"] = round(s, 3)
        return out
