"""The comparison that decides ``correct`` for a forward pass."""

from __future__ import annotations

import math

import numpy as np

#: logits are compared at this many seeded (sequence, position) pairs
N_POSITIONS = 16


def compare(system, reference, seed: int, *, logit_rel_rms_tol: float,
            loss_abs_tol: float) -> dict:
    """``system`` and ``reference`` are ``(loss, logits [B,T,V])``. The
    losses must agree to ``loss_abs_tol``; at a few seeded positions the
    system's logits must lie within ``logit_rel_rms_tol`` of the
    reference's, as RMS error over RMS value. Logits and not arg-max tokens:
    with random weights the top two logits are close and rounding flips
    them."""
    sys_loss, sys_logits = float(system[0]), np.asarray(system[1], np.float32)
    ref_loss, ref_logits = (float(reference[0]),
                            np.asarray(reference[1], np.float32))
    b, t, _ = ref_logits.shape
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, b, N_POSITIONS)
    cols = rng.integers(0, t, N_POSITIONS)
    got, want = sys_logits[rows, cols], ref_logits[rows, cols]
    rel = float(np.sqrt(np.mean((got - want) ** 2))
                / np.sqrt(np.mean(want ** 2)))
    loss_err = abs(sys_loss - ref_loss)
    ok = (math.isfinite(sys_loss) and math.isfinite(rel)
          and rel <= logit_rel_rms_tol and loss_err <= loss_abs_tol)
    return {"ok": bool(ok), "system_loss": sys_loss,
            "reference_loss": ref_loss, "loss_abs_err": loss_err,
            "loss_abs_tol": loss_abs_tol, "logit_rel_rms_err": rel,
            "logit_rel_rms_tol": logit_rel_rms_tol,
            "positions": N_POSITIONS}
