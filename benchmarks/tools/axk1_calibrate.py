"""Re-take the readings that the ``axk1`` family's limits lie between, in
one call on the chip (``benchmarks/AXK1.md`` has the first set):

    python3 benchmarks/tools/axk1_calibrate.py <requests> <seed> \
        [--rehearse] [--only sound,<fault>,...]

Serves ``requests`` requests of ``axk1-serve-closed32-doc16k``'s lengths on
the cell's engine (not timed), drops the engine, and reads every statistic
of ``families/axk1.py``'s comparison on the SAME emitted tokens under the
sound reference and under each fault of ``axk1_faults.py``. Prints one line
a reference and writes every request's numbers to
``chiprun_out/axk1_calibration.json``. The boundary between prompt and
emitted tokens is known here, which the family has to find again: the
shares are the true ones.
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)

from benchmarks.families import axk1 as family  # noqa: E402
from benchmarks.reference import axk1 as ref  # noqa: E402
from benchmarks.tools import axk1_faults  # noqa: E402
from benchmarks.tools.lfm2_calibrate import _load, serve  # noqa: E402

READINGS = ("layer_error", "rerouted_share", "attn_error",
            "attn_error_decoded", "within_share", "within_score")


def readings(fam, config: dict, params, sample: list, pad: int) -> list:
    """Per request, under the reference module as it stands now: every
    emitted token's shortfall and the three readings of the program's
    layers on the reference's inputs."""
    n_pos = min(family.LAYER_POSITIONS, pad)
    held = tuple(config["experts_held"])
    program = family.program_readings(fam.cfg, config)

    @jax.jit
    def read(params, ids, n_seq):
        logits, seen = ref.forward(params, ids, config, experts_held=held,
                                   seen_positions=n_pos,
                                   seen_attention=family.ATTN_POSITIONS)
        nxt = jnp.roll(ids, -1, axis=1)
        short = logits.max(-1) - jnp.take_along_axis(
            logits, nxt[..., None], -1)[..., 0]
        real = (jnp.arange(ids.shape[1])[None, :] < n_seq)[:, :n_pos]
        return short[0], program(params, seen, real)

    rows = []
    for prompt, emitted in sample:
        seq = list(prompt) + list(emitted)
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        short, got = read(params, jnp.asarray(ids), len(seq))
        short = np.asarray(short)[len(prompt) - 1:len(seq) - 1]
        within = float((short <= family.TOKEN_LOGIT_TOL).mean())
        rows.append({
            "prompt": len(prompt), "emitted": len(emitted),
            "within_share": within,
            "within_score": within + family.WITHIN_SMALL_SAMPLE
            / len(emitted) ** 0.5,
            "worst_shortfall": float(short.max()),
            **{name: float(value) for name, value in got.items()}})
    return rows


def main(argv) -> int:
    n, seed, rehearse = int(argv[0]), int(argv[1]), "--rehearse" in argv
    config = _load("configs/ax-k1.json", rehearse)
    traffic = _load("traffic/serve-closed-32-doc16k.json", rehearse)
    fam = family.build_serve(config)
    params = fam.init_params(jax.random.PRNGKey(seed))
    sample = serve(fam, params, traffic, n, seed)
    pad = -(-max(len(p) + len(t) for p, t in sample) // 2048) * 2048
    only = (argv[argv.index("--only") + 1].split(",")
            if "--only" in argv else None)
    out = {"seed": seed, "references": {}}
    for name in ["sound", *axk1_faults.FAULTS]:
        if only and name not in only:
            continue
        undo = axk1_faults.apply(name) if name != "sound" else None
        try:
            rows = readings(fam, config, params, sample, pad)
        finally:
            if undo:
                undo()
        out["references"][name] = rows
        print(name, json.dumps({
            key: [min(r[key] for r in rows), max(r[key] for r in rows)]
            for key in READINGS}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/axk1_calibration.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
