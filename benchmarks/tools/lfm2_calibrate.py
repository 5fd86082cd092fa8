"""Re-take the readings that the ``lfm2_moe`` family's limits lie between,
in one call on the chip (PERF.md section 6 has the first set):

    python3 benchmarks/tools/lfm2_calibrate.py <requests> <seed> [--rehearse]

Serves ``requests`` requests of ``lfm2-serve-closed32``'s lengths on the
cell's engine (not timed), drops the engine, and reads every statistic of
``families/lfm2_moe.py``'s comparison on the SAME emitted tokens under the
sound reference and under each fault of ``lfm2_faults.py``. Prints one line
a reference and writes every request's numbers to
``chiprun_out/lfm2_calibration.json``. About eleven chip-minutes for 24
requests. The boundary between prompt and emitted tokens is known here,
which the family has to find again: the shares are the true ones.
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

from benchmarks.families import lfm2_moe as family  # noqa: E402
from benchmarks.lib import loadgen  # noqa: E402
from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from benchmarks.tools import lfm2_faults  # noqa: E402
from dtf_tpu.parallel import moe  # noqa: E402
from dtf_tpu.serve.engine import DecodeEngine  # noqa: E402
from dtf_tpu.serve.scheduler import Request, Scheduler  # noqa: E402


def _load(name: str, rehearse: bool) -> dict:
    with open(os.path.join(ROOT, "benchmarks", name)) as f:
        data = json.load(f)
    return {**data, **data["rehearse"]} if rehearse else data


def serve(fam, params, traffic: dict, n: int, seed: int) -> list:
    """(prompt, emitted tokens) of ``n`` requests, the pool's from its 40th
    on (another stretch than a window's first)."""
    sched = Scheduler(DecodeEngine(fam.cfg, params, **traffic["engine"]))
    lengths = loadgen.request_lengths(traffic["lengths"])
    rng = np.random.default_rng(seed)
    jobs = []
    for i in range(n):
        n_prompt, n_out = lengths[(40 + i) % len(lengths)]
        prompt = rng.integers(0, fam.vocab_size, int(n_prompt)).tolist()
        jobs.append((sched.submit(Request(prompt=prompt,
                                          max_new=int(n_out))), prompt))
    sched.run_until_idle()
    return [(prompt, sched.poll(rid)["tokens"]) for rid, prompt in jobs]


def readings(fam, config: dict, params, sample: list, pad: int) -> list:
    """Per request, under the reference module as it stands now: every
    emitted token's shortfall, each expert layer's median error, the pairs
    routed otherwise."""
    kwargs = family.reference_kwargs(config)
    layer = moe.DroplessMoE(fam.cfg.d_model, fam.cfg.experts,
                            dtype=fam.cfg.dtype,
                            param_dtype=fam.cfg.param_dtype)
    n_pos = min(family.LAYER_POSITIONS, pad)

    @jax.jit
    def read(params, ids):
        logits, seen = ref.forward(params, ids, return_experts=True,
                                   **kwargs)
        nxt = jnp.roll(ids, -1, axis=1)
        short = logits.max(-1) - jnp.take_along_axis(
            logits, nxt[..., None], -1)[..., 0]
        errors = []
        for i, given in zip(range(config["num_dense_layers"],
                                  config["num_hidden_layers"]),
                            seen["inputs"][:, :, :n_pos]):
            p = params[f"layer_{i}"]["experts"]
            given = given.astype(fam.cfg.dtype)
            got = layer.apply({"params": p}, given).astype(jnp.float32)
            with jax.default_matmul_precision("highest"):
                want = ref.experts_layer(
                    given.astype(jnp.float32), p, top_k=kwargs["top_k"],
                    norm_topk_prob=kwargs["norm_topk_prob"],
                    scale=kwargs["routed_scaling_factor"])[0]
            errors.append(jnp.linalg.norm(got - want, axis=-1)
                          / jnp.linalg.norm(want, axis=-1))
        return short[0], jnp.stack(errors)[:, 0]

    rows = []
    for prompt, emitted in sample:
        seq = list(prompt) + list(emitted)
        ids = np.zeros((1, pad), np.int32)
        ids[0, :len(seq)] = seq
        short, errors = (np.asarray(x) for x in read(params,
                                                      jnp.asarray(ids)))
        errors = errors[:, :min(len(seq), n_pos)]
        short = short[len(prompt) - 1:len(seq) - 1]
        within = float((short <= family.TOKEN_LOGIT_TOL).mean())
        rows.append({
            "emitted": len(emitted), "within_share": within,
            "within_score": within + family.WITHIN_SMALL_SAMPLE
            / len(emitted) ** 0.5,
            "worst_shortfall": float(short.max()),
            "layer_error": float(np.median(errors, axis=1).max()),
            "rerouted_share": float(
                (errors > family.REROUTED_ERROR).mean()),
            "largest_error": float(errors.max())})
    return rows


def main(argv) -> int:
    n, seed, rehearse = int(argv[0]), int(argv[1]), "--rehearse" in argv
    config = _load("configs/lfm2-24b-a2b.json", rehearse)
    traffic = _load("traffic/serve-closed-32-chat4k.json", rehearse)
    fam = family.build_serve(config)
    params = fam.init_params(jax.random.PRNGKey(seed))
    sample = serve(fam, params, traffic, n, seed)
    pad = -(-max(len(p) + len(t) for p, t in sample) // 256) * 256
    out = {"seed": seed, "references": {}}
    for name in ["sound", *lfm2_faults.FAULTS]:
        undo = lfm2_faults.apply(name) if name != "sound" else None
        try:
            rows = readings(fam, config, params, sample, pad)
        finally:
            if undo:
                undo()
        out["references"][name] = rows
        print(name, json.dumps({
            key: [min(r[key] for r in rows), max(r[key] for r in rows)]
            for key in ("layer_error", "rerouted_share", "within_share",
                        "within_score")}), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/lfm2_calibration.json", "w") as f:
        json.dump(out, f)
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
