#!/usr/bin/env python
"""PP activation-memory measurement.

The GPipe schedule is one differentiated ``lax.scan``: autodiff stashes
each scan step's residuals, so WITHOUT remat the backward keeps
O(n_microbatches) per-stage activations live — the classic GPipe stash.
``cfg.remat`` wraps every block in ``jax.checkpoint`` inside the stage, so
only the per-microbatch block INPUTS stay stashed and the rest
rematerializes in the backward.

This script puts numbers on that trade with XLA's own allocator report
(``compiled.memory_analysis().temp_size_in_bytes`` — peak temp allocation
of the compiled fwd+bwd program), across remat on/off and two microbatch
counts, plus the fused-1F1B schedule (``pipeline_1f1b_grads``: forwards
and backwards interleaved in one scan, O(stages) stash, stage recompute
built in) and its ZERO-BUBBLE variant (``pipeline_zb_grads``, ISSUE 18:
backward split into B/W, W deferred into the drain bubble — one extra
depth-S cotangent ring on top of 1F1B's stash) against the same model.
Alongside the measured temps, ``schedule_bubble_model`` prices the IDLE
fraction of both fused schedules at m4/m8 (pure step-count dependency
sim, no compile): the artifact shows what the extra ZB stash buys.
Pure compile-time analysis on the CPU sim: no TPU, no probe, no
timing. Artifact:
``PIPE_MEM.json`` (+ one JSON line per row on stdout); regeneration
MERGES by (schedule, remat, n_microbatches) key, preserving rows a
given run doesn't re-measure.

Cross-check (ISSUE 9 satellite): a GLOBAL-BATCH sweep per schedule
family — temp measured at batch B/2 and B, extrapolated to 2B with the
memory pass's affine model (``dtf_tpu.analysis.memory.affine_temp_model``
— the exact primitive ``python -m dtf_tpu.analysis fit`` inverts max
batch with), and ASSERTED against XLA's measured 2B number within
``PREDICT_TOL``: each 2B row carries a ``predicted_temp_bytes`` column
next to its measured one.  (Batch, not microbatch count, is the swept
axis on purpose: at fixed global batch a higher ``n_microbatches``
SHRINKS each microbatch, so temp is deliberately non-affine there —
that trade is what the main rows above measure.)
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "PIPE_MEM.json")

#: measured-vs-predicted relative tolerance for the affine temp model —
#: XLA's allocator is piecewise (fusion decisions shift with shapes),
#: but stash + working set grow linearly in batch rows; beyond this the
#: fit planner's batch inversion can't be trusted.  Measured slack on
#: this stack: 0.6% (gpipe), 2.7% (gpipe+remat).
PREDICT_TOL = 0.25


def main():
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import shard_batch
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.models import gpt, gpt_pipe

    # explicit 4-device subset: the 8-device sim would otherwise demand
    # every axis product == 8
    mesh = make_mesh(MeshConfig(data=2, pipe=2), devices=jax.devices()[:4])
    seq = int(os.environ.get("DTF_PIPEMEM_SEQ", "256"))
    batch = int(os.environ.get("DTF_PIPEMEM_BATCH", "16"))
    base = gpt.GPTConfig(vocab_size=512, d_model=256, layers=8, heads=8,
                         d_ff=1024, dtype=jnp.float32)
    data = SyntheticData("gpt", batch, seed=0, seq_len=seq,
                         vocab_size=base.vocab_size).batch(0)

    rows = []
    for remat in (False, True):
        cfg = dataclasses.replace(base, remat=remat)
        for n_micro in (4, 8):
            init_fn = gpt_pipe.make_pipe_init(cfg, mesh, seq_len=seq)
            loss_fn = gpt_pipe.make_pipe_loss(cfg, mesh,
                                              n_microbatches=n_micro)
            tx = optax.sgd(1e-3)
            state, shardings = tr.create_train_state(
                init_fn, tx, jax.random.PRNGKey(0), mesh,
                param_rules=gpt_pipe.pipe_rules())
            sharded = shard_batch(data, mesh)

            def fwdbwd(st, bt):
                (loss, _), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, st.extra, bt,
                                      jax.random.PRNGKey(0)),
                    has_aux=True)(st.params)
                return loss, grads

            mem = (jax.jit(fwdbwd)  # aot-ok: bench measurement lowering
                   .lower(state, sharded).compile()
                   .memory_analysis())
            row = {"schedule": "gpipe", "remat": remat,
                   "n_microbatches": n_micro,
                   "temp_bytes": int(mem.temp_size_in_bytes),
                   "arg_bytes": int(mem.argument_size_in_bytes),
                   "out_bytes": int(mem.output_size_in_bytes)}
            rows.append(row)
            print(json.dumps(row), flush=True)

            if remat:
                continue   # the fused schedules' remat IS the schedule
            for sched, maker in (
                    ("1f1b", gpt_pipe.make_pipe_grads_1f1b),
                    ("zb", gpt_pipe.make_pipe_grads_zb)):
                grads_fused = maker(cfg, mesh, n_microbatches=n_micro)

                def fwdbwd_fused(st, bt):
                    loss, _, grads = grads_fused(st.params, st.extra, bt,
                                                 jax.random.PRNGKey(0))
                    return loss, grads

                # measurement lowering of a bench-local wrapper program
                mem = (jax.jit(fwdbwd_fused)  # aot-ok: bench measurement
                       .lower(state, sharded).compile()
                       .memory_analysis())
                row = {"schedule": sched, "remat": False,
                       "n_microbatches": n_micro,
                       "temp_bytes": int(mem.temp_size_in_bytes),
                       "arg_bytes": int(mem.argument_size_in_bytes),
                       "out_bytes": int(mem.output_size_in_bytes)}
                rows.append(row)
                print(json.dumps(row), flush=True)

    # --- batch sweep: the memory pass's affine temp model vs XLA -------
    # temp(batch) measured at B/2 and B, extrapolated to 2B, asserted
    # against the real 2B compile — per schedule family at n_micro=4.
    from dtf_tpu.analysis import memory as memory_pass

    def temp_at(remat, schedule, batch_rows):
        cfg = dataclasses.replace(base, remat=remat)
        init_fn = gpt_pipe.make_pipe_init(cfg, mesh, seq_len=seq)
        tx = optax.sgd(1e-3)
        state, _ = tr.create_train_state(
            init_fn, tx, jax.random.PRNGKey(0), mesh,
            param_rules=gpt_pipe.pipe_rules())
        data = SyntheticData("gpt", batch_rows, seed=0, seq_len=seq,
                             vocab_size=base.vocab_size).batch(0)
        sharded = shard_batch(data, mesh)
        if schedule in ("1f1b", "zb"):
            maker = (gpt_pipe.make_pipe_grads_1f1b if schedule == "1f1b"
                     else gpt_pipe.make_pipe_grads_zb)
            grads_fn = maker(cfg, mesh, n_microbatches=4)

            def fwdbwd(st, bt):
                loss, _, grads = grads_fn(st.params, st.extra, bt,
                                          jax.random.PRNGKey(0))
                return loss, grads
        else:
            loss_fn = gpt_pipe.make_pipe_loss(cfg, mesh, n_microbatches=4)

            def fwdbwd(st, bt):
                (loss, _), grads = jax.value_and_grad(
                    lambda p: loss_fn(p, st.extra, bt,
                                      jax.random.PRNGKey(0)),
                    has_aux=True)(st.params)
                return loss, grads

        mem = (jax.jit(fwdbwd)  # aot-ok: bench measurement lowering
               .lower(state, sharded).compile()
               .memory_analysis())
        return int(mem.temp_size_in_bytes)

    predict_ok = True
    sweep = []
    for sched, remat in (("gpipe", False), ("gpipe", True),
                         ("1f1b", False), ("zb", False)):
        temps = {b: temp_at(remat, sched, b)
                 for b in (batch // 2, batch, 2 * batch)}
        model = memory_pass.affine_temp_model(
            {b: temps[b] for b in (batch // 2, batch)})
        pred = memory_pass.predict_temp(model, 2 * batch)
        meas = temps[2 * batch]
        err = abs(pred - meas) / max(meas, 1)
        row = {"schedule": sched, "remat": remat, "n_microbatches": 4,
               "batch_sweep": {str(b): t for b, t in temps.items()},
               "temp_bytes": meas, "batch": 2 * batch,
               "predicted_temp_bytes": pred,
               "predict_rel_err": round(err, 4)}
        sweep.append(row)
        print(json.dumps(row), flush=True)
        predict_ok = predict_ok and err <= PREDICT_TOL

    # --- step-count bubble model: what the extra ZB stash buys ---------
    # pure dependency-graph sim (parallel/pipeline.schedule_bubble_model)
    # at the measured mesh's S=2 and the ISSUE 18 reference point S=4 —
    # ZB's modeled idle fraction must sit strictly below 1F1B's.
    from dtf_tpu.parallel.pipeline import schedule_bubble_model

    bubble_rows = []
    zb_beats_1f1b = True
    for n_stages in (2, 4):
        for n_micro in (4, 8):
            pair = {}
            for sched in ("1f1b", "zb"):
                m = schedule_bubble_model(n_stages, n_micro, sched)
                pair[sched] = m
                bubble_rows.append(m)
                print(json.dumps(m), flush=True)
            zb_beats_1f1b = zb_beats_1f1b and (
                pair["zb"]["idle_frac"] < pair["1f1b"]["idle_frac"])

    base_row = next(r for r in rows if r["schedule"] == "gpipe"
                    and not r["remat"] and r["n_microbatches"] == 8)
    remat_row = next(r for r in rows if r["schedule"] == "gpipe"
                     and r["remat"] and r["n_microbatches"] == 8)
    f1b_row = next(r for r in rows if r["schedule"] == "1f1b"
                   and r["n_microbatches"] == 8)
    zb_row = next(r for r in rows if r["schedule"] == "zb"
                  and r["n_microbatches"] == 8)
    summary = {
        "config": {"d_model": base.d_model, "layers": base.layers,
                   "d_ff": base.d_ff, "seq": seq, "batch": batch,
                   "mesh": "data2 x pipe2", "backend":
                   jax.default_backend()},
        "rows": rows,
        "remat_temp_reduction_at_m8": round(
            base_row["temp_bytes"] / max(remat_row["temp_bytes"], 1), 2),
        "1f1b_temp_reduction_at_m8": round(
            base_row["temp_bytes"] / max(f1b_row["temp_bytes"], 1), 2),
        "1f1b_vs_gpipe_remat_at_m8": round(
            remat_row["temp_bytes"] / max(f1b_row["temp_bytes"], 1), 2),
        "zb_temp_overhead_vs_1f1b_at_m8": round(
            zb_row["temp_bytes"] / max(f1b_row["temp_bytes"], 1), 2),
        "batch_sweep": sweep,
        "predict_tol": PREDICT_TOL,
        "predicted_within_tol": predict_ok,
        "bubble_model": bubble_rows,
        "zb_idle_below_1f1b": zb_beats_1f1b,
    }
    # merge-preserving regeneration: rows from an older artifact that this
    # run did NOT re-measure (other seq/batch env settings, future
    # schedules) survive; re-measured keys are replaced in place.
    if os.path.exists(ARTIFACT):
        try:
            with open(ARTIFACT) as f:
                old = json.load(f)
        except (OSError, ValueError):
            old = {}
        key = lambda r: (r.get("schedule"), r.get("remat"),
                         r.get("n_microbatches"))
        fresh = {key(r) for r in rows}
        summary["rows"] = rows + [r for r in old.get("rows", ())
                                  if key(r) not in fresh]
    with open(ARTIFACT, "w") as f:
        json.dump(summary, f, indent=1)
    print(json.dumps({"remat_temp_reduction_at_m8":
                      summary["remat_temp_reduction_at_m8"],
                      "1f1b_temp_reduction_at_m8":
                      summary["1f1b_temp_reduction_at_m8"],
                      "zb_temp_overhead_vs_1f1b_at_m8":
                      summary["zb_temp_overhead_vs_1f1b_at_m8"],
                      "zb_idle_below_1f1b": zb_beats_1f1b,
                      "predicted_within_tol": predict_ok}))
    # ISSUE 18's schedule contract: deferring W into the drain bubble
    # must shrink modeled idle at every (S, M) this artifact prices.
    assert zb_beats_1f1b, (
        "zero-bubble modeled idle_frac not strictly below 1F1B's — see "
        "PIPE_MEM.json bubble_model rows")
    # the cross-check satellite's contract: affine extrapolation must
    # track XLA's allocator — fail loudly (after writing the artifact,
    # so the rows are inspectable) when it doesn't.
    assert predict_ok, (
        f"predicted_temp_bytes off by more than {PREDICT_TOL:.0%} on at "
        f"least one batch-sweep row (batch={2 * batch}, n_micro=4) — see "
        f"PIPE_MEM.json batch_sweep[].predict_rel_err")


if __name__ == "__main__":
    main()
