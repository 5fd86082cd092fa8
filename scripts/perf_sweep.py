#!/usr/bin/env python
"""MFU diagnosis for the ResNet-50 bench.

Runs a matrix of experiments on the real chip, each in a watchdogged
subprocess, one after the other (the process contract of bench.py):

- batch sweep: step time at batch 128/256/512/1024;
- XLA's own FLOP count for the compiled step (``compiled.cost_analysis()``)
  so the analytic 3x4.09 GFLOP/img constant in bench.py is cross-checked
  against the compiler instead of trusted;
- dispatch-mode A/B: per-step Python dispatch vs K steps folded into one
  device-side ``lax.scan`` — isolates host->TPU dispatch latency from
  device compute time.

Writes ``PERF_SWEEP.json`` at the repo root; PERF.md interprets it.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "PERF_SWEEP.json")
SENTINEL = "PERF_ROW "
CHILD_TIMEOUT_S = 900


def child():
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import shard_batch
    from dtf_tpu.core.mesh import make_mesh
    from dtf_tpu.models import resnet
    from dtf_tpu.telemetry.accounting import (RESNET50_TRAIN_FLOPS_PER_IMG,
                                              device_peak_flops)

    batch = int(os.environ["DTF_PERF_BATCH"])
    # dispatch | scan | profile
    mode = os.environ.get("DTF_PERF_MODE", "dispatch")
    n_steps = int(os.environ.get("DTF_PERF_STEPS", "20"))
    bf16_input = os.environ.get("DTF_PERF_BF16_IN") == "1"

    mesh = make_mesh()
    model = resnet.resnet50()
    tx = optax.sgd(0.1, momentum=0.9)
    state, shardings = tr.create_train_state(
        resnet.make_init(model, (224, 224, 3)), tx, jax.random.PRNGKey(0),
        mesh)
    step = tr.make_train_step(resnet.make_loss(model), tx, mesh, shardings,
                              log_grad_norm=False)

    rng = np.random.default_rng(0)
    img = rng.random((batch, 224, 224, 3), np.float32)
    if bf16_input:
        # host-side bf16 (ml_dtypes): the transfer and the model input are
        # half the bytes; no device round-trip before shard_batch.
        import ml_dtypes
        img = img.astype(ml_dtypes.bfloat16)
    data = shard_batch(
        {"image": img,
         "label": rng.integers(0, 1000, (batch,)).astype(np.int32)}, mesh)

    row = {"batch": batch, "mode": mode, "n_steps": n_steps,
           "bf16_input": bf16_input, "backend": jax.default_backend()}

    # XLA's own cost model for one compiled step (only once, on the 128 run).
    if os.environ.get("DTF_PERF_COST") == "1":
        try:
            # aot-ok: one-shot XLA cost model of the swept step
            traced = step.lower(state, data)
            cost = traced.compile().cost_analysis()  # aot-ok: cost leg
            if isinstance(cost, (list, tuple)):
                cost = cost[0]
            row["xla_flops_per_step"] = float(cost.get("flops", 0.0))
            row["xla_bytes_accessed"] = float(cost.get("bytes accessed", 0.0))
        except Exception as e:  # cost_analysis is best-effort per backend
            row["cost_error"] = repr(e)[:300]

    if mode == "scan":
        # Fold K steps into one jit call: an inner non-donating jitted step
        # scanned on-device. Removes per-step host dispatch entirely — the
        # delta vs "dispatch" mode IS the dispatch overhead.
        raw = tr.make_train_step(resnet.make_loss(model), tx, mesh, shardings,
                                 log_grad_norm=False, donate=False)

        @jax.jit
        def k_steps(state, data):
            def body(s, _):
                s2, m = raw(s, data)
                return s2, m["loss"]
            return jax.lax.scan(body, state, None, length=n_steps)

        # fence with a VALUE READBACK: float() forces the transfer, which
        # cannot complete before the program has.
        state2, losses = k_steps(state, data)
        float(losses[-1])
        t0 = time.perf_counter()
        state2, losses = k_steps(state, data)
        float(losses[-1])
        dt = time.perf_counter() - t0
    elif mode == "profile":
        import glob
        import gzip
        prof_dir = os.path.join(ROOT, "profile_r03")
        for _ in range(3):
            state, metrics = step(state, data)
        float(metrics["loss"])
        with jax.profiler.trace(prof_dir):
            t0 = time.perf_counter()
            for _ in range(n_steps):
                state, metrics = step(state, data)
            float(metrics["loss"])
            dt = time.perf_counter() - t0
        # parse the XPlane with the tensorboard profile plugin → top ops
        try:
            from tensorboard_plugin_profile.convert import raw_to_tool_data
            xplanes = glob.glob(os.path.join(
                prof_dir, "plugins/profile/*/*.xplane.pb"))
            data_str, _ = raw_to_tool_data.xspace_to_tool_data(
                [xplanes[-1]], "framework_op_stats", {"tqx": "out:csv;"})
            if isinstance(data_str, bytes):
                data_str = data_str.decode()
            if data_str.startswith("\x1f\x8b".encode().decode("latin1")):
                data_str = gzip.decompress(
                    data_str.encode("latin1")).decode()
            row["op_stats_csv_head"] = "\n".join(
                data_str.splitlines()[:25])
        except Exception as e:
            row["profile_parse_error"] = repr(e)[:500]
    else:
        for _ in range(3):
            state, metrics = step(state, data)
        float(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(n_steps):
            state, metrics = step(state, data)
        float(metrics["loss"])
        dt = time.perf_counter() - t0

    img_s = batch * n_steps / dt
    row["sec_per_step"] = round(dt / n_steps, 5)
    row["img_per_sec"] = round(img_s, 1)
    peak = device_peak_flops()   # a CPU row names no mfu
    if peak:
        row["mfu_analytic"] = round(
            img_s * RESNET50_TRAIN_FLOPS_PER_IMG / peak, 4)
        if "xla_flops_per_step" in row:
            row["mfu_xla"] = round(
                row["xla_flops_per_step"] * n_steps / dt / peak, 4)
    print(SENTINEL + json.dumps(row))


def main():
    from _dtf_watchdog import Budget, child_argv, run_budgeted_jobs

    budget = Budget(float(os.environ.get("DTF_PERF_BUDGET_S", "5400")))

    default_grid = []
    for batch in (128, 256, 512, 1024):
        default_grid.append(
            {"DTF_PERF_BATCH": str(batch), "DTF_PERF_MODE": "dispatch",
             "DTF_PERF_COST": "1" if batch == 128 else "0"})
    default_grid.append({"DTF_PERF_BATCH": "256", "DTF_PERF_MODE": "scan"})
    default_grid.append({"DTF_PERF_BATCH": "1024", "DTF_PERF_MODE": "scan"})
    grids = {
        "default": default_grid,
        # round-2 findings: throughput FALLS with batch → probe smaller
        # batches, bf16 host input, the fixed scan fence, and a profile.
        "followup": [
            {"DTF_PERF_BATCH": "64", "DTF_PERF_MODE": "dispatch"},
            {"DTF_PERF_BATCH": "96", "DTF_PERF_MODE": "dispatch"},
            {"DTF_PERF_BATCH": "128", "DTF_PERF_MODE": "dispatch",
             "DTF_PERF_BF16_IN": "1"},
            {"DTF_PERF_BATCH": "128", "DTF_PERF_MODE": "scan"},
            {"DTF_PERF_BATCH": "128", "DTF_PERF_MODE": "profile",
             "DTF_PERF_STEPS": "5"},
        ],
        # bf16 host input dropped: the roofline shows input bytes are
        # ~0.2% of step traffic — not a lever worth chasing.
        "followup2": [
            {"DTF_PERF_BATCH": "128", "DTF_PERF_MODE": "profile",
             "DTF_PERF_STEPS": "5"},
            # scan length 5 (not 20): the 20-step scan-of-train-step graph
            # took >8 min to compile when it was tried (round 3).
            {"DTF_PERF_BATCH": "128", "DTF_PERF_MODE": "scan",
             "DTF_PERF_STEPS": "5"},
        ],
    }
    grid = grids[sys.argv[1] if len(sys.argv) > 1 else "default"]

    tag = sys.argv[1] if len(sys.argv) > 1 else "default"
    artifact = (ARTIFACT if tag == "default"
                else ARTIFACT.replace(".json", f"_{tag}.json"))
    def on_result(row, job, rows, errors):
        # write incrementally so partial progress survives a later hang
        with open(artifact, "w") as f:
            json.dump({"rows": rows, "errors": errors}, f, indent=1)
        print(json.dumps(row if row is not None else errors[-1]))

    rows, errors = run_budgeted_jobs(
        grid, child_argv(os.path.abspath(__file__)),
        lambda line: (json.loads(line[len(SENTINEL):])
                      if line.startswith(SENTINEL) else None),
        budget=budget, cap_s=CHILD_TIMEOUT_S, env_base=dict(os.environ),
        on_result=on_result)
    return 0 if rows else 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        child()
    else:
        sys.exit(main())
