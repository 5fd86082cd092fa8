#!/usr/bin/env python
"""Long-context attention benchmark: ring vs dense, causal-skip on vs off.

Evidence that the long-context path does not waste FLOPs. CPU-sim mode
times, at several sequence lengths:

- dense causal attention (the O(T^2) single-device baseline),
- ring attention over an 8-way ``seq`` mesh WITHOUT causal block skipping,
- ring attention WITH skipping (the default) — incoming blocks entirely
  above the diagonal never run their matmuls.

On real hardware the 8 ring shards run concurrently; under the CPU
8-virtual-device sim they share host cores, so *total* compute is what the
wall clock sees — which is exactly the quantity block-skipping halves.
CPU-sim mode re-execs itself under a clean 8-device virtual-CPU env
(pattern shared with tests/conftest.py).

TPU mode (``bench_attention.py tpu``): flash vs dense on the
REAL chip — fwd and fwd+bwd at seq 1k..32k in bf16, interpret=False,
watchdogged like bench.py (the parent never imports jax). Timing is
scan-amortized (see ``tpu_child``): many iterations inside one jitted
``lax.scan`` with a measured null-jit round trip subtracted, so that one
dispatch's fixed cost does not swamp kernel time.
Dense rows are skipped past seq 8k where the f32 score matrix exceeds v5e
HBM — flash-only rows there ARE the long-context claim. A single chip can't
ring, but flash-vs-dense is the measurable long-context evidence today.

Artifact: ``ATTN_BENCH.json`` with a ``cpu_sim`` section (ring rows) and a
``tpu`` section (flash rows); each mode preserves the other's section.
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "ATTN_BENCH.json")
SENTINEL = "ATTN_TPU_RESULT "
TPU_CHILD_TIMEOUT_S = 900
# Hard total budget for a tpu run (all children): each child's timeout is
# sized to what remains of it.
TPU_TOTAL_BUDGET_S = float(os.environ.get("DTF_ATTN_BUDGET_S", "5400"))


def _read_artifact() -> dict:
    """Guarded read; migrates the legacy (r2) top-level-cpu-rows layout."""
    data = {}
    if os.path.exists(ARTIFACT):
        try:
            with open(ARTIFACT) as f:
                data = json.load(f)
        except (OSError, json.JSONDecodeError):
            data = {}
        if "rows" in data and "cpu_sim" not in data:
            data = {"cpu_sim": data}
    return data


def _merge_artifact(section: str, payload: dict):
    data = _read_artifact()
    data[section] = payload
    with open(ARTIFACT, "w") as f:
        json.dump(data, f, indent=1)


# --------------------------------------------------------------- CPU sim

def cpu_main():
    import functools

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.ops import attention as att

    def timed(fn, *args, reps=5):
        out = fn(*args)
        jax.block_until_ready(out)
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    mesh = make_mesh(MeshConfig(data=1, seq=8))
    b, h, d = 1, 8, 64
    results = {"device_count": jax.device_count(),
               "backend": jax.default_backend(), "rows": []}

    for t in (4096, 8192, 16384):
        q = jax.random.normal(jax.random.PRNGKey(0), (b, h, t, d),
                              jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(1), (b, h, t, d),
                              jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(2), (b, h, t, d),
                              jnp.float32)

        dense = jax.jit(functools.partial(att.dense_attention, causal=True))

        def ring(skip):
            spec = P(None, None, "seq", None)
            fn = functools.partial(att.ring_attention, causal=True,
                                   skip_masked_blocks=skip)
            sm = jax.shard_map(
                lambda q, k, v: fn(q, k, v),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
            return jax.jit(sm)

        def zigzag():
            spec = P(None, None, "seq", None)
            sm = jax.shard_map(
                lambda q, k, v: att.zigzag_ring_attention(q, k, v),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
            return jax.jit(sm)

        def halo(window):
            sm = jax.shard_map(
                lambda q, k, v: att.halo_attention(q, k, v, window=window),
                mesh=mesh, in_specs=(spec_, spec_, spec_), out_specs=spec_)
            return jax.jit(sm)

        spec_ = P(None, None, "seq", None)
        t_dense = timed(dense, q, k, v)
        t_ring_noskip = timed(ring(False), q, k, v)
        t_ring_skip = timed(ring(True), q, k, v)
        # zigzag: same total FLOPs as skip on the CPU sim (shared cores);
        # its extra win — no straggler shard — only shows on real parallel
        # chips, so treat this row as a correctness/overhead check.
        t_zigzag = timed(zigzag(), q, k, v)
        # halo = sliding window under the same seq sharding: total compute
        # O(T·window), so the CPU-sim wall clock (which sees total compute)
        # should fall well below every full-attention variant. Window is
        # capped at the shard length (the halo fetch is one neighbor tail).
        w = min(1024, t // 8)
        t_halo = timed(halo(w), q, k, v)
        row = {"seq": t, "dense_s": round(t_dense, 4),
               "ring_noskip_s": round(t_ring_noskip, 4),
               "ring_skip_s": round(t_ring_skip, 4),
               "zigzag_s": round(t_zigzag, 4),
               "halo_window": w,
               "halo_s": round(t_halo, 4),
               "skip_speedup": round(t_ring_noskip / t_ring_skip, 3),
               "halo_vs_ring_skip": round(t_ring_skip / t_halo, 3)}
        results["rows"].append(row)
        print(row)

    _merge_artifact("cpu_sim", results)


# --------------------------------------------------------------- real TPU

def tpu_child():
    """ONE sequence length per child (DTF_ATTN_SEQ); ~5 compiles each.

    Timing method: one dispatch has a fixed cost that can swamp kernel
    time at short sequences — rows timed one call at a time once came out
    FLAT from seq 1k to 4k (16x the FLOPs, same wall time). So each
    measurement folds ``reps`` iterations into ONE jitted ``lax.scan`` whose
    carry feeds the output back into the next iteration's query (scaled by
    1e-30 — numerically a no-op in bf16, but XLA cannot hoist the
    loop-invariant compute out of the scan). Per-iter time is
    (scan_time - null_jit_time) / reps, with the dispatch round trip
    measured by a trivial jitted readback and reps scaled so kernel FLOPs
    dominate.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax

    from dtf_tpu.ops import attention as att
    from dtf_tpu.ops import flash_attention as fa

    # batch/heads/head_dim default to the long-context bench shape;
    # bench_tune.py's children override them to sweep the TRAIN shapes
    # (e.g. GPT-2-small's b8 h12 d64 s1024) through this same
    # scan-amortized machinery.
    b = int(os.environ.get("DTF_ATTN_B", "2"))
    h = int(os.environ.get("DTF_ATTN_H", "8"))
    d = int(os.environ.get("DTF_ATTN_D", "128"))
    t = int(os.environ["DTF_ATTN_SEQ"])
    # block-shape override for the MXU-roof sweep: the
    # 512x512 default is a diagnosis-driven guess; the sweep measures it
    # against rectangular and larger shapes on the real chip.
    blk_q = int(os.environ.get("DTF_ATTN_BQ", "0"))
    blk_k = int(os.environ.get("DTF_ATTN_BK", "0"))
    blk_h = int(os.environ.get("DTF_ATTN_BH", "0"))  # head fold (fwd only)
    blk_qb = int(os.environ.get("DTF_ATTN_BQB", "0"))  # bwd-only blocks
    blk_kb = int(os.environ.get("DTF_ATTN_BKB", "0"))
    # CPU CI pin: interpret-mode run of this exact child (tiny seq) so a
    # wiring typo can't surface for the first time on the chip
    interp = os.environ.get("DTF_ATTN_INTERPRET") == "1"
    # Carry feedback scale: o*EPS is >30 orders below 1-ulp of any O(1)
    # carry entry, so the add rounds away and the values are unchanged in
    # practice — but XLA cannot prove that, so the scan body stays live.
    EPS = 1e-30

    def med_timed(fn, *args, n=3):
        float(fn(*args))  # compile + warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            float(fn(*args))
            ts.append(time.perf_counter() - t0)
        return statistics.median(ts)

    # round-trip baseline: same dispatch+readback path, ~zero compute
    null_s = med_timed(jax.jit(lambda x: x * 2.0), jnp.float32(1.0), n=5)

    def scan_timed(step, q0, reps):
        @jax.jit
        def loop(q):
            out, _ = lax.scan(lambda c, _: (step(c), None), q, None,
                              length=reps)
            return out.astype(jnp.float32).sum()
        total = med_timed(loop, q0)
        # floor at 1us/iter: null_s jitters a few ms, and a noisy run where
        # the scan median lands below it must not produce 0.0 (the speedup /
        # TFLOP divisions downstream would crash the child after all the
        # measurement time was already spent).
        return max(total - null_s, reps * 1e-6) / reps

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, t, d), jnp.bfloat16)
               for kk in ks)

    def fwd_step(impl):
        return lambda c: c + impl(c, k, v) * EPS

    def fwdbwd_step(impl):
        def loss(q, k, v):
            return impl(q, k, v).astype(jnp.float32).sum()

        def step(c):
            dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(c, k, v)
            return c + (dq + dk + dv) * EPS
        return step

    blk_kw = {}
    if blk_q:
        blk_kw["block_q"] = blk_q
    if blk_k:
        blk_kw["block_k"] = blk_k
    if blk_h:
        blk_kw["block_h"] = blk_h
    if blk_qb:
        blk_kw["block_q_bwd"] = blk_qb
    if blk_kb:
        blk_kw["block_k_bwd"] = blk_kb
    flash = lambda q, k, v: fa.flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=interp, **blk_kw)
    dense = lambda q, k, v: att.dense_attention(  # noqa: E731
        q, k, v, causal=True)

    # reps: enough kernel FLOPs that the subtracted overhead is noise
    fwd_flops = 4 * b * h * t * t * d  # causal halves it; keep conservative
    def reps_for(flops):
        if interp:
            return 2  # CI wiring check, not a measurement
        return max(8, min(512, int(4e12 / flops)))
    r_fwd, r_bwd = reps_for(fwd_flops), reps_for(3.5 * fwd_flops)

    # dense materializes f32 scores [b,h,t,t]; past ~6 GB it cannot fit v5e
    # HBM alongside operands — record that as the finding, don't crash.
    dense_ok = b * h * t * t * 4 < 6e9

    # report the blocks that actually run: unset args resolve through
    # the kernel-tune cache now (a row must not claim the module default
    # while the kernel ran a banked winner)
    from dtf_tpu.tune import resolver as tune_resolver

    plan = tune_resolver.flash_plan(
        seq=t, heads=h, head_dim=d, dtype="bfloat16", causal=True,
        window=0, n_devices=jax.device_count(),
        backend=jax.default_backend())
    # mirrors flash_attention's plan gate EXACTLY: the banked bwd pair
    # applies only on the fully-auto path; any explicit block (fwd or
    # bwd) keeps unset bwd fields on the inherit-the-fwd contract, and
    # a misreported pair here would be persisted and seeded as a
    # "measured" winner for blocks that never ran
    auto_bwd = not (blk_q or blk_k or blk_qb or blk_kb)
    eff_bqb = blk_qb or (plan.block_q_bwd if auto_bwd else 0)
    eff_bkb = blk_kb or (plan.block_k_bwd if auto_bwd else 0)
    row = {"seq": t, "backend": jax.default_backend(), "b": b, "h": h,
           "d": d, "dtype": "bfloat16", "null_jit_s": round(null_s, 5),
           "reps_fwd": r_fwd, "reps_fwdbwd": r_bwd,
           "block_q": min(blk_q or plan.block_q, t),
           "block_k": min(blk_k or plan.block_k, t),
           "block_h": blk_h or plan.block_h,
           "block_q_bwd": eff_bqb, "block_k_bwd": eff_bkb}
    row["flash_fwd_s"] = round(scan_timed(fwd_step(flash), q, r_fwd), 6)
    row["flash_fwdbwd_s"] = round(scan_timed(fwdbwd_step(flash), q, r_bwd), 6)
    if t >= 4096:
        # sliding-window locality on chip: O(T·window) via grid-level block
        # skip — the long-context claim the halo/window stack makes.
        wn = 1024
        flash_w = lambda q, k, v: fa.flash_attention(  # noqa: E731
            q, k, v, causal=True, window=wn, interpret=interp, **blk_kw)
        r_w = reps_for(4 * b * h * t * wn * d)
        row["window"] = wn
        row["flash_window_fwd_s"] = round(
            scan_timed(fwd_step(flash_w), q, r_w), 6)
        row["window_speedup"] = round(
            row["flash_fwd_s"] / row["flash_window_fwd_s"], 3)
    if dense_ok:
        row["dense_fwd_s"] = round(scan_timed(fwd_step(dense), q, r_fwd), 6)
        row["dense_fwdbwd_s"] = round(
            scan_timed(fwdbwd_step(dense), q, r_bwd), 6)
        row["fwd_speedup"] = round(row["dense_fwd_s"] / row["flash_fwd_s"], 3)
        row["fwdbwd_speedup"] = round(
            row["dense_fwdbwd_s"] / row["flash_fwdbwd_s"], 3)
    else:
        row["dense_skipped"] = "f32 scores [b,h,t,t] exceed v5e HBM"
    # achieved TFLOP/s on the causal-true FLOP count (half the full matrix)
    row["flash_fwd_tflops"] = round(
        0.5 * fwd_flops / row["flash_fwd_s"] / 1e12, 2)
    print(SENTINEL + json.dumps(row))


def tpu_main():
    from _dtf_watchdog import Budget, run_budgeted_jobs

    budget = Budget(TPU_TOTAL_BUDGET_S)

    argv = [sys.executable, os.path.abspath(__file__), "tpu", "--child"]
    parse = lambda line: (json.loads(line[len(SENTINEL):])  # noqa: E731
                          if line.startswith(SENTINEL) else None)

    if "--sweep-blocks-bwd" in sys.argv:
        # the still-unmeasured BACKWARD block rows alone (ISSUE 2): the
        # full --sweep-blocks queue runs last in the pipeline and both
        # round-5 windows died before reaching its bwd tail, so this
        # standalone pass banks the five bwd rows early. Fwd stays pinned
        # at its sweep winner (512x1024); (512,1024) repeats as the
        # same-window control row.
        jobs = [{"DTF_ATTN_SEQ": "8192",
                 "DTF_ATTN_BQB": str(bqb), "DTF_ATTN_BKB": str(bkb)}
                for bqb, bkb in ((512, 512), (1024, 512), (512, 1024),
                                 (1024, 1024), (256, 1024))]

        def on_result(row, job, rows, errs):
            tpu = _read_artifact().get("tpu", {})
            tpu["bwd_block_sweep"] = {"rows": rows, "errors": errs}
            _merge_artifact("tpu", tpu)
            print(json.dumps(row if row is not None else errs[-1]))

        rows, errs = run_budgeted_jobs(
            jobs, argv, parse, budget=budget, cap_s=TPU_CHILD_TIMEOUT_S,
            env_base=dict(os.environ), on_result=on_result)
        return 0 if rows else 1

    if "--sweep-blocks" in sys.argv:
        # MXU-roof block-shape search at the headline seq:
        # square vs rectangular vs larger blocks, one child each.
        jobs = [{"DTF_ATTN_SEQ": "8192", "DTF_ATTN_BQ": str(bq),
                 "DTF_ATTN_BK": str(bk), "DTF_ATTN_BH": str(bh)}
                for bq, bk, bh in (
                    (256, 256, 1), (512, 512, 1), (512, 1024, 1),
                    (1024, 512, 1), (1024, 1024, 1), (512, 2048, 1),
                    # head folding (fwd): amortize per-grid-step overhead
                    (512, 512, 2), (512, 512, 4), (1024, 1024, 2))]
        # bwd-only block rows (round 5): fwd pinned at its sweep winner
        # (512x1024 — now the default), vary ONLY the backward blocks.
        # The bwd ran ~92 TF/s vs fwd's ~170 in the round-5 window; its
        # grids stream the opposite extents, so the optimum may differ.
        # (512, 1024) duplicates the inherited fwd default on purpose: a
        # same-run control row, so bwd deltas are read against a
        # baseline measured in THIS run, not one from another session.
        jobs += [{"DTF_ATTN_SEQ": "8192",
                  "DTF_ATTN_BQB": str(bqb), "DTF_ATTN_BKB": str(bkb)}
                 for bqb, bkb in ((512, 512), (1024, 512), (512, 1024),
                                  (1024, 1024), (256, 1024))]

        def on_result(row, job, rows, errs):
            tpu = _read_artifact().get("tpu", {})
            tpu["block_sweep"] = {"rows": rows, "errors": errs}
            _merge_artifact("tpu", tpu)
            print(json.dumps(row if row is not None else errs[-1]))

        rows, errs = run_budgeted_jobs(
            jobs, argv, parse, budget=budget, cap_s=TPU_CHILD_TIMEOUT_S,
            env_base=dict(os.environ), on_result=on_result)
        return 0 if rows else 1

    jobs = [{"DTF_ATTN_SEQ": str(t)}
            for t in (1024, 2048, 4096, 8192, 16384, 32768)]

    def on_result(row, job, rows, errs):
        # incremental write: partial progress survives a later hang; the
        # update preserves sibling keys (block_sweep) in the tpu section
        tpu = _read_artifact().get("tpu", {})
        tpu.update(backend="tpu", rows=rows, errors=errs)
        _merge_artifact("tpu", tpu)
        print(json.dumps(row if row is not None else errs[-1]))

    rows, errs = run_budgeted_jobs(
        jobs, argv, parse, budget=budget, cap_s=TPU_CHILD_TIMEOUT_S,
        env_base=dict(os.environ), on_result=on_result)
    return 0 if rows else 1


if __name__ == "__main__":
    if "tpu" in sys.argv:
        if "--child" in sys.argv:
            tpu_child()
        else:
            sys.exit(tpu_main())
    else:
        from _dtf_env import cpu_sim_env, is_cpu_sim

        if (not is_cpu_sim(os.environ, 8)
                and os.environ.get("_DTF_ATTN_BENCH_REEXEC") != "1"):
            env = cpu_sim_env(8, os.environ)
            env["_DTF_ATTN_BENCH_REEXEC"] = "1"
            os.execve(sys.executable, [sys.executable] + sys.argv, env)
        cpu_main()
