#!/usr/bin/env python
"""Serving-path benchmark: batched greedy decode tokens/sec.

The decode stack (``dtf_tpu/models/gpt.py: generate``) ships two memory
levers whose perf claims previously had no numbers:

- **GQA** (``kv_heads < heads``): the cache shrinks by heads/kv_heads and
  each decode step reads group x fewer cache bytes — decode is HBM-bound,
  so this should show up directly in tokens/sec.
- **rolling window cache** (``attn_window``): O(window) slots instead of
  O(decode_len) — smaller cache reads per step past the window.

Grid: GPT-2 small, batch 8, prompt 128, +512 new tokens — MHA vs GQA
(kv_heads=4) x full vs rolling (window=256) cache. One config per
watchdogged child, one after the other (a chip belongs to one process at
a time). Rows merge into ``BENCH_LM.json`` under ``"decode"`` without
touching the training rows.

Timing: the whole generate() scan is ONE dispatch (~639 sequential
steps), so one dispatch's fixed cost is noise — no scan-folding needed
(contrast scripts/bench_attention.py tpu_child).

``--sweep-serve``: the continuous-batching A/B (``child_serve``) — the
dtf_tpu/serve engine vs a classic fixed-batch server under the same seeded
Poisson arrivals; goodput tokens/sec + TTFT p50/p99 both sides, merged
into ``BENCH_LM.json`` under ``"serve"``. The sweep spans replica count
(engines behind the Router, slots split so capacity is constant) and
prefix-hit ratio (shared prompt stems; hit rows carry an extra
``serve_off`` side — same arrivals, page cache off — so the prefill-work
and TTFT p50 deltas are in-row). The ``DTF_SERVE_LOG_SINK=1`` row (ISSUE
19) attaches the request log sink to the fleet vs the same fleet without
it: host-side appends with zero device readbacks, fenced as a ~zero
goodput/TTFT delta.
"""

import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
ARTIFACT = os.path.join(ROOT, "BENCH_LM.json")
SENTINEL = "BENCH_DECODE_ROW "
CHILD_TIMEOUT_S = 900
TOTAL_BUDGET_S = float(os.environ.get("DTF_DECODE_BUDGET_S", "4500"))


def child():
    import dataclasses

    import jax
    import numpy as np

    from dtf_tpu.models import gpt

    tiny = os.environ.get("DTF_DECODE_TINY") == "1"
    kv_heads = int(os.environ.get("DTF_DEC_KV", "0")) or None
    window = int(os.environ.get("DTF_DEC_WINDOW", "0"))
    prefill_chunk = int(os.environ.get("DTF_DEC_PREFILL_CHUNK", "0"))
    kv_dtype = "int8" if os.environ.get("DTF_DEC_INT8") == "1" else ""
    if tiny:
        b, t_p, n_new = 2, 8, 8
        base = gpt.GPTConfig.tiny(dtype=jax.numpy.bfloat16)
    else:
        b, t_p, n_new = 8, 128, 512
        base = gpt.GPTConfig.gpt2_small()
    total = t_p + n_new
    cfg = dataclasses.replace(base, decode_len=total, kv_heads=kv_heads,
                              attn_window=window, kv_cache_dtype=kv_dtype)
    model = gpt.GPT(cfg, None)
    variables = model.init(jax.random.PRNGKey(0),
                           jax.numpy.zeros((b, 1), jax.numpy.int32))
    params = variables["params"]
    rng = np.random.default_rng(0)
    prompt = jax.numpy.asarray(
        rng.integers(0, cfg.vocab_size, (b, t_p)).astype(np.int32))

    from _dtf_watchdog import fence  # host-readback fence

    def med_timed(fn, n=3):
        out = fn()
        fence(out)                                       # compile + warm
        ts = []
        for _ in range(n):
            t0 = time.perf_counter()
            fence(fn())
            ts.append(time.perf_counter() - t0)
        return out, statistics.median(ts)

    # prefill is ONE parallel forward (gpt.generate's prefill path); its
    # cost is measured with an n_new=1 run and subtracted so
    # decode_tokens_per_sec reflects pure single-token scan throughput.
    run1 = jax.jit(lambda p, ids: gpt.generate(
        model, p, ids, 1, prefill_chunk=prefill_chunk))
    run = jax.jit(lambda p, ids: gpt.generate(
        model, p, ids, n_new, prefill_chunk=prefill_chunk))
    _, t_prefill = med_timed(lambda: run1(params, prompt))
    out, t_total = med_timed(lambda: run(params, prompt))
    assert out.shape == (b, total)
    decode_s = t_total - t_prefill

    kvh = cfg.kv_heads_resolved
    cache_len = min(total, window) if window else total
    d_head = cfg.d_model // cfg.heads
    kv_bytes = 1 + 4.0 / d_head if kv_dtype == "int8" else 2  # + scale
    cache_bytes = 2 * b * kvh * cache_len * d_head * kv_bytes * cfg.layers
    row = {
        "model": ("gpt_tiny" if tiny else "gpt2_small") + "_decode",
        "backend": jax.default_backend(),
        "batch": b, "prompt": t_p, "n_new": n_new,
        "kv_heads": kvh, "heads": cfg.heads, "window": window,
        "prefill_chunk": prefill_chunk, "kv_cache_dtype": kv_dtype,
        "cache_mib": round(cache_bytes / 2**20, 2),
        "sec_total": round(t_total, 4),
        "prefill_s": round(t_prefill, 4),
        "prefill_tokens_per_sec": round(b * t_p / max(t_prefill, 1e-9), 1),
    }
    if decode_s <= 0.05 * t_total or n_new < 2:
        # the prefill-subtraction delta is inside timing noise — an honest
        # null beats a nonsense 1e10 tokens/sec landing in the artifact
        row["decode_tokens_per_sec"] = None
        row["decode_noise_limited"] = True
    else:
        row["decode_tokens_per_sec"] = round(b * (n_new - 1) / decode_s, 1)
        row["ms_per_step"] = round(decode_s / (n_new - 1) * 1e3, 3)
    print(SENTINEL + json.dumps(row))


def child_serve():
    """Continuous-vs-static A/B under the SAME seeded Poisson arrivals:
    the serve side runs the DecodeEngine + Scheduler (per-slot eviction
    frees capacity the moment a request finishes), the static side is the
    classic fixed-batch server (collect n_slots requests, decode the
    worst-case new_max for the whole batch, deliver at batch end — the
    long-request-holds-the-batch cost this engine exists to remove).
    Prompt length is fixed per row (static batching cannot mix lengths);
    the generation lengths vary, which is the headline effect. One JSON
    row with both sides.

    Sweep axes (ISSUE 6): ``DTF_SERVE_REPLICAS`` routes the serve side
    through an N-replica Router (slots SPLIT across replicas so total
    capacity is constant — the row measures routing, not extra HBM);
    ``DTF_SERVE_PREFIX`` stamps that fraction of requests with a shared
    prompt stem and serves with the prefix page cache ON — the row then
    also carries a ``serve_off`` side (same arrivals, cache off) so the
    prefill-work and TTFT deltas are in-row."""
    import dataclasses

    import jax
    import numpy as np

    from _dtf_watchdog import fence
    from dtf_tpu.fault.inject import ServeFaultPlan
    from dtf_tpu.models import gpt
    from dtf_tpu.serve import (DecodeEngine, HealthConfig, PoissonLoadGen,
                               Router, Scheduler, install_serve_fault,
                               replay)
    from dtf_tpu.serve.engine import _cfg_label
    from dtf_tpu.serve.scheduler import _quantile

    tiny = os.environ.get("DTF_DECODE_TINY") == "1"
    if tiny:
        # DTF_SERVE_F32 (optional diagnostic knob, not set by the sweep):
        # run the tiny model at f32 when an UNTRAINED bf16 model's
        # near-tie logits flip argmax between the draft's single-token
        # steps and the verifier's batched pass (matmul-shape rounding)
        # and deflate acceptance — a failure mode a trained checkpoint
        # does not have. The shipped spec rows measure ~0.99 acceptance
        # at bf16 (self-draft), so they run bf16 like everything else.
        dt = (jax.numpy.float32 if os.environ.get("DTF_SERVE_F32") == "1"
              else jax.numpy.bfloat16)
        base = gpt.GPTConfig.tiny(dtype=dt)
        n_slots, t_p, new_min, new_max = 4, 48, 4, 16
        rate, n_req, chunk, page = 200.0, 12, 8, 8
    else:
        base = gpt.GPTConfig.gpt2_small()
        n_slots, t_p, new_min, new_max = 8, 128, 64, 512
        rate, n_req, chunk, page = 2.0, 24, 64, 32
    rate = float(os.environ.get("DTF_SERVE_RATE", rate))
    n_req = int(os.environ.get("DTF_SERVE_N", n_req))
    replicas = int(os.environ.get("DTF_SERVE_REPLICAS", "1"))
    hit_ratio = float(os.environ.get("DTF_SERVE_PREFIX", "0"))
    page = int(os.environ.get("DTF_SERVE_PAGE", page))
    t_p = int(os.environ.get("DTF_SERVE_TP", t_p))
    new_min = int(os.environ.get("DTF_SERVE_NEW_MIN", new_min))
    new_max = int(os.environ.get("DTF_SERVE_NEW_MAX", new_max))
    budget = int(os.environ.get("DTF_SERVE_BUDGET", "4"))
    # ISSUE 13 axes: draft width (0 = speculation off) and disaggregation
    # ratio (dedicated prefill replicas out of `replicas`).
    spec_k = int(os.environ.get("DTF_SERVE_SPEC_K", "0"))
    draft_mode = os.environ.get("DTF_SERVE_DRAFT", "self")
    prefill_reps = int(os.environ.get("DTF_SERVE_PREFILL_REPLICAS", "0"))
    # ISSUE 14 axis: start a ROLLING weight swap at this router tick
    # (0 = off; needs replicas >= 2). The row's A/B partner is the same
    # fleet + arrivals with no swap — TTFT p99 across the swap vs
    # without IS the zero-downtime claim, measured.
    swap_at = int(os.environ.get("DTF_SERVE_SWAP", "0"))
    if swap_at and replicas < 2:
        raise SystemExit("DTF_SERVE_SWAP needs DTF_SERVE_REPLICAS >= 2 "
                         "(a rolling swap drains one replica while the "
                         "others serve)")
    # ISSUE 19 axis: attach the request log sink to the serve side — the
    # A/B partner is the same fleet with the sink off. The sink is
    # host-side file IO with zero device readbacks, so the claim under
    # measurement is a ~zero goodput/TTFT delta, not a win.
    log_sink_on = os.environ.get("DTF_SERVE_LOG_SINK") == "1"
    # long-prompt BURST (the disaggregation row's workload): a contiguous
    # run of requests mid-stream carries a LONG unique prompt; the row
    # then reports short-request TTFT separately — the starvation metric
    # phase routing exists to fix. (No static side on mixed-length rows —
    # fixed-batch serving cannot mix prompt lengths at all.)
    long_frac = float(os.environ.get("DTF_SERVE_LONG", "0"))
    t_p_long = int(os.environ.get("DTF_SERVE_TP_LONG", str(4 * t_p)))
    max_len = (max(t_p, t_p_long) if long_frac > 0 else t_p) + new_max
    max_len = -(-max_len // page) * page    # pages tile the cache
    cfg = dataclasses.replace(base, decode_len=max_len)
    model = gpt.GPT(cfg, None)
    params = model.init(jax.random.PRNGKey(0),
                        jax.numpy.zeros((1, 1), jax.numpy.int32))["params"]
    draft_cfg = draft_params = None
    if spec_k:
        if draft_mode == "half":
            # early-exit draft: half the layers of the measured model —
            # realistic proposal cost, random-init acceptance on the sim
            draft_cfg, draft_params = gpt.draft_truncate(
                base, params, max(1, base.layers // 2))
        else:
            # self-draft: draft == target, the 100%-greedy-acceptance
            # upper bound — measures the speculation MACHINERY (one
            # k-step dispatch + one k+1-wide verify vs k+1 dispatches),
            # not a distilled draft's quality
            draft_cfg, draft_params = base, params
    if prefill_reps and hit_ratio <= 0:
        raise SystemExit("DTF_SERVE_PREFILL_REPLICAS needs "
                         "DTF_SERVE_PREFIX > 0 (the page transport)")
    gen = PoissonLoadGen(rate=rate, n_requests=n_req,
                         vocab_size=base.vocab_size, prompt_min=t_p,
                         prompt_max=t_p, new_min=new_min, new_max=new_max,
                         seed=0)
    arrivals = list(gen.arrivals())
    if hit_ratio > 0:
        # a seeded fraction of requests shares one prompt stem (system-
        # prompt traffic shape): ~3/4 of the prompt by default,
        # page-aligned; DTF_SERVE_STEM_FRAC deepens it (the spec rows
        # model long-system-prompt traffic where nearly all prefill is
        # the shared stem)
        stem_frac = float(os.environ.get("DTF_SERVE_STEM_FRAC", "0.75"))
        stem_len = int(t_p * stem_frac) // page * page
        stem = np.random.default_rng(7).integers(
            0, base.vocab_size, stem_len).tolist()
        pick = np.random.default_rng(8).random(n_req) < hit_ratio
        arrivals = [
            (t, dataclasses.replace(
                req, prompt=stem + list(req.prompt[stem_len:]))
             if pick[i] else req)
            for i, (t, req) in enumerate(arrivals)]
    long_ids: set = set()
    if long_frac > 0:
        # the BURST: a contiguous run of UNIQUE long prompts starting a
        # quarter into the stream — prefill-heavy work that, without
        # disaggregation, competes with every short request's decode
        n_long = max(1, int(round(long_frac * n_req)))
        start_i = n_req // 4
        lrng = np.random.default_rng(9)
        t_burst = arrivals[start_i][0]
        for i in range(start_i, min(start_i + n_long, n_req)):
            # summarization-shaped (long unique input, SHORT output — the
            # canonical disaggregation workload) and SIMULTANEOUS: the
            # whole burst lands at one instant, the head-of-line pile-up
            # that starves a shared fleet's admission queues
            arrivals[i] = (t_burst, dataclasses.replace(
                arrivals[i][1], max_new=max(new_min, 8),
                prompt=lrng.integers(0, base.vocab_size,
                                     t_p_long).tolist()))
            long_ids.add(i)

    # slots split across replicas: capacity-constant routing A/B
    if n_slots % replicas:
        raise SystemExit(f"n_slots={n_slots} not divisible by "
                         f"replicas={replicas}")

    # the degraded-fleet A/B (ISSUE 12): with a serve fault plan in the
    # env, the row grows a "serve_degraded" side — same seeded arrivals,
    # health watchdog on, one replica wedged at a seeded tick — so
    # goodput / TTFT p99 / shed fraction under quarantine+requeue sit
    # next to the fault-free side. Both sides get the same bounded queue
    # so shed pressure is comparable.
    fault_plan = ServeFaultPlan.from_env()
    fault_queue = n_slots if fault_plan is not None else 0

    params_v2 = None
    if swap_at:
        # the "retrained" weights a mid-run publish would deliver: a
        # fresh init — the swap machinery's cost does not depend on how
        # far the weights moved, only the placement + drain do
        params_v2 = model.init(
            jax.random.PRNGKey(1),
            jax.numpy.zeros((1, 1), jax.numpy.int32))["params"]

    def serve_side(prefix_on, inject=False, disagg=0, spec_on=True,
                   swap=False, sink_on=False):
        use_spec = spec_k if spec_on else 0
        sink = None
        if sink_on:
            import shutil
            import tempfile

            from dtf_tpu.serve.logsink import LogSink
            sink = LogSink(tempfile.mkdtemp(prefix="dtf_bench_sink_"))
        pool = (max_len // page) * 2 if prefix_on else 0
        # on a disaggregation ROW, both sides get eager saves AND the
        # shared store — the off side must differ ONLY in phase routing,
        # not in save admission or pool visibility, or the ttft_short
        # delta partly measures the wrong mechanism
        share = prefill_reps > 0 and prefix_on
        engines, store = [], None
        for r in range(replicas):
            pre = r < disagg
            engines.append(DecodeEngine(
                base, params, n_slots=n_slots // replicas,
                max_len=max_len, prefill_chunk=chunk,
                kv_page_size=page if prefix_on else 0,
                prefix_pages=pool,
                page_save_after=1 if share else 2, shared_pages=store,
                draft_cfg=None if (pre or not use_spec) else draft_cfg,
                draft_params=None if (pre or not use_spec)
                else draft_params,
                spec_k=0 if pre else use_spec))
            if share and store is None:
                store = engines[0].page_store
        for e in engines:
            # warm every program outside the timed window (the static
            # side's fence(run(...)) move): first-call backend overhead
            # must not bias the side that happens to run first. The page
            # programs warm with no-op args (n_valid=0 / empty window);
            # the warm prefill leaves slot 0 stale-active, which the
            # first real admission resets by design.
            e.prefill(0, [0] * t_p, seed=0)
            e.decode()
            e.warm_page_programs()
            for k in e.counters:
                e.counters[k] = 0
        health = (HealthConfig(slow_factor=8.0, min_slow_s=0.2,
                               wedge_s=0.5, quarantine_after=2,
                               probation_delay_s=3600.0)
                  if fault_plan is not None and replicas > 1 else False)
        if replicas > 1:
            sched = Router(engines, None, prefill_chunks_per_tick=budget,
                           health=health, max_queue=fault_queue,
                           prefill_replicas=disagg, log_sink=sink)
        else:
            sched = Scheduler(engines[0], None, prefill_chunks_per_tick=budget,
                              max_queue=fault_queue, log_sink=sink)
        if inject:
            # wedge sleeps are real wall time (the watchdog quarantines
            # on measured tick duration); installed AFTER warm-up so the
            # warm decode calls don't consume the seeded tick budget
            install_serve_fault(fault_plan, sched)
        on_tick = None
        if swap:
            from dtf_tpu.serve import SwapConfig

            ticks = [0]

            def on_tick():
                ticks[0] += 1
                if ticks[0] == swap_at and not sched.swap_in_progress:
                    sched.start_swap(params_v2,
                                     config=SwapConfig(canary_ticks=4))
        wall = replay(sched, arrivals, on_tick=on_tick)
        if swap and sched.swap_in_progress:
            sched.finish_swap()
        polls = [sched.poll(r) for r in range(n_req)]
        statuses = {}
        for p in polls:
            statuses[p["status"]] = statuses.get(p["status"], 0) + 1
        # goodput counts DELIVERED work only: tokens of done requests
        goodput = sum(len(p["tokens"]) for p in polls
                      if p["status"] == "done")
        st = sched.stats()
        if replicas > 1:
            ttft50, ttft99 = st["router_ttft_p50_s"], st["router_ttft_p99_s"]
            occ = sum(st[f"replica{i}_serve_occupancy_mean"]
                      for i in range(replicas)) / replicas
        else:
            ttft50, ttft99 = st["serve_ttft_p50_s"], st["serve_ttft_p99_s"]
            occ = st["serve_occupancy_mean"]
        counters = {}
        for e in engines:
            for k, v in e.counters.items():
                counters[k] = counters.get(k, 0) + v
        out = {"tokens_per_sec": round(goodput / max(wall, 1e-9), 1),
               "makespan_s": round(wall, 3),
               "ttft_p50_s": round(ttft50, 5),
               "ttft_p99_s": round(ttft99, 5),
               "occupancy_mean": round(occ, 3),
               "prefill_chunks": counters["prefill_chunks"],
               "pages_loaded": counters["pages_loaded"],
               "pages_saved": counters["pages_saved"],
               "prefix_hit_tokens": counters["prefix_hit_tokens"]}
        if use_spec:
            prop = counters.get("spec_proposed", 0)
            out["decode_steps"] = counters["decode_steps"]
            out["accept_rate"] = (round(counters["spec_accepted"] / prop, 4)
                                  if prop else 0.0)
            out["draft_fallbacks"] = counters.get("draft_fallbacks", 0)
        if disagg:
            out["handoffs"] = st.get("router_handoffs", 0.0)
        if swap:
            # the zero-downtime fence data: a swap mid-run must leave
            # every request done (statuses clean) and its TTFT p99 is
            # read against the no-swap side of the same row
            out["statuses"] = statuses
            out["swaps"] = st.get("router_swaps", 0.0)
            out["swap_rollbacks"] = st.get("router_swap_rollbacks", 0.0)
            out["final_version"] = st.get("router_version", 0.0)
            out["requeued"] = st.get("router_requeued", 0.0)
        if long_ids:
            # per-class TTFT: the SHORT requests' tail is the starvation
            # metric — the burst must not inflate it fleet-wide. Reported
            # in WALL seconds and in per-replica TICKS: on this
            # single-process sim every replica shares one thread, so wall
            # TTFT charges a replica for the whole fleet's work — tick
            # counts are what a real parallel fleet's clock would see,
            # and they are what the disaggregation claim rides on.
            def req_rec(rid):
                if hasattr(sched, "_where"):          # Router
                    if rid in getattr(sched, "_router_shed", {}):
                        return None
                    loc = sched._where.get(rid)
                    return (None if loc is None else
                            sched.schedulers[loc[0]]._recs.get(loc[1]))
                return sched._recs.get(rid)

            def req_ttft(rid):
                rec = req_rec(rid)
                if rec is None or rec.first_token_t is None:
                    return None
                return rec.first_token_t - rec.submit_t

            def req_ttft_ticks(rid):
                rec = req_rec(rid)
                if rec is None or rec.first_token_tick is None:
                    return None
                return rec.first_token_tick - rec.submit_tick

            shorts = [t for r in range(n_req) if r not in long_ids
                      if (t := req_ttft(r)) is not None]
            longs = [t for r in sorted(long_ids)
                     if (t := req_ttft(r)) is not None]
            short_ticks = [t for r in range(n_req) if r not in long_ids
                           if (t := req_ttft_ticks(r)) is not None]
            if shorts:
                out["ttft_short_p50_s"] = round(_quantile(shorts, 0.5), 5)
                out["ttft_short_p99_s"] = round(_quantile(shorts, 0.99), 5)
            if short_ticks:
                out["ttft_short_p50_ticks"] = _quantile(short_ticks, 0.5)
                out["ttft_short_p99_ticks"] = _quantile(short_ticks, 0.99)
            if longs:
                out["ttft_long_p99_s"] = round(_quantile(longs, 0.99), 5)
        if fault_plan is not None:
            shed = st.get("router_shed", st.get("serve_shed", 0.0))
            out["statuses"] = statuses
            out["shed_frac"] = round(shed / n_req, 4)
            out["timeouts"] = st.get("router_timeouts",
                                     st.get("serve_timeouts", 0.0))
            out["quarantines"] = st.get("router_quarantines", 0.0)
            out["requeued"] = st.get("router_requeued", 0.0)
        if sink is not None:
            sink.close()
            sk = sink.stats()
            out["log_sink_records"] = sk["records"]
            out["log_sink_shards"] = sk["shards_committed"]
            shutil.rmtree(sink.dir, ignore_errors=True)
        return out

    # ---- serve side: open-loop Poisson against the engine/router fleet.
    # The in-row A/B partner depends on the swept axis: a disaggregation
    # row compares against the SAME pages with routing off, a prefix row
    # against pages off, a spec row against speculation off — always the
    # same seeded arrivals.
    serve = serve_side(prefix_on=hit_ratio > 0, disagg=prefill_reps,
                       swap=swap_at > 0, sink_on=log_sink_on)
    if swap_at:
        # the swap A/B: the SAME fleet shape (disagg axis included), same
        # arrivals, no swap — the TTFT p99 delta between the sides is
        # what the mid-run swap cost
        serve_off = serve_side(prefix_on=hit_ratio > 0,
                               disagg=prefill_reps)
    elif prefill_reps:
        serve_off = serve_side(prefix_on=True, disagg=0)
    elif spec_k:
        serve_off = serve_side(prefix_on=hit_ratio > 0, spec_on=False)
    elif log_sink_on:
        # the log-sink A/B (ISSUE 19): same fleet, sink off — the sink is
        # host-side appends with zero device readbacks, so the fence here
        # is "recording traffic costs ~nothing", read as the goodput/TTFT
        # delta between the sides
        serve_off = serve_side(prefix_on=hit_ratio > 0)
    elif hit_ratio > 0:
        serve_off = serve_side(prefix_on=False)
    else:
        serve_off = None
    serve_degraded = (serve_side(prefix_on=hit_ratio > 0, inject=True)
                      if fault_plan is not None else None)

    # ---- static side: same arrivals, fixed batches, worst-case decode.
    # TTFT for a static server is delivery time: batch end - arrival (a
    # request's tokens only return when its whole batch completes).
    # Mixed-length burst rows have no static side at all — a fixed-batch
    # server cannot mix prompt lengths, which is half the point.
    if long_ids:
        static = {"skipped": "mixed prompt lengths"}
    else:
        run = jax.jit(lambda p, ids: gpt.generate(model, p, ids, new_max))
        warm_ids = jax.numpy.zeros((n_slots, t_p), jax.numpy.int32)
        fence(run(params, warm_ids))                  # compile outside t0
        t0 = time.perf_counter()
        done_t, end = [], 0.0
        for b0 in range(0, n_req, n_slots):
            batch = arrivals[b0:b0 + n_slots]
            now = time.perf_counter() - t0
            start = max(end, batch[-1][0])            # wait for the batch
            if start > now:
                time.sleep(start - now)
            ids = np.zeros((n_slots, t_p), np.int32)
            for j, (_, req) in enumerate(batch):
                ids[j] = req.prompt
            fence(run(params, jax.numpy.asarray(ids)))
            end = time.perf_counter() - t0
            done_t += [end - arr for arr, _ in batch]
        static_wall = end
        want = sum(req.max_new for _, req in arrivals)   # goodput: wanted
        # same rank definition as the serve side's scheduler stats — a
        # hand-rolled quantile would bias the A/B by one rank at small N
        static = {"tokens_per_sec": round(want / max(static_wall, 1e-9), 1),
                  "makespan_s": round(static_wall, 3),
                  "ttft_p50_s": round(_quantile(done_t, 0.5), 5),
                  "ttft_p99_s": round(_quantile(done_t, 0.99), 5)}

    row = {"model": ("gpt_tiny" if tiny else "gpt2_small") + "_serve_ab",
           "backend": jax.default_backend(), "n_slots": n_slots,
           "replicas": replicas, "prefix_hit_ratio": hit_ratio,
           "page_size": page if hit_ratio > 0 else 0,
           "spec_k": spec_k, "draft": draft_mode if spec_k else "",
           "prefill_replicas": prefill_reps, "swap_at_tick": swap_at,
           "long_frac": long_frac, "t_p_long": t_p_long if long_frac else 0,
           # architecture labels keying the tuner's spec_k winner
           # selection (tune/search.py seed_spec_k_entries)
           "model_arch": _cfg_label(base),
           "draft_arch": _cfg_label(draft_cfg) if spec_k else "",
           "prompt": t_p, "new_min": new_min, "new_max": new_max,
           "rate_rps": rate, "n_requests": n_req, "prefill_chunk": chunk,
           "serve": serve, "static": static}
    if serve_off is not None:
        # the in-row prefix A/B: same arrivals, page cache off — TTFT p50
        # must improve and prefill_chunks strictly drop on the ON side
        row["serve_off"] = serve_off
    if serve_degraded is not None:
        # the degraded-fleet A/B: one replica wedged at a seeded tick,
        # quarantine + requeue on; goodput / TTFT p99 / shed fraction
        # sit next to the fault-free "serve" side above
        row["fault"] = os.environ.get("DTF_FAULT_INJECT", "")
        row["serve_degraded"] = serve_degraded
    print(SENTINEL + json.dumps(row))


def _read() -> dict:
    try:
        with open(ARTIFACT) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return {}


def _merge(rows, errors, key="decode"):
    data = _read()
    data[key] = {"rows": rows, "errors": errors}
    with open(ARTIFACT, "w") as f:
        json.dump(data, f, indent=1)


def main(key="decode"):
    from _dtf_watchdog import Budget, child_argv, run_budgeted_jobs

    budget = Budget(TOTAL_BUDGET_S)
    if key == "serve":
        # each child runs the continuous-vs-static A/B and emits one row
        # holding both sides (same seeded arrivals); the sweep spans the
        # ISSUE 6 axes — replica count (capacity-constant routing) and
        # prefix-hit ratio (rows with hits also carry a serve_off side)
        def on_result(row, job, rows, errors):
            _merge(rows, errors, key="serve")
            print(json.dumps(row if row is not None else errors[-1]))

        tiny = os.environ.get("DTF_DECODE_TINY") == "1"
        serve_jobs = [
            {},                                       # 1 replica, no stems
            {"DTF_SERVE_PREFIX": "0.75"},             # prefix cache A/B
            {"DTF_SERVE_REPLICAS": "2"},              # routing A/B
            {"DTF_SERVE_REPLICAS": "2", "DTF_SERVE_PREFIX": "0.75"},
            # degraded-fleet A/B (ISSUE 12): one replica wedged at a
            # seeded decode tick — quarantine + requeue vs fault-free,
            # goodput/TTFT p99/shed fraction both sides in one row
            {"DTF_SERVE_REPLICAS": "2",
             "DTF_FAULT_INJECT": "wedge_replica@6:replica=1"},
            # hot-swap A/B (ISSUE 14): a rolling weight swap starts at a
            # seeded router tick mid-replay — TTFT p99 across the swap
            # vs the no-swap side on the same seeded arrivals (the
            # zero-downtime fence), all requests terminal `done`
            {"DTF_SERVE_REPLICAS": "2", "DTF_SERVE_SWAP": "6"},
            # log-sink A/B (ISSUE 19): the same fleet records every done
            # request into a serve-log sink vs not — host-side jsonl
            # appends, zero device readbacks, so the fenced claim is a
            # ~zero goodput/TTFT delta (the flywheel's capture is free)
            {"DTF_SERVE_REPLICAS": "2", "DTF_SERVE_LOG_SINK": "1"},
            # ISSUE 13: draft-k sweep — each row carries a spec-off side
            # on the same arrivals; self-draft is the acceptance upper
            # bound (measures the machinery), and the tuner's spec_k
            # winner selection reads the best-goodput row of this sweep.
            # The tiny/CPU-sim rows run the DEEP-CACHE shape (long shared
            # stems via prefix pages — self-spec page loads shortcut the
            # draft prefill too — so every verified token sits deep in
            # the cache): the regime where a verify pass amortizes the
            # per-step cache read across k+1 queries — the only axis on
            # which the compute-bound sim reproduces the chip's
            # memory-bound win (measured crossover ~L=512 on the sim).
            *({"DTF_SERVE_SPEC_K": k,
               **({"DTF_SERVE_TP": "448", "DTF_SERVE_PREFIX": "1.0",
                   "DTF_SERVE_STEM_FRAC": "0.95", "DTF_SERVE_N": "32",
                   "DTF_SERVE_RATE": "400", "DTF_SERVE_NEW_MIN": "256",
                   "DTF_SERVE_NEW_MAX": "256", "DTF_SERVE_BUDGET": "16"}
                  if tiny else {})}
              for k in ("2", "4", "8")),
            # ISSUE 13: disaggregation — 1 of 2 replicas dedicated to
            # prefill; SHORT stem-cached traffic (decode phase) with a
            # simultaneous burst of LONG unique summarization-shaped
            # prompts (prefill phase). The serve_off side is the same
            # fleet with phase routing off; the claim rides the
            # per-replica TICK TTFT columns (ttft_short_*_ticks): the
            # burst's head-of-line admission pile-up must not inflate
            # short-request decode TTFT — on the single-process sim the
            # wall clock charges every replica for the whole fleet's
            # work, so tick counts are the parallel-fleet-honest metric.
            {"DTF_SERVE_REPLICAS": "2", "DTF_SERVE_PREFILL_REPLICAS": "1",
             "DTF_SERVE_PREFIX": "1.0", "DTF_SERVE_STEM_FRAC": "0.95",
             "DTF_SERVE_LONG": "0.33",
             **({"DTF_SERVE_TP_LONG": "704", "DTF_SERVE_N": "24",
                 "DTF_SERVE_RATE": "60", "DTF_SERVE_NEW_MIN": "8",
                 "DTF_SERVE_NEW_MAX": "12"} if tiny else {})},
        ]
        rows, errors = run_budgeted_jobs(
            serve_jobs, child_argv(os.path.abspath(__file__)) + ["--serve"],
            lambda line: (json.loads(line[len(SENTINEL):])
                          if line.startswith(SENTINEL) else None),
            budget=budget, cap_s=CHILD_TIMEOUT_S,
            env_base=dict(os.environ), on_result=on_result)
        return 0 if rows and not errors else 1
    jobs = [  # MHA vs GQA x full vs rolling-window cache
        {"DTF_DEC_KV": "0", "DTF_DEC_WINDOW": "0"},
        {"DTF_DEC_KV": "4", "DTF_DEC_WINDOW": "0"},
        {"DTF_DEC_KV": "0", "DTF_DEC_WINDOW": "256"},
        {"DTF_DEC_KV": "4", "DTF_DEC_WINDOW": "256"},
        # chunked prefill over the windowed-GQA shape: the bounded-memory
        # serving knob's cost vs its one-shot row above
        {"DTF_DEC_KV": "4", "DTF_DEC_WINDOW": "256",
         "DTF_DEC_PREFILL_CHUNK": "64"},
        # int8 KV cache on the same shape: half the cache bytes; decode is
        # HBM-bound, so tokens/sec should track the byte reduction
        {"DTF_DEC_KV": "4", "DTF_DEC_WINDOW": "256", "DTF_DEC_INT8": "1"},
    ]

    def on_result(row, job, rows, errors):
        _merge(rows, errors)
        print(json.dumps(row if row is not None else errors[-1]))

    rows, errors = run_budgeted_jobs(
        jobs, child_argv(os.path.abspath(__file__)),
        lambda line: (json.loads(line[len(SENTINEL):])
                      if line.startswith(SENTINEL) else None),
        budget=budget, cap_s=CHILD_TIMEOUT_S, env_base=dict(os.environ),
        on_result=on_result)
    return 0 if rows and not errors else 1


if __name__ == "__main__":
    if "--child" in sys.argv:
        if "--serve" in sys.argv:
            child_serve()
        else:
            child()
    elif "--sweep-serve" in sys.argv:
        sys.exit(main(key="serve"))
    else:
        sys.exit(main())
