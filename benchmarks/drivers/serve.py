"""A serving cell: ``DecodeEngine`` + ``Scheduler`` in this process, under a
closed loop of clients. No checkpoint, no server, no router.

Traffic file keys (``"kind": "serve"``): ``clients``; ``engine`` and
``scheduler``, the keyword arguments of ``DecodeEngine`` and ``Scheduler``
(what a file leaves out is the program's default, so a later cell switches
on pages or speculation as data); ``lengths`` (see
``lib.loadgen.request_lengths``), ``warm_completions`` (requests that must
end before the window opens), ``check_requests``, ``trace_seconds`` and
``rehearse``. The configuration's ``family`` builds the model.

The window opens once every slot has been filled and ``warm_completions``
requests have ended, which is where ``setup_s`` ends; it closes at the first
tick that ends after ``--seconds``. Requests are greedy.
"""

from __future__ import annotations

import importlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib import loadgen, xtrace
from benchmarks.lib.stats import percentile
from dtf_tpu.serve.engine import DecodeEngine
from dtf_tpu.serve.scheduler import Request, Scheduler
from dtf_tpu.telemetry import Telemetry

#: An emitted token passes if it is the reference's arg-max at its position
#: or its reference logit is within this of the arg-max's. With random
#: weights the top two logits are often closer than bfloat16's rounding
#: through 24 layers moves them (about 1% of the logits' RMS of ~1, so a
#: few hundredths; PERF.md section 6), so identity alone is not the test. A
#: token from a wrong position, a stale cache row or a lower-precision path
#: is off by the logits' spread, about 1: twenty times the tolerance.
TOKEN_LOGIT_TOL = 0.15


def check_tokens(reference_logits, params, sample, pad_to: int) -> dict:
    """One reference forward over prompt + emitted tokens per sampled
    request: holds prefill and decode-through-the-cache to the full
    forward pass."""
    forward = jax.jit(reference_logits)
    worst, exact, total, bad = 0.0, 0, 0, 0
    for prompt, emitted in sample:
        ids = np.zeros((1, pad_to), np.int32)
        seq = list(prompt) + list(emitted)
        ids[0, :len(seq)] = seq
        logits = np.asarray(forward(params, jnp.asarray(ids)))[0]
        for j, tok in enumerate(emitted):
            row = logits[len(prompt) - 1 + j]
            short = float(row.max() - row[tok])
            worst = max(worst, short)
            exact += int(short == 0.0)
            bad += int(not short <= TOKEN_LOGIT_TOL)
            total += 1
    return {"ok": total > 0 and bad == 0, "requests": len(sample),
            "tokens": total, "argmax_identical": exact,
            "outside_tolerance": bad, "worst_logit_shortfall": worst,
            "tolerance": TOKEN_LOGIT_TOL}


def run(job) -> dict:
    traffic = job.traffic
    laps = [("start", time.perf_counter())]   # set-up's phases, for a note
    fam = importlib.import_module(
        f"benchmarks.families.{job.config['family']}").build_serve(job.config)
    # weights on the device from the seed, in one jitted call
    params = fam.init_params(jax.random.PRNGKey(job.seed))
    jax.block_until_ready(params)
    laps.append(("init_s", time.perf_counter()))

    engine = DecodeEngine(fam.cfg, params, **traffic["engine"])
    n_slots, max_len = engine.n_slots, engine.max_len
    # the program's spans are read in the traced run only; the measured run
    # has the scheduler as a user's default leaves it
    tel = Telemetry(watchdog=False) if job.trace else None
    sched = Scheduler(engine, telemetry=tel, **traffic.get("scheduler", {}))
    if job.trace:
        engine.annotate_traces = True
    laps.append(("engine_s", time.perf_counter()))

    lengths = loadgen.request_lengths(traffic["lengths"])
    print(f"# lengths: {loadgen.describe(lengths)}", flush=True)
    token_rng = np.random.default_rng(job.seed)
    prompts = {}

    def submit(number: int) -> int:
        n_prompt, n_out = lengths[number % len(lengths)]
        prompt = token_rng.integers(0, fam.vocab_size, int(n_prompt)).tolist()
        rid = sched.submit(Request(prompt=prompt, max_new=int(n_out)))
        prompts[rid] = prompt
        return rid

    def poll(rid: int):
        rec = sched.poll(rid)
        return len(rec["tokens"]), rec["status"]

    def traced_tick():
        # names the host's side of a tick in the trace, beside the
        # dtf.serve.decode annotation the engine writes
        with jax.profiler.TraceAnnotation("bench.tick"):
            sched.tick()

    loop = loadgen.ClosedLoop(
        clients=traffic["clients"], submit=submit, poll=poll,
        tick=traced_tick if job.trace else sched.tick)
    warm = traffic["warm_completions"]
    while not (len(loop.ended) >= warm
               and loop.next_request >= n_slots + warm):
        loop.step()
    laps.append(("warm_s", time.perf_counter()))

    # ---- the window: closes at the first tick that ends past --seconds
    job.memory.sample()
    t0 = time.perf_counter()
    ticks0 = len(loop.ticks)
    span_names = ("serve_decode", "serve_prefill_chunk", "serve_page_load",
                  "serve_page_save")
    engine_s0 = sum(tel.spans.total(n) for n in span_names) if tel else 0.0
    occupancy = []
    while not occupancy or loop.ticks[-1][0] - t0 < job.seconds:
        loop.step()
        occupancy.append(sched.occupancy)
    t1 = loop.ticks[-1][0]
    job.memory.sample()
    window_ticks = loop.ticks[ticks0:]
    spans = tel.spans.rollup() if tel else {}
    engine_s = (sum(tel.spans.total(n) for n in span_names) - engine_s0
                if tel else None)

    # ---- the traced slice, after the window
    trace = None
    if job.trace:
        trace_dir = xtrace.start()
        t_trace = time.perf_counter()
        while time.perf_counter() - t_trace < traffic["trace_seconds"]:
            loop.step()
        trace = xtrace.stop(trace_dir)

    ended = [e for e in loop.ended if t0 < e["when"] <= t1]
    done = [e for e in ended if e["status"] == "done"]
    # ---- correctness, outside the window: a seeded sample of its requests
    pick = np.random.default_rng(job.seed).permutation(len(done))
    sample = [(prompts[done[i]["rid"]], sched.poll(done[i]["rid"])["tokens"])
              for i in pick[:traffic["check_requests"]]]
    series = {"ttft_ms": [1000.0 * v for v in loadgen.in_window(
                  loop.first_tokens, t0, t1)],
              "itl_ms": [1000.0 * v for v in loadgen.in_window(
                  loop.gaps, t0, t1)],
              "tick_ms": [1000.0 * d for _, d in window_ticks]}
    # the engine's two cache copies leave the reference no room: drop them
    del loop, submit, poll, traced_tick, sched, engine
    check = check_tokens(fam.reference_logits, params, sample, max_len)

    tick_wall = sum(d for _, d in window_ticks)
    values = {
        "window_s": t1 - t0,
        # every token that landed in the window, whether or not its request
        # ended there: all the work over all the time
        "output_tokens_per_s": (len(series["ttft_ms"]) + len(series["itl_ms"]))
        / (t1 - t0),
        "slot_occupancy_pct": 100.0 * sum(occupancy) / len(occupancy),
        "ticks": len(window_ticks),
    }
    if engine_s is not None:
        values["tick_host_ms_mean"] = (
            1000.0 * (tick_wall - engine_s) / len(window_ticks))
    return {
        "correct": bool(check["ok"]) and len(done) == len(ended)
        and len(done) > 0,
        "attempted": len(ended),
        "failed": len(ended) - len(done),
        "window_start": t0,
        "series": series,
        "spans": spans,
        "trace": trace,
        "values": values,
        "notes": {"check": check,
                  "setup_phases": {name: round(t - t_before, 3)
                                   for (_, t_before), (name, t)
                                   in zip(laps, laps[1:])},
                  "window": {
                      "requests_ended": len(ended),
                      # max beside the percentiles: a run that lost seconds
                      # to one stalled tick shows here and nowhere else
                      **{name: {"n": len(xs), **{
                          f"p{q}": round(percentile(xs, q), 3)
                          for q in (50, 90, 95, 99)},
                          "max": round(max(xs), 3)}
                         for name, xs in series.items() if xs}}},
    }
