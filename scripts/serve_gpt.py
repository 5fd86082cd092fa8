#!/usr/bin/env python
"""GPT online serving: continuous-batching decode over a train_gpt checkpoint.

    # explicit requests (semicolon-separated prompts)
    python scripts/serve_gpt.py --logdir=/tmp/dtf_tpu_logs \
        --requests="12,7,99;5,6,7,8" --n_new=32 --emit_tokens

    # seeded Poisson load (benching)
    python scripts/serve_gpt.py --logdir=/tmp/dtf_tpu_logs \
        --poisson_rate=4 --n_requests=32 --max_len=256

The online half of the flagship loop (scripts/generate_gpt.py is the
offline half): restores PARAMS ONLY from the Orbax checkpoint
(``Checkpointer.restore_params`` — no ~3x opt_state read), auto-loads the
architecture manifest train_gpt.py wrote (hand-matched flags are verified
against it, not trusted), builds a :class:`dtf_tpu.serve.DecodeEngine`
(``--n_slots`` concurrent requests, ``--max_len`` per-slot budget) and
pumps a FIFO scheduler with prefill/decode interleave. Prints ONE JSON
line of serving metrics (bench.py idiom): tokens/sec, TTFT p50/p99,
per-token latency, occupancy, queue depth. ``--emit_tokens`` additionally
prints one ``rid:tok,tok,...`` row per completed request.

Sharded serving is opt-in like generate_gpt.py: ``--mesh_data``/
``--mesh_model`` place the KV cache P('data','model') on a device subset
(slots over data shards, heads over TP shards).
"""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from absl import app, flags

from dtf_tpu.cli import flags as dflags

dflags.define_cluster_flags()
dflags.define_mesh_flags()
flags.DEFINE_string("logdir", "/tmp/dtf_tpu_logs", "training logdir whose "
                    "ckpt/ subdir holds the checkpoint to serve")
flags.DEFINE_string("size", "small", "small | medium | tiny; auto-loaded "
                    "from the checkpoint manifest when present")
flags.DEFINE_integer("kv_heads", 0, "grouped-query heads (manifest wins)")
flags.DEFINE_integer("attn_window", 0, "sliding window (manifest wins)")
flags.DEFINE_integer("attn_global_every", 0, "global-layer cadence "
                     "(manifest wins)")
flags.DEFINE_string("kv_cache_dtype", "", "'' or 'int8' (serving-side "
                    "choice; halves the cache bytes)")
flags.DEFINE_integer("n_slots", 8, "concurrent request slots PER REPLICA "
                     "(the KV cache batch dimension)")
flags.DEFINE_integer("max_len", 256, "per-slot token budget "
                     "(prompt + generated)")
flags.DEFINE_integer("prefill_chunk", 16, "fixed width of the prefill "
                     "program (>= 2); long prompts stream through it")
flags.DEFINE_integer("prefill_chunks_per_tick", 4, "prefill/decode "
                     "interleave: at most this many prompt chunks (or "
                     "prefix-page loads) between decode steps (0 = admit "
                     "greedily)")
flags.DEFINE_integer("replicas", 1, "DecodeEngine replicas behind the "
                     "router: one restored param tree, independent KV "
                     "state each, least-occupancy admission with "
                     "queue-depth tiebreak (docs/SERVING.md)")
flags.DEFINE_integer("prefill_replicas", 0, "prefill/decode "
                     "disaggregation: the first N replicas are DEDICATED "
                     "prefill replicas — long uncached prompts route "
                     "there, their KV pages land in a SHARED page store "
                     "(requires --prefix_pages) and decode replicas load "
                     "them in one gather, so a long-prompt burst cannot "
                     "starve fleet decode TTFT (docs/SERVING.md)")
flags.DEFINE_integer("spec_k", 0, "speculative decoding: draft proposals "
                     "per slot per tick (needs --draft_ckpt or "
                     "--draft_layers; 0 with a draft = the kernel-tune "
                     "winner decides, docs/TUNING.md; token streams stay "
                     "identical to plain decode)")
flags.DEFINE_string("draft_ckpt", "", "logdir of a SEPARATE draft-model "
                    "checkpoint (its own manifest resolves the draft "
                    "architecture; vocab must match the served model)")
flags.DEFINE_integer("draft_layers", 0, "early-exit draft: reuse the "
                     "first N layers of the SERVED checkpoint as the "
                     "draft model — speculation without a second "
                     "checkpoint (mutually exclusive with --draft_ckpt)")
flags.DEFINE_enum("draft_precision", "", ["", "auto", "bf16", "int8",
                                          "fp8"],
                  "low-precision compute for the DRAFT model's TP "
                  "projections ('' = bf16, auto = kernel-tune winner, "
                  "int8/fp8 = explicit pin): the proposal loop runs "
                  "cheaper while the bf16 verifier keeps emitted tokens "
                  "byte-identical — only acceptance rate can move "
                  "(docs/TUNING.md, docs/SERVING.md)")
flags.DEFINE_integer("kv_page_size", 0, "prefix page width in tokens "
                     "(with --prefix_pages: must divide --max_len)")
flags.DEFINE_integer("prefix_pages", 0, "prefix KV page-pool size per "
                     "replica (0 = prefix cache off): shared prompt stems "
                     "prefill once and fork into slots")
flags.DEFINE_float("ttft_slo", 0.0, "TTFT objective in seconds (0 = "
                   "untracked): the JSON line reports per-replica and "
                   "fleet compliance fractions")
flags.DEFINE_integer("max_queue", 0, "bounded-queue admission control "
                     "per replica: a submit against a full queue is SHED "
                     "(terminal status + retry_after_s hint) instead of "
                     "queueing forever (0 = unbounded)")
flags.DEFINE_float("ttft_deadline", 0.0, "per-request TTFT deadline in "
                   "seconds (0 = none): a request still waiting for its "
                   "first token past this is evicted with status "
                   "'timeout'")
flags.DEFINE_float("deadline", 0.0, "per-request TOTAL deadline in "
                   "seconds (0 = none); measured from submit")
flags.DEFINE_boolean("health", True, "with --replicas > 1: per-replica "
                     "health watchdog (wedged/slow replicas are "
                     "quarantined, their in-flight requests requeued "
                     "onto survivors, probation re-admits; "
                     "docs/RESILIENCE.md 'Serving')")
flags.DEFINE_float("health_slow_s", 0.0, "health watchdog: min slow-tick "
                   "bar in seconds (0 = library default)")
flags.DEFINE_float("health_wedge_s", 0.0, "health watchdog: single-tick "
                   "wedge bar in seconds — one tick this slow "
                   "quarantines outright (0 = library default)")
flags.DEFINE_float("health_probation_s", 0.0, "health watchdog: "
                   "quarantine→probation delay in seconds (0 = library "
                   "default)")
flags.DEFINE_string("publish_dir", "", "serve PUBLISHED weights (ISSUE "
                    "14): restore params from this publish dir's "
                    "versioned manifest instead of the logdir "
                    "checkpoint; the JSON line reports the version "
                    "actually served")
flags.DEFINE_integer("publish_version", 0, "with --publish_dir: serve "
                     "exactly this published version — NO fallback past "
                     "corruption (the explicit-step restore contract); "
                     "0 = newest servable version (guarded walk, WARNs "
                     "past a corrupt newest)")
flags.DEFINE_integer("swap_poll_ticks", 0, "with --publish_dir and "
                     "--replicas >= 2: poll the publish dir every N "
                     "scheduler ticks and ROLL new versions across the "
                     "fleet with zero downtime (drain one replica, "
                     "swap, probe, re-admit; the first replica is a "
                     "health-gated canary — docs/SERVING.md); 0 = "
                     "serve the startup version only")
flags.DEFINE_integer("canary_ticks", 8, "rolling swap: router ticks the "
                     "first swapped replica serves alone before the "
                     "rest of the fleet follows; a health/SLO breach "
                     "inside the window rolls the fleet back")
flags.DEFINE_string("requests", "", "semicolon-separated comma-lists of "
                    "token ids; empty = Poisson load")
flags.DEFINE_integer("n_new", 32, "max new tokens per explicit request")
flags.DEFINE_float("temperature", 0.0, "0 = greedy, else sampling")
flags.DEFINE_integer("top_k", 0, "top-k filter (0 = off)")
flags.DEFINE_float("top_p", 1.0, "nucleus filter (1.0 = off)")
flags.DEFINE_integer("eos_id", -1, "stop token (-1 = none)")
flags.DEFINE_integer("pad_id", 0, "pad token after eos")
flags.DEFINE_integer("seed", 0, "sampling / load-gen PRNG seed")
flags.DEFINE_float("poisson_rate", 2.0, "requests per second for the "
                   "seeded open-loop load generator")
flags.DEFINE_integer("n_requests", 16, "Poisson-mode request count")
flags.DEFINE_integer("prompt_min", 4, "Poisson-mode min prompt length")
flags.DEFINE_integer("prompt_max", 64, "Poisson-mode max prompt length")
flags.DEFINE_integer("new_min", 8, "Poisson-mode min new tokens")
flags.DEFINE_integer("new_max", 64, "Poisson-mode max new tokens")
flags.DEFINE_boolean("emit_tokens", False, "print rid:tok,... per request")
flags.DEFINE_boolean("telemetry", False, "per-engine-call phase spans "
                     "(serve_prefill_chunk / serve_decode p50/p99 in the "
                     "JSON line) and a compile-event fence over the serve "
                     "loop (docs/OBSERVABILITY.md)")
flags.DEFINE_integer("stats_every", 0, "liveness heartbeat: every N "
                     "scheduler ticks, emit one JSON snapshot line of "
                     "router/scheduler stats() to stderr (per-replica "
                     "occupancy, TTFT p50/p99, ttft_slo_ok_frac); 0 = off")
flags.DEFINE_float("ttft_slo_frac", 0.0, "with --stats_every and "
                   "--ttft_slo: log a WARNING when the TTFT SLO-ok "
                   "fraction drops below this floor (once per "
                   "excursion); with --swap_poll_ticks it is ALSO the "
                   "rolling swap's canary rollback floor — a canary "
                   "whose post-swap SLO-ok fraction dips under it rolls "
                   "the fleet back")
flags.DEFINE_string("trace_out", "", "write a Perfetto-loadable "
                    "chrome-trace JSON of per-request lifecycles (queue "
                    "wait, prefill chunks, decode steps, all tagged with "
                    "end-to-end trace ids) to this path; implies the "
                    "request TraceCollector is on")
flags.DEFINE_string("log_sink_dir", "", "serve-traffic log sink (ISSUE "
                    "19): every terminal request is appended (prompt + "
                    "completion token ids, param version, spec acceptance "
                    "counts, TTFT/latency, replica id) to CRC-framed, "
                    "size-rotated shards under this dir — mountable as "
                    "the 'servelog' stream source for draft distillation "
                    "(docs/DATA.md). Host-side only: zero added device "
                    "readbacks")
flags.DEFINE_string("event_log_dir", "", "fleet EVENT PLANE (ISSUE 20): "
                    "append every host-side lifecycle event (health "
                    "transitions, requeue drains, swap drain/canary/"
                    "commit/rollback, SLO excursions, sink rotations, "
                    "control-plane tick-profiler rollups) to CRC-framed "
                    "size-rotated shards under this dir; `python -m "
                    "dtf_tpu.telemetry timeline` merges them into one "
                    "causally-ordered run story (docs/OBSERVABILITY.md "
                    "§9). Host-side only: zero added device readbacks")
flags.DEFINE_string("draft_publish_dir", "", "poll this publish dir for "
                    "DISTILLED DRAFT versions (train_gpt --distill_draft "
                    "writes them) and roll DRAFT-ONLY swaps across the "
                    "fleet: the base weights ride the transaction "
                    "unchanged, so emitted tokens stay byte-identical and "
                    "only acceptance rate moves; needs --swap_poll_ticks, "
                    "--replicas >= 2 and a draft (--draft_ckpt or "
                    "--draft_layers) — docs/SERVING.md")
FLAGS = flags.FLAGS


def main(argv):
    del argv
    import jax

    from dtf_tpu.checkpoint import Checkpointer, load_model_config
    from dtf_tpu.cli.launch import device_report, init_backend
    from dtf_tpu.core.mesh import MeshConfig, make_mesh
    from dtf_tpu.core.sharding import shard_tree
    from dtf_tpu.metrics import MetricWriter
    from dtf_tpu.models import gpt
    from dtf_tpu.serve import (DecodeEngine, PoissonLoadGen, Request,
                               Scheduler, replay)

    init_backend(FLAGS.backend)
    sharded = FLAGS.mesh_model > 1 or FLAGS.mesh_data > 1
    mesh = None
    if sharded:
        dp = max(FLAGS.mesh_data, 1)
        tp = max(FLAGS.mesh_model, 1)
        if dp * tp > len(jax.devices()):
            raise app.UsageError(
                f"mesh {dp}x{tp} exceeds {len(jax.devices())} devices")
        if FLAGS.n_slots % dp:
            raise app.UsageError(
                f"--n_slots={FLAGS.n_slots} not divisible by the data "
                f"axis ({dp}) — slots shard over 'data'")
        mesh = make_mesh(MeshConfig(data=dp, model=tp),
                         devices=jax.devices()[:dp * tp])

    ckpt_dir = os.path.join(FLAGS.logdir, "ckpt")
    if FLAGS.publish_version and not FLAGS.publish_dir:
        raise app.UsageError(
            "--publish_version needs --publish_dir (it names a PUBLISHED "
            "version, not a checkpoint step)")
    if FLAGS.swap_poll_ticks:
        if not FLAGS.publish_dir and not FLAGS.draft_publish_dir:
            raise app.UsageError(
                "--swap_poll_ticks needs --publish_dir or "
                "--draft_publish_dir (there is nothing to poll for new "
                "versions without a publish dir)")
        if FLAGS.replicas < 2:
            raise app.UsageError(
                "--swap_poll_ticks needs --replicas >= 2: a rolling swap "
                "drains one replica while the others serve (a single "
                "engine cannot swap with zero downtime)")
    if FLAGS.draft_publish_dir:
        if not FLAGS.swap_poll_ticks:
            raise app.UsageError(
                "--draft_publish_dir needs --swap_poll_ticks > 0 (the "
                "draft watcher polls on the same cadence as the weight "
                "swap poller)")
        if not (FLAGS.draft_ckpt or FLAGS.draft_layers):
            raise app.UsageError(
                "--draft_publish_dir rolls DRAFT-ONLY swaps; the fleet "
                "needs a draft to replace — pass --draft_ckpt or "
                "--draft_layers")
    try:
        # kv dtype + page-size legality checked HERE (against the manifest
        # architecture and the serving shape), not inside the AOT build.
        # With --publish_dir the architecture manifest may live next to
        # the publish manifest (train_gpt writes both); the logdir ckpt
        # manifest stays the fallback.
        manifest = (load_model_config(FLAGS.publish_dir)
                    if FLAGS.publish_dir else None) \
            or load_model_config(ckpt_dir)
        decode_cfg = dflags.resolve_decode_config(
            FLAGS, manifest, max_len=FLAGS.max_len,
            kv_page_size=FLAGS.kv_page_size if FLAGS.prefix_pages else 0)
    except ValueError as e:
        raise app.UsageError(str(e))
    try:
        base = gpt.GPTConfig.by_name(decode_cfg["size"])
    except KeyError as e:
        raise app.UsageError(f"--size: {e.args[0]}")
    if FLAGS.replicas < 1:
        raise app.UsageError(f"--replicas={FLAGS.replicas} must be >= 1")
    if FLAGS.kv_page_size and not FLAGS.prefix_pages:
        # the engine would silently run page-less (page_size gated on the
        # pool size) — a half-configured cache should fail at flag time
        raise app.UsageError(
            f"--kv_page_size={FLAGS.kv_page_size} has no effect without "
            "--prefix_pages > 0 (the prefix page cache stays off); set "
            "both or neither")
    cfg = dataclasses.replace(base,
                              kv_heads=decode_cfg["kv_heads"] or None,
                              attn_window=decode_cfg["attn_window"],
                              attn_global_every=decode_cfg[
                                  "attn_global_every"],
                              kv_cache_dtype=decode_cfg["kv_cache_dtype"])

    served_version = 0
    if FLAGS.publish_dir:
        from dtf_tpu.publish import load_published

        try:
            served_version, step, params = load_published(
                FLAGS.publish_dir, FLAGS.publish_version or None)
        except (FileNotFoundError, ValueError, RuntimeError) as e:
            raise app.UsageError(str(e))
        print(f"serving published version {served_version} (train step "
              f"{step}) from {FLAGS.publish_dir}", file=sys.stderr)
    else:
        ckpt = Checkpointer(ckpt_dir)
        if ckpt.latest_step() is None:
            raise app.UsageError(f"no checkpoint under {ckpt_dir}")
        # guarded latest-step restore: a corrupt newest checkpoint WARNs
        # and serves the next older readable step instead of dying at
        # startup
        params = ckpt.restore_params()
        step = ckpt.last_restored_step
        print(f"restored params of step {step} from {ckpt_dir}",
              file=sys.stderr)
    if sharded:
        params = shard_tree(params, mesh, gpt.tp_rules)

    # speculative draft: a separate checkpoint (own manifest) or an
    # early-exit truncation of the served one — either way the verifier
    # samples every delivered token, so draft quality is a THROUGHPUT
    # knob, never a correctness one.
    draft_cfg = draft_params = None
    if FLAGS.draft_ckpt and FLAGS.draft_layers:
        raise app.UsageError(
            "--draft_ckpt and --draft_layers are two ways to get ONE "
            "draft model; pass exactly one")
    if FLAGS.draft_ckpt:
        dckpt_dir = os.path.join(FLAGS.draft_ckpt, "ckpt")
        dmanifest = load_model_config(dckpt_dir)
        if dmanifest is None:
            raise app.UsageError(
                f"--draft_ckpt={FLAGS.draft_ckpt} has no "
                "model_config.json manifest; the draft architecture "
                "cannot be guessed")
        try:
            dbase = gpt.GPTConfig.by_name(dmanifest.get("size", "draft"))
        except KeyError as e:
            raise app.UsageError(f"draft manifest size: {e.args[0]}")
        draft_cfg = dataclasses.replace(
            dbase,
            # a DISTILLED draft (train_gpt --distill_draft) names its
            # base's size but is truncated in depth — the manifest's
            # explicit layer count wins over the preset's
            layers=int(dmanifest.get("layers", dbase.layers)),
            kv_heads=dmanifest.get("kv_heads") or None,
            attn_window=int(dmanifest.get("attn_window", 0) or 0),
            attn_global_every=int(
                dmanifest.get("attn_global_every", 0) or 0),
            kv_cache_dtype=decode_cfg["kv_cache_dtype"])
        dck = Checkpointer(dckpt_dir)
        if dck.latest_step() is None:
            raise app.UsageError(f"no checkpoint under {dckpt_dir}")
        draft_params = dck.restore_params()
        print(f"restored draft params of step {dck.last_restored_step} "
              f"from {dckpt_dir}", file=sys.stderr)
    elif FLAGS.draft_layers:
        try:
            draft_cfg, draft_params = gpt.draft_truncate(
                cfg, params, FLAGS.draft_layers)
        except ValueError as e:
            raise app.UsageError(str(e))
    if FLAGS.spec_k and draft_cfg is None:
        raise app.UsageError(
            f"--spec_k={FLAGS.spec_k} needs a draft model: pass "
            "--draft_ckpt or --draft_layers")
    if FLAGS.draft_precision:
        if draft_cfg is None:
            raise app.UsageError(
                "--draft_precision quantizes the DRAFT model's matmuls; "
                "pass --draft_ckpt or --draft_layers")
        # draft-only: the bf16 verifier re-samples every emitted token,
        # so this moves acceptance rate, never the token stream.
        draft_cfg = dataclasses.replace(
            draft_cfg, matmul_precision=FLAGS.draft_precision)
    if draft_params is not None and sharded and FLAGS.draft_ckpt:
        draft_params = shard_tree(draft_params, mesh, gpt.tp_rules)
    if FLAGS.prefill_replicas:
        if not 0 < FLAGS.prefill_replicas < FLAGS.replicas:
            raise app.UsageError(
                f"--prefill_replicas={FLAGS.prefill_replicas} must leave "
                f"at least one decode replica (--replicas="
                f"{FLAGS.replicas})")
        if not FLAGS.prefix_pages:
            raise app.UsageError(
                "--prefill_replicas needs --prefix_pages > 0: the page "
                "pool is the prefill→decode KV transport")

    tel = None
    if FLAGS.telemetry or FLAGS.trace_out:
        from dtf_tpu.telemetry import Telemetry, TraceCollector

        # serving has its own stall story (the scheduler loop is
        # host-driven); spans + the compile fence are what telemetry
        # adds here, so no watchdog thread. Postmortems go next to the
        # checkpoint's logdir so the serve flight record is findable.
        tel = Telemetry(watchdog=False,
                        out_dir=os.path.join(FLAGS.logdir, "telemetry"))
        if FLAGS.trace_out:
            tel.tracer = TraceCollector()
    writer = MetricWriter(None, also_log=False)
    # the fleet event plane (ISSUE 20): ONE log every serve-side
    # subsystem writes, built first so the sink's own mount-time
    # recovery (orphan adoption) is already on the record
    events = None
    if FLAGS.event_log_dir:
        from dtf_tpu.telemetry.events import EventLog

        events = EventLog(FLAGS.event_log_dir)
    # the serve-traffic log sink (ISSUE 19): one sink for the whole fleet
    # (the pump is single-threaded; records carry their replica id) so
    # the shard sequence a mounted 'servelog' source addresses is global
    sink = None
    if FLAGS.log_sink_dir:
        from dtf_tpu.serve.logsink import LogSink

        sink = LogSink(FLAGS.log_sink_dir, events=events)
    try:
        if FLAGS.replicas > 1:
            from dtf_tpu.serve import HealthConfig, Router

            health = False
            if FLAGS.health:
                overrides = {}
                if FLAGS.health_slow_s > 0:
                    overrides["min_slow_s"] = FLAGS.health_slow_s
                if FLAGS.health_wedge_s > 0:
                    overrides["wedge_s"] = FLAGS.health_wedge_s
                if FLAGS.health_probation_s > 0:
                    overrides["probation_delay_s"] = \
                        FLAGS.health_probation_s
                health = HealthConfig(**overrides)
            # ONE fleet constructor: Router.build owns the role-dependent
            # rules (shared page store on disaggregation, eager saves,
            # no draft programs on prefill replicas)
            sched = Router.build(
                cfg, params, n_replicas=FLAGS.replicas,
                n_slots=FLAGS.n_slots, max_len=FLAGS.max_len,
                prefill_chunk=FLAGS.prefill_chunk, mesh=mesh,
                kv_page_size=FLAGS.kv_page_size,
                prefix_pages=FLAGS.prefix_pages,
                draft_cfg=draft_cfg, draft_params=draft_params,
                spec_k=FLAGS.spec_k,
                prefill_replicas=FLAGS.prefill_replicas,
                writer=writer, telemetry=tel, ttft_slo_s=FLAGS.ttft_slo,
                health=health, max_queue=FLAGS.max_queue,
                prefill_chunks_per_tick=FLAGS.prefill_chunks_per_tick,
                log_sink=sink, events=events)
            engines = [s.engine for s in sched.schedulers]
        else:
            engines = [DecodeEngine(
                cfg, params, n_slots=FLAGS.n_slots, max_len=FLAGS.max_len,
                prefill_chunk=FLAGS.prefill_chunk, mesh=mesh,
                kv_page_size=FLAGS.kv_page_size,
                prefix_pages=FLAGS.prefix_pages, draft_cfg=draft_cfg,
                draft_params=draft_params, spec_k=FLAGS.spec_k)]
            sched = Scheduler(
                engines[0], writer, log_every=0,
                prefill_chunks_per_tick=FLAGS.prefill_chunks_per_tick,
                telemetry=tel, ttft_slo_s=FLAGS.ttft_slo,
                max_queue=FLAGS.max_queue, log_sink=sink)
            if events is not None:
                # the fault installer's crash_in_event_rotate branch and
                # the summary emit read the pump's .events either way
                sched.events = events
    except ValueError as e:     # n_slots/max_len/prefill_chunk/page flags
        raise app.UsageError(str(e))
    if events is not None:
        events.emit("serve_start", replicas=FLAGS.replicas,
                    version=served_version, step=int(step),
                    spec_k=engines[-1].spec_k if FLAGS.replicas > 1
                    else engines[0].spec_k,
                    prefill_replicas=FLAGS.prefill_replicas)
    if served_version:
        # stamp the published version the fleet was BUILT with, so record
        # stamps / page epochs / the skew tripwire carry the real number
        if FLAGS.replicas > 1:
            sched.stamp_version(served_version)
        else:
            engines[0].set_param_version(served_version)
    if tel is not None:
        if FLAGS.trace_out:
            for e in engines:
                e.annotate_traces = True
        tel.start()

    # the hot-swap poller: every --swap_poll_ticks ticks, a NEW published
    # version (digest-verified; corrupt publishes skipped with a WARN)
    # starts a rolling swap across the fleet — the serve loop itself
    # never pauses (docs/SERVING.md "Rolling weight swap")
    watcher = None
    draft_watcher = None
    swap_tick = None
    if FLAGS.swap_poll_ticks:
        from dtf_tpu.publish import PublishWatcher
        from dtf_tpu.serve import SwapConfig

        if FLAGS.publish_dir:
            watcher = PublishWatcher(FLAGS.publish_dir,
                                     applied_version=served_version)
        if FLAGS.draft_publish_dir:
            # the flywheel's return path (ISSUE 19): distilled drafts
            # published by train_gpt --distill_draft roll through
            # Router.maybe_swap_draft — base weights untouched, tokens
            # byte-identical, the acceptance panel shows the payoff
            draft_watcher = PublishWatcher(FLAGS.draft_publish_dir)
        # with a TTFT SLO configured, --ttft_slo_frac doubles as the
        # canary's rollback floor (the same compliance fraction the
        # heartbeat warns on); health verdicts gate regardless
        swap_cfg = SwapConfig(
            canary_ticks=FLAGS.canary_ticks,
            slo_floor=(FLAGS.ttft_slo_frac
                       if FLAGS.ttft_slo > 0 else 0.0))
        draft_factory = None
        if FLAGS.draft_layers:
            draft_factory = lambda p: gpt.draft_truncate(  # noqa: E731
                cfg, p, FLAGS.draft_layers)[1]
        ticks = [0]

        def swap_tick():
            ticks[0] += 1
            if ticks[0] % FLAGS.swap_poll_ticks == 0:
                if watcher is not None:
                    sched.maybe_swap_published(watcher, config=swap_cfg,
                                               draft_factory=draft_factory)
                if draft_watcher is not None:
                    sched.maybe_swap_draft(draft_watcher, config=swap_cfg)

    # serve-side chaos (DTF_FAULT_INJECT=wedge_replica@tick:replica=k |
    # slow_decode@tick | poison_request@n | wedge_in_swap@n:replica=k |
    # corrupt_publish@n) rides the launcher the way PR 11's verbs ride
    # the trainers — the chaos matrix drives this.
    from dtf_tpu.fault.inject import ServeFaultPlan

    fault_plan = ServeFaultPlan.from_env()
    if fault_plan is not None:
        from dtf_tpu.serve import install_serve_fault

        install_serve_fault(fault_plan, sched, watcher=watcher)

    heartbeat = None
    if FLAGS.stats_every:
        from dtf_tpu.serve import Heartbeat

        heartbeat = Heartbeat(sched, every_ticks=FLAGS.stats_every,
                              slo_floor=FLAGS.ttft_slo_frac,
                              flight=tel.flight if tel is not None
                              else None, events=events)
    hooks = [h for h in
             (heartbeat.maybe_emit if heartbeat is not None else None,
              swap_tick) if h is not None]
    on_tick = (None if not hooks
               else hooks[0] if len(hooks) == 1
               else (lambda: [h() for h in hooks]))

    eos = FLAGS.eos_id if FLAGS.eos_id >= 0 else None
    t0 = time.perf_counter()
    rids = []
    if FLAGS.requests:
        for i, row in enumerate(r for r in FLAGS.requests.split(";") if r):
            prompt = [int(t) for t in row.split(",") if t.strip()]
            if not prompt or not all(
                    0 <= t < cfg.vocab_size for t in prompt):
                raise app.UsageError(
                    f"request {i}: token ids must be in "
                    f"[0, {cfg.vocab_size})")
            try:
                rids.append(sched.submit(Request(
                    prompt=prompt, max_new=FLAGS.n_new,
                    temperature=FLAGS.temperature, top_k=FLAGS.top_k,
                    top_p=FLAGS.top_p, eos_id=eos, pad_id=FLAGS.pad_id,
                    seed=FLAGS.seed + i,
                    ttft_deadline_s=FLAGS.ttft_deadline,
                    deadline_s=FLAGS.deadline)))
            except ValueError as e:   # over-long prompt / bad n_new
                raise app.UsageError(f"request {i}: {e}")
        sched.run_until_idle(on_tick=on_tick)
    else:
        prompt_cap = min(FLAGS.prompt_max, FLAGS.max_len - FLAGS.new_min)
        if prompt_cap < FLAGS.prompt_min:
            raise app.UsageError(
                f"--max_len={FLAGS.max_len} leaves no room for prompts in "
                f"[{FLAGS.prompt_min}, ..] plus --new_min={FLAGS.new_min}; "
                "raise --max_len or lower --prompt_min/--new_min")
        try:
            gen = PoissonLoadGen(
                rate=FLAGS.poisson_rate, n_requests=FLAGS.n_requests,
                vocab_size=cfg.vocab_size, prompt_min=FLAGS.prompt_min,
                prompt_max=prompt_cap,
                new_min=FLAGS.new_min, new_max=FLAGS.new_max,
                temperature=FLAGS.temperature, top_k=FLAGS.top_k,
                top_p=FLAGS.top_p, eos_id=eos, seed=FLAGS.seed)
        except ValueError as e:  # rate/prompt/new bound flag errors
            raise app.UsageError(str(e))
        arrivals = gen.arrivals()
        if FLAGS.ttft_deadline > 0 or FLAGS.deadline > 0:
            arrivals = ((t, dataclasses.replace(
                req, ttft_deadline_s=FLAGS.ttft_deadline,
                deadline_s=FLAGS.deadline)) for t, req in arrivals)
        replay(sched, arrivals, on_tick=on_tick)
        rids = list(range(FLAGS.n_requests))   # submit order = id order
    if FLAGS.swap_poll_ticks and getattr(sched, "swap_in_progress", False):
        # a swap that started near the end of the run converges before
        # the final stats line (idle ticks still advance the machine)
        sched.finish_swap()
    wall = time.perf_counter() - t0

    if FLAGS.emit_tokens:
        for rid in rids:
            st = sched.poll(rid)
            print(f"{rid}:" + ",".join(str(t) for t in st["tokens"]))
    polls = [sched.poll(r) for r in rids]
    statuses: dict = {}
    for p in polls:
        statuses[p["status"]] = statuses.get(p["status"], 0) + 1
    n_tokens = sum(len(p["tokens"]) for p in polls)
    cache_bytes = sum(e.cache_bytes() for e in engines)
    out = {"mode": "requests" if FLAGS.requests else "poisson",
           "backend": jax.default_backend(), **device_report(),
           "step": step,
           # the published version serving STARTED on (0 = checkpoint
           # serving) and the one the fleet ended on after any rolling
           # swaps — stats() adds router_version/replica{i}_version
           "served_version": served_version,
           "final_version": int(sched.version if FLAGS.replicas > 1
                                else engines[0].param_version),
           "replicas": FLAGS.replicas,
           "prefill_replicas": FLAGS.prefill_replicas,
           # the RESOLVED draft width (decode replicas; 0 = spec off) —
           # an unset --spec_k reports what the kernel-tune winner chose
           "spec_k": engines[-1].spec_k,
           "draft": ("ckpt" if FLAGS.draft_ckpt
                     else f"layers:{FLAGS.draft_layers}"
                     if FLAGS.draft_layers else ""),
           "draft_precision": FLAGS.draft_precision,
           "request_statuses": statuses,
           "fault_inject": os.environ.get("DTF_FAULT_INJECT", "")
           if fault_plan is not None else "",
           "n_slots": FLAGS.n_slots, "max_len": FLAGS.max_len,
           "prefill_chunk": FLAGS.prefill_chunk,
           "kv_page_size": FLAGS.kv_page_size if FLAGS.prefix_pages else 0,
           "prefix_pages": FLAGS.prefix_pages,
           "requests": len(rids), "generated_tokens": n_tokens,
           "wall_s": round(wall, 4),
           "tokens_per_sec": round(n_tokens / max(wall, 1e-9), 1),
           "cache_mib": round(cache_bytes / 2 ** 20, 2)}
    out.update({k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in sched.stats().items()})
    # the flywheel panel (ISSUE 19): raw per-version acceptance counts
    # next to the rate keys stats() already rendered — a distilled
    # draft's roll reads as accept_by_version growing a new version row
    acc = sched.accept_by_version()
    if acc:
        out["accept_by_version"] = {
            str(v): [p, a] for v, (p, a) in acc.items()}
    if sink is not None:
        # commits the open shard to the manifest; anything torn before
        # this point is recovered by the next sink's orphan adoption
        sink.close()
        out["log_sink_dir"] = FLAGS.log_sink_dir
        out["log_sink"] = sink.stats()
    if FLAGS.draft_publish_dir:
        out["draft_publish_dir"] = FLAGS.draft_publish_dir
    if events is not None:
        # the run's closing record: statuses + the per-version acceptance
        # panel land on the timeline (derive_slo_report's
        # accept_by_version source), then the open shard commits
        events.emit("serve_summary", requests=len(rids),
                    generated_tokens=n_tokens, statuses=statuses,
                    final_version=out["final_version"],
                    accept_by_version={str(v): [p, a]
                                       for v, (p, a) in acc.items()}
                    if acc else {})
        events.close()
        out["event_log_dir"] = FLAGS.event_log_dir
        out["event_log"] = events.stats()
    if heartbeat is not None:
        # heartbeats + SLO-excursion count + worst compliance fraction:
        # a run that breached and recovered must not look clean
        out.update(heartbeat.stats())
    if tel is not None:
        if FLAGS.trace_out and tel.tracer is not None:
            from dtf_tpu.telemetry.profile import export_chrome_trace

            export_chrome_trace(FLAGS.trace_out,
                                request_events=tel.tracer.events,
                                meta={"source": "serve_gpt",
                                      "replicas": FLAGS.replicas})
            out["trace_out"] = FLAGS.trace_out
            out["trace_events"] = len(tel.tracer.events)
        tel.stop()
        out["trace_counts"] = [
            {**e.trace_counts,
             **{f"page_{k}": v for k, v in e.page_trace_counts.items()}}
            for e in engines]
        out["compile_events"] = tel.fence.compile_events
        # without this flag, compile_events==0 would be ambiguous between
        # "steady state" and "jax.monitoring unobservable on this jax"
        out["monitoring_available"] = tel.fence.monitoring_available
        # stamp the serve flight record — acceptance per version rides
        # the logdir-local TELEMETRY.json next to the flight dumps
        from dtf_tpu.telemetry.run import merge_artifact

        extra = {"source": "serve_gpt",
                 "served_version": served_version,
                 "final_version": out["final_version"]}
        if acc:
            extra["accept_by_version"] = {
                str(v): [p, a] for v, (p, a) in acc.items()}
        if sink is not None:
            extra["log_sink"] = sink.stats()
        merge_artifact(
            os.path.join(FLAGS.logdir, "telemetry", "TELEMETRY.json"),
            tel.report(extra))
    print(json.dumps(out))


if __name__ == "__main__":
    app.run(main)
