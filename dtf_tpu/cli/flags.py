"""The reference's flag surface, kept launch-compatible (SURVEY.md §5.6).

The reference defines ``tf.app.flags`` globals (``ps_hosts``, ``worker_hosts``,
``job_name``, ``task_index``, ``issync``, data/lr/batch/steps) and runs via
``tf.app.run``. The contract here (BASELINE north_star): "the existing run
scripts launch unchanged with ``--backend=tpu``". Same names, same comma
separated host lists; on the TPU backend the ps/worker flags collapse into
mesh + process identity (:func:`dtf_tpu.core.dist.collapse_cluster_flags`).
"""

from __future__ import annotations

from typing import NamedTuple

from absl import flags

from dtf_tpu.telemetry.accounting import DEVICE_PEAKS

FLAGS = flags.FLAGS


def define_cluster_flags():
    flags.DEFINE_string("ps_hosts", "", "comma-separated ps host:port list "
                        "(accepted for compatibility; collapsed on tpu)")
    flags.DEFINE_string("worker_hosts", "", "comma-separated worker host:port "
                        "list; becomes the process world on tpu")
    flags.DEFINE_string("job_name", "worker", "'ps' or 'worker'; ps exits "
                        "immediately on the tpu backend")
    flags.DEFINE_integer("task_index", 0, "index within the job")
    flags.DEFINE_boolean("issync", True, "sync gradient aggregation. The tpu "
                         "backend is always synchronous; issync=False warns "
                         "(async PS is an anti-pattern on TPU) and proceeds "
                         "synchronously")
    flags.DEFINE_string("backend", "tpu", "tpu | cpu (cpu = simulated mesh "
                        "for local testing)")
    flags.DEFINE_integer(
        "devices_per_host", 0,
        "fake-hosts harness (cpu multi-worker launches): each host's share "
        "of the simulated mesh — the cluster mesh spans devices_per_host x "
        "n_workers devices, so a relaunch with fewer workers re-forms a "
        "SMALLER mesh and resumes by resharding (docs/RESILIENCE.md). "
        "0 = all local devices (single-process behavior).")


def define_mesh_flags():
    flags.DEFINE_integer("mesh_data", -1, "data-parallel axis size (-1: all "
                         "remaining devices)")
    flags.DEFINE_integer("mesh_seq", 1, "sequence/context-parallel axis size")
    flags.DEFINE_integer("mesh_model", 1, "tensor-parallel axis size")
    flags.DEFINE_integer("mesh_pipe", 1, "pipeline-parallel axis size")
    flags.DEFINE_integer("mesh_expert", 1, "expert-parallel (MoE) axis size")


def define_train_flags(batch_size=64, learning_rate=0.01, train_steps=1000,
                       lr_schedule="constant"):
    flags.DEFINE_string("data_dir", "", "dataset directory (empty: synthetic)")
    flags.DEFINE_string("logdir", "/tmp/dtf_tpu_logs", "checkpoint/summary dir")
    flags.DEFINE_integer("batch_size", batch_size, "GLOBAL batch size (the "
                         "reference's per-worker batch × num workers)")
    flags.DEFINE_float("learning_rate", learning_rate, "learning rate")
    flags.DEFINE_integer("train_steps", train_steps, "stop at this global step")
    flags.DEFINE_integer("checkpoint_every", 200, "steps between saves")
    flags.DEFINE_integer("log_every", 10, "steps between metric logs")
    flags.DEFINE_integer("grad_accum", 1, "gradient-accumulation microbatches")
    flags.DEFINE_boolean("grad_shard", False, "with --grad_accum>1: ZeRO-1 "
                         "weight-update sharding for the accumulator — "
                         "microbatch gradients reduce-scatter over the data "
                         "axis into 1/N f32 shards, the optimizer update "
                         "runs on the shard, params all-gather once per "
                         "step (docs/ZERO.md). Needs a pure-GSPMD loss "
                         "(dense attention; no pallas/ring/overlap "
                         "kernels); falls back to the replicated "
                         "accumulator with a warning otherwise")
    flags.DEFINE_float("clip_grad_norm", 0.0, "clip gradients to this global "
                       "norm before the optimizer update (0 = off)")
    flags.DEFINE_string("lr_schedule", lr_schedule, "constant | linear | "
                        "cosine: LR decay after warmup, over the remaining "
                        "train_steps (see make_lr_schedule)")
    flags.DEFINE_integer("warmup_steps", -1, "linear LR warmup 0 -> "
                         "learning_rate over this many steps; -1 = auto "
                         "(min(1000, train_steps/10 + 1) for decaying "
                         "schedules, 0 for constant)")
    flags.DEFINE_float("lr_min_ratio", 0.0, "decay floor as a fraction of "
                       "--learning_rate (cosine alpha / linear end value)")
    flags.DEFINE_string("optimizer", "", "override the script's recipe "
                        "optimizer: sgd | momentum | adam | adamw | lamb | "
                        "adafactor (empty = keep the recipe default). lamb "
                        "is the BERT-at-scale recipe; adafactor is the "
                        "memory-lean TPU option (factored second moments)")
    flags.DEFINE_float("weight_decay", -1.0, "weight decay for "
                       "adamw/lamb overrides (-1 = optimizer default)")
    flags.DEFINE_integer("seed", 0, "PRNG seed")
    flags.DEFINE_integer("prefetch_depth", 2, "device-input prefetch "
                         "depth: batch N+1's host->device transfer "
                         "dispatches while step N computes "
                         "(dtf_tpu/data/prefetch.py double buffer; 1 = "
                         "off). With a mixture stream this also sizes "
                         "the bounded background producer queue "
                         "(docs/DATA.md)")
    flags.DEFINE_integer("profile_steps", 0, "capture an XPlane profiler "
                         "trace spanning this many steps (0 = off); written "
                         "to <logdir>/profile")
    flags.DEFINE_integer("profile_start", 10, "step at which the profiler "
                         "trace window opens")
    flags.DEFINE_boolean("profile_on_demand", True, "accept live-run "
                         "profile requests: SIGUSR1 or `touch "
                         "<logdir>/profile.trigger` opens a "
                         "--profile_steps-wide (default 5) trace window at "
                         "the next step boundary, no restart needed")
    flags.DEFINE_boolean("telemetry", False, "run-wide observability "
                         "(docs/OBSERVABILITY.md): step-phase spans "
                         "(data_wait/h2d/dispatch/hooks p50/p99), MFU + "
                         "goodput accounting, a train-step compile fence, "
                         "and a crash flight recorder dumping the last "
                         "steps to <logdir>/telemetry/postmortem.json on "
                         "crash/stall/SIGTERM. One RunReport JSON line "
                         "prints at exit. Host-side timers only: adds zero "
                         "blocking device readbacks to the training loop")
    flags.DEFINE_integer("telemetry_keep_steps", 64, "flight-recorder ring "
                         "size: step records kept for the postmortem")
    flags.DEFINE_float("telemetry_min_stall_s", 60.0, "stall watchdog "
                       "floor: no step completion within max(this, "
                       "factor x p99 step time) dumps a stall "
                       "postmortem (0 disables the watchdog thread)")
    flags.DEFINE_float("telemetry_stall_factor", 10.0, "stall watchdog "
                       "multiple of the p99 recent step time (set the "
                       "floor above the longest expected hook pause — eval "
                       "sweep / checkpoint wait)")


def make_lr_schedule(FLAGS):
    """--learning_rate/--lr_schedule/--warmup_steps/--lr_min_ratio -> an
    optax schedule (or a plain float when constant with no warmup — the
    zero-overhead path).

    The schedule is what the BERT/GPT pretraining recipes assume (linear
    warmup then decay); it composes with the rest of the optimizer story
    because the step counter lives in the optax state: grad-accum applies
    the update ONCE per global step (the accumulated mean gradient, so the
    count advances per step, not per microbatch), and ZeRO-1 keeps scalar
    state leaves replicated (core/sharding.py zero1 specs), so every shard
    sees the same schedule position. Both are regression-tested.
    """
    import optax

    lr = FLAGS.learning_rate
    kind = getattr(FLAGS, "lr_schedule", "constant")
    warmup = getattr(FLAGS, "warmup_steps", -1)
    ratio = getattr(FLAGS, "lr_min_ratio", 0.0)
    if warmup < 0:
        warmup = (0 if kind == "constant"
                  else min(1000, FLAGS.train_steps // 10 + 1))
    if kind == "constant" and warmup == 0:
        return lr
    decay = max(FLAGS.train_steps - warmup, 1)
    if kind == "constant":
        body = optax.constant_schedule(lr)
    elif kind == "linear":
        body = optax.linear_schedule(lr, lr * ratio, decay)
    elif kind == "cosine":
        body = optax.cosine_decay_schedule(lr, decay, alpha=ratio)
    else:
        raise ValueError(f"unknown --lr_schedule={kind!r} "
                         "(constant | linear | cosine)")
    if warmup == 0:
        return body
    return optax.join_schedules(
        [optax.linear_schedule(0.0, lr, warmup), body], [warmup])


def make_optimizer(FLAGS, recipe, recipe_uses_wd=False):
    """The script's full optimizer story in one call: LR schedule →
    ``--optimizer`` override (or the script's recipe default) →
    :func:`wrap_optimizer` shaping.

    ``recipe``: ``callable(schedule) -> optax.GradientTransformation`` —
    the launcher's era-faithful default (e.g. adamw(wd=0.01) for BERT,
    nesterov SGD for ResNet), used when ``--optimizer`` is empty so
    existing launch commands keep their exact numerics.
    ``recipe_uses_wd=True`` declares that the recipe itself consumes
    ``--weight_decay`` (BERT/GPT pass it into their adamw; ResNet maps
    it to loss-side L2); otherwise an explicitly-set ``--weight_decay``
    that nothing would consume raises instead of silently training
    without it. Every named override composes with ZeRO-1 (param-shaped state shards via
    ``zero1_opt_specs``; adafactor's rank-reduced factored moments fall
    back to a fresh data-axis spec — see ``_zero1_leaf_spec``),
    grad-accum (one update per global step) and the LR schedule (step
    count lives in optax state); regression-tested in
    tests/test_optimizers.py.
    """
    import optax

    sched = make_lr_schedule(FLAGS)
    name = (getattr(FLAGS, "optimizer", "") or "").lower()
    wd = getattr(FLAGS, "weight_decay", -1.0)

    def decay(default):
        return wd if wd >= 0.0 else default

    def reject_wd():
        # A silently-dropped hyperparameter is worse than an error: a
        # --weight_decay sweep over an optimizer that ignores it would
        # train N identical runs.
        if wd >= 0.0:
            raise ValueError(
                f"--weight_decay has no effect with "
                f"--optimizer={name or '<recipe default>'}; use "
                "adamw | lamb | adafactor (or a launcher whose recipe "
                "consumes it)")

    if not name:
        if not recipe_uses_wd:
            reject_wd()
        tx = recipe(sched)
    elif name == "sgd":
        reject_wd()
        tx = optax.sgd(sched)
    elif name == "momentum":
        reject_wd()
        tx = optax.sgd(sched, momentum=0.9, nesterov=True)
    elif name == "adam":
        reject_wd()
        tx = optax.adam(sched)
    elif name == "adamw":
        tx = optax.adamw(sched, weight_decay=decay(1e-4))   # optax default
    elif name == "lamb":
        tx = optax.lamb(sched, weight_decay=decay(0.0))     # optax default
    elif name == "adafactor":
        # adafactor consumes the schedule directly (it scales updates by
        # its own RMS rule); decay rides optax's weight_decay_rate arg
        tx = optax.adafactor(
            learning_rate=sched,
            weight_decay_rate=(wd if wd >= 0.0 else None))
    else:
        raise ValueError(
            f"unknown --optimizer={name!r} "
            "(sgd | momentum | adam | adamw | lamb | adafactor)")
    return wrap_optimizer(tx, FLAGS)


#: optimizer families that apply weight decay themselves (decoupled decay);
#: launchers whose recipes express regularization as loss-side L2 must drop
#: the L2 when one of these is selected — and route the decay here instead.
DECOUPLED_DECAY_OPTIMIZERS = ("adamw", "lamb", "adafactor")


def resolve_loss_l2(FLAGS, recipe_l2: float):
    """Loss-side L2 coefficient for launchers with an L2-based recipe.

    When ``--optimizer`` picks a decoupled-decay family the loss-side L2
    must be dropped (both would fire), so this returns 0.0 — but if
    ``--weight_decay`` was left unset, the optimizer's own default decay
    may be 0.0 (lamb) or None (adafactor), and the run would silently
    train with NO regularization at all (ADVICE r5 #2). In that case the
    recipe's coefficient is promoted into ``--weight_decay`` (consumed by
    :func:`make_optimizer`) with a warning, so the recipe's regularization
    strength survives the optimizer swap.
    """
    name = (getattr(FLAGS, "optimizer", "") or "").lower()
    if name not in DECOUPLED_DECAY_OPTIMIZERS:
        return FLAGS.weight_decay if FLAGS.weight_decay >= 0 else recipe_l2
    if FLAGS.weight_decay < 0:
        from absl import logging as absl_logging

        FLAGS.weight_decay = recipe_l2
        absl_logging.warning(
            "--optimizer=%s drops the recipe's loss-side L2; defaulting "
            "--weight_decay to the recipe's %g (decoupled decay). Pass "
            "--weight_decay explicitly to override.", name, recipe_l2)
    return 0.0


#: decode-config fields the checkpoint manifest is authoritative for: a
#: hand-matched mismatch on any of these silently garbles decode (wrong
#: head count reads the cache at the wrong stride — no shape error).
DECODE_MANIFEST_FIELDS = ("size", "kv_heads", "attn_window",
                          "attn_global_every")


def resolve_decode_config(FLAGS, manifest, *, max_len=None,
                          kv_page_size=None):
    """Merge the checkpoint's ``model_config.json`` manifest into the
    serving flags (``generate_gpt.py`` / ``serve_gpt.py``).

    Manifest present: its architecture fields WIN — an explicitly passed
    flag that contradicts it raises (the mismatch used to garble decode
    silently), a matching or unset flag just follows it. No manifest (old
    checkpoint): flags pass through untouched, exactly the old contract.
    ``kv_cache_dtype`` is a serving-side choice, not an architecture fact,
    so the flag always wins and the manifest only supplies a default —
    but the CHOICE is validated here against the manifest's architecture
    (head dim) and the serving shape (``max_len``/``kv_page_size``), so an
    illegal combination fails at flag resolution with a usable message
    instead of deep inside the engine's AOT build.
    Raises ValueError — launchers convert to their UsageError.
    """
    out = {f: getattr(FLAGS, f) for f in DECODE_MANIFEST_FIELDS}
    out["kv_cache_dtype"] = getattr(FLAGS, "kv_cache_dtype", "")
    if manifest is not None:
        if int(manifest.get("moe_every", 0) or 0):
            raise ValueError(
                f"checkpoint was trained with moe_every="
                f"{manifest['moe_every']}; the decode stack has no MoE "
                "path — serving a Switch-MoE checkpoint would silently "
                "drop the expert weights")
        for f in DECODE_MANIFEST_FIELDS:
            if f not in manifest:
                continue
            if FLAGS[f].present and getattr(FLAGS, f) != manifest[f]:
                raise ValueError(
                    f"--{f}={getattr(FLAGS, f)!r} contradicts the "
                    f"checkpoint manifest ({manifest[f]!r}); drop the "
                    "flag — the manifest written by the training launcher "
                    "is authoritative")
            out[f] = manifest[f]
        if (not FLAGS["kv_cache_dtype"].present
                and "kv_cache_dtype" in manifest):
            out["kv_cache_dtype"] = manifest["kv_cache_dtype"]
    _validate_kv_cache_dtype(out["kv_cache_dtype"], manifest,
                             max_len=max_len, kv_page_size=kv_page_size)
    return out


def _validate_kv_cache_dtype(dtype: str, manifest, *, max_len=None,
                             kv_page_size=None) -> None:
    """The serving-side KV choices, checked where the error is cheap.

    Everything here WOULD otherwise surface as an opaque trace/compile
    error inside ``DecodeEngine``'s AOT build (or, worse, garbled decode):
    an unknown dtype string, an int8 cache on an architecture whose head
    dim breaks the rope-pair/scale layout, or a page size that does not
    divide the per-slot cache length (a page window crossing the cache end
    cannot be copied fixed-shape).
    """
    if dtype not in ("", "int8"):
        raise ValueError(
            f"kv_cache_dtype={dtype!r} must be '' (store at model dtype) "
            "or 'int8'")
    if kv_page_size is not None and kv_page_size:
        if kv_page_size < 1:
            raise ValueError(f"kv_page_size={kv_page_size} must be >= 1")
        if max_len is not None and max_len % kv_page_size:
            raise ValueError(
                f"kv_page_size={kv_page_size} does not divide the per-slot "
                f"cache length max_len={max_len}; pick a page size that "
                "tiles the cache (pages are fixed-shape copies)")
    if dtype == "int8" and manifest is not None:
        d_model = int(manifest.get("d_model", 0) or 0)
        heads = int(manifest.get("heads", 0) or 0)
        if d_model and heads:
            d_head = d_model // heads
            if d_head % 2:
                raise ValueError(
                    f"kv_cache_dtype=int8 needs an even head dim (rope "
                    f"pairs lanes); manifest says d_model={d_model} / "
                    f"heads={heads} -> d_head={d_head}")


def resolve_grad_shard(FLAGS, mesh, *, blockers=()):
    """``--grad_shard`` viability — the safe-fallback gate (docs/ZERO.md).

    The sharded accumulator needs a real data axis, real accumulation, and
    a pure-GSPMD loss: the shard_map'd kernels (ring/zigzag/halo
    attention, flash, the Pallas fused CE, the collective-matmul overlap,
    pipelined stages) pin their own batch-over-data layouts, which the
    per-shard-group vmap cannot nest inside — those would fail at trace
    time deep inside a kernel. Launchers pass the kernel facts they know
    as ``blockers``; this returns the effective setting, WARNING on
    fallback instead of crashing.
    """
    from absl import logging as absl_logging

    if not getattr(FLAGS, "grad_shard", False):
        return False
    reasons = list(blockers)
    if getattr(FLAGS, "grad_accum", 1) <= 1:
        reasons.append("--grad_accum<=1 (no accumulator to shard)")
    if mesh.shape.get("data", 1) <= 1:
        reasons.append("data axis is 1 (nothing to reduce-scatter over)")
    if reasons:
        absl_logging.warning(
            "--grad_shard falls back to the replicated accumulator: %s",
            "; ".join(reasons))
        return False
    return True


#: v5e HBM per chip (the one table of published peaks); the loss-path
#: picker budgets against a fraction of it because params + optimizer
#: state + activations share the pool. A planning constant: the tuner
#: buckets its banked rows by it on machines with no chip at all.
HBM_BYTES_PER_CHIP = DEVICE_PEAKS["TPU v5 lite"]["hbm_bytes"]
#: monolithic [B,T,V] f32 logits + their cotangent must fit inside this
#: fraction of HBM to pick the fast path. Calibrated against the on-chip
#: map (PERF.md §5): GPT-2-small b8 s1024 (3.3 GB) fits and runs 9 MFU
#: points faster unchunked; b16 (6.6 GB) is where throughput falls over.
LOGITS_HBM_FRACTION = 0.25
#: the token-chunk width the sweep banked as the fast bounded-memory shape
#: (one full-vocab MXU matmul per block — PERF.md §5).
AUTO_LOSS_CHUNK_TOKENS = 4096


class LmLossPath(NamedTuple):
    """The resolved LM loss path (``resolve_lm_loss``). NamedTuple so
    launchers destructure the chunk fields positionally where the old
    2-tuple contract did, with the pallas path and winner provenance
    riding behind."""

    chunk_vocab: int
    chunk_tokens: int
    pallas: bool = False
    source: str = "heuristic"


def resolve_lm_loss(FLAGS, *, batch: int, seq_len: int, vocab_size: int,
                    mesh_shape=None, hbm_bytes: float = HBM_BYTES_PER_CHIP):
    """Pick the LM loss path: HBM estimate + the kernel-tune winners.

    The vocab-chunked loss is a MEMORY lever, not a speed lever: it costs
    ~9 MFU points on GPT and ~5 on BERT versus the monolithic [B,T,V]
    matmul+CE that XLA fuses (BENCH_LM_SWEEP.json rows from before PR 1;
    both train cells run the monolithic path). So: when no fused-loss flag
    is set and the full logits plus their cotangent fit comfortably per
    device, keep the monolithic path; when they don't, take the banked
    loss-path winner from the kernel-tune cache
    (:func:`dtf_tpu.tune.resolver.lm_loss_winner` — seeded from the
    on-chip BENCH_LM_SWEEP rows),
    defaulting to the token-chunked fused CE — one full-vocab MXU
    matmul per block, the faster chunking axis — never the vocab scan.

    EXPLICIT flags always win, but warn when they force a
    measured-slower path: any fused flag on a fitting config (paying
    ~9 MFU points for memory it doesn't need), and ``--loss_chunk_vocab``
    on a non-fitting config where the banked winner is a different
    bounded-memory path.

    Returns :class:`LmLossPath`. TP/pipe restrictions stay here: fused
    losses don't compose with a vocab-sharded head or the pipelined
    loss, so under ``mesh_model > 1`` / ``mesh_pipe > 1`` the monolithic
    path is the only legal one (the launchers additionally reject
    explicit fused flags there).
    """
    from absl import logging as absl_logging

    from dtf_tpu.tune import resolver as tune_resolver

    mesh_shape = mesh_shape or {}
    lchunk = getattr(FLAGS, "loss_chunk_vocab", 0)
    tchunk = getattr(FLAGS, "loss_chunk_tokens", 0)
    lpallas = getattr(FLAGS, "loss_pallas", False)
    # per-device token share: logits shard over the data and seq axes
    shards = max(mesh_shape.get("data", 1), 1) * max(
        mesh_shape.get("seq", 1), 1)
    # f32 logits + cotangent live simultaneously through the backward
    est = 2 * (batch * seq_len / shards) * vocab_size * 4
    fits = est <= LOGITS_HBM_FRACTION * hbm_bytes
    n_devices = 1
    for v in mesh_shape.values():
        n_devices *= max(int(v), 1)
    winner = tune_resolver.lm_loss_winner(
        fits=fits, vocab=vocab_size, seq=seq_len, batch=batch,
        n_devices=n_devices, backend=None)
    if lchunk or tchunk or lpallas:
        which = ("--loss_chunk_vocab" if lchunk else
                 "--loss_chunk_tokens" if tchunk else "--loss_pallas")
        if fits:
            absl_logging.warning(
                "%s forces a fused LM loss but the monolithic [B,T,V] "
                "logits fit (est %.2f GB/device of %.0f GB HBM): the "
                "chunked path costs ~9 GPT MFU points (PERF.md §5) — "
                "drop the flag to let the HBM estimate pick", which,
                est / 1e9, hbm_bytes / 1e9)
        elif lchunk and (winner is None or winner.path != "chunk_vocab"):
            absl_logging.warning(
                "--loss_chunk_vocab forces the measured-slower chunking "
                "axis (the serialized vocab scan costs ~9 GPT MFU "
                "points, PERF.md §5); the banked winner here is %s (%s) "
                "— drop the flag to follow it",
                winner.path if winner else "the token-chunked fused CE",
                winner.source if winner else "PERF.md §5 chunk-axis "
                "ordering")
        return LmLossPath(lchunk, tchunk, lpallas, source="explicit")
    if (mesh_shape.get("model", 1) > 1 or mesh_shape.get("pipe", 1) > 1):
        # fused losses don't compose with a vocab-sharded head / the
        # pipelined loss; the monolithic path is the only legal one here
        return LmLossPath(0, 0, source="tp/pipe mesh: monolithic only")
    if fits:
        if winner is not None and winner.path != "monolithic":
            # a measured bounded-memory path BEAT monolithic at a
            # fitting shape — honor the data over the heuristic
            return _loss_path_from_winner(winner)
        return LmLossPath(0, 0, source="monolithic logits fit (est "
                          f"{est / 1e9:.2f} GB/device)")
    if winner is not None and winner.path in ("chunk_tokens",
                                              "chunk_vocab", "pallas"):
        # a monolithic winner is NOT honored here: the estimate says the
        # logits don't fit, and a banked mono row from a smaller shape
        # must not talk a bigger one into an OOM.
        absl_logging.warning(
            "monolithic [B,T,V] logits estimated at %.2f GB/device "
            "(> %d%% of %.0f GB HBM): taking the banked loss-path "
            "winner %s (%s); pass an explicit fused-loss flag to "
            "override", est / 1e9, int(LOGITS_HBM_FRACTION * 100),
            hbm_bytes / 1e9, winner.path, winner.source)
        return _loss_path_from_winner(winner)
    absl_logging.warning(
        "monolithic [B,T,V] logits estimated at %.2f GB/device (> %d%% of "
        "%.0f GB HBM): auto-selecting the token-chunked fused loss "
        "(chunk=%d); pass --loss_chunk_tokens/--loss_chunk_vocab to "
        "override", est / 1e9, int(LOGITS_HBM_FRACTION * 100),
        hbm_bytes / 1e9, AUTO_LOSS_CHUNK_TOKENS)
    return LmLossPath(0, AUTO_LOSS_CHUNK_TOKENS,
                      source="HBM heuristic (no banked winner)")


def _loss_path_from_winner(winner) -> "LmLossPath":
    if winner.path == "chunk_vocab":
        return LmLossPath(winner.chunk or 8192, 0, source=winner.source)
    if winner.path == "chunk_tokens":
        return LmLossPath(0, winner.chunk or AUTO_LOSS_CHUNK_TOKENS,
                          source=winner.source)
    if winner.path == "pallas":
        return LmLossPath(0, 0, pallas=True, source=winner.source)
    return LmLossPath(0, 0, source=winner.source)


def wrap_optimizer(tx, FLAGS):
    """Apply the optimizer-shaping train flags to a base optax transform.

    Today that is ``--clip_grad_norm`` (global-norm clipping BEFORE the
    update, the standard transformer-training guard). Clipping composes
    correctly with grad-accum (it sees the accumulated mean gradient) and
    ZeRO-1 (optax transforms are pointwise over the sharded tree; the
    global norm is computed with psum'd full gradients before sharding).
    """
    import optax

    clip = getattr(FLAGS, "clip_grad_norm", 0.0)
    if clip and clip > 0.0:
        return optax.chain(optax.clip_by_global_norm(clip), tx)
    return tx
