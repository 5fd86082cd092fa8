"""Launch glue: flags → cluster collapse → mesh → trainer pieces.

This is where the reference's L5/L6 (flag parse → ClusterSpec → Server →
ps join / worker build) becomes: parse the same flags, collapse roles,
``jax.distributed`` bootstrap when multi-process, build the mesh, hand the
script a ready (mesh, cluster_info) pair. See SURVEY.md §7 "Hard parts" #1.
"""

from __future__ import annotations

import logging
import os
import sys

import jax

from dtf_tpu.core import dist
from dtf_tpu.core.mesh import MeshConfig, make_mesh, mesh_summary

log = logging.getLogger("dtf_tpu")

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Place JAX's persistent compile cache. ``JAX_COMPILATION_CACHE_DIR``
    decides when it is set (JAX reads it itself; nothing is set in code);
    otherwise the cache is ``<checkout>/.jax_cache`` — a fixed path,
    because the path is part of the cache key. Every launcher, bench.py,
    chip_smoke.py and tests/conftest.py call this, so processes of one
    run share what they compile."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(_CHECKOUT, ".jax_cache"))


def init_backend(backend: str, info=None):
    """Bring JAX up on the platform ``--backend`` names, or fail.

    ``cpu`` pins the simulated mesh; anything else must be what JAX
    actually found — a launcher asked for the TPU never trains on a CPU
    that JAX fell back to. With ``info`` (a multi-worker launch) the
    distributed bootstrap runs in between, before the first device
    query, and the possibly-updated info is returned.
    """
    enable_compile_cache()
    if backend == "cpu":
        # Local-sim path: the test/dev equivalent of a multi-worker cluster.
        jax.config.update("jax_platforms", "cpu")
    if info is not None:
        info = dist.initialize_or_fake(info, backend)
    platform = jax.devices()[0].platform
    if platform != backend:
        raise RuntimeError(
            f"--backend={backend} but JAX came up on {platform!r} "
            f"({jax.devices()[0].device_kind}): refusing to run on a "
            f"device that was not asked for (pass --backend=cpu for the "
            f"simulated mesh)")
    return info


def device_report() -> dict:
    """What the process ran on, as JAX reports it, for every result a
    launcher prints: a number without its device is not a measurement.
    ``peak_hbm_bytes`` where the backend keeps memory statistics."""
    dev = jax.devices()[0]
    out = {"device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices())}}
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    if peak is not None:
        out["peak_hbm_bytes"] = int(peak)
    return out


def setup(FLAGS):
    """Resolve cluster + mesh from parsed absl FLAGS.

    Returns ``(mesh, info)``. For ``--job_name=ps`` this exits the process
    with status 0 — the TPU-native successor of ``server.join()`` (the PS
    role's state lives sharded on the mesh; the process has nothing to do).

    Multi-worker launches are CHIP-GATED (``dist.initialize_or_fake``):
    true ``jax.distributed.initialize`` on the tpu backend, the fake-hosts
    harness on cpu (this jaxlib refuses cross-process CPU collectives —
    docs/RESILIENCE.md). In fake mode ``--devices_per_host`` sizes each
    host's share of the simulated mesh, so an elastic relaunch with fewer
    workers re-forms a smaller mesh and resumes by resharding.
    """
    info = dist.collapse_cluster_flags(
        ps_hosts=[h for h in FLAGS.ps_hosts.split(",") if h],
        worker_hosts=[h for h in FLAGS.worker_hosts.split(",") if h],
        job_name=FLAGS.job_name,
        task_index=FLAGS.task_index,
    )
    if info.should_exit:
        log.warning("ps role has no work on the %s backend; exiting 0",
                    FLAGS.backend)
        sys.exit(0)
    if not FLAGS.issync:
        log.warning(
            "--issync=0 (async PS SGD) is not reproduced on the TPU backend: "
            "hogwild updates are an anti-pattern under SPMD. Proceeding with "
            "synchronous aggregation (same convergence, no stale gradients).")
    info = init_backend(FLAGS.backend, info)
    devices = None
    dph = getattr(FLAGS, "devices_per_host", 0)
    # cpu only (a real chip's devices are what they are): sizes the
    # simulated cluster — including the 1-worker SURVIVOR relaunch after
    # an elastic shrink, whose mesh must span dph x 1 devices, not every
    # local device.
    if dph and FLAGS.backend == "cpu":
        want = dph * info.num_processes
        have = len(jax.devices())
        if want > have:
            raise ValueError(
                f"--devices_per_host={dph} x {info.num_processes} workers "
                f"= {want} mesh devices, but only {have} simulated devices "
                f"exist (raise --xla_force_host_platform_device_count)")
        devices = jax.devices()[:want]
    mesh = make_mesh(MeshConfig(
        data=FLAGS.mesh_data, seq=FLAGS.mesh_seq, model=FLAGS.mesh_model,
        pipe=FLAGS.mesh_pipe, expert=FLAGS.mesh_expert), devices=devices)
    if info.num_processes > 1:
        from dtf_tpu.core.mesh import assert_host_aligned

        assert_host_aligned(mesh, info.num_processes)
    if info.is_chief:
        log.info("%s | %d process(es), chief=%s fake_hosts=%s",
                 mesh_summary(mesh), info.num_processes, info.is_chief,
                 info.fake_hosts)
    return mesh, info


def host_batches(info, mesh, make_loader):
    """The one data-dispatch for every launch shape.

    ``make_loader(host_index=, host_count=)`` builds one host's loader
    (the kwargs every array loader and ``SyntheticData`` already takes).
    Returns ``(batches, place_batch)`` for the Trainer:

    - single process        → one global loader, default placement;
    - real multi-process    → this process's 1/N loader,
      ``comms.host_local_to_global`` placement (each host contributes its
      addressable shards);
    - fake hosts (cpu sim)  → a ``FakeHostStream`` over ALL N per-host
      loaders + ``comms.fake_hosts_to_global`` placement — the same
      disjoint-rows contract, exercised end to end inside one process.
    """
    from dtf_tpu.core.comms import fake_hosts_to_global, host_local_to_global
    from dtf_tpu.core.mesh import host_views
    from dtf_tpu.data.sharded import FakeHostStream, loaders_for_hosts

    if info.num_processes <= 1:
        return iter(make_loader(host_index=0, host_count=1)), None
    if info.fake_hosts:
        loaders = loaders_for_hosts(make_loader,
                                    host_views(info.num_processes))
        return (iter(FakeHostStream(loaders)),
                lambda hb: fake_hosts_to_global(hb, mesh))
    loader = make_loader(host_index=info.process_id,
                         host_count=info.num_processes)
    return iter(loader), lambda b: host_local_to_global(b, mesh)


def lm_eval_hook(FLAGS, info, mesh, shardings, eval_fn, writer, place_batch,
                 *, kind, mode, vocab_size, batch_shardings=None,
                 telemetry=None):
    """EvalHook for the LM launchers — the one copy of the eval policy.

    Held-out source: ``<data_dir>/val.bin`` when present; a synthetic
    stream at seed+1 ONLY when training itself is synthetic. Training on
    real tokens with no val split returns None (skip eval) with a warning —
    scoring a real model on unrelated synthetic data would masquerade as
    held-out perplexity (same policy as the image path's
    ``detect_image_eval_data``). Sweep = 4 batches. ``batch_shardings``
    must be the same override the train step uses when sequence
    parallelism places batches P('data','seq').
    """
    from dtf_tpu.core import train as tr
    from dtf_tpu.data import formats
    from dtf_tpu.data.synthetic import SyntheticData
    from dtf_tpu.hooks import EvalHook

    eval_data = formats.detect_token_data(
        FLAGS.data_dir, FLAGS.batch_size, FLAGS.seq_len, mode=mode,
        vocab_size=vocab_size, seed=FLAGS.seed, split="val",
        host_index=info.process_id, host_count=info.num_processes)
    if eval_data is not None:
        batches_fn = lambda: (eval_data.batch(i) for i in range(4))  # noqa: E731,E501
    else:
        from dtf_tpu.data.formats import TokenBinData

        if FLAGS.data_dir and TokenBinData.available(FLAGS.data_dir):
            log.warning("no val.bin in %s; skipping held-out eval rather "
                        "than scoring on synthetic data", FLAGS.data_dir)
            return None
        held_out = SyntheticData(
            kind, FLAGS.batch_size, seed=FLAGS.seed + 1,
            seq_len=FLAGS.seq_len, vocab_size=vocab_size,
            host_index=info.process_id, host_count=info.num_processes)
        batches_fn = lambda: (held_out.batch(10_000_000 + i)  # noqa: E731
                              for i in range(4))
    step = tr.make_eval_step(eval_fn, mesh, shardings,
                             batch_shardings=batch_shardings,
                             telemetry=telemetry)
    return EvalHook(step, batches_fn, writer,
                    FLAGS.eval_every or FLAGS.train_steps,
                    place_batch=place_batch)


def profiler_hooks(FLAGS, telemetry=None, flops_per_step=None):
    """[ProfilerHook] from the profiler flags, or [].

    ``--profile_steps`` schedules the classic fixed window; independently,
    ``--profile_on_demand`` (default on) arms the live triggers — SIGUSR1
    or ``touch <logdir>/profile.trigger`` — so a misbehaving run can be
    profiled without restarting with a pre-chosen step window. One hook
    serves both modes (dtf_tpu/hooks.py ProfilerHook docstring).

    Every closed window is parsed into ``<logdir>/profile/
    device_profile.json`` (per-category device-time buckets, comm/compute
    overlap) by the hook's analyze path; ``telemetry`` +
    ``flops_per_step`` additionally put the device-MFU cross-check in the
    RunReport (docs/OBSERVABILITY.md, device-time attribution).
    """
    import os
    import signal as _signal

    scheduled = getattr(FLAGS, "profile_steps", 0)
    on_demand = getattr(FLAGS, "profile_on_demand", False)
    if not scheduled and not on_demand:
        return []

    from dtf_tpu.hooks import ProfilerHook

    return [ProfilerHook(
        os.path.join(FLAGS.logdir, "profile"),
        start_step=FLAGS.profile_start if scheduled else None,
        num_steps=scheduled or 5,
        trigger_file=(os.path.join(FLAGS.logdir, "profile.trigger")
                      if on_demand else None),
        trigger_signal=(getattr(_signal, "SIGUSR1", None)
                        if on_demand else None),
        telemetry=telemetry, flops_per_step=flops_per_step)]


def telemetry_from_flags(FLAGS, info):
    """``--telemetry`` → a configured :class:`dtf_tpu.telemetry.Telemetry`
    (or None). Built on every host — each host keeps its own flight
    recorder (postmortems are per-process facts: the host that hangs is
    the one whose last steps matter) — while :func:`emit_run_report`
    prints only on the chief."""
    if not getattr(FLAGS, "telemetry", False):
        return None
    import os

    import jax

    from dtf_tpu.telemetry import Telemetry

    min_stall = getattr(FLAGS, "telemetry_min_stall_s", 60.0)
    out_dir = os.path.join(FLAGS.logdir, "telemetry")
    if info.num_processes > 1:
        out_dir = os.path.join(out_dir, f"p{info.process_id}")
    return Telemetry(
        out_dir=out_dir,
        keep_steps=getattr(FLAGS, "telemetry_keep_steps", 64),
        stall_factor=getattr(FLAGS, "telemetry_stall_factor", 10.0),
        min_stall_s=min_stall or 60.0,
        watchdog=bool(min_stall),
        # global-batch FLOPs vs ALL chips' peak (mfu would otherwise be
        # overstated by exactly the device count on any multi-chip mesh)
        n_devices=jax.device_count())


def emit_run_report(tel, info, extra=None):
    """Finish the run's telemetry and print THE one RunReport JSON line
    (bench.py idiom; chief only). Returns the report dict (all hosts)."""
    if tel is None:
        return None
    import json

    report = tel.finish({**device_report(), **(extra or {})})
    if info.is_chief:
        print(json.dumps(report))
    return report
