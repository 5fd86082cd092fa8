"""Elastic multi-host training (ISSUE 11): the fake-N-hosts harness, the
dp8→dp4 shrink-resume proof, the run-controller state machine, checkpoint
durability, and the SIGTERM chain — all tier-1 fast, zero cross-process
collectives (the jaxlib blocker docs/RESILIENCE.md engineers around).

The fake twins of the slow-tier multi-process tests live here too: where
those tests exercised the COORDINATION-SERVICE transport (chip-gated now),
these pin the mesh/data-layer half — disjoint per-host shards assembling
into the same global arrays, bitwise — which is the half the CPU sim can
actually prove.
"""

import itertools
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from dtf_tpu.checkpoint import Checkpointer
from dtf_tpu.core import train as tr
from dtf_tpu.core.comms import fake_hosts_to_global, shard_batch
from dtf_tpu.core.mesh import (HostView, MeshConfig, assert_host_aligned,
                               host_views, make_mesh)
from dtf_tpu.data.sharded import FakeHostStream, loaders_for_hosts
from dtf_tpu.data.synthetic import SyntheticData
from dtf_tpu.fault import (ControllerConfig, ControllerPolicy, FaultHook,
                           FaultPlan, HostObservation, RunController,
                           corrupt_latest_checkpoint, read_heartbeat,
                           resume_state, survivor_host_count,
                           survivor_mesh_shape)
from dtf_tpu.fault.inject import InjectedCrash
from dtf_tpu.hooks import CheckpointHook, PreemptionHook, StopAtStepHook
from dtf_tpu.loop import Trainer
from dtf_tpu.telemetry import Telemetry


# ---------------------------------------------------------------------------
# HostView + assembly (the mesh/data harness itself)
# ---------------------------------------------------------------------------

def test_host_view_device_partition(mesh8):
    for n in (1, 2, 4, 8):
        blocks = [v.addressable_devices(mesh8) for v in host_views(n)]
        flat = [d for b in blocks for d in b]
        assert flat == list(mesh8.devices.flat)       # disjoint + covering
        assert all(len(b) == 8 // n for b in blocks)
    with pytest.raises(ValueError, match="not divisible"):
        HostView(0, 3).addressable_devices(mesh8)
    with pytest.raises(ValueError, match="out of range"):
        HostView(2, 2)
    assert HostView(1, 2).batch_rows(16) == (8, 16)
    with pytest.raises(ValueError, match="not divisible"):
        HostView(0, 2).batch_rows(17)


def test_assert_host_aligned(mesh8, mesh_2x2x2):
    assert_host_aligned(mesh8, 4)
    assert_host_aligned(mesh_2x2x2, 2)
    with pytest.raises(ValueError, match="data axis 2"):
        assert_host_aligned(mesh_2x2x2, 4)


def test_fake_hosts_assembly_matches_single_process(mesh8):
    """The harness's core claim: N disjoint per-host shards assemble into
    the byte-identical global array (values AND shardings) single-process
    placement produces — so a step compiled against ``shard_batch``
    placement accepts harness batches without a retrace."""
    loaders = loaders_for_hosts(
        lambda host_index, host_count: SyntheticData(
            "mnist", 16, seed=0, host_index=host_index,
            host_count=host_count),
        host_views(2))
    b0, b1 = loaders[0].batch(0), loaders[1].batch(0)
    got = fake_hosts_to_global([b0, b1], mesh8)
    want = shard_batch({k: np.concatenate([b0[k], b1[k]]) for k in b0},
                       mesh8)
    for k in want:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]))
        assert got[k].sharding == want[k].sharding


def test_fake_hosts_assembly_with_seq_spec(mesh_2x2x2):
    """Sequence-parallel batch specs ride the same assembly: P('data',
    'seq') shards rows across hosts and the seq dim within each host."""
    xs = [{"x": np.arange(2 * 8 * 4, dtype=np.float32
                          ).reshape(2, 8, 4) + 100 * k} for k in range(2)]
    got = fake_hosts_to_global(xs, mesh_2x2x2, spec=P("data", "seq"))
    want = shard_batch({"x": np.concatenate([xs[0]["x"], xs[1]["x"]])},
                       mesh_2x2x2, spec=P("data", "seq"))
    np.testing.assert_array_equal(np.asarray(got["x"]),
                                  np.asarray(want["x"]))
    assert got["x"].sharding == want["x"].sharding


def test_fake_hosts_assembly_rejects_straddling(mesh_2x2x2):
    """data=2 cannot feed 4 hosts: a device's rows would straddle two
    hosts' local arrays — impossible in a real multi-host run, so the
    harness raises instead of silently reading across the boundary."""
    with pytest.raises(ValueError, match="straddle"):
        fake_hosts_to_global(
            [{"x": np.ones((1, 2), np.float32)} for _ in range(4)],
            mesh_2x2x2)


def test_fake_hosts_assembly_rejects_unequal_shares(mesh8):
    with pytest.raises(ValueError, match="equal shares"):
        fake_hosts_to_global([{"x": np.ones((8, 2), np.float32)},
                              {"x": np.ones((4, 2), np.float32)}], mesh8)


def test_fake_host_stream_zips_and_stops():
    loaders = [[{"x": np.full((2,), k * 10 + i)} for i in range(3)]
               for k in range(2)]
    items = list(FakeHostStream(loaders))
    assert len(items) == 3
    assert [float(hb["x"][0]) for hb in items[1]] == [1.0, 11.0]
    with pytest.raises(ValueError):
        FakeHostStream([])


# ---------------------------------------------------------------------------
# Fake twins of the chip-gated multi-process tests (mesh/data layer half)
# ---------------------------------------------------------------------------

def _mnist_losses(n_hosts, *, fake: bool, steps: int = 5):
    """5 mnist softmax steps on a data=n mesh, batches fed either as one
    global loader (the single-process reference) or as n fake hosts."""
    from dtf_tpu.models import mnist

    mesh = make_mesh(MeshConfig(data=n_hosts),
                     devices=jax.devices()[:n_hosts])
    model = mnist.make_model("softmax")
    tx = optax.sgd(0.1)
    state, shardings = tr.create_train_state(
        mnist.make_init(model), tx, jax.random.PRNGKey(0), mesh)
    step = tr.make_train_step(mnist.make_loss(model), tx, mesh, shardings)
    streams = [SyntheticData("mnist", 8 * n_hosts, seed=0, host_index=h,
                             host_count=n_hosts) for h in range(n_hosts)]
    losses = []
    for i in range(steps):
        bs = [s.batch(i) for s in streams]
        if fake:
            batch = fake_hosts_to_global(bs, mesh)
        else:
            batch = shard_batch(
                {k: np.concatenate([b[k] for b in bs]) for k in bs[0]},
                mesh)
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses


@pytest.mark.parametrize("n_hosts", [2, 4])
def test_fake_hosts_training_matches_single_process(n_hosts):
    """Fake twin of test_multiprocess's 2-/4-process loss-parity tests:
    per-host disjoint shards through the harness == the single-process
    run on the concatenated batches, bitwise."""
    np.testing.assert_allclose(_mnist_losses(n_hosts, fake=True),
                               _mnist_losses(n_hosts, fake=False),
                               rtol=0, atol=0)


def test_fake_two_hosts_pipeline_parallel_matches_single_process():
    """Fake twin of the cross-process GPipe test: mesh (data=2, pipe=2),
    stage boundary ppermutes intact, per-host feeding bitwise-equal to
    the global loader."""
    from dtf_tpu.models import gpt, gpt_pipe

    mesh = make_mesh(MeshConfig(data=2, pipe=2), devices=jax.devices()[:4])
    cfg = gpt.GPTConfig.tiny(attn_impl="dense", dtype=jnp.float32)
    init_fn = gpt_pipe.make_pipe_init(cfg, mesh, seq_len=16)
    tx = optax.sgd(0.1)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh,
        param_rules=gpt_pipe.pipe_rules(), zero1=False)
    step = tr.make_train_step(
        gpt_pipe.make_pipe_loss(cfg, mesh, n_microbatches=4), tx, mesh,
        shardings, log_grad_norm=False)
    streams = [SyntheticData("gpt", 16, seed=0, seq_len=16,
                             vocab_size=cfg.vocab_size, host_index=h,
                             host_count=2) for h in range(2)]
    fake_l, ref_l = [], []
    for variant, out in (("fake", fake_l), ("ref", ref_l)):
        # the step donates its state: each variant trains its own copy
        st = jax.tree.map(jnp.copy, state)
        for i in range(3):
            bs = [s.batch(i) for s in streams]
            if variant == "fake":
                batch = fake_hosts_to_global(bs, mesh)
            else:
                batch = shard_batch(
                    {k: np.concatenate([b[k] for b in bs]) for k in bs[0]},
                    mesh)
            st, metrics = step(st, batch)
            out.append(float(metrics["loss"]))
    np.testing.assert_allclose(fake_l, ref_l, rtol=0, atol=0)


def test_fake_two_hosts_bert_tp_zero1_checkpoint_roundtrip(tmp_path):
    """Fake twin of the cross-host TP+ZeRO-1 checkpoint test: train 3
    steps on (data=2, model=2) via the harness, save, restore into a
    FRESH state, continue — losses match the uninterrupted run bitwise."""
    from dtf_tpu.models import bert

    mesh = make_mesh(MeshConfig(data=2, model=2), devices=jax.devices()[:4])
    cfg = bert.BertConfig.tiny()
    model, init_fn = bert.make_init(cfg, None, seq_len=16)
    tx = optax.adam(1e-3)
    state, shardings = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(0), mesh,
        param_rules=bert.tp_rules, zero1=True)
    step = tr.make_train_step(bert.make_loss(model), tx, mesh, shardings)
    streams = [SyntheticData("bert", 8, seed=0, seq_len=16,
                             vocab_size=cfg.vocab_size, host_index=h,
                             host_count=2) for h in range(2)]

    def batch(i):
        return fake_hosts_to_global([s.batch(i) for s in streams], mesh)

    # the step donates its state: the reference run trains its own copy
    ref_state, ref_losses = jax.tree.map(jnp.copy, state), []
    for i in range(5):
        ref_state, m = step(ref_state, batch(i))
        ref_losses.append(float(m["loss"]))

    ckpt = Checkpointer(str(tmp_path / "ck"), async_save=False)
    st = state
    for i in range(3):
        st, m = step(st, batch(i))
    ckpt.save(3, st, force=True)
    ckpt.wait()
    fresh, _ = tr.create_train_state(
        init_fn, tx, jax.random.PRNGKey(7), mesh,
        param_rules=bert.tp_rules, zero1=True)
    restored = ckpt.restore(fresh)
    losses = list(ref_losses[:3])
    for i in (3, 4):
        restored, m = step(restored, batch(i))
        losses.append(float(m["loss"]))
    ckpt.close()
    np.testing.assert_allclose(losses, ref_losses, rtol=0, atol=0)


# ---------------------------------------------------------------------------
# The elastic shrink proof (acceptance): dp8 fake-2-hosts → crash → dp4
# ---------------------------------------------------------------------------

D = 16


def _int_init(rng):
    del rng
    return {"params": {"w": jnp.ones((D, D), jnp.float32),
                       "b": jnp.zeros((D,), jnp.float32)}}


def _int_loss(params, extra, batch, rng):
    del rng
    pred = batch["x"] @ params["w"] + params["b"]
    loss = ((pred - batch["y"]) ** 2).sum() / batch["x"].shape[0]
    return loss, tr.LossAux(extra=extra, metrics={})


def _int_host_batches(step_idx, n_hosts, rows=16):
    """Disjoint per-host shards of a deterministic integer global batch
    (f32 sums of small integers are exact, so dp8 vs dp4 reduction
    grouping cannot produce rounding — the bitwise-parity idiom of
    tests/test_grad_shard.py)."""
    r = np.random.default_rng(step_idx)
    x = r.integers(-3, 4, (rows, D)).astype(np.float32)
    y = r.integers(-3, 4, (rows, D)).astype(np.float32)
    per = rows // n_hosts
    return [{"x": x[k * per:(k + 1) * per], "y": y[k * per:(k + 1) * per]}
            for k in range(n_hosts)]


class _Recorder:
    """Materialize per-step loss/grad-norm (blocking-ok: test code)."""

    telemetry_bucket = "hooks"

    def __init__(self):
        self.rows = {}

    def begin(self, state): ...

    def before_step(self, step): ...

    def after_step(self, step, state, metrics):
        self.rows[step] = {k: float(v) for k, v in metrics.items()}

    def end(self, state): ...


def _dpN_trainer(n_devices, ckpt, hooks, tmp, tag):
    mesh = make_mesh(MeshConfig(data=n_devices),
                     devices=jax.devices()[:n_devices])
    tx = optax.sgd(0.0625)    # 2^-4: keeps the dyadic-exactness window
    state, shardings = tr.create_train_state(
        _int_init, tx, jax.random.PRNGKey(0), mesh)
    tel = Telemetry(out_dir=os.path.join(tmp, f"tel_{tag}"), watchdog=False)
    step = tr.make_train_step(_int_loss, tx, mesh, shardings, telemetry=tel)
    trainer = Trainer(step, mesh, hooks=hooks, checkpointer=ckpt,
                      telemetry=tel)
    return trainer, state, tel


def test_elastic_shrink_dp8_to_dp4_bitwise(tmp_path):
    """The ISSUE 11 acceptance scenario, tier-1 fast: train at dp8 (fake
    2 hosts), lose host 1 at a seeded step (in-process: InjectedCrash —
    the subprocess twin SIGKILLs for real in test_fault_controller.py),
    resume at dp4 from the auto-saved checkpoint, and the continued
    losses/grad-norms match BOTH an uninterrupted dp4-from-checkpoint run
    and the uninterrupted dp8 trajectory, bitwise, with trace_counts
    pinned at {train_step: 1} on every trainer involved."""
    tmp = str(tmp_path)
    ckpt_dir = os.path.join(tmp, "ck")
    views = host_views(2)

    def dp8_batches():
        # fake 2 hosts feed dp8: disjoint 8-row shards assembled per step
        mesh = make_mesh(MeshConfig(data=8))
        for i in itertools.count():
            yield fake_hosts_to_global(_int_host_batches(i, 2), mesh)

    def dp4_batches(start):
        mesh = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
        for i in itertools.count(start):
            yield fake_hosts_to_global(_int_host_batches(i, 1), mesh)

    assert [v.host_index for v in views] == [0, 1]

    # --- uninterrupted dp8 reference (the trajectory truth) -------------
    rec8 = _Recorder()
    trainer8, state8, tel8 = _dpN_trainer(
        8, None, [rec8, StopAtStepHook(10)], tmp, "ref8")
    trainer8.fit(state8, dp8_batches(), max_steps=10)
    assert tel8.trace_counts == {"train_step": 1}

    # --- dp8 run that loses host 1 at step 5 ----------------------------
    ckpt = Checkpointer(ckpt_dir, async_save=False)
    rec_crash = _Recorder()

    class _PeriodicSave:
        """CheckpointHook minus the end-of-run save: a host that DIES
        does not get to save on the way down — only the periodic saves
        that already landed may exist (the SIGKILL reality the
        subprocess twin enforces for real)."""

        telemetry_bucket = "checkpoint"

        def begin(self, state): ...

        def before_step(self, step): ...

        def after_step(self, step, state, metrics):
            if step % 2 == 0:
                ckpt.save(step, state, force=True)

        def end(self, state): ...

    crash_hooks = [
        FaultHook(FaultPlan("crash", 5, host=1), host_index=1),
        rec_crash,
        _PeriodicSave(),
        StopAtStepHook(10),
    ]
    trainer_c, state_c, tel_c = _dpN_trainer(
        8, ckpt, crash_hooks, tmp, "crash")
    with pytest.raises(InjectedCrash):
        trainer_c.fit(state_c, dp8_batches(), max_steps=10)
    ckpt.wait()
    assert tel_c.trace_counts == {"train_step": 1}
    saved = ckpt.latest_step()
    assert saved == 4, f"auto-save should have left step 4, got {saved}"
    # the crash landed in the postmortem (the flight recorder's dump path)
    post = os.path.join(tmp, "tel_crash", "postmortem.json")
    assert "InjectedCrash" in open(post).read()
    ckpt.close()

    # --- controller verdict: host 1 died, survivors relaunch at dp4 -----
    policy = ControllerPolicy()
    d = policy.classify(
        [HostObservation(0, True, None, 0.5),
         HostObservation(1, False, -signal.SIGKILL, None)],
        config=ControllerConfig(), since_launch_s=30.0)
    assert d.kind == "host_lost" and d.dead_hosts == (1,)
    assert policy.shrink(2, 1, config=ControllerConfig(),
                         valid=lambda n: 8 * n // 2 >= 1) == 1

    # --- uninterrupted dp4-from-checkpoint reference --------------------
    ck_ref = Checkpointer(ckpt_dir, async_save=False)
    rec_ref = _Recorder()
    t_ref, s_ref, tel_ref = _dpN_trainer(
        4, ck_ref, [rec_ref, StopAtStepHook(10)], tmp, "ref4")
    t_ref.fit(s_ref, dp4_batches(saved), max_steps=10)
    ck_ref.close()
    assert tel_ref.trace_counts == {"train_step": 1}
    assert sorted(rec_ref.rows) == [5, 6, 7, 8, 9, 10]

    # --- the elastic resume itself (full ceremony, saves re-enabled) ----
    ck_el = Checkpointer(ckpt_dir, async_save=False)
    rec_el = _Recorder()
    t_el, s_el, tel_el = _dpN_trainer(
        4, ck_el, [rec_el, CheckpointHook(ck_el, 2), StopAtStepHook(10)],
        tmp, "elastic")
    final = t_el.fit(s_el, dp4_batches(saved), max_steps=10)
    assert tel_el.trace_counts == {"train_step": 1}
    assert int(final.step) == 10
    assert ck_el.latest_step() == 10
    ck_el.close()

    # --- parity ---------------------------------------------------------
    # THE acceptance bar: the elastic resume is BITWISE identical to the
    # uninterrupted dp4-from-checkpoint run — the relaunch ceremony
    # (resharding restore, controller, re-enabled saves) adds exactly
    # nothing to the numerics.
    for s in rec_el.rows:
        assert rec_el.rows[s] == rec_ref.rows[s], (
            f"elastic vs dp4-reference diverged at step {s}")
    # cross-mesh: the dp4 continuation tracks the uninterrupted dp8
    # trajectory to f32 reduction-grouping tolerance (after a few steps
    # params fill the 24-bit mantissa, so 8-shard vs 4-shard partial-sum
    # grouping may differ in the last ulp — same computation, same data)
    for s in rec_el.rows:
        for k, v in rec_el.rows[s].items():
            np.testing.assert_allclose(v, rec8.rows[s][k], rtol=1e-6,
                                       err_msg=f"step {s} {k}")
    # pre-crash dp8 steps sit on the dp8 trajectory bitwise (same mesh)
    for s in rec_crash.rows:
        assert rec_crash.rows[s] == rec8.rows[s]


def test_resume_state_reshards_onto_smaller_mesh(tmp_path):
    """fault.elastic.resume_state: the standalone resharding restore —
    dp8-written ZeRO-1 state comes back laid out for dp4, values exact,
    resumed step reported."""
    mesh8 = make_mesh(MeshConfig(data=8))
    tx = optax.adam(1e-2)
    state, _ = tr.create_train_state(
        _int_init, tx, jax.random.PRNGKey(0), mesh8)
    state = state.replace(step=jnp.asarray(7, jnp.int32))
    ckpt = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ckpt.save(7, state, force=True)
    ckpt.wait()

    mesh4 = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    restored, shardings, step = resume_state(
        ckpt, _int_init, tx, jax.random.PRNGKey(1), mesh4)
    ckpt.close()
    assert step == 7
    np.testing.assert_array_equal(np.asarray(restored.params["w"]),
                                  np.asarray(state.params["w"]))
    # the adam moments landed in the dp4 ZeRO-1 layout (mesh is dp4)
    mu_w = restored.opt_state[0].mu["w"]
    assert mu_w.sharding.mesh.shape["data"] == 4


def test_survivor_arithmetic():
    from dtf_tpu.fault.elastic import valid_host_counts

    assert survivor_host_count(4, 1) == 3
    with pytest.raises(ValueError):
        survivor_host_count(2, 2)
    with pytest.raises(ValueError):
        survivor_host_count(2, 1, min_hosts=2)
    assert survivor_mesh_shape({"data": 8, "model": 2}, 4, 1) == {
        "data": 6, "model": 2}
    with pytest.raises(ValueError, match="not divisible"):
        survivor_mesh_shape({"data": 6}, 4, 1)
    # every count is mesh-valid by construction; a pinned global batch
    # filters to the survivor data axes that still divide it
    assert valid_host_counts(8, 4) == [1, 2, 3, 4]
    assert valid_host_counts(8, 4, global_batch=16) == [1, 2, 4]
    with pytest.raises(ValueError):
        valid_host_counts(6, 4)


# ---------------------------------------------------------------------------
# SIGTERM chain ordering: flight dump → checkpoint → controller notify
# ---------------------------------------------------------------------------

def test_sigterm_chain_dump_checkpoint_notify_order(tmp_path):
    """ISSUE 11 satellite: a SIGTERM landing INSIDE Checkpointer.save
    (the hard case — the dump handler runs between the save's bytecodes)
    must still produce the full chain in order: flight-recorder dump,
    then the preemption checkpoint made durable, then the controller
    notification; the run exits cleanly at the preempted step."""
    tmp = str(tmp_path)
    events = []
    ckpt = Checkpointer(os.path.join(tmp, "ck"), async_save=False)
    fault = FaultHook(FaultPlan("sigterm_in_save", 3), host_index=0,
                      checkpointer=ckpt, emit=lambda line: None)

    orig_wait = ckpt.wait

    def wait():
        orig_wait()
        events.append("durable")

    ckpt.wait = wait

    rec = _Recorder()
    hooks = [fault, rec, CheckpointHook(ckpt, 3),
             PreemptionHook(ckpt,
                            on_preempt=lambda s: events.append(
                                ("notify", s)))]
    trainer, state, tel = _dpN_trainer(8, None, hooks, tmp, "chain")
    orig_dump = tel.flight.dump

    def dump(reason, extra=None):
        events.append(("dump", reason))
        return orig_dump(reason, extra)

    tel.flight.dump = dump

    def batches():
        mesh = make_mesh(MeshConfig(data=8))
        for i in itertools.count():
            yield fake_hosts_to_global(_int_host_batches(i, 1), mesh)

    final = trainer.fit(state, batches(), max_steps=20)   # exits cleanly
    ckpt.close()
    assert int(final.step) == 3                  # stopped at the fault step
    assert fault.fired
    # the chain, in order: dump strictly before the save went durable,
    # durable strictly before the controller heard about it
    assert ("dump", "sigterm") in events
    i_dump = events.index(("dump", "sigterm"))
    i_durable = next(i for i, e in enumerate(events) if e == "durable")
    i_notify = events.index(("notify", 3))
    assert i_dump < i_durable < i_notify, events
    assert Checkpointer(os.path.join(tmp, "ck")).latest_step() == 3
    post = os.path.join(tmp, "tel_chain", "postmortem.json")
    assert json.loads(open(post).read().splitlines()[0])["reason"] == \
        "sigterm"


def test_plain_sigterm_at_step_boundary_saves_exact_step(tmp_path):
    """The soft case: SIGTERM between steps → PreemptionHook saves the
    exact in-flight step and stops; no postmortem dump needed here (no
    telemetry attached), proving the hook stands alone."""
    tmp = str(tmp_path)
    mesh = make_mesh(MeshConfig(data=8))
    tx = optax.sgd(0.5)
    state, shardings = tr.create_train_state(
        _int_init, tx, jax.random.PRNGKey(0), mesh)
    step = tr.make_train_step(_int_loss, tx, mesh, shardings)
    ckpt = Checkpointer(os.path.join(tmp, "ck"), async_save=False)
    hooks = [FaultHook(FaultPlan("sigterm", 2), host_index=0,
                       emit=lambda line: None),
             PreemptionHook(ckpt)]
    trainer = Trainer(step, mesh, hooks=hooks)

    def batches():
        for i in itertools.count():
            yield fake_hosts_to_global(_int_host_batches(i, 1), mesh)

    final = trainer.fit(state, batches(), max_steps=10)
    assert int(final.step) == 2
    assert ckpt.latest_step() == 2
    ckpt.close()


def test_preemption_hook_without_checkpointer_stops_cleanly():
    """Non-chief fake hosts carry no checkpointer (the chief owns the
    shared dir): SIGTERM must still stop them cleanly, and the optional
    notifier still fires."""
    notified = []
    hook = PreemptionHook(None, on_preempt=notified.append)
    hook.preempted = True
    from dtf_tpu.hooks import StopTraining

    with pytest.raises(StopTraining):
        hook.after_step(5, None, {})
    assert notified == [5]


def test_preemption_notify_suppressed_when_save_fails():
    """The marker means 'step N is durable': a save that failed after
    all retries must NOT notify the controller of a resume point that
    only exists on an older checkpoint — but still stops cleanly."""
    from dtf_tpu.hooks import StopTraining

    class _FailingCkpt:
        def save_durable(self, step, state, **kw):
            return False

    notified = []
    hook = PreemptionHook(_FailingCkpt(), on_preempt=notified.append)
    hook.preempted = True
    with pytest.raises(StopTraining):
        hook.after_step(5, None, {})
    assert notified == []


# ---------------------------------------------------------------------------
# Checkpoint durability (satellite)
# ---------------------------------------------------------------------------

def test_save_durable_retries_transient_failures(tmp_path):
    ckpt = Checkpointer(str(tmp_path / "ck"), async_save=False)
    orig = ckpt._mgr.save
    fails = {"n": 2}

    def flaky(*a, **kw):
        if fails["n"]:
            fails["n"] -= 1
            raise OSError("transient blip")
        return orig(*a, **kw)

    ckpt._mgr.save = flaky
    delays = []
    ok = ckpt.save_durable(3, {"w": jnp.ones((4,))}, retries=3,
                           backoff_s=0.25, sleep=delays.append)
    assert ok
    assert ckpt.latest_step() == 3
    assert delays == [0.25, 0.5]          # exponential backoff
    ckpt.close()


def test_save_durable_gives_up_cleanly_on_previous_checkpoint(
        tmp_path, caplog):
    ckpt = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ckpt.save(1, {"w": jnp.ones((4,))}, force=True)
    ckpt.wait()

    def always_fails(*a, **kw):
        raise OSError("disk on fire")

    ckpt._mgr.save = always_fails
    with caplog.at_level("ERROR", logger="dtf_tpu"):
        ok = ckpt.save_durable(5, {"w": jnp.ones((4,))}, retries=1,
                               backoff_s=0.0, sleep=lambda s: None)
    assert not ok
    assert ckpt.latest_step() == 1         # previous checkpoint intact
    assert any("previous checkpoint" in r.message and "step 1" in r.message
               for r in caplog.records)
    ckpt.close()


def test_restore_falls_back_past_corrupt_newest(tmp_path, caplog):
    """ISSUE 11 satellite: a corrupt/truncated newest checkpoint WARNs
    and falls back to the prior step instead of crashing the relaunch."""
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(d, async_save=False)
    for s in (1, 2):
        ckpt.save(s, {"w": jnp.arange(8.0) * s}, force=True)
    ckpt.wait()
    ckpt.close()
    info = corrupt_latest_checkpoint(d)
    assert info["step"] == 2 and info["files"]

    fresh = Checkpointer(d, async_save=False)
    target = {"w": jnp.zeros((8,))}
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        state, step = fresh.restore_if_exists(target)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(state["w"]),
                                  np.arange(8.0))
    assert any("unreadable" in r.message for r in caplog.records)
    # explicit-step requests get NO fallback — the caller asked for 2
    with pytest.raises(Exception):
        fresh.restore(target, 2)
    fresh.close()


def test_restore_wrong_target_raises_immediately_not_corruption(
        tmp_path, caplog):
    """A WRONG RESTORE TARGET (tree-structure mismatch: the relaunch
    built state for a different model) fails identically on every step —
    it must re-raise as itself at the newest step, NOT walk the history
    and report 'every checkpoint step unreadable'."""
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(d, async_save=False)
    for s in (1, 2):
        ckpt.save(s, {"w": jnp.ones((8,)) * s}, force=True)
    ckpt.wait()
    ckpt.close()
    fresh = Checkpointer(d, async_save=False)
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        # the installed Orbax's wording for a tree-structure mismatch
        with pytest.raises(ValueError,
                           match="tree structures do not match"):
            fresh.restore({"not_w": jnp.zeros((8,))})
    # no fallback walk happened: step 2's failure was terminal
    assert not any("falling back" in r.message for r in caplog.records)
    fresh.close()


def test_restore_params_falls_back_past_corrupt_newest(tmp_path, caplog):
    """ISSUE 12 satellite: serving restore gets PR 11's fallback parity —
    a truncated newest checkpoint WARNs and serves the next older
    readable step's params instead of killing serving startup; an
    explicitly requested step still gets no fallback, and all-corrupt
    fails loudly."""
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(d, async_save=False)
    for s in (1, 2):
        ckpt.save(s, {"params": {"w": jnp.arange(8.0) * s}, "step": s},
                  force=True)
    ckpt.wait()
    ckpt.close()
    info = corrupt_latest_checkpoint(d)
    assert info["step"] == 2 and info["files"]

    fresh = Checkpointer(d, async_save=False)
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        params = fresh.restore_params()
    assert fresh._last_restored_step == 1
    np.testing.assert_array_equal(np.asarray(params["w"]), np.arange(8.0))
    assert any("unreadable" in r.message for r in caplog.records)
    # explicit-step requests get NO fallback — the caller asked for 2
    with pytest.raises(Exception):
        fresh.restore_params(2)
    fresh.close()

    # every step corrupt → loud failure naming the walk
    again = Checkpointer(d, async_save=False)
    for root, _, files in os.walk(os.path.join(d, "1")):
        for name in files:     # damage the remaining readable step too
            p = os.path.join(root, name)
            if os.path.getsize(p) > 0:
                with open(p, "r+b") as f:
                    f.truncate(os.path.getsize(p) // 2)
    with pytest.raises(RuntimeError, match="every checkpoint step"):
        again.restore_params()
    again.close()


def test_restore_params_wrong_target_raises_immediately(tmp_path):
    """A checkpoint with no params subtree (not a TrainState) re-raises
    as itself instead of walking history into a bogus all-corrupt
    story."""
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(d, async_save=False)
    ckpt.save(1, {"w": jnp.ones((4,))}, force=True)   # legacy, no params
    ckpt.wait()
    ckpt.close()
    fresh = Checkpointer(d, async_save=False)
    with pytest.raises(ValueError, match="'params' subtree"):
        fresh.restore_params()
    fresh.close()


def test_restore_all_corrupt_fails_loudly(tmp_path):
    d = str(tmp_path / "ck")
    ckpt = Checkpointer(d, async_save=False)
    ckpt.save(1, {"w": jnp.ones((4,))}, force=True)
    ckpt.wait()
    ckpt.close()
    corrupt_latest_checkpoint(d)
    fresh = Checkpointer(d, async_save=False)
    with pytest.raises(RuntimeError, match="every checkpoint step"):
        fresh.restore({"w": jnp.zeros((4,))})
    fresh.close()


def test_corrupt_latest_checkpoint_requires_steps(tmp_path):
    os.makedirs(tmp_path / "empty", exist_ok=True)
    with pytest.raises(FileNotFoundError):
        corrupt_latest_checkpoint(str(tmp_path / "empty"))
    with pytest.raises(ValueError, match="unknown corruption mode"):
        corrupt_latest_checkpoint(str(tmp_path / "empty"), mode="subtle")


# ---------------------------------------------------------------------------
# Controller state machine + supervision loop (fake processes, fast)
# ---------------------------------------------------------------------------

class _FakeProc:
    """poll() yields the scripted results, repeating the last; terminate/
    kill flip it to a signal exit like a real child would."""

    def __init__(self, polls):
        self._polls = list(polls)
        self._rc = None
        self.pid = 4242
        self.terminated = False

    def poll(self):
        if self._rc is not None:
            return self._rc
        v = self._polls.pop(0) if self._polls else None
        if not self._polls and v is not None:
            self._rc = v
        return v

    def terminate(self):
        self.terminated = True
        self._rc = -signal.SIGTERM

    def kill(self):
        self._rc = -signal.SIGKILL


def _hb_write(path, *, stalled=False, step=1):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump({"t": time.time(), "pid": 1, "step": step,
                   "stalled": stalled}, f)


_FAST = ControllerConfig(max_restarts=3, backoff_base_s=0.001,
                         backoff_max_s=0.01, wedge_timeout_s=60.0,
                         startup_timeout_s=60.0, grace_s=0.05,
                         poll_s=0.001)


def test_policy_classification_matrix():
    p = ControllerPolicy()
    cfg = ControllerConfig()
    alive = HostObservation(0, True, None, 1.0)
    # done / host_lost / wedged(stall) / wedged(stale) / wedged(startup)
    assert p.classify([HostObservation(0, False, 0, None)], config=cfg,
                      since_launch_s=5).kind == "done"
    d = p.classify([alive, HostObservation(1, False, 137, None)],
                   config=cfg, since_launch_s=5)
    assert d.kind == "host_lost" and d.dead_hosts == (1,)
    assert p.classify([HostObservation(0, True, None, 1.0, stalled=True)],
                      config=cfg, since_launch_s=5).kind == "wedged"
    stale = HostObservation(0, True, None, cfg.wedge_timeout_s + 1)
    assert p.classify([stale], config=cfg, since_launch_s=5).kind == \
        "wedged"
    silent = HostObservation(0, True, None, None)
    assert p.classify([silent], config=cfg,
                      since_launch_s=cfg.startup_timeout_s + 1
                      ).kind == "wedged"
    assert p.classify([silent], config=cfg, since_launch_s=5).kind == \
        "running"
    # a host that exited 0 while others still run is NOT a failure
    assert p.classify([HostObservation(0, False, 0, None), alive],
                      config=cfg, since_launch_s=5).kind == "running"
    # backoff growth is exponential and capped
    assert p.backoff_s(0, cfg) == cfg.backoff_base_s
    assert p.backoff_s(1, cfg) == 2 * cfg.backoff_base_s
    assert p.backoff_s(99, cfg) == cfg.backoff_max_s


def test_controller_host_lost_shrinks_and_records_mttr(tmp_path):
    logdir = str(tmp_path)
    hb = lambda h: os.path.join(logdir, f"hb{h}.json")   # noqa: E731
    launches = []

    def launch(n, attempt):
        launches.append(n)
        for h in range(n):
            _hb_write(hb(h))
        if attempt == 0:
            return [_FakeProc([None]), _FakeProc([-signal.SIGKILL])]
        return [_FakeProc([None, None, 0])]

    ctl = RunController(launch, 2, logdir, _FAST, heartbeat_path=hb,
                        valid_hosts=lambda n: n in (1, 2),
                        emit=lambda line: None)
    summary = ctl.run()
    assert summary["final"] == "done"
    assert launches == [2, 1]                   # relaunched SMALLER
    assert summary["restarts"] == 1
    assert summary["causes"] == ["host_lost"]
    assert len(summary["mttr_s"]) == 1 and "mttr_mean_s" in summary
    states = [e.get("state") for e in ctl.events]
    assert "relaunching" in states and "recovered" in states
    # transition lines landed on disk too
    lines = open(os.path.join(logdir, "controller.jsonl")).read()
    assert '"host_lost"' in lines and '"done"' in lines
    # TELEMETRY.json stamping (satellite): restarts + MTTR fields
    art = os.path.join(logdir, "TELEMETRY.json")
    ctl.finish(summary, art, meta={"round": "test"})
    data = json.load(open(art))
    row = data["runs"][-1]
    assert row["telemetry"] == "controller"
    assert row["restarts"] == 1 and row["mttr_s"]


def test_controller_wedged_relaunches_same_size(tmp_path):
    logdir = str(tmp_path)
    hb = lambda h: os.path.join(logdir, f"hb{h}.json")   # noqa: E731
    launches = []

    def launch(n, attempt):
        launches.append(n)
        for h in range(n):
            _hb_write(hb(h), stalled=(attempt == 0 and h == 0))
        if attempt == 0:
            return [_FakeProc([None]), _FakeProc([None])]
        return [_FakeProc([0]), _FakeProc([0])]

    ctl = RunController(launch, 2, logdir, _FAST, heartbeat_path=hb,
                        emit=lambda line: None)
    summary = ctl.run()
    assert summary["final"] == "done"
    assert launches == [2, 2]                   # SAME size after a wedge
    assert summary["causes"] == ["wedged"]
    wedge_ev = next(e for e in ctl.events if e["state"] == "wedged")
    assert "stall watchdog fired" in wedge_ev["reason"]
    # the wedged (alive) hosts were actually stopped
    assert any(e["state"] == "relaunching" for e in ctl.events)


def test_controller_max_restarts_exhaustion_fails_loudly(tmp_path):
    cfg = ControllerConfig(max_restarts=1, backoff_base_s=0.001,
                           grace_s=0.01, poll_s=0.001)
    # every attempt loses its LAST host: 2 → shrink to 1 → budget spent
    ctl = RunController(
        lambda n, a: [_FakeProc([None]) for _ in range(n - 1)]
        + [_FakeProc([1])], 2,
        str(tmp_path), cfg,
        heartbeat_path=lambda h: str(tmp_path / f"hb{h}.json"),
        emit=lambda line: None)
    summary = ctl.run()
    assert summary["final"] == "failed" and summary["cause"] == "host_lost"
    assert summary["restarts"] == 1
    assert summary["causes"] == ["host_lost", "host_lost"]
    fail_ev = next(e for e in ctl.events if e["state"] == "failed")
    assert "max_restarts" in fail_ev["reason"]


def test_controller_no_valid_shrink_fails(tmp_path):
    ctl = RunController(
        lambda n, a: [_FakeProc([None]), _FakeProc([9])], 2,
        str(tmp_path), _FAST,
        heartbeat_path=lambda h: str(tmp_path / f"hb{h}.json"),
        valid_hosts=lambda n: n == 2,          # nothing smaller is legal
        emit=lambda line: None)
    summary = ctl.run()
    assert summary["final"] == "failed"
    assert any("no valid survivor" in e.get("reason", "")
               for e in ctl.events)


def test_stale_heartbeat_from_previous_attempt_is_ignored(tmp_path):
    """A pre-relaunch heartbeat (possibly stalled:true) must not
    instantly re-trigger the wedge verdict on the fresh attempt."""
    logdir = str(tmp_path)
    hb = lambda h: os.path.join(logdir, f"hb{h}.json")   # noqa: E731

    def launch(n, attempt):
        if attempt == 0:
            _hb_write(hb(0), stalled=True)       # wedge, left on disk
            return [_FakeProc([None])]
        # attempt 1 writes NO heartbeat: the stale stalled=true file must
        # read as absent (startup grace), and the proc finishes cleanly
        return [_FakeProc([None, 0])]

    ctl = RunController(launch, 1, logdir, _FAST, heartbeat_path=hb,
                        emit=lambda line: None)
    summary = ctl.run()
    assert summary["final"] == "done"
    assert summary["causes"] == ["wedged"]       # exactly one wedge


def test_read_heartbeat_tolerates_garbage(tmp_path):
    p = str(tmp_path / "hb.json")
    assert read_heartbeat(p) is None
    with open(p, "w") as f:
        f.write("{torn")
    assert read_heartbeat(p) is None
    _hb_write(p, step=42)
    assert read_heartbeat(p)["step"] == 42


def test_watchdog_writes_heartbeat_with_stall_flag(tmp_path):
    """The telemetry side of the controller contract: the stall
    watchdog's poll thread writes liveness with the stalled flag, and a
    wedged loop keeps heartbeating stalled=true."""
    from dtf_tpu.telemetry.flight import FlightRecorder, StallWatchdog

    hb_path = str(tmp_path / "hb.json")
    t = {"now": 100.0}
    flight = FlightRecorder(heartbeat_path=hb_path,
                            clock=lambda: t["now"], wall=lambda: t["now"])
    dog = StallWatchdog(flight, factor=2.0, min_stall_s=5.0)
    flight.record_step(1, {"step_s": 0.1})
    flight.write_heartbeat(stalled=dog.stalled_now())
    hb = read_heartbeat(hb_path)
    assert hb == {"t": 100.0, "pid": os.getpid(), "step": 1,
                  "stalled": False}
    t["now"] += 60.0                      # nothing completes for 60 s
    assert dog.check()                    # stall fired
    flight.write_heartbeat(stalled=dog.stalled_now())
    assert read_heartbeat(hb_path)["stalled"] is True
    flight.record_step(2, {"step_s": 0.1})   # a step completes: re-armed
    flight.write_heartbeat(stalled=dog.stalled_now())
    assert read_heartbeat(hb_path) == {"t": 160.0, "pid": os.getpid(),
                                       "step": 2, "stalled": False}


# ---------------------------------------------------------------------------
# Fault-plan parsing + fit --hosts/--lost (satellites)
# ---------------------------------------------------------------------------

def test_fault_plan_parsing():
    assert FaultPlan.parse("kill@12:host=1") == FaultPlan("kill", 12, 1)
    assert FaultPlan.parse("wedge@7") == FaultPlan("wedge", 7, None)
    assert FaultPlan.from_env({}) is None
    assert FaultPlan.from_env({"DTF_FAULT_INJECT": "sigterm@5"}) == \
        FaultPlan("sigterm", 5, None)
    assert FaultPlan("kill", 3, 1).applies_to(1)
    assert not FaultPlan("kill", 3, 1).applies_to(0)
    assert FaultPlan("kill", 3, None).applies_to(7)
    for bad in ("kill", "melt@3", "kill@-1", "kill@3:chip=1"):
        with pytest.raises(ValueError):
            FaultPlan.parse(bad)


def test_fit_prices_survivor_mesh(tmp_path):
    """ISSUE 11 satellite: `analysis fit --hosts=N --lost=K` reports
    whether the survivor mesh still fits resident state + temp at the
    same global batch — the shrink decision pre-priced."""
    from dtf_tpu.analysis import memory as memory_pass

    out = memory_pass.fit("mnist", hbm_gb=0.001, hosts=2, lost=1)
    assert out["kind"] == "train_shrink"
    assert out["survivor_mesh"]["data"] == 4
    assert out["full"]["mesh"]["data"] == 8
    assert out["survivor"]["mesh"]["data"] == 4
    assert out["full"]["global_batch"] == out["survivor"]["global_batch"]
    # fewer devices, same global batch: per-device demand must GROW
    assert (out["survivor"]["hbm_needed_bytes_at_batch"]
            > out["full"]["hbm_needed_bytes_at_batch"])
    assert out["survivor_fits_same_batch"] == \
        out["survivor"]["fits_at_batch"]
    # and a budget that fits the tiny program reports True
    assert memory_pass.fit("mnist", hbm_gb=1.0, hosts=2,
                           lost=1)["survivor_fits_same_batch"]
    with pytest.raises(ValueError):
        memory_pass.fit("mnist", hbm_gb=1.0, hosts=2, lost=2)
    with pytest.raises(ValueError, match="serve"):
        memory_pass.fit("gpt_serve", hbm_gb=1.0, hosts=2, lost=1)
