"""Streaming data tier (ISSUE 15): the weighted-mixture stream's whole
contract — byte-identical checkpointed resume (same-size AND dp8→dp4
shrink re-partition), mixture-fraction convergence at fixed seed, live
reweighting at a named step, corrupt-record skip-with-WARN, the stream
fault verbs, Checkpointer extra items, and the zero-added-readbacks proof
for the producer + prefetch path. The slow tier closes the full online
loop through the real launchers: stream → train (killed and resumed, with
a stall verb riding the resume) → publish → rolling swap → serve.
"""

import itertools
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from dtf_tpu.checkpoint import Checkpointer
from dtf_tpu.data.stream import (MixtureStream, StreamCheckpointHook,
                                 TFRecordSource, TokenBinSource,
                                 build_stream, parse_stream_spec,
                                 resolve_stream_spec)
from dtf_tpu.fault.inject import (FaultPlan, ServeFaultPlan,
                                  StreamFaultPlan, maybe_stream_fault)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V = 97          # tiny vocab for every corpus in this file
SEQ = 16


def _write_bin(path, seed, n=6000):
    r = np.random.default_rng(seed)
    r.integers(0, V, n).astype(np.uint16).tofile(path)


def _sources(d, seed=0):
    return [TokenBinSource(os.path.join(d, "a.bin"), SEQ, vocab_size=V,
                           seed=seed, salt=0, name="a"),
            TokenBinSource(os.path.join(d, "b.bin"), SEQ, vocab_size=V,
                           seed=seed, salt=1, name="b")]


@pytest.fixture()
def corpus(tmp_path):
    d = str(tmp_path)
    _write_bin(os.path.join(d, "a.bin"), 1)
    _write_bin(os.path.join(d, "b.bin"), 2)
    return d


def _stream(d, *, host_view=None, depth=0, weights=None, seed=3):
    return MixtureStream(_sources(d), weights or {"a": 0.7, "b": 0.3}, 16,
                         seed=seed, host_view=host_view,
                         producer_depth=depth)


def _batches_equal(a, b):
    assert set(a) == set(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])


# ---------------------------------------------------------------------------
# cursor hooks on the existing readers
# ---------------------------------------------------------------------------

def test_token_bin_example_hook_deterministic_and_host_free(corpus):
    from dtf_tpu.data.formats import TokenBinData

    kw = dict(vocab_size=V, seed=5)
    d0 = TokenBinData(os.path.join(corpus, "a.bin"), 8, SEQ,
                      host_index=0, host_count=2, **kw)
    d1 = TokenBinData(os.path.join(corpus, "a.bin"), 8, SEQ,
                      host_index=1, host_count=2, **kw)
    for i in (0, 7, 12345):
        _batches_equal(d0.example(i), d1.example(i))   # host-free
        _batches_equal(d0.example(i), d0.example(i))   # stateless
    assert d0.example(0)["input_ids"].shape == (SEQ,)
    # distinct indices draw distinct windows (overwhelmingly)
    assert not np.array_equal(d0.example(0)["input_ids"],
                              d0.example(1)["input_ids"])
    # the mlm mode rides the same cursor with the BERT schema
    m = TokenBinData(os.path.join(corpus, "a.bin"), 8, SEQ, mode="mlm",
                     **kw).example(3)
    assert set(m) == {"input_ids", "segment_ids", "attention_mask",
                      "mlm_labels"}


@pytest.mark.skipif(
    not __import__("dtf_tpu.data.native", fromlist=["x"]).native_available(),
    reason="no C++ toolchain")
def test_native_idx_cursor_seek_replays(tmp_path):
    from dtf_tpu.data.mnist import write_idx
    from dtf_tpu.data.native import NativeIdxData

    r = np.random.RandomState(0)
    ip = str(tmp_path / "im"), str(tmp_path / "lb")
    write_idx(ip[0], r.randint(0, 256, (64, 4, 4)).astype(np.uint8))
    write_idx(ip[1], r.randint(0, 10, (64,)).astype(np.uint8))
    ref = NativeIdxData(ip[0], ip[1], 8, seed=1)
    consumed = [ref.next_batch() for _ in range(5)]
    assert ref.batches_consumed == 5
    fresh = NativeIdxData(ip[0], ip[1], 8, seed=1)
    fresh.seek(3)
    _batches_equal(fresh.next_batch(), consumed[3])
    with pytest.raises(ValueError, match="backwards"):
        fresh.seek(1)
    ref.close()
    fresh.close()


# ---------------------------------------------------------------------------
# mixture semantics
# ---------------------------------------------------------------------------

def test_mixture_fractions_converge_at_fixed_seed(corpus):
    st = _stream(corpus)
    for i in range(80):
        st.produce(i)
    stats = st.stats()
    assert abs(stats["per_source"]["a"]["realized_frac"] - 0.7) < 0.05
    assert abs(stats["per_source"]["b"]["realized_frac"] - 0.3) < 0.05
    assert stats["per_source"]["a"]["target_frac"] == 0.7
    # cursors sum to every example drawn
    assert sum(s["cursor"] for s in stats["per_source"].values()) == 80 * 16


def test_mixture_reweight_takes_effect_at_named_step(corpus):
    st = _stream(corpus)
    st.reweight(10, {"a": 1, "b": 9})
    for i in range(10):
        st.produce(i)
    before = st.stats()["per_source"]["b"]["examples"]
    for i in range(10, 60):
        st.produce(i)
    after_frac = (st.stats()["per_source"]["b"]["examples"] - before) / (
        50 * 16)
    assert abs(after_frac - 0.9) < 0.05
    # recorded in the state, effective step included
    assert [10, {"a": 0.1, "b": 0.9}] in st.state()["schedule"]
    # history cannot be rewritten
    with pytest.raises(ValueError, match="rewrite history"):
        st.reweight(5, {"a": 1, "b": 1})
    # a reweighted stream restored elsewhere replays the SAME mix
    st2 = _stream(corpus)
    st2.restore(st.state_at(30))
    _batches_equal(st2.produce(30), _replay(corpus, 31)[30])


def _replay(corpus, n_steps, **kw):
    """Uninterrupted reference batches 0..n_steps-1 (fresh stream)."""
    st = _stream(corpus, **kw)
    st.reweight(10, {"a": 1, "b": 9})
    return [st.produce(i) for i in range(n_steps)]


def test_mixture_schema_mismatch_rejected(corpus):
    from dtf_tpu.data.stream.sources import TokenBinSource as TBS

    srcs = [TBS(os.path.join(corpus, "a.bin"), SEQ, vocab_size=V, name="a"),
            TBS(os.path.join(corpus, "b.bin"), SEQ + 2, vocab_size=V,
                name="b")]
    with pytest.raises(ValueError, match="schema|field"):
        MixtureStream(srcs, {"a": 1, "b": 1}, 16)


# ---------------------------------------------------------------------------
# the headline: byte-identical checkpointed resume
# ---------------------------------------------------------------------------

def test_bitwise_resume_same_size(corpus):
    """Kill at N, restore the StreamState, continue: batches N..M are
    byte-identical to the uninterrupted run's."""
    ref = [b for b in itertools.islice(iter(_stream(corpus)), 12)]
    st = _stream(corpus)
    for i in range(5):
        st.produce(i)
    saved = st.state_at(5)          # the checkpoint's view of step 5
    del st                          # the "kill"
    resumed = _stream(corpus)
    resumed.restore(saved)
    for i in range(5, 12):
        _batches_equal(resumed.produce(i), ref[i])


def test_bitwise_resume_with_producer_lookahead(corpus):
    """state_at(step) must describe the TRAINED step even while the
    background producer has run ahead — the saved cursors exclude staged
    batches, and the resume replays them."""
    import time

    ref = [b for b in itertools.islice(iter(_stream(corpus)), 10)]
    st = _stream(corpus, depth=3)
    it = iter(st)
    for i in range(4):               # consumer took 4; producer runs ahead
        _batches_equal(next(it), ref[i])
    deadline = time.perf_counter() + 5.0
    while st.next_step <= 4 and time.perf_counter() < deadline:
        time.sleep(0.01)             # let the producer stage its lookahead
    assert st.next_step > 4          # lookahead actually happened
    saved = st.state_at(4)
    st.close()
    resumed = _stream(corpus, depth=3)
    resumed.restore(saved)
    it2 = iter(resumed)
    for i in range(4, 10):
        _batches_equal(next(it2), ref[i])
    resumed.close()


def test_resume_validates_stream_identity(corpus):
    st = _stream(corpus)
    saved = st.state_at(0)
    for bad, match in (
            (dict(saved, seed=99), "seed"),
            (dict(saved, global_batch=32), "global_batch"),
            (dict(saved, cursors={"a": 0, "zz": 0}), "spec changed"),
            (dict(saved, version=99), "version")):
        with pytest.raises(ValueError, match=match):
            _stream(corpus).restore(bad)


def test_shrink_resume_repartitions_cursors_dp8_to_dp4(corpus, mesh8):
    """The PR 11 shrink path: 2 fake hosts feed dp8; the survivor feeds
    dp4 alone from the SAME StreamState — per-host cursors are a row
    slice of global state, so the re-partition is free and the global
    sequence is byte-identical."""
    import jax

    from dtf_tpu.core.comms import fake_hosts_to_global, shard_batch
    from dtf_tpu.core.mesh import HostView, MeshConfig, make_mesh

    ref = [b for b in itertools.islice(iter(_stream(corpus)), 8)]

    h0 = _stream(corpus, host_view=HostView(0, 2))
    h1 = _stream(corpus, host_view=HostView(1, 2))
    for i in range(5):
        b0, b1 = h0.produce(i), h1.produce(i)
        # disjoint per-host rows concatenate to the global batch
        _batches_equal({k: np.concatenate([b0[k], b1[k]]) for k in b0},
                       ref[i])
        if i == 0:
            # and they assemble onto the mesh exactly like single-process
            # placement (the FakeHostStream/fake_hosts_to_global seam)
            got = fake_hosts_to_global([b0, b1], mesh8)
            want = shard_batch(ref[0], mesh8)
            for k in want:
                np.testing.assert_array_equal(np.asarray(got[k]),
                                              np.asarray(want[k]))
                assert got[k].sharding == want[k].sharding
    # both fake hosts hold the identical (global) state — the property
    # that lets ANY survivor subset resume
    assert h0.state_at(5) == h1.state_at(5)
    saved = h0.state_at(5)

    survivor = _stream(corpus)            # 1 host now covers all rows
    survivor.restore(saved)
    mesh4 = make_mesh(MeshConfig(data=4), devices=jax.devices()[:4])
    for i in range(5, 8):
        got = survivor.produce(i)
        _batches_equal(got, ref[i])
        shard_batch(got, mesh4)           # places cleanly on the dp4 mesh


def test_trainer_kill_resume_bitwise_losses(corpus, mesh8, tmp_path):
    """End to end through the real Trainer/Checkpointer: crash at step 3,
    relaunch with restore-if-exists + StreamCheckpointHook — continued
    losses AND the host batches fed to the mesh are bitwise identical to
    the uninterrupted run's."""
    import jax.numpy as jnp
    import optax

    from dtf_tpu.core import train as tr
    from dtf_tpu.core.comms import shard_batch
    from dtf_tpu.fault import FaultHook
    from dtf_tpu.fault.inject import InjectedCrash
    from dtf_tpu.hooks import CheckpointHook, StopAtStepHook
    from dtf_tpu.loop import Trainer

    def init(rng):
        del rng
        emb = jnp.linspace(-1.0, 1.0, V * 8,
                           dtype=jnp.float32).reshape(V, 8)
        return {"params": {"emb": emb}}

    def loss_fn(params, extra, batch, rng):
        del rng
        x = params["emb"][batch["input_ids"]]
        y = params["emb"][batch["labels"]]
        return ((x - y) ** 2).mean(), tr.LossAux(extra=extra, metrics={})

    tx = optax.sgd(0.0625)

    def trainer_for(ckpt, hooks, captured):
        import jax

        state, shardings = tr.create_train_state(
            init, tx, jax.random.PRNGKey(0), mesh8)
        step = tr.make_train_step(loss_fn, tx, mesh8, shardings)

        def place(b):
            captured.append({k: v.copy() for k, v in b.items()})
            return shard_batch(b, mesh8)

        return Trainer(step, mesh8, hooks=hooks, checkpointer=ckpt,
                       place_batch=place), state

    class Rec:
        telemetry_bucket = "hooks"

        def __init__(self):
            self.rows = {}

        def begin(self, state): ...

        def before_step(self, step): ...

        def after_step(self, step, state, metrics):
            self.rows[step] = {k: float(v) for k, v in metrics.items()}

        def end(self, state): ...

    # uninterrupted reference
    rec_ref, cap_ref = Rec(), []
    t_ref, s_ref = trainer_for(None, [rec_ref, StopAtStepHook(6)], cap_ref)
    t_ref.fit(s_ref, iter(_stream(corpus)), max_steps=6)

    # crash at 3 (checkpoint at 2 carries the stream item). Periodic
    # saves only — a host that DIES does not get to save on the way down
    # (the test_elastic _PeriodicSave idiom; fit's finally still runs end
    # hooks for an in-process crash, which a SIGKILL never would).
    ckdir = str(tmp_path / "ck")
    ck = Checkpointer(ckdir, async_save=False)
    st1 = _stream(corpus)

    class PeriodicSave:
        telemetry_bucket = "checkpoint"

        def begin(self, state): ...

        def before_step(self, step): ...

        def after_step(self, step, state, metrics):
            if step % 2 == 0:
                ck.save(step, state, force=True)

        def end(self, state): ...

    rec1, cap1 = Rec(), []
    t1, s1 = trainer_for(ck, [
        FaultHook(FaultPlan("crash", 3), emit=lambda line: None),
        rec1, StreamCheckpointHook(ck, st1), PeriodicSave(),
        StopAtStepHook(6)], cap1)
    with pytest.raises(InjectedCrash):
        t1.fit(s1, iter(st1), max_steps=6)
    assert ck.latest_step() == 2
    assert os.path.isdir(os.path.join(ckdir, "2", "stream"))
    ck.close()

    # relaunch: restore-if-exists + stream restore, continue to 6
    ck2 = Checkpointer(ckdir, async_save=False)
    st2 = _stream(corpus)
    rec2, cap2 = Rec(), []
    t2, s2 = trainer_for(ck2, [
        rec2, StreamCheckpointHook(ck2, st2), CheckpointHook(ck2, 2),
        StopAtStepHook(6)], cap2)
    final = t2.fit(s2, iter(st2), max_steps=6)
    assert int(final.step) == 6
    ck2.close()

    # losses bitwise on the continued steps, and pre-crash steps too
    for s in rec2.rows:
        assert rec2.rows[s] == rec_ref.rows[s], f"diverged at step {s}"
    for s in rec1.rows:
        assert rec1.rows[s] == rec_ref.rows[s]
    # the fed host batches: resume consumed exactly batches 2..5,
    # byte-identical to the reference's
    assert len(cap2) == 4
    for got, want in zip(cap2, cap_ref[2:6]):
        _batches_equal(got, want)


def test_stream_checkpoint_hook_legacy_seek(corpus, tmp_path, caplog):
    """A checkpoint saved BEFORE the stream existed restores with a WARN
    and the stream fast-forwards by replaying its draws — same batches as
    a saved-state resume when the spec is unchanged."""
    import jax.numpy as jnp

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    ck.save(4, {"params": {"w": jnp.ones((4,))}, "step": 4}, force=True)
    ck.wait()
    ck._last_restored_step = 4          # as restore_if_exists would set
    st = _stream(corpus)
    hook = StreamCheckpointHook(ck, st)
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        hook.begin(None)
    assert any("no stream state" in r.message for r in caplog.records)
    assert st.next_step == 4
    ref = [b for b in itertools.islice(iter(_stream(corpus)), 6)]
    _batches_equal(st.produce(4), ref[4])
    ck.close()


# ---------------------------------------------------------------------------
# corrupt records + fault verbs
# ---------------------------------------------------------------------------

def _write_token_records(path, n=24):
    from dtf_tpu.data import tfrecord as tfr

    payloads = [tfr.encode_example(
        {"tokens": (np.arange(SEQ + 1) * (i + 1)) % V}) for i in range(n)]
    tfr.write_tfrecords(path, payloads)
    return n


def test_tfrecord_source_skips_corrupt_record_with_warn(tmp_path, caplog):
    from dtf_tpu.data import tfrecord as tfr
    from dtf_tpu.data.sharded import epoch_order

    good = str(tmp_path / "good.tfrecord")
    bad = str(tmp_path / "bad.tfrecord")
    n = _write_token_records(good)
    _write_token_records(bad)
    # damage record 7's payload head (framing stays intact: length CRCs
    # untouched, so indexing succeeds and the READ catches it)
    off, _l = tfr.tfrecord_spans(bad, verify_payload_crc=False)
    with open(bad, "r+b") as f:
        f.seek(int(off[7]) + 1)
        f.write(b"\xde\xad")

    src_good = TFRecordSource(good, SEQ, seed=1, name="g")
    src_bad = TFRecordSource(bad, SEQ, seed=1, name="b")
    hit = [int(i) for i in range(n)
           if int(epoch_order(n, 1, 0)[i]) == 7]      # index mapping to 7
    assert len(hit) == 1
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        rows_bad = [src_bad.example(i) for i in range(n)]
    assert sum("failed its payload CRC" in r.message
               for r in caplog.records) == 1           # one WARN per record
    assert src_bad.corrupt_skips == 1                  # real skip counted
    for i in range(n):
        if i == hit[0]:
            # the next example in epoch order stands in
            _batches_equal(rows_bad[i], src_good.example(i + 1))
        else:
            _batches_equal(rows_bad[i], src_good.example(i))
    # deterministic under re-read (resume replays the same skips)
    _batches_equal(TFRecordSource(bad, SEQ, seed=1).example(hit[0]),
                   rows_bad[hit[0]])

    # wholesale damage fails loudly, not silently
    for o in off:
        with open(bad, "r+b") as f:
            f.seek(int(o) + 1)
            f.write(b"\xff\xff")
    broken = TFRecordSource(bad, SEQ, seed=1)
    with pytest.raises(ValueError, match="damaged wholesale"):
        broken.example(0)


def test_stream_fault_plan_parsing_and_family_isolation():
    assert StreamFaultPlan.parse("stall_source@3:source=1") == \
        StreamFaultPlan("stall_source", 3, 1)
    assert StreamFaultPlan.parse("corrupt_record@0") == \
        StreamFaultPlan("corrupt_record", 0, None)
    for bad in ("stall_source", "melt@3", "stall_source@-1",
                "stall_source@3:replica=1"):
        with pytest.raises(ValueError):
            StreamFaultPlan.parse(bad)
    env = {"DTF_FAULT_INJECT": "stall_source@3:source=1"}
    # each installer family sees only its own kinds
    assert maybe_stream_fault(env) is not None
    assert FaultPlan.from_env(env) is None
    assert ServeFaultPlan.from_env(env) is None
    assert maybe_stream_fault({"DTF_FAULT_INJECT": "kill@3"}) is None
    assert maybe_stream_fault({"DTF_FAULT_INJECT": "wedge_replica@3"}) is \
        None
    assert maybe_stream_fault({}) is None


def test_stall_source_verb_is_latency_only(corpus, caplog):
    import time

    ref = [b for b in itertools.islice(iter(_stream(corpus)), 5)]
    st = _stream(corpus)
    st.arm_fault(StreamFaultPlan("stall_source", 2, 0), stall_s=0.2)
    t0 = time.perf_counter()
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        got = [st.produce(i) for i in range(5)]
    assert time.perf_counter() - t0 >= 0.2
    assert any("stalling source" in r.message for r in caplog.records)
    assert st.stats()["stalls"] == 1
    for g, w in zip(got, ref):
        _batches_equal(g, w)                     # latency-only: same bytes


def test_corrupt_record_verb_drives_skip_path(tmp_path, caplog):
    rec = str(tmp_path / "r.tfrecord")
    _write_token_records(rec)
    _write_bin(str(tmp_path / "a.bin"), 1)
    srcs = [TokenBinSource(str(tmp_path / "a.bin"), SEQ, vocab_size=V,
                           seed=0, salt=0, name="a"),
            TFRecordSource(rec, SEQ, seed=1, name="r")]
    st = MixtureStream(srcs, {"a": 1, "r": 1}, 16, seed=3)
    st.arm_fault(StreamFaultPlan("corrupt_record", 1, 1))
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        for i in range(3):
            st.produce(i)                        # keeps running
    assert st.stats()["corrupt_skips"] == 1
    assert any("failed its payload CRC" in r.message
               for r in caplog.records)


def test_corrupt_record_verb_without_record_layer_warns(corpus, caplog):
    st = _stream(corpus)
    st.arm_fault(StreamFaultPlan("corrupt_record", 0, 0))
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        st.produce(0)
    assert any("no record layer" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# Checkpointer extra items
# ---------------------------------------------------------------------------

def test_checkpointer_extra_items_roundtrip_and_legacy(tmp_path, caplog):
    import jax.numpy as jnp

    ck = Checkpointer(str(tmp_path / "ck"), async_save=False)
    # legacy save first: no extras anywhere
    ck.save(1, {"params": {"w": jnp.ones((4,))}, "step": 1}, force=True)
    # explicit kwarg + registered provider compose
    ck.add_extra_provider("stream", lambda step: {"next_step": step})
    ck.save(2, {"params": {"w": jnp.ones((4,))}, "step": 2}, force=True,
            extra_items={"note": {"tag": "hello"}})
    ck.wait()
    assert ck.restore_extra("stream", step=2) == {"next_step": 2}
    assert ck.restore_extra("note", step=2) == {"tag": "hello"}
    with caplog.at_level("WARNING", logger="dtf_tpu"):
        missing = ck.restore_extra("stream", step=1)
    assert missing is None                       # WARN, not a raise
    assert any("no 'stream' item" in r.message for r in caplog.records)
    # reserved names are refused
    with pytest.raises(ValueError, match="reserved"):
        ck.add_extra_provider("params", lambda s: {})
    with pytest.raises(ValueError, match="reserved"):
        ck.save(3, {"params": {"w": jnp.ones((4,))}},
                extra_items={"state": {}})
    # save_durable rides the same plumbing (the SIGTERM path)
    ck.save_durable(4, {"params": {"w": jnp.ones((4,))}, "step": 4})
    assert ck.restore_extra("stream", step=4) == {"next_step": 4}
    # extras also work for the no-params legacy state layout
    ck.save(5, {"w": jnp.ones((4,))}, force=True)
    ck.wait()
    assert ck.restore_extra("stream", step=5) == {"next_step": 5}
    got = ck.restore({"w": jnp.zeros((4,))}, 5)
    np.testing.assert_array_equal(np.asarray(got["w"]), np.ones(4))
    ck.close()


# ---------------------------------------------------------------------------
# zero-added-readbacks with the producer + prefetch path
# ---------------------------------------------------------------------------

def test_stream_fed_fit_keeps_sync_free_loop(corpus):
    """The PR 3 invariant survives the new tier: a stream-fed fit with a
    background producer AND device prefetch still syncs the step counter
    O(1) times, not O(steps) — counter-instrumented like
    tests/test_loop_checkpoint.py."""
    from dtf_tpu.loop import Trainer

    casts = []

    class FakeStep:
        def __init__(self, v):
            self.v = v

        def __int__(self):
            casts.append(1)
            return self.v

    class FakeState:
        def __init__(self, v):
            self.step = FakeStep(v)

    def fake_train_step(state, batch):
        assert batch["input_ids"].shape == (16, SEQ)
        return FakeState(state.step.v + 1), {}

    def run(n):
        casts.clear()
        st = _stream(corpus, depth=2)
        t = Trainer(fake_train_step, mesh=None, place_batch=lambda b: b,
                    prefetch=2)
        out = t.fit(FakeState(0), iter(st), max_steps=n)
        st.close()
        return len(casts), out

    c4, out4 = run(4)
    c16, out16 = run(16)
    assert out4.step.v == 4 and out16.step.v == 16
    assert c4 == c16 and c16 <= 2, (c4, c16)


# ---------------------------------------------------------------------------
# spec resolution (the manifest authority chain)
# ---------------------------------------------------------------------------

def test_close_ends_background_iteration(corpus):
    """close() must END a producer-backed iterator (StopIteration, like
    the inline one) — not leave the consumer hanging in q.get()."""
    st = _stream(corpus, depth=2)
    it = iter(st)
    next(it)
    st.close()
    with pytest.raises(StopIteration):
        while True:
            next(it)


def test_stream_spec_parse_and_validation(tmp_path):
    spec = parse_stream_spec(
        '{"sources": [{"name": "a", "path": "/x/a.bin", "weight": 2}]}')
    assert spec["sources"][0]["name"] == "a"
    p = tmp_path / "s.json"
    p.write_text(json.dumps(spec))
    assert parse_stream_spec(str(p)) == spec       # file form
    # a mistyped PATH is a ValueError like every other bad spec, so the
    # launchers' flag-error conversion catches it
    with pytest.raises(ValueError, match="stream spec path"):
        parse_stream_spec(str(tmp_path / "nope.json"))
    for bad, match in (
            ("{}", "sources"),
            ('{"sources": []}', "sources"),
            ('{"sources": [{"path": "x"}]}', "name"),
            ('{"sources": [{"name": "a", "kind": "nope", "path": "x"}]}',
             "kind"),
            ('{"sources": [{"name": "a"}]}', "path"),
            ('{"sources": [{"name": "a", "kind": "tfrecord"}]}', "pattern"),
            ('{"sources": [{"name": "a", "path": "x", "weight": 0}]}',
             "weight"),
            ('{"sources": [{"name": "a", "path": "x"}, '
             '{"name": "a", "path": "y"}]}', "duplicate"),
            ('{"sources": [{"name": "a", "path": "x"}], '
             '"reweight": [[3]]}', "reweight")):
        with pytest.raises(ValueError, match=match):
            parse_stream_spec(bad)


def test_resolve_stream_spec_manifest_authority():
    spec = {"sources": [{"name": "a", "path": "/x/a.bin", "weight": 1}]}
    other = {"sources": [{"name": "a", "path": "/x/a.bin", "weight": 2}]}
    manifest = {"stream_spec": spec}
    # no manifest: the flag's spec (or None) passes through
    assert resolve_stream_spec("", None) is None
    assert resolve_stream_spec(json.dumps(spec), None) == spec
    # manifest present: inherited when flag absent, accepted when equal
    assert resolve_stream_spec("", manifest) == spec
    assert resolve_stream_spec(json.dumps(spec), manifest) == spec
    # key order does not a contradiction make
    reordered = json.dumps({"sources": [dict(reversed(list(
        spec["sources"][0].items())))]})
    assert resolve_stream_spec(reordered, manifest) == spec
    # a DIFFERENT spec against a manifest is refused
    with pytest.raises(ValueError, match="contradicts"):
        resolve_stream_spec(json.dumps(other), manifest)


def test_build_stream_from_spec_applies_reweight(corpus):
    spec = {"sources": [
        {"name": "a", "path": os.path.join(corpus, "a.bin"), "weight": 7},
        {"name": "b", "path": os.path.join(corpus, "b.bin"), "weight": 3}],
        "reweight": [[5, {"a": 1, "b": 9}]]}
    st = build_stream(spec, global_batch=16, seq_len=SEQ, vocab_size=V,
                      seed=3, producer_depth=0)
    assert [5, {"a": 0.1, "b": 0.9}] in st.state()["schedule"]
    b = st.produce(0)
    assert b["input_ids"].shape == (16, SEQ)


# ---------------------------------------------------------------------------
# slow tier: the full online loop through the real launchers
# ---------------------------------------------------------------------------

def _env(**extra):
    env = dict(os.environ)
    env.pop("DTF_FAULT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    env.update(extra)
    return env


@pytest.mark.slow
def test_stream_launcher_kill_resume_publish_swap_e2e(tmp_path):
    """The whole loop: a stream-fed train_gpt is KILLED mid-run, resumed
    (with a stall verb riding the resume — latency-only), publishes
    versions, and a serve_gpt fleet rolls onto the newest one with every
    request terminal and version-stamped. The resumed trainer's final
    params match an uninterrupted twin's."""
    data = tmp_path / "data"
    data.mkdir()
    _write_bin(str(data / "a.bin"), 1, n=20_000)
    _write_bin(str(data / "b.bin"), 2, n=20_000)
    # vocab_size must match the model (tiny gpt vocab is larger than V;
    # token ids < V are valid everywhere)
    spec = {"sources": [
        {"name": "a", "path": str(data / "a.bin"), "weight": 7},
        {"name": "b", "path": str(data / "b.bin"), "weight": 3}]}
    spec_path = tmp_path / "spec.json"
    spec_path.write_text(json.dumps(spec))
    pub = str(tmp_path / "pub")

    def train(logdir, *args, env=None, expect_rc0=True, pub_dir=pub):
        proc = subprocess.run(
            [sys.executable, os.path.join(ROOT, "scripts", "train_gpt.py"),
             "--backend=cpu",
             "--size=tiny", "--train_steps=4", "--batch_size=16",
             "--seq_len=32", "--checkpoint_every=2",
             f"--stream_spec={spec_path}", f"--logdir={logdir}",
             f"--publish_dir={pub_dir}", "--publish_every=2", "--telemetry",
             *args],
            env=env or _env(), capture_output=True, text=True, timeout=420)
        if expect_rc0:
            assert proc.returncode == 0, (
                f"train_gpt rc={proc.returncode}\n{proc.stdout[-1500:]}\n"
                f"{proc.stderr[-1500:]}")
        return proc

    log1 = str(tmp_path / "log1")
    # killed at step 3 via the in-process host-lost twin (crash@S; the
    # true SIGKILL-no-save-on-the-way-down path is proven bitwise at
    # tier-1 by test_trainer_kill_resume_bitwise_losses — here an
    # in-process crash still runs fit's finally, so the step-3 end save
    # lands and the resume point is deterministic under async saves)
    proc = train(log1, env=_env(DTF_FAULT_INJECT="crash@3"),
                 expect_rc0=False)
    assert proc.returncode != 0, "crash@3 never fired"
    assert Checkpointer(os.path.join(log1, "ckpt")).latest_step() == 3
    assert os.path.isdir(os.path.join(log1, "ckpt", "3", "stream"))

    # resumed — inheriting the manifest's spec (no flag change allowed),
    # with a stall_source verb riding the SAME run: recovery is
    # latency-only, so the bitwise story below must still hold
    proc = train(log1, env=_env(
        DTF_FAULT_INJECT="stall_source@3:source=0"))
    out = proc.stdout + proc.stderr
    assert "done: step=4" in out
    assert "resumed from checkpoint at step 3" in out
    assert "stalling source" in out
    report = json.loads([ln for ln in proc.stdout.splitlines()
                         if '"run_report"' in ln][-1])
    assert report["stream"]["per_source"]["a"]["examples"] > 0
    assert report["stream"]["stalls"] == 1

    # uninterrupted twin: the resumed run's final params match (its own
    # publish dir — sharing pub would have its versions prune v1 out of
    # the rolling-swap scenario below)
    log2 = str(tmp_path / "log2")
    train(log2, pub_dir=str(tmp_path / "pub2"))
    p1 = Checkpointer(os.path.join(log1, "ckpt")).restore_raw(4)["params"]
    p2 = Checkpointer(os.path.join(log2, "ckpt")).restore_raw(4)["params"]
    import jax

    jax.tree.map(lambda a, b: np.testing.assert_allclose(
        np.asarray(a), np.asarray(b), rtol=1e-6), p1, p2)

    # the published versions feed a rolling swap across a live fleet
    from dtf_tpu.publish import read_manifest

    newest = read_manifest(pub)["version"]
    assert newest >= 2
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", "serve_gpt.py"),
         "--backend=cpu",
         f"--logdir={log1}", f"--publish_dir={pub}",
         "--publish_version=1", "--swap_poll_ticks=2", "--canary_ticks=2",
         "--replicas=2", "--n_slots=2", "--max_len=48",
         "--requests=5,9,2;5,9,2,7,1,3;1,2,3,4,5;8,8;2,4,6,8",
         "--n_new=6", "--stats_every=2"],
        env=_env(), capture_output=True, text=True, timeout=420)
    assert proc.returncode == 0, (
        f"serve_gpt rc={proc.returncode}\n{proc.stdout[-1500:]}\n"
        f"{proc.stderr[-1500:]}")
    stats = json.loads([ln for ln in proc.stdout.splitlines()
                        if ln.startswith("{")][-1])
    assert stats["request_statuses"] == {"done": 5}   # every request done
    assert stats["served_version"] == 1
    assert stats["final_version"] == newest           # the fleet rolled
    assert stats["router_swaps"] >= 1
