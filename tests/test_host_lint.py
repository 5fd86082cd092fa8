"""Host-plane soundness pass (dtf_tpu/analysis/host): every seeded
defect class must be caught, pinned/sanctioned spellings must pass, the
SHIPPED tree must be finding-free, and the fixes the pass forced (atomic
_hostio choke point, injectable clocks, mixture locking, resume-event
stamps) must hold under regression."""

import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from dtf_tpu import _hostio
from dtf_tpu.analysis import host
from dtf_tpu.analysis import hostmodel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint_src(tmp_path, src, name="mod.py"):
    p = tmp_path / name
    p.write_text(textwrap.dedent(src))
    return host.lint_paths([str(p)])


def _checks(findings):
    return {f.check for f in findings}


# ---------------------------------------------------------------------------
# seeded defects: unguarded shared state
# ---------------------------------------------------------------------------

SHARED_STATE_DEFECT = """
    import threading

    class Worker:
        def __init__(self):
            self._lock = threading.Lock()
            self._count = 0
            self._thread = None

        def start(self):
            def run():
                while True:
                    self._count += 1    # thread-side write, no lock
            self._thread = threading.Thread(target=run)
            self._thread.start()

        def snapshot(self):
            return self._count          # main-side read
"""


def test_unguarded_shared_state_detected(tmp_path):
    fs = _lint_src(tmp_path, SHARED_STATE_DEFECT)
    assert _checks(fs) == {"unguarded-shared-state"}
    assert "_count" in fs[0].detail and "Worker" in fs[0].detail


def test_guarded_shared_state_clean(tmp_path):
    fs = _lint_src(tmp_path, SHARED_STATE_DEFECT.replace(
        "                    self._count += 1    # thread-side write, no lock",
        "                    with self._lock:\n"
        "                        self._count += 1"))
    assert fs == []


def test_lock_ok_pin_suppresses(tmp_path):
    fs = _lint_src(tmp_path, SHARED_STATE_DEFECT.replace(
        "no lock", "no lock  # lock-ok: publish-once test fixture"))
    assert fs == []


def test_thread_only_attr_needs_no_lock(tmp_path):
    # written and read on the thread side only: single-side ownership
    fs = _lint_src(tmp_path, """
        import threading

        class Worker:
            def __init__(self):
                self._beat = 0

            def start(self):
                def run():
                    self._beat += 1
                threading.Thread(target=run).start()
    """)
    assert fs == []


def test_threadsafe_containers_exempt(tmp_path):
    fs = _lint_src(tmp_path, """
        import queue
        import threading

        class Worker:
            def __init__(self):
                self._q = queue.Queue()
                self._stop = threading.Event()

            def start(self):
                def run():
                    self._q.put(1)
                threading.Thread(target=run).start()

            def close(self):
                self._stop.set()
                self._q.put(None)
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# seeded defects: signal-handler lock discipline
# ---------------------------------------------------------------------------

SIGNAL_DEFECT = """
    import signal
    import threading

    class Recorder:
        def __init__(self):
            self._lock = threading.Lock()
            self.rows = []

        def install(self):
            signal.signal(signal.SIGTERM, self._on_sigterm)

        def _on_sigterm(self, signum, frame):
            self.dump()

        def dump(self):
            with self._lock:
                return list(self.rows)
"""


def test_signal_handler_plain_lock_detected(tmp_path):
    fs = _lint_src(tmp_path, SIGNAL_DEFECT)
    assert _checks(fs) == {"signal-handler-deadlock"}
    assert "_on_sigterm" in fs[0].detail


def test_signal_handler_rlock_clean(tmp_path):
    fs = _lint_src(tmp_path,
                   SIGNAL_DEFECT.replace("threading.Lock()",
                                         "threading.RLock()"))
    assert fs == []


def test_signal_handler_cross_class_lock_detected(tmp_path):
    # the FlightRecorder shape: handler -> self.flight.dump() -> Lock in
    # ANOTHER class, resolved through the typed attribute
    fs = _lint_src(tmp_path, """
        import signal
        import threading

        class Flight:
            def __init__(self):
                self._lock = threading.Lock()
                self.rows = []

            def dump(self):
                with self._lock:
                    return list(self.rows)

        class Telemetry:
            def __init__(self):
                self.flight = Flight()

            def start(self):
                signal.signal(signal.SIGTERM, self._on_sigterm)

            def _on_sigterm(self, signum, frame):
                self.flight.dump()
    """)
    assert _checks(fs) == {"signal-handler-deadlock"}
    assert "Flight._lock" in fs[0].detail


# ---------------------------------------------------------------------------
# seeded defects: atomic-write choke point
# ---------------------------------------------------------------------------

def test_raw_manifest_write_detected(tmp_path):
    fs = _lint_src(tmp_path, """
        import json
        import os

        def commit(path, manifest):
            with open(path + ".tmp", "w") as f:
                json.dump(manifest, f)
            os.rename(path + ".tmp", path)
    """)
    assert _checks(fs) == {"non-atomic-publish"}
    assert len(fs) == 2     # the raw open AND the bare rename


def test_read_open_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        import json

        def load(path):
            with open(path) as f:
                return json.load(f)

        def load_bytes(path):
            with open(path, "rb") as f:
                return f.read()
    """)
    assert fs == []


def test_io_ok_pin_suppresses(tmp_path):
    fs = _lint_src(tmp_path, """
        def damage(path):
            # io-ok: deliberately non-atomic, this IS the damage
            with open(path, "r+b") as f:
                f.write(b"junk")
    """)
    assert fs == []


# ---------------------------------------------------------------------------
# seeded defects: clock discipline
# ---------------------------------------------------------------------------

def test_raw_wall_clock_detected(tmp_path):
    fs = _lint_src(tmp_path, """
        import time

        def stamp():
            return round(time.time(), 3)
    """)
    assert _checks(fs) == {"clock-escape"}


def test_raw_clock_in_serve_health_copy_detected(tmp_path):
    """The ISSUE's named fixture: a copy of serve/health.py with one raw
    time.time() regression — it must trip exactly clock-escape, while
    the shipped original stays clean."""
    src = open(os.path.join(ROOT, "dtf_tpu", "serve", "health.py")).read()
    assert host.lint_paths(
        [os.path.join(ROOT, "dtf_tpu", "serve", "health.py")]) == []
    seeded = src + ("\n\ndef _seeded_regression():\n"
                    "    return time.time()\n")
    p = tmp_path / "health_seeded.py"
    p.write_text(seeded)
    fs = host.lint_paths([str(p)])
    assert _checks(fs) == {"clock-escape"}
    assert str(len(seeded.splitlines())) in fs[0].detail


def test_injectable_default_is_sanctioned(tmp_path):
    fs = _lint_src(tmp_path, """
        import time

        class Ticker:
            def __init__(self, *, clock=time.monotonic, sleep=time.sleep):
                self._clock = clock
                self._sleep = sleep

            def tick(self):
                t0 = self._clock()
                self._sleep(0.0)
                return self._clock() - t0
    """)
    assert fs == []


def test_clock_ok_pin_suppresses(tmp_path):
    fs = _lint_src(tmp_path, """
        import time

        def stamp():
            # clock-ok: real wall stamp correlated with external logs
            return round(time.time(), 3)
    """)
    assert fs == []


def test_from_time_import_detected(tmp_path):
    fs = _lint_src(tmp_path, "from time import monotonic\n")
    assert _checks(fs) == {"clock-escape"}


def test_global_state_rng_detected_seeded_rng_clean(tmp_path):
    fs = _lint_src(tmp_path, """
        import numpy as np

        def bad():
            return np.random.random()

        def also_bad():
            return np.random.default_rng()

        def good(seed):
            return np.random.default_rng(
                np.random.SeedSequence([seed, 7]))
    """)
    assert _checks(fs) == {"clock-escape"}
    assert len(fs) == 2


def test_unparseable_file_is_a_finding(tmp_path):
    fs = _lint_src(tmp_path, "def broken(:\n")
    assert _checks(fs) == {"syntax-error"}


# ---------------------------------------------------------------------------
# the shipped tree + wiring
# ---------------------------------------------------------------------------

def test_shipped_tree_is_finding_free():
    assert host.lint_host() == []


def test_fenced_scope_covers_the_control_plane():
    rels = {os.path.relpath(p, os.path.join(ROOT, "dtf_tpu"))
            for p in host.fenced_files()}
    assert "publish.py" in rels
    assert any(r.startswith("serve" + os.sep) for r in rels)
    assert any(r.startswith("fault" + os.sep) for r in rels)
    assert any(r.startswith("telemetry" + os.sep) for r in rels)
    assert any(r.startswith(os.path.join("data", "stream")) for r in rels)


def test_host_pass_registered():
    from dtf_tpu.analysis import runner
    assert "host" in runner.ALL_PASSES


def test_cli_host_pass_json_line():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    env["_DTF_TPU_ANALYSIS_REEXEC"] = "1"
    proc = subprocess.run(
        [sys.executable, "-m", "dtf_tpu.analysis", "--passes=host"],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert proc.returncode == 0, proc.stderr[-1500:]
    assert out["ok"] is True and out["findings"] == 0
    assert out["passes"] == ["host"]


# ---------------------------------------------------------------------------
# the _hostio choke point
# ---------------------------------------------------------------------------

def test_atomic_replace_writes_and_replaces(tmp_path):
    p = str(tmp_path / "m.json")
    _hostio.atomic_replace(p, "one")
    assert open(p).read() == "one"
    _hostio.atomic_replace(p, "two")
    assert open(p).read() == "two"
    assert os.listdir(tmp_path) == ["m.json"]   # no tmp litter


def test_atomic_replace_makes_parent_dirs(tmp_path):
    p = str(tmp_path / "deep" / "er" / "m.json")
    _hostio.atomic_replace(p, "x")
    assert open(p).read() == "x"


def test_atomic_replace_failure_leaves_old_content(tmp_path,
                                                   monkeypatch):
    p = str(tmp_path / "m.json")
    _hostio.atomic_replace(p, "committed")

    def boom(src, dst):
        raise OSError("disk gone")

    monkeypatch.setattr(_hostio.os, "replace", boom)
    with pytest.raises(OSError):
        _hostio.atomic_replace(p, "torn")
    assert open(p).read() == "committed"
    assert os.listdir(tmp_path) == ["m.json"]   # failed tmp cleaned up


def test_append_line_appends_and_rejects_newlines(tmp_path):
    p = str(tmp_path / "log.jsonl")
    _hostio.append_line(p, json.dumps({"a": 1}))
    _hostio.append_line(p, json.dumps({"a": 2}))
    rows = [json.loads(x) for x in open(p).read().splitlines()]
    assert rows == [{"a": 1}, {"a": 2}]
    with pytest.raises(ValueError):
        _hostio.append_line(p, "two\nlines")


# ---------------------------------------------------------------------------
# regressions on the fixes the pass forced
# ---------------------------------------------------------------------------

def test_span_recorder_injectable_clock():
    from dtf_tpu.telemetry.spans import SpanRecorder
    ticks = iter([10.0, 12.5])
    rec = SpanRecorder(clock=lambda: next(ticks))
    with rec.span("data_wait"):
        pass
    assert rec.total("data_wait") == 2.5 and rec.count("data_wait") == 1


class _TinySource:
    def __init__(self, name, base):
        self.name = name
        self.base = base

    def example(self, i):
        return {"x": np.full((4,), self.base + i, np.int32)}


def _tiny_stream(**kw):
    from dtf_tpu.data.stream import MixtureStream
    srcs = [_TinySource("a", 0), _TinySource("b", 1000)]
    return MixtureStream(srcs, {"a": 0.5, "b": 0.5}, 8, seed=1, **kw)


def test_mixture_injectable_sleep_and_clock_drive_the_stall_verb():
    from dtf_tpu.fault.inject import StreamFaultPlan
    slept = []
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    s = _tiny_stream(clock=clock, sleep=slept.append, stall_s=30.0)
    s.arm_fault(StreamFaultPlan(kind="stall_source", step=1, source=0))
    s.produce(0)
    s.produce(1)
    # the 30s stall ran on the injected sleep — zero real wall time —
    # and the stats counted it exactly once
    assert slept == [30.0]
    assert s.stats()["stalls"] == 1
    # produce_s accumulated from the injected clock: two batches, one
    # fake second each
    assert s.stats()["produce_s"] == 2.0


def test_mixture_fault_decision_fires_once_under_contention():
    """The read-check-set on _fault_fired (and the stalls counter) moved
    under the lock: racing produce(0) calls — the armed-fault hazard the
    host pass flagged — must fire the fault exactly once, never per
    racer. (Step ORDERING stays the single-consumer contract; only the
    fault decision is made atomic.)"""
    from dtf_tpu.fault.inject import StreamFaultPlan
    s = _tiny_stream(sleep=lambda _: None)
    s.arm_fault(StreamFaultPlan(kind="stall_source", step=0, source=0))
    barrier = threading.Barrier(4)

    def worker():
        barrier.wait()
        try:
            s.produce(0)
        except ValueError:
            pass    # losers of the step guard

    ts = [threading.Thread(target=worker) for _ in range(4)]
    for th in ts:
        th.start()
    for th in ts:
        th.join()
    assert s.stats()["stalls"] == 1


def test_publisher_wall_pin_stamps_published_t(tmp_path):
    import jax.numpy as jnp
    from dtf_tpu.publish import ParamPublisher, read_manifest
    pub = ParamPublisher(str(tmp_path), wall=lambda: 111.5)
    try:
        pub.publish(3, {"w": jnp.zeros((2,), jnp.float32)})
    finally:
        pub.close()
    assert read_manifest(str(tmp_path))["published_t"] == 111.5


def test_restore_extra_records_resume_events(tmp_path):
    import jax.numpy as jnp
    from dtf_tpu.checkpoint import Checkpointer
    ckpt = Checkpointer(str(tmp_path), async_save=False,
                        wall=lambda: 222.25)
    try:
        ckpt.save(0, {"w": jnp.zeros((2,), jnp.float32)}, force=True)
        ckpt.wait()
        assert ckpt.restore_extra("stream", step=0) is None
    finally:
        ckpt.close()
    assert ckpt.resume_events == [
        {"event": "missing-extra", "item": "stream", "step": 0,
         "t": 222.25}]


def test_stream_hook_records_legacy_seek_event():
    from dtf_tpu.data.stream.persist import StreamCheckpointHook

    class FakeCkpt:
        last_restored_step = 5

        def add_extra_provider(self, name, fn):
            pass

        def restore_extra(self, name, step=None):
            return None     # a legacy checkpoint: no stream item

    sought = []

    class FakeStream:
        state_at = staticmethod(lambda step: {})
        seek = staticmethod(sought.append)

    hook = StreamCheckpointHook(FakeCkpt(), FakeStream(),
                                wall=lambda: 333.0)
    hook.begin(state=None)
    assert sought == [5]
    assert hook.resume_events == [
        {"event": "legacy-stream-seek", "step": 5, "t": 333.0}]


# ---------------------------------------------------------------------------
# hostmodel precision facts the lints rely on
# ---------------------------------------------------------------------------

def test_hostmodel_resolves_thread_target_and_guards(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        import threading

        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self._n = 0

            def start(self):
                def run():
                    with self._lock:
                        self._n += 1
                threading.Thread(target=run).start()
    """))
    mod = hostmodel.build_module(str(p))
    (cls,) = mod.classes
    assert cls.locks == {"_lock": "Lock"}
    assert cls.thread_targets == {"start.<locals>.run"}
    writes = [a for a in cls.accesses if a.attr == "_n" and a.write
              and a.func != "__init__"]
    assert writes and all(a.guarded for a in writes)


def test_hostmodel_attr_chain_and_subscript_are_writes(tmp_path):
    p = tmp_path / "m.py"
    p.write_text(textwrap.dedent("""
        class C:
            def touch(self):
                self.stats["k"] += 1
                self.child.value = 3
    """))
    (cls,) = hostmodel.build_module(str(p)).classes
    got = {a.attr: a.write for a in cls.accesses}
    assert got == {"stats": True, "child": True}
