"""Serve-traffic flywheel (ISSUE 19, tier-1 fast): the request-log sink's
durability contract (CRC-framed shards, atomic manifest commits, orphan
adoption after a crash mid-rotation), the ``servelog`` stream source's
determinism + filters + corrupt-skip discipline, the sink chaos verbs on
the shared DTF_FAULT_INJECT grammar, per-version speculative acceptance in
the scheduler, and the no-backend import story. The slow tier closes the
whole circle through the real launchers: serve with a sink → distill a
draft from the logged traffic → publish → draft-only rolling swap with
byte-identical tokens.
"""

import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

from dtf_tpu.data.stream import (ServeLogSource, build_stream,
                                 parse_stream_spec)
from dtf_tpu.data.tfrecord import crc32c
from dtf_tpu.data.stream.servelog import (MANIFEST_BASENAME, MANIFEST_VERSION,
                                          decode_record, encode_record,
                                          manifest_path, read_manifest,
                                          shard_name)
from dtf_tpu.fault.inject import (FaultPlan, InjectedCrash, ServeFaultPlan,
                                  StreamFaultPlan)
from dtf_tpu.serve.logsink import LogSink

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, s):
        self.t += s


def _rec(i, *, version=0, status="done", n_prompt=3, n_tokens=4):
    """A deterministic serve-log record shaped like _retire's write."""
    return {"rid": i, "replica": 0, "version": version, "status": status,
            "prompt": [(i + j) % 89 + 1 for j in range(n_prompt)],
            "tokens": [(7 * i + j) % 89 + 1 for j in range(n_tokens)],
            "ttft_s": 0.01, "latency_s": 0.05, "proposed": 4, "accepted": 2}


def _fill(sink, n, **kw):
    for i in range(n):
        sink.record(_rec(i, **kw))


# ---------------------------------------------------------------------------
# record codec
# ---------------------------------------------------------------------------

def test_record_codec_roundtrip_and_damage_detection():
    rec = _rec(3)
    line = encode_record(rec)
    assert decode_record(line) == rec
    # same content -> same bytes (the CRC is a function of the record)
    assert encode_record(dict(reversed(list(rec.items())))) == line
    crc_hex, _, body = line.partition(" ")
    flipped = f"{int(crc_hex, 16) ^ 0xFFFFFFFF:08x} {body}"
    assert decode_record(flipped) is None          # CRC mismatch
    assert decode_record(line[:-3]) is None        # torn body
    assert decode_record(body) is None             # frame missing
    assert decode_record("zzzzzzzz " + body) is None   # non-hex frame
    lst = json.dumps([1, 2])
    assert decode_record(
        f"{crc32c(lst.encode()):08x} {lst}") is None   # JSON, not a dict


# ---------------------------------------------------------------------------
# the sink: rotation, manifest commits, recovery
# ---------------------------------------------------------------------------

def test_sink_rotation_commits_manifest_per_shard(tmp_path):
    d = str(tmp_path / "sink")
    sink = LogSink(d, rotate_bytes=1)      # every record rotates
    _fill(sink, 3)
    st = sink.stats()
    assert st["records"] == 3 and st["rotations"] == 3
    assert st["open_records"] == 0 and st["adopted_shards"] == 0
    man = read_manifest(d)
    assert [s["name"] for s in man["shards"]] == [shard_name(i)
                                                  for i in range(3)]
    assert man["records"] == 3 and man["version"] == MANIFEST_VERSION
    # a second sink over the directory continues the shard sequence
    again = LogSink(d, rotate_bytes=1)
    assert again.stats()["adopted_shards"] == 0
    again.record(_rec(9))
    again.close()
    assert [s["name"] for s in read_manifest(d)["shards"]][-1] \
        == shard_name(3)


def test_sink_flush_and_close_commit_the_open_shard(tmp_path):
    d = str(tmp_path / "sink")
    sink = LogSink(d, rotate_bytes=0)      # rotation disabled
    _fill(sink, 3)
    assert read_manifest(d) is None        # nothing committed yet
    sink.flush()
    assert read_manifest(d)["records"] == 3
    sink.record(_rec(5))
    sink.close()
    man = read_manifest(d)
    assert man["records"] == 4 and len(man["shards"]) == 2
    sink.close()                           # idempotent: no empty shard
    assert len(read_manifest(d)["shards"]) == 2


def test_sink_crash_mid_rotation_and_orphan_adoption(tmp_path, caplog):
    d = str(tmp_path / "sink")
    sink = LogSink(d, rotate_bytes=1)
    fired = []
    sink.arm_crash_rotate(1, note=fired.append)
    sink.record(_rec(0))                   # rotation 0 commits
    with pytest.raises(InjectedCrash, match="adoption must recover"):
        sink.record(_rec(1))               # rotation 1 crashes pre-commit
    assert fired == ["crash_in_log_rotate"]
    # the shard bytes are durable; the manifest never saw them
    assert os.path.exists(os.path.join(d, shard_name(1)))
    assert [s["name"] for s in read_manifest(d)["shards"]] == [shard_name(0)]
    # the next sink adopts the orphan — committed records never lost,
    # never re-ordered, and the orphan's name is never reused
    with caplog.at_level(logging.WARNING, logger="dtf_tpu"):
        healed = LogSink(d, rotate_bytes=1)
    assert healed.stats()["adopted_shards"] == 1
    assert any("adopted orphan shard" in r.getMessage()
               for r in caplog.records)
    man = read_manifest(d)
    assert [s["name"] for s in man["shards"]] == [shard_name(0),
                                                  shard_name(1)]
    assert man["records"] == 2
    healed.record(_rec(2))
    healed.close()
    assert [s["name"] for s in read_manifest(d)["shards"]][-1] \
        == shard_name(2)
    # the recovered directory mounts cleanly with every record present
    src = ServeLogSource(d, 8)
    assert src.n_records == 3 and src.scan_drops == 0


def test_sink_corrupt_verb_damages_exactly_one_record(tmp_path, caplog):
    d = str(tmp_path / "sink")
    sink = LogSink(d, rotate_bytes=0)
    fired = []
    sink.arm_corrupt(1, note=fired.append)
    _fill(sink, 3)
    sink.close()
    assert fired == ["corrupt_log_record"]
    assert sink.stats()["injected_corrupt"] == 1
    # the mounting source drops exactly the damaged record, one WARN
    with caplog.at_level(logging.WARNING, logger="dtf_tpu"):
        src = ServeLogSource(d, 8)
    assert src.n_records == 2 and src.scan_drops == 1
    assert sum("failed its record CRC" in r.getMessage()
               for r in caplog.records) == 1
    # the damaged line's BODY survived — only the frame fails
    with open(os.path.join(d, shard_name(0))) as f:
        lines = [ln for ln in f.read().split("\n") if ln]
    assert decode_record(lines[1]) is None
    assert json.loads(lines[1].partition(" ")[2])["rid"] == 1


# ---------------------------------------------------------------------------
# ServeLogSource: windowing, filters, determinism, read-path skips
# ---------------------------------------------------------------------------

def _sink_dir(tmp_path, recs, name="sink"):
    d = str(tmp_path / name)
    sink = LogSink(d, rotate_bytes=0)
    for r in recs:
        sink.record(r)
    sink.close()
    return d


def test_source_windows_tail_and_pads_short_records(tmp_path):
    long = _rec(0, n_prompt=6, n_tokens=8)       # 14 > seq+1
    short = _rec(1, n_prompt=2, n_tokens=2)      # 4 < seq+1
    d = _sink_dir(tmp_path, [long])
    ex = ServeLogSource(d, 8).example(0)
    assert ex["input_ids"].shape == (8,) and ex["labels"].shape == (8,)
    assert ex["input_ids"].dtype == np.int32
    full = long["prompt"] + long["tokens"]
    np.testing.assert_array_equal(ex["labels"], full[-8:])   # tail window
    d2 = _sink_dir(tmp_path, [short], name="short")
    ex2 = ServeLogSource(d2, 8, pad_id=0).example(0)
    np.testing.assert_array_equal(
        ex2["input_ids"], short["prompt"] + short["tokens"] + [0] * 4)
    assert all(ex2["labels"][3:] == 0)


def test_source_filters_and_empty_survivors_raise(tmp_path):
    recs = [_rec(0, version=0), _rec(1, version=1),
            _rec(2, version=1, n_tokens=1), _rec(3, version=2),
            _rec(4, version=1, status="error")]
    d = _sink_dir(tmp_path, recs)
    assert ServeLogSource(d, 8).n_records == 4          # status=done
    src = ServeLogSource(d, 8, min_version=1, max_version=1)
    assert src.n_records == 2
    assert src.stats()["filtered"] == 3
    assert ServeLogSource(d, 8, min_version=1, max_version=1,
                          min_tokens=2).n_records == 1
    assert ServeLogSource(d, 8, status="error").n_records == 1
    with pytest.raises(ValueError, match="survive the filters"):
        ServeLogSource(d, 8, min_version=99)
    with pytest.raises(FileNotFoundError, match="not a serve-log sink"):
        ServeLogSource(str(tmp_path / "nowhere"), 8)
    # manifest version gate
    bad = str(tmp_path / "bad")
    os.makedirs(bad)
    with open(manifest_path(bad), "w") as f:
        json.dump({"version": 99, "shards": []}, f)
    with pytest.raises(ValueError, match="manifest version"):
        ServeLogSource(bad, 8)


def test_source_counter_determinism_across_instances_and_epochs(tmp_path):
    d = _sink_dir(tmp_path, [_rec(i) for i in range(7)])
    a = ServeLogSource(d, 8, seed=5)
    b = ServeLogSource(d, 8, seed=5)
    for i in (0, 3, 6, 7, 13, 20):       # crosses epoch boundaries
        ex_a, ex_b = a.example(i), b.example(i)
        np.testing.assert_array_equal(ex_a["input_ids"], ex_b["input_ids"])
        np.testing.assert_array_equal(ex_a["labels"], ex_b["labels"])
    # an epoch is a permutation: each record seen exactly once
    seen = {tuple(a.example(i)["input_ids"]) for i in range(7)}
    assert len(seen) == 7
    assert seen == {tuple(a.example(7 + i)["input_ids"]) for i in range(7)}


def test_source_read_path_poison_skips_with_one_warn(tmp_path, caplog):
    d = _sink_dir(tmp_path, [_rec(i) for i in range(4)])
    src = ServeLogSource(d, 8, seed=2)
    twin = ServeLogSource(d, 8, seed=2)
    src.poison_next()
    with caplog.at_level(logging.WARNING, logger="dtf_tpu"):
        got = src.example(0)
    # the next record in epoch order stands in
    np.testing.assert_array_equal(got["input_ids"],
                                  twin.example(1)["input_ids"])
    assert src.corrupt_skips == 1
    assert sum("skipping it" in r.getMessage()
               for r in caplog.records) == 1
    # wholesale damage is a hard error, not an infinite scan
    src._record = lambda rec: None
    with pytest.raises(ValueError, match="damaged wholesale"):
        src.example(0)


# ---------------------------------------------------------------------------
# spec resolution + mixture resume (the PR 15 contract over served traffic)
# ---------------------------------------------------------------------------

def test_stream_spec_accepts_servelog_kind(tmp_path):
    spec = parse_stream_spec(json.dumps({"sources": [
        {"name": "traffic", "kind": "servelog", "path": "/x",
         "min_version": 1, "min_tokens": 2, "weight": 2},
        {"name": "base", "path": "/y", "weight": 1}]}))
    assert spec["sources"][0]["kind"] == "servelog"
    with pytest.raises(ValueError, match="needs a 'path'"):
        parse_stream_spec(json.dumps({"sources": [
            {"name": "traffic", "kind": "servelog"}]}))
    with pytest.raises(ValueError, match="unknown kind"):
        parse_stream_spec(json.dumps({"sources": [
            {"name": "t", "kind": "servelogs", "path": "/x"}]}))


def test_servelog_mixture_bitwise_resume_and_dp8_to_dp4_shrink(tmp_path):
    """The flywheel rides the PR 15 determinism contract end to end:
    a mixture over a sink directory resumes byte-identically from int
    cursors, including the 2-host → 1-host shrink re-partition."""
    d = _sink_dir(tmp_path, [_rec(i, version=i % 2, n_prompt=3 + i % 5,
                                  n_tokens=2 + i % 7)
                             for i in range(23)])
    spec = {"sources": [{"name": "traffic", "kind": "servelog", "path": d,
                         "weight": 1.0}]}

    def stream(**kw):
        kw.setdefault("producer_depth", 0)
        return build_stream(spec, global_batch=8, seq_len=8, seed=11, **kw)

    rst = stream()
    ref = [rst.produce(i) for i in range(8)]
    st = stream()
    for i in range(4):
        st.produce(i)
    saved = st.state_at(4)
    assert set(saved["cursors"]) == {"traffic"}      # int cursors ARE state
    resumed = stream()
    resumed.restore(saved)
    for i in range(4, 8):
        got = resumed.produce(i)
        for k in got:
            np.testing.assert_array_equal(got[k], ref[i][k])
    # two fake hosts cover the same global rows; the survivor resumes
    h0 = stream(host_index=0, host_count=2)
    h1 = stream(host_index=1, host_count=2)
    for i in range(3):
        b0, b1 = h0.produce(i), h1.produce(i)
        for k in b0:
            np.testing.assert_array_equal(
                np.concatenate([b0[k], b1[k]]), ref[i][k])
    assert h0.state_at(3) == h1.state_at(3)          # global addressing
    survivor = stream()
    survivor.restore(h0.state_at(3))
    for k, v in survivor.produce(3).items():
        np.testing.assert_array_equal(v, ref[3][k])
    # the background producer runs AHEAD of the consumer; state_at(step)
    # must still describe the trained prefix, not the staged lookahead
    import time
    pr = stream(producer_depth=3)
    it = iter(pr)
    for i in range(4):
        got = next(it)
        for k in got:
            np.testing.assert_array_equal(got[k], ref[i][k])
    deadline = time.perf_counter() + 5.0
    while pr.next_step <= 4 and time.perf_counter() < deadline:
        time.sleep(0.01)
    assert pr.next_step > 4                          # lookahead happened
    saved = pr.state_at(4)
    pr.close()
    resumed = stream()
    resumed.restore(saved)
    for i in range(4, 8):
        got = resumed.produce(i)
        for k in got:
            np.testing.assert_array_equal(got[k], ref[i][k])


# ---------------------------------------------------------------------------
# chaos verbs: grammar, family isolation, sink arming
# ---------------------------------------------------------------------------

def test_log_fault_verbs_parse_and_family_isolation():
    p = ServeFaultPlan.parse("corrupt_log_record@2")
    assert (p.kind, p.tick) == ("corrupt_log_record", 2)
    assert ServeFaultPlan.parse("crash_in_log_rotate@1").tick == 1
    # the three families ride ONE env var and skip each other's kinds
    for verb in ("corrupt_log_record@2", "crash_in_log_rotate@0"):
        env = {"DTF_FAULT_INJECT": verb}
        assert ServeFaultPlan.from_env(env=env).kind == verb.split("@")[0]
        assert FaultPlan.from_env(env=env) is None
        assert StreamFaultPlan.from_env(env=env) is None


def test_install_serve_fault_arms_the_shared_sink_once(tmp_path):
    from dtf_tpu.serve import Router, install_serve_fault

    clk = _Clock()
    sink = LogSink(str(tmp_path / "sink"), rotate_bytes=0)
    router = Router([_FakeSpecEngine(), _FakeSpecEngine()], clock=clk,
                    health=False, log_sink=sink)
    plan = ServeFaultPlan.parse("corrupt_log_record@5")
    install_serve_fault(plan, router, sleep=clk.advance,
                        emit=lambda line: None)
    assert sink._corrupt_at == 5                 # armed exactly once
    plan = ServeFaultPlan.parse("crash_in_log_rotate@1")
    install_serve_fault(plan, router, sleep=clk.advance,
                        emit=lambda line: None)
    assert sink._crash_rotate_at == 1
    # sinkless fleets take the verbs as a no-op (chaos matrix composes)
    bare = Router([_FakeSpecEngine()], clock=clk, health=False)
    install_serve_fault(plan, bare, sleep=clk.advance,
                        emit=lambda line: None)


# ---------------------------------------------------------------------------
# scheduler: the _retire write point + per-version acceptance
# ---------------------------------------------------------------------------

class _FakeSpecEngine:
    """Host-only SPEC engine for the scheduler's (k+1)-wide tick contract:
    2-D (toks, dones) + per-slot n_emit, with a flippable param_version —
    enough to drive the sink write point and the per-version buckets."""

    n_slots = 2
    max_len = 64
    prefill_chunk = 64
    spec_k = 2
    param_version = 0

    def prefill_chunk_into(self, slot, prompt, chunk_i, *, start=0, **kw):
        return int(prompt[0]) % 7, False

    def decode(self, **kw):
        n = self.n_slots
        toks = np.arange(n * (self.spec_k + 1),
                         dtype=np.int32).reshape(n, -1) % 7 + 1
        dones = np.zeros((n, self.spec_k + 1), bool)
        n_emit = np.full((n,), 2, np.int32)      # 1 of 2 proposals accepted
        return toks, dones, n_emit


def test_scheduler_sinks_done_requests_with_version_and_acceptance(tmp_path):
    from dtf_tpu.serve import Request, Scheduler

    clk = _Clock()
    d = str(tmp_path / "sink")
    sink = LogSink(d, rotate_bytes=0)
    eng = _FakeSpecEngine()
    sched = Scheduler(eng, clock=clk, log_sink=sink, replica_index=3)
    r0 = sched.submit(Request(prompt=[5, 6], max_new=4))
    sched.run_until_idle()
    eng.param_version = 1                        # a draft-only swap landed
    r1 = sched.submit(Request(prompt=[2], max_new=4))
    sched.run_until_idle()
    sink.close()

    acc = sched.accept_by_version()
    assert set(acc) == {0, 1}
    for prop, accepted in acc.values():
        assert prop > 0 and 0 <= accepted < prop
    st = sched.stats()
    assert "serve_spec_accept_rate_v0" in st
    assert "serve_spec_accept_rate_v1" in st

    src = ServeLogSource(d, 8)
    assert src.n_records == 2
    recs = sorted((decode_record(ln) for ln in src._lines),
                  key=lambda r: r["rid"])
    assert [r["rid"] for r in recs] == [r0, r1]
    assert [r["version"] for r in recs] == [0, 1]
    for rec in recs:
        assert rec["replica"] == 3 and rec["status"] == "done"
        assert len(rec["tokens"]) == 4           # max_new honored
        assert rec["proposed"] > 0 and rec["accepted"] >= 0
        assert rec["ttft_s"] is not None and rec["latency_s"] is not None
    assert recs[0]["prompt"] == [5, 6]
    # the served tokens round-trip into training rows through the source
    ex = ServeLogSource(d, 4, min_version=1).example(0)
    np.testing.assert_array_equal(
        ex["labels"], ([2] + recs[1]["tokens"])[-4:])


def test_router_threads_one_sink_and_reports_fleet_acceptance(tmp_path):
    from dtf_tpu.serve import Request, Router

    clk = _Clock()
    sink = LogSink(str(tmp_path / "sink"), rotate_bytes=0)
    router = Router([_FakeSpecEngine(), _FakeSpecEngine()], clock=clk,
                    health=False, log_sink=sink)
    rids = [router.submit(Request(prompt=[i + 1], max_new=3))
            for i in range(4)]
    router.drain()
    assert all(router.poll(r)["status"] == "done" for r in rids)
    st = router.stats()
    assert st["router_log_sink_records"] == 4.0
    assert "router_spec_accept_rate_v0" in st
    fleet = router.accept_by_version()
    assert set(fleet) == {0}
    per_replica = [s.accept_by_version().get(0, (0, 0))
                   for s in router.schedulers]
    assert fleet[0] == (sum(p for p, _ in per_replica),
                        sum(a for _, a in per_replica))
    sink.close()
    # records from BOTH replicas share one shard sequence
    src = ServeLogSource(sink.dir, 8)
    replicas = {decode_record(ln)["replica"] for ln in src._lines}
    assert replicas == {0, 1}


# ---------------------------------------------------------------------------
# fences: srclint + no-backend imports
# ---------------------------------------------------------------------------

def test_srclint_fences_logsink_backend_imports(tmp_path):
    from dtf_tpu.analysis import srclint

    d = tmp_path / "serve"
    d.mkdir()
    bad = d / "logsink.py"
    bad.write_text("import jax\n")
    probs = [p for p in srclint.lint_file(str(bad))
             if "without a backend" in p]
    assert probs and "serve/logsink" in probs[0]
    # the shipped module stays finding-free
    real = os.path.join(ROOT, "dtf_tpu", "serve", "logsink.py")
    assert not [p for p in srclint.lint_file(real)
                if "without a backend" in p]


def test_flywheel_modules_import_without_backend(tmp_path,
                                                 cpu_sim_subprocess_env):
    """Dynamic twin of the fences: the sink (loaded by file location —
    serve/__init__ owns the jax imports) writes shards and the servelog
    source mounts them, in a child whose jax/jaxlib/tensorflow imports
    are POISONED — the flywheel's host plane runs on chipless machines."""
    poison = tmp_path / "poison"
    for mod in ("jax", "tensorflow", "jaxlib"):
        p = poison / mod
        p.mkdir(parents=True)
        (p / "__init__.py").write_text(
            "raise ImportError('no backend on this machine')\n")
    env = dict(cpu_sim_subprocess_env)
    env["PYTHONPATH"] = f"{poison}{os.pathsep}{ROOT}"
    code = (
        "import importlib.util, os\n"
        f"spec = importlib.util.spec_from_file_location('dtf_logsink',\n"
        f"    os.path.join({ROOT!r}, 'dtf_tpu', 'serve', 'logsink.py'))\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "sink = m.LogSink('sink', rotate_bytes=1)\n"
        "for i in range(3):\n"
        "    sink.record({'rid': i, 'version': 0, 'status': 'done',\n"
        "                 'prompt': [1, 2], 'tokens': [3, 4, 5],\n"
        "                 'proposed': 2, 'accepted': 1})\n"
        "sink.close()\n"
        "from dtf_tpu.data.stream import ServeLogSource\n"
        "src = ServeLogSource('sink', 4)\n"
        "assert src.n_records == 3\n"
        "assert src.example(0)['input_ids'].shape == (4,)\n"
        "from dtf_tpu.fault.inject import ServeFaultPlan\n"
        "for v in ('corrupt_log_record@1', 'crash_in_log_rotate@0'):\n"
        "    assert ServeFaultPlan.parse(v).kind == v.split('@')[0]\n"
        "print('NO_BACKEND_OK')\n")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=120,
                          cwd=str(tmp_path))
    assert "NO_BACKEND_OK" in proc.stdout, (proc.stdout, proc.stderr)


# ---------------------------------------------------------------------------
# slow: the full circle through the real launchers
# ---------------------------------------------------------------------------

def _env(**extra):
    env = dict(os.environ)
    env.pop("DTF_FAULT_INJECT", None)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = ROOT
    return {**env, **extra}


def _run(script, *args, timeout=420, env=None):
    # the launchers default to --backend=tpu and refuse anything else
    args = ("--backend=cpu", *args)
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "scripts", script), *args],
        env=env or _env(), capture_output=True, text=True, timeout=timeout)
    assert proc.returncode == 0, (
        f"{script} rc={proc.returncode}\n{proc.stdout[-1500:]}\n"
        f"{proc.stderr[-1500:]}")
    return proc


def _json_line(proc):
    return json.loads([ln for ln in proc.stdout.splitlines()
                       if ln.startswith("{")][-1])


def _token_rows(proc):
    return sorted(ln for ln in proc.stdout.splitlines()
                  if ":" in ln and not ln.startswith("{")
                  and ln.split(":")[0].isdigit())


@pytest.mark.slow
def test_flywheel_full_circle_serve_distill_swap_e2e(tmp_path):
    """Serve with a sink → mount the logged traffic as a stream source →
    distill a 1-layer draft from the served checkpoint → publish → a live
    fleet rolls a DRAFT-ONLY swap — emitted tokens byte-identical to a
    no-swap twin, per-version acceptance spanning both draft versions."""
    base = str(tmp_path / "base")
    sink = str(tmp_path / "sink")
    pub = str(tmp_path / "pub")
    reqs = "5,9,2;5,9,2,7,1,3;1,2,3,4,5;8,8;2,4,6,8;3,1,4"

    _run("train_gpt.py", "--size=tiny", "--train_steps=3",
         "--batch_size=8", "--seq_len=32", "--checkpoint_every=3",
         f"--logdir={base}")

    # 1. the fleet records its traffic
    proc = _run("serve_gpt.py", f"--logdir={base}", "--spec_k=2",
                "--draft_layers=1", f"--log_sink_dir={sink}",
                f"--requests={reqs}", "--n_new=8", "--max_len=48",
                "--n_slots=2")
    stats = _json_line(proc)
    assert stats["request_statuses"] == {"done": 6}
    assert stats["log_sink"]["records"] == 6
    assert "0" in stats["accept_by_version"]
    assert os.path.exists(os.path.join(sink, MANIFEST_BASENAME))

    # 2. the logged traffic trains a fresh draft (init from the served
    #    checkpoint's first layer), published on the PR 14 rails
    spec = {"sources": [{"name": "traffic", "kind": "servelog",
                         "path": sink, "weight": 1}]}
    dlog = str(tmp_path / "distill")
    _run("train_gpt.py", "--distill_draft=1", f"--distill_from={base}",
         f"--stream_spec={json.dumps(spec)}", f"--logdir={dlog}",
         f"--publish_dir={pub}", "--publish_every=3", "--train_steps=6",
         "--batch_size=8", "--seq_len=32", "--checkpoint_every=6")
    from dtf_tpu.publish import read_manifest as read_pub
    newest = read_pub(pub)["version"]
    assert newest >= 1
    dman = json.load(open(os.path.join(dlog, "ckpt",
                                       "model_config.json")))
    assert dman["draft_layers"] == 1 and dman["layers"] == 1
    assert dman["distilled_from"] == base

    # 3. a live fleet rolls the distilled draft in — tokens IDENTICAL to
    #    a twin that never swaps (the verifier owns the rng chain)
    fleet_args = [f"--logdir={base}", "--spec_k=2", "--draft_layers=1",
                  "--replicas=2", "--n_slots=2", "--max_len=48",
                  f"--requests={reqs}", "--n_new=8", "--emit_tokens"]
    swapped = _run("serve_gpt.py", *fleet_args,
                   f"--draft_publish_dir={pub}", "--swap_poll_ticks=1",
                   "--canary_ticks=1")
    plain = _run("serve_gpt.py", *fleet_args)
    assert _token_rows(swapped) == _token_rows(plain)
    st = _json_line(swapped)
    assert st["final_version"] >= 1
    assert st["router_swaps"] >= 1.0
    assert len(st["accept_by_version"]) >= 2     # both draft versions saw
    for v, (prop, acc) in st["accept_by_version"].items():
        assert prop > 0 and acc >= 0
