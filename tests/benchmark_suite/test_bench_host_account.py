"""The host's own account, as the benchmark reads it: each program's trace,
lower and compile-or-load seconds at set-up, and a serve tick's CPU time,
preemptions and garbage-collection pauses — eight ``per_layer`` entries
read by the ``span`` and ``span_idle`` readers that were there."""

import contextlib
import functools
import io
import json
import os

import pytest

from benchmarks import run as bench_run
from benchmarks.lib import xtrace
from benchmarks.readers import span_idle

ROOT = bench_run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]
SERVE_CELLS = [w["name"] for w in MANIFEST["workloads"]
               if "serve" in w["traffic"]]

PROGRAM = ["program_trace_s", "program_lower_s", "program_compile_s",
           "program_cache_hit_pct"]
TICK = ["tick_cpu_ms_mean", "tick_preempted_mean", "gc_pause_ms_per_tick"]
DEVICE = ["gc_idle_ms_per_tick"]


def entry(name: str) -> dict:
    return next(m for m in MANIFEST["per_layer"] if m["name"] == name)


def spec(name: str) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_the_eight_are_declared_with_their_cells_and_readers():
    for name in PROGRAM:
        assert entry(name)["workloads"] == CELLS
        assert (entry(name)["layer"], entry(name)["moves"]) == (
            "executor", "setup_s")
    for name in TICK + DEVICE:
        assert entry(name)["workloads"] == SERVE_CELLS
        assert entry(name)["moves"] == "serve_tokens_per_s"
    readers = {name: spec(name)["reader"] for name in PROGRAM + TICK + DEVICE}
    assert set(readers.values()) == {"span", "span_idle"}
    assert readers["gc_idle_ms_per_tick"] == "span_idle"
    # none is a tile of set-up (test_bench_run_cells.py reads every
    # `setup_*` name as one) nor a slice of the engine's idle split
    # (`idle_ms_per_tick.*`): the gc metric is a cause, not a fourth slice
    for name in PROGRAM + TICK + DEVICE:
        assert not name.startswith(("setup_", "idle_ms_per_tick."))
        assert spec(name)["note"]


@functools.cache
def rehearsal(cell: str) -> tuple:
    """(last line, set-up phases, the values the readers returned) of one
    traced rehearsal on the CPU."""
    values = {}
    read = bench_run.read_metric

    def recording(root, metric, obs):
        value = read(root, metric, obs)
        values[metric["name"]] = value
        return value

    out = io.StringIO()
    bench_run.read_metric = recording
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.main(["--workload", cell, "--seed",
                                 str(2**31 + 37), "--seconds", "0.5",
                                 "--trace", "1", "--rehearse", "1"])
    finally:
        bench_run.read_metric = read
    assert rc == 0
    lines = out.getvalue().splitlines()
    note = "# setup_phases: "
    phases = json.loads(next(x for x in lines if x.startswith(note))[
        len(note):])
    return json.loads(lines[-1]), phases, values


@pytest.mark.parametrize("cell", ["gpt2m-serve-closed32",
                                  "gpt2m-train-b8s1024"])
def test_a_traced_rehearsal_reports_the_host_account(cell):
    """A serve cell reports the three tick metrics and the four program
    metrics, a train cell the four program metrics; the device metric
    needs a device plane, which a CPU has not."""
    last, phases, values = rehearsal(cell)
    assert last["correct"] is True
    would = set(last["rehearsal"]["would_report"])
    want = PROGRAM + (TICK if cell in SERVE_CELLS else [])
    assert set(want) <= would
    assert not set(DEVICE) & would
    # the compiles lie inside the phase that compiles
    phase = "setup_engine_s" if cell in SERVE_CELLS else "setup_warmup_s"
    compiling = sum(values[n] for n in PROGRAM[:3])
    assert 0 < compiling <= phases[phase]
    assert 0 <= values["program_cache_hit_pct"] <= 100
    if cell in SERVE_CELLS:
        assert values["tick_cpu_ms_mean"] > 0
        assert values["tick_preempted_mean"] >= 0
        assert values["gc_pause_ms_per_tick"] >= 0


def test_gc_idle_reads_device_idle_under_a_collection_per_tick():
    """Two ticks; one collection lies over 6 ns of the device's idle time,
    another while the device was busy: 6 ns over 2 ticks."""
    trace = xtrace.Trace(
        ops={"/device:TPU:0": [("a", 0.0, 10.0), ("b", 20.0, 10.0),
                               ("c", 40.0, 10.0)]},
        modules={"/device:TPU:0": [("jit_decode_fn", 0.0, 50.0)]},
        host=[("dtf.serve.tick", 0.0, 25.0), ("dtf.serve.tick", 25.0, 25.0),
              ("dtf.host.gc", 31.0, 6.0), ("dtf.host.gc", 45.0, 2.0)])
    args = {**spec("gc_idle_ms_per_tick")["args"], "scale": 1.0}
    assert span_idle.read({"trace": trace}, **args) == pytest.approx(
        6.0 / 1e9 / 2)
    # a run with no collection in its slice reads 0, and without a tick
    # span (the parent's, untraced) nothing
    trace.host = trace.host[:2]
    assert span_idle.read({"trace": trace}, **args) == 0.0
    trace.host = []
    assert span_idle.read({"trace": trace}, **args) is None
