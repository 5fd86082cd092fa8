"""``run.py`` end to end on the CPU, at each cell's ``rehearse`` sizes; the
contract of its last line; and that a CPU never yields a device metric."""

import contextlib
import functools
import io
import json
import os
import shutil

import pytest

from benchmarks import run as bench_run

ROOT = bench_run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def declared(kind: str, cell: str) -> set:
    return {m["name"] for m in MANIFEST[kind]
            if "workloads" not in m or cell in m["workloads"]}


@functools.cache
def rehearsal(cell: str, trace: int) -> tuple:
    """(exit code, lines printed) of one rehearsal, run once for the tests
    below that read it."""
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 11),
                             "--seconds", "0.5", "--trace", str(trace),
                             "--rehearse", "1"])
    return rc, [x for x in out.getvalue().splitlines() if x.strip()]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu(cell, trace):
    rc, lines = rehearsal(cell, trace)
    out = json.loads(lines[-1])
    assert rc == 0
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0, lines[-8:]
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    # a number from a CPU is never written under a device metric's name
    assert out["metrics"] == {}
    assert "busy_s" not in out["device"] and "breakdown" not in out
    # what `correct` compared, each reading beside its limit, comes last
    assert list(out)[-1] == "limits" and out["limits"]
    for name, entry in out["limits"].items():
        assert set(entry) == {"value", "limit"}, name
    would = set(out["rehearsal"]["would_report"])
    kind = "per_layer" if trace else "end_to_end"
    assert would <= declared(kind, cell)
    if not trace:
        # every end-to-end metric of the cell is computed, setup_s among them
        assert would == declared(kind, cell) and "setup_s" in would
    else:
        # trace readers find no device plane on a CPU and return nothing;
        # every counter and set-up phase the cell declares is read
        assert {m["name"] for m in MANIFEST["per_layer"]
                if m["name"] in declared(kind, cell)
                and (m["source"] == "program_counter"
                     or m["name"].startswith("setup_"))} <= would


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_set_up_phases_tile_setup_s(cell, trace):
    """The phases start where the one before ended, the first at the
    process's start and the last where the window opens: they add up to
    ``setup_s``, and the cell declares each."""
    rc, lines = rehearsal(cell, trace)
    assert rc == 0
    note = "# setup_phases: "
    phases = json.loads(next(x for x in lines if x.startswith(note))[
        len(note):])
    total = phases.pop("setup_s")
    assert set(phases) == {name for name in declared("per_layer", cell)
                           if name.startswith("setup_")}
    assert all(v > 0 for v in phases.values()), phases
    assert sum(phases.values()) == pytest.approx(total, abs=1e-6)


def test_without_rehearse_a_cpu_is_refused_not_fallen_back_to(capsys):
    with pytest.raises(ValueError, match="no published peaks"):
        bench_run.main(["--workload", CELLS[0], "--seed", "1",
                        "--seconds", "0.5", "--trace", "0"])
    assert capsys.readouterr().out.strip() == ""


def test_a_cell_that_needs_more_chips_than_jax_found_prints_no_result(
        tmp_path, capsys):
    root = tmp_path / "copy"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = json.loads(json.dumps(MANIFEST))
    manifest["workloads"][0]["chips"] = 64
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    rc = bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds",
                         "0.5", "--trace", "0", "--rehearse", "1"],
                        root=str(root))
    assert rc != 0 and capsys.readouterr().out.strip() == ""


SERVE_CELLS = [w["name"] for w in MANIFEST["workloads"]
               if "serve" in w["traffic"]]


@pytest.mark.parametrize("cell", SERVE_CELLS)
def test_a_token_altered_where_it_is_produced_reads_not_correct(
        cell, monkeypatch):
    """The timed path broken underneath a whole rehearsed run: each decode
    step hands the scheduler the token after the one it chose. Every
    request ends as before, and ``correct`` has to come out false."""
    from dtf_tpu.serve.engine import DecodeEngine

    decode = DecodeEngine.decode

    def altered(self, **kw):
        toks, done = decode(self, **kw)
        return (toks + 1) % self.cfg.vocab_size, done

    monkeypatch.setattr(DecodeEngine, "decode", altered)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 13),
                             "--seconds", "0.5", "--trace", "0",
                             "--rehearse", "1"])
    line = json.loads(out.getvalue().splitlines()[-1])
    assert rc == 0 and line["attempted"] > 0 and line["failed"] == 0
    assert line["correct"] is False, line["limits"]
