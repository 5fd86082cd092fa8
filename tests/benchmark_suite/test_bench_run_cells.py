"""``run.py`` end to end on the CPU, at each cell's ``rehearse`` sizes; the
contract of its last line; and that a CPU never yields a device metric."""

import json
import os
import shutil

import pytest

from benchmarks import run as bench_run

ROOT = bench_run.ROOT
with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    MANIFEST = json.load(_f)
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def last_line(capsys) -> dict:
    lines = [x for x in capsys.readouterr().out.splitlines() if x.strip()]
    return json.loads(lines[-1])


def declared(kind: str, cell: str) -> set:
    return {m["name"] for m in MANIFEST[kind]
            if "workloads" not in m or cell in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_on_the_cpu(cell, trace, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 11),
                         "--seconds", "0.5", "--trace", str(trace),
                         "--rehearse", "1"])
    out = last_line(capsys)
    assert rc == 0
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(out)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    # a number from a CPU is never written under a device metric's name
    assert out["metrics"] == {}
    assert "busy_s" not in out["device"] and "breakdown" not in out
    would = set(out["rehearsal"]["would_report"])
    kind = "per_layer" if trace else "end_to_end"
    assert would <= declared(kind, cell)
    if not trace:
        # every end-to-end metric of the cell is computed, setup_s among them
        assert would == declared(kind, cell) and "setup_s" in would
    else:
        # trace readers find no device plane on a CPU and return nothing;
        # the span and counter readers do report
        assert would, "a traced run reported no per-layer metric at all"


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
