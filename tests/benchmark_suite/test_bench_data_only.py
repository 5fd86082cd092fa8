"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric as FILES, and edits none that is there.

In a copy of ``benchmarks/`` this adds one file of each kind, with
``chips: 4`` and ``mesh: {data: 4}``, appends the entries to the copy's
``BENCHMARK.json``, and runs the new cell on four of the eight virtual CPU
devices ``tests/conftest.py`` forces. It is the rehearsal of the four-chip
cell that ``PERF.md``'s Open questions keep for the next benchmark PR.
"""

import hashlib
import json
import os
import shutil

from benchmarks import run as bench_run

ROOT = bench_run.ROOT


def digest(root) -> dict:
    out = {}
    for base, _, files in os.walk(os.path.join(root, "benchmarks")):
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def test_a_four_chip_cell_is_added_by_files_alone(tmp_path, capsys):
    root = str(tmp_path / "copy")
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)

    def write(obj, *parts):
        with open(os.path.join(root, *parts), "w") as f:
            json.dump(obj, f)

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "gpt2-medium.json")) as f:
        config = json.load(f)
    config.update(n_layer=12, n_embd=768, n_head=12, n_inner=3072)  # "small"
    write(config, "benchmarks", "configs", "gpt2-small.json")
    write({"kind": "train", "batch": 32, "seq_len": 1024, "grad_accum": 1,
           "fence_every": 10, "mesh": {"data": 4}, "trace_steps": 5,
           "optimizer": {"lr": 1e-4, "weight_decay": 0.01},
           "warmup_steps": 3, "check_seq_len": 1024,
           "rehearse": {"batch": 8, "seq_len": 32, "check_seq_len": 32,
                        "fence_every": 2, "trace_steps": 2}},
          "benchmarks", "traffic", "train-dp4-b32-s1024.json")
    write({"reader": "span", "args": {"span": "hooks", "stat": "p50_s",
                                      "scale": 1000.0}},
          "benchmarks", "metrics", "hooks_ms_p50.json")

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "gpt2-small", "source": config["source"],
        "file": "benchmarks/configs/gpt2-small.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "gpt2s-train-dp4", "config": "gpt2-small",
        "traffic": "train-dp4-b32-s1024", "chips": 4, "why": "test"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "train_tokens_per_s" in (metric["name"], metric.get("moves")):
            metric["workloads"].append("gpt2s-train-dp4")
    manifest["per_layer"].append({
        "name": "hooks_ms_p50", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "trainer loop",
        "moves": "train_tokens_per_s", "workloads": ["gpt2s-train-dp4"]})
    write(manifest, "BENCHMARK.json")

    outs = []
    for trace in ("0", "1"):
        rc = bench_run.main(["--workload", "gpt2s-train-dp4", "--seed", "7",
                             "--seconds", "0.5", "--trace", trace,
                             "--rehearse", "1"], root=root)
        assert rc == 0
        lines = capsys.readouterr().out.splitlines()
        outs.append(json.loads(lines[-1]))
    assert all(o["correct"] and o["attempted"] > 0 for o in outs)
    assert outs[0]["device"]["count"] >= 4
    assert outs[0]["rehearsal"]["would_report"] == ["setup_s",
                                                    "train_tokens_per_s"]
    # the new per-layer metric is read beside the ones that were there
    assert {"hooks_ms_p50", "data_wait_ms_p50"} <= set(
        outs[1]["rehearsal"]["would_report"])
    # three files were added and none that was there was touched
    after = digest(root)
    assert {k: after[k] for k in before} == before
    assert len(after) == len(before) + 3
