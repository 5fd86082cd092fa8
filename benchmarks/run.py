"""Run one cell of the benchmark once, in this process.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data: ``BENCHMARK.json`` names its configuration,
its traffic mix and its metrics; ``benchmarks/traffic/<traffic>.json`` names
the driver (``"kind"``) and ``benchmarks/metrics/<metric>.json`` the reader.
This file knows no cell, configuration or metric by name. The last line of
the output is the result; earlier lines that start with ``#`` are notes.

``--rehearse 1`` runs the cell's ``rehearse`` sizes on whatever JAX finds
(``JAX_PLATFORMS=cpu``), to prove the control flow; its last line holds no
metric, because a number from a CPU is not a device metric. Without it, a
device that is not in the peak table is an error: there is no fallback.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse                     # noqa: E402
import importlib                    # noqa: E402
import json                         # noqa: E402
import math                         # noqa: E402
import os                           # noqa: E402
import sys                          # noqa: E402
import types                        # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)            # the program lies beside benchmarks/


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def by_name(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")
    return found[0]


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def read_metric(root, metric: dict, obs: dict):
    """The metric's own reader on this run's observations; None where it
    finds nothing to read."""
    spec = load_json(root, "benchmarks", "metrics", metric["name"] + ".json")
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    return reader.read(obs, **spec.get("args", {}))


def main(argv=None, root=ROOT) -> int:
    """``root`` holds ``BENCHMARK.json`` and the data files; the tests point
    it at a copy to which they have added cells."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    manifest = load_json(root, "BENCHMARK.json")
    cell = by_name(manifest["workloads"], args.workload, "workload")
    config_entry = by_name(manifest["configs"], cell["config"], "config")
    config = load_json(root, config_entry["file"])
    traffic = load_json(root, "benchmarks", "traffic",
                        cell["traffic"] + ".json")
    if args.rehearse:
        config = {**config, **config.get("rehearse", {})}
        traffic = {**traffic, **traffic.get("rehearse", {})}
    seconds = (args.seconds if args.seconds is not None
               else manifest["run_seconds"])

    import jax

    from benchmarks.lib import peaks as peak_table, xtrace
    from benchmarks.lib.memory import MemoryWatch
    from dtf_tpu.cli.launch import enable_compile_cache

    enable_compile_cache()
    t_imported = time.perf_counter()
    devices = jax.devices()
    dev = devices[0]
    if len(devices) < cell["chips"]:
        print(f"# cell needs {cell['chips']} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    # a device outside the table raises here: no fallback to the CPU
    peaks = None if args.rehearse else peak_table.peaks_for(dev.device_kind)
    memory = MemoryWatch(devices[:cell["chips"]])
    t_devices = time.perf_counter()

    # set-up's phases tile it, each starting where the one before it ended:
    # the driver's first (init_s) holds its import and the program's
    driver = importlib.import_module(f"benchmarks.drivers.{traffic['kind']}")
    obs = driver.run(types.SimpleNamespace(
        config=config, traffic=traffic, seed=args.seed, seconds=seconds,
        trace=bool(args.trace), chips=cell["chips"], memory=memory,
        t_setup=t_devices))
    obs["peaks"] = peaks
    obs["chips"] = cell["chips"]
    values = obs["values"]
    values["setup_s"] = obs["window_start"] - T_PROCESS
    values["setup_imports_s"] = t_imported - T_PROCESS
    values["setup_devices_s"] = t_devices - t_imported
    phases = {k: v for k, v in values.items() if k.startswith("setup_")}
    for key, note in {"setup_phases": phases, **obs["notes"]}.items():
        print(f"# {key}: {json.dumps(note)}", flush=True)

    declared = manifest["per_layer" if args.trace else "end_to_end"]
    metrics = {}
    for metric in declared:
        if applies(metric, cell["name"]):
            value = read_metric(root, metric, obs)
            if value is not None:
                metrics[metric["name"]] = {"value": float(value),
                                           "unit": metric["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    print(f"# memory_stats: {json.dumps(dev.memory_stats() or {})}",
          flush=True)
    # lib/memory.py says what is read; absent where the runtime keeps no
    # such counters
    device.update(memory.report() or {})
    result = {"correct": bool(obs["correct"]),
              "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": metrics,
              "device": device}
    if obs["trace"] is not None:
        summary = xtrace.device_summary(obs["trace"])
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            result["breakdown"] = summary["breakdown"]
    if args.rehearse:
        # a rehearsal proves the control flow; it reports no metric
        result["rehearsal"] = {"would_report": sorted(metrics)}
        result["metrics"] = {}
    # last, in the line and on stderr: what `correct` compared, by limit
    result["limits"] = {
        name: {"value": value if math.isfinite(value) else str(value),
               "limit": limit}
        for name, (value, limit) in obs["limits"].items()}
    print(json.dumps(result), flush=True)
    for name, (value, limit) in obs["limits"].items():
        print(f"# {name}: {value} (limit {limit})", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
