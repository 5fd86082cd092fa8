"""A routed-expert model's whole decode step against its roofline, percent.

Least time of a step: ``lib.moe_cost.decode_step_cost`` — the always-read
weights once, the touched experts' weights once per expert layer (the
program's counter, a mean over the window's decode steps), the valid cache
positions once, over the peak HBM rate; or the step's FLOPs over the peak
FLOP rate if that is larger. Over the step's device time: the median
duration of the program whose name matches ``program`` in the traced slice
(what ``decode_device_ms_p50`` reads). ``config_file`` is the cell's
configuration, under the repo's root. Returns nothing where there is no
trace, no peak, no such program or no such counter."""

import json
import os

from benchmarks.lib import moe_cost, xtrace
from benchmarks.lib.flops import roofline_least_seconds
from benchmarks.lib.stats import percentile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def counter_means(obs, names) -> dict | None:
    """``{name: mean}`` of the ``serve_moe_<name>`` counters, or None if one
    is missing."""
    out = {}
    for name in names:
        rollup = obs["spans"].get(f"serve_moe_{name}")
        if not rollup:
            return None
        out[name] = rollup["mean_s"]
    return out


def load_config(config_file: str) -> dict:
    with open(os.path.join(ROOT, config_file)) as f:
        return json.load(f)


def read(obs, *, config_file: str, program: str = "jit_decode_fn"):
    if obs.get("trace") is None or obs["peaks"] is None:
        return None
    means = counter_means(obs, ("picks", "experts_touched",
                                "cache_positions"))
    durations = xtrace.module_durations(obs["trace"], program)
    if means is None or not durations:
        return None
    config = load_config(config_file)
    flops, nbytes = moe_cost.decode_step_cost(
        config, tokens=means["picks"] / config["num_experts_per_tok"],
        touched=means["experts_touched"],
        cache_positions=means["cache_positions"])
    least = roofline_least_seconds(flops, nbytes, obs["peaks"])[0]
    return 100.0 * least / percentile(durations, 50.0)
