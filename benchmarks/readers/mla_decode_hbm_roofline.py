"""A latent-attention decoder's whole decode step against its roofline,
percent, where the chip holds a share of the routed experts.

Least time of a step: ``lib.mla_moe_cost.decode_step_cost`` — the
always-read weights once, the touched HELD experts' weights once an expert
layer (the program's counters, means over the window's decode steps), the
live latent rows once a layer, over the peak HBM rate; or the step's FLOPs
over the peak FLOP rate if that is larger. Over the step's device time: the
median duration of the program whose name matches ``program`` in the traced
slice (what ``decode_device_ms_p50`` reads). Returns nothing where there is
no trace, no peak, no such program or no such counter (a program without
``serve_moe_held_*``, as the parent commit is)."""

from benchmarks.lib import mla_moe_cost, xtrace
from benchmarks.lib.flops import roofline_least_seconds
from benchmarks.lib.stats import percentile
from benchmarks.readers.decode_hbm_roofline import counter_means, load_config

COUNTERS = ("picks", "held_pairs", "held_touched", "cache_positions")


def read(obs, *, config_file: str, program: str = "jit_decode_fn"):
    if obs.get("trace") is None or obs["peaks"] is None:
        return None
    means = counter_means(obs, COUNTERS)
    durations = xtrace.module_durations(obs["trace"], program)
    if means is None or not durations:
        return None
    config = load_config(config_file)
    flops, nbytes = mla_moe_cost.decode_step_cost(
        config, tokens=means["picks"] / config["num_experts_per_tok"],
        held_pairs=means["held_pairs"], held_touched=means["held_touched"],
        cache_positions=means["cache_positions"])
    least = roofline_least_seconds(flops, nbytes, obs["peaks"])[0]
    return 100.0 * least / percentile(durations, 50.0)
