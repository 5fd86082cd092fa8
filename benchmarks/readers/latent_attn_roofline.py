"""The latent decode-attention kernel against its roofline, percent.

Kernel time: the summed device durations of the instructions whose text
matches ``kernel`` in the traced slice (one call a layer of every decode
step). Least time: ``lib.mla_moe_cost.latent_attention_cost`` of one
layer's step — the live latent rows of the active slots read once, every
head's scores and values over them (the program's counters: active slots
from the routed picks, cached positions summed over the active slots; means
over the window's decode steps) — times the layers, times the executions of
``program`` in the slice. Returns nothing where no such instruction ran or
a counter is missing."""

from benchmarks.lib import mla_moe_cost, xtrace
from benchmarks.lib.flops import roofline_least_seconds
from benchmarks.readers.decode_hbm_roofline import counter_means, load_config


def read(obs, *, config_file: str, kernel: str,
         program: str = "jit_decode_fn"):
    trace = obs.get("trace")
    if trace is None or obs["peaks"] is None:
        return None
    seconds, count = xtrace.op_seconds(trace, kernel)
    means = counter_means(obs, ("picks", "cache_positions"))
    if not count or means is None:
        return None
    config = load_config(config_file)
    flops, nbytes = mla_moe_cost.latent_attention_cost(
        config, slots=means["picks"] / config["num_experts_per_tok"],
        cache_positions=means["cache_positions"])
    steps = len(xtrace.module_durations(trace, program))
    least = (steps * config["num_hidden_layers"]
             * roofline_least_seconds(flops, nbytes, obs["peaks"])[0])
    return 100.0 * least / seconds
