"""The grouped expert product's kernel against its roofline, percent, where
the chip holds a share of the routed experts, over the prefill and decode
calls of the traced slice together.

Kernel time: the summed device durations of the instructions whose text
matches ``kernel`` (anchored, as ``moe_gmm_roofline`` has it). Least time:
``lib.mla_moe_cost.grouped_ffn_cost`` per expert layer — for a decode step
with the program's counters (the pairs that landed on held experts and the
held experts touched, means over the window's steps), for a prefill chunk
with the held experts' share of its counted picks and the held experts that
many uniform choices touch (no counter reads a chunk's routing back) —
times the executions of ``decode_program`` / ``prefill_program`` in the
slice. Returns nothing where no such instruction ran or a counter is
missing."""

from benchmarks.lib import mla_moe_cost, xtrace
from benchmarks.lib.flops import roofline_least_seconds
from benchmarks.readers.decode_hbm_roofline import counter_means, load_config


def read(obs, *, config_file: str, kernel: str,
         decode_program: str = "jit_decode_fn",
         prefill_program: str = "jit_prefill_fn"):
    trace = obs.get("trace")
    if trace is None or obs["peaks"] is None:
        return None
    seconds, count = xtrace.op_seconds(trace, kernel)
    means = counter_means(obs, ("held_pairs", "held_touched",
                                "prefill_picks"))
    if not count or means is None:
        return None
    config = load_config(config_file)
    n_held = config["n_routed_experts"]
    chunk_pairs = (means["prefill_picks"] * n_held
                   / config["routed_experts_published"])
    calls = ((len(xtrace.module_durations(trace, decode_program)),
              means["held_pairs"], means["held_touched"]),
             (len(xtrace.module_durations(trace, prefill_program)),
              chunk_pairs, mla_moe_cost.expected_touched(chunk_pairs,
                                                         n_held)))
    least = 0.0
    for executions, pairs, touched in calls:
        flops, nbytes = mla_moe_cost.grouped_ffn_cost(
            config, pairs=pairs, touched=touched)
        least += (executions * mla_moe_cost.expert_layers(config)
                  * roofline_least_seconds(flops, nbytes, obs["peaks"])[0])
    return 100.0 * least / seconds
