"""The grouped expert product's kernel against its roofline, percent, over
the prefill and decode calls of the traced slice together.

Kernel time: the summed device durations of the instructions whose text
matches ``kernel`` (anchored: an ``XLA Ops`` event is named by the
instruction's whole text, which begins with its name). Least time:
``lib.moe_cost.grouped_ffn_cost`` per expert layer — for a decode step with
the program's counters (picks and experts touched, means over the window's
steps), for a prefill chunk with its counted picks and the experts that
many uniform choices touch (no counter reads a chunk's routing back) —
times the executions of ``decode_program`` / ``prefill_program`` in the
slice. Returns nothing where no such instruction ran or a counter is
missing."""

from benchmarks.lib import moe_cost, xtrace
from benchmarks.lib.flops import roofline_least_seconds
from benchmarks.readers.decode_hbm_roofline import counter_means, load_config


def read(obs, *, config_file: str, kernel: str,
         decode_program: str = "jit_decode_fn",
         prefill_program: str = "jit_prefill_fn"):
    trace = obs.get("trace")
    if trace is None or obs["peaks"] is None:
        return None
    seconds, count = xtrace.op_seconds(trace, kernel)
    means = counter_means(obs, ("picks", "experts_touched", "prefill_picks"))
    if not count or means is None:
        return None
    config = load_config(config_file)
    calls = ((len(xtrace.module_durations(trace, decode_program)),
              means["picks"], means["experts_touched"]),
             (len(xtrace.module_durations(trace, prefill_program)),
              means["prefill_picks"], moe_cost.expected_touched(
                  means["prefill_picks"], config["num_experts"])))
    least = 0.0
    for executions, pairs, touched in calls:
        flops, nbytes = moe_cost.grouped_ffn_cost(
            config, pairs=pairs, touched=touched)
        least += (executions * moe_cost.expert_layers(config)
                  * roofline_least_seconds(flops, nbytes, obs["peaks"])[0])
    return 100.0 * least / seconds
