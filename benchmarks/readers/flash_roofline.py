"""The flash-attention kernels' share of their roofline, in percent.

Kernel time: the summed device durations, per traced step, of the Pallas
custom calls (``custom_call_target="tpu_custom_call"``) whose first operand
is the attention's ``bf16[batch*heads, seq, d_head]``: the forward kernel
and the two backward kernels (dq; dk and dv). The program gives its kernels
no name of their own (the trace shows ``jvp__``, ``transpose_jvp___``), so
they are found by that shape. Least time: ``max(FLOPs / peak FLOP/s, bytes /
peak bytes/s)`` of forward plus backward by ``lib.flops``, times the calls a
step makes. Returns nothing where no such kernel ran (the dense path).
"""

from benchmarks.lib import xtrace
from benchmarks.lib.flops import flash_attention_cost, roofline_least_seconds


def read(obs):
    trace, v = obs.get("trace"), obs["values"]
    if trace is None or obs["peaks"] is None:
        return None
    att = v["attention"]
    bh = v["device_micro_batch"] * att["heads"]
    pattern = (rf"custom-call\(bf16\[{bh},{v['seq_len']},{att['d_head']}\]"
               r'.*custom_call_target="tpu_custom_call"')
    seconds, count = xtrace.op_seconds(trace, pattern)
    steps = len(xtrace.module_durations(trace))
    if not count or not steps:
        return None
    least = 0.0
    for backward in (False, True):
        flops, nbytes = flash_attention_cost(
            batch=v["device_micro_batch"], heads=att["heads"],
            t_q=v["seq_len"], t_k=v["seq_len"], d_head=att["d_head"],
            causal=att["causal"], backward=backward)
        least += roofline_least_seconds(flops, nbytes, obs["peaks"])[0]
    calls = att["calls_per_micro_batch"] * v["grad_accum"]
    return 100.0 * least * calls / (seconds / steps)
