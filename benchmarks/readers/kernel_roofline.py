"""One flash-attention kernel's share of its roofline, in percent, with the
kernel found by the name the program gave it.

Kernel time: the summed device durations, per traced step, of the
instructions whose text matches ``kernel`` inside the steady window. An
``XLA Ops`` event is named by the instruction's whole text, which begins
with its name, so ``kernel`` is anchored with ``^``: ``^%?\\w*dtf_flash_fwd``
finds ``%dtf_flash_fwd.3 = ...`` (the kernel under a Flax module) and
``%jvp_dtf_flash_fwd_.1 = ...`` (called bare under ``jax.grad``), and never
an instruction that merely reads the kernel's output. Least time:
``max(FLOPs / peak FLOP/s, bytes / peak bytes/s)`` of the forward
(``backward`` false) or the backward (true: dq and dk/dv together are one
backward) by ``lib.flops``, times the calls a step makes — the reckoning of
``flash_roofline``, which adds both halves and finds the kernels by operand
shape. The two halves, weighted by their least times, give that reader's
number back. Returns nothing where no such instruction ran.
"""

from benchmarks.lib import xtrace
from benchmarks.lib.flops import flash_attention_cost, roofline_least_seconds


def read(obs, *, kernel: str, backward: bool):
    trace, v = obs.get("trace"), obs["values"]
    if trace is None or obs["peaks"] is None:
        return None
    seconds, count = xtrace.op_seconds(trace, kernel)
    steps = len(xtrace.module_durations(trace))
    if not count or not steps:
        return None
    att = v["attention"]
    flops, nbytes = flash_attention_cost(
        batch=v["device_micro_batch"], heads=att["heads"],
        t_q=v["seq_len"], t_k=v["seq_len"], d_head=att["d_head"],
        causal=att["causal"], backward=backward)
    least = roofline_least_seconds(flops, nbytes, obs["peaks"])[0]
    calls = att["calls_per_micro_batch"] * v["grad_accum"]
    return 100.0 * least * calls / (seconds / steps)
