"""Model FLOP/s utilization: tokens/s x (6N + 12 L d s) / (chips x peak).

N is counted from the training state and leaves out the embedding tables
that are only looked up (``lib.flops.matmul_params``); the peak is the bf16
peak of the table in ``lib/peaks.py``. It is the end-to-end rate times a constant: not a
kernel's roofline share, and it says nothing about idle time."""

from benchmarks.lib.flops import lm_train_flops_per_token
from benchmarks.lib.stats import rate_between_fences


def read(obs, *, series: str = "tokens_done"):
    fences, v = obs["series"].get(series), obs["values"]
    if obs["peaks"] is None or not fences or len(fences) < 2:
        return None
    per_token = lm_train_flops_per_token(
        n_matmul_params=v["n_matmul_params"], layers=v["layers"], width=v["width"],
        seq_len=v["seq_len"])
    return (100.0 * rate_between_fences(fences) * per_token
            / (obs["chips"] * obs["peaks"]["bf16_flops"]))
