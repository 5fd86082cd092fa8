"""Operations and bytes the algorithms need, computed from shapes."""

from __future__ import annotations


def matmul_params(params: dict, lookup_only=()) -> int:
    """The parameters under ``params`` that a matmul multiplies: all of them
    but the tables named in ``lookup_only`` (top-level keys), which are
    gathered from and never multiplied. A table that is also the head (tied)
    is a matmul and is not named there."""
    import jax
    return sum(x.size for name, sub in params.items()
               if name not in lookup_only for x in jax.tree.leaves(sub))


def lm_train_flops_per_token(*, n_matmul_params: int, layers: int,
                             width: int, seq_len: int) -> float:
    """Forward + backward FLOPs of one token of a dense transformer:
    ``6 N + 12 L d s`` (6 per parameter for the weight matmuls, plus the
    attention scores and values; arXiv 2204.06514's accounting, the one
    ``scripts/bench_lm.py`` called ``mfu_analytic``). ``N`` leaves out the
    embedding tables that are only looked up (:func:`matmul_params`): a
    gather is no FLOP, and where the head is not tied the token table would
    otherwise be charged like a second head (11% of GPT-2 medium's count).
    Recomputed operations do not count, and a causal mask is not credited:
    the figure is the convention papers compare by, not the least work."""
    return float(6 * n_matmul_params + 12 * layers * width * seq_len)


def flash_attention_cost(*, batch: int, heads: int, t_q: int, t_k: int,
                         d_head: int, causal: bool, itemsize: int = 2,
                         backward: bool = False) -> tuple[float, float]:
    """``(flops, bytes)`` one flash-attention call needs.

    Forward: two matmuls (``Q K^T`` and ``P V``), 2 FLOPs a multiply-add,
    so ``4 B H Tq Tk D``; a causal mask needs only the pairs with
    ``k <= q``. Backward, by the FlashAttention convention: five matmuls
    (the scores again, ``dV``, ``dP``, ``dQ``, ``dK``), 2.5 times the
    forward. A kernel that recomputes more (this repo's backward runs the
    scores and ``dP`` in both of its kernels) is not credited for it.

    Bytes are the tensors that must cross HBM once: forward reads Q, K, V
    and writes O and the float32 log-sum-exp; backward reads Q, K, V, O,
    dO and the log-sum-exp and writes dQ, dK, dV.
    """
    pairs = (t_q * (t_q + 1) // 2 if causal and t_q == t_k else t_q * t_k)
    if causal and t_q != t_k:
        raise ValueError("causal cost is defined for t_q == t_k")
    matmuls = 5 if backward else 2
    flops = 2.0 * matmuls * batch * heads * pairs * d_head
    q_bytes = batch * heads * t_q * d_head * itemsize
    k_bytes = batch * heads * t_k * d_head * itemsize
    lse_bytes = batch * heads * t_q * 4
    if backward:
        nbytes = 4 * q_bytes + 4 * k_bytes + lse_bytes
    else:
        nbytes = 2 * q_bytes + 2 * k_bytes + lse_bytes
    return flops, float(nbytes)


def roofline_least_seconds(flops: float, nbytes: float, peaks: dict
                           ) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_compute = flops / peaks["bf16_flops"]
    t_memory = nbytes / peaks["hbm_bytes_per_s"]
    return ((t_compute, "compute") if t_compute >= t_memory
            else (t_memory, "memory"))
