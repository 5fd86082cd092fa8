"""The peak table and the functions that count operations and bytes."""

import pytest

from benchmarks.lib import flops, peaks


def test_peak_table_has_the_v5e_and_refuses_any_other_device():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["bf16_flops"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(ValueError, match="no published peaks"):
            peaks.peaks_for(kind)


def test_lm_flops_per_token_is_6n_plus_attention():
    # N 1000, 2 layers, width 8, sequence 16: 6000 + 12*2*8*16 = 9072
    assert flops.lm_train_flops_per_token(
        n_matmul_params=1000, layers=2, width=8, seq_len=16) == 9072.0


def test_matmul_params_leave_out_tables_that_are_only_looked_up():
    import numpy as np
    params = {"token_embed": {"embedding": np.zeros((50, 8))},
              "layer_0": {"mlp_in": {"kernel": np.zeros((8, 32)),
                                     "bias": np.zeros(32)}},
              "lm_head": {"kernel": np.zeros((8, 50))}}
    assert flops.matmul_params(params) == 400 + 288 + 400
    # an untied head: the token table is a gather, not a matmul
    assert flops.matmul_params(params, ("token_embed",)) == 288 + 400


def test_flash_cost_against_a_hand_count():
    # batch 2, 3 heads, 4 queries x 4 keys, head size 8, bf16, not causal.
    # forward: QK^T and PV, each 2*4*4*8 = 256 FLOPs a head -> 512 * 6
    f, b = flops.flash_attention_cost(batch=2, heads=3, t_q=4, t_k=4,
                                      d_head=8, causal=False)
    assert f == 3072.0
    # Q, K, V, O: 4 tensors of 2*3*4*8 bf16 = 384 B each, + lse 2*3*4 f32
    assert b == 4 * 384 + 96
    # backward: five matmuls, and Q,K,V,O,dO in + dQ,dK,dV out
    f, b = flops.flash_attention_cost(batch=2, heads=3, t_q=4, t_k=4,
                                      d_head=8, causal=False, backward=True)
    assert f == 2.5 * 3072.0
    assert b == 8 * 384 + 96


def test_causal_flash_counts_only_the_pairs_a_query_may_see():
    full, _ = flops.flash_attention_cost(batch=1, heads=1, t_q=4, t_k=4,
                                         d_head=8, causal=False)
    causal, _ = flops.flash_attention_cost(batch=1, heads=1, t_q=4, t_k=4,
                                           d_head=8, causal=True)
    assert causal / full == 10 / 16          # 4*5/2 of 4*4 pairs


def test_roofline_names_the_peak_that_bounds():
    row = peaks.peaks_for("TPU v5 lite")
    assert flops.roofline_least_seconds(197e12, 1.0, row) == (1.0, "compute")
    assert flops.roofline_least_seconds(1.0, 819e9, row) == (1.0, "memory")
