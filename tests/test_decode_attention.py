"""Slot-decode attention as one in-place kernel (ops/decode_attention.py),
in interpret mode against the XLA spelling of the same step — the branch of
``models/gpt.py`` the kernel stands in for: a position-mask select writes
each active slot's new K/V row, then every query head attends its slot's
positions up to its index through a float32 softmax.

What the chip's compiler makes of the kernel is fenced in
``tests/test_chip_compile.py``; what it does to an engine's tokens, in
``tests/test_serve.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dtf_tpu.ops import decode_attention as da

# name -> slots, kv_heads, group, d_head, max_len, dtype, bytes of a block
SHAPES = {
    # GPT-2 medium's heads; two blocks of 512 positions
    "mha": (6, 16, 1, 64, 1024, jnp.bfloat16, 1 << 20),
    # the LFM2 cut's heads; four blocks of 256, so up to three idle steps
    "gqa": (6, 8, 4, 64, 1024, jnp.bfloat16, 1 << 18),
    # a float32 cache, heads that fill no tile, blocks of one tile
    "f32": (6, 4, 2, 8, 384, jnp.float32, 1 << 14),
}


def xla_step(q, k_new, v_new, cached_key, cached_value, index, active):
    """The parent's branch: ``_cache_put_rows`` at ``index % max_len`` for
    the active rows, then ``p_s >= 0`` validity over the whole leaf."""
    ck, cv, idx = cached_key, cached_value, index
    d_head, max_len = q.shape[-1], ck.shape[2]
    lane = jnp.arange(max_len)
    hit = (lane[None, :] == (idx % max_len)[:, None]) & active[:, None]
    hit = hit[:, None, :, None]
    ck = jnp.where(hit, k_new[:, :, None, :].astype(ck.dtype), ck)
    cv = jnp.where(hit, v_new[:, :, None, :].astype(cv.dtype), cv)
    p_s = idx[:, None] - jnp.remainder(idx[:, None] - lane[None, :], max_len)
    bias = jnp.where(p_s >= 0, 0.0, -jnp.inf)
    s = jnp.einsum("bkgd,bkld->bkgl", q, ck,
                   preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s * d_head ** -0.5 + bias[:, None, None, :], axis=-1)
    out = jnp.einsum("bkgl,bkld->bkgd", p.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype), ck, cv


@pytest.fixture(params=list(SHAPES))
def case(request, monkeypatch):
    """Operands of one step. The cache is random EVERYWHERE, so every slot
    is a re-used one with stale rows past its index; slot 4 is inactive
    (mid-prefill). Indices: 0, the last position of block 0, the first of
    block 1, the last of the cache, an inactive slot's, one mid-tile."""
    slots, heads, group, d_head, max_len, dtype, block_bytes = \
        SHAPES[request.param]
    monkeypatch.setattr(da, "_BLOCK_BYTES", block_bytes)
    # the module-level jit keys on shapes, not on the block's size
    da._decode_attention.clear_cache()
    block = da.block_positions(heads, d_head, max_len,
                               jnp.dtype(dtype).itemsize)
    assert max_len // block >= 2, "a case must cross a block boundary"
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    normal = lambda key, *shape: jax.random.normal(key, shape, dtype)  # noqa: E731
    args = dict(
        q=normal(keys[0], slots, heads, group, d_head),
        k_new=normal(keys[1], slots, heads, d_head),
        v_new=normal(keys[2], slots, heads, d_head),
        ck=normal(keys[3], slots, heads, max_len, d_head),
        cv=normal(keys[4], slots, heads, max_len, d_head),
        idx=jnp.array([0, block - 1, block, max_len - 1, 5, block + 130],
                      jnp.int32),
        active=jnp.array([True, True, True, True, False, True]))
    yield args
    da._decode_attention.clear_cache()


def test_matches_the_xla_step(case):
    """Outputs of the active slots within the cache dtype's rounding of the
    XLA path (a blockwise softmax sums in another order; the new row's
    probability is not rounded to the cache's dtype), and both leaves
    bit-identical to the select write: the new row where it belongs, every
    other row of every slot as it was."""
    out, ck, cv = da.decode_attention(**_operands(case))
    want, want_ck, want_cv = xla_step(**_operands(case))
    assert out.shape == want.shape and out.dtype == want.dtype
    assert jnp.array_equal(ck, want_ck) and jnp.array_equal(cv, want_cv)
    live = np.asarray(case["active"])
    err = np.abs(np.asarray(out, np.float32) - np.asarray(want, np.float32))
    tol = 2 ** -7 if case["q"].dtype == jnp.bfloat16 else 1e-5
    assert err[live].max() <= tol * np.abs(np.asarray(want, np.float32)).max()


def test_only_the_written_row_changes(case):
    """Against the leaves as they went in: an active slot differs in row
    ``idx % max_len`` alone, and there it holds the new row in the cache's
    dtype; an inactive slot's leaves are bit-identical before and after."""
    _, ck, cv = da.decode_attention(**_operands(case))
    idx, active = np.asarray(case["idx"]), np.asarray(case["active"])
    for new, old, row in ((ck, case["ck"], case["k_new"]),
                          (cv, case["cv"], case["v_new"])):
        new, old = np.asarray(new, np.float32), np.asarray(old, np.float32)
        changed = (new != old).any(axis=(1, 3))              # [slots, L]
        for s in range(len(idx)):
            where = np.flatnonzero(changed[s])
            if not active[s]:
                assert where.size == 0, (s, where)
                continue
            assert where.tolist() in ([idx[s]], []), (s, where)
            assert np.array_equal(
                new[s, :, idx[s]],
                np.asarray(row[s].astype(ck.dtype), np.float32))


def test_stale_rows_past_the_index_are_never_read(case):
    """A re-used slot: what an earlier request left past the index must not
    reach the output. Poisoning every position past each slot's index
    with values that would swamp any softmax changes nothing."""
    ops = _operands(case)
    out, _, _ = da.decode_attention(**ops)
    max_len = ops["cached_key"].shape[2]
    past = (jnp.arange(max_len)[None, :] > case["idx"][:, None])
    past = past[:, None, :, None]
    ops["cached_key"] = jnp.where(past, 3e4, ops["cached_key"]).astype(
        ops["cached_key"].dtype)
    ops["cached_value"] = jnp.where(past, 3e4, ops["cached_value"]).astype(
        ops["cached_value"].dtype)
    poisoned, _, _ = da.decode_attention(**ops)
    live = np.asarray(case["active"])
    assert np.array_equal(np.asarray(out, np.float32)[live],
                          np.asarray(poisoned, np.float32)[live])


def _operands(case) -> dict:
    return dict(q=case["q"], k_new=case["k_new"], v_new=case["v_new"],
                cached_key=case["ck"], cached_value=case["cv"],
                index=case["idx"], active=case["active"])


@pytest.mark.parametrize("why,kw,expect", [
    ("the serve cells' caches", {}, True),
    ("a float32 cache", dict(cache_dtype=jnp.float32), True),
    ("an int8 cache is kept by its own branch", dict(cache_dtype=jnp.int8),
     False),
    ("a rolling window", dict(window=128), False),
    ("a head as wide as the lanes lies the other way round",
     dict(d_head=128), False),
    ("a head that fills no sublane tile", dict(d_head=24), False),
    ("a cache that is not whole tiles", dict(max_len=1000), False),
    ("off the TPU", dict(tpu=False), False),
])
def test_engages_by_what_the_code_can_see(monkeypatch, why, kw, expect):
    kw = dict(dict(cache_dtype=jnp.bfloat16, d_head=64, max_len=1024,
                   window=0, mesh=None, tpu=True), **kw)
    monkeypatch.setattr(da, "on_tpu", lambda tpu=kw.pop("tpu"): tpu)
    assert da.engages(**kw) is expect, why


def test_a_mesh_of_one_device_engages_and_a_larger_one_does_not(monkeypatch):
    from jax.sharding import Mesh

    monkeypatch.setattr(da, "on_tpu", lambda: True)
    kw = dict(cache_dtype=jnp.bfloat16, d_head=64, max_len=1024, window=0)
    devices = np.array(jax.devices())
    assert da.engages(mesh=Mesh(devices[:1], ("data",)), **kw)
    assert not da.engages(mesh=Mesh(devices[:2], ("data",)), **kw)


@pytest.mark.parametrize("heads,d_head,max_len,itemsize,want", [
    (16, 64, 1024, 2, 512),      # gpt2m-serve-closed32: two steps a slot
    (8, 64, 4096, 2, 1024),      # lfm2-serve-closed32: four
    (16, 64, 1024, 4, 256),      # a float32 cache: half the positions
    (16, 64, 384, 2, 384),       # a block divides the cache: 3 tiles, not 4
    (64, 128, 256, 4, 128),      # never under one tile
])
def test_block_positions_follow_from_the_shapes(heads, d_head, max_len,
                                                itemsize, want):
    assert da.block_positions(heads, d_head, max_len, itemsize) == want
