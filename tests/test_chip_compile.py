"""The main path's Pallas kernels, compiled for the chip at real widths.

Interpret mode (every other kernel test here) cannot see what the TPU's
compiler refuses: a slice off the tiling, more scoped VMEM than a kernel
may use. The compiler is installed without the chip and compiles for a
DESCRIBED v5e, so these guard every later PR at no chip time — about two
seconds each. A compile that passes is a compile, not a chip run:
``chip_smoke.py`` runs the same kernels on the chip against their dense
references. The serve engine's two programs are fenced here too: what the
compiler does to a whole KV-cache leaf (a copy, a relayout, a scatter) is
only visible in the TPU's optimised HLO.

All in ONE file on purpose (on-chip-measurement guide, section 2): only
one process may load the TPU's library, so the topology is described
inside a module-scoped fixture of this file — never at import, in a
``skipif`` or in ``parametrize`` arguments — and every compile runs in
the test's own process. The persistent cache is off around them: a
described-device executable is written to it but cannot be read back
without a chip.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from dtf_tpu.models import gpt
from dtf_tpu.ops import embed_gather, flash_attention as fa, fused_ce
from dtf_tpu.serve import engine as serve_engine
from dtf_tpu.tune import resolver

VOCAB = 50304          # GPTConfig's vocab (TP-divisible GPT-2)
TOKENS = 8 * 1024      # batch 8 x seq 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def on_chip(topo):
    """shape, dtype -> a ShapeDtypeStruct placed on one described chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)


@pytest.fixture(scope="module")
def compiled_once():
    """key, builder -> what the builder returned the first time: a serve
    program compiles for up to 20 s, and more than one test reads it."""
    cache = {}

    def get(key, build):
        if key not in cache:
            cache[key] = build()
        return cache[key]

    return get


def _compiles_to_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _flash(window, *, causal=True, **shape):
    """flash_attention with the blocks the CHIP would resolve. Nothing is
    banked for flash on a TPU (asserted: a measured entry would follow
    ``jax.default_backend()``, the CPU's here), so block arguments left at
    0 take ``flash_blocks``, the shape rule, which asks no backend."""
    plan = resolver.flash_plan(dtype="bfloat16", causal=causal,
                               window=window, n_devices=1, backend="tpu",
                               **shape)
    assert not plan.measured, plan

    def attn(q, k, v, kv_mask=None):
        return fa.flash_attention(q, k, v, causal=causal, window=window,
                                  kv_mask=kv_mask)

    return attn


# (batch, heads, seq, head_dim): GPT-2 medium and small at the smoke's
# and the long-context launcher's sequence lengths
FLASH_SHAPES = {"medium_s1024": (8, 16, 1024, 64),
                "small_s1024": (8, 12, 1024, 64),
                "medium_s2048": (2, 16, 2048, 64)}


@pytest.mark.parametrize("name,window", [
    ("medium_s1024", 0), ("medium_s1024", 256), ("small_s1024", 0),
    ("medium_s2048", 0)])
def test_flash_forward_compiles(on_chip, name, window):
    b, h, t, d = FLASH_SHAPES[name]
    q = on_chip((b, h, t, d), jnp.bfloat16)
    _compiles_to_kernel(_flash(window, seq=t, heads=h, head_dim=d), q, q, q)


@pytest.mark.parametrize("name,window", [
    ("medium_s1024", 0), ("medium_s1024", 256), ("medium_s2048", 256)])
def test_flash_backward_compiles(on_chip, name, window):
    b, h, t, d = FLASH_SHAPES[name]
    q = on_chip((b, h, t, d), jnp.bfloat16)
    attn = _flash(window, seq=t, heads=h, head_dim=d)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(attn(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    _compiles_to_kernel(grads, q, q, q)


@pytest.mark.parametrize("t_q,t_k,window", [
    (1024, 2048, 0),      # dkv: key block 1 lies past every query
    (3072, 1024, 512),    # fwd, dq: query block 2's window past every key
])
def test_flash_dead_block_in_a_one_step_row_compiles(on_chip, t_q, t_k,
                                                     window):
    """Under the shape rule these go in blocks of 1024 whose rows are one
    grid step (no scratch), and one block is dead: it stores the empty
    row's zeros itself, a third ``pl.when`` variant — which Mosaic has to
    take as well as interpret mode does."""
    b, h, d = 1, 4, 64
    q = on_chip((b, h, t_q, d), jnp.bfloat16)
    k = on_chip((b, h, t_k, d), jnp.bfloat16)
    attn = _flash(window, seq=t_q, heads=h, head_dim=d)

    def grads(q, k, v):
        return jax.grad(lambda *a: jnp.sum(attn(*a).astype(jnp.float32)),
                        argnums=(0, 1, 2))(q, k, v)

    _compiles_to_kernel(grads, q, k, k)


def _fused_ce_args(on_chip, d_model):
    # the production dtype mix: bf16 hidden states, f32 master head
    plan = resolver.fused_ce_plan(vocab=VOCAB, d_model=d_model,
                                  dtype="bfloat16", n_devices=1,
                                  backend="tpu")

    def loss(x, w, labels):
        return fused_ce.pallas_lm_cross_entropy(
            x, w, labels, ignore_index=-100, block_n=plan.block_n,
            block_v=plan.block_v)[0]

    return loss, (on_chip((TOKENS, d_model), jnp.bfloat16),
                  on_chip((d_model, VOCAB), jnp.float32),
                  on_chip((TOKENS,), jnp.int32))


@pytest.mark.parametrize("d_model", [768, 1024])
def test_fused_ce_forward_compiles(on_chip, d_model):
    loss, args = _fused_ce_args(on_chip, d_model)
    _compiles_to_kernel(loss, *args)


@pytest.mark.parametrize("d_model", [768, 1024])
def test_fused_ce_backward_compiles(on_chip, d_model):
    """The dW kernel at 512x1024 blocks needs 18 MiB (d 768) and 23 MiB
    (d 1024) of the 16 MiB scoped VMEM with an f32 head — refused until
    it chose its own vocab block (fused_ce._dw_block_v)."""
    loss, args = _fused_ce_args(on_chip, d_model)
    _compiles_to_kernel(
        lambda x, w, labels: jax.grad(loss, argnums=(0, 1))(x, w, labels),
        *args)


def test_embedding_gather_compiles(on_chip):
    # a Wide&Deep table: 1M rows x 64, a 4096-example batch of 26 features
    table = on_chip((1_000_000, 64), jnp.float32)
    ids = on_chip((4096, 26), jnp.int32)
    _compiles_to_kernel(embed_gather.gather_rows, table, ids)


def _named_kernel_cases(on_chip):
    """case -> (function, arguments, the ``dtf_*`` names its kernels carry)."""
    b, h, t, d = FLASH_SHAPES["medium_s1024"]
    q = on_chip((b, h, t, d), jnp.bfloat16)
    attn = _flash(0, seq=t, heads=h, head_dim=d)
    return {
        "flash_fwd": (attn, (q, q, q), ["dtf_flash_fwd"]),
        "flash_bwd": (
            lambda q, k, v: jax.grad(
                lambda *a: jnp.sum(attn(*a).astype(jnp.float32)),
                argnums=(0, 1, 2))(q, k, v),
            (q, q, q), ["dtf_flash_fwd", "dtf_flash_dq", "dtf_flash_dkv"]),
    }


@pytest.mark.parametrize("case", ["flash_fwd", "flash_bwd"])
def test_kernels_carry_their_names(on_chip, case):
    """Each flash-attention ``pl.pallas_call`` (the kernels the benchmark's
    cells run) carries its ``dtf_*`` name in the name of its instruction
    in the compiled program, which is what the profiler's ``XLA Ops``
    events start with and how the benchmark's ``kernel_roofline`` reader
    (and a person reading a trace) finds it:
    ``%dtf_flash_fwd.1`` called plainly or under a module's scope,
    ``%jvp_dtf_flash_fwd_.1`` / ``%transpose_jvp_dtf_flash_dq__.1`` called
    bare under ``jax.grad`` as here. Without ``name=`` it is the enclosing
    scope alone (``%attention.1``, ``%jvp__.1``), the same for every
    kernel of a module."""
    fn, args, names = _named_kernel_cases(on_chip)[case]
    text = _compiles_to_kernel(fn, *args)
    for name in names:
        assert re.search(rf"^\s*%\w*{name}\w*(\.\d+)? = .*tpu_custom_call",
                         text, re.M), name


# (batch, heads, seq, head_dim, causal, key mask): the attention of the two
# one-chip train cells (BERT's per micro-batch of 32) and the sweep's long one
CELL_ATTENTION = {
    "gpt2m-train-b8s1024": (8, 16, 1024, 64, True, False),
    "bert-base-train-b256s512": (32, 12, 512, 64, False, True),
    "long_s8192_d128": (1, 16, 8192, 128, True, False),
}


@pytest.mark.parametrize("cell", sorted(CELL_ATTENTION))
def test_cells_flash_kernels_are_what_the_readers_match(on_chip, cell):
    """The three kernels at a cell's attention shape, with the shape
    rule's blocks, compile for the described v5e — and carry what the
    benchmark's readers find them by: the names ``dtf_flash_fwd``,
    ``dtf_flash_dq``, ``dtf_flash_dkv`` (``flash_fwd_roofline`` /
    ``flash_bwd_roofline``) and a FIRST operand
    ``bf16[batch*heads, seq, d_head]`` (``flash_attn_roofline``,
    ``benchmarks/readers/flash_roofline.py``). A kernel renamed, or handed
    its queries in another layout, would read as a silent roofline on the
    chip; here it fails on the CPU."""
    b, h, t, d, causal, masked = CELL_ATTENTION[cell]
    blocks = fa.flash_blocks(t, t, d, causal=causal)
    for kernel in ("fwd", "dq", "dkv"):
        assert (fa.vmem_bytes(kernel, getattr(blocks, kernel), d)
                <= fa._VMEM_LIMIT), (kernel, blocks)
    attn = _flash(0, causal=causal, seq=t, heads=h, head_dim=d)
    q = on_chip((b, h, t, d), jnp.bfloat16)
    args = (q, q, q) + ((on_chip((b, t), jnp.bool_),) if masked else ())

    def grads(q, k, v, *mask):
        return jax.grad(
            lambda *a: jnp.sum(attn(*a, *mask).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    text = _compiles_to_kernel(grads, *args)
    first_operand = rf"bf16\[{b * h},{t},{d}\]"
    for name in ("dtf_flash_fwd", "dtf_flash_dq", "dtf_flash_dkv"):
        lines = re.findall(
            rf"^\s*%\w*{name}\w*(?:\.\d+)? = .*tpu_custom_call.*$", text, re.M)
        assert len(lines) == 1, (name, len(lines))
        assert re.search(
            rf"operand_layout_constraints=\{{{first_operand}\{{", lines[0]
        ), (name, lines[0][:300])


# ---- the serve engine's programs: the KV cache is updated in place ---------

SERVE = dict(n_slots=32, max_len=1024, prefill_chunk=128)   # the serve cell


def _gpt_serve_table(on_chip, monkeypatch, heads, layers=2):
    """``program_table`` at the serve cell's widths and slots with a
    1024-token vocabulary (the full vocabulary's sort alone compiles for 20
    s), every operand on one described chip. The model asks
    ``jax.default_backend()`` whether its decode step runs the Pallas
    kernel; the answer is the CPU's here, so the test gives the chip's.
    Returns ``(programs, place, state)``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = gpt.GPTConfig(d_model=1024, layers=layers, heads=heads, d_ff=4096,
                        vocab_size=1024)
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: on_chip(s.shape, s.dtype), tree)
    state = place(serve_engine.engine_state_struct(
        cfg, n_slots=SERVE["n_slots"], max_len=SERVE["max_len"]))
    model = gpt.GPT(cfg)
    params = place(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32)))["params"])
    programs, _ = serve_engine.program_table(
        cfg, **SERVE, abs_trees={"params": params, "state": state})
    return programs, place, state


def _serve_program(on_chip, monkeypatch, name, heads):
    """The table's ``name``, cut to 2 layers (depth does not touch how a
    cache leaf is written). Returns ``(compiled, state)``."""
    programs, place, state = _gpt_serve_table(on_chip, monkeypatch, heads)
    prog = programs[name]
    return prog.lower(*place(prog.abstract_args)).compile(), state


@pytest.mark.parametrize("d_head", [64, 128])
@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_serve_programs_update_the_cache_in_place(on_chip, monkeypatch,
                                                  compiled_once, name,
                                                  d_head):
    """PR 25's fence. Before it ``jit_decode_fn`` relayouted every cache leaf
    twice a step around a scatter (the compiler keeps a leaf with position
    as the minor dimension and scatters only with position major) and
    ``jit_prefill_fn`` copied every leaf into its un-donated output: 44% of
    the serve cell's device time. Now no instruction produces a whole leaf
    by ``copy``, ``transpose`` or ``scatter``, the donated state is aliased
    to the output whole, and the temporaries stay under one set of leaves.
    (``copy-start``/``copy-done`` pairs are not counted: the compiler's
    prefetch of a leaf into another memory space, the same bytes the
    in-place pass reads and writes.) d_head 128 is fenced beside 64 so
    neither layout pays for the other. Since PR 30 the d_head 64 decode
    program is the one with the ``dtf_decode_attn`` kernel in it (the leaf
    goes through it as it lies, a bitcast either side); d_head 128 keeps
    the select write."""
    heads = 1024 // d_head
    compiled, state = compiled_once(
        ("gpt", name, heads),
        lambda: _serve_program(on_chip, monkeypatch, name, heads))
    leaf = (f"[{SERVE['n_slots']},{heads},{SERVE['max_len']},{d_head}]")
    whole_leaf = re.findall(
        rf"^\s*(?:ROOT )?%\S+ = bf16{re.escape(leaf)}\S* "
        rf"(copy|transpose|scatter)\(", compiled.as_text(), re.M)
    assert not whole_leaf, whole_leaf

    nbytes = lambda tree: sum(  # noqa: E731
        s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(state)
    assert mem.temp_size_in_bytes < nbytes(state["cache"])


# ---- the hybrid sparse decoder: conv state beside K/V, grouped experts -----

LFM2_SERVE = dict(n_slots=32, max_len=4096, prefill_chunk=256)  # its cell


def _lfm2_program(on_chip, monkeypatch, name):
    """The serve cell's ``name`` program at its widths and slots, cut to one
    conv layer (dense FFN) and one attention layer (64 routed experts) and a
    1024-token vocabulary. The program asks ``jax.default_backend()`` whether to run the Pallas
    grouped product; the answer is the CPU's here, so the test gives the
    chip's."""
    from dtf_tpu.parallel import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = gpt.GPTConfig(
        vocab_size=1024, d_model=2048, layers=2, heads=32, kv_heads=8,
        d_ff=11776, norm="rmsnorm", ffn="swiglu", qk_norm=True,
        use_bias=False, tie_head=True, layer_kinds=("conv", "attn"),
        rope_theta=1e6, dense_layers=1, param_dtype=jnp.bfloat16,
        experts=moe.ExpertsConfig(num_experts=64, top_k=4, d_ff=1536))
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: on_chip(s.shape, s.dtype), tree)
    state = place(serve_engine.engine_state_struct(
        cfg, n_slots=LFM2_SERVE["n_slots"], max_len=LFM2_SERVE["max_len"]))
    model = gpt.GPT(cfg)
    params = place(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    programs, _ = serve_engine.program_table(
        cfg, **LFM2_SERVE, abs_trees={"params": params, "state": state})
    prog = programs[name]
    return prog.lower(*place(prog.abstract_args)).compile(), state


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_lfm2_serve_programs_update_both_kinds_of_state_in_place(
        on_chip, monkeypatch, compiled_once, name):
    """PR 25's fence on the model of PR 26: neither the K/V leaves nor the
    conv-state leaf is produced whole by a ``copy``, ``transpose`` or
    ``scatter``, the donated state is aliased to the output whole, the temporaries leave no room for a copy of a leaf, and
    the grouped product is the named Pallas kernel, three calls an expert
    layer. Prefill's temporaries are its dense scores of one chunk against
    a 4096-position cache, [8, 4, 256, 4352] float32 twice (285 MB, more
    than this cut's one attention layer of cache): a K or V leaf more (134
    MB) would pass the limit."""
    compiled, state = compiled_once(
        ("lfm2", name), lambda: _lfm2_program(on_chip, monkeypatch, name))
    text = compiled.as_text()
    n = LFM2_SERVE["n_slots"]
    for leaf in (f"[{n},8,{LFM2_SERVE['max_len']},64]", f"[{n},3,2048]"):
        whole_leaf = re.findall(
            rf"^\s*(?:ROOT )?%\S+ = bf16{re.escape(leaf)}\S* "
            rf"(copy|transpose|scatter)\(", text, re.M)
        assert not whole_leaf, (leaf, whole_leaf)
    nbytes = lambda tree: sum(  # noqa: E731
        s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(state)
    chunk, cached = LFM2_SERVE["prefill_chunk"], LFM2_SERVE["max_len"]
    scores = 32 * chunk * (cached + chunk) * 4
    limit = nbytes(state["cache"]) if name == "decode" else 2.5 * scores
    assert mem.temp_size_in_bytes < limit
    kernels = re.findall(
        r"^\s*%\w*dtf_moe_gmm\w*(?:\.\d+)? = .*tpu_custom_call", text, re.M)
    assert len(kernels) == 3, kernels


# ---- latent attention: one latent leaf, a share of the experts -------------

AXK1_SERVE = dict(n_slots=32, max_len=16384, prefill_chunk=512)  # its cell


def _axk1_program(on_chip, monkeypatch, name):
    """The ``axk1-serve-closed32-doc16k`` cell's ``name`` program at its
    widths and slots, cut to the dense layer and one expert layer (12 of
    192 routed experts held, the shared expert) and a 1024-token
    vocabulary. The program asks ``jax.default_backend()`` whether to run
    its Pallas kernels; the answer is the CPU's here, so the test gives the
    chip's."""
    from dtf_tpu.parallel import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = gpt.GPTConfig(
        vocab_size=1024, d_model=7168, layers=2, heads=64, d_ff=18432,
        norm="rmsnorm", norm_eps=1e-6, ffn="swiglu", use_bias=False,
        layer_kinds=("mla", "mla"), dense_layers=1, shared_expert_ff=2048,
        param_dtype=jnp.bfloat16,
        latent=gpt.LatentAttentionConfig(yarn_factor=32, yarn_mscale=1,
                                         yarn_mscale_all_dim=1),
        experts=moe.ExpertsConfig(
            num_experts=192, top_k=8, d_ff=2048, use_expert_bias=False,
            routed_scaling_factor=2.5, n_group=8, topk_group=4,
            experts_held=(0, 12)))
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: on_chip(s.shape, s.dtype), tree)
    state = place(serve_engine.engine_state_struct(
        cfg, n_slots=AXK1_SERVE["n_slots"], max_len=AXK1_SERVE["max_len"]))
    model = gpt.GPT(cfg)
    params = place(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"])
    programs, _ = serve_engine.program_table(
        cfg, **AXK1_SERVE, abs_trees={"params": params, "state": state})
    prog = programs[name]
    return prog.lower(*place(prog.abstract_args)).compile(), state


@pytest.mark.parametrize("name", ["decode", "prefill"])
def test_axk1_serve_programs_update_the_latent_cache_in_place(
        on_chip, monkeypatch, compiled_once, name):
    """PR 25's fence on the model of PR 31. The latent leaf, [32, 576,
    16384] bfloat16 (604 MB a layer, positions minor), is produced whole by
    no ``copy``, ``transpose`` or ``scatter``; the donated state is aliased
    to the output whole; the temporaries stay under one leaf (a copy or a
    relayout of it would not fit under that). Decode holds one
    ``dtf_mla_decode_attn`` a layer — the leaf goes through the kernel as
    it lies — and prefill writes its chunk as one slab; both hold the
    grouped product three times an expert layer, at the blocks
    ``moe_gmm.column_tile`` finds for 7168 x 2048 (the LFM2 cell's whole
    halves would ask for 29 MB of the 16 MiB scoped VMEM)."""
    compiled, state = compiled_once(
        ("axk1", name), lambda: _axk1_program(on_chip, monkeypatch, name))
    text = compiled.as_text()
    leaf = f"[{AXK1_SERVE['n_slots']},576,{AXK1_SERVE['max_len']}]"
    assert f"bf16{leaf}" in text
    whole_leaf = re.findall(
        rf"^\s*(?:ROOT )?%\S+ = bf16{re.escape(leaf)}\S* "
        rf"(copy|transpose|scatter)\(", text, re.M)
    assert not whole_leaf, whole_leaf
    nbytes = lambda tree: sum(  # noqa: E731
        s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= nbytes(state)
    assert mem.temp_size_in_bytes < nbytes(state["cache"]) / 2
    gmm = re.findall(
        r"^\s*%\w*dtf_moe_gmm\w*(?:\.\d+)? = .*tpu_custom_call", text, re.M)
    assert len(gmm) == 3, gmm
    attn = re.findall(
        r"^\s*%\w*dtf_mla_decode_attn\w*(?:\.\d+)? = .*tpu_custom_call",
        text, re.M)
    assert len(attn) == (2 if name == "decode" else 0), attn


# ---- slot-decode attention: one kernel an attention layer, lowered once ----

DECODE_KERNEL = r"^\s*%\w*dtf_decode_attn\w*(?:\.\d+)? = .*tpu_custom_call"


@pytest.mark.parametrize("family,heads,kernels", [
    ("gpt", 16, 2), ("gpt", 8, 0), ("lfm2", None, 1)])
def test_decode_programs_hold_the_attention_kernel(
        on_chip, monkeypatch, compiled_once, family, heads, kernels):
    """``jit_decode_fn`` at both serve cells' shapes holds
    ``dtf_decode_attn`` as a ``tpu_custom_call``, one an attention layer
    (the GPT cut has two, the LFM2 cut one beside its conv layer), under
    the name the profiler's ``XLA Ops`` events start with. Heads of 128
    lie the other way round in the leaf (d_head minor): that engine keeps
    the select write, and the in-place fence above covers it."""
    if family == "gpt":
        compiled, _ = compiled_once(
            ("gpt", "decode", heads),
            lambda: _serve_program(on_chip, monkeypatch, "decode", heads))
    else:
        compiled, _ = compiled_once(
            ("lfm2", "decode"),
            lambda: _lfm2_program(on_chip, monkeypatch, "decode"))
    found = re.findall(DECODE_KERNEL, compiled.as_text(), re.M)
    assert len(found) == kernels, found


def test_full_depth_decode_lowers_the_kernel_once(on_chip, monkeypatch):
    """The kernel sits behind one module-level ``jax.jit``, so the 24
    attention layers of GPT-2 medium's decode program share one trace and
    one Mosaic lowering: the LOWERED text holds the kernel's module once,
    in one function that the layers call. (Bare ``pallas_call`` sites
    lower once each: 24 copies of the module, which a server pays for at
    every start. PERF.md section 6, PR 30.)"""
    programs, place, _ = _gpt_serve_table(on_chip, monkeypatch, 16,
                                          layers=24)
    prog = programs["decode"]
    text = prog.lower(*place(prog.abstract_args)).as_text()
    assert text.count("tpu_custom_call") == 1
    assert len(re.findall(r"func\.func private @_decode_attention\b",
                          text)) == 1
    assert len(re.findall(r"call @_decode_attention\b", text)) == 24


# ---- the sampler: a step pays for the vocabulary sorts only if it asks -----

def _computations(text: str) -> dict:
    """Optimised HLO text -> {computation: its instruction lines}, the
    entry computation under ``"ENTRY"``."""
    found = re.findall(r"^(ENTRY )?%(\S+) \(.*?\{\n(.*?)^\}", text,
                       re.M | re.S)
    return {"ENTRY" if entry else name: body.splitlines()
            for entry, name, body in found}


def _reached(comps: dict, start, through_conditionals: bool) -> set:
    """The computations ``start`` calls, directly or not."""
    seen, todo = set(), list(start)
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            if through_conditionals or " conditional(" not in line:
                todo += [callee for callee in re.findall(r"%([\w.\-]+)", line)
                         if callee in comps]
    return seen


@pytest.mark.parametrize("name", ["decode", "prefill"])
@pytest.mark.parametrize("family", ["gpt", "lfm2"])
def test_serve_programs_sort_only_inside_a_conditional(
        on_chip, monkeypatch, compiled_once, family, name):
    """PR 27's fence. The token pick chooses its work once per call, outside
    any ``vmap`` (``serve_engine._pick_rows``), so the compiler keeps a
    ``conditional`` and the vocabulary sorts of ``filter_logits_dynamic``
    stay inside one of its branches: a greedy step runs none (they were 42%
    and 34% of the two serve cells' device time). Moved back under a
    ``vmap`` the choice becomes a select, both sides run, and the sorts
    reappear in what the entry computation executes on every call."""
    if family == "gpt":
        compiled, _ = compiled_once(
            ("gpt", name, 16),
            lambda: _serve_program(on_chip, monkeypatch, name, 16))
    else:
        compiled, _ = compiled_once(
            ("lfm2", name), lambda: _lfm2_program(on_chip, monkeypatch, name))
    comps = _computations(compiled.as_text())
    # a model may sort for itself (an expert layer routes and groups its
    # tokens by sorting, under the model's scope ``GPT/``): the sampler's
    # sorts are the others
    sorts = lambda names: [  # noqa: E731
        line.split(" = ")[0].strip() for c in names for line in comps[c]
        if re.search(r" sort\(", line)
        and not re.search(r'op_name="[^"]*/GPT/', line)]
    always = _reached(comps, ["ENTRY"], through_conditionals=False)
    assert not sorts(always), sorts(always)
    branches = [
        callee for c in always for line in comps[c] if " conditional(" in line
        for callee in re.findall(r"%([\w.\-]+)", line) if callee in comps]
    assert branches
    # gpt.filter_logits_dynamic: one sort and two argsorts
    assert sorts(_reached(comps, branches, through_conditionals=True))
