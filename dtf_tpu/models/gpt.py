"""GPT-style causal decoder LM — the long-context flagship.

Beyond the reference's capability list (SURVEY.md §5.7: nothing in
`zjj2wry/distributed-tensorflow` scales sequence length), but first-class
here: this model is the consumer that ties the framework's long-context and
parallelism machinery together —

- **flash attention** (:mod:`dtf_tpu.ops.flash_attention`): fused Pallas
  kernel for the single/tensor-parallel path, wrapped in ``shard_map`` over
  (data, model) so batch/head shards each run a local kernel;
- **ring attention** (:mod:`dtf_tpu.ops.attention`): context parallelism
  over the ``seq`` axis for sequences that don't fit one chip;
- **Megatron TP** over ``model`` (:data:`tp_rules`), same scheme as BERT;
- optional **Switch-MoE** FFN layers (:mod:`dtf_tpu.parallel.moe`) for
  expert parallelism over ``expert``;
- **remat** (``jax.checkpoint``) per block — the HBM-for-FLOPs trade that
  long sequences need.

Pre-LN blocks, RoPE positions (global positions, so they are correct under
sequence sharding), optional grouped-query attention (``kv_heads`` — the
KV cache shrinks by heads/kv_heads, the decode-memory lever), untied LM
head, bf16 compute / f32 params.

The same block also spells today's hybrid sparse decoders (PR 26), each
departure one config field: ``norm="rmsnorm"``, ``ffn="swiglu"``,
``qk_norm``, bias-free projections, a tied head, bfloat16 storage
(``param_dtype``), a per-layer operator (``layer_kinds``: attention or the
gated short convolution :class:`ShortConv`, whose recurrent state lives in
the ``cache`` collection beside K/V) and dropless routed experts
(``experts`` from layer ``dense_layers`` on —
:class:`dtf_tpu.parallel.moe.DroplessMoE`). Forward and serving only:
ROADMAP.md says what training them still lacks.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import Mesh, PartitionSpec as P

from dtf_tpu.core import comms
from dtf_tpu.core.train import LossAux
from dtf_tpu.ops import attention as att
from dtf_tpu.ops import decode_attention
from dtf_tpu.ops import flash_attention as fa
from dtf_tpu.ops.losses import softmax_cross_entropy
from dtf_tpu.parallel import moe as moe_lib


@dataclasses.dataclass(frozen=True)
class LatentAttentionConfig:
    """Multi-head latent attention as today's large sparse decoders publish
    it (``q_lora_rank`` / ``kv_lora_rank`` / ``qk_nope_head_dim`` /
    ``qk_rope_head_dim`` / ``v_head_dim`` and the ``rope_scaling`` block):
    a low-rank query path, keys and values expanded from ONE latent row a
    token, a decoupled rotary key part shared by all heads, YaRN rotary
    frequencies and YaRN's softmax scale. :class:`LatentAttention` has the
    equations."""

    q_rank: int = 1536
    kv_rank: int = 512
    nope_dim: int = 128
    rope_dim: int = 64
    v_dim: int = 128
    #: YaRN: 1 = plain rotary frequencies and the plain softmax scale
    yarn_factor: float = 1.0
    yarn_original_len: int = 4096
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0

    def __post_init__(self):
        if self.rope_dim % 2 or min(self.q_rank, self.kv_rank, self.nope_dim,
                                    self.rope_dim, self.v_dim) < 1:
            raise ValueError(
                f"{self}: every width must be >= 1 and rope_dim even")
        if self.yarn_factor < 1:
            raise ValueError(f"yarn_factor={self.yarn_factor} must be >= 1")
        if self.yarn_factor > 1 and (self.yarn_mscale
                                     != self.yarn_mscale_all_dim):
            raise ValueError(
                f"yarn_mscale={self.yarn_mscale} != yarn_mscale_all_dim="
                f"{self.yarn_mscale_all_dim}: YaRN then scales cos and sin "
                "by m(mscale) / m(mscale_all_dim) != 1, which the rotary "
                "embedding here does not do (no configuration has needed it)")

    @property
    def latent_width(self) -> int:
        """Numbers a cached position holds: the normalised latent row and
        the rotated shared key part."""
        return self.kv_rank + self.rope_dim

    def _m(self, mscale: float) -> float:
        if self.yarn_factor <= 1:
            return 1.0
        return 0.1 * mscale * math.log(self.yarn_factor) + 1.0

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope width)^(-1/2) m(mscale_all_dim)^2``."""
        return ((self.nope_dim + self.rope_dim) ** -0.5
                * self._m(self.yarn_mscale_all_dim) ** 2)

    def frequencies(self, theta: float) -> jax.Array:
        """[rope_dim / 2] float32: the angle pair ``d`` turns by a position.
        YaRN leaves the pairs that turn more than ``beta_fast`` times over
        the original length alone, divides those that turn less than
        ``beta_slow`` times by ``factor``, and ramps between."""
        width = self.rope_dim
        d = jnp.arange(0, width, 2, dtype=jnp.float32)
        plain = theta ** (-d / width)
        if self.yarn_factor <= 1:
            return plain

        def pair_turning(rotations: float) -> float:
            return (width * math.log(self.yarn_original_len
                                     / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(pair_turning(self.yarn_beta_fast)), 0)
        high = min(math.ceil(pair_turning(self.yarn_beta_slow)), width - 1)
        ramp = jnp.clip((d / 2 - low) / max(high - low, 0.001), 0.0, 1.0)
        return plain * (1 - ramp) + plain / self.yarn_factor * ramp


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    #: GPT-2's 50257 BPE vocab padded to a multiple of 128 (the Megatron /
    #: nanoGPT convention): the embedding rows and lm_head columns shard
    #: evenly over any power-of-two `model` axis AND tile the TPU lane
    #: width; 50257 would leave every TP shard ragged (caught by
    #: `python -m dtf_tpu.analysis` as indivisible-dim). The 47 pad tokens
    #: never appear in data; their logits just ride the softmax.
    vocab_size: int = 50304
    d_model: int = 768
    layers: int = 12
    heads: int = 12
    d_ff: int = 3072
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    rope_theta: float = 10000.0
    #: grouped-query attention: number of shared K/V heads (None = heads,
    #: i.e. plain MHA). Must divide ``heads``. The KV cache shrinks by
    #: heads/kv_heads — the decode-memory lever (cache is the decode
    #: footprint at long ``decode_len``).
    kv_heads: Optional[int] = None
    #: attention backend: auto (ring if seq-sharded, flash on tpu, else
    #: dense), or force one of dense|flash|ring.
    attn_impl: str = "auto"
    #: sliding-window attention: query t sees keys in (t-window, t].
    #: 0 = full causal. O(T·window) compute on the flash path (out-of-window
    #: blocks are grid-skipped). Under seq sharding, ring/auto routes to
    #: halo attention (one neighbor-tail ppermute, no ring rotation);
    #: zigzag rejects windows (its permuted layout breaks locality).
    attn_window: int = 0
    #: with attn_window > 0: every k-th layer (1-indexed) uses FULL causal
    #: attention instead — the alternating local/global pattern that keeps
    #: long-range paths while most layers pay O(T·window). 0 = all layers
    #: windowed. Each decode layer sizes its own cache (window slots for
    #: local layers, decode_len for global ones).
    attn_global_every: int = 0
    #: flash-kernel head fold: batch this many heads per forward grid
    #: step (must divide heads; 1 = the proven 2-D kernel). Perf knob for
    #: the flash path only — see ops/flash_attention.py.
    flash_block_h: int = 1
    #: every k-th block uses a Switch-MoE FFN (0 = all dense).
    moe_every: int = 0
    moe: moe_lib.MoeConfig = moe_lib.MoeConfig()
    #: jax.checkpoint each block (long-context memory trade).
    remat: bool = False
    #: >0 enables single-token decode mode with a KV cache of this length
    #: (the "cache" collection; see :func:`generate`).
    decode_len: int = 0
    #: "" = store K/V at ``dtype`` (bf16); "int8" = symmetric per-slot
    #: per-head quantization (amax over d_head -> one f32 scale per
    #: [b, kv_head, slot]): the cache holds HALF the bytes — the third
    #: serving memory lever, multiplicative with GQA (heads/kv_heads) and
    #: the rolling window (decode_len/window). Dequantized at read; the
    #: scale adds 1/d_head overhead (~0.8% at d_head=64).
    kv_cache_dtype: str = ""
    #: multi-token applies may CONTINUE an advanced cache: rope positions
    #: and cache slots offset by cache_index and attention runs against the
    #: full cache, so a long prompt can prefill in bounded-memory chunks
    #: (``generate(..., prefill_chunk=...)``). Static flag — the default
    #: one-shot prefill keeps its flash-kernel fast path.
    chunked_prefill: bool = False
    #: continuous-batching decode mode (:mod:`dtf_tpu.serve`): the
    #: ``cache_index`` variable is PER-ROW ([B] int32, one independent
    #: position per batch slot) instead of one scalar shared by the whole
    #: batch, so each slot of a serving batch can sit at a different
    #: sequence position — a slot resets to index 0 when a new request is
    #: admitted while its neighbors keep decoding. Single-token steps only
    #: (prefill goes through a sliced batch-1 ``chunked_prefill`` model —
    #: see ``serve/engine.py``); a stale slot's old contents need no
    #: clearing because slot validity is derived from the index
    #: (``p_s >= 0`` masks every slot the new request hasn't written).
    slot_decode: bool = False
    #: latency-hiding collective matmul for the Megatron TP projections
    #: (q/k/v + attn_out, mlp_in/mlp_out): the blocking all-gather /
    #: reduce-scatter GSPMD schedules around each sharded einsum becomes a
    #: ppermute ring overlapped with per-chunk matmuls
    #: (:mod:`dtf_tpu.ops.collective_matmul`; docs/OVERLAP.md). Exact
    #: numerics parity with the GSPMD path; no-op unless the mesh has a
    #: real 'model' axis and shapes divide (comms.tp_overlap_viable).
    tp_overlap: bool = False
    #: low-precision compute tier for the TP projections (docs/TUNING.md):
    #: "" = bf16 status quo (no tuner consult), "auto" = the banked
    #: kernel-tune winner per projection site, "int8"/"fp8" = explicit pin
    #: (wins with one WARN over a measured winner). Forward-only: the
    #: custom_vjp keeps gradients full-precision against bf16 master
    #: weights, and on the tp_overlap rings the COMMUNICATED operand is
    #: what quantizes (~2x fewer ring bytes). The serving draft engine is
    #: the first consumer (serve_gpt --draft_precision): the bf16
    #: verifier keeps emitted tokens byte-identical regardless.
    matmul_precision: str = ""
    #: "layernorm" (bias and mean, flax's epsilon) or "rmsnorm" (weight
    #: only, ``norm_eps``); float32 either way.
    norm: str = "layernorm"
    norm_eps: float = 1e-5
    #: the dense FFN: "gelu" = ``W_out gelu(W_in x)`` with biases, "swiglu"
    #: = ``W2 (silu(W1 x) * W3 x)`` (``mlp_gate`` / ``mlp_up`` / ``mlp_out``).
    ffn: str = "gelu"
    #: RMSNorm over each head's width on q and on k (learned weight),
    #: before the rotary embedding.
    qk_norm: bool = False
    #: False = every projection bias-free.
    use_bias: bool = True
    #: the head is the token embedding, transposed (no ``lm_head`` leaf).
    tie_head: bool = False
    #: per-layer operator: ``"attn"``, ``"conv"`` (the gated short
    #: convolution) or ``"mla"`` (latent attention, ``latent``); () =
    #: attention in every layer.
    layer_kinds: tuple = ()
    #: the widths of the ``"mla"`` layers (:class:`LatentAttention`)
    latent: Optional[LatentAttentionConfig] = None
    #: taps of the short convolution, and columns of its decode state.
    conv_kernel: int = 3
    #: dropless routed experts (parallel/moe.py DroplessMoE) as the FFN of
    #: every layer from ``dense_layers`` on; layers before it keep the
    #: dense FFN of width ``d_ff``. None = no such layer.
    experts: Optional[moe_lib.ExpertsConfig] = None
    dense_layers: int = 0
    #: width of the SHARED expert: one SwiGLU every token meets, added to
    #: the routed experts' output in every layer that has them (0 = none).
    #: A field of the block: it is not routed to, and every chip of an
    #: expert-parallel deployment computes it alike.
    shared_expert_ff: int = 0
    #: storage dtype of the matrices (embedding, projections, experts);
    #: norm weights, the router and its bias stay float32.
    param_dtype: jnp.dtype = jnp.float32

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise ValueError(f"norm={self.norm!r} must be layernorm or "
                             "rmsnorm")
        if self.ffn not in ("gelu", "swiglu"):
            raise ValueError(f"ffn={self.ffn!r} must be gelu or swiglu")
        if self.layer_kinds and (
                len(self.layer_kinds) != self.layers
                or set(self.layer_kinds) - {"attn", "conv", "mla"}):
            raise ValueError(
                f"layer_kinds={self.layer_kinds} must name 'attn', 'conv' "
                f"or 'mla' for each of the {self.layers} layers")
        if ("mla" in self.layer_kinds) != (self.latent is not None):
            raise ValueError(
                "layer_kinds names 'mla' layers exactly when latent gives "
                "their widths")
        if self.latent is not None and (self.attn_window
                                        or self.kv_cache_dtype):
            raise ValueError(
                "a latent cache has no rolling window and no int8 form "
                "(attn_window / kv_cache_dtype): its one row a position "
                "carries no per-head scale")
        if self.shared_expert_ff < 0 or (self.shared_expert_ff
                                         and self.experts is None):
            raise ValueError(
                f"shared_expert_ff={self.shared_expert_ff} must be >= 0 and "
                "needs routed experts beside it")
        if self.conv_kernel < 2:
            raise ValueError(f"conv_kernel={self.conv_kernel} must be >= 2")
        if self.experts is not None and self.moe_every:
            raise ValueError(
                "experts (dropless) and moe_every (Switch) are two expert "
                "layers: pick one")
        if not 0 <= self.dense_layers <= self.layers:
            raise ValueError(
                f"dense_layers={self.dense_layers} must be in [0, layers="
                f"{self.layers}]")
        if self.kv_heads is not None and (
                self.kv_heads < 1 or self.heads % self.kv_heads):
            raise ValueError(
                f"kv_heads={self.kv_heads} must be >=1 and divide "
                f"heads={self.heads}")
        if self.attn_window < 0:
            # a negative window silently masks EVERY key: all-zero outputs
            # on the dense path, all--inf softmax (NaN) in decode
            raise ValueError(f"attn_window={self.attn_window} must be >= 0")
        if self.attn_global_every < 0:
            raise ValueError(
                f"attn_global_every={self.attn_global_every} must be >= 0")
        if self.kv_cache_dtype not in ("", "int8"):
            raise ValueError(
                f"kv_cache_dtype={self.kv_cache_dtype!r} must be '' (store "
                "at dtype) or 'int8'")
        if self.matmul_precision not in ("", "auto", "bf16", "int8",
                                         "fp8"):
            raise ValueError(
                f"matmul_precision={self.matmul_precision!r} must be '' "
                "(bf16, no tuner), 'auto' (kernel-tune winner), 'bf16', "
                "'int8' or 'fp8'")
        if self.slot_decode and self.decode_len <= 0:
            raise ValueError(
                "slot_decode requires decode_len > 0 (it is a property of "
                "the KV-cache decode mode)")
        if self.slot_decode and self.chunked_prefill:
            raise ValueError(
                "slot_decode and chunked_prefill are different models of "
                "the same cache: the serving engine slices one slot into a "
                "batch-1 chunked_prefill model instead (serve/engine.py)")

    def layer_window(self, layer: int) -> int:
        """Effective sliding window for layer ``layer`` (0-indexed): 0 when
        the layer is a designated global layer, else ``attn_window``."""
        if (self.attn_window and self.attn_global_every
                and (layer + 1) % self.attn_global_every == 0):
            return 0
        return self.attn_window

    @property
    def kv_heads_resolved(self) -> int:
        return self.heads if self.kv_heads is None else self.kv_heads

    def layer_kind(self, layer: int) -> str:
        return self.layer_kinds[layer] if self.layer_kinds else "attn"

    @property
    def has_recurrent_state(self) -> bool:
        """True when some layer keeps a running state in the cache (a conv
        layer): the serving features that index the cache by POSITION
        (prefix pages, speculative rollback) do not apply to it."""
        return "conv" in self.layer_kinds

    @property
    def has_latent_cache(self) -> bool:
        """True when some layer caches latent rows (an ``"mla"`` layer): one
        ``[rows, width, positions]`` leaf without a head axis, which the
        prefix page cache, the speculative verify step and the int8 cache
        do not serve yet (docs/SERVING.md)."""
        return "mla" in self.layer_kinds

    def layer_has_experts(self, layer: int) -> bool:
        return self.experts is not None and layer >= self.dense_layers

    @staticmethod
    def by_name(name: str) -> "GPTConfig":
        """The ONE size registry — every CLI/bench size switch routes
        here so adding a size is a single edit. Raises KeyError with the
        valid names for a typo (callers convert to their UsageError)."""
        sizes = {"small": GPTConfig.gpt2_small,
                 "medium": GPTConfig.gpt2_medium,
                 "draft": GPTConfig.gpt2_draft,
                 "tiny": GPTConfig.tiny}
        if name not in sizes:
            raise KeyError(
                f"unknown GPT size {name!r}; pick one of {sorted(sizes)}")
        return sizes[name]()

    @staticmethod
    def gpt2_small() -> "GPTConfig":
        return GPTConfig()

    @staticmethod
    def gpt2_medium() -> "GPTConfig":
        """GPT-2 medium (355M): the single-chip MFU sweet spot — wider
        matmuls (d_model 1024, d_ff 4096) fill the MXU better than
        small's 768/3072 while params+adam+ZeRO-1 still fit one v5e."""
        return GPTConfig(d_model=1024, layers=24, heads=16, d_ff=4096)

    @staticmethod
    def gpt2_draft() -> "GPTConfig":
        """The speculative-decoding DRAFT size (~25M non-embedding):
        shares the GPT-2 vocab (a draft must propose in the verifier's
        token space) at a quarter of small's depth and half its width —
        cheap enough that k proposals cost less than one verifier step,
        deep enough to track small's greedy stream on natural text."""
        return GPTConfig(d_model=384, layers=3, heads=6, d_ff=1536)

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        return GPTConfig(vocab_size=128, d_model=32, layers=2, heads=4,
                         d_ff=64, **kw)


def effective_attn_impl(impl: str, seq_sharded: bool) -> str:
    """Resolve ``attn_impl='auto'`` exactly as the attention block
    dispatches it (ring when seq-sharded, flash on TPU, dense otherwise).

    THE single source of truth for the dispatch: launchers call this to
    decide ``--grad_shard`` viability (everything but ``dense`` runs in a
    shard_map the per-shard-group vmap cannot nest — docs/ZERO.md), so a
    dispatch change here cannot drift from the blocker logic.
    """
    if impl != "auto":
        return impl
    if seq_sharded:
        return "ring"
    return "flash" if jax.default_backend() == "tpu" else "dense"


#: Megatron TP placement over the `model` mesh axis.
tp_rules = [
    (r"token_embed/embedding", P("model", None)),
    (r"(query|key|value)/kernel", P(None, "model")),
    (r"attn_out/kernel", P("model", None)),
    (r"mlp_in/kernel", P(None, "model")),
    (r"mlp_out/kernel", P("model", None)),
    (r"(query|key|value|mlp_in)/bias", P("model")),
    (r"lm_head/kernel", P(None, "model")),
] + moe_lib.ep_rules()


def rope(x: jax.Array, positions: jax.Array, theta: float, *,
         freqs: Optional[jax.Array] = None) -> jax.Array:
    """Rotary embedding. x [B,H,T,D] (D even), positions [T] global indices —
    correct under seq sharding because positions are global, not local.
    ``positions`` may also be PER-ROW [B,T] (the ``slot_decode`` step, where
    every serving slot sits at its own position); the angles then broadcast
    over heads only. ``freqs`` [D/2] replaces the plain ``theta**(-2i/D)``
    (YaRN: :meth:`LatentAttentionConfig.frequencies`)."""
    d = x.shape[-1]
    if freqs is None:
        freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions[..., None].astype(jnp.float32) * freqs    # [...,T,D/2]
    if angles.ndim == 3:                   # [B,T,D/2] → broadcast over heads
        angles = angles[:, None]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x1 * sin + x2 * cos
    out = jnp.stack([y1, y2], axis=-1).reshape(x.shape)
    return out.astype(x.dtype)


def _kv_quant(a: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization over the last (d_head) axis: returns
    (int8 values, f32 scale with keepdims). Zero rows quantize to zeros
    with the epsilon scale — dequant reproduces zero exactly."""
    s = jnp.maximum(jnp.max(jnp.abs(a.astype(jnp.float32)), axis=-1,
                            keepdims=True), 1e-6) / 127.0
    q = jnp.clip(jnp.round(a.astype(jnp.float32) / s),
                 -127, 127).astype(jnp.int8)
    return q, s


def _cache_read(cfg, cvar, svar) -> jax.Array:
    """Cache contents at compute dtype (dequantizing when int8). XLA can
    fuse the dequant multiply into the consuming einsum; the capacity win
    (half the resident bytes) holds regardless."""
    if svar is None:
        return cvar.value
    return (cvar.value.astype(jnp.float32) * svar.value).astype(cfg.dtype)


def _cache_put_at(cfg, cvar, svar, slots, a) -> None:
    """Gather-indexed cache write (prefill paths) — ONE definition with
    :func:`_cache_put_dyn` of how quantization happens, so the three
    write sites cannot desynchronize."""
    if svar is None:
        cvar.value = cvar.value.at[:, :, slots, :].set(a.astype(cfg.dtype))
    else:
        q, s = _kv_quant(a)
        cvar.value = cvar.value.at[:, :, slots, :].set(q)
        svar.value = svar.value.at[:, :, slots, :].set(s)


def _cache_put_dyn(cfg, cvar, svar, slot, a) -> None:
    """Single-slot dynamic cache write (the decode step)."""
    if svar is None:
        cvar.value = jax.lax.dynamic_update_slice_in_dim(
            cvar.value, a.astype(cfg.dtype), slot, axis=2)
    else:
        q, s = _kv_quant(a)
        cvar.value = jax.lax.dynamic_update_slice_in_dim(
            cvar.value, q, slot, axis=2)
        svar.value = jax.lax.dynamic_update_slice_in_dim(
            svar.value, s, slot, axis=2)


def _cache_put_rows(cfg, cvar, svar, positions, a, active=None) -> None:
    """Per-row cache write (the ``slot_decode`` steps): batch row b writes
    ``a[b, :, j, :]`` at its own cache slot ``positions[b, j]`` — the
    vectorized counterpart of :func:`_cache_put_dyn` for per-slot cache
    indices. ``a`` is [B,H,t,D], ``positions`` [B,t]: t = 1 is the decode
    step (the caller passes the rolled slot ``idx % cache_len``), t > 1
    the speculative VERIFY step (slot = absolute position, the full-cache
    layout that mode requires).

    Spelled as position-mask selects over the leaf, not a scatter: the TPU
    compiler keeps a cache leaf with POSITION as the minor (lane)
    dimension and scatters only with position major, so
    ``.at[rows, :, slots, :].set`` cost two relayouts of the whole cache
    per step (PERF.md §6, PR 25). The t selects fuse into one elementwise
    pass on the leaf as it lies — in place when the caller donates the
    cache — and store the same values at the same positions. A position
    at or past the cache end matches no slot and is DROPPED, never
    wrapped (a wrapped verify write would clobber live early positions
    with speculative K/V that a rejected tail could not roll back).
    ``active`` [B] bool folds into the mask: an inactive row (a slot
    mid-prefill) matches nothing, so it rides the fixed-shape step
    untouched."""
    lane = jnp.arange(cvar.value.shape[2])

    def put(var, upd):                                 # upd [B,H,t,D|1]
        out = var.value
        for j in range(upd.shape[2]):
            hit = lane == positions[:, j, None]                    # [B, L]
            if active is not None:
                hit = hit & active[:, None]
            out = jnp.where(hit[:, None, :, None], upd[:, :, j:j + 1, :],
                            out)
        var.value = out

    if svar is None:
        put(cvar, a.astype(cfg.dtype))
    else:
        q, s = _kv_quant(a)
        put(cvar, q)
        put(svar, s)


def _norm(cfg: GPTConfig, name: str):
    """The block's normalisation, float32: LayerNorm or RMSNorm."""
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                          param_dtype=jnp.float32, name=name)
    return nn.LayerNorm(dtype=jnp.float32, name=name)


class CausalSelfAttention(nn.Module):
    cfg: GPTConfig
    mesh: Optional[Mesh]
    #: effective sliding window for THIS layer (cfg.layer_window(i) — 0 on
    #: designated global layers). No default on purpose: a call site that
    #: forgets to thread it must fail loudly, not silently train
    #: full-causal under a windowed config.
    window: int
    #: True when this module already runs INSIDE a shard_map manual over
    #: the 'seq' axis (the PP x SP composition: pipeline stages carry
    #: seq-sharded activations). RoPE positions then come from the axis
    #: index and attention uses the per-shard ring/halo collectives
    #: directly — a nested shard_map would be illegal here.
    manual_seq: bool = False

    def _cache_vars(self, b: int, kv_heads: int, d_head: int):
        """The KV-cache collection — ONE definition shared by the
        single-token decode branch and the prefill write, so their layouts
        cannot desynchronize. Rolling buffer under a sliding window:
        position p lives in slot p % L with L = window, so the cache holds
        exactly the last `window` positions — decode memory is O(window),
        not O(decode_len) (the Mistral rolling-cache recipe). Without a
        window, L = decode_len and slots are positions (slot = idx).

        Standard flax decode idiom: init() only ALLOCATES the cache
        (has_variable is False on the init trace, so no slot is written
        and cache_index stays 0); mutation happens only on real apply()
        calls. Without this guard, init's dummy token would occupy slot 0
        and every later step would be off by one.
        """
        cfg = self.cfg
        is_initialized = self.has_variable("cache", "cached_key")
        # NOTE: a new cache variable must also be added to
        # _BATCH_LED_CACHE_KEYS / _NON_BATCH_CACHE_KEYS below (beam search
        # reorders batch-led leaves by key path and asserts completeness).
        cache_len = (min(cfg.decode_len, self.window)
                     if self.window else cfg.decode_len)
        quant = cfg.kv_cache_dtype == "int8"
        store = jnp.int8 if quant else cfg.dtype
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (b, kv_heads, cache_len, d_head), store)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (b, kv_heads, cache_len, d_head), store)
        sk = sv = None
        if quant:
            sk = self.variable("cache", "key_scale", jnp.zeros,
                               (b, kv_heads, cache_len, 1), jnp.float32)
            sv = self.variable("cache", "value_scale", jnp.zeros,
                               (b, kv_heads, cache_len, 1), jnp.float32)
        # slot_decode: one independent position counter per batch row (the
        # continuous-batching mode); otherwise the classic shared scalar.
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((b,) if cfg.slot_decode else (),
                                             jnp.int32))
        return ck, cv, sk, sv, ci, cache_len, is_initialized

    @nn.compact
    def __call__(self, x, deterministic: bool, prefill_len=None,
                 decode_active=None):
        cfg = self.cfg
        d_head = cfg.d_model // cfg.heads
        kv_heads = cfg.kv_heads_resolved
        group = cfg.heads // kv_heads
        t = x.shape[1]
        if cfg.slot_decode and t != 1 and self.window:
            raise ValueError(
                "the slot VERIFY step (slot_decode, multi-token apply) "
                "needs the full windowless cache layout; "
                f"attn_window={self.window} rolls the buffer, so a "
                "rejected speculative tail would clobber live positions "
                "it cannot roll back")
        if prefill_len is not None and not (
                cfg.decode_len > 0 and t != 1 and cfg.chunked_prefill):
            raise ValueError(
                "prefill_len only applies to the chunked-prefill path "
                "(decode_len > 0, chunked_prefill=True, multi-token chunk)")
        if decode_active is not None and not cfg.slot_decode:
            raise ValueError(
                "decode_active only applies to the slot_decode/verify "
                "steps (per-row cache indices)")
        # ONE projection constructor for every branch (train + decode):
        # comms.TpDense is a drop-in nn.Dense (identical param tree). With
        # --tp_overlap, q/k/v become collective ag_matmuls and attn_out a
        # collective matmul_rs; otherwise (and in every non-viable shape,
        # e.g. decode's t=1) its dispatch is the plain einsum. PP x SP
        # stages run inside a manual shard_map already, where a nested one
        # would be illegal — hence the manual_seq gate.
        overlap = (cfg.tp_overlap and self.mesh is not None
                   and not self.manual_seq)
        dense = lambda name, nh: comms.TpDense(  # noqa: E731
            nh * d_head, self.mesh, "column", overlap=overlap,
            use_bias=cfg.use_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, precision=cfg.matmul_precision,
            name=name)
        out_dense = lambda: comms.TpDense(  # noqa: E731
            cfg.d_model, self.mesh, "row", overlap=overlap,
            use_bias=cfg.use_bias, dtype=cfg.dtype,
            param_dtype=cfg.param_dtype, precision=cfg.matmul_precision,
            name="attn_out")

        def split(v, nh):
            return v.reshape(v.shape[0], t, nh, d_head).transpose(0, 2, 1, 3)

        q = split(dense("query", cfg.heads)(x), cfg.heads)
        k = split(dense("key", kv_heads)(x), kv_heads)
        v = split(dense("value", kv_heads)(x), kv_heads)
        if cfg.qk_norm:
            # over each head's width, before rope, in every branch below
            head_norm = lambda name: nn.RMSNorm(  # noqa: E731
                epsilon=cfg.norm_eps, dtype=jnp.float32,
                param_dtype=jnp.float32, name=name)
            q = head_norm("q_norm")(q).astype(cfg.dtype)
            k = head_norm("k_norm")(k).astype(cfg.dtype)

        def expand_kv(a):
            # GQA: query head h reads shared K/V head h // group. jnp.repeat
            # on the head axis produces exactly that alignment, and keeps
            # head-sharded layouts consistent (shard s's q heads see shard
            # s's repeated kv heads).
            return jnp.repeat(a, group, axis=1) if group > 1 else a

        if cfg.slot_decode and t != 1:
            # SLOT VERIFY (speculative decoding, serve/engine.py): t tokens
            # per row — the pending token plus k draft proposals — scored
            # in ONE batched pass, each row at its OWN cache position.
            # Position j of a row computes the same formula j sequential
            # slot_decode steps would: all t K/V land in the cache first
            # (slot = position; the full-cache layout, enforced above),
            # every query reads the POST-write cache — like the t=1 branch
            # reads its own freshly written K (which also keeps int8
            # self-reads dequantized identically) — and query j's validity
            # mask is the t=1 formula evaluated at index idx+j. Logits
            # agree with sequential decode to matmul-shape rounding (the
            # chunked-prefill parity class — batching t rows reassociates
            # reductions); the TESTED contract is token-stream identity,
            # exactly like chunked vs one-shot prefill's decode
            # continuation. Writes past the cache end DROP (never wrap —
            # _cache_put_rows): their queries' tokens sit past the slot
            # budget and are never delivered. The caller rolls cache_index
            # back to the accepted boundary afterwards (cache_rollback);
            # rejected-tail K/V needs no clearing — validity is derived
            # from the index.
            b = x.shape[0]
            ck, cv, sk, sv, ci, cache_len, is_initialized = self._cache_vars(
                b, kv_heads, d_head)
            idx = ci.value                                         # [B]
            qpos = idx[:, None] + jnp.arange(t)                    # [B, t]
            q = rope(q, qpos, cfg.rope_theta)
            k = rope(k, qpos, cfg.rope_theta)
            if is_initialized:
                _cache_put_rows(cfg, ck, sk, qpos, k, active=decode_active)
                _cache_put_rows(cfg, cv, sv, qpos, v, active=decode_active)
                ci.value = (idx + t if decode_active is None
                            else idx + t * decode_active.astype(jnp.int32))
            slots = jnp.arange(cache_len)
            # query j sees slot s iff the t=1 step at index idx+j would:
            # p_s = newest position <= idx+j congruent to s, valid iff >= 0
            p_s = qpos[:, :, None] - jnp.remainder(
                qpos[:, :, None] - slots[None, None, :], cache_len)
            bias = jnp.where(p_s >= 0, 0.0, -jnp.inf)          # [B, t, L]
            keys = _cache_read(cfg, ck, sk)
            vals = _cache_read(cfg, cv, sv)
            qg = q.reshape(b, kv_heads, group, t, d_head)
            s = jnp.einsum("bkgtd,bkld->bkgtl", qg, keys,
                           preferred_element_type=jnp.float32)
            s = s * d_head ** -0.5 + bias[:, None, None]
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bkgtl,bkld->bkgtd", p.astype(vals.dtype),
                             vals, preferred_element_type=jnp.float32)
            out = out.astype(cfg.dtype).transpose(0, 3, 1, 2, 4)
            out = out.reshape(b, t, cfg.d_model)
            return out_dense()(out)

        if cfg.decode_len > 0 and t != 1 and cfg.chunked_prefill:
            # CHUNKED PREFILL: continue a (possibly already-advanced) cache
            # with a t-token chunk. Rope positions and cache slots offset by
            # cache_index, and attention runs against the FULL cache — chunk
            # i attends its own chunk's keys plus every pre-chunk position
            # still in its window, so consecutive chunk applies reproduce
            # the one-shot prefill exactly (parity-tested on logits; with
            # an int8 cache, pre-chunk keys read back dequantized, so
            # "exactly" relaxes to quantization tolerance). Costs
            # [t, L+t] dense scores per layer instead of the flash kernel:
            # the bounded-memory trade chunking exists for.
            b = x.shape[0]
            ck, cv, sk, sv, ci, cache_len, is_initialized = self._cache_vars(
                b, kv_heads, d_head)
            start = ci.value if is_initialized else jnp.int32(0)
            qpos = start + jnp.arange(t)
            q = rope(q, qpos, cfg.rope_theta)
            k = rope(k, qpos, cfg.rope_theta)
            # Attend against the PRE-write cache snapshot + the chunk's own
            # K/V. Writing first and attending the cache would evict keys
            # still inside earlier in-chunk queries' windows the moment the
            # rolling buffer wraps (any chunk >= 2 tokens) — the snapshot
            # keeps every key any query can legally see.
            k_old = _cache_read(cfg, ck, sk)
            v_old = _cache_read(cfg, cv, sv)
            if is_initialized:
                keep = min(cache_len, t)
                wslots = jnp.remainder(qpos[t - keep:], cache_len)
                pre = [None if var is None else var.value
                       for var in (ck, cv, sk, sv)]
                _cache_put_at(cfg, ck, sk, wslots, k[:, :, t - keep:, :])
                _cache_put_at(cfg, cv, sv, wslots, v[:, :, t - keep:, :])
                if prefill_len is None:
                    ci.value = start + t
                else:
                    # RIGHT-PADDED chunk (the serving engine's fixed-width
                    # prefill program): only the first prefill_len tokens
                    # are real. Their causal mask already hides the padding
                    # from every valid query (pad sits at LATER positions),
                    # but the rolling-buffer write may have landed pad K/V
                    # in slots that still hold live pre-chunk positions —
                    # restore those slots from the pre-write snapshot and
                    # advance the index by the VALID count only. Written
                    # slots are distinct (min(L,t) consecutive positions),
                    # so the scatter of per-token validity is well-defined.
                    invalid = jnp.zeros((cache_len,), bool).at[wslots].set(
                        jnp.arange(t - keep, t) >= prefill_len)
                    mask = invalid[None, None, :, None]
                    for var, old in zip((ck, cv, sk, sv), pre):
                        if var is not None:
                            var.value = jnp.where(mask, old, var.value)
                    ci.value = start + prefill_len
            # cache slots decode at idx_old = start-1 (newest pre-chunk
            # position congruent to s; same formula as single-token decode).
            # All-valid < start <= qpos, so causality is automatic there.
            slots = jnp.arange(cache_len)
            idx_old = start - 1
            p_s = idx_old - jnp.remainder(idx_old - slots, cache_len)
            ok_old = jnp.broadcast_to(p_s[None, :] >= 0, (t, cache_len))
            ok_new = qpos[None, :] <= qpos[:, None]       # intra-chunk causal
            if self.window:
                ok_old = ok_old & (p_s[None, :] > qpos[:, None] - self.window)
                ok_new = ok_new & (qpos[None, :] > qpos[:, None] - self.window)
            bias = jnp.where(jnp.concatenate([ok_old, ok_new], axis=1),
                             0.0, -jnp.inf)               # [t, L+t]
            keys = jnp.concatenate([k_old, k.astype(cfg.dtype)], axis=2)
            vals = jnp.concatenate([v_old, v.astype(cfg.dtype)], axis=2)
            qg = q.reshape(b, kv_heads, group, t, d_head)
            s = jnp.einsum("bkgtd,bkld->bkgtl", qg, keys,
                           preferred_element_type=jnp.float32)
            s = s * d_head ** -0.5 + bias[None, None, None]
            p = jax.nn.softmax(s, axis=-1)
            out = jnp.einsum("bkgtl,bkld->bkgtd", p.astype(vals.dtype),
                             vals, preferred_element_type=jnp.float32)
            out = out.astype(cfg.dtype).transpose(0, 3, 1, 2, 4)
            out = out.reshape(b, t, cfg.d_model)
            return out_dense()(out)

        if cfg.decode_len > 0 and t != 1:
            # PREFILL: the whole prompt in one causal forward (parallel,
            # MXU-shaped) instead of t sequential single-token steps. The
            # attention math is the ordinary full-sequence path below; the
            # only decode-specific work is the one-shot cache write, which
            # happens after rope (the cache stores roped K). Must be the
            # FIRST cache-mutating call (cache_index is assumed 0, matching
            # generate()'s usage); decode then continues token-by-token.
            pass  # falls through to the full-sequence path
        elif cfg.decode_len > 0:
            # KV-cache decode: one token in, attend against all cached
            # positions <= idx. Cache layout [B, H, L, D] matches training.
            # slot_decode: idx is PER-ROW [B] — rope positions, cache
            # writes and the validity mask all go row-wise, so every slot
            # of a serving batch decodes at its own position.
            b = x.shape[0]
            ck, cv, sk, sv, ci, cache_len, is_initialized = self._cache_vars(
                b, kv_heads, d_head)
            idx = ci.value
            idx_b = idx if cfg.slot_decode else idx[None]        # [B] or [1]
            q = rope(q, idx_b[:, None], cfg.rope_theta)
            k = rope(k, idx_b[:, None], cfg.rope_theta)
            if (is_initialized and cfg.slot_decode
                    and decode_attention.engages(
                        cache_dtype=ck.value.dtype, d_head=d_head,
                        max_len=cache_len, window=self.window,
                        mesh=self.mesh)):
                # ONE kernel a layer (ops/decode_attention.py): each active
                # slot's new K/V row written where it lies in the donated
                # leaf, its live positions read once. Same contract as the
                # branch below: an inactive row writes nothing and does not
                # advance, validity follows from the index alone.
                active = (jnp.ones((b,), bool) if decode_active is None
                          else decode_active)
                out, ck.value, cv.value = decode_attention.decode_attention(
                    q.reshape(b, kv_heads, group, d_head), k[:, :, 0, :],
                    v[:, :, 0, :], ck.value, cv.value, idx, active)
                ci.value = idx + active.astype(jnp.int32)
                return out_dense()(out.reshape(b, 1, cfg.d_model))
            if is_initialized:
                slot = jax.lax.rem(idx, jnp.int32(cache_len))
                if cfg.slot_decode:
                    # decode_active masks the whole step per row: an
                    # inactive slot (mid-prefill in the serving engine)
                    # neither writes its cache nor advances its index, so
                    # the fixed-shape all-slots step cannot corrupt it.
                    _cache_put_rows(cfg, ck, sk, slot[:, None], k,
                                    active=decode_active)
                    _cache_put_rows(cfg, cv, sv, slot[:, None], v,
                                    active=decode_active)
                    ci.value = (idx + 1 if decode_active is None
                                else idx + decode_active.astype(jnp.int32))
                else:
                    _cache_put_dyn(cfg, ck, sk, slot, k)
                    _cache_put_dyn(cfg, cv, sv, slot, v)
                    ci.value = idx + 1
            # slot s currently holds position p_s = idx - ((idx - s) mod L):
            # the newest position <= idx congruent to s. Valid iff p_s >= 0.
            # This single formula covers both layouts — unwritten slots of
            # the plain cache (s > idx) get p_s < 0, and a full rolling
            # buffer keeps exactly the last L = window positions. (It is
            # also why slot_decode needs no cache clearing on slot reuse:
            # resetting a row's index to 0 invalidates every stale slot.)
            slots = jnp.arange(cache_len)
            p_s = idx_b[:, None] - jnp.remainder(
                idx_b[:, None] - slots[None, :], cache_len)
            bias = jnp.where(p_s >= 0, 0.0, -jnp.inf)            # [B|1, L]
            # Grouped attention straight against the un-expanded cache:
            # materializing expand_kv(cache) would re-read group x the cache
            # bytes per token per layer — the exact cost GQA removes. Query
            # head h = kv*group + g reads shared head kv.
            keys = _cache_read(cfg, ck, sk)
            vals = _cache_read(cfg, cv, sv)
            qg = q[:, :, 0, :].reshape(b, kv_heads, group, d_head)
            s = jnp.einsum("bkgd,bkld->bkgl", qg, keys,
                           preferred_element_type=jnp.float32)
            s = s * d_head ** -0.5 + bias[:, None, None, :]
            p = jax.nn.softmax(s, axis=-1)  # >=1 valid key: no dead rows
            out = jnp.einsum("bkgl,bkld->bkgd", p.astype(vals.dtype),
                             vals, preferred_element_type=jnp.float32)
            out = out.astype(cfg.dtype).reshape(b, 1, cfg.d_model)
            return out_dense()(out)

        seq_sharded = (self.mesh is not None
                       and self.mesh.shape.get("seq", 1) > 1)
        impl = effective_attn_impl(cfg.attn_impl, seq_sharded)

        if self.manual_seq:
            # t is the LOCAL shard length; global positions via axis index
            positions = jax.lax.axis_index("seq") * t + jnp.arange(t)
        elif impl == "zigzag" and seq_sharded:
            # rows arrive in the zigzag layout (the data layer permuted
            # them; see zigzag_batch) — RoPE needs their GLOBAL positions,
            # which are exactly the permutation values.
            positions = att.zigzag_permutation(t, self.mesh.shape["seq"])
        else:
            positions = jnp.arange(t)
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
        if cfg.decode_len > 0:
            # prefill cache write: the last min(L, t) roped-K / V rows land
            # at their rolling slots (slot = pos % L, same layout the
            # single-token branch maintains) and cache_index advances by t.
            # K/V are still UNexpanded here — the cache holds kv_heads.
            ck, cv, sk, sv, ci, cache_len, is_initialized = self._cache_vars(
                x.shape[0], kv_heads, d_head)
            # One-shot prefill only: rope used positions 0..t-1 and the
            # slot math below assumes the sequence starts at 0, so a
            # multi-token apply on an ALREADY-ADVANCED cache would corrupt
            # it. The index is traced under jit (generate() upholds the
            # invariant by construction there), but eager misuse is caught.
            if (is_initialized
                    and not isinstance(ci.value, jax.core.Tracer)
                    and int(ci.value) != 0):
                raise ValueError(
                    "multi-token decode apply needs an EMPTY cache (one-"
                    "shot prefill); to continue an advanced cache use "
                    "GPTConfig(chunked_prefill=True) / "
                    "generate(prefill_chunk=...)")
            if is_initialized:
                keep = min(cache_len, t)
                slots = jnp.remainder(jnp.arange(t - keep, t), cache_len)
                _cache_put_at(cfg, ck, sk, slots, k[:, :, t - keep:, :])
                _cache_put_at(cfg, cv, sv, slots, v[:, :, t - keep:, :])
                ci.value = ci.value + t
        # expand AFTER rope (rope on kv_heads is cheaper); the repeat is a
        # transient — cache/params only ever hold kv_heads. The seq-sharded
        # ring skips it entirely: ring_attention folds query groups into
        # rows so the UNEXPANDED K/V ride the ring (group x less ICI).
        ring_gqa = (((impl == "ring" and seq_sharded) or self.manual_seq)
                    and not self.window and group > 1)
        if not ring_gqa:
            k, v = expand_kv(k), expand_kv(v)

        if self.window and seq_sharded and impl == "zigzag":
            raise ValueError(
                f"attn_window={self.window} is not supported with "
                "seq-sharded zigzag (the permuted layout breaks locality); "
                "use attn_impl=ring — windowed seq sharding routes to halo "
                "attention, which is already load-balanced")
        if self.manual_seq:
            # PP x SP: per-shard collectives inside the enclosing manual
            # context — windowed layers fetch one neighbor halo, full
            # layers ride the ring (unexpanded GQA K/V). Falls through to
            # the shared projection tail below.
            if self.window:
                out = att.halo_attention(q, k, v, window=self.window)
            else:
                out = att.ring_attention(q, k, v, causal=True)
        elif impl == "zigzag":
            if seq_sharded:
                out = att.zigzag_ring_attention_sharded(q, k, v, self.mesh)
            else:
                out = att.dense_attention(q, k, v, causal=True,
                                          window=self.window)
        elif impl == "ring":
            if self.window and seq_sharded:
                # windowed + seq-sharded: halo attention — one neighbor-
                # tail ppermute instead of rotating every K/V shard
                out = att.halo_attention_sharded(q, k, v, self.mesh,
                                                 window=self.window)
            elif self.window:
                # ring's own seq=1 fallback is windowless dense — route the
                # window explicitly rather than silently train full-causal
                out = att.dense_attention(q, k, v, causal=True,
                                          window=self.window)
            else:
                out = att.ring_attention_sharded(q, k, v, self.mesh,
                                                 causal=True)
        elif impl == "flash":
            # interpret mode (here and at every other `default_backend()
            # != "tpu"` in models/, ops/ and parallel/) is reachable only
            # when the CPU was ASKED for: a launcher given --backend=tpu
            # refuses whatever else JAX came up on (cli/launch.py
            # init_backend), so the chip path never interprets a kernel.
            out = fa.flash_attention_sharded(
                q, k, v, self.mesh, causal=True, window=self.window,
                block_h=cfg.flash_block_h,
                interpret=jax.default_backend() != "tpu")
        else:
            out = att.dense_attention(q, k, v, causal=True,
                                      window=self.window)
        out = out.transpose(0, 2, 1, 3).reshape(x.shape[0], t, cfg.d_model)
        out = out_dense()(out)
        return nn.Dropout(cfg.dropout)(out, deterministic=deterministic)


class _Kernel(nn.Module):
    """A bias-free projection's ``kernel`` for a caller that multiplies it
    in more than one form (the same leaf name ``nn.Dense`` gives)."""

    shape: tuple
    param_dtype: jnp.dtype

    @nn.compact
    def __call__(self):
        return self.param("kernel", nn.initializers.lecun_normal(),
                          self.shape, self.param_dtype)


def _divisor_at_most(n: int, limit: int) -> int:
    d = min(n, limit)
    while n % d:
        d -= 1
    return d


#: cached positions a chunk's queries meet in one pass of the prefill loop
_LATENT_PREFILL_BLOCK = 1024


class LatentAttention(nn.Module):
    """Multi-head latent attention (:class:`LatentAttentionConfig`), no bias:

    ``c_q = RMSNorm(W_qa h)``; ``q = W_qb c_q`` -> heads x (nope + rope
    widths); ``[c_kv ; k_r] = W_kva h``; ``c_kv <- RMSNorm(c_kv)``; ``k_r``
    is rotated and is ONE key part shared by all heads; ``[k_i ; v_i] =
    W_kvb,i c_kv``; ``score_ij = (q_i^nope . k_i,j + q_i^rope . k_r,j)
    sigma``; causal softmax; ``out = W_o concat_i sum_j p_ij v_i,j``.

    **The cache** (``decode_len > 0``) is ONE leaf, ``cached_latent`` [rows,
    kv_rank + rope_dim, positions]: per position the normalised ``c_kv`` and
    the rotated ``k_r`` (576 numbers at the published widths), never
    per-head K/V, and no head axis. Positions are the MINOR axis: the lanes
    of the TPU, the layout the decode kernel reads as it lies and a chunk
    writes as one slab. Validity follows from ``cache_index`` alone, as for
    K/V: a stale row is never read, so nothing is cleared.

    **Two forms of one attention.** A multi-token apply (prefill, one-shot
    or continuing an advanced cache) EXPANDS: the chunk's own keys and
    values come from its fresh ``c_kv``, and the cached rows it attends to
    are expanded through ``W_kvb`` a block of positions at a time under a
    running softmax, up to the index and no further. At 512 queries a chunk
    that is half the arithmetic of the absorbed form (scores over 192 and
    values over 128 numbers a head and position plus one expansion a
    position, against 576 and 512). The single-token step ABSORBS:
    ``q~_i = W_kvb,i^K^T q_i^nope``, ``score_ij = (q~_i . c_kv,j + q_i^rope
    . k_r,j) sigma``, ``o_i = W_kvb,i^V (sum_j p_ij c_kv,j)`` — the live
    latent rows are read once and nothing is expanded
    (``ops/decode_attention.py: latent_decode_attention`` where it engages,
    the same arithmetic in XLA over all positions elsewhere).
    ``prefill_len`` and ``decode_active`` as :class:`CausalSelfAttention`:
    pad columns of a ragged chunk are never written, an inactive row of the
    slot step writes nothing and does not advance."""

    cfg: GPTConfig
    mesh: Optional[Mesh]

    @nn.compact
    def __call__(self, x, deterministic: bool, prefill_len=None,
                 decode_active=None):
        cfg, la = self.cfg, self.cfg.latent
        b, t, _ = x.shape
        heads, rank = cfg.heads, la.kv_rank
        nope, rot, v_dim = la.nope_dim, la.rope_dim, la.v_dim
        width = la.latent_width
        if cfg.slot_decode and t != 1:
            raise ValueError(
                "the slot VERIFY step is not written for a latent cache: "
                "speculative decoding does not serve a model with latent "
                "attention")
        dense = lambda name, n: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name)
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=cfg.norm_eps, dtype=jnp.float32, param_dtype=jnp.float32,
            name=name)
        f32 = jnp.float32

        c_q = norm("q_a_norm")(dense("q_a", la.q_rank)(x)).astype(cfg.dtype)
        q = dense("q_b", heads * (nope + rot))(c_q).reshape(
            b, t, heads, nope + rot).transpose(0, 2, 1, 3)       # [B,H,t,.]
        kv = dense("kv_a", width)(x)                             # [B,t,W]
        c_kv = norm("kv_a_norm")(kv[..., :rank]).astype(cfg.dtype)
        w_kvb = _Kernel((rank, heads * (nope + v_dim)), cfg.param_dtype,
                        name="kv_b")().astype(cfg.dtype).reshape(
                            rank, heads, nope + v_dim)
        w_k, w_v = w_kvb[..., :nope], w_kvb[..., nope:]          # [c,H,d]
        sigma = la.softmax_scale
        turn = lambda a, pos: rope(  # noqa: E731
            a, pos, cfg.rope_theta, freqs=la.frequencies(cfg.rope_theta))

        def project_out(out):                                    # [B,H,t,v]
            out = out.astype(cfg.dtype).transpose(0, 2, 1, 3).reshape(
                b, t, heads * v_dim)
            return dense("attn_out", cfg.d_model)(out)

        def expanded(c):                  # [B,n,rank] -> k^nope, v [B,H,n,.]
            kv_i = jnp.einsum("bnc,chd->bhnd", c, w_kvb,
                              preferred_element_type=f32).astype(cfg.dtype)
            return kv_i[..., :nope], kv_i[..., nope:]

        def fold(carry, q_nope, q_rope, c, k_rope, visible):
            """One block of keys into the running softmax ``(m, l, acc)`` of
            the queries: keys and values expanded from the block's latent
            rows ``c`` [B,n,rank] and its rotated parts ``k_rope`` [B,n,R];
            ``visible`` [t|1, n] bool."""
            m, l, acc = carry
            k_nope, v = expanded(c)
            s = (jnp.einsum("bhqd,bhkd->bhqk", q_nope, k_nope,
                            preferred_element_type=f32)
                 + jnp.einsum("bhqr,bkr->bhqk", q_rope, k_rope,
                              preferred_element_type=f32)) * sigma
            s = jnp.where(visible, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new[..., None])
            acc = alpha[..., None] * acc + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(cfg.dtype), v,
                preferred_element_type=f32)
            return m_new, alpha * l + jnp.sum(p, axis=-1), acc

        def own_attention(q_nope, q_rope, k_rope):
            """The tokens of this apply among themselves, causal: the
            running softmax's first block (every query sees itself, so its
            maximum is finite from here on)."""
            at = jnp.arange(t)
            empty = (jnp.full((b, heads, t), -jnp.inf, f32),
                     jnp.zeros((b, heads, t), f32),
                     jnp.zeros((b, heads, t, v_dim), f32))
            return fold(empty, q_nope, q_rope, c_kv, k_rope,
                        at[None, :] <= at[:, None])

        if cfg.decode_len <= 0:
            pos = jnp.arange(t)
            _, l, acc = own_attention(
                q[..., :nope], turn(q[..., nope:], pos),
                turn(kv[:, None, :, rank:], pos)[:, 0])
            out = project_out(acc / l[..., None])
            return nn.Dropout(cfg.dropout)(out, deterministic=deterministic)

        ready = self.has_variable("cache", "cached_latent")
        # NOTE: a new cache variable must also be classified in the
        # registries below (_LATENT_CACHE_KEYS): beams, the serve engine's
        # slot slicing and the page cache select leaves by name
        leaf = self.variable("cache", "cached_latent", jnp.zeros,
                             (b, width, cfg.decode_len), cfg.dtype)
        ci = self.variable("cache", "cache_index",
                           lambda: jnp.zeros((b,) if cfg.slot_decode else (),
                                             jnp.int32))
        max_len = cfg.decode_len

        if t != 1:
            # PREFILL, one-shot or continuing: the expanded form
            if t > max_len:
                raise ValueError(
                    f"a {t}-token apply does not fit the latent cache of "
                    f"{max_len} positions")
            start = ci.value if ready else jnp.int32(0)
            qpos = start + jnp.arange(t)
            q_nope, q_rope = q[..., :nope], turn(q[..., nope:], qpos)
            k_rope = turn(kv[:, None, :, rank:], qpos)[:, 0]     # [B,t,R]
            block = _divisor_at_most(max_len, _LATENT_PREFILL_BLOCK)
            cached = leaf.value

            def cached_block(j, carry):
                rows = jnp.swapaxes(jax.lax.dynamic_slice(
                    cached, (0, 0, j * block), (b, width, block)), 1, 2)
                return fold(carry, q_nope, q_rope, rows[..., :rank],
                            rows[..., rank:],
                            (j * block + jnp.arange(block) < start)[None, :])

            _, l, acc = jax.lax.fori_loop(
                0, (start + block - 1) // block, cached_block,
                own_attention(q_nope, q_rope, k_rope))
            if ready:
                # the chunk's rows as one slab [B, W, t] at their positions;
                # a slab that would cross the cache's end is moved back to
                # fit and its rows moved forward in it, so a position at or
                # past the end is dropped, never wrapped
                n_valid = t if prefill_len is None else prefill_len
                new = jnp.swapaxes(jnp.concatenate(
                    [c_kv, k_rope.astype(cfg.dtype)], axis=-1), 1, 2)
                at0 = jnp.minimum(start, max_len - t)
                shift = start - at0
                old = jax.lax.dynamic_slice(cached, (0, 0, at0),
                                            (b, width, t))
                moved = jax.lax.dynamic_slice(
                    jnp.concatenate([jnp.zeros_like(new), new], axis=2),
                    (0, 0, t - shift), (b, width, t))
                col = jnp.arange(t)
                keep = (col >= shift) & (col - shift < n_valid)
                leaf.value = jax.lax.dynamic_update_slice(
                    cached, jnp.where(keep[None, None, :], moved, old),
                    (0, 0, at0))
                ci.value = start + n_valid
            return project_out(acc / l[..., None])

        # DECODE: one token a row against the latent rows, absorbed
        idx = ci.value
        idx_b = idx if cfg.slot_decode else jnp.broadcast_to(idx, (b,))
        q_abs = jnp.einsum("bhd,chd->bhc", q[:, :, 0, :nope], w_k,
                           preferred_element_type=f32).astype(cfg.dtype)
        q_lat = jnp.concatenate(
            [q_abs, turn(q[..., nope:], idx_b[:, None])[:, :, 0]], axis=-1)
        new = jnp.concatenate(
            [c_kv[:, 0], turn(kv[:, None, :, rank:], idx_b[:, None])[
                :, 0, 0].astype(cfg.dtype)], axis=-1)             # [B,W]
        active = (jnp.ones((b,), bool) if decode_active is None
                  else decode_active)
        if (ready and cfg.slot_decode
                and decode_attention.latent_engages(
                    cache_dtype=leaf.value.dtype, width=width, rank=rank,
                    max_len=max_len, mesh=self.mesh)):
            o, leaf.value = decode_attention.latent_decode_attention(
                q_lat, new, leaf.value, idx, active, rank=rank, scale=sigma)
        else:
            rows = leaf.value
            lane = jnp.arange(max_len)
            if ready:
                hit = (lane[None, :] == idx_b[:, None]) & active[:, None]
                rows = jnp.where(hit[:, None, :], new[:, :, None], rows)
                leaf.value = rows
            s = jnp.einsum("bhw,bwl->bhl", q_lat, rows,
                           preferred_element_type=f32) * sigma
            s = jnp.where(lane[None, None, :] <= idx_b[:, None, None], s,
                          -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bhl,bcl->bhc", p.astype(cfg.dtype),
                           rows[:, :rank], preferred_element_type=f32)
        if ready:
            ci.value = idx + (active.astype(jnp.int32) if cfg.slot_decode
                              else 1)
        out = jnp.einsum("bhc,chd->bhd", o.astype(cfg.dtype), w_v,
                         preferred_element_type=f32)
        return project_out(out[:, :, None, :])


class ShortConv(nn.Module):
    """Gated short convolution, the hybrid decoders' cheap operator:
    ``[B, C, X] = split3(W_in u)``; ``z = B * X``; ``c_t = sum_j w[j] *
    z_{t-(L-1)+j}`` (depthwise, causal, ``L = conv_kernel`` taps, zeros
    before the sequence's start); ``out = W_out (C * c)``. No bias anywhere.

    Decoding (``decode_len > 0``) keeps the last ``L`` columns of ``z`` per
    row as ``conv_state`` [B, L, d] in the ``cache`` collection (width minor:
    the TPU's lanes; [B, d, L] would pad 3 columns to 128). It is a RUNNING
    state, not a position-indexed one, so K/V's habits do not carry over
    (docs/SERVING.md): a stale state is read, so whoever re-uses a row must
    zero it; a multi-token apply CONTINUES the state it finds (chunked
    prefill, and one-shot prefill from the zeros ``init`` leaves); with
    ``prefill_len`` the new state is the last ``L`` VALID columns, so the
    pad of a ragged last chunk never enters it; ``decode_active`` rows alone
    advance in the slot step."""

    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, prefill_len=None, decode_active=None):
        cfg = self.cfg
        b, t, d = x.shape
        taps = cfg.conv_kernel
        dense = lambda name, n: nn.Dense(  # noqa: E731
            n, use_bias=False, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            name=name)
        gate_b, gate_c, xs = jnp.split(dense("in_proj", 3 * d)(x), 3, axis=-1)
        z = gate_b * xs                                        # [B, t, d]
        w = self.param("conv_w", nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=0, out_axis=1), (taps, d),
            jnp.float32)

        def conv(cols):
            """[B, taps - 1 + n, d] -> [B, n, d]: output i reads columns
            i .. i + taps - 1."""
            n = cols.shape[1] - (taps - 1)
            return sum(w[j] * cols[:, j:j + n].astype(jnp.float32)
                       for j in range(taps))

        if cfg.decode_len > 0:
            ready = self.has_variable("cache", "conv_state")
            state = self.variable("cache", "conv_state", jnp.zeros,
                                  (b, taps, d), cfg.dtype)
            if t == 1:
                new = jnp.concatenate([state.value[:, 1:], z], axis=1)
                c = conv(new)
                if ready and decode_active is not None:
                    new = jnp.where(decode_active[:, None, None], new,
                                    state.value)
            else:
                if cfg.slot_decode:
                    raise ValueError(
                        "the slot VERIFY step has no conv state to roll a "
                        "rejected tail back to: speculative decoding does "
                        "not serve a model with conv layers")
                cols = jnp.concatenate([state.value, z], axis=1)
                c = conv(cols[:, 1:])
                new = jax.lax.dynamic_slice_in_dim(
                    cols, t if prefill_len is None else prefill_len, taps,
                    axis=1)
            if ready:
                state.value = new
        else:
            c = conv(jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0))))
        y = gate_c.astype(jnp.float32) * c
        return dense("out_proj", d)(y.astype(cfg.dtype))


class Block(nn.Module):
    cfg: GPTConfig
    mesh: Optional[Mesh]
    use_moe: bool
    window: int  # no default — see CausalSelfAttention.window
    manual_seq: bool = False  # see CausalSelfAttention.manual_seq
    #: the layer's operator: "attn", "conv" or "mla" (GPTConfig.layer_kinds)
    op: str = "attn"
    #: the FFN is the dropless routed-expert layer (GPTConfig.experts)
    experts: bool = False

    @nn.compact
    def __call__(self, x, deterministic: bool, prefill_len=None,
                 decode_active=None):
        cfg = self.cfg
        overlap = (cfg.tp_overlap and self.mesh is not None
                   and not self.manual_seq)
        h = _norm(cfg, "ln1")(x)
        if self.op == "conv":
            x = x + ShortConv(cfg, name="conv")(h, prefill_len,
                                                decode_active)
        elif self.op == "mla":
            x = x + LatentAttention(cfg, self.mesh, name="attention")(
                h, deterministic, prefill_len, decode_active)
        else:
            x = x + CausalSelfAttention(cfg, self.mesh, self.window,
                                        manual_seq=self.manual_seq,
                                        name="attention")(h, deterministic,
                                                          prefill_len,
                                                          decode_active)
        if overlap:
            x = comms.tp_token_sharded(x, self.mesh)
        h = _norm(cfg, "ln2")(x)
        tp_dense = lambda name, n, parallel: comms.TpDense(  # noqa: E731
            n, self.mesh, parallel, overlap=overlap, use_bias=cfg.use_bias,
            dtype=cfg.dtype, param_dtype=cfg.param_dtype,
            precision=cfg.matmul_precision, name=name)
        if self.experts:
            # tokens that are not there (a slot that is not decoding, the
            # pad of a ragged prefill chunk) choose no expert
            live = None
            if decode_active is not None:
                live = jnp.broadcast_to(decode_active[:, None], x.shape[:2])
            elif prefill_len is not None:
                live = jnp.broadcast_to(
                    jnp.arange(x.shape[1])[None, :] < prefill_len,
                    x.shape[:2])
            y = moe_lib.DroplessMoE(cfg.d_model, cfg.experts,
                                    dtype=cfg.dtype,
                                    param_dtype=cfg.param_dtype,
                                    name="experts")(h, live)
            if cfg.shared_expert_ff:
                # the shared expert: every token, every chip alike
                y = y + tp_dense("shared_out", cfg.d_model, "row")(
                    nn.silu(tp_dense("shared_gate", cfg.shared_expert_ff,
                                     "column")(h))
                    * tp_dense("shared_up", cfg.shared_expert_ff,
                               "column")(h))
        elif self.use_moe:
            y = moe_lib.SwitchFFN(cfg.d_model, cfg.d_ff, cfg.moe,
                                  dtype=cfg.dtype, name="moe")(h)
        elif cfg.ffn == "swiglu":
            y = (nn.silu(tp_dense("mlp_gate", cfg.d_ff, "column")(h))
                 * tp_dense("mlp_up", cfg.d_ff, "column")(h))
            y = tp_dense("mlp_out", cfg.d_model, "row")(y)
        else:
            # the Megatron pair (collective matmuls under overlap; gelu
            # runs on the feature-sharded activations in between, and the
            # residual stream stays token-sharded over ('seq','model'))
            y = tp_dense("mlp_in", cfg.d_ff, "column")(h)
            y = nn.gelu(y, approximate=True)
            y = tp_dense("mlp_out", cfg.d_model, "row")(y)
        y = nn.Dropout(cfg.dropout)(y, deterministic=deterministic)
        if overlap:
            # keep the residual stream in the Megatron-SP token-sharded
            # layout between blocks (comms.tp_token_sharded docstring)
            return comms.tp_token_sharded(x + y, self.mesh)
        return x + y


class GPT(nn.Module):
    """Decoder-only LM. Input ids [B,T] → logits [B,T,V] (or the pre-head
    hidden states with ``return_hidden=True`` — the vocab-chunked loss
    path applies the lm_head itself, fused chunk by chunk)."""

    cfg: GPTConfig
    mesh: Optional[Mesh] = None

    @nn.compact
    def __call__(self, input_ids, *, deterministic: bool = True,
                 return_hidden: bool = False, prefill_len=None,
                 decode_active=None):
        cfg = self.cfg
        overlap = cfg.tp_overlap and self.mesh is not None
        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="token_embed")
        x = embed(input_ids)
        if overlap:
            # pin the embed OUTPUT to the baseline batch layout first (the
            # vocab-sharded masked-lookup + psum spelling, no table
            # gather), then enter the Megatron-SP layout with a local
            # slice below.
            x = comms.tp_activation_gathered(x, self.mesh)
        x = nn.Dropout(cfg.dropout)(x, deterministic=deterministic)
        if overlap:
            x = comms.tp_token_sharded(x, self.mesh)
        block = Block
        if cfg.remat:
            block = nn.remat(Block, static_argnums=(2,))
        for i in range(cfg.layers):
            use_moe = cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0
            x = block(cfg, self.mesh, use_moe, cfg.layer_window(i),
                      op=cfg.layer_kind(i),
                      experts=cfg.layer_has_experts(i),
                      name=f"layer_{i}")(x, deterministic, prefill_len,
                                         decode_active)
        x = _norm(cfg, "ln_f")(x)
        if return_hidden:
            # the chunked-loss path applies lm_head itself; the Dense
            # below must still exist at init time, which it does — init
            # always runs with return_hidden=False
            return x
        if overlap:
            # the ONE gather the head genuinely needs (Megatron-SP): the
            # ACTIVATIONS come back over the TP axis for the vocab-parallel
            # head matmul — never the [D, V] head kernel.
            x = comms.tp_activation_gathered(x, self.mesh)
        if cfg.tie_head:
            # the table at its storage dtype, accumulated in float32
            return jnp.einsum("btd,vd->btv", x.astype(cfg.dtype),
                              embed.embedding.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)
        if cfg.param_dtype != jnp.float32:
            # an untied head stored as the other matrices are, multiplied
            # as the tied one is: accumulated in float32
            head = _Kernel((cfg.d_model, cfg.vocab_size), cfg.param_dtype,
                           name="lm_head")()
            return jnp.einsum("btd,dv->btv", x.astype(cfg.dtype),
                              head.astype(cfg.dtype),
                              preferred_element_type=jnp.float32)
        logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                          param_dtype=jnp.float32, name="lm_head")(x)
        return logits


def zigzag_batch(batch: dict, seq_shards: int) -> dict:
    """Permute a CLM batch into the zigzag layout (host-side numpy).

    With ``attn_impl="zigzag"`` the whole model runs in the permuted order
    (per-token CE is order-invariant; RoPE gets the true global positions
    inside the attention module), so permuting input_ids and labels at the
    data layer is the ONLY change training needs.
    """
    import numpy as np

    from dtf_tpu.ops.attention import zigzag_permutation

    t = batch["input_ids"].shape[1]
    perm = np.asarray(zigzag_permutation(t, seq_shards))
    return {**batch, "input_ids": batch["input_ids"][:, perm],
            "labels": batch["labels"][:, perm]}


def make_init(cfg: GPTConfig, mesh: Optional[Mesh] = None, seq_len: int = 128):
    model = GPT(cfg, mesh)
    b = mesh.shape.get("data", 1) if mesh is not None else 1

    def init_fn(rng):
        ids = jnp.zeros((b, seq_len), jnp.int32)
        return model.init(rng, ids, deterministic=True)

    return model, init_fn


def cache_shardings(mesh: Mesh, cache_shapes):
    """NamedSharding tree for a KV-cache collection: [B, H, L, D] leaves
    shard batch over ``data`` and heads over ``model`` (the layout
    ``decode_len`` exists for — each TP shard serves its own heads, each DP
    shard its own sequences); scalar indices replicate."""
    from jax.sharding import NamedSharding

    def leaf(s):
        if getattr(s, "ndim", 0) == 4:
            return NamedSharding(mesh, P("data", "model", None, None))
        return NamedSharding(mesh, P())

    return jax.tree.map(leaf, cache_shapes)


def filter_logits(logits: jax.Array, *, top_k: int = 0,
                  top_p: float = 1.0) -> jax.Array:
    """Top-k / nucleus (top-p) filtering: disallowed logits become -inf.

    Static shapes throughout (sorts + thresholds, no gather of a dynamic
    count), so it jits and vmaps cleanly inside the decode scan. ``top_k=0``
    and ``top_p=1.0`` are no-ops; the highest-probability token is always
    kept. k-filter applies first, then the nucleus is computed over the
    k-survivors (the standard sequential-warper composition). Exactly k
    tokens survive the k-filter — ties at the k-th logit are broken by
    token index (lower index wins), matching sorted-order semantics rather
    than keeping every tied token. Callers should pass ALREADY-TEMPERED
    logits (logits/temperature) so the nucleus reflects the distribution
    actually sampled — ``generate`` does.
    """
    if top_k <= 0 and top_p >= 1.0:
        return logits
    vocab = logits.shape[-1]
    desc = jnp.sort(logits, axis=-1)[..., ::-1]   # serves the top-p pass
    if top_k > 0:
        k = min(top_k, vocab)
        # rank via double argsort (stable ⇒ ties broken by token index);
        # a plain `logits < desc[k-1]` threshold would keep EVERY token
        # tied with the k-th largest (ADVICE r3)
        order = jnp.argsort(-logits, axis=-1)
        ranks = jnp.argsort(order, axis=-1)       # 0 = largest logit
        logits = jnp.where(ranks < k, logits, -jnp.inf)
        desc = jnp.where(jnp.arange(vocab) < k, desc, -jnp.inf)
    if top_p < 1.0:
        probs = jax.nn.softmax(desc, axis=-1)     # -inf rows contribute 0
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p          # first excluded crosses top_p
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        logits = jnp.where(logits < thresh, -jnp.inf, logits)
    return logits


def filter_logits_dynamic(logits: jax.Array, *, top_k: jax.Array,
                          top_p: jax.Array) -> jax.Array:
    """:func:`filter_logits` with TRACED ``top_k`` / ``top_p`` scalars.

    The serving engine (:mod:`dtf_tpu.serve`) folds per-slot sampling
    params into ONE fixed-shape decode program (vmapped over slots), so
    k/p arrive as runtime values, not Python ints. Same semantics as the
    static path — including its no-op gates: the k-filter is selected only
    where ``top_k > 0`` and the nucleus only where ``top_p < 1``, so a
    slot running (0, 1.0) sees BIT-identical logits to an offline
    ``generate()`` with the filters off (the engine/offline parity
    contract), rather than "numerically equivalent" recomputed ones.
    """
    vocab = logits.shape[-1]
    desc = jnp.sort(logits, axis=-1)[..., ::-1]
    k = jnp.clip(top_k, 1, vocab)             # only read where top_k > 0
    order = jnp.argsort(-logits, axis=-1)
    ranks = jnp.argsort(order, axis=-1)       # 0 = largest logit
    use_k = top_k > 0
    logits = jnp.where(use_k & (ranks >= k), -jnp.inf, logits)
    desc = jnp.where(use_k & (jnp.arange(vocab) >= k), -jnp.inf, desc)
    probs = jax.nn.softmax(desc, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_p
    thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True)
    return jnp.where((top_p < 1.0) & (logits < thresh), -jnp.inf, logits)


def generate(model: GPT, params, prompt: jax.Array, n_new: int,
             *, rng: Optional[jax.Array] = None,
             temperature: float = 0.0,
             top_k: int = 0, top_p: float = 1.0,
             eos_id: Optional[int] = None, pad_id: int = 0,
             prefill_chunk: int = 0,
             mesh: Optional[Mesh] = None) -> jax.Array:
    """Autoregressive decode: one-pass prefill + a single-token ``lax.scan``.

    ``model.cfg.decode_len`` must cover prompt+new tokens. ``prompt``
    [B, T_p] int32; returns [B, T_p + n_new]. Greedy when temperature==0,
    else temperature sampling with optional ``top_k`` / nucleus ``top_p``
    filtering (:func:`filter_logits`). The prompt is PREFILLED in one
    parallel causal forward that writes the KV cache (MXU-shaped work,
    not T_p sequential steps); generation is then a jittable scan with the
    cache as carried state, one token per step — the standard TPU serving
    shape.

    ``eos_id``: once a sequence emits it, every later token is ``pad_id``
    (the scan stays fixed-length — static shapes — but the output is
    properly terminated per sequence).

    ``prefill_chunk``: 0 = the whole prompt in one forward (fastest —
    flash-kernel attention). >0 = prefill in chunks of that many tokens
    via the cache-continuing path (``GPTConfig.chunked_prefill``): peak
    prefill activation memory is O(chunk·(L+chunk)) instead of O(T_p²),
    the knob for prompts whose one-shot score matrix doesn't fit.
    Matches one-shot prefill logits exactly (parity-tested), including
    rolling-window caches that wrap mid-prompt — at full-precision cache
    dtypes. With ``kv_cache_dtype="int8"`` chunked prefill reads
    pre-chunk keys back DEQUANTIZED while one-shot attends raw K/V, so
    parity is within quantization tolerance, not exact (tested).

    ``mesh``: shard the decode — the KV cache lands P('data','model')
    (batch over data shards, heads over TP shards; see
    :func:`cache_shardings`), the prompt P('data'). Params keep whatever
    sharding the caller placed them with (e.g. :data:`tp_rules`); GSPMD
    propagates through the scan, so TP decode needs no other change.
    """
    cfg = model.cfg
    b, t_p = prompt.shape
    total = t_p + n_new
    if n_new < 1:
        raise ValueError(f"n_new={n_new} must be >= 1")
    if cfg.decode_len < total:
        raise ValueError(
            f"decode_len={cfg.decode_len} < prompt+new={total}")
    if rng is None:
        rng = jax.random.PRNGKey(0)
    if mesh is not None:
        if b % mesh.shape.get("data", 1):
            raise ValueError(f"decode batch {b} not divisible by the data "
                             f"axis ({mesh.shape.get('data', 1)})")
        kv_heads = cfg.kv_heads_resolved
        if cfg.heads % mesh.shape.get("model", 1):
            raise ValueError(f"{cfg.heads} heads not divisible by the model "
                             f"axis ({mesh.shape.get('model', 1)})")
        if kv_heads % mesh.shape.get("model", 1):
            raise ValueError(f"{kv_heads} kv_heads not divisible by the "
                             f"model axis ({mesh.shape.get('model', 1)}) — "
                             "the cache shards heads over 'model'")

    # Build an all-zeros cache (index 0, no slots written) without
    # materialising a throwaway parameter set: eval_shape traces init
    # abstractly, then we allocate zeros matching the cache collection.
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((b, 1), jnp.int32)))
    if mesh is not None:
        csh = cache_shardings(mesh, shapes["cache"])
        # sharding-aware allocation: each device materializes only its
        # shard — the global-zeros-then-reshard form would OOM device 0 for
        # exactly the cache sizes this path exists for.
        cache0 = jax.tree.map(
            lambda s, sh: jnp.zeros(s.shape, s.dtype, device=sh),
            shapes["cache"], csh)
        prompt = jax.device_put(
            prompt, jax.sharding.NamedSharding(mesh, P("data", None)))
    else:
        cache0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                              shapes["cache"])

    def pick(nxt_logits, sub):
        if temperature > 0.0:
            # temper FIRST so the nucleus is built from the distribution
            # actually sampled (the standard warper ordering).
            filtered = filter_logits(nxt_logits / temperature,
                                     top_k=top_k, top_p=top_p)
            nxt = jax.random.categorical(sub, filtered, -1)
        else:
            nxt = jnp.argmax(nxt_logits, -1)
        return nxt.astype(jnp.int32)

    logits, cache = _prefill(model, params, cache0, prompt, prefill_chunk)
    rng, sub = jax.random.split(rng)
    tok0 = pick(logits[:, -1], sub)
    # EOS semantics: a sequence that has EMITTED eos_id keeps stepping (the
    # scan is fixed-length — the standard TPU shape) but every later token
    # is pad_id. done flips AFTER the eos token itself is kept.
    done0 = (tok0 == eos_id) if eos_id is not None else None

    def body(carry, _):
        cache, tok, done, rng = carry
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tok[:, None],
            deterministic=True, mutable=["cache"])
        rng, sub = jax.random.split(rng)
        nxt = pick(logits[:, 0], sub)
        if eos_id is not None:
            nxt = jnp.where(done, jnp.int32(pad_id), nxt)
            done = done | (nxt == eos_id)
        return (mut["cache"], nxt, done, rng), nxt

    (_, _, _, _), toks = jax.lax.scan(
        body, (cache, tok0, done0, rng), None, length=n_new - 1)
    out = jnp.concatenate(
        [prompt, tok0[:, None], toks.T.astype(jnp.int32)], axis=1)
    return out


def _prefill(model: GPT, params, cache0, prompt, prefill_chunk: int):
    """The shared prompt prefill: one parallel causal forward that writes
    the KV cache (t_p MXU-shaped steps collapse into one), or — with
    ``prefill_chunk`` — a static Python loop of cache-continuing applies
    at O(chunk·(L+chunk)) peak memory. Returns (logits, cache)."""
    cfg = model.cfg
    t_p = prompt.shape[1]
    if prefill_chunk > 0:
        cmodel = GPT(dataclasses.replace(cfg, chunked_prefill=True),
                     model.mesh)
        cache, logits = cache0, None
        for s0 in range(0, t_p, prefill_chunk):
            logits, mut = cmodel.apply(
                {"params": params, "cache": cache},
                prompt[:, s0:s0 + prefill_chunk],
                deterministic=True, mutable=["cache"])
            cache = mut["cache"]
        return logits, cache
    logits, mut = model.apply({"params": params, "cache": cache0},
                              prompt, deterministic=True,
                              mutable=["cache"])
    return logits, mut["cache"]


#: cache-collection leaves whose leading dim is the batch (beam search
#: clones and reorders exactly these); every other cache key must appear in
#: _NON_BATCH_CACHE_KEYS, so an unrecognized leaf fails loudly instead of
#: silently riding the beams unreordered. The SAME set is the paged-leaf
#: registry: every batch-led leaf is [rows, H, L, D]-shaped, so the prefix
#: page cache (dtf_tpu/serve/pages.py) reads/writes fixed-size windows of
#: the L axis through :func:`cache_load_pages` / :func:`cache_save_pages` —
#: a new cache variable added to _cache_vars must be classified here or
#: every consumer (beams, serve slot slicing, pages) fails loudly at once.
_BATCH_LED_CACHE_KEYS = frozenset(
    {"cached_key", "cached_value", "key_scale", "value_scale"})
_NON_BATCH_CACHE_KEYS = frozenset({"cache_index"})
#: RECURRENT leaves ([rows, ...], ShortConv's ``conv_state``): led by the
#: batch like the above — beams reorder them, the serve engine slices them
#: per slot — but they hold a running summary and no positions. A stale one
#: IS read (admission zeroes it), and there is nothing in it to page or to
#: roll back to.
_RECURRENT_CACHE_KEYS = frozenset({"conv_state"})
#: LATENT leaves ([rows, width, L], LatentAttention's ``cached_latent``): led
#: by the batch and indexed by position like K/V, so beams reorder them, the
#: serve engine slices them per slot and a stale row is never read; but
#: there is no head axis and positions are the MINOR axis, which the page
#: programs' ``[rows, H, L, D]`` windows do not read yet.
_LATENT_CACHE_KEYS = frozenset({"cached_latent"})
#: every leaf whose leading dim is the batch: what beams reorder and the
#: serve engine slices per slot
_ROW_LED_CACHE_KEYS = (_BATCH_LED_CACHE_KEYS | _RECURRENT_CACHE_KEYS
                       | _LATENT_CACHE_KEYS)


def _path_key(k) -> str:
    return getattr(k, "key", str(k))


def _cache_leaf_name(path) -> str:
    return _path_key(path[-1])


def _set_by_path(tree: dict, path, leaf) -> None:
    node = tree
    for k in path[:-1]:
        node = node.setdefault(_path_key(k), {})
    node[_path_key(path[-1])] = leaf


def _get_by_path(tree, path):
    node = tree
    for k in path:
        node = node[_path_key(k)]
    return node


def _paged_leaf_check(name: str) -> bool:
    """True for paged leaves, False for index leaves; loud otherwise —
    the completeness contract of ``_BATCH_LED_CACHE_KEYS``."""
    if name in _NON_BATCH_CACHE_KEYS:
        return False
    if name in _RECURRENT_CACHE_KEYS:
        raise ValueError(
            f"cache leaf {name!r} is a recurrent state: it has no positions "
            "to page (the prefix page cache does not serve a model with "
            "conv layers)")
    if name in _LATENT_CACHE_KEYS:
        raise ValueError(
            f"cache leaf {name!r} is a latent cache, [rows, width, "
            "positions]: the page programs copy [rows, heads, positions, "
            "width] windows and do not page it yet (the prefix page cache "
            "does not serve a model with latent attention)")
    if name not in _BATCH_LED_CACHE_KEYS:
        raise ValueError(
            f"unknown cache leaf {name!r}: add it to "
            "_BATCH_LED_CACHE_KEYS or _NON_BATCH_CACHE_KEYS so the "
            "page cache knows whether to page it")
    return True


def cache_index_of(cache) -> jax.Array:
    """The cache's position counter — the first ``cache_index`` leaf.
    Every layer's counter advances in lockstep (each apply touches all
    layers equally), so one leaf is the whole cache's position."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        if _cache_leaf_name(path) == "cache_index":
            return leaf
    raise ValueError("cache has no cache_index leaf")


def cache_rollback(cache, new_index, active=None):
    """Set every layer's ``cache_index`` to ``new_index`` — the
    speculative-decode ROLLBACK: after a verify pass wrote k+1 candidate
    positions, the accepted boundary is a per-row index assignment and
    nothing else. Rejected-tail K/V stays in the cache as stale bytes;
    the validity bias (``p_s >= 0``) derives visibility from the index,
    so no clearing pass exists to forget. ``active`` (optional [S] bool)
    preserves inactive rows' current per-leaf counters — a mid-prefill
    slot's index must not be clobbered by its neighbors' verify tick."""
    def leaf(path, x):
        if _cache_leaf_name(path) != "cache_index":
            return x
        ni = jnp.broadcast_to(new_index, x.shape).astype(x.dtype)
        return jnp.where(active, ni, x) if active is not None else ni

    return jax.tree_util.tree_map_with_path(leaf, cache)


def draft_truncate(cfg: GPTConfig, params, n_layers: int
                   ) -> tuple[GPTConfig, dict]:
    """An EARLY-EXIT draft from a trained checkpoint: the first
    ``n_layers`` blocks of ``params`` (plus embed / final LN / head)
    reused as the speculative draft model — a draft without a second
    checkpoint. Proposal quality is what the truncated stack gives (the
    usual early-exit trade); correctness never depends on it — the
    verifier samples every delivered token. The returned tree SHARES the
    kept leaves with ``params`` (no copy)."""
    if not 1 <= n_layers < cfg.layers:
        raise ValueError(
            f"draft n_layers={n_layers} must be in [1, {cfg.layers}) — "
            "a draft at full depth proposes at full cost")
    if cfg.moe_every:
        raise ValueError("draft_truncate does not support MoE configs "
                         "(the decode stack has no MoE path)")
    dcfg = dataclasses.replace(cfg, layers=n_layers)
    keep = {"token_embed", "ln_f", "lm_head"} | {
        f"layer_{i}" for i in range(n_layers)}
    missing = keep - set(params)
    if missing:
        raise ValueError(f"params tree is missing {sorted(missing)} — "
                         "not a GPT checkpoint?")
    return dcfg, {k: params[k] for k in sorted(keep)}


def cache_load_pages(cache, pool, slot, page_ids, n_valid):
    """The paged READ view: gather pool pages ``page_ids[:n_valid]`` into
    the leading positions of slot ``slot`` of every batch-led cache leaf,
    in ONE fixed-shape op (the serving prefix cache admits a whole pinned
    chain per compiled call — per-page dispatches would cost as much host
    overhead as the transformer chunks they replace).

    ``cache`` leaves are ``[S, H, L, D]``, ``pool`` leaves ``[P, H, p, D]``
    at the same tree paths with ``L = len(page_ids) * p`` (pages tile the
    cache — the engine validates ``max_len % page_size == 0``); entries of
    ``page_ids`` at or past ``n_valid`` are ignored (positions keep their
    current contents). Copies are bitwise: int8 caches bring their scale
    leaves through the same paths."""
    def per_leaf(path, leaf):
        if not _paged_leaf_check(_cache_leaf_name(path)):
            return leaf
        pleaf = _get_by_path(pool, path)
        p = pleaf.shape[2]
        m = leaf.shape[2] // p
        # OOB-safe: ids past n_valid may be anything in [0, P) — their
        # gathered rows are masked back to the current contents below
        pages = pleaf[jnp.clip(page_ids, 0, pleaf.shape[0] - 1)]
        flat = pages.transpose(1, 0, 2, 3).reshape(
            leaf.shape[1], m * p, leaf.shape[3])
        cur = jax.lax.dynamic_slice(
            leaf, (slot, 0, 0, 0), (1,) + leaf.shape[1:])[0]
        mask = (jnp.arange(m * p) < n_valid * p)[None, :, None]
        row = jnp.where(mask, flat, cur)
        return jax.lax.dynamic_update_slice(leaf, row[None],
                                            (slot, 0, 0, 0))

    return jax.tree_util.tree_map_with_path(per_leaf, cache)


def cache_save_pages(cache, pool, slot, page_ids):
    """The paged WRITE view: scatter slot ``slot``'s cache row, split into
    pages, to pool entries ``page_ids`` in ONE fixed-shape op. Page ``j``
    lands at ``page_ids[j]``; point unwanted pages at an out-of-range id
    (``>= P``) — drop-mode scatter discards them, the fixed-shape spelling
    of "save only the new pages". Returns the updated pool."""
    def per_leaf(path, pleaf):
        if not _paged_leaf_check(_cache_leaf_name(path)):
            return pleaf
        leaf = _get_by_path(cache, path)
        p = pleaf.shape[2]
        m = leaf.shape[2] // p
        row = jax.lax.dynamic_slice(
            leaf, (slot, 0, 0, 0), (1,) + leaf.shape[1:])[0]
        pages = row.reshape(leaf.shape[1], m, p,
                            leaf.shape[3]).transpose(1, 0, 2, 3)
        return pleaf.at[page_ids].set(pages, mode="drop")

    return jax.tree_util.tree_map_with_path(per_leaf, pool)


def generate_beam(model: GPT, params, prompt: jax.Array, n_new: int, *,
                  num_beams: int = 4,
                  eos_id: Optional[int] = None, pad_id: int = 0,
                  length_penalty: float = 0.0,
                  prefill_chunk: int = 0) -> jax.Array:
    """Beam-search decode: the deterministic search the sampling family
    (:func:`generate`) doesn't cover. [B, T_p] -> [B, T_p + n_new].

    Standard fixed-width beam search in one ``lax.scan`` (static shapes):
    the cache runs at batch B*k; every step expands k beams x V tokens,
    keeps the global top-k per batch row, and REORDERS the KV cache along
    the batch axis to follow the surviving beams (the per-step gather is
    beam search's inherent cost). Finished beams (``eos_id``) are frozen:
    their only continuation is ``pad_id`` at zero added log-prob, so
    their score stays comparable while the scan stays fixed-length.
    ``length_penalty`` alpha rescores finals by ``score / len**alpha``
    (0 = pure sum-logprob; GNMT-style normalization at 1.0). The emitted
    (parent, token) lattice is backtraced after the scan — O(n) memory,
    no in-scan sequence buffers.

    Composes with ``prefill_chunk`` (shared :func:`_prefill`) and any
    ``model.cfg`` cache variant (GQA / rolling window / int8 — batch-led
    leaves are selected by key path, see ``_BATCH_LED_CACHE_KEYS``). Sharded
    (mesh) decode is not wired for beams; shard the batch outside.
    """
    cfg = model.cfg
    b, t_p = prompt.shape
    k = num_beams
    if k < 1:
        raise ValueError(f"num_beams={k} must be >= 1")
    if n_new < 1:
        raise ValueError(f"n_new={n_new} must be >= 1")
    if cfg.decode_len < t_p + n_new:
        raise ValueError(
            f"decode_len={cfg.decode_len} < prompt+new={t_p + n_new}")

    # Prefill ONCE at batch B (k identical beams would pay k-fold
    # redundant prompt compute and O(T_p^2) activation memory), then
    # clone the cache k-fold into the beam-expanded layout: rows
    # [b*k + i] are batch b's beams.
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((b, 1), jnp.int32)))
    cache0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                          shapes["cache"])
    logits, cache = _prefill(model, params, cache0, prompt, prefill_chunk)

    # Batch-led cache leaves are selected BY KEY PATH, not by leading-dim
    # size: a future leaf with a colliding shape[0] must not be silently
    # (mis)reordered, and a renamed batch-led leaf must fail loudly here
    # rather than ride the beams unreordered. The shape check is demoted to
    # an assertion on the selected leaves.
    def _map_batch_led(fn, cache, lead):
        def per_leaf(path, leaf):
            name = getattr(path[-1], "key", str(path[-1]))
            if name in _ROW_LED_CACHE_KEYS:
                assert getattr(leaf, "ndim", 0) >= 1 and \
                    leaf.shape[0] == lead, (
                        f"cache leaf {name!r} expected leading dim "
                        f"{lead}, got {getattr(leaf, 'shape', None)}")
                return fn(leaf)
            if name not in _NON_BATCH_CACHE_KEYS:
                # a hard error, not an assert: silently riding the beams
                # unreordered corrupts decode output (and -O strips asserts)
                raise ValueError(
                    f"unknown cache leaf {name!r}: add it to "
                    "_BATCH_LED_CACHE_KEYS or _NON_BATCH_CACHE_KEYS so "
                    "beam search knows whether to reorder it")
            return leaf

        return jax.tree_util.tree_map_with_path(per_leaf, cache)

    cache = _map_batch_led(lambda leaf: jnp.repeat(leaf, k, axis=0),
                           cache, b)
    logits = jnp.repeat(logits[:, -1:], k, axis=0)           # [B*k, 1, V]

    def reorder(cache, parent):
        rows = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
        return _map_batch_led(lambda leaf: leaf[rows], cache, b * k)

    def expand(scores, logprobs, done):
        """(scores [B,k], logprobs [B,k,V], done [B,k]) -> top-k beams:
        (new scores, parent [B,k], token [B,k], new done)."""
        if eos_id is not None:
            # frozen beams continue ONLY as pad at zero added log-prob
            frozen = jnp.full(logprobs.shape[-1:], -jnp.inf
                              ).at[pad_id].set(0.0)
            logprobs = jnp.where(done[:, :, None], frozen[None, None],
                                 logprobs)
        total = scores[:, :, None] + logprobs                # [B,k,V]
        v = total.shape[-1]
        flat = total.reshape(b, k * v)
        new_scores, idx = jax.lax.top_k(flat, k)             # [B,k]
        parent = idx // v
        token = (idx % v).astype(jnp.int32)
        new_done = jnp.take_along_axis(done, parent, 1)
        if eos_id is not None:
            new_done = new_done | (token == eos_id)
        return new_scores, parent, token, new_done

    logprobs0 = jax.nn.log_softmax(
        logits[:, -1].astype(jnp.float32).reshape(b, k, -1))
    # (the repeat above makes every beam's row identical; the score mask
    # below is what breaks the symmetry)
    # beams 1..k-1 start at -inf so the first top-k comes from beam 0
    # (all beams are identical clones until they diverge here)
    scores0 = jnp.where(jnp.arange(k)[None, :] == 0, 0.0, -jnp.inf)
    scores0 = jnp.broadcast_to(scores0, (b, k))
    done0 = jnp.zeros((b, k), bool)
    scores, parent0, tok0, done = expand(scores0, logprobs0, done0)
    cache = reorder(cache, parent0)
    lens0 = jnp.ones((b, k), jnp.float32)                    # tokens emitted

    def body(carry, _):
        cache, scores, tok, done, lens = carry
        logits, mut = model.apply(
            {"params": params, "cache": cache}, tok.reshape(b * k, 1),
            deterministic=True, mutable=["cache"])
        logprobs = jax.nn.log_softmax(
            logits[:, 0].astype(jnp.float32).reshape(b, k, -1))
        new_scores, parent, token, new_done = expand(scores, logprobs, done)
        lens = jnp.take_along_axis(lens, parent, 1) + jnp.where(
            jnp.take_along_axis(done, parent, 1), 0.0, 1.0)
        cache = reorder(mut["cache"], parent)
        return ((cache, new_scores, token, new_done, lens),
                (parent, token))

    (cache, scores, tok, done, lens), (parents, tokens) = jax.lax.scan(
        body, (cache, scores, tok0, done, lens0), None, length=n_new - 1)
    # prepend step 1 so the backtrace covers every emitted token
    parents = jnp.concatenate([parent0[None], parents], axis=0)  # [S,B,k]
    tokens = jnp.concatenate([tok0[None], tokens], axis=0)       # [S,B,k]

    final = scores
    if length_penalty:
        final = scores / jnp.maximum(lens, 1.0) ** length_penalty
    best = jnp.argmax(final, axis=1)                             # [B]

    def back(idx, pt):
        par, tk = pt                                             # [B,k]
        t = jnp.take_along_axis(tk, idx[:, None], 1)[:, 0]
        nidx = jnp.take_along_axis(par, idx[:, None], 1)[:, 0]
        return nidx, t

    _, toks = jax.lax.scan(back, best, (parents, tokens), reverse=True)
    return jnp.concatenate([prompt, toks.T.astype(jnp.int32)], axis=1)


def make_eval(model: GPT, *, loss_chunk: int = 0,
              loss_chunk_tokens: int = 0, loss_pallas: bool = False):
    """Held-out eval: mean next-token CE and perplexity (ignore -100).

    ``loss_chunk`` / ``loss_chunk_tokens`` / ``loss_pallas``: same
    fused-CE options as :func:`make_loss` — a training run that only
    fits with a fused loss would otherwise OOM at its first EVAL (full
    [B,T,V] logits)."""
    fused = _fused_ce(loss_chunk, loss_chunk_tokens, loss_pallas,
                      model.mesh)

    def eval_fn(params, extra, batch):
        cfg = model.cfg
        out = model.apply({"params": params}, batch["input_ids"],
                          deterministic=True,
                          mutable=["losses"] if cfg.moe_every else False,
                          return_hidden=fused is not None)
        y = out[0] if cfg.moe_every else out
        if fused is not None:
            loss, _ = fused(y, params["lm_head"]["kernel"], batch["labels"])
        else:
            loss, _ = softmax_cross_entropy(y, batch["labels"],
                                            ignore_index=-100)
        return {"eval_loss": loss, "eval_ppl": jnp.exp(loss)}

    return eval_fn


def _fused_ce(loss_chunk: int, loss_chunk_tokens: int,
              loss_pallas: bool = False, mesh=None):
    """Resolve the head-fused CE options to one callable (or None for
    the monolithic-logits path). Vocab chunking bounds memory at
    O(N·chunk) with an online-lse scan; token chunking bounds it at
    O(chunk·V) with a plain CE per token block — the faster shape on
    chip (losses.py: token_chunked_lm_cross_entropy docstring); the
    pallas kernel keeps logits in VMEM tiles entirely (ops/fused_ce.py
    — the flash-attention move applied to the LM head)."""
    if sum(map(bool, (loss_chunk, loss_chunk_tokens, loss_pallas))) > 1:
        raise ValueError("loss_chunk (vocab), loss_chunk_tokens and "
                         "loss_pallas are mutually exclusive")
    from dtf_tpu.ops.losses import (chunked_lm_cross_entropy,
                                    token_chunked_lm_cross_entropy)
    if loss_pallas:
        from dtf_tpu.ops.fused_ce import pallas_lm_cross_entropy_sharded

        def pallas_ce(y, w, lab):
            # the shard_map boundary lives in the op (like flash's
            # _sharded variants): a bare pallas_call under jit would
            # all-gather the DP/SP-sharded tokens and run redundantly
            mean, n = pallas_lm_cross_entropy_sharded(
                y, w, lab, mesh, ignore_index=-100,
                interpret=jax.default_backend() != "tpu")
            return mean, n

        return pallas_ce
    if loss_chunk_tokens:
        return lambda y, w, lab: token_chunked_lm_cross_entropy(
            y, w, lab, chunk=loss_chunk_tokens, ignore_index=-100)
    if loss_chunk:
        return lambda y, w, lab: chunked_lm_cross_entropy(
            y, w, lab, chunk=loss_chunk, ignore_index=-100)
    return None


def make_loss(model: GPT, *, loss_chunk: int = 0,
              loss_chunk_tokens: int = 0, loss_pallas: bool = False):
    """Next-token CE: batch = {"input_ids" [B,T], "labels" [B,T]} where
    labels are input_ids shifted left by the data layer (-100 = ignore).

    ``loss_chunk > 0``: compute CE fused with the lm_head in vocab chunks
    of that width (:func:`dtf_tpu.ops.losses.chunked_lm_cross_entropy`) —
    identical numbers, O(N·chunk) instead of O(N·V) live logits memory
    (the single-chip batch-size ceiling for a 50k vocab).
    ``loss_chunk_tokens > 0``: chunk TOKENS instead — O(chunk·V) live
    logits and one full-vocab MXU matmul per block, the faster chunking
    axis on chip (:func:`~dtf_tpu.ops.losses.token_chunked_lm_cross_entropy`).
    ``loss_pallas``: the Pallas fused head+CE kernel — logits live only
    in VMEM tiles (:mod:`dtf_tpu.ops.fused_ce`).
    All compose with DP/SP; under TP (lm_head sharded over 'model')
    prefer the standard path — chunk slices fight the vocab sharding.
    """
    fused = _fused_ce(loss_chunk, loss_chunk_tokens, loss_pallas,
                      model.mesh)

    def loss_fn(params, extra, batch, rng):
        cfg = model.cfg
        out = model.apply(
            {"params": params}, batch["input_ids"],
            deterministic=cfg.dropout == 0.0,
            rngs={"dropout": rng} if cfg.dropout else {},
            mutable=["losses"] if cfg.moe_every else False,
            return_hidden=fused is not None)
        y, mut = out if cfg.moe_every else (out, {})
        if fused is not None:
            loss, n = fused(y, params["lm_head"]["kernel"], batch["labels"])
        else:
            loss, n = softmax_cross_entropy(y, batch["labels"],
                                            ignore_index=-100)
        loss = loss + moe_lib.moe_aux_loss(mut, cfg.moe)
        return loss, LossAux(extra=extra, metrics={"lm_tokens": n}, weight=n)

    return loss_fn
