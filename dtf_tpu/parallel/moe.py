"""Mixture-of-Experts with expert parallelism over the ``expert`` mesh axis.

Not in the reference (SURVEY.md §2c marks EP out of its scope) — built
because a complete TPU framework needs the sparse-FFN scaling axis. The
design is the classic TPU MoE (Mesh-TF / GShard / Switch lineage), chosen
because it is *all dense einsums* — exactly what GSPMD partitions well:

- a router scores tokens per expert (f32 softmax);
- top-1 (Switch) dispatch with a fixed capacity C per expert: token→slot
  assignment becomes a one-hot dispatch tensor [G, E, C] (G = tokens);
- ``expert_in = einsum('gec,gd->ecd', dispatch, x)`` — with the E dim
  sharded ``P('expert')``, XLA lowers this to the token all-to-all over ICI;
- each expert runs its FFN on its [C, d] slab (weights stacked [E, ...] and
  expert-sharded — the MoE analogue of PS-sharded variables);
- ``out = einsum('ecd,gec->gd', expert_out, combine)`` routes results back
  (second all-to-all) scaled by the router gate.

Static shapes throughout (capacity drop/pad instead of ragged dispatch):
XLA-friendly, MXU-friendly, and the standard TPU trade — tokens past an
expert's capacity are dropped (their residual path carries them).

Grouped dispatch (GShard §3.2, VERDICT r2 weak #5): a flat dispatch tensor
over all G global tokens is [G, E, C] with C ∝ G/E — O(G²·cap/E) memory and
a G-long cumsum, ~5 GB at BERT-base shapes. Splitting tokens into ``n``
groups of ``s = G/n`` makes it [n, s, E, C_g] with C_g ∝ s/E — total
G·s·cap bytes, i.e. divided by n — and the cumsum (the token→slot race for
capacity) runs *within* each group, which is exactly GShard's semantics.
The group axis rides the ``data`` mesh axis; E rides ``expert``; the two
dispatch einsums still lower to the same pair of all-to-alls.

Beside it, :class:`DroplessMoE` (PR 26) is the serving-side expert layer of
today's sparse models: sigmoid scores with a per-expert choice bias, top-k
of many, SwiGLU experts, NO capacity and no dropped token. It computes only
the chosen (token, expert) pairs, grouped by expert
(:func:`group_layout` + a grouped matrix product), and is told which
contiguous range of experts it holds (``experts_held``), so a chip's share
of a layer is data. It has no loss, no bias update and no all-to-all yet
(ROADMAP.md): forward only.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.sharding import PartitionSpec as P

from dtf_tpu.ops import moe_gmm


@dataclasses.dataclass(frozen=True)
class MoeConfig:
    num_experts: int = 8
    capacity_factor: float = 1.25
    #: load-balancing auxiliary loss weight (Switch eq. 4).
    aux_loss_weight: float = 1e-2
    #: dispatch groups (GShard G-dim). None → one group per batch row, the
    #: shape that keeps dispatch memory linear in tokens; 1 → flat dispatch
    #: over all tokens (only sane for toy shapes — memory is quadratic).
    num_groups: int | None = None
    #: experts per token: 1 = Switch, 2 = GShard top-2 (normalized gates;
    #: second choices queue behind all first choices for capacity).
    top_k: int = 1

    def __post_init__(self):
        if self.top_k not in (1, 2):
            raise ValueError(f"top_k={self.top_k} must be 1 or 2")


def expert_capacity(tokens_per_group: int, num_experts: int,
                    cfg: MoeConfig) -> int:
    """Slots per expert per group: ``cf · top_k · s / e`` (GShard sets
    C ∝ k — top-2 routes ~2s/e entries per expert, and since second choices
    queue behind firsts, an unscaled capacity would drop essentially every
    second choice, silently degrading to a down-gated top-1)."""
    return max(1, int(cfg.capacity_factor * cfg.top_k
                      * tokens_per_group / num_experts))


def top1_dispatch(router_logits: jax.Array, num_experts: int,
                  capacity: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Switch-style top-1 routing → (dispatch [G,E,C], combine [G,E,C], aux).

    ``router_logits`` [G, E] (f32). Tokens beyond an expert's capacity are
    dropped (dispatch row all-zero). ``aux`` is the load-balance loss term:
    E * Σ_e (fraction of tokens to e) * (mean router prob of e).
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    gate = probs.max(axis=-1)                                   # [G]
    choice = probs.argmax(axis=-1)                              # [G]
    onehot = jax.nn.one_hot(choice, num_experts,
                            dtype=jnp.float32)                  # [G,E]
    # position of each token within its chosen expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # [G,E]
    in_cap = (pos < capacity) & (onehot > 0)
    pos = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    cap_onehot = jax.nn.one_hot(pos, capacity,
                                dtype=jnp.float32)              # [G,E,C]
    dispatch = cap_onehot * in_cap[..., None]
    combine = dispatch * gate[:, None, None]
    # load-balance aux (Switch Transformer eq. 4)
    frac_tokens = onehot.mean(axis=0)                           # [E]
    frac_probs = probs.mean(axis=0)                             # [E]
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


def top2_dispatch(router_logits: jax.Array, num_experts: int,
                  capacity: int) -> tuple[jax.Array, jax.Array, jax.Array]:
    """GShard top-2 routing → (dispatch [G,E,C], combine [G,E,C], aux).

    Each token goes to its two highest-probability experts with gates
    renormalized over the pair. Capacity policy (GShard §3.3): within an
    expert's queue, ALL first choices precede second choices, so overflow
    drops second choices first. ``aux`` is the same first-choice
    load-balance term as top-1.
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    g1 = probs.max(axis=-1)                                     # [G]
    oh1 = jax.nn.one_hot(probs.argmax(axis=-1), num_experts,
                         dtype=jnp.float32)                     # [G,E]
    probs2 = probs * (1.0 - oh1)
    g2 = probs2.max(axis=-1)
    oh2 = jax.nn.one_hot(probs2.argmax(axis=-1), num_experts,
                         dtype=jnp.float32)
    denom = g1 + g2 + 1e-9
    g1n, g2n = g1 / denom, g2 / denom

    pos1 = jnp.cumsum(oh1, axis=0) * oh1 - 1.0                  # [G,E]
    # second choices queue AFTER every first choice bound for that expert
    pos2 = (jnp.cumsum(oh2, axis=0)
            + oh1.sum(axis=0, keepdims=True)) * oh2 - 1.0
    d_parts = []
    for pos, oh in ((pos1, oh1), (pos2, oh2)):
        in_cap = (pos < capacity) & (oh > 0)
        slot = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
        d_parts.append(jax.nn.one_hot(slot, capacity, dtype=jnp.float32)
                       * in_cap[..., None])
    dispatch = d_parts[0] + d_parts[1]                          # disjoint
    combine = (d_parts[0] * g1n[:, None, None]
               + d_parts[1] * g2n[:, None, None])
    frac_tokens = oh1.mean(axis=0)
    frac_probs = probs.mean(axis=0)
    aux = num_experts * jnp.sum(frac_tokens * frac_probs)
    return dispatch, combine, aux


class SwitchFFN(nn.Module):
    """Expert-parallel FFN block (drop-in for a dense MLP in a transformer).

    Input [B, T, d] → output [B, T, d]. Expert weights are stacked [E, ...]
    and intended for ``P('expert', ...)`` sharding (see :func:`ep_rules`);
    the dispatch/combine einsums then carry the all-to-alls. The router's
    aux loss is stored in the ``losses`` collection (sow) — pull it with
    ``mutable=['losses']`` and add ``aux_loss_weight`` x its mean to the loss.
    """

    d_model: int
    d_ff: int
    cfg: MoeConfig = MoeConfig()
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        b, t, d = x.shape
        g = b * t
        e = self.cfg.num_experts
        n = b if self.cfg.num_groups is None else self.cfg.num_groups
        if g % n:
            raise ValueError(f"num_groups={n} must divide tokens {g} (={b}x{t})")
        s = g // n  # tokens per group; the capacity race runs within a group
        capacity = expert_capacity(s, e, self.cfg)
        tokens = x.reshape(n, s, d)

        router = nn.Dense(e, dtype=jnp.float32, param_dtype=jnp.float32,
                          name="router")
        route = top1_dispatch if self.cfg.top_k == 1 else top2_dispatch
        dispatch, combine, aux = jax.vmap(
            route, in_axes=(0, None, None))(
                router(tokens), e, capacity)  # [n,s,e,c] x2, aux [n]
        self.sow("losses", "moe_aux", jnp.mean(aux))

        w_in = self.param("w_in", nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal"), (e, d, self.d_ff), jnp.float32)
        w_out = self.param("w_out", nn.initializers.variance_scaling(
            1.0, "fan_in", "truncated_normal"), (e, self.d_ff, d), jnp.float32)

        # all-to-all #1: tokens → their expert's per-group slab. With n on
        # 'data' and e on 'expert' this is the GShard token shuffle over ICI.
        slabs = jnp.einsum("nsec,nsd->necd", dispatch.astype(self.dtype),
                           tokens.astype(self.dtype))
        h = jnp.einsum("necd,edf->necf", slabs, w_in.astype(self.dtype))
        h = nn.gelu(h, approximate=True)
        h = jnp.einsum("necf,efd->necd", h, w_out.astype(self.dtype))
        # all-to-all #2: expert outputs → token order, gated
        out = jnp.einsum("necd,nsec->nsd", h.astype(jnp.float32),
                         combine).astype(x.dtype)
        return out.reshape(b, t, d)


def ep_rules(axis: str = "expert"):
    """Param-placement rules: expert-stacked weights sharded over ``axis``."""
    return [(r"w_(in|out)$", P(axis, None, None))]


def moe_aux_loss(mutables: dict, cfg: MoeConfig) -> jax.Array:
    """Mean of all sown aux terms × weight (0 if the model has no MoE)."""
    losses = mutables.get("losses", {})
    leaves = jax.tree.leaves(losses)
    if not leaves:
        return jnp.zeros((), jnp.float32)
    return cfg.aux_loss_weight * sum(jnp.mean(l) for l in leaves) / len(leaves)


# ---------------------------------------------------------------------------
# Dropless top-k experts (sigmoid router, SwiGLU experts) — forward only
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ExpertsConfig:
    """A dropless routed-expert layer as today's sparse decoders publish it
    (``num_experts`` / ``num_experts_per_tok`` / ``moe_intermediate_size`` /
    ``norm_topk_prob`` / ``use_expert_bias`` / ``routed_scaling_factor`` /
    ``n_group`` / ``topk_group``)."""

    num_experts: int = 64
    top_k: int = 4
    d_ff: int = 1536
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    #: the contiguous range ``(lo, hi)`` of experts THIS layer holds (None =
    #: all). The router keeps its full width and its top-k; the layer
    #: computes its own experts' part of the result and leaves the rest
    #: out — a chip's share of an expert-parallel deployment, without the
    #: exchange. The shares of disjoint ranges add up to the whole layer.
    experts_held: Optional[tuple[int, int]] = None
    #: group-limited choice: the experts lie in ``n_group`` contiguous
    #: groups, a group's score is the sum of its two largest choice scores,
    #: and only the ``topk_group`` best groups' experts can be chosen. One
    #: group = a plain top-k over all experts.
    n_group: int = 1
    topk_group: int = 1

    def __post_init__(self):
        if not 1 <= self.top_k <= self.num_experts:
            raise ValueError(
                f"top_k={self.top_k} must be in [1, num_experts="
                f"{self.num_experts}]")
        if (self.n_group < 1 or self.num_experts % self.n_group
                or not 1 <= self.topk_group <= self.n_group
                or self.top_k > self.topk_group
                * (self.num_experts // self.n_group)
                or (self.n_group > 1
                    and self.num_experts // self.n_group < 2)):
            raise ValueError(
                f"n_group={self.n_group} must divide num_experts="
                f"{self.num_experts} into groups of at least two, and "
                f"topk_group={self.topk_group} of them must hold top_k="
                f"{self.top_k} experts")
        lo, hi = self.held
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"experts_held={self.experts_held} must be a non-empty range "
                f"inside [0, {self.num_experts})")

    @property
    def held(self) -> tuple[int, int]:
        return (0, self.num_experts) if self.experts_held is None \
            else tuple(self.experts_held)


def router_scores(tokens: jax.Array, w_g: jax.Array) -> jax.Array:
    """``sigmoid(W_g x)`` [G, E] in float32 at the highest precision: a
    top-k choice flips on rounding."""
    return jax.nn.sigmoid(jnp.dot(
        tokens.astype(jnp.float32), w_g,
        precision=jax.lax.Precision.HIGHEST))


def route_topk(scores: jax.Array, bias: Optional[jax.Array], cfg: ExpertsConfig
               ) -> tuple[jax.Array, jax.Array]:
    """``scores`` [G, E] float32 (after the sigmoid) -> (experts [G, k]
    int32, weights [G, k] float32). The bias moves the CHOICE only; the
    weights are the chosen experts' own scores, normalised over the chosen
    (``s_i / (sum + 1e-6)``) and scaled. With ``n_group`` > 1 the choice is
    limited to the ``topk_group`` groups whose two largest choice scores
    sum highest."""
    choice = scores if bias is None else scores + bias[None, :]
    if cfg.n_group > 1:
        grouped = choice.reshape(choice.shape[0], cfg.n_group, -1)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)   # [G, n]
        _, kept = jax.lax.top_k(group_score, cfg.topk_group)
        kept = jnp.any(kept[:, :, None] == jnp.arange(cfg.n_group), axis=1)
        choice = jnp.where(kept[:, :, None], grouped,
                           -jnp.inf).reshape(choice.shape)
    _, experts = jax.lax.top_k(choice, cfg.top_k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-6)
    return experts.astype(jnp.int32), weights * cfg.routed_scaling_factor


def group_layout(group: jax.Array, counts: jax.Array, tm: int) -> dict:
    """Where each (token, expert) pair's row lies when rows are laid out
    group by group, every group padded to whole ``tm``-row tiles.

    ``group`` [P] int32: the pair's group in ``[0, n_groups)``, or
    ``n_groups`` for a pair that is left out (an expert held elsewhere, a
    masked token); ``counts`` [n_groups] int32: the pairs of each group.
    Static row count ``M = P + n_groups * (tm - 1)`` rounded
    up to tiles — the worst case; tiles past ``n_used`` hold nothing.
    Gathers and two small sorts only, no scatter. Returns ``src`` [M] (the pair whose row this is), ``valid`` [M],
    ``row_of_pair`` [P] (0 for a pair left out), ``kept`` [P],
    ``tile_group`` [M // tm] and ``n_used`` [1]."""
    p, n_groups = group.shape[0], counts.shape[0]
    padded = (counts + tm - 1) // tm * tm
    pad_end = jnp.cumsum(padded)
    pad_start = pad_end - padded
    sorted_start = jnp.cumsum(counts) - counts
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    rank_sorted = jnp.argsort(order).astype(jnp.int32)   # pair -> sorted pos

    m = -(-(p + n_groups * (tm - 1)) // tm) * tm
    rows = jnp.arange(m, dtype=jnp.int32)
    g_row = jnp.searchsorted(pad_end, rows, side="right").astype(jnp.int32)
    g_in = jnp.minimum(g_row, n_groups - 1)
    rank = rows - pad_start[g_in]
    valid = (g_row < n_groups) & (rank < counts[g_in])
    src = order[jnp.clip(sorted_start[g_in] + rank, 0, p - 1)]

    kept = group < n_groups
    g_pair = jnp.minimum(group, n_groups - 1)
    row_of_pair = jnp.where(
        kept, pad_start[g_pair] + rank_sorted - sorted_start[g_pair], 0)

    n_tiles = m // tm
    n_used = pad_end[-1] // tm
    tile_group = g_row[::tm]
    # empty tiles keep the last used group's weights: no traffic for them
    last = tile_group[jnp.maximum(n_used - 1, 0)]
    tile_group = jnp.minimum(
        jnp.where(jnp.arange(n_tiles) < n_used, tile_group, last),
        n_groups - 1)
    return {"src": src, "valid": valid,
            "row_of_pair": row_of_pair, "kept": kept,
            "tile_group": tile_group.astype(jnp.int32),
            "n_used": n_used.astype(jnp.int32)[None]}


def tile_rows(pairs: int, n_groups: int) -> int:
    """Rows of a tile for the Pallas product: about twice the mean group
    (``pairs`` are the pairs EXPECTED on these ``n_groups`` experts: a
    layer that holds a share of its experts expects that share of its
    pairs), so that most groups fill one tile, between 16 (bfloat16's
    sublane packing) and 128 (the MXU's edge)."""
    tm = moe_gmm.MIN_TILE_ROWS
    while tm < 128 and tm < 2 * pairs // max(n_groups, 1):
        tm *= 2
    return tm


class DroplessMoE(nn.Module):
    """Routed experts without capacity. Input [B, T, d] -> output [B, T, d].

    ``s = sigmoid(W_g x)`` in float32 (a top-k choice flips on rounding);
    the ``k`` largest of ``s + b`` are chosen; the output is
    ``sum_chosen weight_i * W2_i (silu(W1_i x) * W3_i x)`` over the chosen
    experts this layer holds. Only the chosen pairs are computed: rows are
    grouped by expert (:func:`group_layout`) and run through three grouped
    products. ``token_mask`` [B, T] bool leaves whole tokens out (a serving
    slot that is not decoding, the pad columns of a ragged prefill chunk):
    their rows are zero and they touch no expert's weights.

    Sows, into the ``moe_stats`` collection when it is mutable, what the
    routing did to the unmasked tokens over ALL experts: ``touched`` (experts
    with at least one token) and ``max_load`` (tokens on the fullest one);
    and over the experts HELD here: ``held_pairs`` (the pairs this layer
    computes) and ``held_touched`` (held experts with at least one token).

    The grouped product is the ``dtf_moe_gmm`` Pallas kernel on a TPU and
    ``jax.lax.ragged_dot`` on any other backend (the rule flash attention
    follows).
    """

    d_model: int
    cfg: ExpertsConfig = ExpertsConfig()
    dtype: jnp.dtype = jnp.bfloat16
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, token_mask=None):
        cfg = self.cfg
        b, t, d = x.shape
        g, e, k = b * t, cfg.num_experts, cfg.top_k
        lo, hi = cfg.held
        n_held = hi - lo
        tokens = x.reshape(g, d)

        w_g = self.param("router", nn.initializers.lecun_normal(), (d, e),
                         jnp.float32)
        bias = (self.param("expert_bias", nn.initializers.zeros, (e,),
                           jnp.float32) if cfg.use_expert_bias else None)
        init = nn.initializers.variance_scaling(
            1.0, "fan_in", "normal", in_axis=-2, out_axis=-1, batch_axis=0)
        w1 = self.param("w1", init, (n_held, d, cfg.d_ff), self.param_dtype)
        w3 = self.param("w3", init, (n_held, d, cfg.d_ff), self.param_dtype)
        w2 = self.param("w2", init, (n_held, cfg.d_ff, d), self.param_dtype)

        experts, weights = route_topk(router_scores(tokens, w_g), bias,
                                      cfg)                     # [G, k]

        live = jnp.ones((g,), bool) if token_mask is None \
            else token_mask.reshape(g)
        pair_expert = jnp.where(live[:, None], experts, e).reshape(g * k)
        load = jnp.sum(pair_expert[:, None] == jnp.arange(e)[None, :],
                       axis=0, dtype=jnp.int32)                # [E]
        self.sow("moe_stats", "touched", jnp.sum(load > 0, dtype=jnp.int32))
        self.sow("moe_stats", "max_load", jnp.max(load))

        held = (pair_expert >= lo) & (pair_expert < hi)
        group = jnp.where(held, pair_expert - lo, n_held).astype(jnp.int32)
        on_tpu = jax.default_backend() == "tpu"
        tm = tile_rows(g * k * n_held // e, n_held) if on_tpu else 1
        counts = load[lo:hi]
        self.sow("moe_stats", "held_pairs", jnp.sum(counts))
        self.sow("moe_stats", "held_touched",
                 jnp.sum(counts > 0, dtype=jnp.int32))
        lay = group_layout(group, counts, tm)
        if on_tpu:
            def product(a, w):
                return moe_gmm.grouped_matmul(
                    a, w, lay["tile_group"], lay["n_used"], tm=tm)
        else:
            def product(a, w):
                return jax.lax.ragged_dot(
                    a, w, counts,
                    preferred_element_type=jnp.float32).astype(a.dtype)

        rows = jnp.where(lay["valid"][:, None],
                         tokens[lay["src"] // k].astype(self.dtype), 0)
        w1, w3, w2 = (w.astype(self.dtype) for w in (w1, w3, w2))
        gate = product(rows, w1).astype(jnp.float32)
        up = product(rows, w3).astype(jnp.float32)
        out_rows = product((jax.nn.silu(gate) * up).astype(self.dtype), w2)

        # a pair left out reads row 0, which may hold anything: select, do
        # not multiply by zero
        out_pairs = jnp.where(
            lay["kept"][:, None],
            out_rows[lay["row_of_pair"]].astype(jnp.float32), 0.0)
        out = (out_pairs * weights.reshape(g * k, 1)).reshape(
            g, k, d).sum(axis=1)
        return out.astype(x.dtype).reshape(b, t, d)
